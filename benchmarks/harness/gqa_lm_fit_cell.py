"""The driver of token cells whose model is an ``afmoe``-family decoder
(grouped-query attention, sliding-window and global layers):
``lm_fit_cell.py``'s phases as they are (``prepare``, ``first_steps``,
``reference_readings``, ``compare_sides``, ``memory_peak_bytes``,
``fit_cell.window``), with this family's scopes and its work module
(``work_gqa.py``) in the place of ``attn.mla`` and ``work_lm.py``, which that
driver names in its body. Beside them:

* the faults of this mechanism, planted in the reference through its
  configuration (``reference_readings(prep, fault=...)``), for the tools that
  take the readings a cell's limits are set from;
* ``grad_gap_big_2nd`` / ``dparam_gap_big_2nd``: the second-worst leaf of
  4096 elements or more, where ``check.compare`` gives the worst: the worst
  leaf is a stack of expert matrices that few rows reached, whose tail fails
  a correct program now and then (PERF.md section 7);
* the windowed call sites' tile counts (``zoo_attention_window_tiles_total``)
  in the facts.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time
from typing import Callable, Dict, Optional

from . import check, fit_cell, lm_fit_cell, scopes, tokens, \
    trace as trace_mod, work_gqa
from .lm_fit_cell import (Prepared, first_steps, memory_peak_bytes,  # noqa: F401
                          prepare)
from .spec import REPO_ROOT, Cell

SCOPES = ("attn.gqa", "attn.window", "attn.global", "moe.router",
          "moe.experts", "moe.shared", "lm_head", "optimizer", "prologue")
FAULTS = ("sliding_as_causal", "rope_on_global", "half_batch")


def reference_readings(prep_like, quant=None, fault: Optional[str] = None
                       ) -> Dict:
    """``lm_fit_cell.reference_readings``; ``quant`` makes it the control,
    ``fault`` one of ``FAULTS``: the sliding layers run plain causal, RoPE on
    the global layers too, or half of the batch left out, which at one
    sequence a step is the labels of the sequence's second half."""
    if fault is None:
        return lm_fit_cell.reference_readings(prep_like, quant=quant)
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    batch = prep_like.global_batch
    if fault == "half_batch" and batch > 1:
        return lm_fit_cell.reference_readings(prep_like, quant=quant,
                                              rows=batch // 2)
    planted = {"reference_label_positions":
               int(prep_like.model_cfg["sequence_length"]) // 2} \
        if fault == "half_batch" else {"reference_fault": fault}
    return lm_fit_cell.reference_readings(dataclasses.replace(
        prep_like, model_cfg=dict(prep_like.model_cfg, **planted)),
        quant=quant)


def _second_worst(gaps: Dict[str, float], sizes: Dict[str, int]) -> float:
    big = sorted(v for k, v in gaps.items() if sizes[k] >= check.BIG_LEAF)
    return big[-2] if len(big) > 1 else big[-1]


def compare_sides(side: Dict, ref: Dict, shapes: Dict[str, tuple],
                  reference) -> Dict:
    """``lm_fit_cell.compare_sides``'s numbers, and the two second-worst
    big leaves."""
    import numpy as np
    numbers = lm_fit_cell.compare_sides(side, ref, shapes, reference)
    sizes = {k: int(np.prod(v)) for k, v in shapes.items()}
    numbers["grad_gap_big_2nd"] = _second_worst(
        check.leaf_gaps(side["grad1_norm"], ref["grad1_norm"]), sizes)
    numbers["dparam_gap_big_2nd"] = _second_worst(check.leaf_gaps(
        side["dparam_norm"], ref["dparam_norm"],
        leave_out=check.negligible_leaves(ref["grad1_norm"])), sizes)
    return numbers


def window_tiles() -> Dict[str, float]:
    """What the windowed flash call sites traced so far counted."""
    from analytics_zoo_tpu.obs.registry import REGISTRY
    family = REGISTRY.counter("zoo_attention_window_tiles_total", "",
                              labelnames=("kind",))
    return {kind: family.labels(kind=kind).value
            for kind in ("visited", "needed")}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        cluster_mode: str = "tpu", scratch: Optional[str] = None,
        tamper: Optional[Callable[[Prepared], None]] = None) -> Dict:
    """One run of the cell; ``tamper`` as in ``fit_cell.run``."""
    from analytics_zoo_tpu.compile import compile_stats
    from analytics_zoo_tpu.obs.registry import REGISTRY
    from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import \
        moe_counters
    scratch = scratch or os.path.join(
        os.environ.get("TMPDIR") or os.path.join(REPO_ROOT, ".bench_tmp"),
        f"bench_{cell.name}")
    trace_dir = os.path.join(scratch, "trace") if trace else None
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch, exist_ok=True)
    try:
        mesh, devices = fit_cell.open_context(cell, cluster_mode)
        prep = prepare(cell, seed, mesh, devices)
        if tamper is not None:
            tamper(prep)
        first_steps(prep)
        cstats = compile_stats()
        setup_s = time.perf_counter() - t_start
        fell_through = REGISTRY.counter(
            "zoo_attention_reference_on_tpu_total", "")
        on_reference = fell_through.value
        moe0 = moe_counters(prep.est.engine.extra_vars)
        spans = fit_cell.window(prep, seconds, trace_dir)
        moe1 = moe_counters(prep.est.engine.extra_vars)
        on_reference = fell_through.value - on_reference
        peak = memory_peak_bytes(devices)
        rows = tokens.count_bad_rows(prep.data, prep.fed)
        program = prep.program
        reference = cell.load("reference")
        fit_cell.free_program(prep)
        t_ref = time.perf_counter()
        ref = reference_readings(prep)
        numbers = compare_sides(program, ref, prep.shapes, reference)
        wfacts = spans["window"]
        numbers["infeed_bad_rows"] = rows["bad"]
        numbers["window_losses_not_finite"] = \
            0 if wfacts["losses_finite"] else 1
        numbers["compiles_in_window"] = wfacts["compiles_in_window"]
        numbers["moe_dropped_rows"] = moe1["moe_dropped_rows"]
        numbers["attention_reference_on_tpu"] = int(on_reference)
        reference_s = time.perf_counter() - t_ref
        reduction = by_scope = None
        if trace_dir is not None:
            found = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler wrote no trace to "
                                   f"{trace_dir}")
            reduction = trace_mod.reduce_xplane(found[0], cell.chips)
            by_scope = scopes.scope_seconds(found[0], SCOPES)
        seq = int(cell.traffic["sequence_length"])
        steps = max(moe1["moe_steps"] - moe0["moe_steps"], 1)
        rows_per_step = (moe1["moe_rows_total"] - moe0["moe_rows_total"]) \
            / steps
        flops = work_gqa.train_flops_per_sample(
            prep.model_cfg, seq, rows_per_step / prep.global_batch)
        return {
            "end_to_end": {
                "setup_s": setup_s,
                "train_samples_per_s_per_chip":
                    wfacts["samples"] / wfacts["seconds"] / cell.chips},
            "numbers": numbers,
            "attempted": wfacts["samples"] // prep.global_batch,
            "failed": 0 if wfacts["losses_finite"] else 1,
            "memory_peak_bytes": peak,
            "devices": devices,
            "trace": reduction,
            "facts": {"window": wfacts, "traced": spans["traced"],
                      "chips": cell.chips, "global_batch": prep.global_batch,
                      "steps_per_epoch": prep.steps_per_epoch,
                      "sequence_length": seq,
                      "tokens_per_s_per_chip": wfacts["samples"] * seq
                      / wfacts["seconds"] / cell.chips,
                      "train_flops_per_sample": flops,
                      "model_config": prep.model_cfg,
                      "dtype_bytes": 2,
                      "scope_seconds": by_scope,
                      "window_tiles": window_tiles(),
                      "moe": dict(moe1, moe_local_rows=rows_per_step),
                      "compile": {"setup_compile_s": cstats["compile_s"],
                                  "setup_compiles": cstats["compiles"],
                                  "setup_disk_hits": cstats["disk_hits"],
                                  "fallbacks": cstats["fallbacks"]},
                      "reference_s": reference_s,
                      "program_readings": {"losses": program["losses"]},
                      "reference_losses": ref["losses"]},
        }
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
