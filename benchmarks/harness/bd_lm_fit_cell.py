"""The driver of token cells whose model is trained as a block-diffusion LM
(the ``sdar_moe`` family: a step draws noise and runs ``[x_t | x_0]`` under
the block-diffusion mask): ``lm_fit_cell.py``'s phases as they are
(``first_steps``, ``reference_readings``, ``memory_peak_bytes``,
``fit_cell.window``), ``gqa_lm_fit_cell.py``'s comparison (the worst and the
second-worst big leaf), with this family's scopes and its work module
(``work_bd.py``). Beside them:

* ``prepare`` gives the model's view of the configuration the noise's seed
  (the factory's ``noise_seed`` of the run's seed), so that the reference
  draws the program's noise for the same steps;
* the faults of this mechanism, planted in the reference through its
  configuration (``reference_readings(prep, fault=...)``), for the tools that
  take the readings a cell's limits are set from;
* ``attention_reference_on_tpu``: attention call sites off the flash kernel
  on the chip since set-up began, held at 0;
* the block-diffusion call sites' score entries
  (``zoo_attention_bd_entries_total``) in the facts.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time
from typing import Callable, Dict, Optional

from . import fit_cell, lm_fit_cell, scopes, tokens, trace as trace_mod, \
    work_bd
from .gqa_lm_fit_cell import compare_sides                      # noqa: F401
from .lm_fit_cell import Prepared, first_steps, memory_peak_bytes  # noqa: F401
from .spec import REPO_ROOT, Cell

# ``moe.shared`` reads 0 (no shared expert): ``moe_share_pct.train`` adds it
SCOPES = ("attn.gqa", "attn.bd", "bd.noise", "bd.loss", "moe.router",
          "moe.experts", "moe.shared", "lm_head", "optimizer", "prologue")
FAULTS = ("bd_mask_as_causal", "noisy_block_causal",
          "clean_positions_continue", "half_labels")


def prepare(cell: Cell, seed: int, mesh, devices) -> Prepared:
    prep = lm_fit_cell.prepare(cell, seed, mesh, devices)
    prep.model_cfg = dict(prep.model_cfg,
                          noise_seed=cell.load("factory").noise_seed(seed))
    return prep


def reference_readings(prep_like, quant=None, fault: Optional[str] = None
                       ) -> Dict:
    """``lm_fit_cell.reference_readings``; ``quant`` makes it the control,
    ``fault`` one of ``FAULTS``: the 2L positions under a plain causal mask,
    causal inside each noisy block, the clean copy at positions L..2L-1, or
    half of the labels (of a batch of one sequence, the loss of its first
    half's positions; of a larger one, its first half's sequences)."""
    if fault is None:
        return lm_fit_cell.reference_readings(prep_like, quant=quant)
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is none of {FAULTS}")
    batch = prep_like.global_batch
    if fault == "half_labels" and batch > 1:
        return lm_fit_cell.reference_readings(prep_like, quant=quant,
                                              rows=batch // 2)
    planted = {"reference_label_positions":
               int(prep_like.model_cfg["sequence_length"]) // 2} \
        if fault == "half_labels" else {"reference_fault": fault}
    return lm_fit_cell.reference_readings(dataclasses.replace(
        prep_like, model_cfg=dict(prep_like.model_cfg, **planted)),
        quant=quant)


def bd_entries() -> Dict[str, float]:
    """What the block-diffusion flash call sites traced so far counted."""
    from analytics_zoo_tpu.obs.registry import REGISTRY
    family = REGISTRY.counter("zoo_attention_bd_entries_total", "",
                              labelnames=("kind",))
    return {kind: family.labels(kind=kind).value
            for kind in ("computed", "needed")}


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
        moe0 = moe_counters(prep.est.engine.extra_vars)
        spans = fit_cell.window(prep, seconds, trace_dir)
        moe1 = moe_counters(prep.est.engine.extra_vars)
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
        # traced since set-up began: a step that fell off its kernel was
        # traced in set-up and runs in every step of the window
        numbers["attention_reference_on_tpu"] = int(REGISTRY.counter(
            "zoo_attention_reference_on_tpu_total", "").value)
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
        flops = work_bd.train_flops_per_sample(
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
                      "bd_entries": bd_entries(),
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
