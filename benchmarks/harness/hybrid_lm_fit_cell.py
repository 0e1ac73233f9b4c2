"""The driver of token cells whose model is a ``nemotron_h``-family decoder
(Mamba-2 mixers, plain grouped-query attention and latent-space expert layers,
one mixer a block): ``lm_fit_cell.py``'s phases as they are (``prepare``,
``first_steps``, ``reference_readings``, ``memory_peak_bytes``,
``fit_cell.window``) and ``gqa_lm_fit_cell.py``'s comparison (the worst and
the second-worst big leaf), with this family's scopes and its work module
(``work_hybrid.py``) in the place of the ones those drivers name in their
bodies. Beside them:

* the faults of this mechanism, planted in the reference through its
  configuration (``reference_readings(prep, fault=...)``), for the tools that
  take the readings a cell's limits are set from;
* ``ssm_sequential_scan_on_tpu``: scans the program traced position by
  position on the chip (``zoo_ssm_sequential_scan_on_tpu_total``), held at 0;
* ``scan_matmul_s``: the device time of the convolution-or-dot operations
  under ``ssm.scan``, which the dense products' reader takes out of the
  trace's ``matmul_s``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import shutil
import time
from typing import Callable, Dict, Optional

from . import fit_cell, lm_fit_cell, scopes, tokens, trace as trace_mod, \
    work_hybrid, xplane
from .gqa_lm_fit_cell import compare_sides                      # noqa: F401
from .lm_fit_cell import (Prepared, first_steps, memory_peak_bytes,  # noqa: F401
                          prepare)
from .spec import REPO_ROOT, Cell

SCOPES = ("ssm.mixer", "ssm.conv", "ssm.scan", "attn.gqa", "attn.global",
          "moe.router", "moe.latent", "moe.experts", "moe.shared", "mtp",
          "lm_head", "optimizer", "prologue")
FAULTS = ("state_reset_at_chunks", "relu_not_squared", "half_labels")
HELD_AT_ZERO = {"attention_reference_on_tpu":
                "zoo_attention_reference_on_tpu_total",
                "ssm_sequential_scan_on_tpu":
                "zoo_ssm_sequential_scan_on_tpu_total"}


def reference_readings(prep_like, quant=None, fault: Optional[str] = None
                       ) -> Dict:
    """``lm_fit_cell.reference_readings``; ``quant`` makes it the control,
    ``fault`` one of ``FAULTS``: the scan's state set to zero at every chunk
    boundary, the experts' ReLU not squared, or half of the labels (of a
    batch of one sequence, the labels of its second half; of a larger one,
    its second half's sequences)."""
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


def scan_matmul_seconds(path: str) -> Optional[float]:
    """Device seconds, over the traced steps on device 0, of the operations
    under ``ssm.scan`` that the trace classes as convolution or dot (fusions
    around them included): ``trace.reduce_devices``' ``matmul_s`` rule on the
    operations whose scope path names the scan."""
    space = xplane.parse(path)
    lo, hi = float("inf"), float("-inf")
    device = None
    for plane in space.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m and (device is None or int(m.group(1)) < device[0]):
            device = (int(m.group(1)), plane)
        elif plane.name.startswith("/host:"):
            names, _ = xplane.plane_tables(plane)
            for line in plane.lines:
                for s, e, ev in xplane.events(plane, line):
                    if names[ev.metadata_id].name == trace_mod.TRACED_SPAN:
                        lo, hi = min(lo, s), max(hi, e)
    if device is None:
        return None
    if lo > hi:                          # no such span: the whole trace
        lo, hi = float("-inf"), float("inf")
    plane = device[1]
    md, stat_names = xplane.plane_tables(plane)
    under: Dict[int, bool] = {}
    total = 0.0
    for line in plane.lines:
        if line.name != trace_mod.OPS_LINE:
            continue
        for s, e, ev in xplane.events(plane, line):
            if not lo <= s <= hi:
                continue
            mid = ev.metadata_id
            if mid not in under:
                m = md[mid]
                category = next(
                    (str(xplane.stat_value(st)) for st in m.stats
                     if stat_names.get(st.metadata_id) == "hlo_category"), "")
                under[mid] = "ssm.scan" in scopes._strings(m, stat_names) \
                    and trace_mod.op_class(m.name, category) == "matmul"
            if under[mid]:
                total += e - s
    return total / 1e9


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
        for k, name in HELD_AT_ZERO.items():
            # traced since set-up began: a step that fell off its kernel was
            # traced in set-up and runs in every step of the window
            numbers[k] = int(REGISTRY.counter(name, "").value)
        reference_s = time.perf_counter() - t_ref
        reduction = by_scope = scan_dots = None
        if trace_dir is not None:
            found = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler wrote no trace to "
                                   f"{trace_dir}")
            reduction = trace_mod.reduce_xplane(found[0], cell.chips)
            by_scope = scopes.scope_seconds(found[0], SCOPES)
            scan_dots = scan_matmul_seconds(found[0])
        seq = int(cell.traffic["sequence_length"])
        steps = max(moe1["moe_steps"] - moe0["moe_steps"], 1)
        rows_per_step = (moe1["moe_rows_total"] - moe0["moe_rows_total"]) \
            / steps
        flops = work_hybrid.train_flops_per_sample(
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
                      "scan_matmul_s": scan_dots,
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
