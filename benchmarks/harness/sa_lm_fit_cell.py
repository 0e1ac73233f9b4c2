"""The driver of token cells whose model attends through learned sparse
attention (the ``KeyeVL2`` family: a lightning indexer picks each query's
keys, the flash kernel reads those alone, the indexer learns by a KL loss
beside the LM loss): ``lm_fit_cell.py``'s ``prepare``, ``memory_peak_bytes``
and ``fit_cell.window`` as they are, ``gqa_lm_fit_cell.py``'s comparison (the
worst and the second-worst big leaf), with this family's scopes and its work
module (``work_sa.py``). Beside them:

* ``first_steps``: ``lm_fit_cell``'s, with each step's ``L_I`` read from the
  step's outputs (``sa_counters``), so that the LM loss and the indexer's
  are compared apart, and the first step's selection (how many keys each
  query chose in each of 128 spans of the keys);
* the comparison adds ``lm_loss_gap_<k>``, ``indexer_loss_gap_<k>`` and
  ``sa_selection_diff_share``: at least the share of the program's selected
  (query, key) pairs the reference did not select (the excess of the
  program's count over the reference's in each query's span, over all the
  program chose); pairs swapped inside one query's span are not seen;
* the faults of this mechanism, planted in the reference through its
  configuration (``reference_readings(prep, fault=...)``), for the tools
  that take the readings a cell's limits are set from;
* ``attention_reference_on_tpu``: attention call sites off the flash kernel
  on the chip since set-up began, held at 0;
* the selected-key walks' score entries, computed and needed, in the facts;
* ``sa_indexer_matmul_s``: the device time of the convolution-or-dot
  operations under ``sa.indexer`` (``scope_matmul_seconds``), which
  ``sa_dense_dot_roofline`` takes out of the dense products' time.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
import shutil
import time
from typing import Callable, Dict, Optional

import numpy as np

from . import fit_cell, lm_fit_cell, scopes, tokens, trace as trace_mod, \
    work_sa, xplane
from .gqa_lm_fit_cell import compare_sides as _gqa_compare_sides
from .lm_fit_cell import Prepared, memory_peak_bytes, prepare  # noqa: F401
from .spec import REPO_ROOT, Cell

# ``moe.shared`` reads 0 (no shared expert): ``moe_share_pct.train`` adds it
SCOPES = ("attn.gqa", "attn.sa", "sa.indexer", "moe.router", "moe.experts",
          "moe.shared", "lm_head", "optimizer", "prologue")
FAULTS = ("dense_attention", "no_causal_clip", "indexer_loss_dropped",
          "kl_target_head0")


def first_steps(prep: Prepared) -> None:
    """``lm_fit_cell.first_steps``'s three steps through ``fit``, one a
    call, with each step's ``L_I`` and the first step's selection read from
    the step's outputs."""
    import jax
    from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import \
        sa_counters
    est, factory = prep.est, prep.cell.load("factory")
    reference = prep.cell.load("reference")
    cfg = prep.cell.config
    names = sorted(prep.shapes)
    beta1 = float(cfg["optimizer"]["beta_1"])
    weight = float(prep.model_cfg.get("indexer_loss_weight", 1.0))
    losses, lm_losses, indexer_losses = [], [], []
    grad1 = loads1 = selection1 = None
    for _ in range(lm_fit_cell.CHECK_STEPS):
        stats = fit_cell._fit(est, prep.feed, 1, steps_per_epoch=1)
        loss = float(stats[-1]["train_loss"])
        sa = sa_counters(est.engine.extra_vars)
        losses.append(loss)
        indexer_losses.append(sa["sa_indexer_kl"])
        lm_losses.append(loss - weight * sa["sa_indexer_kl"])
        if grad1 is None:
            mu = fit_cell._by_name(
                lm_fit_cell._adam_first_moment(est.engine.opt_state),
                factory, cfg, names)
            grad1 = {k: np.asarray(v, np.float32) / np.float32(1.0 - beta1)
                     for k, v in jax.device_get(mu).items()}
            loads1 = lm_fit_cell._loads(est)
            selection1 = sa["sa_key_bins"]
    dparam = reference.change_since_start(
        prep.model_cfg, prep.seed,
        fit_cell._by_name(est.engine.params, factory, cfg, names))
    prep.program = {"losses": losses, "lm_losses": lm_losses,
                    "indexer_losses": indexer_losses,
                    "grad1_norm": reference.leaf_norms(grad1),
                    "dparam_norm": reference.leaf_norms(dparam),
                    "grad1": grad1, "dparam": dparam, "loads1": loads1,
                    "selection1": selection1}
    prep.fed = prep.feed.take()


def reference_readings(prep_like, quant=None, fault: Optional[str] = None
                       ) -> Dict:
    """``lm_fit_cell.reference_readings`` with the loss's two parts and the
    first step's selection; ``quant`` makes it the control, ``fault`` one
    of ``FAULTS``: every causal key in place of the selection, the top-k
    without its causal clip, ``L_I`` left out of the loss, or the KL's
    target head 0's probabilities alone."""
    if fault is not None:
        if fault not in FAULTS:
            raise ValueError(f"fault {fault!r} is none of {FAULTS}")
        prep_like = dataclasses.replace(prep_like, model_cfg=dict(
            prep_like.model_cfg, reference_fault=fault))
    return lm_fit_cell.reference_readings(prep_like, quant=quant)


def _gaps(got, want):
    out = []
    for a, b in zip(got, want):
        gap = abs(a - b) / abs(b) if b else (0.0 if a == b else math.inf)
        out.append(gap if math.isfinite(gap) else math.inf)
    return out


def compare_sides(side: Dict, ref: Dict, shapes: Dict[str, tuple],
                  reference) -> Dict:
    """``gqa_lm_fit_cell.compare_sides``'s numbers, the two losses' gaps
    step by step, and ``sa_selection_diff_share``."""
    numbers = _gqa_compare_sides(side, ref, shapes, reference)
    for name in ("lm", "indexer"):
        for k, gap in enumerate(_gaps(side[f"{name}_losses"],
                                      ref[f"{name}_losses"]), 1):
            numbers[f"{name}_loss_gap_{k}"] = gap
    extra = total = 0
    for layer, want in ref["selection1"].items():
        got = np.asarray(side["selection1"][layer], np.int64)
        extra += np.maximum(got - np.asarray(want, np.int64), 0).sum()
        total += got.sum()
    numbers["sa_selection_diff_share"] = float(extra) / max(float(total),
                                                            1.0)
    return numbers


def scope_matmul_seconds(path: str, scope: str) -> Optional[float]:
    """Device seconds, over the traced steps on device 0, of the operations
    under ``scope`` that the trace classes as convolution or dot (fusions
    around them included): ``trace.reduce_devices``' ``matmul_s`` rule on the
    operations whose scope path names ``scope``."""
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
                under[mid] = scope in scopes._strings(m, stat_names) \
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
        moe_counters, sa_counters
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
        sa1 = sa_counters(prep.est.engine.extra_vars)
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
        reduction = by_scope = indexer_dots = None
        if trace_dir is not None:
            found = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler wrote no trace to "
                                   f"{trace_dir}")
            reduction = trace_mod.reduce_xplane(found[0], cell.chips)
            by_scope = scopes.scope_seconds(found[0], SCOPES)
            indexer_dots = scope_matmul_seconds(found[0], "sa.indexer")
        seq = int(cell.traffic["sequence_length"])
        steps = max(moe1["moe_steps"] - moe0["moe_steps"], 1)
        rows_per_step = (moe1["moe_rows_total"] - moe0["moe_rows_total"]) \
            / steps
        flops = work_sa.train_flops_per_sample(
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
                      "sa_entries": {
                          "computed": sa1["sa_entries_computed"],
                          "needed": sa1["sa_entries_needed"]},
                      "sa_indexer_kl": sa1["sa_indexer_kl"],
                      "sa_indexer_matmul_s": indexer_dots,
                      "moe": dict(moe1, moe_local_rows=rows_per_step),
                      "compile": {"setup_compile_s": cstats["compile_s"],
                                  "setup_compiles": cstats["compiles"],
                                  "setup_disk_hits": cstats["disk_hits"],
                                  "fallbacks": cstats["fallbacks"]},
                      "reference_s": reference_s,
                      "program_readings": {
                          "losses": program["losses"],
                          "lm_losses": program["lm_losses"],
                          "indexer_losses": program["indexer_losses"]},
                      "reference_losses": {
                          "losses": ref["losses"],
                          "lm_losses": ref["lm_losses"],
                          "indexer_losses": ref["indexer_losses"]}},
        }
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
