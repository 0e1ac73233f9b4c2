"""The driver of token cells: ``TPUEstimator.fit`` on arrays of token ids.

The same phases as ``fit_cell.run`` (whose window, feed, context and memory
reading it uses as they are):

1. set-up: the program's context, the packed sequences made from the seed
   and handed to the program's front door for arrays (``learn_utils.
   data_to_iterator``: the ``BatchIterator`` and pump every ``fit({"x": ids,
   "y": ids})`` builds, made once here so that the recording feed can wrap
   it), the estimator built by the configuration's factory, the benchmark's
   seeded weights in the place of the module's own, then the first three
   steps through ``fit`` (one step a call). After the first, Adam's first
   moment over ``1 - beta_1`` is the first gradient as the optimizer got it
   (clipped); the expert layers' per-expert loads of that step are the
   program's side of the routing comparison;
2. the window: whole ``fit`` epochs (``fit_cell.window``);
3. peak memory, the program's state freed, and the plain reference over the
   batches the feed delivered: a sequence at a time, gradients summed.

A parameter tree is 2.7 GB at the cell's size, so both sides' gradient and
parameter change wait on the host while the other side runs, and their
distances are taken there, leaf by leaf.
"""

from __future__ import annotations

import glob
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, fit_cell, scopes, tokens, trace as trace_mod, work_lm
from .feed import RecordingFeed
from .spec import REPO_ROOT, Cell

CHECK_STEPS = fit_cell.CHECK_STEPS
SCOPES = ("attn.mla", "moe.router", "moe.experts", "moe.shared", "mtp",
          "lm_head", "optimizer", "prologue")


@dataclass
class Prepared:
    cell: Cell
    seed: int
    mesh: object
    devices: list
    data: np.ndarray
    feed: RecordingFeed
    est: object
    global_batch: int
    steps_per_epoch: int
    shapes: Dict[str, tuple]
    model_cfg: dict
    pipeline: object = None
    program: Dict = field(default_factory=dict)
    fed: List = field(default_factory=list)


def model_config(cell: Cell) -> dict:
    """The model's view of the configuration (the router's full width, the
    experts held), with the traffic's sequence length beside it."""
    return dict(cell.load("factory").model_config(cell.config),
                sequence_length=int(cell.traffic["sequence_length"]))


def prepare(cell: Cell, seed: int, mesh, devices) -> Prepared:
    from analytics_zoo_tpu.orca.learn import utils as learn_utils
    cfg, traffic = cell.config, cell.traffic
    mcfg = model_config(cell)
    global_batch = int(cfg["per_chip_batch"]) * cell.chips
    data = tokens.make_sequences(traffic, int(cfg["vocab_size"]), seed)
    pipeline = learn_utils.data_to_iterator(
        {"x": data, "y": data}, global_batch, mesh,
        shuffle=bool(traffic["shuffle"]), seed=seed % fit_cell.SEED_MOD,
        pad_tail=False)
    feed = RecordingFeed(pipeline, keep=CHECK_STEPS)
    factory, reference = cell.load("factory"), cell.load("reference")
    est = factory.build(cfg, mesh, global_batch, pipeline.steps_per_epoch,
                        seed)
    weights = reference.make_weights(mcfg, seed)
    fit_cell._set_weights(est, factory, cfg, weights,
                          np.zeros((1, data.shape[1]), data.dtype))
    del weights
    return Prepared(cell, seed, mesh, devices, data, feed, est, global_batch,
                    pipeline.steps_per_epoch, reference.param_shapes(mcfg),
                    mcfg, pipeline=pipeline)


def _adam_first_moment(opt_state):
    import jax

    def is_adam(node):
        return hasattr(node, "mu") and hasattr(node, "nu")

    found = [n.mu for n in jax.tree_util.tree_leaves(opt_state,
                                                     is_leaf=is_adam)
             if is_adam(n)]
    if len(found) != 1:
        raise ValueError(f"{len(found)} Adam states in the optimizer's "
                         f"state; expected one")
    return found[0]


def _loads(est) -> Dict[str, np.ndarray]:
    """Each expert block's per-expert token-choices of the last step."""
    import jax
    stats = jax.device_get(est.engine.extra_vars.get("moe_stats", {}))
    return {k: np.asarray(v["mlp"]["load"]) for k, v in stats.items()}


def first_steps(prep: Prepared) -> None:
    import jax
    est, factory = prep.est, prep.cell.load("factory")
    reference = prep.cell.load("reference")
    cfg = prep.cell.config
    names = sorted(prep.shapes)
    beta1 = float(cfg["optimizer"]["beta_1"])
    losses, grad1, loads1 = [], None, None
    for _ in range(CHECK_STEPS):
        stats = fit_cell._fit(est, prep.feed, 1, steps_per_epoch=1)
        losses.append(float(stats[-1]["train_loss"]))
        if grad1 is None:
            mu = fit_cell._by_name(_adam_first_moment(est.engine.opt_state),
                                   factory, cfg, names)
            grad1 = {k: np.asarray(v, np.float32) / np.float32(1.0 - beta1)
                     for k, v in jax.device_get(mu).items()}
            loads1 = _loads(est)
    dparam = reference.change_since_start(
        prep.model_cfg, prep.seed,
        fit_cell._by_name(est.engine.params, factory, cfg, names))
    prep.program = {"losses": losses,
                    "grad1_norm": reference.leaf_norms(grad1),
                    "dparam_norm": reference.leaf_norms(dparam),
                    "grad1": grad1, "dparam": dparam, "loads1": loads1}
    prep.fed = prep.feed.take()


def reference_readings(prep_like, quant=None, rows=None,
                       drop_mtp: bool = False) -> Dict:
    """The plain reference over the batches that were fed, from the same
    seeded weights; ``quant`` makes it the control, ``rows`` and ``drop_mtp``
    the faults."""
    reference = prep_like.cell.load("reference")
    weights = reference.make_weights(prep_like.model_cfg, prep_like.seed)
    batches = [x for ep in prep_like.fed for x, _ in ep]
    out = reference.first_steps(prep_like.model_cfg, weights, batches,
                                quant=quant, rows=rows, drop_mtp=drop_mtp)
    del weights
    out["dparam"] = reference.change_since_start(
        prep_like.model_cfg, prep_like.seed, out.pop("params"))
    out["dparam_norm"] = reference.leaf_norms(out["dparam"])
    out["loads1"] = {k: np.stack([np.bincount(
        c.reshape(-1), minlength=int(prep_like.model_cfg["n_routed_experts"]))
        for c in v]).sum(0) for k, v in out.pop("choices1").items()}
    return out


def compare_sides(side: Dict, ref: Dict, shapes: Dict[str, tuple],
                  reference) -> Dict:
    """``check.compare``'s numbers between a side and the reference, the
    leaves' distances taken on the host, plus the routing comparison: the
    share of the first step's token-choices that went to another expert
    than the reference's, at least (half the summed difference of the
    per-expert loads over the choices)."""
    for name, key in (("grad1", "grad_diff"), ("dparam", "dparam_diff")):
        if name in side:
            side[key] = reference.diff_norms(side.pop(name), ref[name])
    sizes = {k: int(np.prod(v)) for k, v in shapes.items()}
    numbers = check.compare(side, ref, sizes)
    moved = total = 0
    for k, want in ref["loads1"].items():
        moved += np.abs(np.asarray(side["loads1"][k], np.int64)
                        - np.asarray(want, np.int64)).sum() / 2
        total += np.asarray(want, np.int64).sum()
    numbers["choice_diff_share"] = float(moved) / max(float(total), 1.0)
    return numbers


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip, read after the window: the larger of the
    live arrays' own peak (reached in set-up, while the seeded weights
    replace the module's: 13.65 GB) and the live arrays now plus the loaded
    programs' scratch (what a step holds while it runs: 8.6 + 4.8 GB).
    ``fit_cell.memory_peak_bytes`` adds the two peaks, which here lie at
    different times and add up to more than the chip has."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(max(s.get("peak_bytes_in_use", 0),
                         s.get("bytes_in_use", 0)
                         + s.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


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
        flops = work_lm.train_flops_per_sample(
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
                      "moe": dict(moe1, moe_local_rows=rows_per_step),
                      "compile": {"setup_compile_s": cstats["compile_s"],
                                  "setup_compiles": cstats["compiles"],
                                  "setup_disk_hits": cstats["disk_hits"],
                                  "fallbacks": cstats["fallbacks"]},
                      "reference_s": reference_s,
                      "program_readings": {"losses": program["losses"]},
                      "reference_losses": ref["losses"],
                      "reference_head_losses": {
                          "main": ref["main_losses"],
                          "mtp": ref["mtp_losses"]}},
        }
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
