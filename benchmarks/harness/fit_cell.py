"""The driver of training cells: ``TPUEstimator.fit`` on ``ImageNetPipeline``.

One run of a cell, in the order the phases happen:

1. set-up: the program's context on the cell's chips, the data set written
   from the seed, the estimator built by the configuration's factory, the
   benchmark's seeded weights put in the place of the module's own, then the
   first three training steps through ``fit`` itself (one step a call, so that
   the state can be read in between). They compile the step, fill the pump,
   and give the program's side of `correct`;
2. the window: whole ``fit`` epochs on that same estimator and feed, from a
   host timestamp to the epoch-end sync of the first epoch that ends after
   ``--seconds``; in a traced run the profiler wraps a short ``fit`` call of
   ``TRACED_STEPS`` steps in the middle of it;
3. the device's peak memory is read, the program's state is freed, and the
   plain reference follows the same three steps from the same weights on the
   batches the infeed really delivered, which are themselves checked against
   the data set.

The program is driven only through its public entry points
(``init_orca_context``, ``ImageNetPipeline``, ``TPUEstimator.fit``,
``data_pipeline_stats``, ``compile_stats``); nothing loops over
``engine.train_batch``.
"""

from __future__ import annotations

import functools
import gc
import glob
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, dataset, trace as trace_mod, work
from .feed import RecordingFeed
from .spec import REPO_ROOT, Cell

CHECK_STEPS = 3
# The device profiler keeps 0.4-0.8 GB of host memory for every traced step
# of a step program this size (26 GB for a 32-step epoch on a 40 GiB host, my
# chip run, PR 32), so a traced run wraps two short fit calls and not whole
# epochs: one for the profiler to settle (its start stalls the first steps by
# seconds), one that is read.
SETTLE_STEPS = 2
TRACED_STEPS = 12
SEED_MOD = 2 ** 31 - 1          # the program's RandomState and PRNGKey take 32 bits


# --- what the configurations' factories share --------------------------------

def sgd_estimator(module, cfg: dict, mesh, global_batch: int,
                  steps_per_epoch: int, seed: int):
    """A ``TPUEstimator`` with the configuration's SGD-momentum recipe, as
    chip_smoke.py builds its ResNet: rate 0 rising by a step to the peak over
    the warm-up epochs, then polynomial decay."""
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.losses import \
        sparse_categorical_crossentropy
    from analytics_zoo_tpu.orca.learn.optimizers import SGD
    from analytics_zoo_tpu.orca.learn.optimizers.schedule import (
        Poly, SequentialSchedule, Warmup)
    opt = cfg["optimizer"]
    if opt["kind"] != "sgd_momentum":
        raise ValueError(f"sgd_estimator builds sgd_momentum, the "
                         f"configuration asks for {opt['kind']!r}")
    peak = opt["peak_lr_per_256"] * global_batch / 256
    warm = opt["warmup_epochs"] * steps_per_epoch
    decay = opt["decay_epochs"] * steps_per_epoch
    sched = (SequentialSchedule()
             .add(Warmup(delta=peak / warm), warm)
             .add(Poly(opt["decay_power"], decay), decay))
    return TPUEstimator(
        module,
        loss=functools.partial(sparse_categorical_crossentropy,
                               from_logits=True),
        optimizer=SGD(learningrate=0.0, momentum=opt["momentum"],
                      weightdecay=opt["weight_decay"],
                      leaningrate_schedule=sched),
        mesh=mesh, seed=seed % SEED_MOD)


# --- set-up -------------------------------------------------------------------

@dataclass
class Prepared:
    cell: Cell
    seed: int
    mesh: object
    devices: list
    data_dir: str
    pipeline: object
    feed: RecordingFeed
    est: object
    global_batch: int
    steps_per_epoch: int
    shapes: Dict[str, tuple]
    dataset_bytes: int
    program: Dict = field(default_factory=dict)      # its side of `correct`
    fed: List = field(default_factory=list)          # the batches, by epoch


def open_context(cell: Cell, cluster_mode: str = "tpu"):
    """The program's context on exactly the chips the cell asks for. The
    compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says or, unset, to
    a fixed directory inside the checkout."""
    import jax
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    # on the CPU (the benchmark's own tests) nothing persists
    cache = {"compile_cache_dir": os.path.join(REPO_ROOT, ".zoo_compile_cache")} \
        if cluster_mode == "tpu" else {}
    ctx = init_orca_context(cluster_mode=cluster_mode, **cache)
    devices = list(ctx.devices[:cell.chips])
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chip(s), "
                         f"JAX found {jax.device_count()}")
    return create_mesh({"dp": cell.chips}, devices=devices), devices


def seeded_weights(cfg: dict, shapes: Dict[str, tuple], seed: int) -> Dict:
    """The benchmark's weights for a configuration: the program and the
    reference both start from these."""
    from reference import nn
    return nn.make_weights(shapes, seed, cfg.get("init_scale_by_suffix"))


def _set_weights(est, factory, cfg: dict, weights: Dict, sample) -> None:
    """Build the engine, then put the benchmark's weights where the module's
    own initial values were (same tree, same shardings)."""
    import jax
    import jax.numpy as jnp
    eng = est.engine
    eng.build((sample,))
    by_path = {factory.program_path(cfg, name): name for name in weights}

    def pick(path, old):
        key = tuple(getattr(p, "key", getattr(p, "name", None)) for p in path)
        new = weights[by_path.pop(key)]
        if new.shape != old.shape:
            raise ValueError(f"{key}: the program has {old.shape}, the "
                             f"reference {new.shape}")
        # a copy: the step donates its parameters
        return jax.device_put(jnp.copy(new).astype(old.dtype), old.sharding)

    eng.params = jax.tree_util.tree_map_with_path(pick, eng.params)
    if by_path:
        raise ValueError(f"weights with no place in the program: "
                         f"{sorted(by_path.values())[:5]}")


def _by_name(tree, factory, cfg: dict, names) -> Dict:
    """The program's tree as the reference names its leaves."""
    out = {}
    for name in names:
        node = tree
        for k in factory.program_path(cfg, name):
            node = node[k]
        out[name] = node
    return out


def _momentum_trace(opt_state, params):
    """The SGD momentum buffer: the one part of the optimizer's state shaped
    like the parameters. After the first step it is the first gradient as the
    optimizer got it (m1 = momentum * 0 + g1)."""
    import jax
    want = jax.tree_util.tree_structure(params)
    found = []

    def walk(node):
        if jax.tree_util.tree_structure(node) == want:
            found.append(node)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)
        elif hasattr(node, "_fields"):
            for f in node._fields:
                walk(getattr(node, f))
        elif isinstance(node, dict):
            for child in node.values():
                walk(child)

    walk(opt_state)
    if len(found) != 1:
        raise ValueError(f"{len(found)} parts of the optimizer state are "
                         f"shaped like the parameters; expected the one "
                         f"momentum buffer")
    return found[0]


def _fit(est, feed, epochs: int, steps_per_epoch: Optional[int] = None):
    import jax
    stats = est.fit(feed, epochs=epochs, steps_per_epoch=steps_per_epoch,
                    verbose=False)
    jax.block_until_ready(est.engine.params)
    return stats


def prepare(cell: Cell, seed: int, mesh, devices, data_dir: str) -> Prepared:
    import jax
    from analytics_zoo_tpu.orca.data.image import ImageNetPipeline
    cfg, traffic = cell.config, cell.traffic
    global_batch = int(cfg["per_chip_batch"]) * cell.chips
    shutil.rmtree(data_dir, ignore_errors=True)
    nbytes = dataset.write_image_shards(data_dir, traffic, seed)
    pipeline = ImageNetPipeline(data_dir, batch_size=global_batch, mesh=mesh,
                                crop_size=int(traffic["crop"]), train=True,
                                seed=seed % SEED_MOD)
    feed = RecordingFeed(pipeline, keep=CHECK_STEPS)
    factory, reference = cell.load("factory"), cell.load("reference")
    est = factory.build(cfg, mesh, global_batch, pipeline.steps_per_epoch,
                        seed)
    shapes = reference.param_shapes(cfg)
    from reference import nn
    weights = seeded_weights(cfg, shapes, seed)
    crop = int(traffic["crop"])
    _set_weights(est, factory, cfg, weights,
                 np.zeros((1, crop, crop, 3), np.uint8))
    del weights
    return Prepared(cell, seed, mesh, devices, data_dir, pipeline, feed, est,
                    global_batch, pipeline.steps_per_epoch, shapes, nbytes)


def first_steps(prep: Prepared) -> None:
    """The first steps, through ``fit`` and the feed the window uses, one
    step a call: each step's loss, the first gradient's leaf norms from the
    optimizer's state after step 1, the parameters' change after the last."""
    import jax
    import jax.numpy as jnp
    from reference import nn, train
    est, factory = prep.est, prep.cell.load("factory")
    names = sorted(prep.shapes)
    losses, grad1 = [], None
    for _ in range(CHECK_STEPS):
        stats = _fit(est, prep.feed, 1, steps_per_epoch=1)
        losses.append(float(stats[-1]["train_loss"]))
        if grad1 is None:
            # a copy: the next step donates the optimizer's state
            grad1 = jax.tree.map(jnp.copy, _by_name(
                _momentum_trace(est.engine.opt_state, est.engine.params),
                factory, prep.cell.config, names))
    start = seeded_weights(prep.cell.config, prep.shapes, prep.seed)
    dparam = jax.jit(lambda new, old: {
        k: new[k].astype(old[k].dtype) - old[k] for k in old})(
        _by_name(est.engine.params, factory, prep.cell.config, names), start)
    del start
    prep.program = {"losses": losses, "grad1_norm": train.leaf_norms(grad1),
                    "dparam_norm": train.leaf_norms(dparam),
                    "grad1": grad1, "dparam": dparam}
    prep.fed = prep.feed.take()


# --- the window ---------------------------------------------------------------

def _pipeline_counters(est) -> Dict[str, float]:
    snap = est.data_pipeline_stats()
    return {k: snap[k] for k in ("stall_s", "stall_n", "assemble_s",
                                 "assemble_n", "h2d_bytes", "depth_peak")}


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: (after[k] if k == "depth_peak" else after[k] - before[k])
            for k in after}


def window(prep: Prepared, seconds: float, trace_dir: Optional[str]) -> Dict:
    """Whole ``fit`` epochs until the first epoch-end sync after ``seconds``.
    Returns the window's facts and, in a traced run, those of the traced
    epochs, which the per-layer metrics read."""
    import jax
    from analytics_zoo_tpu.compile import compile_stats
    est, feed = prep.est, prep.feed
    per_epoch = prep.steps_per_epoch * prep.global_batch
    compiles0 = compile_stats()["compiles"]
    c0, fed0 = _pipeline_counters(est), feed.batches_fed
    losses: List[float] = []
    traced = None
    traced_steps = min(TRACED_STEPS, prep.steps_per_epoch)
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def run(epochs: int, steps: Optional[int] = None):
        with jax.profiler.TraceAnnotation("bench:fit_call"):
            for s in _fit(est, feed, epochs, steps):
                losses.append(float(s["train_loss"]))

    with jax.profiler.TraceAnnotation("bench:window"):
        run(1)
        epoch_s = time.perf_counter() - t0
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host threads feed the chip
            # level 1 keeps the benchmark's own annotations; at the default
            # the runtime's threads log every transfer chunk: 300 MB of
            # events and 26 GB of host memory for a 32-step epoch
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                # the profiler's own start stalls the first steps after it
                # (gaps of 4.7 s and 1.8 s seen): one fit call lets it settle,
                # the next one is read
                run(1, SETTLE_STEPS)
                tc0, tf0, tt0 = _pipeline_counters(est), feed.batches_fed, \
                    time.perf_counter()
                with jax.profiler.TraceAnnotation("bench:traced_steps"):
                    run(1, traced_steps)
                tt1 = time.perf_counter()
            finally:
                jax.profiler.stop_trace()
            traced = {"seconds": tt1 - tt0,
                      "samples": (feed.batches_fed - tf0) * prep.global_batch,
                      "steps": traced_steps,
                      "counters": _delta(_pipeline_counters(est), tc0)}
        while True:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            # as few fit calls as the clock allows: a user's fit(epochs=90)
            # pays the call's own set-up once, not once an epoch
            run(max(1, int(0.9 * left / epoch_s)))
    t1 = time.perf_counter()
    facts = {"seconds": t1 - t0,
             "samples": (feed.batches_fed - fed0) * prep.global_batch,
             "epochs": (feed.batches_fed - fed0) // prep.steps_per_epoch,
             "samples_per_epoch": per_epoch,
             "counters": _delta(_pipeline_counters(est), c0),
             "compiles_in_window": compile_stats()["compiles"] - compiles0,
             "losses_finite": bool(np.all(np.isfinite(losses))),
             "first_epoch_loss": losses[0], "last_epoch_loss": losses[-1]}
    return {"window": facts, "traced": traced}


# --- the reference's side -----------------------------------------------------

def reference_readings(prep_like, quant=None, rows=None) -> Dict:
    """The plain reference over the batches that were fed, from the same
    seeded weights. ``prep_like`` needs ``cell``, ``seed``, ``shapes``,
    ``fed``, ``global_batch``, ``steps_per_epoch``, ``mesh``."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from reference import nn, train
    cell = prep_like.cell
    reference = cell.load("reference")
    weights = seeded_weights(cell.config, prep_like.shapes, prep_like.seed)
    batches = [b for ep in prep_like.fed for b in ep]
    lrs = [nn.learning_rate(cell.config["optimizer"], prep_like.global_batch,
                            prep_like.steps_per_epoch, k)
           for k in range(len(batches))]
    sharding = None
    if cell.chips > 1:
        mesh = prep_like.mesh
        weights = jax.device_put(weights, NamedSharding(mesh, P()))

        def sharding(ndim):
            return NamedSharding(mesh, P(*(("dp",) + (None,) * (ndim - 1))))
    out = train.first_steps(reference.forward, cell.config, weights, batches,
                            lrs, quant=quant, rows=rows,
                            batch_sharding=sharding)
    del weights
    return out


def compare_sides(side: Dict, ref: Dict, shapes: Dict[str, tuple]) -> Dict:
    """The numbers compared between a side (the program; in the readings, the
    control or a fault in its place) and the reference; takes the leaves'
    distances on the device and drops the side's trees."""
    from reference import train
    for name in ("grad1", "dparam"):
        if name in side:
            side[f"{name[:-1] if name == 'grad1' else name}_diff"] = \
                train.diff_norms(side.pop(name), ref[name])
    sizes = {k: int(np.prod(v)) for k, v in shapes.items()}
    return check.compare(side, ref, sizes)


def free_program(prep: Prepared) -> None:
    """Drop the estimator, its state and the pump's buffers, so that the
    reference has the chip to itself."""
    import jax
    from analytics_zoo_tpu.compile import reset_compile_cache
    prep.est.shutdown()
    prep.est = prep.pipeline = prep.feed = None
    reset_compile_cache()        # the process-wide store holds the executables
    jax.clear_caches()
    gc.collect()


# --- one run -------------------------------------------------------------------

def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip: live arrays ("in use") and the loaded
    programs' scratch ("reserved", where the step's activations live) are
    disjoint in this runtime's ``memory_stats()``, so the peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        cluster_mode: str = "tpu", scratch: Optional[str] = None,
        tamper: Optional[Callable[[Prepared], None]] = None) -> Dict:
    """One run of the cell. ``tamper`` is for the benchmark's own tests: it
    gets the prepared estimator and feed before the first step and breaks the
    timed path underneath, and `correct` has to come out false."""
    from analytics_zoo_tpu.compile import compile_stats
    scratch = scratch or os.path.join(
        os.environ.get("TMPDIR") or os.path.join(REPO_ROOT, ".bench_tmp"),
        f"bench_{cell.name}")
    data_dir = os.path.join(scratch, "data")
    trace_dir = os.path.join(scratch, "trace") if trace else None
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch, exist_ok=True)
    try:
        mesh, devices = open_context(cell, cluster_mode)
        prep = prepare(cell, seed, mesh, devices, data_dir)
        if tamper is not None:
            tamper(prep)
        first_steps(prep)
        cstats = compile_stats()
        setup_s = time.perf_counter() - t_start
        spans = window(prep, seconds, trace_dir)
        peak = memory_peak_bytes(devices)
        index = dataset.ShardIndex(data_dir)
        rows = dataset.count_bad_rows(index, prep.fed)
        del index
        program = prep.program
        free_program(prep)
        t_ref = time.perf_counter()
        ref = reference_readings(prep)
        numbers = compare_sides(program, ref, prep.shapes)
        numbers["infeed_bad_rows"] = rows["bad"]
        numbers["window_losses_not_finite"] = \
            0 if spans["window"]["losses_finite"] else 1
        numbers["compiles_in_window"] = spans["window"]["compiles_in_window"]
        reference_s = time.perf_counter() - t_ref
        reduction = None
        if trace_dir is not None:
            found = glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError(f"the profiler wrote no trace to "
                                   f"{trace_dir}")
            reduction = trace_mod.reduce_xplane(found[0], cell.chips)
        wfacts = spans["window"]
        flops = work.train_flops_per_sample(cell.config["layers"])
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
                      "train_flops_per_sample": flops,
                      "layers": cell.config["layers"],
                      "dtype_bytes": 2,
                      "compile": {"setup_compile_s": cstats["compile_s"],
                                  "setup_compiles": cstats["compiles"],
                                  "setup_disk_hits": cstats["disk_hits"],
                                  "fallbacks": cstats["fallbacks"]},
                      "dataset_bytes": prep.dataset_bytes,
                      "reference_s": reference_s,
                      "program_readings": {"losses": program["losses"]},
                      "reference_losses": ref["losses"]},
        }
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
