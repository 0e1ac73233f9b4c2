"""Unified Orca Estimator on the TPU engine.

One estimator replaces the reference's per-framework factories (TF1
``Estimator.from_graph/from_keras`` at pyzoo/zoo/orca/learn/tf/estimator.py:
291,335; TF2 ``Estimator.from_keras`` at orca/learn/tf2/estimator.py:36; torch
at orca/learn/pytorch/estimator.py:38; bigdl at orca/learn/bigdl/estimator.py:30).
The fit/evaluate/predict signatures and stats dicts mirror the reference so
user code ports; the execution is a single jitted step over the mesh.
"""

from __future__ import annotations

import itertools
import logging
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ...common.context import get_context
from ...obs import trace as _trace
from ..data.shard import HostXShards
from . import utils as learn_utils
from .engine import TrainEngine
from .losses import convert_loss
from .metrics import convert_metrics_list
from .optimizers.optimizers_impl import convert_optimizer
from .trigger import EveryEpoch, TrainerState, Trigger

logger = logging.getLogger("analytics_zoo_tpu")


def _close(batches):
    """Close an epoch's iterator: a generator over an ``InfeedPump`` then
    joins the pump's producer thread. A plain iterator has nothing to
    close."""
    close = getattr(batches, "close", None)
    if close is not None:
        close()


class Estimator:
    """Factory namespace, mirroring ``zoo.orca.learn.*.estimator.Estimator``."""

    @staticmethod
    def from_keras(model_creator: Optional[Callable] = None, *,
                   model=None, config: Optional[dict] = None,
                   loss=None, optimizer="adam", metrics=None,
                   model_dir: Optional[str] = None, backend: str = "tpu",
                   workers_per_node: int = 1, seed: int = 0,
                   prologue=None, sharding=None):
        """Build an estimator from a flax module (or creator function), the
        TPU-native analogue of from_keras(model_creator) (reference:
        orca/learn/tf2/estimator.py:36-93). ``config`` is passed to the
        creator like the reference's config dict."""
        module = model if model is not None else model_creator(config or {})
        # allow creators that return (module, loss, optimizer)
        if isinstance(module, tuple):
            module, loss, optimizer = module
        return TPUEstimator(module, loss=loss, optimizer=optimizer,
                            metrics=metrics, model_dir=model_dir,
                            config=config, seed=seed, prologue=prologue,
                            sharding=sharding)

    @staticmethod
    def from_jax(module=None, **kwargs):
        return Estimator.from_keras(model=module, **kwargs)

    # from_torch lives in orca.learn.pytorch.estimator (adapter layer)

    @staticmethod
    def latest_checkpoint(model_dir: str):
        path, _ = learn_utils.find_latest_checkpoint(model_dir)
        return path


class TPUEstimator:
    """The engine-backed estimator (replaces TensorFlow2Estimator,
    PyTorchRayEstimator, TensorFlowEstimator, BigDLEstimator)."""

    def __init__(self, module, loss=None, optimizer="adam", metrics=None,
                 model_dir: Optional[str] = None,
                 config: Optional[dict] = None, seed: int = 0, mesh=None,
                 fsdp: bool = False, compile_cache=None, prologue=None,
                 sharding=None):
        self.ctx = get_context()
        self.mesh = mesh if mesh is not None else self.ctx.mesh
        self.module = module
        self.config = config or {}
        self.model_dir = model_dir
        with _trace.stage("estimator.init"):
            self.loss_fn = convert_loss(loss) if loss is not None else None
            self.metrics = convert_metrics_list(metrics)
            tx = convert_optimizer(optimizer)
            # compile plane: default is the process-wide executable cache;
            # ``compile_cache=False`` (arg or config key) opts out to plain
            # jit
            if compile_cache is None:
                compile_cache = self.config.get("compile_cache", None)
            # transfer plane: an on-device input prologue (orca/learn/
            # prologue.BatchPrologue) moves cast/normalize/one-hot INSIDE
            # the jitted step so the wire carries narrow source dtypes
            # (uint8/int32)
            if prologue is None:
                prologue = self.config.get("prologue", None)
            # sharding plane (parallel/sharding.py): SpecLayout-driven
            # fsdp×tp param sharding over the multi-axis mesh — models
            # bigger than one chip. Knobs: ``sharding`` arg (SpecLayout |
            # True | False) / config ``sharding`` / ZOO_SHARDING_PLANE,
            # ZOO_FSDP_BUCKET_MB. All-default means OFF: the engine's step
            # is byte-identical.
            from ...parallel.sharding import SpecLayout
            spec_layout = SpecLayout.resolve(self.config, sharding)
            self.engine = TrainEngine(module, tx, self.loss_fn, self.metrics,
                                      self.mesh, seed=seed, fsdp_params=fsdp,
                                      compile_cache=compile_cache,
                                      prologue=prologue, sharding=spec_layout)
        # one stats object spans iterator assembly, the pump's H2D stage and
        # the engine's dispatches — the estimator is where they all meet
        from ...native.infeed import PipelineStats
        self._pipeline_stats = PipelineStats()
        self.engine.pipeline_stats = self._pipeline_stats
        self._trainer_state = TrainerState()
        self.train_stats: List[Dict[str, float]] = []
        self._tb_train = None
        self._tb_val = None
        self._profile_open = False      # fit(profile=<dir>)'s session
        # probed fuse factors per (mode, input signature): fit with
        # validation_data evaluates every epoch, and hyperparameter loops
        # re-fit — the probe answer cannot change for the same
        # model/shapes, so pay it once
        self._fuse_probe_cache: Dict = {}
        # checkpoint plane (analytics_zoo_tpu.ckpt): lazily bound to the
        # first model_dir save_checkpoint/load_checkpoint touches
        self._ckpt_plane = None

    # --- checkpoint plane ---------------------------------------------------
    def _ckpt(self, model_dir: str):
        """The CheckpointPlane for ``model_dir`` (one per estimator; rebound
        if a caller switches directories). Knobs ride ``config``:
        ``ckpt_async`` (default True — the loop pays only the device→host
        snapshot, a writer thread drains behind training),
        ``ckpt_keep_last_k``/``ckpt_keep_best_k`` retention,
        ``ckpt_passphrase`` (encrypted at rest via utils/crypto),
        ``ckpt_max_inflight`` (back-to-back trigger window, default 2)."""
        from ...ckpt import CheckpointPlane
        if self._ckpt_plane is None or self._ckpt_plane.root != model_dir:
            if self._ckpt_plane is not None:
                self._ckpt_plane.close()
            cfg = self.config
            self._ckpt_plane = CheckpointPlane(
                model_dir,
                keep_last_k=cfg.get("ckpt_keep_last_k"),
                keep_best_k=cfg.get("ckpt_keep_best_k"),
                metric_mode=cfg.get("ckpt_metric_mode", "min"),
                passphrase=cfg.get("ckpt_passphrase"),
                async_save=bool(cfg.get("ckpt_async", True)),
                max_inflight=int(cfg.get("ckpt_max_inflight", 2)),
                fsync=bool(cfg.get("ckpt_fsync", True)))
        return self._ckpt_plane

    def flush_checkpoints(self, timeout: Optional[float] = None) -> bool:
        """Drain pending async checkpoint writes (no-op without a plane).
        fit() calls this on every exit path; the preemption handler calls
        it explicitly so the write lands inside the grace window."""
        if self._ckpt_plane is None:
            return True
        return self._ckpt_plane.flush(timeout)

    # --- pipeline observability ---------------------------------------------
    def data_pipeline_stats(self, reset: bool = False) -> Dict[str, Any]:
        """Cumulative input-pipeline stage counters: ``assemble_s`` (host
        batch gather), ``h2d_s`` (+``h2d_bytes``/``h2d_MBps``, device
        staging: on an accelerator the time to enqueue), ``step_s`` (engine
        dispatch), ``stall_s`` (training loop starved waiting on the
        infeed), ``first_batch_s`` (each epoch's wait for the pump's first
        batch, which ``stall_s`` leaves out), ``open_ahead_s`` /
        ``open_ahead_n`` (the epochs that ``fit`` opened before the sync of
        the epoch before them, so that this wait passed while the device
        was still working: ``open_ahead_n / first_batch_n`` of all), plus
        the pump's prefetch ``depth`` history. Every future perf PR should
        look here first to see where epoch time goes."""
        snap = self._pipeline_stats.snapshot()
        if self._ckpt_plane is not None:
            # checkpoint-plane counters (bytes written, dedup ratio, save
            # stall vs hidden write time) ride along like the compile ones
            snap["ckpt"] = self._ckpt_plane.stats.snapshot()
        if self.engine.compile_cache is not None:
            # compile-plane counters ride along: compiles vs cache hits and
            # (estimated) compile seconds saved, cumulative for the cache
            # this engine compiles through (shared process-wide by default)
            snap["compile"] = self.engine.compile_cache.stats.snapshot()
        shard = self.engine.sharding_snapshot()
        if shard is not None:
            # sharding-plane accounting (mesh axes, fsdp buckets/gather
            # bytes, per-device state bytes) — absent when the plane is
            # off so existing consumers see no new key
            snap["sharding"] = shard
        from ...resilience.stats import resilience_snapshot
        res = resilience_snapshot()
        if res:
            # resilience-plane counters (process-wide: faults fired,
            # watchdog trips, supervisor restarts, retries) — omitted on
            # healthy runs so existing consumers see no new key
            snap["resilience"] = res
        if reset:
            self._pipeline_stats.reset()
        return snap

    # --- gradient clipping (reference: orca/learn/tf/estimator.py
    # set_constant_gradient_clipping / set_l2_norm_gradient_clipping,
    # Estimator.scala:68-141) ------------------------------------------------
    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float):
        self.engine.set_gradient_clipping(min_value=min_value,
                                          max_value=max_value)
        return self

    def set_l2_norm_gradient_clipping(self, clip_norm: float):
        self.engine.set_gradient_clipping(norm=clip_norm)
        return self

    def clear_gradient_clipping(self):
        self.engine.clear_gradient_clipping()
        return self

    # --- tensorboard (reference: orca/learn/tf/estimator.py:167-220,
    # pipeline/estimator/Estimator.scala:116-122) ----------------------------
    def set_tensorboard(self, log_dir: str, app_name: str):
        from ...utils.tensorboard import FileWriter
        self._tb_dir = os.path.join(log_dir, app_name)
        self._tb_train = FileWriter(os.path.join(self._tb_dir, "train"))
        self._tb_val = FileWriter(os.path.join(self._tb_dir, "validation"))
        return self

    def get_train_summary(self, tag: str = "Loss"):
        from ...utils.tensorboard import read_scalars
        if self._tb_train is None:
            return []
        self._tb_train.flush()
        scalars = read_scalars(os.path.join(self._tb_dir, "train"))
        return scalars.get(tag, [])

    def get_validation_summary(self, tag: str):
        from ...utils.tensorboard import read_scalars
        if self._tb_val is None:
            return []
        self._tb_val.flush()
        scalars = read_scalars(os.path.join(self._tb_dir, "validation"))
        return scalars.get(tag, [])

    # --- fit ----------------------------------------------------------------
    def fit(self, data, epochs: int = 1, batch_size: int = 32,
            feature_cols=None, label_cols=None,
            validation_data=None, session_config=None,
            checkpoint_trigger: Optional[Trigger] = None,
            steps_per_epoch: Optional[int] = None,
            shuffle: bool = True, verbose: bool = True,
            callbacks=None, profile=False,
            max_failure_retries: Optional[int] = None,
            initial_epoch: int = 0
            ) -> List[Dict[str, float]]:
        """Train. Accepts dict-of-ndarray {'x','y'}, (x, y) tuples, XShards
        (dict or pandas shards + feature/label cols), or a data_creator
        callable — same surface as the reference estimators' fit
        (orca/learn/tf2/estimator.py:166-263).

        ``profile`` — True collects per-step data-wait / step-execution
        timings into the epoch stats (the Ray torch runner's ``profile=True``,
        reference torch_runner.py:360); a directory path additionally wraps
        the call up to the end of its first epoch in a ``jax.profiler``
        trace, which holds the program's spans as ``zoo:<name>`` annotations
        beside the device's ops (docs/observability.md). The session starts
        before ``fit.prepare``, and on a TPU host it slows every
        host-to-device transfer (the runtime logs each chunk it re-tiles):
        an estimator's first call, whose prepare puts one sample batch for
        ``engine.build``, spent ~2 s there in such a capture against 0.13 s
        outside one (256 uint8 ImageNet images, v5e; PERF.md, PR 33; a
        call on a built engine takes no sample), so read host times from
        ``ZOO_TRACE=1`` and device times from the capture.

        ``max_failure_retries`` — when ``model_dir`` is set, a failing
        training step is retried from the latest checkpoint up to this many
        times (default 5), matching the reference's retry-from-snapshot loop
        in InternalDistriOptimizer (Topology.scala:1256-1337).

        ``initial_epoch`` — offset for the shuffle-seed epoch counter, for
        callers that split one logical training run across several fit()
        calls (the AutoML scheduler's pause/resume): with it, epoch i of a
        resumed run draws the same shuffle order as epoch i of an
        uninterrupted one, keeping segmented training bit-equivalent."""
        if isinstance(profile, str):
            # before the root span: a span is live, and mirrored into the
            # trace, only if the session collects when it opens
            jax.profiler.start_trace(profile)
            self._profile_open = True
        step0 = self._trainer_state.iteration
        try:
            # root span of the training trace (obs plane): everything the
            # call does; prepare, epoch, dispatch, infeed-lane and
            # ckpt-writer spans all chain under this trace id
            with _trace.span("fit", epochs=epochs,
                             initial_epoch=initial_epoch) as root:
                try:
                    return self._fit(
                        data, epochs, batch_size, feature_cols, label_cols,
                        validation_data, checkpoint_trigger, steps_per_epoch,
                        shuffle, verbose, profile, max_failure_retries,
                        initial_epoch)
                finally:
                    root.set(steps=self._trainer_state.iteration - step0)
        finally:
            self._stop_profile()

    def _stop_profile(self):
        """End ``fit(profile=<dir>)``'s profiler session, once: after the
        call's first epoch, or when the call ends before that."""
        if self._profile_open:
            self._profile_open = False
            jax.profiler.stop_trace()

    def _fit(self, data, epochs, batch_size, feature_cols, label_cols,
             validation_data, checkpoint_trigger, steps_per_epoch, shuffle,
             verbose, profile, max_failure_retries, initial_epoch):
        with _trace.span("fit.prepare"):
            it, checkpoint_trigger, can_recover, retries_left, fuse = \
                self._fit_prepare(data, batch_size, feature_cols, label_cols,
                                  checkpoint_trigger, steps_per_epoch,
                                  shuffle, max_failure_retries,
                                  initial_epoch)
        import contextlib

        from .preemption import PreemptionWatcher

        epoch_stats = []
        watcher = PreemptionWatcher() if can_recover else None
        try:
            with (watcher if watcher is not None
                  else contextlib.nullcontext()):
                return self._fit_loop(it, epochs, steps_per_epoch,
                                      batch_size, feature_cols,
                                      label_cols, validation_data,
                                      checkpoint_trigger, profile,
                                      verbose, can_recover,
                                      retries_left, epoch_stats,
                                      watcher, fuse)
        finally:
            # returning from fit() means every queued checkpoint is
            # durable — resumers (AutoML pause/resume, a supervisor
            # restart) read the dir right after. A failed async write
            # gets one blocking retry; past that, log-and-continue (an
            # exception here would mask the loop's own)
            if not self.flush_checkpoints() and self.model_dir is not None:
                try:
                    self.save_checkpoint(self.model_dir, blocking=True)
                except Exception as save_err:       # noqa: BLE001
                    logger.error(
                        "final checkpoint could not be written (%s); the "
                        "newest restore point predates this fit's last "
                        "trigger", save_err)

    def _fit_prepare(self, data, batch_size, feature_cols, label_cols,
                     checkpoint_trigger, steps_per_epoch, shuffle,
                     max_failure_retries, initial_epoch):
        """What a ``fit`` call does before its first epoch: the iterator, on
        an unbuilt engine one sample for ``engine.build``, checkpoint
        arming, the fuse factor."""
        it = learn_utils.data_to_iterator(
            data, batch_size, self.mesh, feature_cols, label_cols,
            shuffle=shuffle, config=self.config,
            stats=self._pipeline_stats)
        # BatchIterator counts shuffle epochs in `_epoch`; duck-typed
        # pipelines (e.g. ImageNetPipeline) use `_epoch_idx`
        counter = next((c for c in ("_epoch", "_epoch_idx")
                        if hasattr(it, c)), None)
        if initial_epoch:
            # A silent no-op here would break the pause/resume
            # bit-equivalence the parameter exists for, so warn when
            # neither counter exists.
            if counter is not None:
                setattr(it, counter, int(initial_epoch))
            else:
                logger.warning(
                    "fit(initial_epoch=%d): iterator %s has no epoch "
                    "counter to re-align; resumed epochs will not replay "
                    "the uninterrupted run's shuffle order",
                    initial_epoch, type(it).__name__)
        if self.engine.params is None:
            self._build_engine(next(it.epoch(shuffle=False, prefetch=False)))
        elif counter is not None:
            # a built engine needs no sample: nothing is assembled, put or
            # fetched while the device sits empty. The sample's epoch()
            # consumed one shuffle seed; keep every call's epochs on the
            # seeds they had, so that segmented (initial_epoch) and
            # uninterrupted runs stay bit-equivalent
            setattr(it, counter, getattr(it, counter) + 1)
        checkpoint_trigger = (Trigger.convert_trigger(checkpoint_trigger)
                              if checkpoint_trigger else None)
        if checkpoint_trigger is not None:
            # sync interval marks to the starting iteration (composites
            # forward to children) so resumed runs fire on boundaries
            checkpoint_trigger.arm(self._trainer_state)
        # recovery is opted into by checkpointing (a trigger) or an explicit
        # retry count; a bare model_dir (often set just to control save()
        # paths) must not start writing ckpt-* directories on its own
        opted_in = (checkpoint_trigger is not None
                    or max_failure_retries is not None
                    or "max_failure_retries" in self.config)
        retries_left = (self.config.get("max_failure_retries", 5)
                        if max_failure_retries is None
                        else max_failure_retries)
        can_recover = (self.model_dir is not None and retries_left > 0
                       and opted_in)
        if can_recover and \
                learn_utils.find_latest_checkpoint(self.model_dir)[0] is None:
            # guarantee a restore point exists before the first step
            self.save_checkpoint(self.model_dir)
        try:
            fuse = self._choose_fuse(it, steps_per_epoch, checkpoint_trigger)
        except (KeyboardInterrupt, SystemExit):
            raise
        except (ValueError, TypeError):
            raise           # config/validation errors must surface
        except Exception as e:
            # the auto-probe dispatches real (rolled-back) train steps
            # before _fit_loop's retry handler exists; a chip failure there
            # must not crash a recoverable fit. The probe's finally already
            # restored the state snapshot — just train unfused.
            if not can_recover:
                raise
            logger.warning("fuse probe failed (%s: %s); training unfused",
                           type(e).__name__, e)
            fuse = 1
        return it, checkpoint_trigger, can_recover, retries_left, fuse

    def _build_engine(self, sample):
        """Build an unbuilt engine from the one row of a sample batch that
        ``engine.build`` reads, cut on the device: the batch itself is not
        fetched back."""
        if self.engine.params is None:
            self.engine.build(tuple(np.asarray(a[:1]) for a in sample.x))

    def _choose_fuse(self, it, steps_per_epoch, trigger=None) -> int:
        """Pick the scan-fusion factor for this fit. Small-model steps are
        dominated by per-dispatch host latency (VERDICT r4: fraud MLP ran at
        14% of the chip's compute rate through the per-batch loop); fusing k
        steps into one jitted lax.scan amortizes it. ``auto`` (default) times
        the pipelined dispatch loop and sizes k so a fused group runs
        ~0.25-0.5 s (``auto_fuse_factor`` target, pow2-rounded);
        big-model steps (≥10 ms) stay unfused. Set config
        ``steps_per_dispatch`` to an int to pin, or 1 to disable."""
        if not getattr(it, "supports_fused", False) or \
                steps_per_epoch is not None:
            # custom iterators (streaming pipelines) and explicit
            # steps_per_epoch keep the exact per-step loop
            return 1
        cfg = self._fuse_cfg()
        batch_bytes = self._iter_batch_bytes(it)
        if cfg != "auto":
            k = cfg
        elif it.steps_per_epoch < 2:
            return 1
        else:
            # cache per input signature, like the eval probe: repeated
            # fits on one estimator (hyperparameter loops, warm restarts)
            # must not re-pay the probe's dispatches + state snapshot
            key = ("train", it.local_bs) + tuple(
                (np.asarray(a[:1]).shape[1:], str(np.asarray(a[:1]).dtype))
                for a in tuple(it.x) + tuple(it.y or ()))
            k = self._fuse_probe_cache.get(key)
            if k is None:
                with _trace.stage("fit.fuse_probe", mode="train"):
                    k = self._auto_probe_fuse(it, batch_bytes,
                                              probe_key=key)
                self._fuse_probe_cache[key] = k
        return self._apply_fuse_caps(k, batch_bytes, it.steps_per_epoch,
                                     trigger)

    def _fuse_cfg(self):
        """steps_per_dispatch config, parsed once for fit and evaluate:
        "auto" (default) or a pinned positive int (1 disables fusion)."""
        cfg = self.config.get("steps_per_dispatch", "auto")
        if cfg == "auto":
            return "auto"
        return max(1, int(cfg)) if cfg else 1

    @staticmethod
    def _iter_batch_bytes(it) -> int:
        row_bytes = sum(int(np.asarray(a[:1]).nbytes)
                        for a in tuple(it.x) + tuple(it.y or ()))
        return row_bytes * it.local_bs

    @staticmethod
    def _apply_fuse_caps(k, batch_bytes, steps, trigger=None) -> int:
        """Caps shared by the pinned and auto paths, for both train and
        eval fusion: superbatch memory, checkpoint cadence, epoch length."""
        if batch_bytes > 0:
            byte_cap = max(learn_utils.MAX_GROUP_BYTES // batch_bytes, 1)
            if k > byte_cap:
                logger.warning(
                    "steps_per_dispatch %d capped to %d so a stacked "
                    "superbatch stays under %dMB", k, byte_cap,
                    learn_utils.MAX_GROUP_BYTES >> 20)
                k = byte_cap
        # keep checkpoint cadence exact: never fuse past the trigger's
        # interval (composite triggers report their tightest child cap)
        cap = trigger.fuse_cap() if trigger is not None else None
        if cap:
            k = min(k, cap)
        return max(1, min(k, steps))

    def _probe_aux_key(self, step_key: Optional[str], probe_key
                       ) -> Optional[str]:
        """Disk key for a persisted fuse-probe result: the engine step's
        structural executable key (compile-plane fingerprint — model tree,
        avals, mesh, optimizer structure) + the probe's input signature."""
        if step_key is None or probe_key is None:
            return None
        return step_key + "/" + repr(probe_key)

    def _auto_probe_fuse(self, it, batch_bytes: int, probe_key=None) -> int:
        """Time the pipelined dispatch loop with REAL train steps, then roll
        the engine state back to the snapshot — the probe leaves the
        optimizer trajectory exactly as if it never ran, so auto-fused and
        pinned runs train identically. Gated first on the analytic
        compute estimate (cheap: the AOT lowering shares the jit executable
        cache), so compute-dominated models skip both the probe and the
        snapshot copy of params+opt_state. Results persist into the compile
        plane's aux store, so a warm restart skips the probe dispatches
        entirely, not just the compile."""
        import jax
        import jax.numpy as jnp
        eng = self.engine
        cache = eng.compile_cache
        # the probe's throwaway epoch() must not advance the iterator's
        # shuffle-seed counter, or auto runs would see different data orders
        # than pinned runs — restore it on EVERY exit path
        epoch_counter = getattr(it, "_epoch", None)
        gen = it.epoch(shuffle=False, prefetch=False)
        snap = None
        aux_key = None
        try:
            b0 = next(gen)
            if cache is not None:
                aux_key = self._probe_aux_key(
                    eng.train_step_cache_key(b0), probe_key)
                if aux_key is not None:
                    stored = cache.get_aux("fuse", aux_key)
                    if stored is not None:
                        return int(stored)
            compute_s = learn_utils.estimate_step_compute_s(
                eng.ensure_jit_train(), eng.train_step_args(b0),
                list(self.mesh.devices.flat))
            if compute_s is not None and compute_s >= 0.01:
                # compute-dominated: nothing worth amortizing
                if cache is not None and aux_key is not None:
                    cache.put_aux("fuse", aux_key, 1)
                return 1
            m = max(2, min(6, it.steps_per_epoch - 1,
                           int((64 << 20) // max(batch_bytes, 1)) or 2))
            probe = [b0]
            for _ in range(m):
                b = next(gen, None)
                if b is None:
                    break
                probe.append(b)       # device_put happens here, untimed
            snap = eng.snapshot()
            jax.block_until_ready(eng.train_batch(b0))   # compile + warm
            dt = float("inf")
            for _ in range(2):      # min-of-2 washes out contention spikes
                t0 = time.perf_counter()
                for i in range(m):
                    loss = eng.train_batch(probe[i % len(probe)])
                jax.block_until_ready(loss)
                dt = min(dt, (time.perf_counter() - t0) / m)
        finally:
            if snap is not None:
                eng.restore_snapshot(snap)
            gen.close()
            if epoch_counter is not None:
                it._epoch = epoch_counter
        k = learn_utils.auto_fuse_factor(dt, it.steps_per_epoch,
                                         batch_bytes=batch_bytes,
                                         compute_s=compute_s)
        if k > 1:
            logger.info("fusing %d train steps per dispatch "
                        "(pipelined probe %.2f ms/step)", k, dt * 1e3)
        if cache is not None and aux_key is not None:
            cache.put_aux("fuse", aux_key, int(k))
        return k

    def _fit_loop(self, it, epochs, steps_per_epoch, batch_size,
                  feature_cols, label_cols, validation_data,
                  checkpoint_trigger, profile, verbose, can_recover,
                  retries_left, epoch_stats, watcher, fuse=1):
        ep = 0
        # epoch `ep`'s open iterator and first batch, where the epoch before
        # it opened them ahead of its own sync (`_fit_epoch`)
        ahead = None
        try:
            while ep < epochs:
                opened, ahead = ahead, None
                try:
                    with _trace.span("epoch", epoch=ep):
                        stats, ahead = self._fit_epoch(
                            it, ep, steps_per_epoch, checkpoint_trigger,
                            profile, watcher, fuse, opened,
                            more=ep + 1 < epochs)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    if not can_recover or retries_left <= 0:
                        raise
                    retries_left -= 1
                    # load_checkpoint flushes pending async writes first and
                    # returns the path it ACTUALLY restored (logging a scanner
                    # guess here could name a different dir than the one the
                    # plane's fallback logic lands on)
                    path = self.load_checkpoint(self.model_dir)
                    logger.warning(
                        "training failed at epoch %d (%s: %s); restored "
                        "checkpoint %s, retrying (%d retries left)",
                        ep + 1, type(e).__name__, e, path, retries_left)
                    self._trainer_state.iteration = self.engine.step
                    continue                 # re-run the failed epoch
                finally:
                    # fit(profile=<dir>) traces up to the end of the call's
                    # first epoch, its epoch-end sync included
                    self._stop_profile()
                if watcher is not None and watcher.triggered:
                    # preemption notice (SIGTERM on spot/preemptible TPU VMs):
                    # checkpoint IMMEDIATELY — the grace window is short, and
                    # validation/logging must not stand between the notice and
                    # the restore point. The epoch is partial; flag it so
                    # consumers don't read its stats as a full epoch. Pending
                    # async writes are flushed too: the host may die right
                    # after the grace window, so queued != durable is not
                    # acceptable here.
                    self.save_checkpoint(self.model_dir)
                    if not self.flush_checkpoints():
                        # the async write failed (disk full?): one blocking
                        # retry — a stale restore point on preemption loses a
                        # whole trigger interval of work
                        try:
                            self.save_checkpoint(self.model_dir, blocking=True)
                        except Exception as save_err:   # noqa: BLE001
                            logger.error(
                                "preemption checkpoint could not be written "
                                "(%s); resume will use the previous restore "
                                "point", save_err)
                    stats["preempted"] = True
                    stats["partial_epoch"] = True
                    epoch_stats.append(stats)
                    logger.warning(
                        "stopping after a preemption notice "
                        "(checkpointed at step %d)", self.engine.step)
                    break
                if validation_data is not None:
                    val = self.evaluate(validation_data, batch_size=batch_size,
                                        feature_cols=feature_cols,
                                        label_cols=label_cols, verbose=False)
                    stats.update({f"val_{k}": v for k, v in val.items()})
                    self._trainer_state.score = val.get(
                        next(iter(self.metrics), "loss"), val.get("loss"))
                    if self._tb_val is not None:
                        for k, v in val.items():
                            if isinstance(v, (int, float)):
                                self._tb_val.add_scalar(
                                    k, float(v), self._trainer_state.iteration)
                if checkpoint_trigger and self.model_dir and \
                        checkpoint_trigger(self._trainer_state):
                    self.save_checkpoint(self.model_dir)
                if verbose:
                    logger.info("epoch %d: %s", ep + 1, stats)
                epoch_stats.append(stats)
                ep += 1
        finally:
            # preemption, or validation raised: that epoch never runs
            if ahead is not None:
                _close(ahead[0])
        self.train_stats.extend(epoch_stats)
        return epoch_stats

    @staticmethod
    def _open_epoch(it, fuse: int):
        """An epoch's open iterator and its first batch (None: it has
        none)."""
        gen = iter(it.epoch(fuse=fuse) if fuse > 1 else it.epoch())
        return gen, next(gen, None)

    def _fit_epoch(self, it, ep: int, steps_per_epoch: Optional[int],
                   checkpoint_trigger, profile, watcher=None, fuse: int = 1,
                   opened=None, more: bool = False):
        """One epoch of the hot loop; raises through to fit()'s retry.
        Returns the epoch's stats and the next epoch's ``_open_epoch``, or
        None where it opened none.

        With ``fuse`` > 1 the iterator yields stacked superbatches and each
        dispatch runs ``fuse`` optimizer steps inside one jitted lax.scan
        (``TrainEngine.train_batch_group``) — numerically identical to the
        per-step loop, but host dispatch latency is amortized k-fold.
        Checkpoint triggers and preemption are checked between dispatches
        (≤ ~0.5 s apart by construction of the auto fuse factor).

        ``opened`` is this epoch's ``_open_epoch`` where the epoch before
        made it. With ``more`` (another epoch of this call follows) the next
        epoch's is made after the last dispatch and BEFORE the epoch-end
        sync: a pump takes an assembly and a transfer to its first batch,
        and here they pass while the device works off its queue instead of
        after the sync has emptied it. This epoch's iterator is closed
        first (never two pumps alive); a call's last epoch opens nothing,
        and an epoch that fails after opening closes what it opened."""
        t0 = time.time()
        losses = []                # device scalars (fuse=1) or (k,) arrays
        tb_steps = []
        nsteps = steps_per_epoch or it.steps_per_epoch
        prof = {"data_s": 0.0, "step_s": 0.0} if profile else None
        steps_done = 0
        td = time.perf_counter()
        gen, first = opened or self._open_epoch(it, fuse)
        if prof is not None:
            prof["data_s"] += time.perf_counter() - td
        batches = itertools.chain((first,), gen)
        try:
            while fuse > 1 or steps_done < nsteps:
                if prof is not None:
                    td = time.perf_counter()
                batch = next(batches, None)
                if batch is None:
                    break
                if prof is not None:
                    ts = time.perf_counter()
                    prof["data_s"] += ts - td
                if getattr(batch, "fused", 1) > 1:
                    loss = self.engine.train_batch_group(batch)
                    took = batch.fused
                else:
                    loss = self.engine.train_batch(batch)
                    took = 1
                steps_done += took
                if prof is not None:
                    jax.block_until_ready(loss)
                    prof["step_s"] += time.perf_counter() - ts
                losses.append(loss)
                self._trainer_state.iteration += took
                if self._tb_train is not None:
                    # keep the device array; flush with ONE device_get at
                    # epoch end so logging never blocks async dispatch
                    tb_steps.extend(
                        range(self._trainer_state.iteration - took + 1,
                              self._trainer_state.iteration + 1))
                if checkpoint_trigger and self.model_dir:
                    self._trainer_state.epoch_finished = False
                    if checkpoint_trigger(self._trainer_state):
                        self.save_checkpoint(self.model_dir)
                if watcher is not None and watcher.triggered:
                    break        # preemption: end the epoch at this step
        finally:
            # joins the pump's producer, also where `steps_per_epoch` or a
            # failing step left the epoch's iterator unfinished
            _close(gen)
        ahead = None
        if more and not (watcher is not None and watcher.triggered):
            t_open = time.perf_counter()
            with _trace.span("epoch.open_ahead", epoch=ep + 1):
                ahead = self._open_epoch(it, fuse)
            self._pipeline_stats.add("open_ahead",
                                     time.perf_counter() - t_open)
        try:
            # the epoch-end sync is where a wedged device actually blocks on
            # real TPUs (dispatch is async) — bound it like the dispatches
            from ...resilience.watchdog import watched
            with _trace.span("epoch.sync"):
                host_losses = watched("engine.sync", jax.device_get, losses)
            if host_losses:
                host_losses = np.concatenate(
                    [np.atleast_1d(np.asarray(l)) for l in host_losses])
            if self._tb_train is not None:
                for step, lv in zip(tb_steps, host_losses):
                    self._tb_train.add_scalar("Loss", float(lv), step)
                self._tb_train.flush()
            mean_loss = float(np.mean(host_losses))
            self._trainer_state.epoch += 1
            self._trainer_state.epoch_finished = True
            self._trainer_state.loss = mean_loss
            dt = time.time() - t0
            stats = {"epoch": ep + 1, "train_loss": mean_loss,
                     "num_samples": len(it.x[0]) if hasattr(it, "x") else None,
                     "time_s": round(dt, 3)}
            if prof is not None:
                n = max(len(host_losses), 1)
                stats["profile"] = {
                    "mean_data_s": prof["data_s"] / n,
                    "mean_step_s": prof["step_s"] / n,
                    "steps": len(host_losses)}
            return stats, ahead
        except BaseException:
            if ahead is not None:
                _close(ahead[0])
            raise

    # --- evaluate -----------------------------------------------------------
    def evaluate(self, data, batch_size: int = 32, feature_cols=None,
                 label_cols=None, num_steps: Optional[int] = None,
                 verbose: bool = True) -> Dict[str, float]:
        """(reference surface: orca/learn/tf2/estimator.py:264-347)"""
        it = learn_utils.data_to_iterator(
            data, batch_size, self.mesh, feature_cols, label_cols,
            shuffle=False, config=self.config,
            stats=self._pipeline_stats)
        sample = next(it.epoch(shuffle=False, prefetch=False))
        self._build_engine(sample)
        fuse = self._choose_eval_fuse(it, sample, num_steps)
        states = self.engine.init_metric_states()
        # accumulate device scalars; ONE device_get at the end so eval keeps
        # async dispatch going (fit() already works this way)
        losses, counts = [], []
        for i, batch in enumerate(
                it.epoch(shuffle=False, fuse=fuse) if fuse > 1
                else it.epoch(shuffle=False)):
            if num_steps is not None and i >= num_steps:
                break
            if getattr(batch, "fused", 1) > 1:
                states, batch_loss, n = self.engine.eval_batch_group(
                    states, batch)
            else:
                states, batch_loss, n = self.engine.eval_batch(states, batch)
            losses.append(batch_loss)
            counts.append(n)
        from ...resilience.watchdog import watched
        host_losses, host_counts = watched("engine.sync", jax.device_get,
                                           (losses, counts))
        loss_sum = float(np.sum(host_losses))
        count = float(np.sum(host_counts))
        result = self.engine.finalize_metrics(states, loss_sum, count)
        if verbose:
            logger.info("validation: %s", result)
        return result

    def _choose_eval_fuse(self, it, sample, num_steps) -> int:
        """Fuse factor for evaluate(): eval is stateless apart from metric
        accumulators, so fusing is always semantics-preserving — the probe
        times real eval dispatches (chaining the donated metric states) and
        discards the probe states. The probed k is cached per input
        signature: fit(validation_data=...) evaluates every epoch and the
        answer cannot change for the same model/shapes. ``num_steps`` pins
        the per-step loop so explicit step counts stay exact."""
        if not getattr(it, "supports_fused", False) or num_steps is not None \
                or it.steps_per_epoch < 2:
            return 1
        cfg = self._fuse_cfg()
        batch_bytes = self._iter_batch_bytes(it)
        if cfg != "auto":
            k = cfg
        else:
            key = ("eval", it.local_bs) + tuple(
                (np.asarray(a[:1]).shape[1:], str(np.asarray(a[:1]).dtype))
                for a in tuple(it.x) + tuple(it.y or ()))
            k = self._fuse_probe_cache.get(key)
            if k is None:
                with _trace.stage("fit.fuse_probe", mode="eval"):
                    k = self._auto_probe_eval_fuse(it, sample, batch_bytes,
                                                   probe_key=key)
                self._fuse_probe_cache[key] = k
        return self._apply_fuse_caps(k, batch_bytes, it.steps_per_epoch)

    def _auto_probe_eval_fuse(self, it, sample, batch_bytes: int,
                              probe_key=None) -> int:
        import jax
        eng = self.engine
        cache = eng.compile_cache
        states = eng.init_metric_states()
        aux_key = None
        if cache is not None:
            aux_key = self._probe_aux_key(
                eng.eval_step_cache_key(states, sample), probe_key)
            if aux_key is not None:
                stored = cache.get_aux("fuse", aux_key)
                if stored is not None:
                    return int(stored)
        states, loss, _ = eng.eval_batch(states, sample)   # compile
        jax.block_until_ready(loss)
        compute_s = learn_utils.estimate_step_compute_s(
            eng._jit_eval,
            (eng.params, eng.extra_vars, states, sample.x, sample.y,
             sample.w),
            list(self.mesh.devices.flat))
        if compute_s is not None and compute_s >= 0.01:
            if cache is not None and aux_key is not None:
                cache.put_aux("fuse", aux_key, 1)
            return 1
        dt = float("inf")
        m = 6
        for _ in range(2):          # min-of-2 washes out contention spikes
            t0 = time.perf_counter()
            for _ in range(m):
                states, loss, _ = eng.eval_batch(states, sample)
            jax.block_until_ready(loss)
            dt = min(dt, (time.perf_counter() - t0) / m)
        k = learn_utils.auto_fuse_factor(dt, it.steps_per_epoch,
                                         batch_bytes=batch_bytes,
                                         compute_s=compute_s)
        if cache is not None and aux_key is not None:
            cache.put_aux("fuse", aux_key, int(k))
        return k

    # --- predict ------------------------------------------------------------
    def predict(self, data, batch_size: int = 32, feature_cols=None,
                ) -> Any:
        """Returns XShards with a 'prediction' key for XShards input
        (reference: orca/learn/tf2/estimator.py:348-405), or an ndarray for
        array input."""
        is_shards = isinstance(data, HostXShards)
        shards = learn_utils.xshards_from_arrays(data, feature_cols, None)
        chunked = learn_utils.chunk_shards(shards)
        it = learn_utils.BatchIterator(chunked, batch_size, self.mesh,
                                       pad_tail=True,
                                       stats=self._pipeline_stats)
        self.engine.build(tuple(np.asarray(a[:1]) for a in chunked["x"]))
        # dispatch ahead, fetch in CHUNKS: per-batch device_get would
        # serialize each dispatch behind a host round trip, but holding
        # every batch's outputs on device until one final fetch would make
        # predict's HBM footprint proportional to the dataset — chunked
        # fetches keep async dispatch flowing with bounded residency
        fetched = []
        pending, pending_bytes = [], 0
        for batch in it.epoch(shuffle=False):
            preds = self.engine.predict_batch(batch.x)
            pending.append((preds, batch.w))
            pending_bytes += sum(getattr(l, "nbytes", 0)
                                 for l in jax.tree_util.tree_leaves(preds))
            if pending_bytes >= (256 << 20):
                fetched.extend(jax.device_get(pending))
                pending, pending_bytes = [], 0
        fetched.extend(jax.device_get(pending))
        outs = []
        for pred_np, w in fetched:
            if w is None:                       # full batch, no padding
                outs.append(tuple(np.asarray(p) for p in pred_np)
                            if isinstance(pred_np, (list, tuple))
                            else np.asarray(pred_np))
                continue
            mask = np.asarray(w) > 0
            if isinstance(pred_np, (list, tuple)):
                outs.append(tuple(np.asarray(p)[mask] for p in pred_np))
            else:
                outs.append(np.asarray(pred_np)[mask])
        if isinstance(outs[0], tuple):
            result = tuple(np.concatenate([o[i] for o in outs])
                           for i in range(len(outs[0])))
        else:
            result = np.concatenate(outs)
        if not is_shards:
            return result
        # re-partition predictions to match input shard row counts
        sizes = [len(  # rows per original partition
            learn_utils.nest.flatten(p)[0]) for p in shards.collect()]
        pred_parts, off = [], 0
        for s in sizes:
            if isinstance(result, tuple):
                pred_parts.append(tuple(r[off:off + s] for r in result))
            else:
                pred_parts.append(result[off:off + s])
            off += s
        return learn_utils.update_predict_xshards(
            data if isinstance(data, HostXShards) else shards,
            HostXShards(pred_parts))

    # --- persistence --------------------------------------------------------
    def get_model(self):
        return {"params": jax.device_get(self.engine.params),
                **jax.device_get(self.engine.extra_vars or {})}

    def save(self, path: str):
        """Pickle full weights (the reference TF2 estimator pickles weights
        too, tf2/estimator.py:406-420)."""
        state = self.engine.get_state()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "wb") as f:
            pickle.dump(state, f)
        return path

    def load(self, path: str):
        with open(path, "rb") as f:
            state = pickle.load(f)
        if self.engine.params is None:
            # params arrive fully formed; engine can adopt without build
            self.engine.params = state["params"]
        self.engine.set_state(state)
        return self

    def save_checkpoint(self, model_dir: str, blocking: bool = False,
                        meta: Optional[Dict] = None):
        """Checkpoint through the plane (analytics_zoo_tpu.ckpt): per-leaf
        content-addressed blobs + manifest, committed atomically. By
        default the write drains on the plane's writer thread — the loop
        pays only the device→host snapshot; ``blocking=True`` (or config
        ``ckpt_async: False``) waits for the committed write. ``meta``
        rides the manifest (the training supervisor records its epoch
        boundary there)."""
        plane = self._ckpt(model_dir)
        shard_meta = self.engine.sharding_manifest_meta()
        if shard_meta is not None:
            # record the writing run's layout in the manifest: provenance,
            # not a format switch — params and moments are stored in
            # canonical tree form regardless
            meta = {**(meta or {}), "sharding": shard_meta}
        path = plane.save(self.engine.get_state(), self.engine.step,
                          score=self._trainer_state.score,
                          meta=meta, blocking=blocking)
        logger.info("checkpoint %s: %s",
                    "saved" if blocking else "queued", path)
        return path

    def load_checkpoint(self, model_dir: str, step: Optional[int] = None):
        """Restore the newest *committed* checkpoint (or exactly ``step``):
        pending async writes are flushed first, uncommitted/corrupt dirs
        are skipped with fallback to the previous good one, and legacy
        ``state.pkl`` checkpoints load unchanged."""
        plane = self._ckpt(model_dir)
        try:
            path, state = plane.restore(step=step)
        except FileNotFoundError:
            raise FileNotFoundError(f"no checkpoint under {model_dir}")
        if self.engine.params is None:
            self.engine.params = state["params"]
        self.engine.set_state(state)
        return path

    def shutdown(self):
        if self._ckpt_plane is not None:
            self._ckpt_plane.flush()
            self._ckpt_plane.close()
            self._ckpt_plane = None
