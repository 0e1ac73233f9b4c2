"""Input-pipeline utilities: convert user data (dict-of-ndarray, XShards,
pandas shards, creator functions) into padded, mesh-sharded device batches.

Replaces the reference's per-backend data plumbing: arrays2dict/
dataframe_to_xshards (pyzoo/zoo/orca/learn/utils.py:191-311), TFDataset
per-core batching (pyzoo/zoo/tfpark/tf_dataset.py:117-160), and the Ray
LocalStore shuttle (pyzoo/zoo/orca/data/ray_xshards.py:67-94). TPU rule: the
global batch is sharded on the mesh's data axes; ragged tails are padded and
masked with a per-example weight so no record is dropped and no shape is
dynamic (SURVEY.md §7 hard-part #2).
"""

from __future__ import annotations

import logging
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...native import transfer as xfer
from ...native.infeed import _MAX_DEPTH, PipelineStats, _default_workers
from ...utils import nest
from ..data.chunked import ChunkedArray, as_chunked
from ..data.shard import HostXShards

logger = logging.getLogger("analytics_zoo_tpu")


@dataclass
class Batch:
    """One global batch: tuples of feature/label arrays plus a mask weight."""
    x: Tuple[np.ndarray, ...]
    y: Optional[Tuple[np.ndarray, ...]]
    # (batch,) 1.0 for real rows, 0.0 for padding; None == all ones (the
    # jitted step synthesizes them on device — no transfer for full batches)
    w: Optional[np.ndarray]
    # >1: arrays are stacked (fused, batch, ...) superbatches for the
    # engine's scan-fused multi-step path (train_batch_group)
    fused: int = 1


def _as_tuple(v) -> Tuple:
    if v is None:
        return ()
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,)


def xshards_from_arrays(data: Any, feature_cols=None, label_cols=None,
                        num_shards: Optional[int] = None) -> HostXShards:
    """Normalize any supported input into XShards of {'x': tuple, 'y': tuple}."""
    if isinstance(data, HostXShards):
        return normalize_xshards(data, feature_cols, label_cols)
    try:
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            data = HostXShards([data])
            return normalize_xshards(data, feature_cols, label_cols)
    except ImportError:
        pass
    if isinstance(data, dict):
        x, y = data.get("x"), data.get("y")
    elif isinstance(data, tuple) and len(data) == 2:
        x, y = data
    else:
        x, y = data, None
    shard = {"x": _as_tuple(x)}
    if y is not None:
        shard["y"] = _as_tuple(y)
    n = num_shards or 1
    flat_len = len(nest.flatten(shard)[0])
    n = min(n, max(flat_len, 1))
    if n == 1:
        # single shard: keep the caller's arrays as-is — no index-copy
        return HostXShards([{k: tuple(np.asarray(a) for a in v)
                             for k, v in shard.items()}])
    return HostXShards([_slice_dict(shard, idx)
                        for idx in np.array_split(np.arange(flat_len), n)])


def _slice_dict(shard: Dict, idx: np.ndarray) -> Dict:
    out = {}
    for k, v in shard.items():
        out[k] = tuple(np.asarray(a)[idx] for a in v)
    return out


def normalize_xshards(shards: HostXShards, feature_cols=None,
                      label_cols=None) -> HostXShards:
    """Map pandas-DataFrame or raw-dict shards to {'x': tuple, 'y': tuple}
    (the reference's process_xshards_of_pandas_dataframe,
    orca/learn/utils.py:253-264)."""
    first = shards.collect()[0] if shards.num_partitions() else None

    def from_df(df):
        x = tuple(df[c].to_numpy() for c in feature_cols)
        out = {"x": x}
        if label_cols:
            out["y"] = tuple(df[c].to_numpy() for c in label_cols)
        return out

    def from_dict(d):
        if "x" in d:
            out = {"x": _as_tuple(d["x"])}
            if "y" in d and d["y"] is not None:
                out["y"] = _as_tuple(d["y"])
            return out
        # column-keyed dict shards (e.g. ParquetDataset.read_as_xshards):
        # feature_cols/label_cols select the tensors, like the reference's
        # dataframe-to-shard path
        if not feature_cols:
            raise ValueError(
                "shards are column dicts; pass feature_cols (and label_cols)"
                f" — available keys: {sorted(d.keys())}")
        out = {"x": tuple(np.asarray(d[c]) for c in feature_cols)}
        if label_cols:
            out["y"] = tuple(np.asarray(d[c]) for c in label_cols)
        return out

    try:
        import pandas as pd
        if isinstance(first, pd.DataFrame):
            if not feature_cols:
                raise ValueError(
                    "feature_cols is required for pandas-DataFrame XShards")
            return shards.transform_shard(from_df)
    except ImportError:
        pass
    if isinstance(first, dict):
        return shards.transform_shard(from_dict)
    raise ValueError(f"unsupported shard element type {type(first)}")


def concat_shards(shards: HostXShards) -> Dict[str, Tuple[np.ndarray, ...]]:
    """Merge shards into contiguous arrays — a full O(dataset) copy. Kept
    for callers that genuinely need one flat array (e.g. FeatureSet DRAM
    tiers); the training path uses :func:`chunk_shards` instead."""
    parts = shards.collect()
    if not parts:
        raise ValueError("empty XShards")
    keys = parts[0].keys()
    out = {}
    for k in keys:
        n = len(parts[0][k])
        out[k] = tuple(
            np.concatenate([np.asarray(p[k][i]) for p in parts])
            for i in range(n))
    return out


def chunk_shards(shards: HostXShards
                 ) -> Dict[str, Tuple[ChunkedArray, ...]]:
    """Zero-copy counterpart of :func:`concat_shards`: each leaf becomes a
    :class:`ChunkedArray` over the per-partition arrays. Row order is the
    partition concatenation order, so batch streams built on top are
    bit-identical to the merged path for the same seed."""
    parts = shards.collect()
    if not parts:
        raise ValueError("empty XShards")
    keys = parts[0].keys()
    out = {}
    for k in keys:
        n = len(parts[0][k])
        out[k] = tuple(
            ChunkedArray([p[k][i] for p in parts]) for i in range(n))
    return out


# Peak dense bf16 FLOP/s of ONE jax device, keyed by the ``device_kind``
# string JAX reports (chip_smoke.py's stage 0 prints it). Source: Google
# Cloud TPU documentation, the system-architecture page of each generation
# (v2 45, v3 123, v4 275, v5e 197, v5p 459, v6e 918 TFLOP/s per chip). v2/v3
# devices are TensorCores, two to a chip, so they carry half the chip's
# figure; from v4 on a device is a chip.
PEAK_BF16_FLOPS = {
    "TPU v2": 22.5e12,
    "TPU v3": 61.5e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}

# typical training MFU assumed when converting cost-analysis FLOPs to a
# compute-time estimate (shared by the fuse gate and bench.py)
ASSUMED_TRAIN_MFU = 0.3

# ceiling for one stacked (fuse, batch, ...) superbatch — bounds HBM staging
# and host gather granularity for the scan-fused dispatch path
MAX_GROUP_BYTES = 256 << 20


def peak_bf16_flops(device) -> Optional[float]:
    """Peak dense bf16 FLOP/s of a jax device. None for a CPU device (no
    meaningful peak: MFU is not reported there); a device kind missing from
    :data:`PEAK_BF16_FLOPS` is an error, never a silent 0."""
    if device.platform == "cpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak bf16 FLOP/s on record for device kind "
            f"{device.device_kind!r} (platform {device.platform!r}); add it "
            f"to orca.learn.utils.PEAK_BF16_FLOPS with its source") from None


def estimate_step_compute_s(jitted, args, devices) -> Optional[float]:
    """Analytic per-step compute-time estimate: XLA's own cost-analysis
    FLOPs for the compiled step, divided by ASSUMED_TRAIN_MFU of the
    device's peak bf16 rate (a typical training MFU). Used to decide
    whether a step is compute-dominated INDEPENDENT of wall-clock
    measurements, which conflate dispatch overhead and contention with
    compute. Returns None on the CPU (no peak) or when the backend reports
    no FLOPs; a failing compile raises — the step itself would fail too."""
    # cost_analysis reports the PER-DEVICE program (post-SPMD
    # partitioning), so the denominator is ONE device's peak, not the
    # summed mesh peak — summing under-estimated compute time by the
    # device count, mis-classifying compute-dominated models as
    # dispatch-bound, scan-fusing them and coarsening their
    # checkpoint/preemption cadence
    peak = peak_bf16_flops(devices[0])
    if peak is None:
        return None
    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float((cost or {}).get("flops", 0.0) or 0.0)
    return flops / (ASSUMED_TRAIN_MFU * peak) if flops > 0 else None


def auto_fuse_factor(step_time_s: float, steps_per_epoch: int,
                     batch_bytes: int = 0,
                     compute_s: Optional[float] = None,
                     target_s: float = 0.25, max_fuse: int = 128,
                     max_group_bytes: int = MAX_GROUP_BYTES) -> int:
    """How many train steps to fuse into one dispatch (lax.scan group).

    ``step_time_s`` is the pipelined per-step wall time of the dispatched
    train step — measure it as min-of-several runs of (m non-blocking calls
    + one fetch)/m, so contention spikes and the tail round trip wash out.
    ``compute_s`` is the analytic estimate from ``estimate_step_compute_s``;
    when available it decides the compute-dominated gate (≥10 ms → stay
    unfused: per-step triggers and infeed granularity are worth more than
    the <2% dispatch saving), so a contended chip can't masquerade as a
    big model. k is then sized so one fused group runs
    ~``target_s``: if the measured time was mostly per-call dispatch
    overhead, that overhead shrinks k-fold; if it was mostly compute, the
    group just batches ~target_s of work. Either way the host leaves the
    hot path. ``batch_bytes`` caps k so a stacked superbatch stays under
    ``max_group_bytes``.
    """
    if steps_per_epoch < 2:
        return 1
    gate = compute_s if compute_s is not None else step_time_s
    if gate >= 0.01:
        return 1
    if compute_s is not None and compute_s < step_time_s:
        # the measured step is (overhead + compute) and the analytic part
        # says compute is the small piece. Sizing k off step_time alone is
        # too timid exactly when overhead is worst; sizing off compute_s
        # alone overshoots when the model runs below the assumed MFU. The
        # geometric mean hedges both: group wall time lands within
        # sqrt(step_time/compute) of target either way.
        denom = math.sqrt(max(compute_s, 1e-6) * step_time_s)
    else:
        denom = max(step_time_s, 1e-5)
    k = int(target_s / denom)
    if k <= 1:
        return 1
    k = 1 << (k - 1).bit_length()           # round UP to a power of two
    if batch_bytes > 0:
        k = min(k, max(max_group_bytes // batch_bytes, 1))
    return max(1, min(k, max_fuse, steps_per_epoch))


class BatchIterator:
    """Epoch iterator over host-local data producing padded global batches.

    The per-host arrays are treated as this process's stripe of the global
    dataset; ``batch_size`` is the *global* batch (the reference's TFDataset
    batch semantics, tf_dataset.py:135-149), so each host contributes
    batch_size / process_count rows per step.

    Wire format: source dtypes are preserved end-to-end — uint8 pixels and
    int32 labels ship as-is (cast/normalize belongs on device, see
    ``orca/learn/prologue.py``) and wide leaves (f64/i64) are narrowed
    per batch to their canonical device form (``narrow_wire`` — the cast
    ``device_put`` would perform anyway, paid on the batch instead of as a
    resident duplicate of the dataset). On the prefetch path, batch
    gathers go into a reusable :class:`StagingPool` ring instead of fresh
    allocations (non-CPU backends; see ``native/transfer.py``).
    """

    supports_fused = True       # capability flag: epoch(fuse=k) is available

    def __init__(self, data: Dict[str, Tuple[np.ndarray, ...]],
                 batch_size: int, mesh: Mesh, shuffle: bool = False,
                 seed: int = 0, pad_tail: bool = True,
                 stats: Optional[PipelineStats] = None,
                 prefetch_depth: int = 2,
                 prefetch_workers: Optional[int] = None):
        # leaves are ChunkedArrays: per-shard chunks stay separate and
        # batches gather across chunk boundaries (zero-copy views within a
        # chunk) — the dataset is never merged into one contiguous copy
        self.x = tuple(as_chunked(a) for a in data["x"])
        self.y = (tuple(as_chunked(a) for a in data["y"])
                  if data.get("y") is not None else None)
        self.n = len(self.x[0])
        self._staging = None        # lazily-built StagingPool (or False)
        self.stats = stats if stats is not None else PipelineStats()
        self.prefetch_depth = prefetch_depth
        self.prefetch_workers = prefetch_workers
        self.mesh = mesh
        nproc = jax.process_count()
        if batch_size % (nproc or 1):
            raise ValueError(
                f"global batch_size {batch_size} must divide across "
                f"{nproc} processes")
        self.local_bs = max(batch_size // max(nproc, 1), 1)
        # The sharded leading dim must divide by the local share of the data
        # axes (the reference instead hard-errors on batch % node*core != 0,
        # tf_dataset.py:135-149; padding+masking is strictly more permissive).
        data_axis = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        local_div = max(data_axis // max(nproc, 1), 1)
        if self.local_bs % local_div:
            self.local_bs = math.ceil(self.local_bs / local_div) * local_div
        self.global_bs = self.local_bs * max(nproc, 1)
        if self.global_bs != batch_size:
            logger.warning(
                "batch_size %d is not divisible by the %d-way data axes; "
                "training with effective global batch %d",
                batch_size, data_axis, self.global_bs)
        self.shuffle = shuffle
        self.seed = seed
        self.pad_tail = pad_tail
        self.steps_per_epoch = (
            math.ceil(self.n / self.local_bs) if pad_tail
            else self.n // self.local_bs)
        if self.steps_per_epoch == 0:
            raise ValueError(
                f"dataset has {self.n} rows < local batch {self.local_bs}")
        self._epoch = 0
        self._sharding_cache: Dict[int, NamedSharding] = {}

    def _sharding(self, ndim: int, fused: bool = False) -> NamedSharding:
        key = (ndim, fused)
        if key not in self._sharding_cache:
            # fused superbatches carry a leading scan axis that must stay
            # unsharded; the batch axis (0 or 1) gets the data axes
            lead = (None,) if fused else ()
            spec = lead + (("dp", "fsdp"),) + (None,) * (ndim - len(lead) - 1)
            self._sharding_cache[key] = NamedSharding(self.mesh, P(*spec))
        return self._sharding_cache[key]

    def _device_put(self, arr: np.ndarray, fused: bool = False):
        """Place ONE array on the mesh (kept for callers staging single
        leaves; batches go through :meth:`_put_batch`)."""
        return xfer.sharded_put(arr, self._sharding(arr.ndim, fused))

    def _staging_pool(self):
        """Reusable host gather buffers for the prefetch path. Ring sized
        above the pump's WORST-CASE in-flight window — assembly workers,
        the adaptive lane ceiling, the adaptive delivery-depth ceiling
        (device_put may hold the host buffer until its async DMA
        completes), the consumer's batch, and margin — so a buffer is
        never rewritten while its batch may still be read. None when
        staging is off (CPU backend — its device_put may alias numpy
        buffers zero-copy; ``ZOO_HOST_STAGING`` overrides)."""
        if self._staging is None:
            if not xfer.staging_enabled():
                self._staging = False
            else:
                workers = self.prefetch_workers or _default_workers()
                self._staging = xfer.StagingPool(
                    ring=workers + xfer.MAX_H2D_LANES
                    + max(_MAX_DEPTH, self.prefetch_depth) + 4)
        return self._staging or None

    def _gather_leaf(self, a: ChunkedArray, idx: np.ndarray,
                     staged: bool) -> np.ndarray:
        # wide leaves bypass the ring: their narrow_wire astype allocates
        # anyway, so staging a wide intermediate would just double the
        # gathered bytes
        if staged and xfer.narrows_to(a.dtype) is None:
            pool = self._staging_pool()
            if pool is not None:
                out = pool.acquire((len(idx),) + a.shape[1:], a.dtype,
                                   tag=id(a))
                return a.gather(idx, out=out)
        return xfer.narrow_wire(a.gather(idx))

    def _assemble_group(self, idx: np.ndarray, fuse: int,
                        staged: bool = False) -> Batch:
        """One stacked (fuse, local_bs, ...) superbatch."""
        xs = tuple(
            self._gather_leaf(a, idx, staged).reshape(
                (fuse, self.local_bs) + a.shape[1:])
            for a in self.x)
        ys = (tuple(
            self._gather_leaf(a, idx, staged).reshape(
                (fuse, self.local_bs) + a.shape[1:])
            for a in self.y) if self.y is not None else None)
        return Batch(x=xs, y=ys, w=None, fused=fuse)

    def _assemble_batch(self, idx: np.ndarray, w: Optional[np.ndarray],
                        staged: bool = False) -> Batch:
        """One plain batch; chunk-aware gather (a contiguous in-chunk index
        run comes back as a zero-copy view)."""
        xs = tuple(self._gather_leaf(a, idx, staged) for a in self.x)
        ys = (tuple(self._gather_leaf(a, idx, staged) for a in self.y)
              if self.y is not None else None)
        return Batch(x=xs, y=ys, w=w)

    def _host_batch_tasks(self, shuffle: bool, fuse: int = 1,
                          staged: bool = False
                          ) -> Iterator[Callable[[], Batch]]:
        """Plan an epoch: yield zero-arg assembly tasks in batch order.

        The planner itself only slices the (native, off-GIL generated)
        shuffle order — cheap — while the gather work lives in the tasks,
        which the InfeedPump fans out over its assembly workers and
        re-orders. Running the tasks inline (``_host_batches``) is
        bit-identical: the epoch order is fixed here, not by scheduling.

        ``fuse`` > 1 groups that many consecutive FULL batches into ONE
        stacked superbatch (leaves ``(fuse, local_bs, ...)``) for the
        engine's scan-fused multi-step dispatch. The ragged tail falls back
        to ordinary single batches (last one padded + masked) — padding a
        whole superbatch would synthesize fully-empty steps whose zero-grad
        optimizer updates are NOT no-ops under momentum/Adam.
        """
        from functools import partial

        from analytics_zoo_tpu.native import shuffled_indices
        if shuffle:
            order = shuffled_indices(self.n, seed=self.seed + self._epoch)
        else:
            order = np.arange(self.n, dtype=np.int64)
        self._epoch += 1
        group = self.local_bs * max(fuse, 1)
        n_groups = self.n // group if fuse > 1 else 0
        for s in range(n_groups):
            yield partial(self._assemble_group,
                          order[s * group:(s + 1) * group], fuse,
                          staged)
        done = n_groups * group
        tail_steps = (math.ceil((self.n - done) / self.local_bs)
                      if self.pad_tail
                      else (self.n - done) // self.local_bs) \
            if fuse > 1 else self.steps_per_epoch
        for s in range(tail_steps):
            idx = order[done + s * self.local_bs:
                        done + (s + 1) * self.local_bs]
            real = len(idx)
            if real < self.local_bs:
                idx = np.concatenate(
                    [idx, np.zeros(self.local_bs - real, dtype=idx.dtype)])
                w = np.zeros(self.local_bs, dtype=np.float32)
                w[:real] = 1.0
            else:
                # full batch: weights are all ones — send None and let the
                # jitted step synthesize them, saving a per-step
                # host->device transfer (the infeed is the scarce resource)
                w = None
            yield partial(self._assemble_batch, idx, w, staged)

    def _host_batches(self, shuffle: bool, fuse: int = 1) -> Iterator[Batch]:
        """Assembled host batches, inline (single-threaded) — the
        non-prefetch path and the bench's direct-feed loops."""
        for task in self._host_batch_tasks(shuffle, fuse):
            yield task()

    def _put_batch(self, b: Batch) -> Batch:
        """Stage a whole batch pytree into HBM with per-leaf, batch-sharded
        placement (``native.transfer.put_tree``): each chip receives ONLY
        its slice of the batch, cut host-side — no full-batch replication
        ahead of slicing. Multihost rides the same helper
        (``make_array_from_process_local_data`` per leaf)."""
        fused = b.fused > 1
        leaves = list(b.x) + list(b.y or ()) + (
            [b.w] if b.w is not None else [])
        shardings = [self._sharding(a.ndim, fused) for a in leaves]
        put = xfer.put_tree(leaves, shardings)
        nx, ny = len(b.x), len(b.y or ())
        return Batch(
            x=tuple(put[:nx]),
            y=tuple(put[nx:nx + ny]) if b.y is not None else None,
            w=put[nx + ny] if b.w is not None else None,
            fused=b.fused)

    def epoch(self, shuffle: Optional[bool] = None,
              prefetch: bool = True, fuse: int = 1) -> Iterator[Batch]:
        """Yield device-resident batches. With prefetch, assembly tasks fan
        out over the pump's worker threads and an in-order H2D stage keeps
        the next batches staged in HBM while the current step runs
        (SURVEY.md §7 hard part #1 — infeed throughput). ``fuse`` > 1 yields
        stacked superbatches for ``TrainEngine.train_batch_group``."""
        shuffle = self.shuffle if shuffle is None else shuffle
        if not prefetch:
            for task in self._host_batch_tasks(shuffle, fuse):
                t0 = time.perf_counter()
                b = task()
                t1 = time.perf_counter()
                out = self._put_batch(b)
                t2 = time.perf_counter()
                self.stats.add("assemble", t1 - t0)
                self.stats.add("h2d", t2 - t1)
                yield out
            return
        from analytics_zoo_tpu.native.infeed import InfeedPump
        yield from InfeedPump(
            lambda: self._host_batch_tasks(shuffle, fuse, staged=True),
            device_put=self._put_batch,
            depth=self.prefetch_depth,
            workers=self.prefetch_workers,
            stats=self.stats)


def data_to_iterator(data: Any, batch_size: int, mesh: Mesh,
                     feature_cols=None, label_cols=None, shuffle=False,
                     seed: int = 0, pad_tail: bool = True,
                     config: Optional[dict] = None,
                     stats: Optional[PipelineStats] = None) -> BatchIterator:
    """Front door: any supported data form -> BatchIterator. The batches
    come straight out of the shard chunks (``chunk_shards``) — no merged
    dataset copy is ever built."""
    if hasattr(data, "epoch") and hasattr(data, "steps_per_epoch"):
        if stats is not None and hasattr(data, "stats"):
            data.stats = stats
        return data                 # already a batch iterator (duck-typed),
        # e.g. orca.data.image.imagenet.ImageNetPipeline streaming from disk
    if callable(data):  # data_creator(config, batch_size) like tf2/pytorch est.
        produced = data(config or {}, batch_size)
        return data_to_iterator(produced, batch_size, mesh, feature_cols,
                                label_cols, shuffle, seed, pad_tail,
                                config=config, stats=stats)
    shards = xshards_from_arrays(data, feature_cols, label_cols)
    chunked = chunk_shards(shards)
    cfg = config or {}
    return BatchIterator(chunked, batch_size, mesh, shuffle=shuffle,
                         seed=seed, pad_tail=pad_tail, stats=stats,
                         prefetch_depth=int(cfg.get("infeed_depth", 2)),
                         prefetch_workers=cfg.get("infeed_workers"))


def update_predict_xshards(xshards: HostXShards,
                           pred_shards: HostXShards) -> HostXShards:
    """Attach predictions to the original shards (reference:
    orca/learn/utils.py:116-125)."""
    def merge(pair):
        d, pred = pair
        out = dict(d) if isinstance(d, dict) else {"x": d}
        out["prediction"] = pred
        return out
    return xshards.zip(pred_shards).transform_shard(merge)


def find_latest_checkpoint(model_dir: str, model_type: str = "tpu"):
    """Locate the newest versioned checkpoint under model_dir (reference:
    orca/learn/utils.py:24-69 scans for model.<iter> files; here step
    dirs). One scanner — ``ckpt.format.loadable_step_dirs`` — decides
    candidacy for this, the plane and the hot-reload watcher: plane dirs
    count only when COMMITTED (a manifest without its COMMIT marker is a
    torn write and must never be the resume point); ``bare_ok`` keeps
    this function's historical acceptance of bare step dirs from
    pre-plane layouts."""
    from ...ckpt.format import loadable_step_dirs
    dirs = loadable_step_dirs(model_dir, bare_ok=True)
    if not dirs:
        return None, None
    step, path = dirs[-1]
    return path, step
