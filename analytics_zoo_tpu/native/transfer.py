"""Sharded host→device transfer plane — the one place H2D placement lives.

Every training/serving path used to stage batches with its own
``jax.device_put`` incantation. This module centralizes the three levers
for a bandwidth-bound host→device link:

* **Narrow wire dtypes** (:func:`narrow_wire`) — f64/i64/u64 host arrays are
  pre-narrowed to the dtype JAX would canonicalize them to on device anyway
  (x64 disabled, the default), so the wire carries half the bytes for the
  exact same device bits. uint8 / int32 / f32 ride through untouched.
* **Batch-sharded placement** (:func:`sharded_put`) — instead of handing the
  whole host array to the runtime with a sharding (which may replicate the
  full buffer to every chip before slicing), each chip's slice is cut on the
  host and transferred directly to its device via
  ``make_array_from_single_device_arrays``. N chips → N disjoint transfers,
  no replicated bytes.
* **Reusable staging buffers** (:class:`StagingPool`) — batch assembly
  gathers into a fixed ring of preallocated host buffers instead of a fresh
  allocation per batch, killing malloc/page-fault churn on the hot path.
  Enabled automatically on non-CPU backends (TPU PJRT always copies host
  memory during ``device_put``, so ring reuse is safe); the CPU backend may
  alias aligned numpy buffers zero-copy, so staging stays off there unless
  ``ZOO_HOST_STAGING=1`` forces it.

The InfeedPump drives these through N parallel transfer lanes
(``ZOO_H2D_LANES``) — see :mod:`analytics_zoo_tpu.native.infeed`.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence

import numpy as np

from ..resilience import faults as _faults
from ..resilience import watchdog as _watchdog

__all__ = ["narrow_wire", "wire_nbytes", "sharded_put", "put_tree",
           "StagingPool", "staging_enabled", "default_h2d_lanes",
           "MAX_H2D_LANES"]

# hard ceiling for adaptive lane growth: beyond a handful of concurrent
# DMA streams the link is saturated and extra lanes only add contention
MAX_H2D_LANES = 8


def default_h2d_lanes() -> int:
    """Parallel H2D transfer-lane count (``ZOO_H2D_LANES``, default 2)."""
    env = os.environ.get("ZOO_H2D_LANES")
    if env:
        return max(1, min(int(env), MAX_H2D_LANES))
    return 2


# --- narrow wire format ------------------------------------------------------

_NARROW = {np.dtype(np.float64): np.float32,
           np.dtype(np.int64): np.int32,
           np.dtype(np.uint64): np.uint32,
           np.dtype(np.complex128): np.complex64}


def narrows_to(dtype) -> Optional[np.dtype]:
    """The canonical device dtype ``narrow_wire`` would cast to, or None
    when the dtype already rides narrow (or x64 is enabled)."""
    target = _NARROW.get(np.dtype(dtype) if dtype is not None else None)
    if target is None:
        return None
    from jax import config as _jax_config
    if _jax_config.jax_enable_x64:
        return None
    return np.dtype(target)


def narrow_wire(a: np.ndarray) -> np.ndarray:
    """Pre-narrow a host array to its canonical device dtype.

    With x64 disabled (the JAX default) ``device_put`` canonicalizes
    f64→f32 / i64→i32 / u64→u32 anyway — narrowing on the host first is
    bit-identical and halves the bytes the wire carries. Source dtypes that
    already ride narrow (uint8 pixels, int32 ids, f32 features) pass through
    untouched, zero-copy. With x64 enabled this is a no-op: the user asked
    for wide device arrays.
    """
    target = _NARROW.get(getattr(a, "dtype", None))
    if target is None:
        return a
    from jax import config as _jax_config
    if _jax_config.jax_enable_x64:
        return a
    return a.astype(target)


def wire_nbytes(leaves) -> int:
    """Bytes a leaf list will actually put on the wire (post-narrowing)."""
    total = 0
    for a in leaves:
        n = int(getattr(a, "nbytes", 0))
        dt = getattr(a, "dtype", None)
        if dt is not None and np.dtype(dt) in _NARROW:
            n //= 2
        total += n
    return total


# --- sharded placement -------------------------------------------------------

def sharded_put(arr, sharding, stats=None):
    """Place one host array on the mesh with per-device slice transfers.

    For a batch-sharded ``NamedSharding`` each addressable device receives
    ONLY its slice (cut host-side, row slices of a C-contiguous batch are
    zero-copy views), assembled into one logical array via
    ``make_array_from_single_device_arrays`` — no host-side replication of
    the full batch. Fully-replicated shardings, scalars, multi-process
    placement and any slicing failure fall back to the runtime's own
    ``device_put`` / ``make_array_from_process_local_data``.

    ``stats`` (a :class:`~analytics_zoo_tpu.native.infeed.PipelineStats`)
    records the transfer under the ``h2d`` stage. Callers that already time
    the stage (the InfeedPump) should leave it None to avoid double counts.
    """
    import jax

    a = np.asarray(arr)
    if stats is not None:
        import time
        t0 = time.perf_counter()
    out = _place(jax, a, sharding)
    if stats is not None:
        stats.add("h2d", time.perf_counter() - t0, nbytes=a.nbytes)
    return out


def _place(jax, a, sharding):
    # resilience hooks: the `h2d.put` fault site (chaos tests model a lost
    # DMA link here) and the dispatch watchdog's H2D wait bound — both one
    # global read when disarmed. The fault fires INSIDE the watched
    # section so a delay-mode fault (modelling a hung DMA) trips the
    # watchdog like the real thing would.
    wd = _watchdog.active()
    if wd is not None:
        token = wd.enter("h2d.put")
        try:
            _faults.fire("h2d.put")
            return _place_inner(jax, a, sharding)
        finally:
            wd.exit(token)
    _faults.fire("h2d.put")
    return _place_inner(jax, a, sharding)


def _place_inner(jax, a, sharding):
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, a)
    if a.ndim == 0 or sharding.is_fully_replicated:
        return jax.device_put(a, sharding)
    imap = sharding.addressable_devices_indices_map(a.shape)
    if len(imap) <= 1:
        return jax.device_put(a, sharding)
    shards = [jax.device_put(a[idx], d) for d, idx in imap.items()]
    return jax.make_array_from_single_device_arrays(
        a.shape, sharding, shards)


def put_tree(leaves: Sequence, shardings: Sequence, stats=None) -> List:
    """Per-leaf :func:`sharded_put` over a flat leaf list (one batch)."""
    import time
    t0 = time.perf_counter()
    import jax
    out = [_place(jax, np.asarray(a), s) for a, s in zip(leaves, shardings)]
    if stats is not None:
        stats.add("h2d", time.perf_counter() - t0,
                  nbytes=sum(int(getattr(a, "nbytes", 0)) for a in leaves))
    return out


# --- host staging buffers ----------------------------------------------------

def staging_enabled() -> bool:
    """Reusable host staging buffers: on for non-CPU backends, off for CPU
    (whose ``device_put`` may alias aligned numpy buffers zero-copy — ring
    reuse would corrupt staged batches). ``ZOO_HOST_STAGING=1/0``
    overrides."""
    env = os.environ.get("ZOO_HOST_STAGING", "").strip()
    if env in ("0", "1"):
        return env == "1"
    import jax
    return jax.default_backend() != "cpu"


class StagingPool:
    """Fixed ring of reusable host batch buffers, keyed by (shape, dtype).

    ``acquire`` returns the next buffer in the key's ring, allocating until
    the ring is full. Safe while at most ``ring - 1`` batches of one
    signature are simultaneously between assembly and the end of their
    ``device_put`` (the pump's in-flight window: assembly workers + transfer
    lanes — size the ring above that). No locking on the buffer itself: the
    ring hand-off is the synchronization.
    """

    def __init__(self, ring: int = 12):
        self.ring = max(2, int(ring))
        self._lock = threading.Lock()
        self._rings = {}        # (tag, shape, dtype) -> [buffers], cursor

    def acquire(self, shape, dtype, tag=None) -> np.ndarray:
        """``tag`` partitions the rings (e.g. per batch leaf): two leaves
        sharing one (shape, dtype) signature must not share a ring, or
        each batch would draw the ring down twice and halve the in-flight
        headroom the ring size guarantees."""
        key = (tag, tuple(shape), np.dtype(dtype).str)
        with self._lock:
            bufs, cur = self._rings.get(key, ([], 0))
            if len(bufs) < self.ring:
                buf = np.empty(shape, dtype)
                bufs.append(buf)
                self._rings[key] = (bufs, 0)
                return buf
            buf = bufs[cur]
            self._rings[key] = (bufs, (cur + 1) % len(bufs))
            return buf

    @property
    def allocated_bytes(self) -> int:
        with self._lock:
            return sum(b.nbytes for bufs, _ in self._rings.values()
                       for b in bufs)
