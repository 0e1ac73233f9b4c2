"""ctypes bindings for the native host runtime (native/zoo_runtime.cc).

Auto-builds the shared library with g++ on first use (under native/build/,
stamped with a hash of the source and the compile command so a stale or
foreign build is never loaded); every binding has a numpy fallback so the
package works even without a toolchain — ``version()`` says which is in
use. This replaces the reference's JNI native layer
(PersistentMemoryAllocator.java:37-43, MTSampleToMiniBatch.scala:139) with a
C++ layer under the one-Python-process-per-host model."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
import time
import weakref
from typing import Optional

import numpy as np

logger = logging.getLogger("analytics_zoo_tpu")

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_PKG_DIR, "zoo_runtime.cc")
# build under the package dir when writable, else a per-user cache dir —
# pip installs may land in a read-only site-packages.
_BUILD_DIR = os.path.join(_PKG_DIR, "build")
if not os.access(_PKG_DIR, os.W_OK):
    _BUILD_DIR = os.path.join(
        os.environ.get("XDG_CACHE_HOME",
                       os.path.join(os.path.expanduser("~"), ".cache")),
        "analytics_zoo_tpu", "native")
_SO = os.path.join(_BUILD_DIR, "libzoo_runtime.so")
_STAMP = _SO + ".stamp"
# no -march=native: the build directory travels with the checkout (a copied
# disk, a shared filesystem) to hosts with another CPU
_CXX = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread"]

_lib = None
_lib_lock = threading.Lock()


def _build_stamp() -> str:
    """Identity of the build the current checkout asks for: the source's
    content and the compile command."""
    h = hashlib.sha256(" ".join(_CXX).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def _stamp_on_disk() -> Optional[str]:
    try:
        with open(_STAMP, encoding="utf-8") as f:
            return f.read().strip()
    except OSError:
        return None


def _build(stamp: str) -> Optional[str]:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # build beside the target and rename: a concurrent process (fleet
    # workers start together) never loads a half-written library
    tmp_so, tmp_stamp = (f"{p}.{os.getpid()}.tmp" for p in (_SO, _STAMP))
    try:
        subprocess.run(_CXX + [_SRC, "-o", tmp_so], check=True,
                       capture_output=True, timeout=180)
        with open(tmp_stamp, "w", encoding="utf-8") as f:
            f.write(stamp)
        os.replace(tmp_so, _SO)
        os.replace(tmp_stamp, _STAMP)
        return _SO
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            OSError) as e:
        logger.warning("native runtime build failed (%s); using numpy "
                       "fallbacks", e)
        return None


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable.
    The library is rebuilt whenever its stamp differs from the one the
    checkout's source and compile command give."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        path = _SO
        try:
            stamp = _build_stamp()
        except OSError as e:
            logger.warning("native runtime source unreadable (%s); using "
                           "numpy fallbacks", e)
            path = None
        else:
            if not os.path.exists(path) or _stamp_on_disk() != stamp:
                path = _build(stamp)
        if path is None:
            _lib = False
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            logger.warning("native runtime load failed: %s", e)
            _lib = False
            return None
        lib.za_arena_create.restype = ctypes.c_void_p
        lib.za_arena_create.argtypes = [ctypes.c_size_t]
        lib.za_arena_alloc.restype = ctypes.c_void_p
        lib.za_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                       ctypes.c_size_t]
        lib.za_arena_used.restype = ctypes.c_size_t
        lib.za_arena_used.argtypes = [ctypes.c_void_p]
        lib.za_arena_capacity.restype = ctypes.c_size_t
        lib.za_arena_capacity.argtypes = [ctypes.c_void_p]
        lib.za_arena_reset.argtypes = [ctypes.c_void_p]
        lib.za_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.za_queue_create.restype = ctypes.c_void_p
        lib.za_queue_create.argtypes = [ctypes.c_size_t]
        lib.za_queue_push.restype = ctypes.c_int
        lib.za_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int]
        lib.za_queue_pop.restype = ctypes.c_int
        lib.za_queue_pop.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.c_int]
        lib.za_queue_size.restype = ctypes.c_size_t
        lib.za_queue_size.argtypes = [ctypes.c_void_p]
        lib.za_queue_close.argtypes = [ctypes.c_void_p]
        lib.za_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.za_shuffled_indices.argtypes = [
            ctypes.c_uint64, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64]
        lib.za_gather_rows.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int]
        lib.za_pad_sequences_i32.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float)]
        lib.za_f32_to_bf16.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_int64]
        lib.za_version.restype = ctypes.c_char_p
        _lib = lib
        return lib


def available() -> bool:
    return load() is not None


def version() -> str:
    lib = load()
    return lib.za_version().decode() if lib else "numpy-fallback"


# --- high-level wrappers -----------------------------------------------------

class Arena:
    """Aligned bump allocator for staging buffers.

    Lifetime contract: ``reset()`` logically invalidates previously returned
    arrays (their memory will be reused by subsequent allocs) — callers must
    not hold views across a reset. The native block is only freed once BOTH
    ``close()`` (or GC of the Arena) has been requested AND no ``alloc_array``
    views remain alive: each returned array's base buffer pins the Arena and
    is tracked with a finalizer, and ``close()`` defers the actual
    ``za_arena_destroy`` until the last view dies.
    """

    def __init__(self, capacity: int):
        self._lib = load()
        self.capacity = capacity
        self._live_views = 0
        self._close_requested = False
        # RLock: cyclic GC can fire a view finalizer (_on_view_dead) in the
        # SAME thread while it holds this lock inside alloc_array — a plain
        # Lock would self-deadlock. Reentrancy is safe: close() can't sneak
        # in (it needs this lock), so the arena can't be destroyed mid-alloc.
        self._state_lock = threading.RLock()
        if self._lib:
            self._h = self._lib.za_arena_create(capacity)
            if not self._h:
                raise MemoryError(f"arena of {capacity} bytes")
        else:
            self._h = None

    def alloc_array(self, shape, dtype=np.float32, align: int = 64
                    ) -> np.ndarray:
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        if self._lib:
            with self._state_lock:
                if self._close_requested or self._h is None:
                    raise RuntimeError("arena is closed")
                ptr = self._lib.za_arena_alloc(self._h, nbytes, align)
                if not ptr:
                    raise MemoryError("arena exhausted")
                self._live_views += 1
            # Python-object construction happens OUTSIDE the critical
            # section (it can trigger GC → view finalizers); the count is
            # already reserved, so a concurrent close() stays deferred.
            fin = None
            try:
                buf = (ctypes.c_char * nbytes).from_address(ptr)
                # the array's .base chain ends at `buf`; pinning the Arena
                # on it keeps the native block alive while any view exists
                buf._zoo_arena = self
                fin = weakref.finalize(buf, self._on_view_dead)
                return np.frombuffer(buf, dtype=dtype).reshape(shape)
            except BaseException:
                # detach the finalizer before the manual rollback so the
                # reservation is only ever decremented once (a live finalizer
                # would fire again at buf collection — double-decrement)
                if fin is not None:
                    fin.detach()
                self._on_view_dead()  # roll back the reservation
                raise
        return np.empty(shape, dtype)

    def _on_view_dead(self):
        with self._state_lock:
            self._live_views -= 1
            do_free = self._close_requested and self._live_views == 0
        if do_free:
            self._destroy()

    @property
    def used(self) -> int:
        return self._lib.za_arena_used(self._h) if self._lib else 0

    def reset(self):
        if self._lib:
            self._lib.za_arena_reset(self._h)

    def _destroy(self):
        with self._state_lock:
            h, self._h = self._h, None
        if h:
            self._lib.za_arena_destroy(h)

    def close(self):
        """Request teardown; frees immediately if no views are outstanding,
        otherwise when the last view is garbage-collected."""
        if self._lib and self._h:
            with self._state_lock:
                self._close_requested = True
                do_free = self._live_views == 0
            if do_free:
                self._destroy()

    def __del__(self):
        try:
            self.close()
        except (OSError, RuntimeError, AttributeError):
            # interpreter-shutdown teardown: the ctypes lib or our own
            # attributes may already be gone; nothing to log to either
            pass


def shuffled_indices(n: int, seed: int = 0) -> np.ndarray:
    lib = load()
    out = np.empty(n, np.int64)
    if lib and n:
        lib.za_shuffled_indices(
            ctypes.c_uint64(seed),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n)
        return out
    return np.random.RandomState(seed).permutation(n).astype(np.int64)


def gather_rows(src: np.ndarray, idx: np.ndarray,
                num_threads: int = 4,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """out[i] = src[idx[i]] — threaded memcpy batch assembly.

    ``out`` lets callers gather straight into a preallocated destination
    (e.g. a contiguous slice of a larger staging buffer) instead of paying
    a fresh allocation per batch; it must be C-contiguous with the gather's
    shape and dtype."""
    lib = load()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, np.int64)
    shape = (len(idx),) + src.shape[1:]
    if out is not None:
        if (out.shape != shape or out.dtype != src.dtype
                or not out.flags.c_contiguous):
            raise ValueError(
                f"out must be C-contiguous {shape} {src.dtype}, got "
                f"{out.shape} {out.dtype}")
    if lib is None:
        if out is None:
            return src[idx]
        np.take(src, idx, axis=0, out=out)
        return out
    if out is None:
        out = np.empty(shape, src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], initial=1))
    lib.za_gather_rows(
        src.ctypes.data_as(ctypes.c_char_p), row_bytes,
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(idx),
        out.ctypes.data_as(ctypes.c_char_p), num_threads)
    return out


def pad_sequences(seqs, max_len: int, pad_value: int = 0,
                  return_mask: bool = True):
    """Ragged python/np int sequences -> (n, max_len) int32 (+f32 mask)."""
    lib = load()
    n = len(seqs)
    if lib is None:
        out = np.full((n, max_len), pad_value, np.int32)
        mask = np.zeros((n, max_len), np.float32)
        for i, s in enumerate(seqs):
            k = min(len(s), max_len)
            out[i, :k] = np.asarray(s[:k], np.int32)
            mask[i, :k] = 1.0
        return (out, mask) if return_mask else out
    flat = np.concatenate([np.asarray(s, np.int32) for s in seqs]) \
        if n else np.zeros(0, np.int32)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(s) for s in seqs], out=offsets[1:])
    out = np.empty((n, max_len), np.int32)
    mask = np.empty((n, max_len), np.float32) if return_mask else None
    lib.za_pad_sequences_i32(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, max_len, pad_value,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        if return_mask else None)
    return (out, mask) if return_mask else out


def f32_to_bf16_bits(x: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even f32 -> bf16 bit pattern (uint16 view)."""
    lib = load()
    x = np.ascontiguousarray(x, np.float32)
    if lib is None:
        bits = x.view(np.uint32)
        rounding = 0x7FFF + ((bits >> 16) & 1)
        return ((bits + rounding) >> 16).astype(np.uint16)
    out = np.empty(x.shape, np.uint16)
    lib.za_f32_to_bf16(
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)), x.size)
    return out


class NativeQueue:
    """Blocking MPMC queue keyed by token; payloads stay in a python dict
    (the native side orders tokens; arrays never cross the ABI)."""

    def __init__(self, capacity: int = 8):
        self._lib = load()
        self._store = {}
        self._next = 1
        self._plock = threading.Lock()
        self._closed = threading.Event()
        if self._lib:
            self._q = self._lib.za_queue_create(capacity)
        else:
            import queue
            self._q = queue.Queue(maxsize=capacity)

    def put(self, item, timeout_ms: int = -1) -> bool:
        if self._lib:
            with self._plock:
                token = self._next
                self._next += 1
                self._store[token] = item
            ok = self._lib.za_queue_push(self._q, ctypes.c_void_p(token),
                                         timeout_ms)
            if not ok:
                with self._plock:
                    self._store.pop(token, None)
            return bool(ok)
        # fallback: poll in short slices so close() can unblock a waiter
        import queue as _queue
        deadline = (None if timeout_ms < 0
                    else time.monotonic() + timeout_ms / 1000)
        while not self._closed.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except _queue.Full:
                if deadline is not None and time.monotonic() > deadline:
                    return False
        return False

    def get(self, timeout_ms: int = -1):
        if self._lib:
            out = ctypes.c_void_p()
            ok = self._lib.za_queue_pop(self._q, ctypes.byref(out),
                                        timeout_ms)
            if not ok:
                return None
            with self._plock:
                return self._store.pop(out.value)
        import queue as _queue
        deadline = (None if timeout_ms < 0
                    else time.monotonic() + timeout_ms / 1000)
        while True:
            try:
                return self._q.get(timeout=0.05)
            except _queue.Empty:
                if self._closed.is_set():
                    return None
                if deadline is not None and time.monotonic() > deadline:
                    return None

    def qsize(self) -> int:
        if self._lib:
            return self._lib.za_queue_size(self._q)
        return self._q.qsize()

    def close(self):
        self._closed.set()
        if self._lib and self._q:
            self._lib.za_queue_close(self._q)

    def destroy(self):
        if self._lib and self._q:
            self._lib.za_queue_destroy(self._q)
            self._q = None
