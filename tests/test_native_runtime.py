"""Native C++ host runtime: allocator, queue, shuffle, batch assembly,
infeed pump (counterpart of the reference's JNI layer — pmem allocator,
MTSampleToMiniBatch)."""

import numpy as np
import pytest

from analytics_zoo_tpu.native import (Arena, InfeedPump, NativeQueue,
                                      available, f32_to_bf16_bits,
                                      gather_rows, pad_sequences,
                                      shuffled_indices, version)


def test_native_library_builds():
    assert available(), "g++ is in the image; the native lib must build"
    assert "native" in version()


def test_arena_alloc_reset():
    a = Arena(1 << 16)
    x = a.alloc_array((8, 8), np.float32)
    x[:] = 3.0
    assert a.used >= 8 * 8 * 4
    y = a.alloc_array((4,), np.int64)
    y[:] = 7
    assert x.sum() == 192.0          # distinct buffers
    a.reset()
    assert a.used == 0
    with pytest.raises(MemoryError):
        Arena(1 << 16).alloc_array((1 << 20,), np.float64)
    a.close()


def test_arena_views_pin_native_memory():
    """Returned arrays keep the Arena (and its native block) alive: GC of
    the Arena, and even an explicit close(), must not free memory while a
    view exists (close defers to the last view's death)."""
    import gc
    import weakref

    a = Arena(1 << 16)
    arr = a.alloc_array((16,), np.float32)
    arr[:] = 5.0
    ref = weakref.ref(a)
    a.close()                      # deferred: view still alive
    del a
    gc.collect()
    assert ref() is not None       # pinned through arr.base
    assert arr.sum() == 80.0       # memory still valid
    del arr
    gc.collect()
    assert ref() is None           # freed once the last view died


def test_arena_rejects_alloc_after_close():
    a = Arena(1 << 16)
    a.close()
    if a._lib:  # native path only; numpy fallback has no close semantics
        with pytest.raises(RuntimeError):
            a.alloc_array((4,), np.float32)


def test_shuffled_indices_deterministic_permutation():
    a = shuffled_indices(1000, seed=42)
    b = shuffled_indices(1000, seed=42)
    c = shuffled_indices(1000, seed=43)
    assert (a == b).all()
    assert not (a == c).all()
    assert sorted(a.tolist()) == list(range(1000))


def test_gather_rows_matches_numpy():
    rng = np.random.RandomState(0)
    src = rng.randn(512, 17).astype(np.float32)
    idx = rng.randint(0, 512, 2048).astype(np.int64)
    np.testing.assert_array_equal(gather_rows(src, idx), src[idx])
    # multi-dim rows
    src3 = rng.randn(64, 4, 5).astype(np.float32)
    np.testing.assert_array_equal(gather_rows(src3, idx % 64),
                                  src3[idx % 64])


def test_pad_sequences_semantics():
    out, mask = pad_sequences([[1, 2, 3, 4, 5], [9], []], max_len=3)
    assert out.tolist() == [[1, 2, 3], [9, 0, 0], [0, 0, 0]]
    assert mask.tolist() == [[1, 1, 1], [1, 0, 0], [0, 0, 0]]
    out2 = pad_sequences([[7]], max_len=2, pad_value=-1, return_mask=False)
    assert out2.tolist() == [[7, -1]]


def test_bf16_conversion_matches_jax():
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = rng.randn(1000).astype(np.float32) * 100
    ours = f32_to_bf16_bits(x)
    ref = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)).view(np.uint16)
    np.testing.assert_array_equal(ours, ref)


def test_native_queue_fifo_and_close():
    q = NativeQueue(capacity=2)
    assert q.put("a") and q.put("b")
    assert not q.put("c", timeout_ms=50)      # full
    assert q.get() == "a"
    assert q.get() == "b"
    assert q.get(timeout_ms=50) is None       # empty
    q.close()
    q.destroy()


def test_native_queue_threads():
    import threading
    q = NativeQueue(capacity=4)
    got = []

    def consumer():
        while True:
            item = q.get()
            if item is None or item == "stop":
                break
            got.append(item)

    t = threading.Thread(target=consumer)
    t.start()
    for i in range(100):
        q.put(i)
    q.put("stop")
    t.join(timeout=10)
    assert got == list(range(100))
    q.destroy()


def test_infeed_pump_prefetches_in_order():
    batches = [np.full((2, 2), i, np.float32) for i in range(10)]

    def factory():
        return iter(batches)

    seen = [np.asarray(b)[0, 0] for b in InfeedPump(factory, depth=3)]
    assert seen == list(range(10))


def test_infeed_pump_propagates_errors():
    def factory():
        yield np.ones(2)
        raise RuntimeError("loader exploded")

    pump = InfeedPump(factory)
    with pytest.raises(RuntimeError, match="loader exploded"):
        list(pump)


def test_infeed_pump_slow_consumer_gets_sentinel():
    """Regression: the _STOP sentinel must survive a full queue.

    With depth=2 and a consumer that stalls on the first item (simulating the
    first-step jit compile), the producer finishes all puts while both slots
    are full; a timed sentinel put used to be dropped silently, leaving the
    consumer blocked forever in q.get(). The pump must deliver every batch
    AND terminate."""
    import time
    batches = [np.full((2,), i, np.float32) for i in range(3)]

    def factory():
        return iter(batches)

    seen = []
    for b in InfeedPump(factory, depth=2):
        if not seen:
            time.sleep(0.5)     # producer fills + exhausts iterator meanwhile
        seen.append(float(np.asarray(b)[0]))
    assert seen == [0.0, 1.0, 2.0]


def test_infeed_pump_abandoned_consumer_does_not_hang(caplog):
    """Breaking out of iteration mid-stream must unblock the producer's
    blocking sentinel put via q.close()."""
    import logging
    def factory():
        return iter(np.full((2,), i, np.float32) for i in range(50))

    it = iter(InfeedPump(factory, depth=2))
    next(it)
    with caplog.at_level(logging.WARNING, logger="analytics_zoo_tpu"):
        it.close()               # generator finally: q.close() + join
    # if close() stopped unblocking the producer, the pump would fall back
    # to the 30s join timeout and log this leak warning
    assert "infeed producer did not stop" not in caplog.text


def test_build_stamp_forces_rebuild_when_source_changes(tmp_path,
                                                        monkeypatch):
    """The library is rebuilt whenever the stamp (hash of the source + the
    compile command) differs from the one on disk — an mtime says nothing
    about a build directory that travelled with a copied checkout — and it
    is not built for this host's CPU only."""
    import os
    import shutil

    from analytics_zoo_tpu.native import runtime as rt

    assert "-march=native" not in rt._CXX
    src = tmp_path / "zoo_runtime.cc"
    shutil.copy(rt._SRC, src)
    build = tmp_path / "build"
    so = build / "libzoo_runtime.so"
    monkeypatch.setattr(rt, "_SRC", str(src))
    monkeypatch.setattr(rt, "_BUILD_DIR", str(build))
    monkeypatch.setattr(rt, "_SO", str(so))
    monkeypatch.setattr(rt, "_STAMP", str(so) + ".stamp")

    def load_fresh():
        monkeypatch.setattr(rt, "_lib", None)
        assert rt.load() is not None
        return (so.stat().st_mtime_ns, (build / "libzoo_runtime.so.stamp"
                                        ).read_text())

    built, stamp = load_fresh()
    assert stamp == rt._build_stamp()
    assert load_fresh() == (built, stamp)            # same stamp: no rebuild
    with open(src, "a") as f:
        f.write("\n// a change that an mtime could have missed\n")
    os.utime(src, ns=(0, 0))                         # older than the .so
    rebuilt, stamp2 = load_fresh()
    assert stamp2 != stamp and stamp2 == rt._build_stamp()
    assert rebuilt != built
