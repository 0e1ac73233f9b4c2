"""Set-up stages (``obs/trace.py stage``): always-on self-time counters at the
sites that run a handful of times a process, a span of the same name when
spans are live, and JAX's own compile events filed under the open stage."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.compile import ExecutableCache
from analytics_zoo_tpu.obs import REGISTRY, trace

BUILD_LEAVES = ("engine.init_vars", "engine.place_params", "engine.opt_init")


def _family(name):
    (fam,) = [f for f in REGISTRY.families() if f.name == name]
    return {tuple(sorted(labels.items())): child.value
            for labels, child in fam.samples()}


def seconds(stage):
    return _family("zoo_setup_seconds_total").get((("stage", stage),), 0.0)


def events(stage):
    return _family("zoo_setup_events_total").get((("stage", stage),), 0.0)


def jax_events(event, stage):
    return _family("zoo_jax_compile_events_total").get(
        (("event", event), ("stage", stage)), 0.0)


@pytest.fixture(autouse=True)
def disarmed():
    trace.disarm()
    trace.clear()
    yield
    trace.disarm()
    trace.clear()


def test_self_time_over_two_levels_of_nesting():
    s0 = {n: seconds(n) for n in ("t.outer", "t.mid", "t.leaf")}
    t0 = time.perf_counter()
    with trace.stage("t.outer") as outer:
        time.sleep(0.02)
        with trace.stage("t.mid") as mid:
            time.sleep(0.03)
            with trace.stage("t.leaf") as leaf:
                time.sleep(0.04)
            with trace.stage("t.leaf") as leaf2:
                time.sleep(0.01)
    wall = time.perf_counter() - t0
    own = {n: seconds(n) - s0[n] for n in s0}
    # a stage's own share is its duration less the stages inside it ...
    assert own["t.leaf"] == pytest.approx(leaf.duration_s + leaf2.duration_s)
    assert own["t.mid"] == pytest.approx(
        mid.duration_s - leaf.duration_s - leaf2.duration_s)
    assert own["t.outer"] == pytest.approx(outer.duration_s - mid.duration_s)
    assert own["t.outer"] >= 0.02 and own["t.mid"] >= 0.03
    # ... so the shares add up to the union: nothing is counted twice
    assert sum(own.values()) == pytest.approx(outer.duration_s)
    assert outer.duration_s <= wall
    assert events("t.leaf") >= 2


def test_a_sibling_threads_stage_is_no_part_of_this_threads():
    s0 = {n: seconds(n) for n in ("t.main", "t.side")}
    inside = {}

    def side():
        inside["before"] = trace.current_stage()
        with trace.stage("t.side"):
            inside["during"] = trace.current_stage()
            time.sleep(0.05)

    with trace.stage("t.main") as main:
        th = threading.Thread(target=side)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
        assert trace.current_stage() == "t.main"
    assert trace.current_stage() == "none"
    assert inside == {"before": "none", "during": "t.side"}
    # the sibling's 50 ms lie inside main's interval and are not taken off it
    assert seconds("t.main") - s0["t.main"] == pytest.approx(main.duration_s)
    assert seconds("t.side") - s0["t.side"] >= 0.05


def test_a_disarmed_stage_counts_and_leaves_the_ring_empty():
    assert not trace.enabled()
    s0, e0 = seconds("t.quiet"), events("t.quiet")
    with trace.stage("t.quiet", label="x") as st:
        st.set(text_bytes=3)
        time.sleep(0.005)
    assert trace.RING.recorded == 0 and trace.spans() == []
    assert seconds("t.quiet") - s0 >= 0.005
    assert events("t.quiet") - e0 == 1


def test_an_armed_stage_is_one_span_with_its_name_and_parent():
    s0 = seconds("t.armed")
    with trace.tracing():
        with trace.span("fit") as root:
            with trace.stage("t.armed", label="train") as st:
                st.set(text_bytes=7)
        spans = trace.drain()
    (got,) = [s for s in spans if s.name == "t.armed"]
    assert got.parent_id == root.span_id and got.trace_id == root.trace_id
    assert got.attrs == {"label": "train", "text_bytes": 7}
    assert [s.name for s in spans] == ["t.armed", "fit"]
    # the counter and the span share the boundary
    assert seconds("t.armed") - s0 == pytest.approx(got.duration_s, abs=1e-3)


def test_an_exception_inside_a_stage_still_closes_it():
    e0 = events("t.raises")
    with trace.tracing():
        with pytest.raises(KeyError):
            with trace.stage("t.outer2"):
                with trace.stage("t.raises"):
                    raise KeyError("x")
        spans = trace.drain()
    assert trace.current_stage() == "none"
    assert events("t.raises") - e0 == 1
    by = {s.name: s for s in spans}
    assert by["t.raises"].attrs["error"] == "KeyError"
    assert by["t.raises"].parent_id == by["t.outer2"].span_id
    with trace.stage("t.after"):
        assert trace.current_stage() == "t.after"


def _fresh_fn():
    def fn(x, y):
        return jnp.tanh(x @ y).sum()
    return fn


def test_compile_stages_and_stats_cold_then_warm(tmp_path):
    """Cold: the step is lowered, compiled and saved, nothing loaded. Warm (a
    new cache object on the same directory, as a new process has): lowered
    again, loaded, not compiled."""
    names = ("compile.lower", "compile.xla", "compile.load", "compile.save",
             "compile.first_call")
    # placed over every device, as the engine's state is: an executable read
    # back from disk is loaded for all the backend's devices
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    x = jax.device_put(np.ones((8, 8), np.float32), NamedSharding(
        Mesh(np.array(jax.devices()), ("dp",)), PartitionSpec()))

    def one(cache):
        s0 = {n: seconds(n) for n in names}
        t0 = time.perf_counter()
        out = cache.wrap(_fresh_fn(), label="toy")(x, x)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        return {n: seconds(n) - s0[n] for n in names}, wall

    cold_cache = ExecutableCache(cache_dir=str(tmp_path))
    cold, cold_wall = one(cold_cache)
    snap = cold_cache.stats.snapshot()
    assert snap["compiles"] == 1 and snap["disk_hits"] == 0
    assert cold["compile.xla"] == pytest.approx(snap["compile_s"], abs=1e-5)
    assert cold["compile.xla"] > 0 and cold["compile.load"] == 0
    assert cold["compile.lower"] == pytest.approx(snap["lower_s"], abs=1e-5)
    assert snap["lower_s"] > 0 and snap["load_s"] == 0
    assert snap["by_label"]["toy"]["lower_s"] == snap["lower_s"]
    persisted = list(tmp_path.glob("exe-*.pkl"))
    if not persisted:
        pytest.skip("this backend does not serialize executables")
    assert cold["compile.save"] > 0
    # self times: the first call's own share and the stages inside it add up
    # to no more than the call
    assert cold["compile.first_call"] > 0
    assert sum(cold.values()) <= cold_wall

    warm_cache = ExecutableCache(cache_dir=str(tmp_path))
    heard = []
    warm_cache.add_listener(heard.append)
    warm, warm_wall = one(warm_cache)
    snap = warm_cache.stats.snapshot()
    assert snap["compiles"] == 0 and snap["disk_hits"] == 1
    assert warm["compile.xla"] == 0 and warm["compile.save"] == 0
    assert warm["compile.load"] == pytest.approx(snap["load_s"], abs=1e-5)
    assert snap["load_s"] > 0 and snap["lower_s"] > 0
    assert snap["compile_s"] == 0
    assert snap["by_label"]["toy"]["load_s"] == snap["load_s"]
    assert sum(warm.values()) <= warm_wall
    # a study's log (TrialRuntime's listener) hears what the hit cost
    (hit,) = [e for e in heard if e["event"] == "disk_hit"]
    assert hit["load_s"] == pytest.approx(snap["load_s"], abs=1e-3)
    delta = warm_cache.stats.delta_since(snap)
    assert delta["lower_s"] == 0 and delta["load_s"] == 0


def test_a_cached_signature_opens_no_stage():
    cache = ExecutableCache()
    f = cache.wrap(_fresh_fn(), label="toy")
    x = np.ones((4, 4), np.float32)
    jax.block_until_ready(f(x, x))
    before = _family("zoo_setup_events_total")
    with trace.tracing():
        for _ in range(3):
            jax.block_until_ready(f(x, x))
        assert trace.drain() == []
    assert _family("zoo_setup_events_total") == before
    # a new signature is a first call again
    e0 = events("compile.first_call")
    jax.block_until_ready(f(x[:2], x))
    assert events("compile.first_call") - e0 == 1


def test_cache_key_probe_lowers_once_under_the_stage():
    cache = ExecutableCache()
    f = cache.wrap(_fresh_fn(), label="probe")
    x = np.ones((4, 4), np.float32)
    with trace.tracing():
        assert f.cache_key(x, x) is not None
        assert f.lowered_text(x, x)
        spans = trace.drain()
    (low,) = [s for s in spans if s.name == "compile.lower"]
    assert low.attrs["label"] == "probe" and low.attrs["text_bytes"] > 0
    assert 0 <= low.attrs["trace_s"] + low.attrs["text_s"] <= \
        low.duration_s + 2e-3
    lowered_s = cache.stats.snapshot()["lower_s"]
    assert lowered_s > 0
    # the call reuses that lowering: only the lint's pass is added
    with trace.tracing():
        jax.block_until_ready(f(x, x))
        spans = trace.drain()
    assert [s.attrs.get("part") for s in spans
            if s.name == "compile.lower"] == ["lint"]
    first = [s for s in spans if s.name == "compile.first_call"]
    assert len(first) == 1
    assert all(s.parent_id == first[0].span_id for s in spans
               if s.name.startswith("compile.") and s is not first[0])


def test_engine_build_fills_its_leaves_once(orca_context):
    import flax.linen as nn
    import optax

    from analytics_zoo_tpu.orca.learn.engine import TrainEngine

    class Toy(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(3)(nn.relu(nn.Dense(5)(x)))

    names = ("engine.build",) + BUILD_LEAVES
    eng = TrainEngine(Toy(), optax.adam(1e-3), None, {}, orca_context.mesh,
                      compile_cache=False)
    e0 = {n: events(n) for n in names}
    s0 = {n: seconds(n) for n in names}
    t0 = time.perf_counter()
    eng.build((np.zeros((2, 7), np.float32),))
    wall = time.perf_counter() - t0
    assert {n: events(n) - e0[n] for n in names} == dict.fromkeys(names, 1)
    own = {n: seconds(n) - s0[n] for n in names}
    assert all(v > 0 for v in own.values())
    assert own["engine.init_vars"] > own["engine.build"]   # the eager init
    assert sum(own.values()) <= wall
    eng.build((np.zeros((2, 7), np.float32),))              # built: returns
    assert {n: events(n) - e0[n] for n in names} == dict.fromkeys(names, 1)


def test_jax_compile_events_are_filed_under_the_open_stage():
    def fresh(n):
        # a shape no other test uses: the eager op compiles here
        return jnp.arange(n, dtype=jnp.float32).reshape(1, n) * 3.0 + 1.0

    staged0 = jax_events("backend_compile", "t.eager")
    with trace.stage("t.eager"):
        jax.block_until_ready(fresh(1237))
    assert jax_events("backend_compile", "t.eager") - staged0 >= 1
    assert jax_events("trace", "t.eager") >= 1
    assert jax_events("to_mlir", "t.eager") >= 1
    none0 = jax_events("backend_compile", "none")
    staged1 = jax_events("backend_compile", "t.eager")
    jax.block_until_ready(fresh(1249))
    assert jax_events("backend_compile", "none") - none0 >= 1
    assert jax_events("backend_compile", "t.eager") == staged1
    secs = _family("zoo_jax_compile_seconds_total")
    assert secs[(("event", "backend_compile"), ("stage", "t.eager"))] > 0


def test_backend_compile_covers_a_persistent_cache_hits_retrieval(tmp_path):
    """What the family's doc string says of JAX 0.9.0: the retrieval of a
    persistent-cache hit lies inside ``backend_compile``'s interval."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def run(stage):
            f = jax.jit(lambda x: jnp.cos(x) * 1.7 + x.sum())
            with trace.stage(stage):
                jax.block_until_ready(f(np.ones((3, 11), np.float32)))
            jax.clear_caches()

        run("t.pc_cold")
        if not list(tmp_path.iterdir()):
            pytest.skip("no persistent compilation cache on this backend")
        assert jax_events("cache_miss", "t.pc_cold") >= 1
        hit0 = jax_events("cache_hit", "t.pc_warm")
        run("t.pc_warm")
        assert jax_events("cache_hit", "t.pc_warm") - hit0 >= 1
        secs = _family("zoo_jax_compile_seconds_total")
        got = secs[(("event", "cache_retrieval"), ("stage", "t.pc_warm"))]
        around = secs[(("event", "backend_compile"), ("stage", "t.pc_warm"))]
        assert 0 < got <= around
    finally:
        jax.config.update("jax_compilation_cache_dir", keep[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          keep[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          keep[2])
        cc.reset_cache()
