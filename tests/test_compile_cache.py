"""Compile plane suite: shared + persistent XLA executable cache.

The claims under test mirror ISSUE 3's acceptance criteria: structurally
identical engines share ONE executable even when scalar hyperparameters
differ (hyperparams-as-arguments), sharing never changes numerics
(bit-identical losses vs the baked-constant/uncached path), structural
changes (clip constants, mesh, shapes) miss, executables round-trip
through the disk cache (or degrade cleanly), the stats counters account
compiles/hits/seconds-saved, and a TrialRuntime study logs
``compile``/``cache_hit`` events while an entire scalar-hyperparam rung
compiles exactly once.
"""

import json
import os

import numpy as np
import pytest

import flax.linen as nn

from analytics_zoo_tpu.compile import ExecutableCache
from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
from analytics_zoo_tpu.orca.learn.optimizers import Adam


class _MLP(nn.Module):
    hidden: int = 8

    @nn.compact
    def __call__(self, x):
        return nn.Dense(1)(nn.relu(nn.Dense(self.hidden)(x)))[:, 0]


def _data(n=64, features=4, seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.rand(n, features).astype(np.float32),
            "y": r.rand(n).astype(np.float32)}


def _estimator(cache, lr=1e-3, **kw):
    # steps_per_dispatch pinned to 1: these tests count single-step
    # executables, not fuse-probe behavior (covered separately below)
    return TPUEstimator(_MLP(), loss="mse", optimizer=Adam(lr=lr),
                        config={"steps_per_dispatch": 1},
                        compile_cache=cache, **kw)


def _losses(stats):
    return [e["train_loss"] for e in stats]


# --- sharing across scalar hyperparameters ----------------------------------

def test_two_engines_different_lr_share_one_executable(orca_context):
    """Two engines with identical structure but different lr must share ONE
    train-step executable (lr rides in opt_state via inject_hyperparams),
    and the shared path must be bit-identical to the baked-constant
    uncached path."""
    data = _data()
    cache = ExecutableCache()
    est1 = _estimator(cache, lr=1e-3)
    est1.fit(data, epochs=2, batch_size=16, shuffle=False, verbose=False)
    snap = cache.stats.counts("train")
    assert snap["compiles"] == 1 and snap["cache_hits"] == 0

    est2 = _estimator(cache, lr=1e-1)
    s2 = est2.fit(data, epochs=2, batch_size=16, shuffle=False,
                  verbose=False)
    snap = cache.stats.counts("train")
    assert snap["compiles"] == 1, "second lr must NOT compile again"
    assert snap["cache_hits"] == 1

    # bit-identical to the baked-constant path: same lr, lr baked into the
    # jit as a constant, compile plane off
    import optax
    est3 = TPUEstimator(_MLP(), loss="mse", optimizer=optax.adam(1e-1),
                        config={"steps_per_dispatch": 1},
                        compile_cache=False)
    s3 = est3.fit(data, epochs=2, batch_size=16, shuffle=False,
                  verbose=False)
    assert _losses(s2) == _losses(s3)


def test_identical_refit_is_a_cache_hit_and_bit_identical(orca_context):
    """Acceptance: a second in-process fit of an identical model reports a
    cache hit, with losses bit-identical to the uncached (plain-jit)
    path."""
    data = _data()
    cache = ExecutableCache()
    est1 = _estimator(cache, lr=3e-3)
    s1 = est1.fit(data, epochs=2, batch_size=16, shuffle=False,
                  verbose=False)
    est2 = _estimator(cache, lr=3e-3)
    s2 = est2.fit(data, epochs=2, batch_size=16, shuffle=False,
                  verbose=False)
    snap = cache.stats.counts("train")
    assert snap["compiles"] == 1 and snap["cache_hits"] == 1
    assert _losses(s1) == _losses(s2)

    uncached = _estimator(False, lr=3e-3)
    s3 = uncached.fit(data, epochs=2, batch_size=16, shuffle=False,
                      verbose=False)
    assert _losses(s2) == _losses(s3)
    # plain jit, not a CachedFunction
    assert not hasattr(uncached.engine.ensure_jit_train(), "cache_key")


# --- structural changes must miss -------------------------------------------

def test_cache_miss_on_clip_change(orca_context):
    data = _data()
    cache = ExecutableCache()
    est = _estimator(cache)
    est.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    assert cache.stats.counts("train")["compiles"] == 1
    est.set_l2_norm_gradient_clipping(1.0)
    est.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    snap = cache.stats.counts("train")
    assert snap["compiles"] == 2, "clip constants are part of the program"
    # same clip config from a fresh engine: hit again
    est2 = _estimator(cache)
    est2.set_l2_norm_gradient_clipping(1.0)
    est2.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    assert cache.stats.counts("train")["compiles"] == 2
    assert cache.stats.counts("train")["cache_hits"] >= 1


def test_cache_miss_on_shape_change(orca_context):
    data = _data()
    cache = ExecutableCache()
    est = _estimator(cache)
    est.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    est.fit(data, epochs=1, batch_size=32, shuffle=False, verbose=False)
    assert cache.stats.counts("train")["compiles"] == 2


def test_cache_miss_on_mesh_change(orca_context):
    import jax
    from jax.sharding import Mesh

    devs = jax.local_devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    sub = Mesh(np.asarray(devs[:4]).reshape(4, 1, 1, 1),
               ("dp", "fsdp", "tp", "sp"))
    data = _data()
    cache = ExecutableCache()
    est1 = _estimator(cache)
    est1.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    est2 = _estimator(cache, mesh=sub)
    est2.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    snap = cache.stats.counts("train")
    assert snap["compiles"] == 2, "a different mesh is a different program"


# --- persistence ------------------------------------------------------------

def test_disk_round_trip_or_clean_fallback(orca_context, tmp_path):
    """A second cache instance over the same directory (a simulated warm
    restart) must either load the executable from disk (serialization
    supported — it is on CPU PJRT) or recompile cleanly; numerics are
    identical either way."""
    data = _data()
    cache1 = ExecutableCache(cache_dir=str(tmp_path))
    s1 = _estimator(cache1).fit(data, epochs=1, batch_size=16,
                                shuffle=False, verbose=False)
    assert cache1.stats.counts("train")["compiles"] == 1

    cache2 = ExecutableCache(cache_dir=str(tmp_path))
    s2 = _estimator(cache2).fit(data, epochs=1, batch_size=16,
                                shuffle=False, verbose=False)
    snap = cache2.stats.counts("train")
    # disk hit when the backend serializes; clean recompile otherwise —
    # never a crash, never a numeric change
    assert snap["disk_hits"] + snap["compiles"] >= 1
    if snap["disk_hits"]:
        assert snap["compiles"] == 0
    assert _losses(s1) == _losses(s2)


def test_fuse_probe_persisted_across_restart(orca_context, tmp_path):
    """Satellite: the estimator's auto fuse-probe result rides the disk
    cache keyed by the train step's structural key — a warm restart skips
    the probe's timing dispatches AND the state snapshot, not just the
    compile."""
    data = _data(n=128)
    cache1 = ExecutableCache(cache_dir=str(tmp_path))
    est1 = TPUEstimator(_MLP(), loss="mse", optimizer=Adam(lr=1e-3),
                        compile_cache=cache1)
    est1.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    k1 = next(iter(est1._fuse_probe_cache.values()))
    aux_files = [f for f in os.listdir(tmp_path) if f.startswith("aux-fuse")]
    assert aux_files, "probe result must be persisted"

    cache2 = ExecutableCache(cache_dir=str(tmp_path))
    est2 = TPUEstimator(_MLP(), loss="mse", optimizer=Adam(lr=1e-3),
                        compile_cache=cache2)
    # the probe needs a device-state snapshot; the persisted path must not
    est2.engine.snapshot = lambda: pytest.fail(
        "fuse probe ran despite a persisted result")
    est2.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    assert next(iter(est2._fuse_probe_cache.values())) == k1


# --- stats ------------------------------------------------------------------

def test_stats_counters_and_reset(orca_context):
    data = _data()
    cache = ExecutableCache()
    _estimator(cache).fit(data, epochs=1, batch_size=16, shuffle=False,
                          verbose=False)
    _estimator(cache).fit(data, epochs=1, batch_size=16, shuffle=False,
                          verbose=False)
    snap = cache.stats.snapshot()
    assert snap["compiles"] >= 1
    assert snap["cache_hits"] >= 1
    assert snap["compile_s"] > 0
    assert snap["saved_s"] > 0
    assert snap["fallbacks"] == 0
    assert "train" in snap["by_label"]
    cache.stats.reset()
    zero = cache.stats.snapshot()
    assert zero["compiles"] == 0 and zero["by_label"] == {}


def test_data_pipeline_stats_carries_compile_section(orca_context):
    data = _data()
    est = _estimator(ExecutableCache())
    est.fit(data, epochs=1, batch_size=16, shuffle=False, verbose=False)
    snap = est.data_pipeline_stats()
    assert snap["compile"]["compiles"] >= 1


# --- serving ----------------------------------------------------------------

def test_serving_precompile_counts_and_shares(orca_context):
    import jax
    from analytics_zoo_tpu.pipeline.inference import InferenceModel
    from analytics_zoo_tpu.serving import ClusterServing, InMemoryBroker

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(3)(x)

    cache = ExecutableCache()
    module = Net()
    variables = module.init(jax.random.PRNGKey(0),
                            np.zeros((1, 4), np.float32))
    model = InferenceModel(compile_cache=cache).load_jax(module, variables)
    serving = ClusterServing(model, queue=InMemoryBroker(),
                             batch_size=8).start(
        example=np.zeros((2, 4), np.float32))
    try:
        warm = cache.stats.counts("serving")
        assert warm["compiles"] >= 1 and warm["cache_hits"] == 0
        metrics = serving.metrics()
        assert metrics["compile"]["compiles"] == warm["compiles"]
    finally:
        serving.stop()

    # a second worker serving the same program compiles nothing
    model2 = InferenceModel(compile_cache=cache).load_jax(
        Net(), Net().init(jax.random.PRNGKey(1),
                          np.zeros((1, 4), np.float32)))
    model2.precompile(np.zeros((2, 4), np.float32), max_bucket=8)
    after = cache.stats.counts("serving")
    assert after["compiles"] == warm["compiles"]
    assert after["cache_hits"] >= 1


# --- AutoML: one compile per rung + study event log -------------------------

def _mlp_builder():
    from analytics_zoo_tpu.automl.model_builder import ModelBuilder

    def model_creator(config):
        return _MLP()

    return ModelBuilder(model_creator, loss_creator=lambda c: "mse")


def test_asha_rung_compiles_once_and_logs_events(orca_context, tmp_path):
    """Acceptance: a 4-trial study over scalar lr (same model/shape) on one
    chip performs exactly ONE train-step compile; the study's JSONL event
    log records the compile and every reuse as ``compile``/``cache_hit``
    lines."""
    import jax
    from analytics_zoo_tpu.automl.scheduler.runtime import TrialRuntime
    from analytics_zoo_tpu.automl.search.search_engine import Trial

    cache = ExecutableCache()
    trials = [Trial(i, {"lr": lr, "batch_size": 16,
                        "steps_per_dispatch": 1})
              for i, lr in enumerate([1e-3, 3e-3, 1e-2, 3e-2])]
    runtime = TrialRuntime(
        trials, _mlp_builder(), _data(), metric="mse", metric_mode="min",
        max_t=2, eta=2, grace_period=1,
        devices=[jax.local_devices()[0]],     # one chip = one device key
        compile_cache=cache, logs_dir=str(tmp_path))
    done = runtime.run(resume=False)
    assert all(t.state == "done" for t in done)

    snap = cache.stats.counts("train")
    assert snap["compiles"] == 1, \
        f"an entire scalar-hyperparam rung must compile once, got {snap}"
    assert snap["cache_hits"] == 3

    events = [json.loads(line) for line in
              open(os.path.join(tmp_path, "study_events.jsonl"))]
    kinds = {e["event"] for e in events}
    assert "compile" in kinds and "cache_hit" in kinds
    compile_events = [e for e in events if e["event"] == "compile"]
    assert all({"label", "key", "seconds"} <= set(e) for e in compile_events)
    assert runtime.summary()["compile"]["cache_hits"] >= 3


# --- cache placement ---------------------------------------------------------

def _placement(monkeypatch, backend, env_dir=None, zoo_dir=None):
    """configure_compile_cache against a throwaway cache and a recording
    jax.config.update (returned as the dict of updates): no process-wide
    state is touched."""
    import jax

    from analytics_zoo_tpu.compile import cache as cache_mod
    updates, local = {}, ExecutableCache()
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(cache_mod, "get_compile_cache", lambda: local)
    for name, value in (("JAX_COMPILATION_CACHE_DIR", env_dir),
                        ("ZOO_COMPILE_CACHE", zoo_dir)):
        if value is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, value)
    return updates, local


def test_cache_placement_env_var_wins_and_is_not_set_in_code(
        tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the executable store moves there and
    no code path updates jax_compilation_cache_dir — JAX reads the variable
    itself, and whoever set it owns the placement."""
    from analytics_zoo_tpu.compile import configure_compile_cache
    env_dir = str(tmp_path / "from-env")
    updates, local = _placement(monkeypatch, "tpu", env_dir=env_dir,
                            zoo_dir=str(tmp_path / "ignored"))
    assert configure_compile_cache(str(tmp_path / "also-ignored")) == env_dir
    assert "jax_compilation_cache_dir" not in updates
    assert local.cache_dir == env_dir and os.path.isdir(env_dir)
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_cache_placement_defaults(tmp_path, monkeypatch):
    """Nothing set: an accelerator gets ONE fixed git-ignored path in the
    checkout (the path is part of JAX's key: no tempdir, pid or timestamp);
    the CPU backend persists nothing, so tests never turn compiles into
    disk hits. An explicit directory still works on either."""
    from analytics_zoo_tpu.compile import (DEFAULT_CACHE_DIR,
                                           configure_compile_cache)
    from analytics_zoo_tpu.compile import cache as cache_mod
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_CACHE_DIR == os.path.join(root, ".zoo_compile_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".zoo_compile_cache/" in f.read().split()

    updates, local = _placement(monkeypatch, "cpu")
    assert configure_compile_cache() is None
    assert not updates and local.cache_dir is None

    fixed = str(tmp_path / "fixed")
    monkeypatch.setattr(cache_mod, "DEFAULT_CACHE_DIR", fixed)
    updates, local = _placement(monkeypatch, "tpu")
    assert configure_compile_cache() == fixed
    assert updates["jax_compilation_cache_dir"] == fixed
    assert local.cache_dir == fixed

    explicit = str(tmp_path / "explicit")
    updates, local = _placement(monkeypatch, "cpu", zoo_dir=explicit)
    assert configure_compile_cache() == explicit
    assert updates["jax_compilation_cache_dir"] == explicit


def test_cache_files_land_under_the_env_dir(tmp_path):
    """End to end in a fresh process: with JAX_COMPILATION_CACHE_DIR set,
    JAX's own entries and the exe-*.pkl store land in that directory and
    nowhere else."""
    import subprocess
    import sys
    cache = tmp_path / "cache"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import jax, jax.numpy as jnp\n"
        "from analytics_zoo_tpu import init_orca_context\n"
        "from analytics_zoo_tpu.compile import get_compile_cache\n"
        "init_orca_context('local')\n"
        "f = get_compile_cache().wrap(lambda x: jnp.sin(x) * 2, label='t')\n"
        "f(jnp.ones((8, 8))).block_until_ready()\n"
        "jax.jit(lambda x: jnp.cos(x) + 1)(jnp.ones((4, 4)))"
        ".block_until_ready()\n"
        "assert jax.config.jax_compilation_cache_dir == "
        f"{str(cache)!r}\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("ZOO_COMPILE_CACHE", None)
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   cwd=str(tmp_path), timeout=120)
    names = os.listdir(cache)
    assert any(n.startswith("exe-") and n.endswith(".pkl") for n in names)
    assert any(n.startswith("jit_") for n in names), names
    assert os.listdir(tmp_path) == ["cache"]
