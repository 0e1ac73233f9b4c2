"""The train step under every layout the engine places, against one device.

The engine trains one way: `TrainEngine._train_step`, jitted over the mesh,
with GSPMD owning every collective. What differs between runs is where the
state rests: replicated over a dp mesh (the default, what the four-chip
ResNet cell runs), or laid out by `SpecLayout` over fsdp x tp. Each case
trains the same float32 net on the same global batches under one layout
and on a one-device mesh, and compares canonical (checkpoint-form)
parameters and per-epoch losses.

Tolerances. Two XLA programs that compute the same sums in another order
agree to rounding, not to the bit, and how far apart they land depends on
the XLA build and the CPU it vectorises for. The largest gaps measured over
every case of a family on the host this file was written on (jax 0.9.0,
XLA:CPU, 8 virtual devices, `jax_default_matmul_precision=highest`) are in
the comment beside each constant; the constant is ten times that, and never
under 1e-6 (a handful of float32 ulps of an O(1) weight), so that a case
which happens to be bit-equal here has room on another build. Nothing here
compares two layouts with `==`.
"""

import numpy as np
import pytest

import jax
import flax.linen as nn
import optax

from analytics_zoo_tpu.orca.learn.engine import TrainEngine
from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
from analytics_zoo_tpu.orca.learn.utils import data_to_iterator
from analytics_zoo_tpu.parallel.mesh import create_mesh
from analytics_zoo_tpu.parallel.sharding import FsdpPlan, SpecLayout
from analytics_zoo_tpu.parallel.tensor_parallel import TPMLP

# largest |layout - one device| over a family's cases, canonical params and
# epoch losses alike (measured, see the module docstring), times ten and
# rounded up
TOL_STEP = 5e-6     # measured 4.1e-7 (dp=8: 15 optimizer x clip cases)
TOL_LAYOUT = 5e-6   # measured 4.1e-7 (SpecLayout: 15 layout x optimizer cases)
TOL_CKPT = 2e-6     # measured 1.5e-7 (12 ordered pairs of layouts)
TOL_FUSED = 1e-6    # measured 0 (the scan's body is the step itself)
TOL_STATS = 1e-6    # measured 1.9e-9 (BatchNorm's running mean and variance)


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(64)(x))
        x = nn.relu(nn.Dense(32)(x))
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(1)(x)[:, 0]


class TPNet(nn.Module):
    """Dense layers that ride the fsdp buckets around one block whose
    kernels declare tp specs: both halves of `SpecLayout` in one tree."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(32)(x))
        x = TPMLP(64, out_dim=32, name="tp_mlp")(x)
        return nn.Dense(1)(x)[:, 0]


class NormDropNet(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        x = nn.Dense(32)(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        x = nn.Dropout(0.25, deterministic=not train)(nn.relu(x))
        return nn.Dense(1)(x)[:, 0]


class DropOnly(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dropout(0.5, deterministic=not train)(x)


# layout name -> (mesh axes, SpecLayout or False, model). "one" is the
# reference everything is held to: a mesh of the first device alone.
LAYOUTS = {
    "one": ({"dp": 1}, False, MLP),
    "dp8": ({"dp": -1}, False, MLP),
    "fsdp8": ({"dp": 1, "fsdp": -1}, True, MLP),
    "dp2xfsdp4": ({"dp": 2, "fsdp": 4}, True, MLP),
    "fsdp4xtp2": ({"dp": 1, "fsdp": 4, "tp": 2}, True, TPNet),
}

# Adam-like updates divide by sqrt(v) + eps: where a gradient element is
# within rounding of zero, optax's default eps of 1e-8 turns one ulp of
# difference in the gradient into a visible share of a whole step (measured
# at the default: one element of 4257 off by 5.0e-6 after 8 steps, every other
# case under 3.3e-7). 1e-6 keeps the moments doing their work and bounds
# that amplification, so that the measured gap is a property of the layout
# and not of which element a host happens to round the other way.
_EPS = 1e-6

OPTIMIZERS = {
    "sgd": lambda: optax.sgd(0.05),
    "sgd_momentum": lambda: optax.sgd(0.05, momentum=0.9),
    "adam": lambda: optax.adam(1e-2, eps=_EPS),
    "adamw": lambda: optax.adamw(1e-2, eps=_EPS, weight_decay=0.01),
    "rmsprop": lambda: optax.rmsprop(1e-2, eps=_EPS),
}

CLIPS = {
    "noclip": lambda est: None,
    "l2clip": lambda est: est.set_l2_norm_gradient_clipping(0.05),
    "constclip": lambda est: est.set_constant_gradient_clipping(-0.01, 0.01),
}


def _mesh(layout):
    axes = LAYOUTS[layout][0]
    if layout == "one":
        return create_mesh(axes, devices=jax.devices()[:1])
    return create_mesh(axes)


def _data(n=128, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(n, d).astype(np.float32),
            "y": rng.rand(n).astype(np.float32)}


def _est(layout, optimizer="sgd", model=None, clip="noclip", fuse=1, **kw):
    _, sharded, default_model = LAYOUTS[layout]
    est = TPUEstimator((model or default_model)(), loss="mse",
                       optimizer=OPTIMIZERS[optimizer](),
                       seed=0, mesh=_mesh(layout),
                       config={"steps_per_dispatch": fuse},
                       sharding=SpecLayout() if sharded else False, **kw)
    CLIPS[clip](est)
    return est


def _fit(est, epochs=2, **kw):
    stats = est.fit(dict(_data()), epochs=epochs, batch_size=32,
                    verbose=False, **kw)
    return np.asarray([s["train_loss"] for s in stats])


def _first_batch(est, data):
    """The first batch as `fit` would place it, on a built engine."""
    it = data_to_iterator(dict(data), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    batch = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in batch.x))
    return batch


def _canon(est):
    """Parameters in canonical (checkpoint) tree form, one flat vector."""
    tree = est.engine.get_state()["params"]
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def _assert_same_run(got, want, tol):
    (loss_g, est_g), (loss_w, est_w) = got, want
    np.testing.assert_allclose(loss_g, loss_w, rtol=0, atol=tol)
    np.testing.assert_allclose(_canon(est_g), _canon(est_w), rtol=0,
                               atol=tol)


def _run(layout, model=None, **kw):
    est = _est(layout, model=model, **kw)
    return _fit(est), est


# --- the dp step against one device ------------------------------------------
@pytest.mark.parametrize("clip", sorted(CLIPS))
@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
def test_step_matches_one_device(orca_context, optimizer, clip):
    """dp=8 under GSPMD, same global batches, 2 epochs (8 steps)."""
    _assert_same_run(_run("dp8", optimizer=optimizer, clip=clip),
                     _run("one", optimizer=optimizer, clip=clip), TOL_STEP)


# --- SpecLayout against one device --------------------------------------------
@pytest.mark.parametrize("optimizer", sorted(OPTIMIZERS))
@pytest.mark.parametrize("layout", ["fsdp8", "dp2xfsdp4", "fsdp4xtp2"])
def test_layout_matches_one_device(orca_context, layout, optimizer):
    model = LAYOUTS[layout][2]
    got = _run(layout, optimizer=optimizer)
    assert got[1].engine.fsdp_plan is not None
    _assert_same_run(got, _run("one", model=model, optimizer=optimizer),
                     TOL_LAYOUT)


# --- checkpoints across layouts -----------------------------------------------
_CKPT_LAYOUTS = ["one", "dp8", "fsdp8", "fsdp4xtp2"]


@pytest.mark.parametrize(
    "src,dst", [(s, d) for s in _CKPT_LAYOUTS for d in _CKPT_LAYOUTS
                if s != d])
def test_checkpoint_restores_across_layouts(orca_context, tmp_path, src, dst):
    """Save after epoch 1 under `src`, restore under `dst`, train epoch 2:
    the uninterrupted two-epoch run on one device, within tolerance.
    Checkpoints hold canonical trees, so no layout knows of another."""
    whole = _run("one", model=TPNet, optimizer="adam")
    first = _est(src, model=TPNet, optimizer="adam")
    loss1 = _fit(first, epochs=1)
    first.save_checkpoint(str(tmp_path), blocking=True)
    second = _est(dst, model=TPNet, optimizer="adam")
    second.load_checkpoint(str(tmp_path))
    assert second.engine.step == first.engine.step == 4
    loss2 = _fit(second, epochs=1, initial_epoch=1)
    _assert_same_run((np.concatenate([loss1, loss2]), second), whole,
                     TOL_CKPT)


# --- fused dispatch -------------------------------------------------------------
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("layout", ["dp8", "fsdp8", "dp2xfsdp4", "fsdp4xtp2"])
def test_fused_dispatch_matches_sequential(orca_context, layout, k):
    """k steps scanned in one program against k dispatches of the step,
    under the same layout."""
    fused = _run(layout, optimizer="adam", fuse=k)
    assert fused[1].engine._jit_train_multi is not None
    _assert_same_run(fused, _run(layout, optimizer="adam"), TOL_FUSED)


# --- what makes a multi-chip run comparable with a one-chip reference -----------
@pytest.mark.parametrize("layout", ["dp8", "fsdp8"])
def test_batch_stats_are_global(orca_context, layout):
    """After one step, BatchNorm's running statistics are those of the
    whole batch (not a replica's share averaged), and equal one device's."""
    data = _data(n=64)

    def one_step(name):
        est = _est(name, model=NormDropNet)
        x0 = data["x"][:1]
        est.engine.build((x0,))
        start = jax.device_get(est.engine.get_state()["params"])
        est.fit(dict(data), epochs=1, batch_size=64, verbose=False)
        assert est.engine.step == 1
        stats = jax.device_get(est.engine.extra_vars["batch_stats"])
        return start, jax.tree_util.tree_leaves(stats)

    start, got = one_step(layout)
    _, want = one_step("one")
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=TOL_STATS)
    # and from first principles: flax keeps 0.99 of the old statistic
    dense = start["Dense_0"]
    h = data["x"] @ np.asarray(dense["kernel"]) + np.asarray(dense["bias"])
    stats = dict(zip(("mean", "var"), got))     # leaves sort: mean, var
    np.testing.assert_allclose(stats["mean"], 0.01 * h.mean(0), rtol=0,
                               atol=TOL_STATS)
    np.testing.assert_allclose(stats["var"], 0.99 + 0.01 * h.var(0),
                               rtol=0, atol=TOL_STATS)


def test_dropout_mask_does_not_depend_on_layout(orca_context):
    """The step's dropout key is the seed folded with the step, nothing of
    the mesh: every layout drops the same elements of the global batch."""
    data = _data(n=32)

    def mask(name):
        est = _est(name, model=DropOnly)
        batch, eng = _first_batch(est, data), est.engine
        rng = jax.random.fold_in(jax.random.PRNGKey(eng.seed), 0)
        out, _ = jax.jit(lambda x: eng._apply(
            eng.params, eng.extra_vars, x, True, rng))(batch.x)
        return np.asarray(out) != 0

    want = mask("one")
    assert 0.3 < want.mean() < 0.7
    for name in ("dp8", "fsdp8"):
        assert (mask(name) == want).all(), name


# --- state bytes ----------------------------------------------------------------
@pytest.mark.parametrize("layout", ["fsdp8", "fsdp4xtp2"])
def test_state_bytes_per_device(orca_context, layout):
    """A device holds 1/fsdp of every bucket and of its Adam moments, its
    tp share of the held leaves, and whole what neither axis splits."""
    est = _est(layout, optimizer="adam")
    x0 = _data()["x"][:1]
    est.engine.build((x0,))
    eng, plan = est.engine, est.engine.fsdp_plan
    n = plan.n_dev
    ridden = plan.layout.padded_total * 4
    held = sum(
        int(np.prod(leaf.sharding.shard_shape(leaf.shape))) * 4
        for leaf in jax.tree_util.tree_leaves(eng.params[FsdpPlan.HELD_KEY]))
    # Adam: two moments shaped like the parameters, and one int32 count
    assert eng.per_device_state_bytes() == 3 * (ridden // n + held) + 4
    one = _est("one", model=LAYOUTS[layout][2], optimizer="adam")
    one.engine.build((x0,))
    assert eng.per_device_state_bytes() * 2 < one.engine.per_device_state_bytes()


# --- executables ----------------------------------------------------------------
def _train_key(layout, cache):
    est = _est(layout, compile_cache=cache)
    batch = _first_batch(est, _data())
    fn = est.engine.ensure_jit_train()
    return fn.cache_key(*est.engine.train_step_args(batch))


@pytest.mark.parametrize("layout", ["dp8", "fsdp8"])
def test_executables_shared_by_layout(orca_context, layout):
    """Two engines of one layout compile once; a one-device engine never
    takes a dp=8 engine's executable, nor a sharded one's."""
    from analytics_zoo_tpu.compile.cache import ExecutableCache
    cache = ExecutableCache()
    assert _train_key(layout, cache) == _train_key(layout, cache)
    assert _train_key(layout, cache) != _train_key("one", cache)
    a, b = _est(layout, compile_cache=cache), _est(layout,
                                                   compile_cache=cache)
    _fit(a, epochs=1)
    compiles = cache.stats.counts("train")["compiles"]
    assert compiles == 1
    _fit(b, epochs=1)
    assert cache.stats.counts("train")["compiles"] == compiles


# --- the default step -----------------------------------------------------------
def test_default_step_is_train_step_and_deterministic(orca_context):
    """An estimator with no option set runs `TrainEngine._train_step`
    itself, and a seed fixes its weights: two runs of one program on one
    layout are equal to the bit."""
    (l0, e0), (l1, e1) = _run("dp8", optimizer="adam"), \
        _run("dp8", optimizer="adam")
    assert e0.engine.sharding is None and e0.engine.fsdp_plan is None
    assert e0.engine._jit_train._fn.__func__ is TrainEngine._train_step
    assert (l0 == l1).all()
    assert (_canon(e0) == _canon(e1)).all()


# --- checkpoints an earlier version wrote ----------------------------------------
def test_state_from_a_comms_run_restores(orca_context, tmp_path):
    """Checkpoints written while the engine had an explicit dp wire carry
    an error-feedback residual, its layout's signature and a manifest
    entry. They hold canonical trees like any other: the state restores,
    the stray keys are ignored."""
    from analytics_zoo_tpu.ckpt import read_manifest
    src = _est("dp8", optimizer="adam")
    _fit(src, epochs=1)
    state = src.engine.get_state()
    old = dict(state, comms_resid=np.zeros((8, 3968), np.float32),
               comms_layout_sig="0123456789abcdef")
    plane = src._ckpt(str(tmp_path))
    path = plane.save(old, src.engine.step, blocking=True,
                      meta={"comms": {"sharded_update": True,
                                      "wire_dtype": "int8", "buckets": 1}})
    assert read_manifest(path)["meta"]["comms"]["sharded_update"] is True
    for layout in ("dp8", "fsdp8"):
        dst = _est(layout, optimizer="adam")
        dst.load_checkpoint(str(tmp_path))
        back = dst.engine.get_state()
        assert set(back) == set(state)
        for a, b in zip(jax.tree_util.tree_leaves(back["params"]),
                        jax.tree_util.tree_leaves(state["params"])):
            assert (np.asarray(a) == np.asarray(b)).all()
        assert np.isfinite(_fit(dst, epochs=1, initial_epoch=1)).all()
