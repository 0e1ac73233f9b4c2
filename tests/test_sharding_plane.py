"""Sharding plane (PR 17): canonical SpecLayout over the (dp, fsdp, tp)
mesh — fsdp bucketed param gathers + tp layers through one layout object
(parallel/sharding.py + engine + serving).

Numerics contract under test, on the 8-device f32 CPU mesh:

* BucketLayout's flatten/unflatten and FsdpPlan's composite ↔ canonical
  tree conversions round-trip bit-exactly (host-side data movement, no
  arithmetic);
* sharded (fsdp×tp) training agrees with replicated training on the SAME
  mesh to rounding — two XLA programs, so never `==`; each tolerance is
  ten times the gap measured here (tests/test_train_layouts.py holds every
  layout to one device the same way);
* checkpoints store canonical tree form, so fsdp-sharded ↔ replicated
  restores are bit-exact in BOTH directions;
* serving through a sharded InferenceModel predicts what the replicated
  layout predicts to a few ulps while each device holds ~1/fsdp of the
  weights;
* the compiled train program's per-axis collectives match the engine's
  declared accounting (hlo_lint's accounting rule).
"""

import numpy as np
import pytest

import jax
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
from analytics_zoo_tpu.parallel.mesh import create_mesh, parse_mesh_axes
from analytics_zoo_tpu.parallel.sharding import (BucketLayout, FsdpPlan,
                                                 SpecLayout)
from analytics_zoo_tpu.parallel.tensor_parallel import TPMLP


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(64)(x))
        x = nn.relu(nn.Dense(32)(x))
        return nn.Dense(1)(x)[:, 0]


class TPNet(nn.Module):
    """fsdp-ridden Dense layers around one tp block — both plane halves
    coexist in a single param tree."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(32)(x))
        x = TPMLP(64, out_dim=32, name="tp_mlp")(x)
        return nn.Dense(1)(x)[:, 0]


def _data(n=192, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(n, d).astype(np.float32),
            "y": rng.rand(n).astype(np.float32)}


def _est(mesh, model, sharding, optimizer="sgd", **kw):
    return TPUEstimator(model, loss="mse", optimizer=optimizer, seed=0,
                        mesh=mesh, config={"steps_per_dispatch": 1},
                        sharding=sharding, **kw)


def _fit(mesh, model, sharding, optimizer="sgd", epochs=2, **kw):
    est = _est(mesh, model, sharding, optimizer=optimizer, **kw)
    stats = est.fit(dict(_data()), epochs=epochs, batch_size=32,
                    verbose=False)
    return [s["train_loss"] for s in stats], est


def _canon_params(est):
    """Params in canonical (checkpoint) tree form, flattened."""
    tree = est.engine.get_state()["params"]
    return np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree_util.tree_leaves(tree)])


def _tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        np.asarray(x).shape == np.asarray(y).shape
        and (np.asarray(x) == np.asarray(y)).all()
        for x, y in zip(la, lb))


def _assert_within_ulps(got, want, ulps):
    """|got - want| in float32 ulps at the largest |want|. Counting each
    element's own ulps would read the same absolute rounding as thousands
    of ulps wherever a prediction lands near zero."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    one = np.spacing(np.float32(np.abs(want).max()))
    assert np.abs(got - want).max() <= ulps * one


# two programs over one set of weights: sharded serving splits output
# features over fsdp, and XLA vectorises the narrower matmuls otherwise.
# Measured here: 1.5 ulps on these tests' own inputs, 4.1 over 180 probes
# of 20 random initialisations; ten times that, rounded up
SERVING_ULPS = 64


# --- BucketLayout: the flat vector under the fsdp buckets --------------------
def _random_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"a": {"kernel": rng.randn(7, 5).astype(np.float32),
                  "bias": rng.randn(5).astype(np.float32)},
            "b": [rng.randn(3, 3, 2).astype(np.float32),
                  rng.randn(1).astype(np.float32)],
            "c": rng.randn(131).astype(np.float32)}


def test_bucket_round_trip_bit_exact(orca_context):
    tree = _random_tree()
    lo = BucketLayout.build(tree, 8, 0.0005)     # tiny buckets -> several
    assert len(lo.bucket_sizes) > 1
    assert all(b % 8 == 0 for b in lo.bucket_sizes)
    assert lo.padded_total == sum(lo.bucket_sizes) == 8 * lo.shard_size
    assert lo.total == sum(lo.sizes) <= lo.padded_total

    flat = lo.flatten_np(tree)
    assert flat.shape == (lo.padded_total,)
    assert (flat[lo.total:] == 0).all()
    # on the host and traced (what a jitted step runs), unflatten inverts
    # the flatten exactly
    for back in (lo.unflatten(flat), jax.jit(lo.unflatten)(flat)):
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            assert a.dtype == np.asarray(b).dtype and a.shape == b.shape
            assert (np.asarray(a) == np.asarray(b)).all()


def test_layout_deterministic(orca_context):
    tree = _random_tree()
    assert BucketLayout.build(tree, 8, 0.0005).signature() == \
        BucketLayout.build(tree, 8, 0.0005).signature()
    # another bucket size, or another axis size, is another layout
    assert BucketLayout.build(tree, 8, 0.001).signature() != \
        BucketLayout.build(tree, 8, 0.0005).signature()
    assert BucketLayout.build(tree, 4, 0.0005).signature() != \
        BucketLayout.build(tree, 8, 0.0005).signature()


def test_non_f32_leaf_rejected(orca_context):
    # the flat vector is f32: ints would round, narrow floats would change
    # precision through it, so neither round-trips and both are refused
    for bad in (np.ones(4, np.int32), np.ones(4, np.float16)):
        with pytest.raises(ValueError, match="f32"):
            BucketLayout.build({"w": bad}, 8, 0.0)


def test_grad_allreduce_mean_skips_absent_axes(orca_context):
    """Regression: the default ``axes=("dp", "fsdp")`` used to raise inside
    any mesh that does not bind an ``fsdp`` axis (e.g. a user's 1-D
    ``Mesh(devices, ("dp",))``)."""
    from jax import shard_map
    from jax.sharding import Mesh

    from analytics_zoo_tpu.parallel import collective as C

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = jax.jit(shard_map(lambda v: C.grad_allreduce_mean(v),
                            mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp")))(x)
    np.testing.assert_array_equal(np.asarray(out), np.full((8, 1), 3.5))
    # but NO bound axis at all still fails loudly — a silent no-op would
    # let replicas diverge
    with pytest.raises(NameError, match="none of the axes"):
        jax.jit(lambda v: C.grad_allreduce_mean(v))(x)


# --- SpecLayout resolution + rules ------------------------------------------
def test_resolve_off_by_default(orca_context):
    assert SpecLayout.resolve({}, None) is None
    assert SpecLayout.resolve({}, False) is None
    assert SpecLayout.resolve({"sharding": True}, False) is None


def test_resolve_arg_config_env(orca_context, monkeypatch):
    assert isinstance(SpecLayout.resolve({}, True), SpecLayout)
    lay = SpecLayout.resolve({"sharding": {"bucket_mb": 2.0}}, None)
    assert lay is not None and lay.bucket_mb == 2.0
    monkeypatch.setenv("ZOO_SHARDING_PLANE", "1")
    assert isinstance(SpecLayout.resolve({}, None), SpecLayout)
    monkeypatch.setenv("ZOO_FSDP_BUCKET_MB", "0.5")
    assert SpecLayout.resolve({}, True).bucket_mb == 0.5
    # an explicit field wins over the env knob
    assert SpecLayout.resolve(
        {"sharding": {"bucket_mb": 2.0}}, None).bucket_mb == 2.0


def test_spec_rules_embed_tables_fsdp_x_tp(orca_context):
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    lay = SpecLayout()
    assert lay.spec_for(("ncf", "embed_table"), (64, 16), mesh) \
        == P("fsdp", "tp")
    # a non-dividing dim drops only that axis
    assert lay.spec_for(("m", "embed_table"), (64, 15), mesh) \
        == P("fsdp", None)
    assert lay.spec_for(("dense", "kernel"), (64, 16), mesh) == P()


def test_fsdp_leaf_spec_never_splits_contraction_dims(orca_context):
    """Serving fallback: trailing (output-feature) dim only — an inner
    split would change the matmul reduction order (partial sums +
    all-reduce) and break serving bit-identity."""
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    lay = SpecLayout()
    k = np.zeros((16, 64), np.float32)
    assert lay._fsdp_leaf_spec(k, mesh) == P(None, "fsdp")
    # trailing dim does not divide -> replicate, never the inner dim
    assert lay._fsdp_leaf_spec(np.zeros((32, 1), np.float32), mesh) == P()
    # vectors split dim 0 (bias adds are elementwise over features)
    assert lay._fsdp_leaf_spec(np.zeros((64,), np.float32), mesh) \
        == P("fsdp")
    # tiny leaves replicate
    assert lay._fsdp_leaf_spec(np.zeros((4,), np.float32), mesh) == P()


def test_batch_axes_exclude_tp(orca_context):
    lay = SpecLayout()
    assert lay.batch_axes(create_mesh({"dp": 1, "fsdp": 4, "tp": 2})) \
        == ("fsdp",)
    assert lay.batch_axes(create_mesh({"dp": 2, "fsdp": 2, "tp": 2})) \
        == ("dp", "fsdp")
    assert lay.batch_axes(create_mesh({"dp": -1})) == ("dp",)


def test_parse_mesh_axes():
    assert parse_mesh_axes("dp=1,fsdp=4,tp=2") \
        == {"dp": 1, "fsdp": 4, "tp": 2}
    assert parse_mesh_axes("dp=1,fsdp=-1")["fsdp"] == -1
    with pytest.raises(ValueError):
        parse_mesh_axes("dp=1,bogus")


def test_fingerprint_distinguishes_layouts(orca_context):
    assert SpecLayout().fingerprint() \
        != SpecLayout(bucket_mb=2.0).fingerprint()
    assert SpecLayout().fingerprint() \
        != SpecLayout(fsdp=False).fingerprint()


# --- FsdpPlan composite round-trip ------------------------------------------
def test_fsdp_plan_roundtrip_bit_exact(orca_context):
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    rng = np.random.RandomState(0)
    params = {"a": {"kernel": rng.randn(16, 64).astype(np.float32),
                    "bias": rng.randn(64).astype(np.float32)},
              "b": {"kernel": rng.randn(64, 32).astype(np.float32),
                    "tiny": rng.randn(3).astype(np.float32)}}
    specs = SpecLayout().merge_specs(params, None, mesh)
    plan = FsdpPlan.build(params, specs, mesh, bucket_mb=0.001)
    assert plan is not None
    comp = plan.to_composite(params)
    assert FsdpPlan.is_composite(comp)
    assert len(comp[FsdpPlan.FLAT_KEY]) >= 2    # multi-bucket at 1 KiB
    back = plan.composite_to_tree(comp)
    assert _tree_equal(params, back)


def test_fsdp_plan_none_when_nothing_rides(orca_context):
    params = {"w": np.zeros((16, 8), np.float32)}
    # fsdp axis of size 1 -> plane degrades to plain specs
    assert FsdpPlan.build(params, None,
                          create_mesh({"dp": -1}), axis="fsdp") is None
    # everything below the 2*axis_size floor -> nothing to bucket
    tiny = {"w": np.zeros((4,), np.float32)}
    assert FsdpPlan.build(tiny, None,
                          create_mesh({"dp": 1, "fsdp": -1})) is None


# --- sharded against replicated on one mesh ----------------------------------
def test_sharded_train_matches_replicated_sgd(orca_context):
    """fsdp×tp vs replicated on the SAME mesh, SGD, 12 steps. The gathers
    and the output-dim splits keep each element's sum in order, so the two
    programs came out equal to the bit where this was measured (gap 0);
    the bound is the floor tests/test_train_layouts.py uses."""
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    ls, es = _fit(mesh, TPNet(), SpecLayout())
    lr, er = _fit(mesh, TPNet(), False)
    assert es.engine.fsdp_plan is not None
    np.testing.assert_allclose(ls, lr, rtol=0, atol=1e-6)
    np.testing.assert_allclose(_canon_params(es), _canon_params(er),
                               rtol=0, atol=1e-6)


def test_sharded_train_adam_allclose(orca_context):
    """adam's compound sqrt/div fuses program-dependently (~1 ulp)."""
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    ls, es = _fit(mesh, MLP(), SpecLayout(), optimizer="adam")
    lr, er = _fit(mesh, MLP(), False, optimizer="adam")
    np.testing.assert_allclose(_canon_params(es), _canon_params(er),
                               rtol=0, atol=1e-6)


def test_pure_fsdp_mesh_trains(orca_context):
    losses, est = _fit(create_mesh({"dp": 1, "fsdp": -1}), MLP(),
                       SpecLayout())
    assert np.isfinite(losses).all()
    snap = est.engine.sharding_snapshot()
    assert snap["fsdp"]["axis_size"] == 8
    full = sum(int(l.nbytes) for l in
               jax.tree.leaves(est.engine.params)
               + jax.tree.leaves(est.engine.opt_state))
    # per-device param+opt bytes shrink ~1/fsdp — the capacity headline
    assert snap["per_device_state_bytes"] * 4 < full


# --- checkpoint contract -----------------------------------------------------
def test_ckpt_cross_restore_both_directions(orca_context, tmp_path):
    """Canonical tree-form checkpoints: sharded save -> replicated load
    and replicated save -> sharded load, both bit-exact (the PR 8/12
    contract extended to the params)."""
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    _, es = _fit(mesh, MLP(), SpecLayout(),
                 model_dir=str(tmp_path / "s"))
    _, er = _fit(mesh, MLP(), False, model_dir=str(tmp_path / "r"))
    es.save_checkpoint(str(tmp_path / "s"), blocking=True)
    er.save_checkpoint(str(tmp_path / "r"), blocking=True)

    # sharded ckpt -> replicated engine
    er2 = _est(mesh, MLP(), False)
    er2.load_checkpoint(str(tmp_path / "s"))
    assert _tree_equal(er2.engine.get_state()["params"],
                       es.engine.get_state()["params"])
    # replicated ckpt -> sharded engine (params arrive composite inside)
    es2 = _est(mesh, MLP(), SpecLayout())
    es2.load_checkpoint(str(tmp_path / "r"))
    assert _tree_equal(es2.engine.get_state()["params"],
                       er.engine.get_state()["params"])
    assert _tree_equal(es2.engine.get_state()["opt_state"],
                       er.engine.get_state()["opt_state"])


def test_ckpt_manifest_records_sharding(orca_context, tmp_path):
    from analytics_zoo_tpu.ckpt import read_manifest
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    _, est = _fit(mesh, MLP(), SpecLayout(),
                  model_dir=str(tmp_path / "m"))
    path = est.save_checkpoint(str(tmp_path / "m"), blocking=True)
    meta = read_manifest(path).get("meta") or {}
    assert meta.get("sharding", {}).get("fsdp") is True


# --- serving -----------------------------------------------------------------
def test_serving_sharded_matches_replicated(orca_context):
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    m = MLP()
    x0 = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    variables = m.init(jax.random.PRNGKey(0), x0)
    shd = InferenceModel(mesh=mesh, sharding=SpecLayout()).load_jax(
        m, variables)
    rep = InferenceModel(mesh=mesh).load_jax(m, variables)
    xq = np.random.RandomState(1).randn(13, 16).astype(np.float32)
    _assert_within_ulps(shd.predict(xq), rep.predict(xq), SERVING_ULPS)

    def dev_bytes(model):
        return sum(int(leaf.addressable_shards[0].data.nbytes)
                   for leaf in jax.tree_util.tree_leaves(model._variables))

    assert dev_bytes(shd) < dev_bytes(rep)
    # batch shards over (dp, fsdp) only; buckets round to that divisor
    assert shd._data_spec == P(("fsdp",))
    assert all(b % 4 == 0 for b in shd.buckets)


def test_serving_hot_swap_keeps_layout(orca_context):
    from analytics_zoo_tpu.pipeline.inference.inference_model import \
        InferenceModel
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    m = MLP()
    x0 = np.zeros((4, 16), np.float32)
    v1 = m.init(jax.random.PRNGKey(0), x0)
    v2 = m.init(jax.random.PRNGKey(1), x0)
    im = InferenceModel(mesh=mesh, sharding=SpecLayout()).load_jax(m, v1)
    im._hot_swap("p", {"module": m,
                       "state": {"params": jax.device_get(v2["params"]),
                                 "extra_vars": {}}}, 7)
    rep = InferenceModel(mesh=mesh).load_jax(m, v2)
    xq = np.random.RandomState(2).randn(9, 16).astype(np.float32)
    _assert_within_ulps(im.predict(xq), rep.predict(xq), SERVING_ULPS)
    shards = {str(l.sharding.spec) for l in
              jax.tree_util.tree_leaves(im._variables)}
    assert any("fsdp" in s for s in shards)


# --- embedding tables (friesian / NCF layout) -------------------------------
def test_embed_table_shards_over_fsdp_x_tp(orca_context):
    class Rec(nn.Module):
        @nn.compact
        def __call__(self, ids):
            table = self.param("embed_table", nn.initializers.normal(),
                               (64, 16))
            return table[ids].sum(axis=-1)

    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    variables = Rec().init(jax.random.PRNGKey(0),
                           np.zeros((4,), np.int32))
    sh = SpecLayout().param_shardings(mesh, variables)
    spec = sh["params"]["embed_table"].spec
    assert spec == P("fsdp", "tp")


# --- compiled-program accounting --------------------------------------------
def test_compiled_accounting_verified(orca_context):
    """hlo_lint's sharding rule on the COMPILED program (collectives only
    exist post-SPMD-partitioner): fsdp gathers in whole sweeps with
    declared bytes, grad combine present, tp collective present."""
    from analytics_zoo_tpu.analysis.hlo_lint import (
        HloLinter, collectives_by_mesh_axes, declared_accounting,
        parse_collectives)
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    est = TPUEstimator(TPNet(), loss="mse", optimizer="sgd", seed=0,
                       mesh=mesh, config={"steps_per_dispatch": 1},
                       sharding=SpecLayout())
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator
    it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    b0 = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in b0.x))
    fn = est.engine.ensure_jit_train()
    text = fn.lower(*est.engine.train_step_args(b0)).compile().as_text()
    declared = declared_accounting(est.engine._sharding_key())
    assert declared is not None and declared["plane"] == "sharding"
    assert HloLinter().lint_text(text, label="t:train",
                                 declared=declared) == []
    bya = collectives_by_mesh_axes(
        parse_collectives(text), {"fsdp": 4, "tp": 2})
    fsdp = bya["by_axis"].get("fsdp", {})
    assert fsdp.get("all_gather", 0) >= declared["fsdp"]["buckets"]
    assert bya["by_axis"].get("tp", {}).get("all_reduce", 0) >= 1


def test_compile_key_salted_by_layout(orca_context):
    """Two engines on the same mesh, plane on vs off, must never share a
    train executable."""
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    def key(sharding):
        est = TPUEstimator(MLP(), loss="mse", optimizer="sgd", seed=0,
                           mesh=mesh, config={"steps_per_dispatch": 1},
                           sharding=sharding)
        it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                              shuffle=False, config=est.config)
        b0 = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in b0.x))
        fn = est.engine.ensure_jit_train()
        return fn.cache_key(*est.engine.train_step_args(b0))

    assert key(SpecLayout()) != key(False)
    assert key(SpecLayout()) != key(SpecLayout(bucket_mb=0.01))
