"""Comms plane (PR 8): bucketed gradient reduce-scatter, ZeRO-1 sharded
weight update, quantized allreduce wire (parallel/comms.py + engine).

Numerics contract under test, on the 8-device f32 CPU mesh:

* bucket assembly/disassembly round-trips the grad pytree bit-exactly;
* within the comms plane, flat-psum == bucketed == sharded_update, all
  bit-identical (reduce_scatter+all_gather is the same per-element N-sum
  as psum; the optax update is elementwise, so sharding it changes
  nothing — arXiv:2004.13336);
* the default path (plane off) is byte-for-byte the pre-plane GSPMD step;
* the quantized wire's error-feedback residual bounds drift over 50 steps;
* sharded and unsharded runs read each other's checkpoints;
* the compile-plane key misses when the bucket layout changes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
from analytics_zoo_tpu.parallel.comms import (BucketLayout, CommsConfig,
                                              CommsPlan, build_layout)


class MLP(nn.Module):
    """Several small leaves on purpose — bucketing exists for trees where
    per-leaf collectives dominate."""

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(32)(x))
        x = nn.relu(nn.Dense(16)(x))
        x = nn.relu(nn.Dense(16)(x))
        return nn.Dense(1)(x)[:, 0]


def _data(n=256, d=12, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(n, d).astype(np.float32),
            "y": rng.rand(n).astype(np.float32)}


def _fit(cfg, epochs=2, seed=0, data=None, model_dir=None, fuse=1, **kw):
    est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=seed,
                       model_dir=model_dir,
                       config={"steps_per_dispatch": fuse, **cfg}, **kw)
    stats = est.fit(dict(data or _data()), epochs=epochs, batch_size=32,
                    verbose=False)
    return [s["train_loss"] for s in stats], est


def _flat_params(est):
    return np.concatenate([np.asarray(l).ravel() for l in
                           jax.tree_util.tree_leaves(est.engine.params)])


def _flat_tree(tree):
    return np.concatenate([np.asarray(l).ravel() for l in
                           jax.tree_util.tree_leaves(tree)]) \
        if jax.tree_util.tree_leaves(tree) else np.zeros(0)


# ---------------------------------------------------------------------------
# bucket layout
# ---------------------------------------------------------------------------
def _random_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"a": {"kernel": rng.randn(7, 5).astype(np.float32),
                  "bias": rng.randn(5).astype(np.float32)},
            "b": [rng.randn(3, 3, 2).astype(np.float32),
                  rng.randn(1).astype(np.float32)],
            "c": rng.randn(131).astype(np.float32)}


def test_bucket_round_trip_bit_exact(orca_context):
    tree = _random_tree()
    cfg = CommsConfig(bucket_mb=0.0005)      # tiny buckets -> several
    lo = build_layout(tree, 8, cfg)
    assert len(lo.bucket_sizes) > 1
    assert all(b % 8 == 0 for b in lo.bucket_sizes)
    assert lo.padded_total == sum(lo.bucket_sizes) == 8 * lo.shard_size

    flat = lo.flatten(tree)
    back = lo.unflatten(flat)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == np.asarray(b).dtype
        assert (np.asarray(a) == np.asarray(b)).all()

    # bucket split/join and the scattered (replica-major) order round-trip
    assert (np.asarray(lo.unbuckets(lo.buckets(flat))) ==
            np.asarray(flat)).all()
    scat = lo.to_scattered(flat)
    assert (np.asarray(lo.from_scattered(scat)) == np.asarray(flat)).all()
    # numpy twins agree with the jnp versions bit-for-bit
    assert (lo.flatten_np(tree) == np.asarray(flat)).all()
    assert (lo.to_scattered_np(np.asarray(flat)) == np.asarray(scat)).all()
    assert (lo.from_scattered_np(np.asarray(scat)) ==
            np.asarray(flat)).all()


def test_layout_deterministic_and_int8_alignment(orca_context):
    tree = _random_tree()
    cfg = CommsConfig(bucket_mb=0.0005)
    assert build_layout(tree, 8, cfg).signature() == \
        build_layout(tree, 8, cfg).signature()
    # a different bucket size is a different layout identity
    assert build_layout(tree, 8, CommsConfig(bucket_mb=0.001)).signature() \
        != build_layout(tree, 8, cfg).signature()
    # int8 buckets must also split into whole scale blocks
    lo8 = build_layout(tree, 8, CommsConfig(bucket_mb=0.0005,
                                            wire_dtype="int8", block=64))
    assert all(b % 64 == 0 and b % 8 == 0 for b in lo8.bucket_sizes)


def test_non_f32_leaf_rejected(orca_context):
    # the plane's bit-identity / lossless-round-trip contracts are f32-only:
    # ints AND narrow floats (whose moments would truncate through the f32
    # flat vector) are rejected up front
    for bad in (np.ones(4, np.int32), np.ones(4, np.float16)):
        with pytest.raises(ValueError, match="f32"):
            build_layout({"w": bad}, 8, CommsConfig(explicit=True))


# ---------------------------------------------------------------------------
# satellite: grad_allreduce_mean on a single-axis mesh
# ---------------------------------------------------------------------------
def test_grad_allreduce_mean_skips_absent_axes(orca_context):
    """Regression: the default ``axes=("dp", "fsdp")`` used to raise inside
    any mesh that does not bind an ``fsdp`` axis (e.g. a user's 1-D
    ``Mesh(devices, ("dp",))``)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from analytics_zoo_tpu.parallel import collective as C
    from jax import shard_map

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


# ---------------------------------------------------------------------------
# bit-identity within the plane
# ---------------------------------------------------------------------------
def test_default_path_stays_off_and_deterministic(orca_context):
    """All-default config keeps the comms plane OFF — the engine runs the
    exact pre-plane GSPMD step (same arg signature, no residual, no
    telemetry key) and is deterministic per seed."""
    from analytics_zoo_tpu.orca.learn.engine import TrainEngine
    l0, e0 = _fit({})
    l1, e1 = _fit({})
    assert e0.engine.comms is None and e0.engine.comms_cfg is None
    assert e0.engine.comms_resid is None
    assert "comms" not in e0.data_pipeline_stats()
    # the executable IS the pre-plane step function — the plane never
    # rewires the default path, so per-seed weights cannot move
    wrapped = getattr(e0.engine._jit_train, "_fn", None)
    if wrapped is not None:             # compile plane on: inspectable
        assert wrapped.__func__ is TrainEngine._train_step
    assert l0 == l1
    assert (_flat_params(e0) == _flat_params(e1)).all()


def test_bucketed_bit_identical_to_flat_psum(orca_context):
    lf, ef = _fit({"comms_plane": True})
    lb, eb = _fit({"grad_bucket_mb": 0.001})
    assert ef.engine.comms is not None
    assert ef.engine.comms.cfg.effective_bucket_mb == 0      # leafwise wire
    assert len(eb.engine.comms.layout.bucket_sizes) > 1
    assert lf == lb
    assert (_flat_params(ef) == _flat_params(eb)).all()


def test_sharded_update_bit_identical_to_unsharded(orca_context):
    lb, eb = _fit({"grad_bucket_mb": 0.001})
    ls, es = _fit({"grad_bucket_mb": 0.001}, sharded_update=True)
    assert ls == lb
    assert (_flat_params(eb) == _flat_params(es)).all()
    # the optimizer moment trees agree too (checkpoint/canonical form)
    ob = _flat_tree(eb.engine.get_state()["opt_state"])
    os_ = _flat_tree(es.engine.get_state()["opt_state"])
    assert (ob == os_).all()


@pytest.mark.parametrize("opt", ["sgd", "adamw"])
def test_sharded_bit_identity_other_optimizers_and_padded_tail(
        orca_context, opt):
    """The elementwise-update argument holds for every optax transform we
    ship (momentum SGD, decoupled weight decay, ...), including batches
    with a padded tail (per-example weights in the loss)."""
    data = _data(n=200)                 # 200 % 48 != 0 -> padded last batch

    def run(shard):
        est = TPUEstimator(MLP(), loss="mse", optimizer=opt, seed=0,
                           config={"steps_per_dispatch": 1,
                                   "grad_bucket_mb": 0.001},
                           sharded_update=shard)
        stats = est.fit(dict(data), epochs=2, batch_size=48, verbose=False)
        return [s["train_loss"] for s in stats], _flat_params(est)

    lb, wb = run(False)
    ls, ws = run(True)
    assert lb == ls
    assert (wb == ws).all()


def test_sharded_update_fused_dispatch_bit_identical(orca_context):
    """The k-fused lax.scan path (train_batch_group) carries the comms
    step's extra state (resid slot) through the carry unchanged."""
    l1, e1 = _fit({"grad_bucket_mb": 0.001}, sharded_update=True, fuse=1)
    l4, e4 = _fit({"grad_bucket_mb": 0.001}, sharded_update=True, fuse=4)
    assert np.allclose(l1, l4, rtol=0, atol=0)
    assert (_flat_params(e1) == _flat_params(e4)).all()


def test_clipping_matches_between_sharded_and_unsharded(orca_context):
    """Norm clipping computes its scale from the reduce-scattered shards in
    BOTH update modes, so sharding cannot move the clip threshold."""
    def clipped(shard):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1,
                                   "grad_bucket_mb": 0.001},
                           sharded_update=shard)
        est.set_l2_norm_gradient_clipping(0.05)
        stats = est.fit(dict(_data()), epochs=2, batch_size=32,
                        verbose=False)
        return [s["train_loss"] for s in stats], _flat_params(est)

    lb, wb = clipped(False)
    ls, ws = clipped(True)
    assert lb == ls
    assert (wb == ws).all()


# ---------------------------------------------------------------------------
# ZeRO-1 memory: optimizer state HBM per replica shrinks by the dp degree
# ---------------------------------------------------------------------------
def test_sharded_opt_state_is_sharded_over_dp(orca_context):
    _, es = _fit({"grad_bucket_mb": 0.001}, sharded_update=True)
    lo = es.engine.comms.layout
    moments = [l for l in jax.tree_util.tree_leaves(es.engine.opt_state)
               if getattr(l, "ndim", 0) == 1
               and l.shape[0] == lo.padded_total]
    assert len(moments) >= 2            # adam mu + nu
    for leaf in moments:
        shard_shape = leaf.addressable_shards[0].data.shape
        assert shard_shape == (lo.padded_total // 8,)
        assert "dp" in str(leaf.sharding.spec)
    # vs the unsharded run, whose moments replicate the full vector
    _, eb = _fit({"grad_bucket_mb": 0.001})
    full = [l for l in jax.tree_util.tree_leaves(eb.engine.opt_state)
            if getattr(l, "ndim", 0) >= 1]
    for leaf in full:
        assert leaf.addressable_shards[0].data.shape == leaf.shape


# ---------------------------------------------------------------------------
# quantized wire + error feedback
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_quantized_wire_error_feedback_bounds_drift(orca_context, wire):
    data = _data(n=128)
    steps = 50
    epochs = -(-steps * 32 // 128)      # >= 50 optimizer steps
    le, ee = _fit({"grad_bucket_mb": 0.001}, epochs=epochs, data=data)
    lq, eq = _fit({"grad_bucket_mb": 0.001, "allreduce_dtype": wire,
                   "allreduce_block": 64}, epochs=epochs, data=data)
    assert eq.engine.comms_steps >= steps
    # the EF residual is alive (quantization error is being carried)
    resid = np.asarray(eq.engine.comms_resid)
    assert resid.shape == (8, eq.engine.comms.layout.padded_total)
    assert np.abs(resid).max() > 0
    # drift stays bounded: the compressed run tracks the exact run's loss
    # trajectory and does not diverge over 50 steps
    le, lq = np.asarray(le), np.asarray(lq)
    assert np.all(np.abs(lq - le) <= 5e-3 * np.maximum(np.abs(le), 1e-3))
    assert np.abs(lq[-1] - le[-1]) <= 2e-3 * max(abs(le[-1]), 1e-3)
    # wire accounting: bf16 halves the f32 grad bytes, int8 quarters them
    # (modulo per-block scales and bucket padding)
    snap = eq.data_pipeline_stats()["comms"]
    ratio = snap["grad_bytes_f32"] / snap["wire_bytes_per_step"]
    assert ratio >= (1.9 if wire == "bf16" else 3.0)


def test_quantize_wire_helper(orca_context):
    from analytics_zoo_tpu.parallel.comms import quantize_wire
    x = jnp.asarray(np.random.RandomState(0).randn(512).astype(np.float32))
    assert (np.asarray(quantize_wire(x, "f32", 64)) == np.asarray(x)).all()
    b = np.asarray(quantize_wire(x, "bf16", 64))
    assert np.abs(b - np.asarray(x)).max() <= 0.01 * np.abs(x).max()
    q = np.asarray(quantize_wire(x, "int8", 64))
    # block-scaled int8: error bounded by half a quantization step per block
    blocks = np.asarray(x).reshape(-1, 64)
    scales = np.abs(blocks).max(1, keepdims=True) / 127.0
    assert np.all(np.abs(q.reshape(-1, 64) - blocks) <= scales * 0.5 + 1e-7)
    # an all-zero block must not divide by zero
    z = np.asarray(quantize_wire(jnp.zeros(128), "int8", 64))
    assert (z == 0).all()


# ---------------------------------------------------------------------------
# checkpoints: sharded <-> unsharded restore round trip
# ---------------------------------------------------------------------------
def test_ckpt_sharded_to_unsharded_round_trip(orca_context, tmp_path):
    data = _data()
    cfg = {"grad_bucket_mb": 0.001, "ckpt_async": False}

    # reference: one uninterrupted unsharded run, 4 epochs
    lref, eref = _fit(cfg, epochs=4, data=data)

    # sharded run for 2 epochs -> checkpoint -> restore into an UNSHARDED
    # estimator -> 2 more epochs must land on the reference bit-exactly
    l1, e1 = _fit(cfg, epochs=2, data=data, sharded_update=True)
    d1 = str(tmp_path / "sharded")
    e1.save_checkpoint(d1, blocking=True)

    e2 = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                      config={"steps_per_dispatch": 1, **cfg})
    e2.load_checkpoint(d1)
    assert e2.engine.step == e1.engine.step
    l2 = [s["train_loss"] for s in
          e2.fit(dict(data), epochs=2, batch_size=32, verbose=False,
                 initial_epoch=2)]
    assert l1 + l2 == lref
    assert (_flat_params(e2) == _flat_params(eref)).all()

    # the manifest records the writing run's comms plane
    from analytics_zoo_tpu.ckpt.format import (loadable_step_dirs,
                                               manifest_meta)
    meta = manifest_meta(loadable_step_dirs(d1)[-1][1])
    assert meta["comms"]["sharded_update"] is True
    assert meta["comms"]["layout_sig"] == \
        e1.engine.comms.layout.signature()
    e1.shutdown()
    e2.shutdown()


def test_ckpt_unsharded_to_sharded_round_trip(orca_context, tmp_path):
    data = _data()
    cfg = {"grad_bucket_mb": 0.001, "ckpt_async": False}

    lref, eref = _fit(cfg, epochs=4, data=data, sharded_update=True)

    l1, e1 = _fit(cfg, epochs=2, data=data)          # unsharded writer
    d1 = str(tmp_path / "unsharded")
    e1.save_checkpoint(d1, blocking=True)

    e2 = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                      config={"steps_per_dispatch": 1, **cfg},
                      sharded_update=True)
    e2.load_checkpoint(d1)
    # restored straight into the sharded representation
    lo = e2.engine.comms.layout
    moments = [l for l in jax.tree_util.tree_leaves(e2.engine.opt_state)
               if getattr(l, "ndim", 0) == 1
               and l.shape[0] == lo.padded_total]
    assert moments and all(
        m.addressable_shards[0].data.shape == (lo.padded_total // 8,)
        for m in moments)
    l2 = [s["train_loss"] for s in
          e2.fit(dict(data), epochs=2, batch_size=32, verbose=False,
                 initial_epoch=2)]
    assert l1 + l2 == lref
    assert (_flat_params(e2) == _flat_params(eref)).all()
    e1.shutdown()
    e2.shutdown()


def test_ckpt_restore_unambiguous_param_matching_padded_total(
        orca_context, tmp_path):
    """Regression: a single 1-D param of exactly ``padded_total`` elements
    makes tree-form Adam moments the same shape as the sharded run's flat
    moment vectors. The restore path must NOT shape-sniff which form it
    got (it would skip the tree->flat conversion and bind scattered-order
    slices of flat-order moments — silently permuted); state dicts are
    canonical tree form unless explicitly marked ``opt_state_form="flat"``."""

    class VecModel(nn.Module):
        @nn.compact
        def __call__(self, x):
            w = self.param("w", nn.initializers.normal(0.02), (1024,))
            return (x @ w.reshape(16, 64)).sum(axis=-1)

    data = _data(d=16)
    cfg = {"steps_per_dispatch": 1, "grad_bucket_mb": 0.002,
           "ckpt_async": False}

    def fit(epochs, est=None, initial_epoch=0):
        if est is None:
            est = TPUEstimator(VecModel(), loss="mse", optimizer="adam",
                               seed=0, config=dict(cfg),
                               sharded_update=True)
        losses = [s["train_loss"] for s in
                  est.fit(dict(data), epochs=epochs, batch_size=32,
                          verbose=False, initial_epoch=initial_epoch)]
        return losses, est

    lref, eref = fit(4)
    l1, e1 = fit(2)

    # preconditions that make the shapes ambiguous: the one param IS the
    # whole padded flat vector, over a genuinely multi-bucket layout
    # (scattered order != flat order, so a skipped conversion permutes)
    lo = e1.engine.comms.layout
    assert lo.total == lo.padded_total == 1024
    assert len(lo.bucket_sizes) > 1
    state = e1.engine.get_state()
    moments = [l for l in jax.tree_util.tree_leaves(state["opt_state"])
               if getattr(l, "ndim", 0) == 1]
    assert moments and all(m.shape == (lo.padded_total,) for m in moments)

    d1 = str(tmp_path / "vec")
    e1.save_checkpoint(d1, blocking=True)
    e2 = TPUEstimator(VecModel(), loss="mse", optimizer="adam", seed=0,
                      config=dict(cfg), sharded_update=True)
    e2.load_checkpoint(d1)
    l2, _ = fit(2, est=e2, initial_epoch=2)
    assert l1 + l2 == lref
    assert (_flat_params(e2) == _flat_params(eref)).all()
    e1.shutdown()
    e2.shutdown()


# ---------------------------------------------------------------------------
# compile plane: bucket layout is part of the executable identity
# ---------------------------------------------------------------------------
def test_compile_key_misses_when_bucket_layout_changes(orca_context):
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    def key_for(bucket_mb):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1,
                                   "grad_bucket_mb": bucket_mb})
        it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                              shuffle=False, config=est.config)
        batch = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in batch.x))
        return est.engine.train_step_cache_key(batch)

    k_small, k_small2, k_big = key_for(0.001), key_for(0.001), key_for(4.0)
    assert k_small is not None and k_big is not None
    assert k_small == k_small2          # same layout -> shared executable
    assert k_small != k_big             # layout change -> compile-key miss


# ---------------------------------------------------------------------------
# telemetry + guards
# ---------------------------------------------------------------------------
def test_comms_telemetry_counts(orca_context):
    _, ef = _fit({"comms_plane": True})
    _, eb = _fit({"grad_bucket_mb": 0.001}, sharded_update=True)
    flat, buck = (ef.data_pipeline_stats()["comms"],
                  eb.data_pipeline_stats()["comms"])
    assert flat["collectives_per_step"] == flat["grad_leaves"] == 8
    assert buck["buckets"] >= 2
    assert buck["collectives_per_step"] == buck["buckets"] + 1
    assert buck["collectives_per_step"] < flat["collectives_per_step"]
    assert buck["sharded_update"] is True
    assert buck["steps"] == eb.engine.comms_steps > 0
    assert buck["wire_bytes_total"] == \
        buck["wire_bytes_per_step"] * buck["steps"]
    assert buck["opt_shard_elems"] * 8 == buck["opt_full_elems"]


def test_comms_requires_pure_dp_mesh(orca_context):
    from analytics_zoo_tpu.parallel.mesh import create_mesh, pure_dp
    mesh = create_mesh({"dp": 4, "tp": 2})
    assert not pure_dp(mesh)
    est = TPUEstimator(MLP(), loss="mse", optimizer="adam", mesh=mesh,
                       config={"steps_per_dispatch": 1,
                               "grad_bucket_mb": 1.0})
    with pytest.raises(ValueError, match="pure data-parallel"):
        est.fit(dict(_data()), epochs=1, batch_size=32, verbose=False)


def test_comms_and_sharding_planes_are_exclusive(orca_context):
    """PR 17: the explicit dp wire and the SpecLayout plane own different
    collectives — combining them on a multi-axis mesh is a config error
    whose message names the plane that does support such meshes."""
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    from analytics_zoo_tpu.parallel.sharding import SpecLayout
    mesh = create_mesh({"dp": 1, "fsdp": 4, "tp": 2})
    with pytest.raises(ValueError, match="mutually exclusive"):
        TPUEstimator(MLP(), loss="mse", optimizer="sgd", mesh=mesh,
                     sharding=SpecLayout(),
                     config={"steps_per_dispatch": 1,
                             "grad_bucket_mb": 1.0})


def test_comms_config_resolve_env(orca_context, monkeypatch):
    assert not CommsConfig.resolve({}).active
    monkeypatch.setenv("ZOO_SHARDED_UPDATE", "1")
    monkeypatch.setenv("ZOO_GRAD_BUCKET_MB", "8")
    monkeypatch.setenv("ZOO_ALLREDUCE_DTYPE", "bf16")
    cfg = CommsConfig.resolve({})
    assert cfg.active and cfg.sharded_update and cfg.bucket_mb == 8.0 \
        and cfg.wire_dtype == "bf16"
    # config dict wins over env
    cfg2 = CommsConfig.resolve({"allreduce_dtype": "f32",
                                "grad_bucket_mb": 2})
    assert cfg2.wire_dtype == "f32" and cfg2.bucket_mb == 2.0
    with pytest.raises(ValueError):
        CommsConfig(wire_dtype="fp8")


# ---------------------------------------------------------------------------
# PR 11: overlapped backward-comms pipeline
# ---------------------------------------------------------------------------
def test_segment_plan_matches_flat_bucketing_bit_exact(orca_context):
    """The overlapped pipeline's per-bucket assembly (each bucket built
    straight from its own leaf slices) must produce the EXACT elements of
    ``layout.buckets(layout.flatten(tree))`` — same values, same order —
    for every segment grouping. Only the dependence structure changes."""
    from analytics_zoo_tpu.parallel.comms import SegmentPlan

    tree = _random_tree()
    lo = build_layout(tree, 8, CommsConfig(bucket_mb=0.0005, overlap=True))
    assert len(lo.bucket_sizes) > 1
    ref = [np.asarray(b) for b in lo.buckets(lo.flatten(tree))]

    for n_seg in (0, 1, 2, len(lo.bucket_sizes) + 5):
        sp = SegmentPlan.build(lo, n_seg)
        # every bucket is covered by pieces + padding, nothing overlaps
        for k, b in enumerate(lo.bucket_sizes):
            covered = sum(p.stop - p.start for p in sp.bucket_pieces[k])
            assert covered + sp.bucket_pad[k] == b
        assert sum(len(s) for s in sp.segments) == len(lo.bucket_sizes)
        got = sp.bucket_values(tree)
        got_np = sp.bucket_values_np(tree)
        for r, g, gn in zip(ref, got, got_np):
            assert (r == np.asarray(g)).all()
            assert (r == gn).all()
    # the default is maximum overlap: one segment per bucket
    assert SegmentPlan.build(lo).n_segments == len(lo.bucket_sizes)
    assert SegmentPlan.build(lo, 1).n_segments == 1
    assert SegmentPlan.build(lo, 2).n_segments == 2


def test_overlapped_bit_identical_to_flat_bucketed_sharded(orca_context):
    """The full numerics contract, PR-11 edition: flat == bucketed ==
    sharded == overlapped (+ overlapped sharded), all bit-identical on
    the f32 mesh — the overlap only moves the reduce-scatters inside the
    backward's dependence graph, never a value."""
    lf, _ = _fit({"comms_plane": True})
    lb, eb = _fit({"grad_bucket_mb": 0.001})
    lo_, eo = _fit({"grad_bucket_mb": 0.001, "comms_overlap": True})
    los, eos = _fit({"grad_bucket_mb": 0.001, "comms_overlap": True},
                    sharded_update=True)
    assert eo.engine.comms.segplan is not None
    assert eo.engine.comms.segplan.n_segments == \
        len(eo.engine.comms.layout.bucket_sizes) > 1
    assert lf == lb == lo_ == los
    wb = _flat_params(eb)
    assert (wb == _flat_params(eo)).all()
    assert (wb == _flat_params(eos)).all()
    # wire accounting is byte-for-byte the bucketed leg's
    sb = eb.data_pipeline_stats()["comms"]
    so = eos.data_pipeline_stats()["comms"]
    assert so["wire_bytes_per_step"] == sb["wire_bytes_per_step"]
    assert so["overlap"] is True and sb["overlap"] is False
    assert so["segments"] == so["buckets"]


def test_overlapped_clipped_and_fused_variants_bit_identical(orca_context):
    """Clip-norm (scale computed from the reduce-scattered shards) and the
    scan-fused multi-step dispatch both ride the overlapped step without
    moving a bit."""
    def clipped(cfg, fuse=1, **kw):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": fuse, **cfg}, **kw)
        est.set_l2_norm_gradient_clipping(0.05)
        stats = est.fit(dict(_data()), epochs=2, batch_size=32,
                        verbose=False)
        return [s["train_loss"] for s in stats], _flat_params(est)

    lb, wb = clipped({"grad_bucket_mb": 0.001}, sharded_update=True)
    lo_, wo = clipped({"grad_bucket_mb": 0.001, "comms_overlap": True},
                      sharded_update=True)
    assert lb == lo_ and (wb == wo).all()
    # scan-fused multi-step: k overlapped steps in one dispatch
    l4, w4 = clipped({"grad_bucket_mb": 0.001, "comms_overlap": True},
                     fuse=4, sharded_update=True)
    assert l4 == lb and (w4 == wb).all()
    # segment-count override regroups the pipeline without moving a bit
    l2, w2 = clipped({"grad_bucket_mb": 0.001, "comms_overlap": True,
                      "comms_segments": 2}, sharded_update=True)
    assert l2 == lb and (w2 == wb).all()


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_overlapped_ef_residual_drift_bounded(orca_context, wire):
    """The EF residual (quantized wire) rides the overlapped step: the
    per-bucket residual add/subtract is bit-identical to the flat-vector
    form, so overlapped+quantized == bucketed+quantized exactly, and the
    drift vs the exact wire stays inside the PR-8 bounds over 50 steps."""
    data = _data(n=128)
    steps = 50
    epochs = -(-steps * 32 // 128)
    le, _ = _fit({"grad_bucket_mb": 0.001, "comms_overlap": True},
                 epochs=epochs, data=data)
    lq, eq = _fit({"grad_bucket_mb": 0.001, "allreduce_dtype": wire,
                   "allreduce_block": 64, "comms_overlap": True},
                  epochs=epochs, data=data)
    lqb, eqb = _fit({"grad_bucket_mb": 0.001, "allreduce_dtype": wire,
                     "allreduce_block": 64}, epochs=epochs, data=data)
    # overlapped quantized == bucketed quantized, bit for bit (weights
    # AND the carried residual)
    assert lq == lqb
    assert (_flat_params(eq) == _flat_params(eqb)).all()
    assert (np.asarray(eq.engine.comms_resid)
            == np.asarray(eqb.engine.comms_resid)).all()
    # residual alive + drift vs the exact overlapped wire bounded
    assert np.abs(np.asarray(eq.engine.comms_resid)).max() > 0
    le, lq = np.asarray(le), np.asarray(lq)
    assert np.all(np.abs(lq - le) <= 5e-3 * np.maximum(np.abs(le), 1e-3))
    assert np.abs(lq[-1] - le[-1]) <= 2e-3 * max(abs(le[-1]), 1e-3)


def test_overlap_salts_the_compile_key(orca_context):
    """Overlap on/off and the segment override are program shape: each
    must miss the executable cache (extra_key regression = the golden
    distinct_train_executables collapse)."""
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    def key_for(cfg):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1, **cfg})
        it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                              shuffle=False, config=est.config)
        batch = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in batch.x))
        return est.engine.train_step_cache_key(batch)

    k_off = key_for({"grad_bucket_mb": 0.001})
    k_on = key_for({"grad_bucket_mb": 0.001, "comms_overlap": True})
    k_on2 = key_for({"grad_bucket_mb": 0.001, "comms_overlap": True})
    k_seg = key_for({"grad_bucket_mb": 0.001, "comms_overlap": True,
                     "comms_segments": 2})
    assert None not in (k_off, k_on, k_seg)
    assert k_on == k_on2                 # same shape -> shared executable
    assert len({k_off, k_on, k_seg}) == 3


def test_overlap_knobs_resolve_and_default_bucket(orca_context,
                                                  monkeypatch):
    monkeypatch.setenv("ZOO_COMMS_OVERLAP", "1")
    monkeypatch.setenv("ZOO_COMMS_SEGMENTS", "3")
    cfg = CommsConfig.resolve({})
    assert cfg.active and cfg.overlap and cfg.segments == 3
    # overlap alone resolves the default bucket size (the pipeline is
    # bucket-staged by definition)
    assert cfg.effective_bucket_mb == CommsConfig.DEFAULT_BUCKET_MB
    # config dict wins over env
    cfg2 = CommsConfig.resolve({"comms_overlap": False})
    assert not cfg2.overlap
    assert "overlap=1" in cfg.fingerprint()
    assert cfg.fingerprint() != CommsConfig.resolve(
        {"comms_segments": 0}).fingerprint()
    with pytest.raises(ValueError, match="comms_segments"):
        CommsConfig(overlap=True, segments=-1)


# ---------------------------------------------------------------------------
# PR 12: pod-scale hierarchical comms — ICI reduce-scatter x DCN exchange
# ---------------------------------------------------------------------------
def _hier_cfg(dcn=2, **extra):
    return {"grad_bucket_mb": 0.001, "comms_hierarchy": True,
            "comms_dcn_axis": dcn, **extra}


def test_hier_layout_alignment_and_device_order(orca_context):
    """Host-boundary rule: every bucket splits into whole host chunks
    (and, for the int8 DCN wire, the chunk into whole scale blocks); the
    device-major scattered order (sigma-permuted) round-trips bit-exactly
    and collapses onto chunk-major without hierarchy."""
    tree = _random_tree()
    cfg = CommsConfig(bucket_mb=0.0005, hierarchy=True, dcn_size=2)
    lo = build_layout(tree, 8, cfg, ici=4, dcn=2)
    assert lo.hierarchical and (lo.ici, lo.dcn) == (4, 2)
    assert len(lo.bucket_sizes) > 1
    assert all(b % 8 == 0 for b in lo.bucket_sizes)
    # int8 DCN-only wire: the quantized bucket/ici chunk must split into
    # whole scale blocks
    lo8 = build_layout(tree, 8, CommsConfig(
        bucket_mb=0.0005, wire_dtype="int8", block=64, hierarchy=True,
        dcn_size=2), ici=4, dcn=2)
    assert all(b % (4 * 64) == 0 for b in lo8.bucket_sizes)
    assert lo8.resid_elems == lo8.padded_total // 4
    # sigma = (k % ici) * dcn + k // ici, a permutation
    perm = lo.device_perm()
    assert sorted(perm.tolist()) == list(range(8))
    assert perm[1] == 2 and perm[4] == 1      # (h,i)=(0,1)->2, (1,0)->1
    flat = lo.flatten_np(tree)
    dscat = lo.to_device_scattered_np(flat)
    assert (lo.from_device_scattered_np(dscat) == flat).all()
    # row k of the device-major order IS chunk sigma(k) of the chunk-major
    rows_d = dscat.reshape(8, lo.shard_size)
    rows_c = lo.to_scattered_np(flat).reshape(8, lo.shard_size)
    assert all((rows_d[k] == rows_c[perm[k]]).all() for k in range(8))
    # no hierarchy: identity (device-major == chunk-major bit for bit)
    lo_flat = build_layout(tree, 8, CommsConfig(bucket_mb=0.0005))
    assert (lo_flat.to_device_scattered_np(flat) ==
            lo_flat.to_scattered_np(flat)).all()
    # the hierarchy factors into the layout identity
    assert lo.signature() != lo_flat.signature()


def test_hier_topology_probe(orca_context):
    """dp_topology factors from process locality: contiguous equal blocks
    -> (nproc, n/nproc); interleaved or single-process -> (1, n);
    override validated."""
    from types import SimpleNamespace

    from analytics_zoo_tpu.parallel.mesh import dp_topology

    def mesh_of(procs):
        devs = np.array([SimpleNamespace(process_index=p) for p in procs],
                        dtype=object).reshape(len(procs), 1, 1, 1)
        return SimpleNamespace(shape={"dp": len(procs), "fsdp": 1,
                                      "tp": 1, "sp": 1},
                               axis_names=("dp", "fsdp", "tp", "sp"),
                               devices=devs)

    assert dp_topology(mesh_of([0, 0, 0, 0, 1, 1, 1, 1])) == (2, 4)
    assert dp_topology(mesh_of([0, 0, 1, 1, 2, 2, 3, 3])) == (4, 2)
    # interleaved process order: a "host group" would span DCN — refuse
    assert dp_topology(mesh_of([0, 1, 0, 1, 0, 1, 0, 1])) == (1, 8)
    # single process: no host boundary
    assert dp_topology(mesh_of([0] * 8)) == (1, 8)
    # override wins (the simulated-mesh split) and is validated
    assert dp_topology(mesh_of([0] * 8), dcn_override=2) == (2, 4)
    with pytest.raises(ValueError):
        dp_topology(mesh_of([0] * 8), dcn_override=3)
    # the real 8-dev single-process mesh probes flat
    assert dp_topology(orca_context.mesh) == (1, 8)


def test_hier_numpy_twins_match_device_bitwise(orca_context):
    """The decomposition's MATH, bit-exact against the device: the
    two-level reduce-scatter / allreduce over a bucket equals the numpy
    host twins (linear-in-group-order sums) bit for bit — which is what
    makes the hierarchy checkable on hosts whose jaxlib lacks
    multiprocess CPU collectives."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from analytics_zoo_tpu.parallel.comms import (hier_allreduce_np,
                                                  hier_mean_np,
                                                  hier_reduce_scatter_np)

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    rng = np.random.RandomState(3)
    for ici, dcn in ((4, 2), (2, 4)):
        b = 64
        stacked = (rng.rand(8, b).astype(np.float32) - 0.5) * 3
        tree = {"w": np.zeros(b, np.float32)}   # one bucket of exactly b
        cfg = CommsConfig(bucket_mb=4.0, hierarchy=True, dcn_size=dcn)
        lo = build_layout(tree, 8, cfg, ici=ici, dcn=dcn)
        assert lo.bucket_sizes == (b,)
        plan = CommsPlan(cfg, lo)

        def rs_body(v):
            out, _, _ = plan.hier_reduce([v[0]], None)
            return out[0]

        def ar_body(v):
            out, _, _ = plan.hier_reduce([v[0]], None)
            return plan.hier_gather_buckets(out)

        rs = shard_map(rs_body, mesh=mesh, in_specs=(P("dp", None),),
                       out_specs=P("dp"), check_vma=False)
        # unsharded exchange (allreduce + ici gather)
        ar = shard_map(ar_body, mesh=mesh, in_specs=(P("dp", None),),
                       out_specs=P("dp"), check_vma=False)

        cfg_sh = CommsConfig(bucket_mb=4.0, hierarchy=True, dcn_size=dcn,
                             sharded_update=True)
        plan_sh = CommsPlan(cfg_sh, build_layout(tree, 8, cfg_sh,
                                                 ici=ici, dcn=dcn))

        def rs_sh_body(v):
            out, _, _ = plan_sh.hier_reduce([v[0]], None)
            return out[0]

        rs_sh = shard_map(rs_sh_body, mesh=mesh,
                          in_specs=(P("dp", None),),
                          out_specs=P("dp"), check_vma=False)

        got_ar = np.asarray(jax.jit(ar)(stacked)).reshape(8, b)
        assert (got_ar == hier_allreduce_np(stacked, ici, dcn)).all()
        got_sh = np.asarray(jax.jit(rs_sh)(stacked)).reshape(8, b // 8)
        assert (got_sh == hier_reduce_scatter_np(stacked, ici, dcn)).all()
        # the allreduce twin / n is the mean the unsharded update applies
        assert (hier_mean_np(stacked, ici, dcn) ==
                hier_allreduce_np(stacked, ici, dcn)[0] / 8).all()
        # unsharded chunks (pre-gather) also match the twin's chunk rows
        got_rs = np.asarray(jax.jit(rs)(stacked)).reshape(8, b // ici)
        full = hier_allreduce_np(stacked, ici, dcn)[0]
        for h in range(dcn):
            for i in range(ici):
                want = full[i * (b // ici):(i + 1) * (b // ici)]
                assert (got_rs[h * ici + i] == want).all()


def test_hier_exact_sums_match_flat_bitwise(orca_context):
    """When every partial sum is exactly representable (integer-valued
    grads), the two-level association and the flat linear reduction agree
    BITWISE — the flat == hierarchical contract, asserted where it is
    mathematically meaningful (for generic floats the two associations
    differ at last-ulp level, documented in parallel/comms.py)."""
    from analytics_zoo_tpu.parallel.comms import (hier_allreduce_np,
                                                  group_sum_np)

    rng = np.random.RandomState(7)
    stacked = rng.randint(-512, 512, (8, 64)).astype(np.float32)
    flat_lin = group_sum_np(stacked, [list(range(8))])[0]
    assert (hier_allreduce_np(stacked, 4, 2)[0] == flat_lin).all()
    assert (hier_allreduce_np(stacked, 2, 4)[0] == flat_lin).all()


def test_hier_bit_identity_family(orca_context):
    """Within the two-level wire the whole PR-8/11 family holds:
    single-bucket == multi-bucket == overlapped == ZeRO-1-sharded ==
    scan-fused, bit-identical — and a dcn=1 factorization collapses
    byte-for-byte onto the classic bucketed wire."""
    data = _data()
    lh, eh = _fit(_hier_cfg(), data=data)
    l1, _ = _fit({"comms_hierarchy": True, "comms_dcn_axis": 2},
                 data=data)                      # single default bucket
    lo_, _ = _fit(_hier_cfg(comms_overlap=True), data=data)
    ls, es = _fit(_hier_cfg(), data=data, sharded_update=True)
    lf, _ = _fit(_hier_cfg(), data=data, fuse=2, sharded_update=True)
    wh = _flat_params(eh)
    assert lh == l1 == lo_ == ls == lf
    assert (wh == _flat_params(es)).all()
    assert eh.engine.comms.summary()["buckets"] > 1
    hier = es.engine.comms.summary()["hierarchy"]
    assert (hier["ici_axis"], hier["dcn_axis"]) == (4, 2)
    # DCN moves 1/ici of the flat wire's bytes — the point of the plan
    assert hier["dcn_wire_bytes_per_step"] * 4 == \
        hier["ici_wire_bytes_per_step"]

    # dcn=1: the hierarchical plan IS the classic bucketed program
    lb, eb = _fit({"grad_bucket_mb": 0.001}, data=data)
    ld1, ed1 = _fit(_hier_cfg(dcn=1), data=data)
    assert ld1 == lb
    assert (_flat_params(ed1) == _flat_params(eb)).all()
    assert ed1.engine.comms.summary()["hierarchy"]["active"] is False
    # ici=1 (one chip per host — dcn == dp) equally collapses: there are
    # no fast links to pre-reduce on, and labelling the full axis "DCN"
    # would misclassify the global loss/clip reductions
    li1, ei1 = _fit(_hier_cfg(dcn=8), data=data)
    assert li1 == lb
    assert (_flat_params(ei1) == _flat_params(eb)).all()
    assert ei1.engine.comms.summary()["hierarchy"]["active"] is False
    assert not build_layout(_random_tree(), 8,
                            CommsConfig(bucket_mb=0.001, hierarchy=True,
                                        dcn_size=8),
                            ici=1, dcn=8).hierarchical


def test_hier_clipping_matches_between_update_modes(orca_context):
    """The norm-clip scale comes from each replica's unique-ownership
    pieces in BOTH hierarchical update modes, so ZeRO-1 cannot move the
    clip threshold by an ulp."""
    def clipped(shard):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1,
                                   **_hier_cfg()},
                           sharded_update=shard)
        est.set_l2_norm_gradient_clipping(0.05)
        stats = est.fit(dict(_data()), epochs=2, batch_size=32,
                        verbose=False)
        return [s["train_loss"] for s in stats], _flat_params(est)

    lb, wb = clipped(False)
    ls, ws = clipped(True)
    assert lb == ls
    assert (wb == ws).all()


@pytest.mark.parametrize("wire", ["bf16", "int8"])
def test_hier_quantize_dcn_only_ef_drift(orca_context, wire):
    """DCN-only quantization: the residual lives on the post-ICI chunk
    domain (padded/ici per replica), sharded == unsharded stays
    bit-identical, and error feedback bounds the drift vs the exact-f32
    hierarchical wire."""
    data = _data()
    lf32, _ = _fit(_hier_cfg(), epochs=3, data=data)
    lq, eq = _fit(_hier_cfg(allreduce_dtype=wire), epochs=3, data=data)
    lqs, eqs = _fit(_hier_cfg(allreduce_dtype=wire), epochs=3, data=data,
                    sharded_update=True)
    assert lq == lqs
    assert (_flat_params(eq) == _flat_params(eqs)).all()
    lo = eq.engine.comms.layout
    assert lo.resid_elems == lo.padded_total // lo.ici
    assert eq.engine.comms_resid.shape == (8, lo.resid_elems)
    drift = float(np.abs(np.asarray(lq) - np.asarray(lf32)).max())
    assert drift < (5e-5 if wire == "bf16" else 5e-4), drift
    # classic-wire variant: flat-domain residual, quantize before ICI
    lqc, eqc = _fit(_hier_cfg(allreduce_dtype=wire,
                              comms_quantize_dcn=False),
                    epochs=3, data=data)
    loc = eqc.engine.comms.layout
    assert loc.resid_elems == loc.padded_total
    driftc = float(np.abs(np.asarray(lqc) - np.asarray(lf32)).max())
    assert driftc < (5e-5 if wire == "bf16" else 5e-4), driftc


def test_hier_ckpt_round_trips(orca_context, tmp_path):
    """Checkpoints stay wire-agnostic: a hierarchical ZeRO-1 run's state
    is stored in canonical tree form (device-major scattered order
    converted losslessly), restores bit-exactly into a hierarchical
    continuation AND into a classic sharded run's representation."""
    data = _data()
    cfg = {**_hier_cfg(), "ckpt_async": False}
    lref, eref = _fit(cfg, epochs=4, data=data, sharded_update=True)

    l1, e1 = _fit(cfg, epochs=2, data=data, sharded_update=True)
    d1 = str(tmp_path / "hier")
    e1.save_checkpoint(d1, blocking=True)

    # hier -> hier continuation lands on the uninterrupted run bit-exactly
    e2 = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                      config={"steps_per_dispatch": 1, **cfg},
                      sharded_update=True)
    e2.load_checkpoint(d1)
    l2 = [s["train_loss"] for s in
          e2.fit(dict(data), epochs=2, batch_size=32, verbose=False,
                 initial_epoch=2)]
    assert l1 + l2 == lref
    assert (_flat_params(e2) == _flat_params(eref)).all()

    # the canonical tree form a hierarchical writer stores equals what a
    # classic sharded engine restores from — same tree, no wire baked in
    e3 = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                      config={"steps_per_dispatch": 1,
                              "grad_bucket_mb": 0.001,
                              "ckpt_async": False},
                      sharded_update=True)
    e3.load_checkpoint(d1)
    assert e3.engine.step == e1.engine.step
    assert (_flat_params(e3) == _flat_params(e1)).all()
    # moment leaves re-scattered for the classic layout: converting both
    # engines' opt state back to tree form must agree bit-for-bit
    t1 = e1.engine.comms.opt_flat_to_tree(
        jax.device_get(e1.engine.opt_state))
    t3 = e3.engine.comms.opt_flat_to_tree(
        jax.device_get(e3.engine.opt_state))
    assert (_flat_tree(t1) == _flat_tree(t3)).all()
    e1.shutdown()
    e2.shutdown()
    e3.shutdown()


def test_hier_salts_compile_key(orca_context):
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    def key_for(cfg, **kw):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1, **cfg}, **kw)
        it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                              shuffle=False, config=est.config)
        batch = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in batch.x))
        return est.engine.train_step_cache_key(batch)

    k_classic = key_for({"grad_bucket_mb": 0.001})
    k_hier = key_for(_hier_cfg())
    k_hier2 = key_for(_hier_cfg())
    k_dcn4 = key_for(_hier_cfg(dcn=4))
    k_qdcn = key_for(_hier_cfg(allreduce_dtype="bf16"))
    k_qclassic = key_for(_hier_cfg(allreduce_dtype="bf16",
                                   comms_quantize_dcn=False))
    assert k_hier == k_hier2              # same wire -> shared executable
    assert len({k_classic, k_hier, k_dcn4, k_qdcn, k_qclassic}) == 5


def test_hier_knob_resolution(orca_context, monkeypatch):
    monkeypatch.setenv("ZOO_COMMS_HIERARCHY", "1")
    monkeypatch.setenv("ZOO_COMMS_DCN_AXIS", "2")
    cfg = CommsConfig.resolve({})
    assert cfg.active and cfg.hierarchy and cfg.dcn_size == 2
    assert cfg.quantize_dcn is True
    assert cfg.effective_bucket_mb == CommsConfig.DEFAULT_BUCKET_MB
    # config dict wins over env
    cfg2 = CommsConfig.resolve({"comms_dcn_axis": 4,
                                "comms_quantize_dcn": False})
    assert cfg2.dcn_size == 4 and cfg2.quantize_dcn is False
    monkeypatch.delenv("ZOO_COMMS_HIERARCHY")
    monkeypatch.delenv("ZOO_COMMS_DCN_AXIS")
    # the hierarchy knobs are program shape -> they salt the fingerprint
    assert cfg.fingerprint() != CommsConfig.resolve(
        {"grad_bucket_mb": 4.0}).fingerprint()
    with pytest.raises(ValueError):
        CommsConfig.resolve({"comms_dcn_axis": 2})  # dcn without hierarchy


def test_hier_accounting_verified_and_tamper(orca_context):
    """The per-axis hlo_lint cross-check passes on the real lowered
    program and fails when the declared DCN accounting is tampered —
    moving bytes onto the cross-host links cannot pass unnoticed."""
    from analytics_zoo_tpu.analysis.hlo_lint import HloLinter
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                       config={"steps_per_dispatch": 1, **_hier_cfg()},
                       sharded_update=True)
    it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    batch = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in batch.x))
    fn = est.engine.ensure_jit_train()
    text = fn.lower(*est.engine.train_step_args(batch)).as_text()
    declared = est.engine.comms_snapshot()
    assert not HloLinter().lint_text(text, label="train",
                                     declared=declared)
    bad = dict(declared, hierarchy=dict(
        declared["hierarchy"],
        dcn_wire_bytes_per_step=declared["hierarchy"]
        ["dcn_wire_bytes_per_step"] + 64))
    findings = HloLinter().lint_text(text, label="train", declared=bad)
    assert findings and any("DCN leg moves" in f.message
                            for f in findings)


# ---------------------------------------------------------------------------
# PR 16: native quantized collectives — the int8 ring that really moves bytes
# ---------------------------------------------------------------------------
def _native_cfg(**extra):
    return {"grad_bucket_mb": 0.001, "allreduce_dtype": "int8",
            "allreduce_block": 64, "comms_native_int8": True, **extra}


def _native_hier_cfg(**extra):
    return _native_cfg(comms_hierarchy=True, comms_dcn_axis=2, **extra)


def _build_lowered(cfg, **kw):
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                       config={"steps_per_dispatch": 1, **cfg}, **kw)
    it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                          shuffle=False, config=est.config)
    batch = next(it.epoch(shuffle=False, prefetch=False))
    est.engine.build(tuple(np.asarray(a) for a in batch.x))
    fn = est.engine.ensure_jit_train()
    text = fn.lower(*est.engine.train_step_args(batch)).as_text()
    return est, text, est.engine.comms_snapshot()


def test_native_layout_alignment_and_validation(orca_context):
    """Every ring hop chunk (bucket / n_dev) must split into whole scale
    blocks — the native alignment (n_dev*block) subsumes both legacy int8
    alignments — and the ring is program shape: it salts the layout
    identity and is rejected without the int8 wire it implements."""
    tree = _random_tree()
    lo = build_layout(tree, 8, CommsConfig(
        bucket_mb=0.0005, wire_dtype="int8", block=64, native_int8=True))
    assert all(b % (8 * 64) == 0 for b in lo.bucket_sizes)
    lo_sim = build_layout(tree, 8, CommsConfig(
        bucket_mb=0.0005, wire_dtype="int8", block=64))
    assert lo.signature() != lo_sim.signature()
    # packed hop operand = int8 payload + 4 bitcast scale bytes per block
    for b in lo.bucket_sizes:
        chunk = b // 8
        assert lo.native_hop_chunk_bytes(b) == chunk + (chunk // 64) * 4
    assert lo.native_hops_per_step() == len(lo.bucket_sizes) * 7
    assert lo.wire_bytes_per_step() == sum(
        7 * lo.native_hop_chunk_bytes(b) for b in lo.bucket_sizes)
    # hierarchical: only the DCN ring hops (dcn - 1 per bucket) are native
    lo_h = build_layout(tree, 8, CommsConfig(
        bucket_mb=0.0005, wire_dtype="int8", block=64, native_int8=True,
        hierarchy=True, dcn_size=2), ici=4, dcn=2)
    assert lo_h.native_hops_per_step() == len(lo_h.bucket_sizes) * 1
    assert lo_h.dcn_wire_bytes_per_step() == sum(
        lo_h.native_hop_chunk_bytes(b) for b in lo_h.bucket_sizes)
    # native is the int8 wire's implementation, and rides the DCN leg only
    with pytest.raises(ValueError, match="native"):
        CommsConfig(native_int8=True)
    with pytest.raises(ValueError, match="native"):
        CommsConfig(native_int8=True, wire_dtype="int8", hierarchy=True,
                    dcn_size=2, quantize_dcn=False)


def test_native_knob_resolution(orca_context, monkeypatch):
    monkeypatch.setenv("ZOO_COMMS_NATIVE_INT8", "1")
    monkeypatch.setenv("ZOO_ALLREDUCE_DTYPE", "int8")
    cfg = CommsConfig.resolve({})
    assert cfg.active and cfg.native_int8 and cfg.wire_dtype == "int8"
    assert cfg.fingerprint().endswith(":native=1")
    # config dict wins over env
    assert not CommsConfig.resolve({"comms_native_int8": False}).native_int8
    monkeypatch.delenv("ZOO_COMMS_NATIVE_INT8")
    monkeypatch.delenv("ZOO_ALLREDUCE_DTYPE")
    # off keeps every pre-existing fingerprint byte-identical (cached
    # executables stay valid)
    assert "native" not in CommsConfig.resolve(
        {"grad_bucket_mb": 0.001, "allreduce_dtype": "int8"}).fingerprint()


def test_native_quantize_pack_roundtrip(orca_context):
    from analytics_zoo_tpu.parallel.comms import (
        dequantize_blocks, dequantize_blocks_np, pack_wire,
        quantize_blocks, quantize_blocks_np, quantize_wire, unpack_wire)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(512).astype(np.float32))
    q, s = quantize_blocks(x, 64)
    # the split form IS the simulated wire's math, bit for bit
    assert (np.asarray(dequantize_blocks(q, s, 64)) ==
            np.asarray(quantize_wire(x, "int8", 64))).all()
    # pack -> one int8 hop operand (payload + 4 B/block of bitcast
    # scales); unpack round-trips both exactly
    packed = pack_wire(q, s)
    assert packed.dtype == jnp.int8 and packed.shape == (512 + 8 * 4,)
    q2, s2 = unpack_wire(packed, 512, 64)
    assert (np.asarray(q2) == np.asarray(q).reshape(-1)).all()
    assert (np.asarray(s2) == np.asarray(s)).all()
    # numpy twins are bit-exact (np.round and jnp.round both half-even)
    qn, sn = quantize_blocks_np(np.asarray(x), 64)
    assert (qn == np.asarray(q).reshape(-1)).all()
    assert (sn == np.asarray(s)).all()
    assert (dequantize_blocks_np(qn, sn, 64) ==
            np.asarray(dequantize_blocks(q, s, 64))).all()
    # zero blocks carry scale 1.0: nothing divides by zero and padding
    # dequantizes to exact 0.0
    qz, sz = quantize_blocks(jnp.zeros(128), 64)
    assert (np.asarray(qz) == 0).all() and (np.asarray(sz) == 1.0).all()
    # ragged final block (a bucket's padded tail): the tail zeros share
    # the last real values' scale and come back as exact zeros
    tail = jnp.concatenate([jnp.asarray(rng.randn(40), jnp.float32),
                            jnp.zeros(24)])
    qt, st = quantize_blocks(tail, 64)
    deq = np.asarray(dequantize_blocks(qt, st, 64))
    assert (deq[40:] == 0).all() and np.abs(deq[:40]).max() > 0


def test_native_ring_matches_twin_and_exact_reduce(orca_context):
    """The ring's MATH, checked two ways on one bucket: generic floats
    match the numpy host twin to within an ulp per hop (the device may
    contract dequant-multiply + accumulate into one FMA; everything else
    — quantize math, accumulation order, EF capture — is identical), and
    where the quantization is exact (block-constant 127*k values, so
    every scale is the integer k) the ring equals the exact linear
    reduce-scatter it replaces BITWISE, with a residual of exact zero."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from analytics_zoo_tpu.parallel.comms import (
        native_ring_reduce_scatter_np)

    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    b, block = 512, 64
    tree = {"w": np.zeros(b, np.float32)}
    cfg = CommsConfig(bucket_mb=4.0, wire_dtype="int8", block=block,
                      native_int8=True)
    lo = build_layout(tree, 8, cfg)
    assert lo.bucket_sizes == (b,)
    plan = CommsPlan(cfg, lo)

    def ring_body(v, r):
        shards, nr = plan.native_reduce_scatter_bucket_list([v[0]], r[0])
        return shards[0], nr

    ring = jax.jit(shard_map(
        ring_body, mesh=mesh, in_specs=(P("dp", None), P("dp", None)),
        out_specs=(P("dp"), P("dp")), check_vma=False))

    rng = np.random.RandomState(3)
    stacked = (rng.rand(8, b).astype(np.float32) - 0.5) * 3
    resid = (rng.randn(8, b) * 1e-3).astype(np.float32)
    got, got_r = ring(stacked, resid)
    want, want_r = native_ring_reduce_scatter_np(stacked, block,
                                                 resid=resid.copy())
    # one f32 ulp at these magnitudes is ~1e-6; 7 hops of possible FMA
    # contraction stay well inside 1e-5 while any REAL divergence (wrong
    # chunk routing, a dropped hop, misaligned EF) is orders larger
    assert np.abs(np.asarray(got).reshape(8, -1) - want).max() < 1e-5
    assert np.abs(np.asarray(got_r).reshape(8, b) - want_r).max() < 1e-5

    # exact case: block-constant values 127*k (k integer) quantize to
    # +-127 with scale exactly |k| at EVERY hop — lossless end to end
    k = rng.randint(-8, 9, (8, b // block)).astype(np.float32)
    exact = np.repeat(k * 127.0, block, axis=1)
    got_e, got_re = ring(exact, np.zeros_like(exact))
    full = exact.sum(0)                  # any association exact: integers
    csize = b // 8
    rows = np.asarray(got_e).reshape(8, csize)
    for p in range(8):
        assert (rows[p] == full[p * csize:(p + 1) * csize]).all()
    assert (np.asarray(got_re) == 0).all()

    # DCN-group rings (the hierarchical leg): twin == device per group,
    # same ulp-per-hop window
    groups = [[0, 4], [1, 5], [2, 6], [3, 7]]   # ici=4, dcn=2 rings
    want_g, _ = native_ring_reduce_scatter_np(stacked, block,
                                              resid=resid.copy(),
                                              groups=groups)

    def ring_g_body(v, r):
        perm = [(g[j], g[(j + 1) % 2]) for g in groups for j in range(2)]
        from analytics_zoo_tpu.parallel import collective as Cx
        pos = Cx.axis_index("dp") // 4
        return plan._native_exchange(v[0], r[0], perm, 2, pos)

    ring_g = jax.jit(shard_map(
        ring_g_body, mesh=mesh, in_specs=(P("dp", None), P("dp", None)),
        out_specs=(P("dp"), P("dp")), check_vma=False))
    got_g, _ = ring_g(stacked, resid)
    assert np.abs(np.asarray(got_g).reshape(8, -1) - want_g).max() < 1e-5


@pytest.mark.parametrize("variant", ["classic", "hier"])
def test_native_wire_error_feedback_bounds_drift(orca_context, variant):
    """The PR-8 EF contract carries over to the native ring: 50 steps of
    int8-on-the-wire training track the exact-f32 run within the same
    drift bounds as the simulated wire, with the residual alive on the
    same domain (flat classic / post-ICI chunk hierarchical)."""
    data = _data(n=128)
    steps = 50
    epochs = -(-steps * 32 // 128)      # >= 50 optimizer steps
    base = {"grad_bucket_mb": 0.001} if variant == "classic" \
        else _hier_cfg()
    le, _ = _fit(base, epochs=epochs, data=data)
    lq, eq = _fit({**base, "allreduce_dtype": "int8",
                   "allreduce_block": 64, "comms_native_int8": True},
                  epochs=epochs, data=data)
    assert eq.engine.comms_steps >= steps
    lo = eq.engine.comms.layout
    resid = np.asarray(eq.engine.comms_resid)
    want_elems = (lo.padded_total // lo.ici if variant == "hier"
                  else lo.padded_total)
    assert resid.shape == (8, want_elems)
    assert np.abs(resid).max() > 0
    le, lq = np.asarray(le), np.asarray(lq)
    assert np.all(np.abs(lq - le) <= 5e-3 * np.maximum(np.abs(le), 1e-3))
    assert np.abs(lq[-1] - le[-1]) <= 2e-3 * max(abs(le[-1]), 1e-3)
    snap = eq.data_pipeline_stats()["comms"]
    assert snap["native_int8"] and snap["native_hops"] > 0
    if variant == "classic":
        # the packed ring moves ~(n-1)/n * (1 + 4/block) int8 bytes per
        # f32 gradient element — better than 4x under the f32 wire
        ratio = snap["grad_bytes_f32"] / snap["wire_bytes_per_step"]
        assert ratio >= 3.0
    else:
        # the DCN leg genuinely shrinks vs the bf16 wire (the bench gate)
        hier = snap["hierarchy"]
        tree = jax.tree_util.tree_map(np.asarray, eq.engine.params)
        lo_bf = build_layout(tree, 8, CommsConfig(
            bucket_mb=0.001, wire_dtype="bf16", hierarchy=True,
            dcn_size=2), ici=4, dcn=2)
        assert (lo_bf.dcn_wire_bytes_per_step()
                / hier["dcn_wire_bytes_per_step"]) >= 1.9


def test_native_bit_identity_family(orca_context):
    """The wire moved but the update math did not: sharded == unsharded,
    overlapped and scan-fused dispatch all stay bit-identical on the
    native ring, for the classic and the hierarchical variants."""
    data = _data()
    ln, en = _fit(_native_cfg(), data=data)
    ls, es = _fit(_native_cfg(), data=data, sharded_update=True)
    lo_, _ = _fit(_native_cfg(comms_overlap=True), data=data)
    lf, _ = _fit(_native_cfg(), data=data, fuse=2, sharded_update=True)
    assert ln == ls == lo_ == lf
    assert (_flat_params(en) == _flat_params(es)).all()
    lh, eh = _fit(_native_hier_cfg(), data=data)
    lhs, ehs = _fit(_native_hier_cfg(), data=data, sharded_update=True)
    assert lh == lhs
    assert (_flat_params(eh) == _flat_params(ehs)).all()


def test_native_clipping_matches_between_update_modes(orca_context):
    """Norm clipping reads each replica's unique-ownership ring chunks,
    so ZeRO-1 cannot move the clip threshold by an ulp under the native
    wire either."""
    def clipped(shard):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1,
                                   **_native_cfg()},
                           sharded_update=shard)
        est.set_l2_norm_gradient_clipping(0.05)
        stats = est.fit(dict(_data()), epochs=2, batch_size=32,
                        verbose=False)
        return [s["train_loss"] for s in stats], _flat_params(est)

    lb, wb = clipped(False)
    ls, ws = clipped(True)
    assert lb == ls
    assert (wb == ws).all()


def test_native_salts_compile_key(orca_context):
    """Native on/off is program shape — the simulated-wire executable
    cannot be reused for the ring (and vice versa)."""
    from analytics_zoo_tpu.orca.learn.utils import data_to_iterator

    def key_for(cfg):
        est = TPUEstimator(MLP(), loss="mse", optimizer="adam", seed=0,
                           config={"steps_per_dispatch": 1, **cfg})
        it = data_to_iterator(dict(_data()), 32, est.mesh, None, None,
                              shuffle=False, config=est.config)
        batch = next(it.epoch(shuffle=False, prefetch=False))
        est.engine.build(tuple(np.asarray(a) for a in batch.x))
        return est.engine.train_step_cache_key(batch)

    k_sim = key_for({"grad_bucket_mb": 0.001, "allreduce_dtype": "int8",
                     "allreduce_block": 64})
    k_nat = key_for(_native_cfg())
    k_nat2 = key_for(_native_cfg())
    k_nat_h = key_for(_native_hier_cfg())
    assert None not in (k_sim, k_nat, k_nat_h)
    assert k_nat == k_nat2               # same wire -> shared executable
    assert len({k_sim, k_nat, k_nat_h}) == 3


def test_native_accounting_byte_exact_and_tamper(orca_context):
    """The acceptance flip: hlo_lint checks the native wire BYTE-EXACT —
    no simulated-wire exemption — so tampering the declared hop count or
    byte totals fails the gate on the real lowered program."""
    from analytics_zoo_tpu.analysis.hlo_lint import HloLinter

    est, text, declared = _build_lowered(_native_hier_cfg(),
                                         sharded_update=True)
    assert declared["native_int8"] and declared["native_hops"] > 0
    assert not HloLinter().lint_text(text, label="train",
                                     declared=declared)
    bad_hops = dict(declared, native_hops=declared["native_hops"] + 1)
    f1 = HloLinter().lint_text(text, label="train", declared=bad_hops)
    assert f1 and any("ring hops" in f.message for f in f1)
    bad_bytes = dict(declared, hierarchy=dict(
        declared["hierarchy"],
        dcn_wire_bytes_per_step=declared["hierarchy"]
        ["dcn_wire_bytes_per_step"] + 4))
    f2 = HloLinter().lint_text(text, label="train", declared=bad_bytes)
    assert f2 and any("DCN leg moves" in f.message for f in f2)

    # classic ring: the flat wire-byte claim is checked too (the
    # simulated int8 wire skips this check; the native one must not)
    est2, text2, declared2 = _build_lowered(_native_cfg())
    assert not HloLinter().lint_text(text2, label="train",
                                     declared=declared2)
    bad3 = dict(declared2,
                wire_bytes_per_step=declared2["wire_bytes_per_step"] + 4)
    f3 = HloLinter().lint_text(text2, label="train", declared=bad3)
    assert f3 and any("gradient wire moves" in f.message for f in f3)
