"""Long-context attention: flash kernel (interpret mode), ring attention and
Ulysses sequence parallelism on the 8-device virtual mesh, values + grads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from analytics_zoo_tpu.ops.attention import (
    FLASH_RESIDUAL_NAMES, flash_attention, mha_reference)
from analytics_zoo_tpu.parallel.ring_attention import (
    ring_attention, sequence_sharded_attention, ulysses_attention)


def _qkv(b=2, s=64, h=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_blockwise_attention_matches_reference():
    """Scan-over-K-blocks exact attention (the flash backward path): value
    and gradients must match materialized attention."""
    from analytics_zoo_tpu.ops.attention import blockwise_attention

    for causal in (False, True):
        q, k, v = _qkv(s=96)
        ref = mha_reference(q, k, v, causal=causal)
        out = blockwise_attention(q, k, v, causal=causal, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

        def loss_ref(q, k, v):
            return (mha_reference(q, k, v, causal=causal) ** 2).sum()

        def loss_blk(q, k, v):
            return (blockwise_attention(q, k, v, causal=causal,
                                        block_k=32) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_blk):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=5e-4, atol=5e-4)

    # decode shape (s_q < s_k): causal alignment must be bottom-right like
    # mha_reference — the single query sees every key
    q1 = q[:, :1]
    ref = mha_reference(q1, k, v, causal=True)
    out = blockwise_attention(q1, k, v, causal=True, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_causal_decode_shape_matches_reference():
    """Causal with s_q < s_k (decode): the kernel masks bottom-right
    aligned — fwd, _lse_pass and _flash_bwd must all use the same
    (s_k - s_q) offset (round-3 advisor finding), so both values and
    gradients must match mha_reference."""
    q, k, v = _qkv(s=64)
    qs = q[:, :32]
    ref = mha_reference(qs, k, v, causal=True)
    out = flash_attention(qs, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_ref(qs, k, v):
        return jnp.sum(mha_reference(qs, k, v, causal=True) ** 2)

    def loss_flash(qs, k, v):
        return jnp.sum(flash_attention(qs, k, v, causal=True,
                                       block_q=16, block_k=16) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qs, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(qs, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_bf16_matches_reference():
    """bf16 q/k/v: the kernel keeps matmul operands in bf16 (MXU rate) with
    f32 accumulation and f32 softmax state — values and grads must agree
    with the f32 reference to bf16 precision."""
    q, k, v = _qkv(s=64)
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(qb, kb, vb, causal=True, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a),
                                   rtol=6e-2, atol=6e-2)


def test_flash_block_autofit_stays_on_kernel():
    """Default tiles with a sequence divisible by 128 but by no larger
    ladder rung: fit_block must shrink the tile (kernel path, no O(S^2)
    materialize) and the numerics must still match the reference.
    s=1152 > 1024, 1152 % 1024 != 0, 1152 % 512 != 0, 1152 % 256 != 0,
    so only the 128 rung of the divisor ladder keeps this on the kernel."""
    q, k, v = _qkv(s=1152)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)     # default 1024x1024 tiles
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_grads_match_reference():
    q, k, v = _qkv(s=32)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=16, block_k=16) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its equations'
    parameters (remat, cond, custom_vjp); a kernel's own body is left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)


def pallas_kernels(jaxpr):
    """The kernel function's name of every ``pallas_call`` in a jaxpr."""
    return [eqn.params["jaxpr"].debug_info.func_name
            for eqn in equations(jaxpr) if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("d_qk,d_v", [(48, 32), (32, 32)])
def test_remat_policy_keeps_the_forward_kernels_results(d_qk, d_v):
    """Under a remat that saves ``FLASH_RESIDUAL_NAMES`` the backward pass
    reads the first launch's output and logsumexp: one forward kernel, the
    fused backward kernel, and the same gradients to the bit as the plain
    remat's, which runs the forward kernel a second time."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 32, 2, d), jnp.float32)
               for d in (d_qk, d_qk, d_v))

    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16))

    keep = jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUAL_NAMES)
    plain = jax.grad(jax.checkpoint(f), (0, 1, 2))
    kept = jax.grad(jax.checkpoint(f, policy=keep), (0, 1, 2))
    backward = ["_flash_bwd_fused_kernel"]
    assert pallas_kernels(jax.make_jaxpr(plain)(q, k, v).jaxpr) == \
        ["_flash_kernel"] * 2 + backward
    assert pallas_kernels(jax.make_jaxpr(kept)(q, k, v).jaxpr) == \
        ["_flash_kernel"] + backward
    for a, b in zip(plain(q, k, v), kept(q, k, v)):
        assert a.shape == b.shape and bool(jnp.all(a == b))
        assert bool(jnp.any(a != 0))


def _flash_grads(q, k, v, w, causal, block=16):
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=block,
                                       block_k=block) * w)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _mla_qkv(d_qk, d_v, s_q, s_k=64, seed=5):
    rng = np.random.RandomState(seed)
    mk = lambda s, d: jnp.asarray(rng.randn(2, s, 2, d), jnp.float32) * 0.5
    return mk(s_q, d_qk), mk(s_k, d_qk), mk(s_k, d_v), mk(s_q, d_v)


@pytest.mark.parametrize("causal,s_q", [(False, 64), (True, 64), (True, 32)])
@pytest.mark.parametrize("d_qk,d_v", [(64, 64), (192, 128)])
def test_fused_backward_equals_the_two_kernel_backward(monkeypatch, d_qk,
                                                       d_v, causal, s_q):
    """One launch (dQ accumulated beside dK/dV) and the dQ + dK/dV pair give
    the same float32 gradients to the bit: for a fixed q tile the dS . k
    contributions arrive in ascending k order either way. Non-causal,
    causal, and the causal decode shape s_q < s_k; equal heads and MLA's.
    The counter says which backward each trace took."""
    import analytics_zoo_tpu.ops.attention as attn
    q, k, v, w = _mla_qkv(d_qk, d_v, s_q)
    fused_n, pair_n = (attn._BACKWARD_FUSED.value,
                       attn._BACKWARD_TWO_KERNEL.value)
    fused = _flash_grads(q, k, v, w, causal)
    assert (attn._BACKWARD_FUSED.value,
            attn._BACKWARD_TWO_KERNEL.value) == (fused_n + 1, pair_n)
    monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", 0)
    pair = _flash_grads(q, k, v, w, causal)
    assert (attn._BACKWARD_FUSED.value,
            attn._BACKWARD_TWO_KERNEL.value) == (fused_n + 1, pair_n + 1)
    for a, b in zip(fused, pair):
        assert a.shape == b.shape and bool(jnp.all(a == b))
        assert bool(jnp.any(a != 0))


def test_a_dq_past_the_vmem_budget_takes_the_two_kernel_backward():
    """The shape decides the path: a (batch, head)'s dQ accumulator and
    output block are counted in bytes from s_q, d and the dtype, and past
    the module's budget the dQ kernel and the dK/dV kernel run, with
    mha_reference's gradients. The budget admits the token cell's 8192 x
    192 in bf16 three times over, and no sequence a 16 GB chip can train
    at four times that."""
    import analytics_zoo_tpu.ops.attention as attn
    assert attn._fused_bwd_dq_bytes(8192, 192, jnp.bfloat16) == 16 * attn.MIB
    assert attn._fused_bwd_dq_bytes(8192, 64, jnp.float32) == 12 * attn.MIB
    assert attn._fused_bwd_dq_bytes(8192, 192, jnp.bfloat16) * 3 \
        <= attn._FUSED_BWD_DQ_BYTES \
        < attn._fused_bwd_dq_bytes(32768, 192, jnp.bfloat16)
    # the smallest float32 sequence past the budget at d = 8: 128 lanes
    s_q = attn._FUSED_BWD_DQ_BYTES // (128 * 12) + 512
    assert attn._fused_bwd_dq_bytes(s_q, 8, jnp.float32) \
        > attn._FUSED_BWD_DQ_BYTES
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, s_q, 1, 8), jnp.float32) * 0.5
    k, v = (jnp.asarray(rng.randn(1, 16, 1, 8), jnp.float32) * 0.5
            for _ in range(2))
    w = jnp.asarray(rng.randn(1, s_q, 1, 8), jnp.float32)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * w)

    flash = jax.grad(loss(flash_attention, block_q=512, block_k=16),
                     argnums=(0, 1, 2))
    kernels = pallas_kernels(jax.make_jaxpr(flash)(q, k, v).jaxpr)
    assert kernels == ["_flash_kernel", "_flash_bwd_dq_kernel",
                       "_flash_bwd_dkv_kernel"]
    g_ref = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(flash(q, k, v), g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def _forward_call(q, k, v, **kw):
    """The forward kernel's ``pallas_call`` equation under differentiation
    (the launch that also writes the logsumexp)."""
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: flash_attention(*a, **kw), q, k, v)[0])(q, k, v).jaxpr
    (eqn,) = [e for e in equations(jaxpr)
              if e.primitive.name == "pallas_call"]
    return eqn


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("d_qk,d_v,v_lanes", [(192, 128, 128), (128, 128, 128),
                                              (64, 64, 65), (48, 32, 33)])
def test_forward_normaliser_follows_vs_head_size(d_qk, d_v, v_lanes, causal):
    """Where d_v fills whole 128-lane tiles the kernel takes v as it is and
    keeps the normaliser as a running float32 row sum in a third scratch;
    elsewhere (the layers' 64) v carries the ones column, which is free
    there. Either way the output and the base-2 logsumexp are
    mha_reference's."""
    from analytics_zoo_tpu.ops.attention import _flash_fwd
    q, k, v, _ = _mla_qkv(d_qk, d_v, 64)
    eqn = _forward_call(q, k, v, causal=causal, block_q=16, block_k=16)
    assert eqn.invars[2].aval.shape[-1] == v_lanes
    # the kernel's refs: q, k, v; output, logsumexp; then the scratch
    n_scratch = len(eqn.params["jaxpr"].invars) - 3 - 2
    assert n_scratch == (2 if v_lanes > d_v else 3)
    sm_scale = d_qk ** -0.5
    out, (_, _, _, _, lse) = _flash_fwd(q, k, v, causal, sm_scale, 16, 16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mha_reference(q, k, v, causal=causal)),
        rtol=2e-5, atol=2e-6)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), logits,
                           -jnp.inf)
    want = jax.nn.logsumexp(logits, axis=-1) / np.log(2.0)   # (B, H, S)
    np.testing.assert_allclose(np.asarray(lse).reshape(want.shape),
                               np.asarray(want), rtol=2e-6, atol=2e-6)


def _sp_mesh():
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("dp", "sp"))


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_matches_full(strategy, causal):
    q, k, v = _qkv(b=2, s=64, h=4, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    mesh = _sp_mesh()
    fn = ring_attention if strategy == "ring" else ulysses_attention
    spec = P("dp", "sp", None, None)

    @jax.jit
    @shard_map(mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def run(ql, kl, vl):
        return fn(ql, kl, vl, axis_name="sp", causal=causal)

    out = run(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_grads():
    q, k, v = _qkv(b=1, s=32, h=2, d=8)
    mesh = _sp_mesh()
    spec = P(None, "sp", None, None)

    def loss_ring(q, k, v):
        out = shard_map(
            lambda ql, kl, vl: ring_attention(ql, kl, vl, axis_name="sp",
                                              causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_ulysses_flash_kernel_path():
    """ulysses with use_flash=True under shard_map (on CPU this exercises
    flash_attention's vma-aware fallback; on TPU, the pallas kernel)."""
    q, k, v = _qkv(b=2, s=64, h=4, d=16)
    ref = mha_reference(q, k, v, causal=True)
    mesh = _sp_mesh()
    spec = P("dp", "sp", None, None)

    @jax.jit
    @shard_map(mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def run(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, axis_name="sp", causal=True,
                                 use_flash=True)

    np.testing.assert_allclose(np.asarray(run(q, k, v)), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_mixed_vma_cross_attention():
    """Replicated q against sequence-sharded k/v must lift q's vma."""
    q, k, v = _qkv(b=1, s=32, h=2, d=8)
    mesh = _sp_mesh()

    @jax.jit
    @shard_map(mesh=mesh, in_specs=(P(), P(None, "sp"), P(None, "sp")),
                   out_specs=P("sp"))
    def run(ql, kl, vl):
        # local full attention on each device's k/v shard — the point is
        # that mixed-vma inputs compile and run, not the combine.
        return flash_attention(ql, kl, vl, block_q=16, block_k=16)

    out = run(q, k, v)
    assert np.isfinite(np.asarray(out)).all()


def test_sequence_sharded_wrapper():
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    mesh = create_mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(b=2, s=32, h=4, d=8)
    ref = mha_reference(q, k, v, causal=False)
    out = sequence_sharded_attention(mesh, q, k, v, strategy="ring")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("d_qk,d_v,dq_budget,backward_calls", [
    (64, 64, None, 1), (128, 128, None, 1), (192, 128, None, 1),
    (192, 128, 0, 2)])
def test_flash_attention_lowers_to_mosaic_for_tpu(monkeypatch, d_qk, d_v,
                                                  dq_budget, backward_calls):
    """The path tier-1 cannot execute: with the backend decision forced to
    the compiled kernel, cross-lowering for platform tpu runs the Pallas ->
    Mosaic lowering on the CPU. One custom call forward; for the gradient
    forward-with-lse and the fused backward (2 a call site), or
    forward-with-lse, dQ and dK/dV (3) where dQ is past the budget — no
    interpret mode, no mha_reference."""
    import analytics_zoo_tpu.ops.attention as attn
    monkeypatch.setattr(attn, "_interpret", lambda: False)
    if dq_budget is not None:
        monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", dq_budget)
    qk = jax.ShapeDtypeStruct((1, 512, 2, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 512, 2, d_v), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    fwd = jax.export.export(
        jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)),
        platforms=["tpu"])(qk, qk, v).mlir_module()
    grad = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(qk, qk, v).mlir_module()
    assert fwd.count("tpu_custom_call") == 1
    assert grad.count("tpu_custom_call") == 1 + backward_calls


def test_flash_attention_refuses_unknown_platforms(monkeypatch):
    """Interpret mode is for the CPU backend only; a backend with no kernel
    lowering is an error, not a silent interpreter run."""
    import analytics_zoo_tpu.ops.attention as attn
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        attn._interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn._interpret() is False


def test_reference_fall_through_on_tpu_is_counted(monkeypatch):
    """A shape no kernel tile fits takes mha_reference; on a TPU that is a
    counted, logged event (chip_smoke.py asserts the count stays zero)."""
    import analytics_zoo_tpu.ops.attention as attn
    q, _, _ = _qkv(s=32)
    _, k, v = _qkv(s=16)            # causal with s_q > s_k: rows see no key
    before = attn._REFERENCE_ON_TPU.value
    flash_attention(q, k, v, causal=True)
    assert attn._REFERENCE_ON_TPU.value == before       # CPU: silent
    monkeypatch.setattr(attn, "_interpret", lambda: False)
    out = flash_attention(q, k, v, causal=True)
    assert attn._REFERENCE_ON_TPU.value == before + 1
    np.testing.assert_allclose(out, mha_reference(q, k, v, causal=True),
                               rtol=1e-6)


# --- grouped queries and a causal window --------------------------------------

def _gqa_qkv(s_q, s_k, h, h_kv, d=16, d_v=16, seed=11):
    rng = np.random.RandomState(seed)
    mk = lambda s, hh, dd: jnp.asarray(rng.randn(1, s, hh, dd),   # noqa: E731
                                       jnp.float32) * 0.5
    return mk(s_q, h, d), mk(s_k, h_kv, d), mk(s_k, h_kv, d_v), \
        mk(s_q, h, d_v)


@pytest.mark.parametrize("s_q,s_k,h,h_kv,window,block", [
    (64, 64, 4, 2, None, 16),       # grouped alone
    (64, 64, 8, 1, None, 16),       # one kv head for all
    (64, 64, 2, 2, 24, 16),         # a window alone, no multiple of the tile
    (128, 128, 8, 2, 40, 16),       # both; q tiles wholly outside the window
    (128, 128, 4, 2, 33, 32),       # the far edge inside a tile's first row
    (32, 64, 4, 2, 24, 16),         # the decode shape, s_q < s_k
    (64, 64, 4, 1, 17, 8),          # more bands than the window has tiles
])
def test_grouped_and_windowed_flash_matches_reference(monkeypatch, s_q, s_k,
                                                      h, h_kv, window, block):
    """k and v with fewer heads than q and a causal window, forward and
    gradients against ``mha_reference`` given the same mask (which repeats k
    and v and writes the mask out); dK and dV come back at the kv heads'
    count, summed over each group inside the kernel; the fused backward and
    the dQ + dK/dV pair agree to the bit."""
    import analytics_zoo_tpu.ops.attention as attn
    q, k, v, w = _gqa_qkv(s_q, s_k, h, h_kv)

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, window=window, **kw) * w)

    tiles = dict(block_q=block, block_k=block)
    out = flash_attention(q, k, v, causal=True, window=window, **tiles)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(mha_reference(q, k, v, causal=True, window=window)),
        rtol=2e-5, atol=2e-6)
    want = jax.grad(loss(mha_reference), (0, 1, 2))(q, k, v)

    def flash(backward):
        # a closure of its own each time: the budget is read as the
        # backward is traced
        grad = jax.grad(loss(flash_attention, **tiles), (0, 1, 2))
        assert pallas_kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr) == \
            ["_flash_kernel"] + backward
        return grad(q, k, v)

    fused = flash(["_flash_bwd_fused_kernel"])
    monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", 0)
    pair = flash(["_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"])
    for a, b, c in zip(fused, pair, want):
        assert a.shape == c.shape and bool(jnp.all(a == b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4,
                                   atol=1e-5)


def _path_counts():
    import analytics_zoo_tpu.ops.attention as attn
    return {path: child.value for path, child in (
        ("fused", attn._BACKWARD_FUSED),
        ("fused_by_head", attn._BACKWARD_FUSED_BY_HEAD),
        ("two_kernel", attn._BACKWARD_TWO_KERNEL))}


_BACKWARD_KERNELS = {
    "fused": ["_flash_bwd_fused_kernel"],
    "fused_by_head": ["_flash_bwd_fused_kernel"],
    "two_kernel": ["_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"]}


@pytest.mark.parametrize("s_q,s_k,group,window,d", [
    *[(64, 64, group, window, d) for group in (2, 4, 8)
      for window in (None, 24) for d in (64, 128)],
    (48, 64, 4, 24, 64),            # the decode shape, s_q < s_k
])
def test_a_group_past_the_budget_is_one_launch_a_head_at_a_time(
        monkeypatch, s_q, s_k, group, window, d):
    """Between the two: the group's dQ is past the budget, one query
    head's dQ with the kv head's dK and dV is not. The backward is still one
    launch, grid (kv heads, group, k blocks, q tiles), and its gradients are
    the two-kernel pair's to the bit (dK and dV meet their tiles head by
    head and q tile by q tile, dQ in ascending k order, as the pair's do)
    and ``mha_reference``'s within 2e-3. Two query heads a kv head never
    take it: dK and dV held whole are as many bytes as a second head's dQ
    twice over, so past the group's dQ the pair runs. The counter's three
    labels say which path each trace took."""
    import analytics_zoo_tpu.ops.attention as attn
    q, k, v, w = _gqa_qkv(s_q, s_k, 2 * group, 2, d=d, d_v=d)
    one_head = attn._fused_bwd_dq_bytes(s_q, d, q.dtype)
    by_head = one_head + 2 * attn._fused_bwd_dq_bytes(s_k, d, k.dtype)
    whole_group = group * one_head
    path = "fused_by_head" if by_head < whole_group else "two_kernel"
    assert path == ("two_kernel" if group == 2 else "fused_by_head")

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, window=window, **kw) * w)

    def flash(budget, path):
        monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", budget)
        before = _path_counts()
        grad = jax.grad(loss(flash_attention, block_q=16, block_k=16),
                        (0, 1, 2))
        jaxpr = jax.make_jaxpr(grad)(q, k, v).jaxpr
        assert pallas_kernels(jaxpr) == \
            ["_flash_kernel"] + _BACKWARD_KERNELS[path]
        moved = {p: n - before[p] for p, n in _path_counts().items()}
        assert moved == {p: int(p == path) for p in moved}
        return grad(q, k, v), [tuple(e.params["grid_mapping"].grid)
                               for e in equations(jaxpr)
                               if e.primitive.name == "pallas_call"]

    # between one head's bytes and the group's; what by-head holds, if less
    mine, grids = flash(min(by_head, whole_group - 1), path)
    if path == "fused_by_head":
        assert len(grids[1]) == 4 and grids[1][:3] == (2, group, s_k // 16)
    pair, _ = flash(0, "two_kernel")
    want = jax.grad(loss(mha_reference), (0, 1, 2))(q, k, v)
    for a, b, c in zip(mine, pair, want):
        assert a.shape == c.shape and bool(jnp.all(a == b))
        assert bool(jnp.any(a != 0))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-3,
                                   atol=2e-3)


@pytest.mark.parametrize("b,s,h,h_kv,d,d_v,window,path", [
    (1, 16384, 32, 4, 128, 128, None, "fused_by_head"),  # the grouped-query
    (1, 16384, 32, 4, 128, 128, 2048, "fused_by_head"),  # cell's two kinds
    (1, 8192, 4, 1, 128, 128, None, "fused"),            # the Nemotron cell
    (2, 8192, 32, 32, 192, 128, None, "fused"),          # the MLA cell
    (1, 32768, 32, 4, 128, 128, None, "two_kernel"),     # grouped, past 16384
    (1, 65536, 2, 2, 128, 128, None, "two_kernel"),      # a head past 49152
])
def test_the_shape_alone_chooses_the_backward(b, s, h, h_kv, d, d_v, window,
                                              path):
    """No budget patched, nothing computed (the gradient is traced on
    ``ShapeDtypeStruct``s): the three cells' shapes in bfloat16 and the
    path each compiles, by bytes against the one ``_FUSED_BWD_DQ_BYTES``;
    past it, the pair."""
    import analytics_zoo_tpu.ops.attention as attn
    assert attn._FUSED_BWD_DQ_BYTES == 48 * attn.MIB
    q, k, v = (jax.ShapeDtypeStruct((b, s, heads, width), jnp.bfloat16)
               for heads, width in ((h, d), (h_kv, d), (h_kv, d_v)))
    before = _path_counts()
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32).sum(),
        (0, 1, 2)))(q, k, v).jaxpr
    assert pallas_kernels(jaxpr) == ["_flash_kernel"] + \
        _BACKWARD_KERNELS[path]
    moved = {p: n - before[p] for p, n in _path_counts().items()}
    assert moved == {p: int(p == path) for p in moved}
    backward = [e for e in equations(jaxpr)
                if e.primitive.name == "pallas_call"][1]
    if path == "fused_by_head":
        bands = 5 if window else s // 512    # 2048 / 512 + the diagonal's
        assert tuple(backward.params["grid_mapping"].grid) == \
            (b * h_kv, h // h_kv, s // 512, bands)
        # one query head's dQ, the kv head's whole dK and dV
        assert [tuple(x.aval.shape) for x in backward.outvars] == \
            [(b * h, s, d), (b * h_kv, s, d), (b * h_kv, s, d_v)]


def test_a_window_is_a_mask_inside_the_causal_one():
    """``0 <= t - j < window``: a window of one sees the token alone, a
    window of the sequence is the causal mask (and takes its kernels), a
    shorter one differs from it; without ``causal`` it is refused, as are
    query heads the kv heads do not divide."""
    q, k, v, _ = _gqa_qkv(32, 32, 4, 2)
    causal = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, window=1,
                                   block_q=8, block_k=8)),
        np.asarray(jnp.repeat(v, 2, axis=2)), rtol=1e-6, atol=1e-6)
    whole = jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal=True, window=32, block_q=8, block_k=8))(q, k, v)
    plain = jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal=True, block_q=8, block_k=8))(q, k, v)
    assert str(whole) == str(plain)
    short = flash_attention(q, k, v, causal=True, window=9, block_q=8,
                            block_k=8)
    assert float(jnp.abs(short - causal).max()) > 1e-3
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q[:, :, :3], k, v, causal=True)


@pytest.mark.parametrize("by_head", [False, True])
@pytest.mark.parametrize("window,block,band", [(40, 16, 4), (16, 16, 2),
                                               (100, 32, 5)])
def test_a_windowed_grid_walks_the_bands_alone(monkeypatch, window, block,
                                               band, by_head):
    """The innermost grid extent of every windowed kernel is the widest
    band's tiles, not the sequence's: tiles wholly outside the window are
    no grid steps at all, and ``zoo_attention_window_tiles_total`` counts
    as many tiles visited as the mask needs, forward and backward: the
    fused backward's one pass, whole group or (``by_head``: four query
    heads on one kv head, a budget that holds one head's gradients) a head
    at a time. Run as plain causal the same call would compute several
    times as many."""
    import analytics_zoo_tpu.ops.attention as attn
    s, h, h_kv = 256, 4, 1 if by_head else 2
    q, k, v, w = _gqa_qkv(s, s, h, h_kv)
    if by_head:
        monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES",
                            3 * attn._fused_bwd_dq_bytes(s, 16, q.dtype))
    before = (attn._TILES_VISITED.value, attn._TILES_NEEDED.value)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=window, block_q=block,
        block_k=block) * w), (0, 1, 2)))(q, k, v).jaxpr
    grids = [tuple(e.params["grid_mapping"].grid) for e in equations(jaxpr)
             if e.primitive.name == "pallas_call"]
    n = s // block
    assert grids == [(h, n, band), (h_kv, h // h_kv, n, band) if by_head
                     else (h_kv, n, (h // h_kv) * band)]
    visited, needed = (attn._TILES_VISITED.value - before[0],
                       attn._TILES_NEEDED.value - before[1])
    assert visited == needed == 2 * h * attn._window_tiles(
        s, s, block, block, window)["needed"]
    assert needed < 2 * h * n * (n + 1) // 2       # the causal triangle's


def test_the_mla_paths_results_are_the_parents_to_the_bit():
    """Equal heads, no window: the flash kernels' float32 output and
    gradients are, to the bit, what the tree before grouped queries and
    windows gave on the same inputs (the digests were taken from commit
    86d9285's ``ops/attention.py`` in this installation: the fused backward
    at 48/32 and 192/128, the pair at the decode shape, the non-causal
    64/64 call site)."""
    import hashlib
    import analytics_zoo_tpu.ops.attention as attn
    parents = {(48, 32, True, 64, None): "bc162117368110dd",
               (192, 128, True, 64, None): "0bbe4d8b75470898",
               (192, 128, True, 32, 0): "10cbe3aa4faac686",
               (64, 64, False, 64, None): "4eceb98b5745e9ea"}
    keep = attn._FUSED_BWD_DQ_BYTES
    try:
        for (d_qk, d_v, causal, s_q, budget), want in parents.items():
            q, k, v, w = _mla_qkv(d_qk, d_v, s_q)
            attn._FUSED_BWD_DQ_BYTES = keep if budget is None else budget
            out = flash_attention(q, k, v, causal=causal, block_q=16,
                                  block_k=16)
            grads = _flash_grads(q, k, v, w, causal)
            digest = hashlib.sha256()
            for a in (out, *grads):
                digest.update(np.asarray(a, np.float32).tobytes())
            assert digest.hexdigest()[:16] == want, (d_qk, d_v, causal, s_q)
    finally:
        attn._FUSED_BWD_DQ_BYTES = keep


@pytest.mark.parametrize("budget,path,calls", [
    (None, "fused", 2), (6 * 2 ** 20, "fused_by_head", 2),
    (0, "two_kernel", 3)])
def test_grouped_windowed_flash_lowers_to_mosaic_for_tpu(monkeypatch, budget,
                                                         path, calls):
    """The band-walking kernels through the Pallas -> Mosaic lowering, 8
    query heads on 2 kv heads: the fused backward (2 custom calls a
    gradient); with 6 MiB, which hold a head's dQ and the kv head's dK and
    dV (2 MiB each) and not the group's 8 MiB of dQ, the fused backward a
    head at a time (2); past the budget, the pair (3); no k or v of 8 heads
    is an operand of any of them."""
    import analytics_zoo_tpu.ops.attention as attn
    monkeypatch.setattr(attn, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=640).astype(
            jnp.float32).sum()

    if budget is not None:
        monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", budget)
    before = _path_counts()
    grad = jax.export.export(
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
        platforms=["tpu"])(q, kv, kv).mlir_module()
    assert _path_counts() == dict(before, **{path: before[path] + 1})
    assert grad.count("tpu_custom_call") == calls
    assert "tensor<8x2048x128xbf16>" in grad          # q, by (b*h, s, d)
    assert "tensor<2x2048x128xbf16>" in grad          # k and v as given
