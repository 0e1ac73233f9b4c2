"""Expert parallelism (Switch MoE over the ep axis): with ample capacity
the all-to-all dispatched layer must equal the dense per-token
gather-through-its-expert computation exactly, gradients must match, and
overflow must drop (not corrupt) tokens. Beyond-parity axis — SURVEY
§2.3: the reference has no expert parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from analytics_zoo_tpu.parallel.expert_parallel import (
    expert_sharding, moe_apply, stack_expert_params)


def _mesh(ep=4):
    return Mesh(np.asarray(jax.devices()[:ep]).reshape(ep), ("ep",))


def _expert_fn(p, t):
    return jnp.tanh(t @ p["w1"]) @ p["w2"]


def _setup(e, d, h, n, seed=0):
    rng = np.random.RandomState(seed)
    experts = [{"w1": jnp.asarray(rng.randn(d, h).astype(np.float32) * .4),
                "w2": jnp.asarray(rng.randn(h, d).astype(np.float32) * .4)}
               for _ in range(e)]
    router = jnp.asarray(rng.randn(d, e).astype(np.float32))
    x = jnp.asarray(rng.randn(n, d).astype(np.float32))
    return experts, router, x


def _dense_reference(experts, router, x):
    probs = jax.nn.softmax(x @ router, axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    outs = jnp.stack([_expert_fn(p, x) for p in experts])   # (E, N, d)
    return gate[:, None] * jnp.take_along_axis(
        outs, idx[None, :, None], axis=0)[0]


def test_moe_matches_dense_reference():
    mesh = _mesh(4)
    experts, router, x = _setup(4, 8, 16, 32)
    stacked = stack_expert_params(experts)
    stacked = jax.device_put(stacked, expert_sharding(mesh, stacked))

    y, aux = jax.jit(lambda p, r, x: moe_apply(
        _expert_fn, p, r, x, mesh=mesh, capacity_factor=4.0))(
        stacked, router, x)
    ref = _dense_reference(experts, router, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) >= 1.0 - 1e-6       # load-balance term >= 1


def test_moe_gradients_match_dense():
    mesh = _mesh(4)
    experts, router, x = _setup(4, 6, 12, 16, seed=3)
    stacked = stack_expert_params(experts)

    def loss_moe(p, r):
        y, _ = moe_apply(_expert_fn, p, r, x, mesh=mesh,
                         capacity_factor=4.0)
        return jnp.sum(y ** 2)

    def loss_dense(p, r):
        per = [jax.tree_util.tree_map(lambda l: l[i], p) for i in range(4)]
        return jnp.sum(_dense_reference(per, r, x) ** 2)

    g_moe = jax.jit(jax.grad(loss_moe, argnums=(0, 1)))(stacked, router)
    g_dense = jax.grad(loss_dense, argnums=(0, 1))(stacked, router)
    for a, b in zip(jax.tree_util.tree_leaves(g_moe),
                    jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_moe_overflow_drops_tokens():
    """With capacity 1, only the FIRST token each rank routes to a given
    expert survives; later ones drop to zero output (Switch semantics)
    instead of corrupting the buffer — and survivors still match the
    dense computation."""
    mesh = _mesh(2)
    experts, router, x = _setup(2, 4, 8, 8, seed=1)
    stacked = stack_expert_params(experts)
    y, _ = moe_apply(_expert_fn, stacked, router, x, mesh=mesh,
                     capacity_factor=0.01)     # capacity = 1
    y = np.asarray(y)

    idx = np.argmax(np.asarray(jax.nn.softmax(x @ router, -1)), -1)
    expected_keep = []
    for rank in range(2):
        seen = set()
        for i in range(4):
            tok = rank * 4 + i
            if idx[tok] not in seen:
                seen.add(idx[tok])
                expected_keep.append(tok)
    got = set(np.where(np.abs(y).sum(-1) > 1e-9)[0])
    assert got == set(expected_keep), (got, expected_keep)
    ref = np.asarray(_dense_reference(experts, router, x))
    for tok in expected_keep:
        np.testing.assert_allclose(y[tok], ref[tok], rtol=1e-5, atol=1e-6)


def _dense_reference_topk(experts, router, x, top_k):
    """Dense top-k: renormalized gates over the chosen experts (GShard)."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    if top_k > 1:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    outs = jnp.stack([_expert_fn(p, x) for p in experts])   # (E, N, d)
    y = jnp.zeros_like(x)
    for k in range(top_k):
        pick = jnp.take_along_axis(outs, topi[:, k][None, :, None],
                                   axis=0)[0]
        y = y + topv[:, k][:, None] * pick
    return y


def test_moe_multi_expert_per_rank_matches_dense():
    """E = 2 x ep experts (two resident per rank): all-to-all dispatch +
    vmapped local experts must equal the dense computation."""
    mesh = _mesh(4)
    experts, router, x = _setup(8, 8, 16, 32, seed=5)
    stacked = stack_expert_params(experts)
    stacked = jax.device_put(stacked, expert_sharding(mesh, stacked))
    y, aux = jax.jit(lambda p, r, x: moe_apply(
        _expert_fn, p, r, x, mesh=mesh, capacity_factor=8.0))(
        stacked, router, x)
    ref = _dense_reference_topk(experts, router, x, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) >= 1.0 - 1e-6


def test_moe_top2_matches_dense():
    """top_k=2 (GShard): renormalized two-expert mixture equals dense."""
    mesh = _mesh(4)
    experts, router, x = _setup(8, 8, 16, 32, seed=7)
    stacked = stack_expert_params(experts)
    stacked = jax.device_put(stacked, expert_sharding(mesh, stacked))
    y, aux = jax.jit(lambda p, r, x: moe_apply(
        _expert_fn, p, r, x, mesh=mesh, capacity_factor=8.0, top_k=2))(
        stacked, router, x)
    ref = _dense_reference_topk(experts, router, x, 2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) > 0.0


def test_moe_top2_gradients_match_dense():
    mesh = _mesh(2)
    experts, router, x = _setup(4, 6, 12, 16, seed=9)
    stacked = stack_expert_params(experts)

    def loss_moe(p, r):
        y, _ = moe_apply(_expert_fn, p, r, x, mesh=mesh,
                         capacity_factor=8.0, top_k=2)
        return jnp.sum(y ** 2)

    def loss_dense(p, r):
        per = [jax.tree_util.tree_map(lambda l: l[i], p) for i in range(4)]
        return jnp.sum(_dense_reference_topk(per, r, x, 2) ** 2)

    g_moe = jax.jit(jax.grad(loss_moe, argnums=(0, 1)))(stacked, router)
    g_dense = jax.grad(loss_dense, argnums=(0, 1))(stacked, router)
    for a, b in zip(jax.tree_util.tree_leaves(g_moe),
                    jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_moe_rejects_mismatched_experts():
    mesh = _mesh(2)
    experts, router, x = _setup(3, 4, 8, 8)   # 3 experts on ep=2
    with pytest.raises(ValueError, match="multiple"):
        moe_apply(_expert_fn, stack_expert_params(experts),
                  jnp.zeros((4, 3), jnp.float32), x, mesh=mesh)


def test_moe_rejects_mismatched_router():
    mesh = _mesh(2)
    experts, _, x = _setup(2, 4, 8, 8)
    bad_router = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="router_weights"):
        moe_apply(_expert_fn, stack_expert_params(experts), bad_router, x,
                  mesh=mesh)


def test_softmax_topk_gates_are_the_renormalised_top_k_of_the_softmax():
    """``route_softmax_topk``'s gates ``softmax(r[C])`` are ``softmax(r)[C] /
    sum_C softmax(r)`` over the same choice, in value and in gradient."""
    from analytics_zoo_tpu.parallel.expert_parallel import route_softmax_topk
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(32, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 64) * 0.5, jnp.float32)
    c = jnp.asarray(rng.randn(32, 8), jnp.float32)

    def plain(w):
        probs = jax.nn.softmax(jnp.dot(x, w, precision="highest"), axis=-1)
        top, idx = jax.lax.top_k(probs, 8)
        return idx, top / jnp.sum(top, -1, keepdims=True)

    idx, gates = route_softmax_topk(x, w, top_k=8)
    want_idx, want = plain(w)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want),
                               rtol=1e-5, atol=1e-7)
    got_g = jax.grad(lambda w: jnp.sum(route_softmax_topk(
        x, w, top_k=8)[1] * c))(w)
    want_g = jax.grad(lambda w: jnp.sum(plain(w)[1] * c))(w)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                               rtol=1e-4, atol=1e-6)
