"""The decoder language model (MLA, a sigmoid-routed expert layer of which
one rank holds a share, multi-token prediction) against the benchmark's plain
float32 reference, at small widths on the CPU; the flash kernels at a head
size of v's own; the expert layer's share, no-drop and bias-update rules; the
model trained through ``TPUEstimator.fit`` on arrays."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import joyai_llm_flash as ref                    # noqa: E402
from harness import spec, work_lm                               # noqa: E402

from analytics_zoo_tpu.ops.attention import (                   # noqa: E402
    flash_attention, mha_reference)
from analytics_zoo_tpu.parallel import expert_parallel as ep    # noqa: E402
from analytics_zoo_tpu.parallel.expert_parallel import (        # noqa: E402
    expert_load, grouped_matmul, held_experts_ffn, noaux_bias_update,
    route_noaux_tc)
from analytics_zoo_tpu.pipeline.api.keras.layers import decoder_lm  # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    DecoderLM, moe_counters, next_token_loss, rope_interleaved)
from test_attention import equations, pallas_kernels            # noqa: E402

CFG = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=16,
    experts_held=4, first_expert=4, num_experts_per_tok=4,
    n_shared_experts=1, num_hidden_layers=3, first_k_dense_replace=1,
    num_nextn_predict_layers=1, rms_norm_eps=1e-6, rope_theta=10000.0,
    routed_scaling_factor=2.5, compute_dtype="float32", mtp_loss_weight=0.3,
    bias_update_rate=1e-3,
    init=dict(embedding_std=1.0, out_proj_scale=0.5, router_std=1.0))


def _tree(flat):
    out = {}
    for name, a in flat.items():
        node = out
        parts = name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = a
    return out


def _flat(tree):
    return {"/".join(str(getattr(p, "key", p)) for p in path): a
            for path, a in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def sides():
    """Program and reference on the same seeded weights and ids: logits of
    both heads, the loss and every leaf's gradient."""
    model = DecoderLM.from_config(CFG)
    ids = np.random.RandomState(0).randint(0, 96, (2, 32)).astype(np.uint16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(ids[:1]))
    weights = ref.make_weights(CFG, 7)
    extra = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(p):
        preds, new = model.apply({"params": p, **extra}, jnp.asarray(ids),
                                 train=True, mutable=list(extra))
        return jnp.mean(next_token_loss(jnp.asarray(ids), preds, 0.3)), \
            (preds, new)

    (loss, (preds, new)), grads = jax.jit(jax.value_and_grad(
        loss_of, has_aux=True))(_tree(weights))
    ref_grad = jax.jit(jax.value_and_grad(
        lambda p, seq: ref.sequence_loss(CFG, p, {}, seq), has_aux=True))
    ref_forward = jax.jit(lambda p, seq: ref.forward(CFG, p, {}, seq))
    ref_losses, ref_grads, ref_logits = [], None, []
    for seq in ids:
        (l, _), g = ref_grad(weights, jnp.asarray(seq))
        ref_losses.append(float(l))
        ref_grads = g if ref_grads is None else jax.tree.map(
            jnp.add, ref_grads, g)
        ref_logits.append(ref_forward(weights, jnp.asarray(seq)))
    return dict(model=model, variables=variables, weights=weights,
                loss_of=loss_of, loss=float(loss), preds=preds, new=new,
                grads=_flat(grads),
                ref_loss=float(np.mean(ref_losses)),
                ref_grads={k: v / len(ids) for k, v in ref_grads.items()},
                ref_logits=ref_logits)


def test_program_tree_is_the_references(sides):
    shapes = {k: tuple(v.shape)
              for k, v in _flat(sides["variables"]["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}


@pytest.mark.parametrize("head", [0, 1])
def test_logits_match_reference(sides, head):
    for b, (main, mtp, _) in enumerate(sides["ref_logits"]):
        want = (main, mtp)[head]
        got = sides["preds"][head][b]
        # the MTP head's last position is fed a pad token: defined alike
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_loss_matches_reference(sides):
    assert sides["loss"] == pytest.approx(sides["ref_loss"], rel=1e-5)


def test_every_leafs_gradient_matches_reference(sides):
    assert set(sides["grads"]) == set(sides["ref_grads"])
    for name, want in sides["ref_grads"].items():
        got = np.asarray(sides["grads"][name])
        scale = float(jnp.abs(want).max()) + 1e-12
        assert float(np.abs(got - np.asarray(want)).max()) <= 2e-4 * scale, \
            name


def test_each_block_runs_the_flash_forward_kernel_once(sides):
    """The blocks are rematerialised, but their policy keeps the flash
    kernel's output and logsumexp: the gradient's program holds two flash
    kernels a block (forward and the fused backward), not a second forward
    and not a dQ launch beside the dK/dV one."""
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sides["loss_of"](p)[0]))(
        _tree(sides["weights"])).jaxpr
    flash = sorted(n for n in pallas_kernels(jaxpr) if "flash" in n)
    blocks = CFG["num_hidden_layers"] + CFG["num_nextn_predict_layers"]
    assert flash == sorted(blocks * ["_flash_kernel",
                                     "_flash_bwd_fused_kernel"])
    # one policy object for all blocks: with one a block, blocks of one shape
    # stop sharing a lowered function (3.5x the functions at the cell's size)
    policies = [eqn.params["policy"] for eqn in equations(jaxpr)
                if eqn.primitive.name == "remat2" and eqn.params["policy"]]
    assert len(policies) == blocks and len(set(map(id, policies))) == 1


def test_a_training_forward_moves_bias_and_counters(sides):
    new = sides["new"]
    for block in ("layers_1", "layers_2", "mtp_block"):
        bias = np.asarray(
            new["router_state"][block]["mlp"]["e_score_correction_bias"])
        load = np.asarray(new["moe_stats"][block]["mlp"]["load"])
        assert load.sum() == 2 * 32 * 4
        np.testing.assert_allclose(
            bias, 1e-3 * np.sign(load.mean() - load), atol=1e-9)
    counters = moe_counters(new)
    assert counters["moe_dropped_rows"] == 0 and counters["moe_steps"] == 1
    assert 0 < counters["moe_local_rows"] < 3 * 2 * 32 * 4


def test_init_does_not_run_the_held_experts():
    """``init`` shapes the parameters and the state; the dispatch and the
    grouped products, which shape none, are not in its program."""
    model = DecoderLM.from_config(CFG)
    jaxpr = jax.make_jaxpr(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.uint16)).jaxpr
    assert not [n for n in pallas_kernels(jaxpr) if "gmm" in n]
    assert not [e for e in equations(jaxpr)
                if e.primitive.name in ("sort", "scatter-add")]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 32), jnp.uint16))
    assert set(shapes["moe_stats"]["layers_1"]["mlp"]) >= {
        "rows_total", "rows_moved", "dropped_rows", "load"}


def test_the_counters_report_rows_moved_over_routed(sides):
    """``moe_counters`` divides what the layers' gathers fetched by what the
    router sent: at these sizes (64 tokens, 4 of 16 experts held, top-4: a
    chunk of 128 rows in sub-blocks of 16) each layer moved its rows rounded
    up to 16."""
    from analytics_zoo_tpu.obs.registry import REGISTRY
    layers = [v["mlp"] for v in sides["new"]["moe_stats"].values()]
    moved = sum(float(l["rows_moved"]) for l in layers)
    routed = sum(float(l["rows_total"]) for l in layers)
    assert moved == sum(-(-float(l["rows_total"]) // 16) * 16 for l in layers)
    counters = moe_counters(sides["new"])
    assert counters["moe_rows_moved_over_routed"] == pytest.approx(
        moved / routed)
    assert 1.0 <= counters["moe_rows_moved_over_routed"] < 2.0
    assert REGISTRY.gauge("zoo_moe_rows_moved_over_routed", "").value == \
        pytest.approx(moved / routed)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_takes_a_head_size_of_vs_own(causal):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 64, 2, 24), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)

    def flash(*a):
        return flash_attention(*a, causal=causal, block_q=16, block_k=16)

    def plain(*a):
        return mha_reference(*a, causal=causal)

    out = flash(q, k, v)
    assert out.shape == (2, 64, 2, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


def test_rope_rotates_adjacent_pairs():
    rng = np.random.RandomState(2)
    x = rng.randn(1, 6, 2, 8).astype(np.float32)
    got = np.asarray(rope_interleaved(jnp.asarray(x), 100.0))
    z = x[..., 0::2] + 1j * x[..., 1::2]                 # pairs as complex
    inv = 100.0 ** (-np.arange(0, 8, 2) / 8)
    ang = np.arange(6)[:, None] * inv[None, :]
    want = z * np.exp(1j * ang)[None, :, None, :]
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=1e-5)
    # what attention sees depends on the distance alone
    q, k = jnp.asarray(rng.randn(1, 6, 1, 8)), jnp.asarray(rng.randn(1, 6, 1, 8))
    same_q = jnp.broadcast_to(q[:, :1], q.shape)
    same_k = jnp.broadcast_to(k[:, :1], k.shape)
    dots = np.einsum("bqhd,bkhd->qk",
                     np.asarray(rope_interleaved(same_q, 100.0)),
                     np.asarray(rope_interleaved(same_k, 100.0)))
    np.testing.assert_allclose(dots[1, 0], dots[4, 3], rtol=1e-4)
    np.testing.assert_allclose(dots[0, 2], dots[3, 5], rtol=1e-4)
    # and the reference's is the same rotation
    np.testing.assert_allclose(
        np.asarray(ref.rope(jnp.asarray(x[0]), 100.0)), got[0], atol=1e-6)


def _layer_inputs(n=64, d=16, f=8, e=32, seed=3):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    p = {"m/gate": jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32),
         "m/experts_gate_proj": jnp.asarray(rng.randn(e, d, f) * .3,
                                            jnp.float32),
         "m/experts_up_proj": jnp.asarray(rng.randn(e, d, f) * .3,
                                          jnp.float32),
         "m/experts_down_proj": jnp.asarray(rng.randn(e, f, d) * .3,
                                            jnp.float32),
         "m/shared_experts/gate_proj/kernel":
             jnp.asarray(rng.randn(d, f) * .3, jnp.float32),
         "m/shared_experts/up_proj/kernel":
             jnp.asarray(rng.randn(d, f) * .3, jnp.float32),
         "m/shared_experts/down_proj/kernel":
             jnp.asarray(rng.randn(f, d) * .3, jnp.float32)}
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=f,
               n_routed_experts=e, experts_held=e, first_expert=0)
    return x, p, cfg


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 16 ranks give, plus the shared expert
    counted once, are the uncut reference's expert layer."""
    x, p, cfg = _layer_inputs()
    bias = jnp.zeros((32,))
    whole, _ = ref.expert_layer(cfg, p, "m", x, bias, None)
    idx, gates = route_noaux_tc(x, p["m/gate"], bias, top_k=4, scaling=2.5)
    total = jnp.zeros_like(x)
    rows = 0
    for rank in range(16):
        lo = 2 * rank
        y, counters = held_experts_ffn(
            x, idx, gates, p["m/experts_gate_proj"][lo:lo + 2],
            p["m/experts_up_proj"][lo:lo + 2],
            p["m/experts_down_proj"][lo:lo + 2],
            first_expert=lo, n_experts=32)
        total = total + y
        rows += int(counters["local_rows"])
        assert int(counters["dropped_rows"]) == 0
    assert rows == 64 * 4                    # every token-choice, once
    shared = ref.swiglu(x, p["m/shared_experts/gate_proj/kernel"],
                        p["m/shared_experts/up_proj/kernel"],
                        p["m/shared_experts/down_proj/kernel"], None)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hot_share", [0.5, 1.0])
def test_no_token_is_dropped_where_one_expert_gets_the_rows(hot_share):
    x, p, cfg = _layer_inputs(n=64)
    rng = np.random.RandomState(4)
    # every token's first choice is expert 5, of the four experts held
    # (4..7): with the second choice among the other three it gets half of
    # the rows routed here, with the second choice elsewhere all of them,
    # far over a balanced share either way
    second = rng.choice([4, 6, 7], 64) if hot_share == 0.5 \
        else rng.randint(8, 32, 64)
    idx = np.stack([np.full(64, 5), second,
                    rng.randint(8, 32, 64), rng.randint(8, 32, 64)], 1)
    gates = jnp.asarray(rng.rand(64, 4), jnp.float32)
    idx = jnp.asarray(idx, jnp.int32)
    w = [p[f"m/experts_{n}_proj"][4:8] for n in ("gate", "up", "down")]
    y, counters = held_experts_ffn(x, idx, gates, *w, first_expert=4,
                                   n_experts=32)
    assert int(counters["local_rows"]) == int(64 / hot_share)
    assert int(counters["dropped_rows"]) == 0
    assert float(counters["rows_max_over_mean"]) == pytest.approx(
        4 * hot_share)
    # the static first chunk holds 64 rows: at half, the rest went the
    # long way
    held_p = {k: v[4:8] if "experts_" in k else v for k, v in p.items()}
    want = ref.experts_part(cfg, held_p, "m", x, idx, gates, None, first=4,
                            held=4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    got = jax.grad(lambda x: jnp.sum(jnp.sin(held_experts_ffn(
        x, idx, gates, *w, first_expert=4, n_experts=32)[0])))(x)
    want_g = jax.grad(lambda x: jnp.sum(jnp.sin(ref.experts_part(
        cfg, held_p, "m", x, idx, gates, None, first=4, held=4))))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_g),
                               rtol=1e-3, atol=1e-5)


# the sub-block walk: the rows a chunk moves follow the rows routed here
_WALK = {8: dict(n=32, top_k=4, held=4, experts=16, d=16, f=8),
         512: dict(n=1024, top_k=4, held=4, experts=16, d=16, f=8)}
_WALK_ROWS = ("0", "1", "B-1", "B", "B+1", "straddle", "chunk", "past")


def _walk_sizes(tile):
    s = _WALK[tile]
    chunk_rows = 2 * s["n"] * s["top_k"] * s["held"] // s["experts"]
    assert chunk_rows % tile == 0 and (chunk_rows >= 512) == (tile == 512)
    return chunk_rows, ep._sub_rows(chunk_rows, tile)


def _walk_rows(tile, case):
    chunk_rows, b = _walk_sizes(tile)
    return {"0": 0, "1": 1, "B-1": b - 1, "B": b, "B+1": b + 1,
            "straddle": chunk_rows - b - b // 2, "chunk": chunk_rows,
            "past": chunk_rows + b + b // 2}[case]


def _walk_inputs(tile, rows, first=4, seed=7):
    """``rows`` token-choices on the held experts ``first .. first + held -
    1``, every token's choices distinct, the rest elsewhere."""
    s = _WALK[tile]
    n, top_k, held, experts = s["n"], s["top_k"], s["held"], s["experts"]
    rng = np.random.RandomState(seed + rows)
    here = np.arange(first, first + held)
    away = np.setdiff1d(np.arange(experts), here)
    idx = np.empty((n, top_k), np.int32)
    for t in range(n):
        c = rows // n + (t < rows % n)
        idx[t] = rng.permutation(np.concatenate(
            [rng.permutation(here)[:c], rng.permutation(away)[:top_k - c]]))
    assert ((idx >= first) & (idx < first + held)).sum() == rows
    w = [jnp.asarray(rng.randn(*shape) * .3, jnp.float32)
         for shape in ((held, s["d"], s["f"]), (held, s["d"], s["f"]),
                       (held, s["f"], s["d"]))]
    return (jnp.asarray(rng.randn(n, s["d"]), jnp.float32),
            jnp.asarray(idx), jnp.asarray(rng.rand(n, top_k), jnp.float32),
            w)


def _dense_held(x, idx, gates, w, first=4):
    """``sum_i g_ti F_i(x_t)`` over the held experts, every expert applied
    to every token, float32."""
    y = jnp.zeros_like(x)
    for i in range(w[0].shape[0]):
        g = jnp.sum(jnp.where(idx == first + i, gates, 0.0), axis=-1)
        y = y + g[:, None] * ((jax.nn.silu(x @ w[0][i]) * (x @ w[1][i]))
                              @ w[2][i])
    return y


@functools.lru_cache(maxsize=None)
def _walk_programs(tile, remat):
    experts = _WALK[tile]["experts"]

    def layer(x, gates, w, idx):
        return held_experts_ffn(x, idx, gates, *w, first_expert=4,
                                n_experts=experts)

    if remat:
        layer = jax.checkpoint(layer, policy=decoder_lm._KEPT_ACROSS_REMAT)

    def loss(x, gates, w, idx):
        y, counters = layer(x, gates, w, idx)
        return jnp.sum(jnp.sin(y)), (y, counters)

    def dense(x, gates, w, idx):
        y = _dense_held(x, idx, gates, w)
        return jnp.sum(jnp.sin(y)), y

    return tuple(jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                            has_aux=True))
                 for f in (loss, dense))


def _assert_walk_matches_dense(tile, case, remat=False):
    chunk_rows, b = _walk_sizes(tile)
    rows = _walk_rows(tile, case)
    x, idx, gates, w = _walk_inputs(tile, rows)
    program, dense = _walk_programs(tile, remat)
    (_, (y, counters)), grads = program(x, gates, w, idx)
    (_, want), want_grads = dense(x, gates, w, idx)
    assert int(counters["local_rows"]) == rows
    assert int(counters["dropped_rows"]) == 0
    # the first chunk's gathers fetch its rows rounded up to a sub-block;
    # an overflow's chunks are fetched whole
    first = min(rows, chunk_rows)
    assert int(counters["moved_rows"]) == \
        -(-first // b) * b + -(-(rows - first) // chunk_rows) * chunk_rows
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    for got, ref_g in zip(jax.tree.leaves(grads),
                          jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref_g),
                                   rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("case", _WALK_ROWS)
@pytest.mark.parametrize("tile", [8, 512])
def test_a_chunk_moves_the_rows_routed_and_equals_the_dense_layer(tile,
                                                                  case):
    """Output and gradients (x, gates, the three weight stacks) against a
    dense float32 evaluation, nothing dropped, and the gathers fetched the
    routed rows rounded up to a sub-block (an overflow's chunks whole); at
    the 512-row tile the grouped products run interpreted."""
    _assert_walk_matches_dense(tile, case)


@pytest.mark.parametrize("case", ["0", "straddle", "past"])
def test_the_walk_is_the_same_under_the_blocks_remat_policy(case):
    _assert_walk_matches_dense(8, case, remat=True)


def test_grouped_matmul_matches_ragged_dot():
    rng = np.random.RandomState(5)
    lhs = jnp.asarray(rng.randn(32, 8), jnp.float32)
    rhs = jnp.asarray(rng.randn(3, 8, 4), jnp.float32)
    sizes = jnp.asarray([5, 0, 9, 18], jnp.int32)     # 18 rows of no group

    def oracle(l, r):
        return jax.lax.ragged_dot(l, r, sizes[:-1])

    a = grouped_matmul(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(a), np.asarray(oracle(lhs, rhs)),
                               atol=1e-5)
    assert not np.asarray(a)[14:].any()
    for ga, gb in zip(
            jax.grad(lambda l, r: jnp.sum(jnp.sin(grouped_matmul(
                l, r, sizes))), (0, 1))(lhs, rhs),
            jax.grad(lambda l, r: jnp.sum(jnp.sin(oracle(l, r))),
                     (0, 1))(lhs, rhs)):
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), atol=1e-5)


def test_router_gates_and_bias():
    x, p, _ = _layer_inputs()
    bias = jnp.zeros((32,)).at[3].set(10.0)     # steers the choice only
    idx, gates = route_noaux_tc(x, p["m/gate"], bias, top_k=4, scaling=2.5)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    scores = jax.nn.sigmoid(x @ p["m/gate"])
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(
        np.asarray(gates), np.asarray(chosen / chosen.sum(-1, keepdims=True)
                                      * 2.5), rtol=1e-5)
    ref_idx, ref_gates = ref.route(dict(CFG, num_experts_per_tok=4),
                                   x, p["m/gate"], bias)
    assert np.array_equal(np.sort(np.asarray(idx)),
                          np.sort(np.asarray(ref_idx)))


def test_bias_update_moves_against_the_load():
    idx = jnp.asarray([[0, 1], [0, 2], [0, 1], [0, 3]])   # loads 4,2,1,1,0,0
    new = noaux_bias_update(jnp.zeros((6,)), expert_load(idx, 6), 0.001)
    # mean load 8/6: experts 0 and 1 are over it, the others under
    np.testing.assert_allclose(
        np.asarray(new), [-.001, -.001, .001, .001, .001, .001], atol=1e-9)


def _plain_route(x, router_w, bias, top_k, scaling):
    """The router as it was written before the choice was kept and the
    scalars stopped moving: ``lax.top_k``, a gather of the chosen scores."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    router_w.astype(jnp.float32)))
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), gates * scaling


def _router_inputs(experts, top_k, n=48, tied=16):
    """One-hot rows, so that ``x @ w`` is ``w``'s rows to the bit and a tie
    can be planted: in the first ``tied`` rows an expert outside the choice
    is given the logit (and has the bias) of the last expert chosen."""
    rng = np.random.RandomState(experts + top_k)
    logits = rng.randn(n, experts).astype(np.float32)
    bias = rng.choice([-0.25, 0.25], experts).astype(np.float32)
    pairs = []
    for row in range(tied):
        key = 1.0 / (1.0 + np.exp(-logits[row].astype(np.float64))) + bias
        order = np.argsort(-key)
        last = order[top_k - 1]
        other = next(e for e in order[top_k:] if bias[e] == bias[last])
        logits[row, other] = logits[row, last]
        pairs.append((int(last), int(other)))
    return jnp.eye(n, dtype=jnp.float32), jnp.asarray(logits), \
        jnp.asarray(bias), pairs


@pytest.mark.parametrize("experts,top_k", [(256, 8), (128, 8), (512, 22)])
def test_the_router_is_the_plain_one_with_no_scalar_moved(experts, top_k):
    """The three cells' routers (experts / choices a token) on few rows:
    ``lax.top_k``'s choice to the bit, planted ties at the cut included;
    gates, ``d x`` and ``d router_w`` under the blocks' remat policy within
    float32 rounding; the load is ``bincount``'s; the bias takes no
    gradient."""
    x, w, bias, pairs = _router_inputs(experts, top_k)
    probe = jnp.asarray(np.random.RandomState(1).randn(x.shape[0], top_k),
                        jnp.float32)

    def loss(route, x, w, bias):
        idx, gates = route(x, w, bias, top_k=top_k, scaling=2.5)
        return jnp.sum(jnp.sin(gates) * probe), (idx, gates)

    def new_route(x, w, bias, **kw):
        return jax.checkpoint(
            functools.partial(route_noaux_tc, **kw),
            policy=decoder_lm._KEPT_ACROSS_REMAT)(x, w, bias)

    (_, (idx, gates)), grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, new_route), argnums=(0, 1, 2),
        has_aux=True))(x, w, bias)
    (_, (want_idx, want_gates)), want_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, _plain_route), argnums=(0, 1, 2),
        has_aux=True))(x, w, bias)
    assert idx.dtype == jnp.int32 and np.array_equal(idx, want_idx)
    for row, (last, other) in enumerate(pairs):
        # the tie is at the cut: the lower index is in, the other out
        chosen = set(np.asarray(idx[row]).tolist())
        assert (min(last, other) in chosen) and \
            (max(last, other) not in chosen)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates),
                               rtol=1e-6, atol=0)
    for got, want in zip(grads[:2], want_grads[:2]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-7)
    assert not np.asarray(grads[2]).any()
    load = expert_load(idx, experts)
    assert load.dtype == jnp.int32 and load.shape == (experts,)
    assert np.array_equal(load, jnp.bincount(idx.reshape(-1),
                                             length=experts))


def _scoped_equations(jaxpr, path=""):
    """``(named-scope path, equation)`` of every equation, the path carried
    down into the jaxprs of an equation's parameters (a ``jit`` inside a
    scope starts its own name stack)."""
    for eqn in jaxpr.eqns:
        here = f"{path}/{eqn.source_info.name_stack}"
        yield here, eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _scoped_equations(sub, here)


def _family_cfgs():
    # imported here: both modules import this one
    from test_decoder_gqa import CFG as gqa_cfg
    from test_decoder_nemotron_h import CFG as hybrid_cfg
    # name: (toy configuration, expert layers with the MTP module's, the
    # functions the parent's step lowered to)
    return {"mla": (CFG, 3, 291), "gqa": (gqa_cfg, 3, 299),
            "hybrid": (hybrid_cfg, 3, 320)}


@pytest.mark.parametrize("family", ["mla", "gqa", "hybrid"])
def test_the_router_decides_once_a_step_and_moves_no_scalar(family):
    """A count, never a rate: in the gradient of each family's toy model one
    top-k an expert layer (the rematerialised forward reads the choice the
    policy kept), no gather or scatter under ``moe.router``, and no more
    lowered functions than before the choice was named (one policy object
    for every block)."""
    cfg, expert_layers, parent_functions = _family_cfgs()[family]
    model = DecoderLM.from_config(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, 32)),
                      jnp.uint16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), ids[:1])
    extra = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(p):
        preds, new = model.apply({"params": p, **extra}, ids, train=True,
                                 mutable=list(extra))
        return jnp.mean(next_token_loss(ids, preds, 0.3)), new

    grad = jax.grad(loss_of, has_aux=True)
    found = list(_scoped_equations(
        jax.make_jaxpr(grad)(variables["params"]).jaxpr))
    router = [eqn.primitive.name for path, eqn in found
              if "moe.router" in path]
    assert router.count("top_k") == expert_layers
    assert not [n for n in router
                if n == "sort" or "gather" in n or "scatter" in n]
    # the selection lives nowhere else
    assert sum(eqn.primitive.name == "top_k" for _, eqn in found) \
        == expert_layers
    lowered = jax.jit(grad).lower(variables["params"]).as_text()
    assert lowered.count("func.func") <= parent_functions


def test_trains_through_the_estimator_on_arrays():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    ctx = init_orca_context("local")
    mesh = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    model = DecoderLM.from_config(dict(CFG, compute_dtype="bfloat16",
                                         num_hidden_layers=2))
    est = TPUEstimator(model, loss=model.loss(),
                       optimizer=AdamWeightDecay(lr=3e-3, weight_decay=0.1,
                                                 beta_2=0.95),
                       mesh=mesh, seed=0)
    ids = np.random.RandomState(6).randint(0, 96, (8, 32)).astype(np.uint16)
    stats = est.fit({"x": ids, "y": ids}, epochs=3, batch_size=4,
                    verbose=False)
    losses = [s["train_loss"] for s in stats]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    counters = moe_counters(est.engine.extra_vars)
    assert counters["moe_steps"] == 6 and counters["moe_dropped_rows"] == 0
    bias = jax.device_get(est.engine.extra_vars["router_state"])
    assert np.abs(bias["layers_1"]["mlp"]["e_score_correction_bias"]).max() > 0
    est.shutdown()


def test_the_configurations_parameter_count_is_pinned():
    """680.4 M parameters, 10.89 GB at 16 B a parameter: the cut of
    ISSUE 35, counted three ways."""
    with open(os.path.join(BENCH, "configs",
                           "joyai_llm_flash_ep16.json")) as f:
        cfg = json.load(f)
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    mcfg = factory.model_config(cfg)
    assert mcfg["n_routed_experts"] == 256 and mcfg["experts_held"] == 16
    n = ref.param_count(mcfg)
    assert n == 680_439_808 == work_lm.param_count(mcfg)
    assert abs(n - 680.4e6) / 680.4e6 < 0.01
    module = DecoderLM.from_config(mcfg)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["params"])) == n
