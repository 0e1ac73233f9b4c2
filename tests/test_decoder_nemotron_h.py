"""The decoder language model built from the ``nemotron_h`` family's keys (one
mixer a block: Mamba-2, plain grouped-query attention, the latent-space expert
layer with two-matrix squared-ReLU experts; an MTP module from its own
pattern) against the benchmark's plain float32 reference, at small widths on
the CPU; the chunked scan against the literal recurrence; the shares of the
mixers and of the expert layer; the two-matrix expert form; the
configuration's parameter count; the model trained through
``TPUEstimator.fit`` on arrays."""

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

from reference import nemotron_h as ref                         # noqa: E402
from harness import spec, work_hybrid                           # noqa: E402

from analytics_zoo_tpu.ops import ssm                           # noqa: E402
from analytics_zoo_tpu.parallel.expert_parallel import (        # noqa: E402
    held_experts_ffn, route_noaux_tc)
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    DecoderLM, GQAttention, Mamba2Mixer, moe_counters, next_token_loss,
    relu_squared)
from test_attention import pallas_kernels                       # noqa: E402
from test_decoder_lm import _flat, _tree                        # noqa: E402

CFG = dict(
    model_type="nemotron_h", vocab_size=96, hidden_size=32,
    hybrid_override_pattern="ME*EM*", num_hidden_layers=5,
    mamba_num_heads=8, mamba_head_dim=4, n_groups=2, ssm_state_size=8,
    conv_kernel=4, chunk_size=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, n_routed_experts=16, experts_held=4,
    first_expert=4, num_experts_per_tok=6, moe_intermediate_size=24,
    moe_latent_size=16, moe_shared_expert_intermediate_size=40,
    n_shared_experts=1, routed_scaling_factor=5.0, norm_topk_prob=True,
    n_group=1, topk_group=1, mlp_hidden_act="relu2", mamba_hidden_act="silu",
    num_nextn_predict_layers=1, mtp_hybrid_override_pattern="*E",
    layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
    bias_update_rate=1e-3, compute_dtype="float32",
    init=dict(embedding_std=1.0, out_proj_scale=0.5, router_std=1.0))
SEQ = 32             # four chunks of the scan


@pytest.fixture(scope="module")
def sides():
    """Program and reference on the same seeded weights and ids: logits of
    both heads, the loss and every leaf's gradient."""
    model = DecoderLM.from_config(CFG)
    ids = np.random.RandomState(0).randint(0, 96, (2, SEQ)).astype(np.uint16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(ids[:1]))
    weights = ref.make_weights(CFG, 7)
    extra = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(p):
        preds, new = model.apply({"params": p, **extra}, jnp.asarray(ids),
                                 train=True, mutable=list(extra))
        return jnp.mean(next_token_loss(jnp.asarray(ids), preds)), \
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
        ref_logits.append(ref_forward(weights, jnp.asarray(seq))[:2])
    return dict(model=model, variables=variables, weights=weights, ids=ids,
                loss_of=loss_of, loss=float(loss), preds=preds, new=new,
                grads=_flat(grads),
                ref_loss=float(np.mean(ref_losses)),
                ref_grads={k: v / len(ids) for k, v in ref_grads.items()},
                ref_logits=ref_logits)


def test_program_tree_is_the_references(sides):
    shapes = {k: tuple(v.shape)
              for k, v in _flat(sides["variables"]["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}
    # one norm and one mixer a block; in_proj's columns [z | x | B | C | dt]
    assert sorted(k.split("/")[1] for k in shapes
                  if k.startswith("layers_0/")) == ["mixer"] * 8 + ["norm"]
    assert shapes["layers_0/mixer/in_proj/kernel"] == (32, 32 + 32 + 32 + 8)
    assert shapes["layers_0/mixer/conv1d_weight"] == (4, 32 + 2 * 2 * 8)
    assert shapes["layers_2/self_attn/k_proj/kernel"] == (32, 2 * 16)
    assert "layers_2/self_attn/gate_proj/kernel" not in shapes
    assert "layers_2/self_attn/q_norm/weight" not in shapes
    # the experts' rows are the latent space's, the shared expert's its own
    assert shapes["layers_1/mlp/experts_up_proj"] == (4, 16, 24)
    assert shapes["layers_1/mlp/experts_down_proj"] == (4, 24, 16)
    assert "layers_1/mlp/experts_gate_proj" not in shapes
    assert shapes["layers_1/mlp/shared_experts/up_proj/kernel"] == (32, 40)
    # the MTP module is its pattern's blocks
    assert {k.split("/")[1] for k in shapes if k.startswith("mtp_layers_")} \
        == {"norm", "self_attn", "mlp"}


@pytest.mark.parametrize("head", [0, 1])
def test_logits_match_reference(sides, head):
    for b, want in enumerate(sides["ref_logits"]):
        np.testing.assert_allclose(np.asarray(sides["preds"][head][b]),
                                   np.asarray(want[head]), rtol=2e-4,
                                   atol=2e-5)


def test_loss_matches_reference(sides):
    assert sides["loss"] == pytest.approx(sides["ref_loss"], rel=1e-5)


def test_every_leafs_gradient_matches_reference(sides):
    assert set(sides["grads"]) == set(sides["ref_grads"])
    for name, want in sides["ref_grads"].items():
        got = np.asarray(sides["grads"][name])
        scale = float(jnp.abs(want).max()) + 1e-12
        assert float(np.abs(got - np.asarray(want)).max()) <= 2e-4 * scale, \
            name


@pytest.mark.parametrize("planted", [
    dict(reference_fault="state_reset_at_chunks"),
    dict(reference_fault="relu_not_squared"),
    dict(reference_label_positions=SEQ // 2)])
def test_the_references_faults_are_another_model(sides, planted):
    """The faults the cell's readings plant in the reference change what it
    computes: the carried state and the squared activation are in its
    logits, the labels in its loss."""
    seq = jnp.asarray(sides["ids"][0])
    faulty_cfg = dict(CFG, **planted)
    clean, _ = ref.sequence_loss(CFG, sides["weights"], {}, seq)
    faulty, _ = ref.sequence_loss(faulty_cfg, sides["weights"], {}, seq)
    logits = ref.forward(CFG, sides["weights"], {}, seq)[0]
    moved = jnp.abs(ref.forward(faulty_cfg, sides["weights"], {}, seq)[0]
                    - logits).max() / jnp.abs(logits).max()
    if "reference_fault" in planted:
        assert float(moved) > 0.02
    else:
        assert float(moved) == 0.0
        assert abs(float(faulty) - float(clean)) > 1e-3 * float(clean)


def test_a_training_forward_moves_bias_and_counters(sides):
    """Every mixer is on the normal path: the attention blocks take the
    flash kernels, the expert blocks leave their state and counters."""
    new = sides["new"]
    assert set(new["moe_stats"]) == {"layers_1", "layers_3", "mtp_layers_1"}
    counters = moe_counters(new)
    assert counters["moe_steps"] == 1 and counters["moe_dropped_rows"] == 0
    assert counters["moe_local_rows"] > 0
    bias = new["router_state"]["layers_1"]["mlp"]["e_score_correction_bias"]
    assert float(jnp.abs(bias).max()) == pytest.approx(1e-3)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sides["loss_of"](p)[0]))(
        _tree(sides["weights"])).jaxpr
    flash = [n for n in pallas_kernels(jaxpr) if "flash" in n]
    assert sorted(flash) == sorted(
        2 * ["_flash_kernel", "_flash_bwd_fused_kernel"])


# --- the scan ------------------------------------------------------------------

def scan_inputs(seed, bsz=2, seq=48, h=4, p=8, g=2, n=16):
    rs = np.random.RandomState(seed)
    f = (lambda *s: jnp.asarray(rs.randn(*s), jnp.float32))
    # small dt |A|: what position 0 wrote is still there chunks later
    dt = jax.nn.softplus(f(bsz, seq, h) - 3.0)
    a = -jnp.exp(jnp.asarray(rs.rand(h), jnp.float32))
    return f(bsz, seq, h, p), dt, a, f(bsz, seq, g, n), f(bsz, seq, g, n), \
        f(h)


def state_reset_at_chunks(x, dt, a, b, c, d, chunk):
    """What a chunked scan that loses its carry computes: every chunk
    scanned from a zero state."""
    cut = (lambda t: t.reshape((-1, chunk) + t.shape[2:]))
    y = ssm.ssd_scan_sequential(cut(x), cut(dt), a, cut(b), cut(c), d)
    return y.reshape(x.shape)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_scan_is_the_recurrence(chunk):
    args = scan_inputs(1)
    want = ssm.ssd_scan_sequential(*args)
    got = ssm.ssd_scan(*args, chunk_size=chunk)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    # the carried state matters: without it the result is another one
    lost = state_reset_at_chunks(*args, chunk)
    assert float(jnp.abs(lost - want).max()) > 0.05 * scale
    np.testing.assert_allclose(np.asarray(lost[:, :chunk]),
                               np.asarray(want[:, :chunk]), atol=1e-5 * scale)


def test_chunked_scans_gradients_are_the_recurrences():
    args = scan_inputs(2)
    loss = (lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a)))))
    want = jax.grad(loss(ssm.ssd_scan_sequential), argnums=range(6))(*args)
    got = jax.grad(loss(lambda *a: ssm.ssd_scan(*a, chunk_size=8)),
                   argnums=range(6))(*args)
    lost = jax.grad(loss(lambda *a: state_reset_at_chunks(*a, 8)),
                    argnums=range(6))(*args)
    for g, w, l in zip(got, want, lost):
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale
        assert float(jnp.abs(l - w).max()) > 0.02 * scale


def test_off_the_tiling_the_scan_runs_position_by_position():
    args = scan_inputs(3, seq=44)            # no whole number of chunks of 8
    before = ssm._SEQUENTIAL_ON_TPU.value
    got = ssm.ssd_scan(*args, chunk_size=8)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ssm.ssd_scan_sequential(*args)))
    assert ssm._SEQUENTIAL_ON_TPU.value == before        # counted on a TPU
    # a sequence shorter than a chunk is one chunk
    short = scan_inputs(4, seq=5)
    np.testing.assert_allclose(
        np.asarray(ssm.ssd_scan(*short, chunk_size=8)),
        np.asarray(ssm.ssd_scan_sequential(*short)), rtol=1e-5, atol=1e-5)


def test_causal_conv_is_the_sum_over_its_taps():
    rs = np.random.RandomState(5)
    x, w, b = rs.randn(2, 9, 6), rs.randn(4, 6), rs.randn(6)
    want = np.zeros_like(x) + b
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += w[k] * x[:, t - 3 + k]
    got = ssm.causal_conv1d(*(jnp.asarray(a, jnp.float32)
                              for a in (x, w, b)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_init_does_not_run_the_scan():
    model = DecoderLM.from_config(dict(CFG, num_nextn_predict_layers=0))
    jaxpr = jax.make_jaxpr(model.init)(jax.random.PRNGKey(0),
                                       jnp.zeros((1, SEQ), jnp.uint16))
    text = str(jaxpr)
    assert "cumsum" not in text and "scan" not in text


# --- the shares ----------------------------------------------------------------

def mamba_share(p, rank, t, heads, head_dim, groups, state):
    """Rank ``rank`` of ``t``'s columns and rows of an uncut mixer's
    parameters: its heads, their B/C group(s) and norm group(s)."""
    inner, gn = heads * head_dim, groups * state
    part = (lambda lo, width: np.arange(lo + rank * width // t,
                                        lo + (rank + 1) * width // t))
    xbc = np.concatenate([part(0, inner), part(inner, gn),
                          part(inner + gn, gn)])
    cols = np.concatenate([part(0, inner), inner + xbc,
                           part(2 * inner + 2 * gn, heads)])
    head = part(0, heads)
    return {"in_proj": {"kernel": p["in_proj"]["kernel"][:, cols]},
            "conv1d_weight": p["conv1d_weight"][:, xbc],
            "conv1d_bias": p["conv1d_bias"][xbc],
            "A_log": p["A_log"][head], "dt_bias": p["dt_bias"][head],
            "D": p["D"][head], "norm_weight": p["norm_weight"][part(0, inner)],
            "out_proj": {"kernel": p["out_proj"]["kernel"][part(0, inner)]}}


def test_the_eight_mamba_shares_add_up_to_the_uncut_mixer():
    """Each of 8 ranks holds 2 of 16 heads with their one B/C and norm
    group, the matching columns of in_proj and rows of out_proj; the partial
    sums of the output projections are the uncut mixer's output."""
    sizes = dict(head_dim=4, state_size=8, conv_kernel=4, chunk_size=8,
                 eps=1e-5, dtype=jnp.float32)
    whole = Mamba2Mixer(num_heads=16, n_groups=8, **sizes)
    x = jnp.asarray(np.random.RandomState(6).randn(2, 24, 32), jnp.float32)
    p = jax.jit(whole.init)(jax.random.PRNGKey(1), x)["params"]
    rs = np.random.RandomState(7)
    p = jax.tree.map(lambda a: a + 0.3 * jnp.asarray(
        rs.randn(*a.shape), jnp.float32) if a.ndim == 1 else a, p)
    want = whole.apply({"params": p}, x)
    share = Mamba2Mixer(num_heads=2, n_groups=1, **sizes)
    total = sum(share.apply(
        {"params": mamba_share(p, r, 8, 16, 4, 8, 8)}, x) for r in range(8))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_the_eight_attention_shares_add_up_to_the_uncut_layer():
    """8 query heads over 2 kv heads on 8 ranks: a rank holds one query head
    and the kv head it reads (each kv head is on four ranks), the matching
    columns of q, k and v and rows of o."""
    sizes = dict(head_dim=8, rope_theta=1e4, eps=1e-5, gated=False,
                 qk_norm=False, dtype=jnp.float32)
    whole = GQAttention(num_heads=8, num_kv_heads=2, **sizes)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 16, 32), jnp.float32)
    p = jax.jit(whole.init)(jax.random.PRNGKey(2), x)["params"]
    assert set(p) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    p = jax.tree.map(lambda a: 10 * a, p)
    want = whole.apply({"params": p}, x)
    share = GQAttention(num_heads=1, num_kv_heads=1, **sizes)
    total = 0
    for rank in range(8):
        q = slice(8 * rank, 8 * rank + 8)
        kv = slice(8 * (rank // 4), 8 * (rank // 4) + 8)
        total = total + share.apply({"params": {
            "q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][q]}}}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_the_64_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 64 ranks give (8 of 512 experts each, in
    the latent space), with the router, the projection down, the projection
    up and the shared expert counted once, are the uncut reference's
    expert layer."""
    rng = np.random.RandomState(3)
    n, hid, lat, f, e, sw = 48, 16, 8, 12, 512, 20
    mk = lambda *s: jnp.asarray(rng.randn(*s) * .3, jnp.float32)  # noqa: E731
    x = jnp.asarray(rng.randn(n, hid), jnp.float32)
    p = {"m/gate": mk(hid, e), "m/latent_down_proj/kernel": mk(hid, lat),
         "m/latent_up_proj/kernel": mk(lat, hid),
         "m/experts_up_proj": mk(e, lat, f),
         "m/experts_down_proj": mk(e, f, lat),
         "m/shared_experts/up_proj/kernel": mk(hid, sw),
         "m/shared_experts/down_proj/kernel": mk(sw, hid)}
    cfg = dict(CFG, hidden_size=hid, moe_latent_size=lat,
               moe_intermediate_size=f, n_routed_experts=e, experts_held=e,
               first_expert=0, num_experts_per_tok=22,
               moe_shared_expert_intermediate_size=sw)
    bias = jnp.zeros((e,))
    whole, _ = ref.expert_layer(cfg, p, "m", x, bias, None)
    idx, gates = route_noaux_tc(x, p["m/gate"], bias, top_k=22, scaling=5.0)
    u = x @ p["m/latent_down_proj/kernel"]
    total, rows = jnp.zeros_like(u), 0
    for rank in range(64):
        lo = 8 * rank
        y, counters = held_experts_ffn(
            u, idx, gates, None, p["m/experts_up_proj"][lo:lo + 8],
            p["m/experts_down_proj"][lo:lo + 8], first_expert=lo,
            n_experts=e, activation=relu_squared)
        total = total + y
        rows += int(counters["local_rows"])
        assert int(counters["dropped_rows"]) == 0
    assert rows == n * 22                    # every token-choice, once
    shared = ref.relu2_mlp(cfg, x, p["m/shared_experts/up_proj/kernel"],
                           p["m/shared_experts/down_proj/kernel"], None)
    np.testing.assert_allclose(
        np.asarray(total @ p["m/latent_up_proj/kernel"] + shared),
        np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hot", [False, True])
def test_two_matrix_experts_equal_a_dense_loop(hot):
    """``held_experts_ffn`` with no gate stack computes ``act(x W1) W2`` an
    expert, values and gradients, where the rows overflow the first chunk
    too."""
    rng = np.random.RandomState(4)
    n, d, f, e, held, first, k = 40, 8, 12, 16, 4, 8, 3
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    w1 = jnp.asarray(rng.randn(held, d, f) * .3, jnp.float32)
    w2 = jnp.asarray(rng.randn(held, f, d) * .3, jnp.float32)
    idx = np.stack([rng.choice(e, k, replace=False) for _ in range(n)])
    if hot:
        idx[:, 0] = first + 1                # every token lands here
        idx[:, 1:] = np.where(idx[:, 1:] == first + 1, 0, idx[:, 1:])
    idx = jnp.asarray(idx, jnp.int32)
    gates = jnp.asarray(rng.rand(n, k), jnp.float32)

    def dense(x, w1, w2, gates):
        y = jnp.zeros_like(x)
        for j in range(held):
            w = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)
            y = y + relu_squared(x @ w1[j]) @ w2[j] * w[:, None]
        return y

    def ours(x, w1, w2, gates):
        return held_experts_ffn(x, idx, gates, None, w1, w2,
                                first_expert=first, n_experts=e,
                                activation=relu_squared)[0]

    np.testing.assert_allclose(np.asarray(ours(x, w1, w2, gates)),
                               np.asarray(dense(x, w1, w2, gates)),
                               rtol=1e-4, atol=1e-5)
    loss = (lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a)))))
    got = jax.grad(loss(ours), argnums=range(4))(x, w1, w2, gates)
    want = jax.grad(loss(dense), argnums=range(4))(x, w1, w2, gates)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)


# --- the configuration ---------------------------------------------------------

@pytest.mark.parametrize("key,value,match", [
    ("n_group", 2, "one group"), ("norm_topk_prob", False, "renormalised"),
    ("mlp_hidden_act", "silu", "squared ReLU"),
    ("tie_word_embeddings", True, "untied"), ("use_bias", True, "no bias"),
    ("hybrid_override_pattern", "ME-EM", "names"),
    ("hybrid_override_pattern", "ME*", "names"),
    ("mtp_hybrid_override_pattern", "", "MTP"),
    ("mixer_parallel_size", 3, "whole shares"),
    ("mixer_parallel_rank", 1, "whole shares")])
def test_from_config_refuses_what_the_model_does_not_compute(key, value,
                                                             match):
    with pytest.raises(ValueError, match=match):
        DecoderLM.from_config(dict(CFG, **{key: value}))


def test_from_config_reads_the_published_key_names():
    model = DecoderLM.from_config(dict(CFG, mixer_parallel_size=2,
                                       mixer_parallel_rank=1))
    assert model.layer_kinds == tuple("ME*EM")          # the first five
    assert model.mtp_kinds == ("*", "E")
    assert dict(model.mamba) == dict(
        num_heads=4, head_dim=4, n_groups=1, state_size=8, conv_kernel=4,
        chunk_size=8, eps=1e-5)
    assert dict(model.attention) == dict(
        num_heads=2, num_kv_heads=1, head_dim=16, rope_theta=10000.0,
        eps=1e-5, gated=False, qk_norm=False)
    experts = dict(model.experts)
    assert experts["latent_size"] == 16 and experts["shared_width"] == 40
    assert experts["activation"] == "relu2" and experts["gated"] is False
    assert experts["num_experts_per_tok"] == 6
    assert experts["routed_scaling_factor"] == 5.0
    assert model.rms_norm_eps == 1e-5
    # four ranks over two kv heads: each kv head on two ranks
    assert DecoderLM.from_config(dict(
        CFG, n_groups=4, mixer_parallel_size=4)).attention["num_kv_heads"] == 1


def test_trains_through_the_estimator_on_arrays():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    ctx = init_orca_context("local")
    mesh = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    model = DecoderLM.from_config(dict(CFG, compute_dtype="bfloat16",
                                       num_hidden_layers=3))
    est = TPUEstimator(model, loss=model.loss(),
                       optimizer=AdamWeightDecay(lr=3e-3, weight_decay=0.1,
                                                 beta_2=0.95),
                       mesh=mesh, seed=0)
    ids = np.random.RandomState(6).randint(0, 96, (8, SEQ)).astype(np.uint16)
    stats = est.fit({"x": ids, "y": ids}, epochs=3, batch_size=4,
                    verbose=False)
    losses = [s["train_loss"] for s in stats]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    counters = moe_counters(est.engine.extra_vars)
    assert counters["moe_steps"] == 6 and counters["moe_dropped_rows"] == 0
    bias = jax.device_get(est.engine.extra_vars["router_state"])
    assert np.abs(bias["layers_1"]["mlp"]["e_score_correction_bias"]).max() > 0
    est.shutdown()


def _leaves(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_the_configurations_parameter_count_is_pinned():
    """700.9 M parameters, 11.21 GB at 16 B a parameter: the cut of
    ISSUE 41, counted three ways from shapes alone; every width the catalog
    row's."""
    with open(os.path.join(BENCH, "configs",
                           "nemotron3_super_tp8_ep64.json")) as f:
        cfg = json.load(f)
    for key, width in (("hidden_size", 4096), ("mamba_head_dim", 64),
                       ("ssm_state_size", 128), ("conv_kernel", 4),
                       ("chunk_size", 128), ("head_dim", 128),
                       ("moe_latent_size", 1024),
                       ("moe_intermediate_size", 2688),
                       ("moe_shared_expert_intermediate_size", 5376),
                       ("num_experts_per_tok", 22),
                       ("routed_scaling_factor", 5), ("expand", 2)):
        assert cfg[key] == width
    assert len(cfg["hybrid_override_pattern"]) == 88    # kept whole
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    mcfg = factory.model_config(cfg)
    assert mcfg["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert mcfg["n_routed_experts"] == 512 and mcfg["experts_held"] == 8
    assert mcfg["mamba_num_heads"] == 128 and mcfg["mixer_parallel_size"] == 8
    n = ref.param_count(mcfg)
    assert n == 700_862_960 == work_hybrid.param_count(mcfg)
    assert abs(n - 700.9e6) / 700.9e6 < 0.001
    assert abs(16 * n - 11.21e9) / 11.21e9 < 0.001
    module = DecoderLM.from_config(mcfg)
    assert dict(module.mamba)["num_heads"] == 16
    assert dict(module.attention)["num_heads"] == 4
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert _leaves(shapes["params"]) == n
    m = work_hybrid.matrices(mcfg)
    assert m["mamba"] == 4096 * 2320 + 1024 * 4096              # 13.70 M
    assert m["attention"] == 5_242_880                          # 5.25 M
    assert m["router"] + m["latent"] + m["shared"] + 8 * m["expert"] == \
        98_566_144                                              # 98.57 M


def test_the_catalog_rows_keys_build_the_whole_models_structure():
    """``from_config`` on the published keys alone: 88 one-mixer blocks by
    the pattern and the MTP module, counted from shapes with no memory."""
    with open(os.path.join(BENCH, "configs",
                           "nemotron3_super_tp8_ep64.json")) as f:
        cfg = json.load(f)
    published = {k[:-len("_published")]: v for k, v in cfg.items()
                 if k.endswith("_published")}
    whole = {k: v for k, v in dict(cfg, **published).items()
             if k not in ("mixer_parallel_size", "mixer_parallel_rank")}
    module = DecoderLM.from_config(whole)
    assert len(module.layer_kinds) == 88 and module.mtp_kinds == ("*", "E")
    assert [module.layer_kinds.count(k) for k in "ME*"] == [40, 40, 8]
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))["params"]
    flat = {k: tuple(v.shape) for k, v in _flat(shapes).items()}
    assert flat["layers_0/mixer/in_proj/kernel"] == (
        4096, 2 * 8192 + 2 * 8 * 128 + 128)
    assert flat["layers_7/self_attn/q_proj/kernel"] == (4096, 32 * 128)
    assert flat["layers_7/self_attn/k_proj/kernel"] == (4096, 2 * 128)
    assert flat["layers_1/mlp/experts_up_proj"] == (512, 1024, 2688)
    assert flat["layers_1/mlp/gate"] == (4096, 512)
    assert flat["mtp_layers_1/mlp/shared_experts/up_proj/kernel"] == (
        4096, 5376)
    mamba = sum(int(np.prod(s)) for k, s in flat.items()
                if k.startswith("layers_0/"))
    assert abs(mamba - 109.64e6) / 109.64e6 < 0.001             # ISSUE 41
    assert 120e9 < _leaves(shapes) < 126e9                      # 120B-A12B
