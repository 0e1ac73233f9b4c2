"""The decoder language model built from the ``afmoe`` family's keys (gated
grouped-query attention with q/k norms, sliding-window and global layers,
sandwich norms, a scaled embedding, the sigmoid-routed expert layer of which
one rank holds a share) against the benchmark's plain float32 reference, at
small widths on the CPU, with a toy ``layer_types`` that has both kinds and a
window shorter than the sequence; the eight shares of an expert layer; the
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

from reference import trinity_mini as ref                       # noqa: E402
from harness import spec, work_gqa                              # noqa: E402

from analytics_zoo_tpu.parallel.expert_parallel import (        # noqa: E402
    held_experts_ffn, route_noaux_tc)
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    DecoderLM, moe_counters, next_token_loss, rope_half)
from test_attention import equations, pallas_kernels            # noqa: E402
from test_decoder_lm import _flat, _tree                        # noqa: E402

SLIDING, FULL = "sliding_attention", "full_attention"
CFG = dict(
    model_type="afmoe", vocab_size=96, hidden_size=32, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=48,
    moe_intermediate_size=16, num_experts=16, experts_held=4, first_expert=4,
    num_experts_per_tok=4, num_shared_experts=1, num_hidden_layers=4,
    num_dense_layers=1, layer_types=[SLIDING, SLIDING, FULL, SLIDING],
    sliding_window=12, rope_theta=10000.0, rms_norm_eps=1e-5,
    route_norm=True, route_scale=2.826, score_func="sigmoid",
    mup_enabled=True, load_balance_coeff=1e-3, compute_dtype="float32",
    init=dict(embedding_std=1.0, out_proj_scale=0.5, router_std=1.0))
SEQ = 32


@pytest.fixture(scope="module")
def sides():
    """Program and reference on the same seeded weights and ids: logits, the
    loss and every leaf's gradient."""
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
        ref_logits.append(ref_forward(weights, jnp.asarray(seq))[0])
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
    # four norms a block, q and k norms over a head's width, no MTP module
    assert shapes["layers_2/pre_mlp_layernorm/weight"] == (32,)
    assert shapes["layers_2/self_attn/k_norm/weight"] == (16,)
    assert shapes["layers_2/self_attn/k_proj/kernel"] == (32, 2 * 16)
    assert sides["preds"][1] is None


def test_logits_match_reference(sides):
    for b, want in enumerate(sides["ref_logits"]):
        np.testing.assert_allclose(np.asarray(sides["preds"][0][b]),
                                   np.asarray(want), rtol=2e-4, atol=2e-5)


def test_loss_matches_reference(sides):
    assert sides["loss"] == pytest.approx(sides["ref_loss"], rel=1e-5)


def test_every_leafs_gradient_matches_reference(sides):
    assert set(sides["grads"]) == set(sides["ref_grads"])
    for name, want in sides["ref_grads"].items():
        got = np.asarray(sides["grads"][name])
        scale = float(jnp.abs(want).max()) + 1e-12
        assert float(np.abs(got - np.asarray(want)).max()) <= 2e-4 * scale, \
            name


@pytest.mark.parametrize("fault", ["sliding_as_causal", "rope_on_global"])
def test_the_references_faults_are_another_model(sides, fault):
    """The two faults the cell's readings plant in the reference change its
    loss: the window and the global layers' missing RoPE are in the
    numbers `correct` compares."""
    seq = jnp.asarray(sides["ids"][0])
    clean, _ = ref.sequence_loss(CFG, sides["weights"], {}, seq)
    faulty, _ = ref.sequence_loss(dict(CFG, reference_fault=fault),
                                  sides["weights"], {}, seq)
    assert abs(float(faulty) - float(clean)) > 1e-3 * float(clean)


def _cells_configuration():
    """The grouped-query cell's configuration file and the model's keys its
    factory makes of it."""
    with open(os.path.join(BENCH, "configs", "trinity_mini_ep8.json")) as f:
        cfg = json.load(f)
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    return cfg, factory.model_config(cfg)


def test_each_layer_takes_the_kernels_of_its_kind(sides):
    """Two flash kernels a block (forward, fused backward: the remat policy
    keeps the forward's results), k and v handed over at their own two
    heads, the sliding layers' grids the window's bands (here one tile), and
    the windowed call sites counted."""
    import analytics_zoo_tpu.ops.attention as attn
    before = attn._TILES_NEEDED.value
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sides["loss_of"](p)[0]))(
        _tree(sides["weights"])).jaxpr
    flash = sorted(n for n in pallas_kernels(jaxpr) if "flash" in n)
    assert flash == sorted(4 * ["_flash_kernel", "_flash_bwd_fused_kernel"])
    forward = [e for e in equations(jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["jaxpr"].debug_info.func_name == "_flash_kernel"]
    assert len(forward) == 4
    for e in forward:
        q, k, v = (x.aval.shape for x in e.invars[:3])
        # (v carries its ones column at this head size)
        assert q == (2 * 4, SEQ, 16) and k == (2 * 2, SEQ, 16) == v[:2] + (16,)
    assert attn._TILES_NEEDED.value > before


def test_the_cells_own_blocks_take_one_backward_launch_each():
    """The same at the cell's own 16384 positions, 32 query heads on 4 kv
    heads of 128, bfloat16, traced on shapes (nothing computed; a sliding
    and a global block of the published widths with dense FFNs, a small
    vocabulary): under the one remat policy two flash kernels a block, the
    forward and ONE backward. A kv head's 8 x 16384 x 128 of dQ do not fit
    the fused kernel's VMEM budget at once, so its grid takes the group's
    query heads in turn, (kv heads, group, k blocks, q tiles or a band's),
    and ``zoo_attention_backward_total`` counts ``fused_by_head``."""
    import analytics_zoo_tpu.ops.attention as attn
    model = DecoderLM.from_config(dict(
        _cells_configuration()[1], num_hidden_layers=2, num_dense_layers=2,
        layer_types=[SLIDING, FULL], vocab_size=256))
    assert model.layer_windows == (2048, None)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))["params"]
    ids = jax.ShapeDtypeStruct((1, 16384), jnp.uint16)
    before = (attn._BACKWARD_FUSED_BY_HEAD.value,
              attn._BACKWARD_FUSED.value + attn._BACKWARD_TWO_KERNEL.value)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, ids: jnp.mean(next_token_loss(
        ids, model.apply({"params": p}, ids, train=True)))))(params, ids).jaxpr
    assert pallas_kernels(jaxpr) == \
        2 * ["_flash_kernel"] + 2 * ["_flash_bwd_fused_kernel"]
    grids = [tuple(e.params["grid_mapping"].grid) for e in equations(jaxpr)
             if e.primitive.name == "pallas_call"]
    # forward 1024 x 1024 tiles, the window's band 3 of them; backward 512 x
    # 512, the band 5; the global block's backward comes first
    assert grids == [(32, 16, 3), (32, 16, 16), (4, 8, 32, 32), (4, 8, 32, 5)]
    assert (attn._BACKWARD_FUSED_BY_HEAD.value,
            attn._BACKWARD_FUSED.value + attn._BACKWARD_TWO_KERNEL.value) == \
        (before[0] + 2, before[1])


def test_rope_half_rotates_the_two_halves():
    rng = np.random.RandomState(2)
    x = rng.randn(1, 6, 2, 8).astype(np.float32)
    got = np.asarray(rope_half(jnp.asarray(x), 100.0))
    z = x[..., :4] + 1j * x[..., 4:]                 # pairs (i, i + d/2)
    inv = 100.0 ** (-np.arange(0, 8, 2) / 8)
    want = z * np.exp(1j * np.arange(6)[:, None] * inv[None, :])[
        None, :, None, :]
    np.testing.assert_allclose(got[..., :4], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:], want.imag, atol=1e-5)
    # the reference's, a sequence at a time
    np.testing.assert_allclose(
        got, np.asarray(ref.rope(jnp.asarray(x[0]), 100.0)[None]), atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 8 ranks give (16 of 128 experts each),
    plus the shared expert counted once, are the uncut reference's expert
    layer."""
    rng = np.random.RandomState(3)
    n, d, f, e = 64, 16, 8, 128
    mk = lambda *s: jnp.asarray(rng.randn(*s) * .3, jnp.float32)  # noqa: E731
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    p = {"m/gate": mk(d, e), "m/experts_gate_proj": mk(e, d, f),
         "m/experts_up_proj": mk(e, d, f), "m/experts_down_proj": mk(e, f, d),
         "m/shared_experts/gate_proj/kernel": mk(d, f),
         "m/shared_experts/up_proj/kernel": mk(d, f),
         "m/shared_experts/down_proj/kernel": mk(f, d)}
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=f, num_experts=e,
               experts_held=e, first_expert=0, num_experts_per_tok=8)
    bias = jnp.zeros((e,))
    whole, _ = ref.expert_layer(cfg, p, "m", x, bias, None)
    idx, gates = route_noaux_tc(x, p["m/gate"], bias, top_k=8, scaling=2.826)
    total, rows = jnp.zeros_like(x), 0
    for rank in range(8):
        lo = 16 * rank
        y, counters = held_experts_ffn(
            x, idx, gates, p["m/experts_gate_proj"][lo:lo + 16],
            p["m/experts_up_proj"][lo:lo + 16],
            p["m/experts_down_proj"][lo:lo + 16],
            first_expert=lo, n_experts=e)
        total = total + y
        rows += int(counters["local_rows"])
        assert int(counters["dropped_rows"]) == 0
    assert rows == n * 8                     # every token-choice, once
    shared = ref.swiglu(x, p["m/shared_experts/gate_proj/kernel"],
                        p["m/shared_experts/up_proj/kernel"],
                        p["m/shared_experts/down_proj/kernel"], None)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("key,value,match", [
    ("score_func", "softmax", "sigmoid"), ("route_norm", False, "sigmoid"),
    ("rope_scaling", {"type": "yarn"}, "scaling"),
    ("tie_word_embeddings", True, "untied"),
    ("layer_types", [SLIDING, "linear_attention", FULL, FULL], "names"),
    ("layer_types", [SLIDING, FULL], "names")])
def test_from_config_refuses_what_the_model_does_not_compute(key, value,
                                                             match):
    with pytest.raises(ValueError, match=match):
        DecoderLM.from_config(dict(CFG, **{key: value}))


def test_from_config_reads_the_published_key_names():
    model = DecoderLM.from_config(dict(CFG, layer_types=CFG["layer_types"]
                                       + [FULL] * 3))
    assert model.layer_windows == (12, 12, None, 12)     # the first four
    assert model.first_k_dense_replace == 1 and model.sandwich_norms
    assert model.embed_scale == pytest.approx(32 ** 0.5)
    assert dict(model.attention) == dict(
        kind="gqa", num_heads=4, num_kv_heads=2, head_dim=16,
        rope_theta=10000.0)
    assert model.experts["routed_scaling_factor"] == 2.826
    assert model.experts["n_routed_experts"] == 16
    assert model.experts["experts_held"] == 4
    assert DecoderLM.from_config(dict(CFG, mup_enabled=False)
                                 ).embed_scale == 1.0


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


def test_the_configurations_parameter_count_is_pinned():
    """705.5 M parameters, 11.29 GB at 16 B a parameter: the cut of
    ISSUE 39, counted three ways; every width the catalog row's."""
    cfg, mcfg = _cells_configuration()
    for key, width in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("intermediate_size", 6144),
                       ("moe_intermediate_size", 1024),
                       ("num_experts_per_tok", 8), ("sliding_window", 2048)):
        assert cfg[key] == width
    assert len(cfg["layer_types"]) == 32               # kept whole
    assert mcfg["num_experts"] == 128 and mcfg["experts_held"] == 16
    assert mcfg["layer_types"] == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    n = ref.param_count(mcfg)
    assert n == 705_473_792 == work_gqa.param_count(mcfg)
    assert abs(n - 705.5e6) / 705.5e6 < 0.001
    assert abs(16 * n - 11.29e9) / 11.29e9 < 0.001
    module = DecoderLM.from_config(mcfg)
    assert module.layer_windows == (2048, 2048, None, 2048, 2048)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["params"])) == n
