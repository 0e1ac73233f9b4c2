"""The decoder language model built from the ``KeyeVL2`` family's keys
(grouped-query attention with q/k norms and MRoPE, DeepSeek Sparse
Attention's lightning indexer choosing each query's keys, the selected-key
flash kernels, the indexer's KL loss beside the LM loss, a softmax-routed
expert layer of which one rank holds a share) against the benchmark's plain
float32 reference, at small widths on the CPU; which leaves each loss
reaches; MRoPE; the eight shares of a layer; the configuration's parameter
count; the model trained through ``TPUEstimator.fit`` on arrays."""

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

from reference import keye_vl2 as ref                           # noqa: E402
from harness import spec, work_sa                               # noqa: E402

from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    SA_STATS, Block, DecoderLM, rope_half, sa_counters, moe_counters,
    sparse_attention_loss)
from test_attention import pallas_kernels                       # noqa: E402
from test_decoder_lm import _flat, _tree, sublayer_sizes        # noqa: E402

CFG = dict(
    model_type="KeyeVL2", vocab_size=96, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=48, moe_intermediate_size=16, num_experts=16,
    experts_held=4, first_expert=4, num_experts_per_tok=4,
    num_hidden_layers=2, rope_theta=1e7, rms_norm_eps=1e-6,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
               "q_chunk_size": 32, "topk": 24},
    compute_dtype="float32",
    init=dict(embedding_std=1.0, out_proj_scale=0.5, router_std=1.0))
SEQ = 128


@pytest.fixture(scope="module")
def sides():
    """Program and reference on the same seeded weights and ids: logits,
    both losses and every leaf's gradient, and what each loss alone sends
    to the leaves."""
    model = DecoderLM.from_config(CFG)
    ids = np.random.RandomState(0).randint(0, 96, (2, SEQ)).astype(np.uint16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(ids[:1]))
    weights = ref.make_weights(CFG, 7)
    extra = {k: v for k, v in variables.items() if k != "params"}

    def preds_of(p):
        return model.apply({"params": p, **extra}, jnp.asarray(ids),
                           train=True, mutable=list(extra))

    def loss_of(p, weight=1.0, lm=1.0):
        (logits, l_i), new = preds_of(p)
        nll = sparse_attention_loss(jnp.asarray(ids), (logits, l_i),
                                    indexer_weight=0.0)
        return jnp.mean(lm * nll + weight * l_i), ((logits, l_i), new)

    (loss, (preds, new)), grads = jax.jit(jax.value_and_grad(
        loss_of, has_aux=True))(_tree(weights))
    split = {name: _flat(jax.jit(jax.grad(
        lambda p, w=w, lm=lm: loss_of(p, w, lm)[0]))(_tree(weights)))
        for name, w, lm in (("lm", 0.0, 1.0), ("indexer", 1.0, 0.0))}
    ref_grad = jax.jit(jax.value_and_grad(
        lambda p, seq: ref.sequence_loss(CFG, p, seq), has_aux=True))
    ref_losses, ref_lm, ref_li, ref_grads = [], [], [], None
    for seq in ids:
        (l, (lm, li, _, _)), g = ref_grad(weights, jnp.asarray(seq))
        ref_losses.append(float(l))
        ref_lm.append(float(lm))
        ref_li.append(float(li))
        ref_grads = g if ref_grads is None else jax.tree.map(
            jnp.add, ref_grads, g)
    return dict(model=model, variables=variables, weights=weights, ids=ids,
                loss_of=loss_of, loss=float(loss), preds=preds, new=new,
                grads=_flat(grads), split=split,
                ref_loss=float(np.mean(ref_losses)),
                ref_lm=float(np.mean(ref_lm)), ref_li=float(np.mean(ref_li)),
                ref_grads={k: v / len(ids) for k, v in ref_grads.items()})


def test_program_tree_is_the_references(sides):
    shapes = {k: tuple(v.shape)
              for k, v in _flat(sides["variables"]["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}
    # the indexer beside the attention: 4 query heads of 16, one key head
    # with its LayerNorm, a weight a head
    a = "layers_1/self_attn"
    assert shapes[f"{a}/indexer_q_proj/kernel"] == (32, 64)
    assert shapes[f"{a}/indexer_k_norm/bias"] == (16,)
    assert shapes[f"{a}/indexer_weights_proj/kernel"] == (32, 4)
    assert set(sides["variables"]) == {"params", "moe_stats", SA_STATS}


def test_both_losses_match_reference(sides):
    """L_LM and L_I apart, and their sum: float32 on both sides at the
    highest precision, the program's in the flash kernel's tiles and the
    indexer's chunks (another order of summation)."""
    logits, l_i = sides["preds"]
    assert float(jnp.mean(l_i)) == pytest.approx(sides["ref_li"], rel=1e-5)
    assert sides["loss"] == pytest.approx(sides["ref_loss"], rel=1e-5)
    assert sides["loss"] - float(jnp.mean(l_i)) == pytest.approx(
        sides["ref_lm"], rel=1e-5)
    assert logits.shape == (2, SEQ, 96)


def test_every_leafs_gradient_matches_reference(sides):
    """Within 2e-4 of the leaf's largest entry."""
    assert set(sides["grads"]) == set(sides["ref_grads"])
    for name, want in sides["ref_grads"].items():
        got = np.asarray(sides["grads"][name])
        scale = float(jnp.abs(want).max()) + 1e-12
        assert float(np.abs(got - np.asarray(want)).max()) <= 2e-4 * scale, \
            name


def test_each_loss_reaches_its_own_leaves(sides):
    """The indexer reads its input detached and its target is not
    differentiated: L_LM sends nothing to an indexer leaf, L_I nothing to
    any other, and each reaches all of its own."""
    for name, g in sides["split"]["lm"].items():
        moved = bool(np.any(np.asarray(g)))
        assert moved == ("indexer" not in name), name
    for name, g in sides["split"]["indexer"].items():
        moved = bool(np.any(np.asarray(g)))
        assert moved == ("indexer" in name), name


def test_the_step_leaves_its_selection_and_loss_in_sa_stats(sides):
    """Each layer's last step: its L_I (the model's output's mean over the
    layers), the walk's entries against the selected ones (every query's
    min(24, t + 1) keys), how many keys each query chose in each span."""
    out = sa_counters(sides["new"])
    assert out["sa_steps"] == 1
    assert out["sa_indexer_kl"] == pytest.approx(
        float(jnp.mean(sides["preds"][1])), rel=1e-5)
    need = sum(min(24, t + 1) for t in range(SEQ))
    assert out["sa_entries_needed"] == 2 * need
    assert 2 * need <= out["sa_entries_computed"] <= 2 * SEQ * (SEQ + 32) / 2
    keys = np.minimum(24, np.arange(SEQ) + 1)
    for layer, bins in out["sa_key_bins"].items():
        assert bins.shape == (SEQ, min(128, SEQ))
        np.testing.assert_array_equal(bins.sum(-1), 2 * keys)


def test_mrope_is_the_references_and_rotate_half_on_text():
    """Distinct temporal, height and width positions rotate each section's
    pairs by its own stream, as the reference's MRoPE; with the three equal
    it is plain rotate-half RoPE, to the bit."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 40, 3, 16).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, 1000, (3, 40)))
    got = rope_half(x, 1e7, pos, (2, 3, 3))
    want = ref.mrope(x[0], 1e7, pos, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    text = jnp.broadcast_to(jnp.arange(40), (3, 40))
    np.testing.assert_array_equal(np.asarray(rope_half(x, 1e7, text,
                                                       (2, 3, 3))),
                                  np.asarray(rope_half(x, 1e7)))
    with pytest.raises(ValueError, match="MRoPE"):
        rope_half(x, 1e7, text, (2, 3, 2))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A whole layer, attention counted once: the program's block as each
    of the 8 ranks holds it (16 of 128 experts), less what all but one add
    twice (the input and the attention), is the uncut reference block."""
    cfg = dict(CFG, num_experts=128, num_experts_per_tok=8, experts_held=128,
               first_expert=0, num_hidden_layers=1)
    weights = ref.make_weights(cfg, 3)
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 32), jnp.float32)
    whole, _, _, _ = ref.block(cfg, weights, "layers_0", x, None)
    after_attention = x + ref.attention(cfg, weights, "layers_0/self_attn",
                                        ref.rms_norm(
        x, weights["layers_0/input_layernorm/weight"], 1e-6), None)[0]
    total = jnp.zeros_like(x)
    for rank in range(8):
        part = dict(cfg, experts_held=16, first_expert=16 * rank)
        model = DecoderLM.from_config(part)
        p = dict(weights)
        for name in ("experts_gate_proj", "experts_up_proj",
                     "experts_down_proj"):
            key = f"layers_0/mlp/{name}"
            p[key] = weights[key][16 * rank:16 * rank + 16]
        layer = model.layers[0]
        block = Block(layer=layer, eps=1e-6, dtype=jnp.float32)
        params = _tree({k[len("layers_0/"):]: v for k, v in p.items()
                        if k.startswith("layers_0/")})
        (y, _), _ = block.apply({"params": params}, x[None], mutable=True)
        total = total + y[0]
    np.testing.assert_allclose(np.asarray(total - 7 * after_attention),
                               np.asarray(whole), rtol=2e-4, atol=2e-4)


def test_each_block_walks_its_selection_and_keeps_it_across_the_remat(sides):
    """Two flash kernels a block (forward, fused backward: the remat policy
    keeps the forward's results) and one selection a block (kept too: the
    backward pass does not choose again)."""
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sides["loss_of"](p)[0]))(
        _tree(sides["weights"])).jaxpr
    flash = sorted(n for n in pallas_kernels(jaxpr) if "flash" in n)
    assert flash == sorted(2 * ["_flash_kernel", "_flash_bwd_fused_kernel"])
    # a selection shifts twice (the bisection's candidate bit, the
    # packing): once a block, not again in the backward pass
    assert str(jaxpr).count("shift_left") == 2 * 2


def _cells_configuration():
    with open(os.path.join(BENCH, "configs",
                           "keye_vl2_30b_a3b_ep8.json")) as f:
        cfg = json.load(f)
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    return cfg, factory.model_config(cfg)


def test_from_config_reads_the_published_key_names():
    cfg, _ = _cells_configuration()
    catalog = {k: cfg[k] for k in (
        "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act",
        "hidden_size", "intermediate_size", "max_position_embeddings",
        "max_window_layers", "mlp_only_layers", "model_type",
        "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
        "num_experts_per_tok", "num_key_value_heads", "rms_norm_eps",
        "rope_scaling", "rope_theta", "sa_config", "sliding_window",
        "tie_word_embeddings", "use_sliding_window")}
    model = DecoderLM.from_config(dict(catalog, num_experts=128,
                                       num_local_experts=128,
                                       num_hidden_layers=48,
                                       vocab_size=151936))
    assert model.indexer_loss_weight == 1.0 and not model.diffusion_block
    sizes = sublayer_sizes(model)
    assert sizes["gqa"] == dict(
        num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e7,
        eps=1e-6, gated=False, qk_norm=True, indexer_heads=16,
        indexer_head_dim=64, indexer_topk=2048, indexer_rows=512,
        mrope_section=(16, 24, 24))
    assert sizes["experts"]["scoring"] == "softmax"
    assert sizes["experts"]["n_routed_experts"] == 128
    assert len(model.layers) == 48 and not model.mtp_layers


@pytest.mark.parametrize("key,value,match", [
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("norm_topk_prob", False, "softmax"),
    ("use_sliding_window", True, "window"),
    ("tie_word_embeddings", True, "untied"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "type": "yarn"}, "MRoPE"),
    ("rope_scaling", {"mrope_section": [2, 3, 2]}, "mrope_section"),
    ("sa_config", dict(CFG["sa_config"], indexer_num_kv_heads=2),
     "one key head"),
    ("sa_config", dict(CFG["sa_config"], kv_chunk_size=64), "equal")])
def test_from_config_refuses_what_the_model_does_not_compute(key, value,
                                                             match):
    with pytest.raises(ValueError, match=match):
        DecoderLM.from_config(dict(CFG, **{key: value}))


def test_trains_through_the_estimator_on_arrays():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    ctx = init_orca_context("local")
    mesh = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    model = DecoderLM.from_config(dict(CFG, compute_dtype="bfloat16",
                                       num_hidden_layers=1))
    est = TPUEstimator(model, loss=model.loss(),
                       optimizer=AdamWeightDecay(lr=3e-3, weight_decay=0.1,
                                                 beta_2=0.95),
                       mesh=mesh, seed=0)
    ids = np.random.RandomState(6).randint(0, 96, (4, 64)).astype(np.uint16)
    stats = est.fit({"x": ids, "y": ids}, epochs=2, batch_size=2,
                    verbose=False)
    losses = [s["train_loss"] for s in stats]
    assert np.all(np.isfinite(losses))
    assert moe_counters(est.engine.extra_vars)["moe_steps"] == 4
    sa = sa_counters(est.engine.extra_vars)
    assert sa["sa_steps"] == 4 and 0 < sa["sa_indexer_kl"] < losses[-1]
    est.shutdown()


def test_the_configurations_parameter_count_is_pinned():
    """659,190,016 parameters, 10.55 GB at 16 B a parameter: the
    configuration's cut, counted three ways; every width the catalog
    row's."""
    cfg, mcfg = _cells_configuration()
    for key, width in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("intermediate_size", 6144),
                       ("moe_intermediate_size", 768),
                       ("num_experts_per_tok", 8), ("rope_theta", 10000000)):
        assert cfg[key] == width
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert mcfg["num_experts"] == 128 and mcfg["experts_held"] == 16
    assert mcfg["vocab_size"] == 18992 and mcfg["num_hidden_layers"] == 6
    n = ref.param_count(mcfg)
    assert n == 659_190_016 == work_sa.param_count(mcfg)
    assert abs(16 * n - 10.55e9) / 10.55e9 < 0.001
    module = DecoderLM.from_config(mcfg)
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["params"])) == n
