"""The decoder language model built from the ``granitemoehybrid`` family's
keys (a mixer by ``layer_types`` and a SwiGLU MLP a block, the muP scalars, a
head tied to the embedding) against the benchmark's plain float32 reference,
at small widths on the CPU; the tied leaf's two gradients; each muP scalar;
the vocabulary's eight shares; the chunked scan at the family's chunk of 256
against the literal recurrence; the dispatch by ``model_type``; both
parameter counts; the model trained through ``TPUEstimator.fit`` on
arrays."""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import granite_h as ref                          # noqa: E402
from harness import spec, work_granite                          # noqa: E402

from analytics_zoo_tpu.ops import ssm                           # noqa: E402
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    DecoderLM, moe_counters, next_token_loss)
from analytics_zoo_tpu.pipeline.api.keras.layers import decoder_lm  # noqa: E402
from test_attention import equations                            # noqa: E402
from test_decoder_lm import (                                   # noqa: E402
    _bit_equal, _flat, _tree, remat_block_sides, remat_kept_bytes,
    sublayer_sizes)

CFG = dict(
    model_type="granitemoehybrid", vocab_size=96, hidden_size=32,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=2, mamba_d_state=8,
    mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2, mamba_conv_bias=True,
    mamba_proj_bias=False, num_attention_heads=2, num_key_value_heads=1,
    attention_multiplier=0.0625, attention_bias=False,
    position_embedding_type="nope", embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=8, shared_intermediate_size=48,
    intermediate_size=48, num_local_experts=0, hidden_act="silu",
    rms_norm_eps=1e-5, tie_word_embeddings=True, compute_dtype="float32",
    time_step_min=0.0005, time_step_max=0.005,
    init=dict(embedding_std=0.5, out_proj_scale=0.5))
SEQ = 32             # four chunks of the scan
MUP = {"embedding_multiplier": 1, "residual_multiplier": 1,
       "logits_scaling": 1}


def program_sides(cfg, ids, weights):
    """The program's logits, mean loss and flat gradient on ``weights``."""
    model = DecoderLM.from_config(cfg)

    def loss_of(p):
        logits, _ = model.apply({"params": p}, jnp.asarray(ids))
        return jnp.mean(next_token_loss(jnp.asarray(ids), (logits, None))), \
            logits

    (loss, logits), grads = jax.jit(jax.value_and_grad(
        loss_of, has_aux=True))(_tree(weights))
    return logits, float(loss), _flat(grads)


def reference_sides(cfg, ids, weights):
    grad = jax.jit(jax.value_and_grad(
        lambda p, seq: ref.sequence_loss(cfg, p, seq)))
    forward = jax.jit(lambda p, seq: ref.forward(cfg, p, seq))
    losses, grads, logits = [], None, []
    for seq in ids:
        loss, g = grad(weights, jnp.asarray(seq))
        losses.append(float(loss))
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        logits.append(forward(weights, jnp.asarray(seq)))
    return (jnp.stack(logits), float(np.mean(losses)),
            {k: v / len(ids) for k, v in grads.items()})


def agree(program, reference) -> bool:
    """Logits, loss and every leaf's gradient within the comparison's
    tolerances (float32 on both sides, the chunked scan against the
    recurrence and the flash kernel against materialised scores)."""
    (logits, loss, grads), (want_logits, want_loss, want_grads) = \
        program, reference
    if not np.allclose(np.asarray(logits), np.asarray(want_logits),
                       rtol=2e-4, atol=2e-5):
        return False
    if abs(loss - want_loss) > 1e-5 * abs(want_loss):
        return False
    for name, want in want_grads.items():
        scale = float(jnp.abs(want).max()) + 1e-12
        if float(np.abs(np.asarray(grads[name]) - np.asarray(want)).max()) \
                > 2e-4 * scale:
            return False
    return set(grads) == set(want_grads)


@pytest.fixture(scope="module")
def sides():
    ids = np.random.RandomState(0).randint(0, 96, (2, SEQ)).astype(np.uint16)
    weights = ref.make_weights(CFG, 7)
    return dict(ids=ids, weights=weights,
                program=program_sides(CFG, ids, weights),
                reference=reference_sides(CFG, ids, weights))


def test_program_tree_is_the_references(sides):
    model = DecoderLM.from_config(CFG)
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, SEQ), jnp.uint16)))
    shapes = {k: tuple(v.shape) for k, v in _flat(variables["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}
    assert set(variables) == {"params"}            # no expert state at all
    # no head leaf: the embedding is the head
    assert "lm_head" not in shapes
    assert sorted(k.split("/")[1] for k in shapes
                  if k.startswith("layers_1/")) == [
        "input_layernorm", "mlp", "mlp", "mlp", "post_attention_layernorm",
        "self_attn", "self_attn", "self_attn", "self_attn"]
    assert shapes["layers_0/mixer/in_proj/kernel"] == (32, 64 + 64 + 32 + 8)
    assert shapes["layers_1/self_attn/k_proj/kernel"] == (32, 16)
    assert shapes["layers_2/mlp/gate_proj/kernel"] == (32, 48)


def test_logits_loss_and_every_gradient_match_reference(sides):
    assert agree(sides["program"], sides["reference"])


def test_the_tied_leafs_gradient_is_the_lookups_plus_the_heads(sides):
    """With the head untied and set to the embedding's transpose, the
    program computes the same function; the tied leaf's gradient is the sum
    of the untied lookup's and the untied head's (transposed)."""
    weights = dict(sides["weights"])
    weights["lm_head"] = weights["embed_tokens/embedding"].T
    _, loss, grads = program_sides(dict(CFG, tie_word_embeddings=False),
                                   sides["ids"], weights)
    assert loss == pytest.approx(sides["program"][1], rel=1e-6)
    tied = np.asarray(sides["program"][2]["embed_tokens/embedding"])
    lookup = np.asarray(grads["embed_tokens/embedding"])
    head = np.asarray(grads["lm_head"]).T
    np.testing.assert_allclose(tied, lookup + head, rtol=1e-5,
                               atol=1e-6 * np.abs(tied).max())
    # both parts carry weight
    assert np.abs(lookup).max() > 0.05 * np.abs(tied).max()
    assert np.abs(head).max() > 0.05 * np.abs(tied).max()


@pytest.mark.parametrize("scalar", sorted(MUP))
def test_each_mup_scalar_is_in_the_model(sides, scalar):
    """The reference with that one scalar set to 1 fails the comparison the
    program passes."""
    unscaled = dict(CFG, **{scalar: MUP[scalar]})
    assert not agree(sides["program"],
                     reference_sides(unscaled, sides["ids"],
                                     sides["weights"]))


@pytest.mark.parametrize("planted", [
    dict(reference_fault="state_reset_at_chunks"),
    dict(reference_fault="attention_at_inverse_sqrt_d"),
    dict(reference_label_positions=SEQ // 2)])
def test_the_references_faults_are_another_model(sides, planted):
    """The faults the cell's readings plant in the reference change what it
    computes: the carried state and the softmax's scale in its logits, the
    labels in its loss."""
    cfg = dict(CFG, **planted)
    seq = jnp.asarray(sides["ids"][0])
    w = sides["weights"]
    if "reference_fault" in planted:
        got, want = ref.forward(cfg, w, seq), ref.forward(CFG, w, seq)
        assert float(jnp.abs(got - want).max()) > 1e-3
    else:
        loss = float(ref.sequence_loss(cfg, w, seq))
        assert abs(loss - float(ref.sequence_loss(CFG, w, seq))) > 1e-3


def test_the_eight_vocabulary_shares_are_the_uncut_head(sides):
    """Vocabulary-parallel over 8: for the same hidden states, the 8 slices'
    tied heads (each its own rows of the table, over ``logits_scaling``),
    side by side, are the uncut head's logits."""
    ids = jnp.asarray(sides["ids"][:1])
    weights = sides["weights"]
    model = DecoderLM.from_config(CFG)
    hidden = {}

    def keep_final_norm(next_fun, args, kwargs, context):
        out = next_fun(*args, **kwargs)
        if context.module.name == "norm" and \
                context.method_name == "__call__":
            hidden["h"] = out
        return out

    with nn.intercept_methods(keep_final_norm):
        whole, _ = model.apply({"params": _tree(weights)}, ids)
    rows = CFG["vocab_size"] // 8
    share_model = DecoderLM.from_config(dict(CFG, vocab_size=rows))

    def same_hidden(next_fun, args, kwargs, context):
        if context.module.name == "norm" and \
                context.method_name == "__call__":
            return hidden["h"]
        return next_fun(*args, **kwargs)

    @jax.jit
    def share_logits(params):
        with nn.intercept_methods(same_hidden):
            return share_model.apply({"params": params}, ids % rows)[0]

    parts = []
    for r in range(8):
        share = dict(weights)
        share["embed_tokens/embedding"] = \
            weights["embed_tokens/embedding"][r * rows:(r + 1) * rows]
        parts.append(share_logits(_tree(share)))
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, -1)),
                               np.asarray(whole), rtol=1e-6, atol=1e-6)
    # and the reference's head, on those hidden states
    np.testing.assert_allclose(
        np.asarray(ref.head(CFG, weights, hidden["h"][0], None)),
        np.asarray(whole[0]), rtol=1e-5, atol=1e-6)


# --- the scan at the family's chunk ---------------------------------------------

def scan_inputs(seed, seq, bsz=1, h=4, p=8, g=1, n=16):
    rs = np.random.RandomState(seed)
    f = (lambda *s: jnp.asarray(rs.randn(*s), jnp.float32))
    # small dt |A|: what position 0 wrote is still there chunks later
    dt = jax.nn.softplus(f(bsz, seq, h) - 5.0)
    a = -jnp.exp(jnp.asarray(rs.rand(h), jnp.float32))
    return f(bsz, seq, h, p), dt, a, f(bsz, seq, g, n), f(bsz, seq, g, n), \
        f(h)


@pytest.mark.parametrize("what", ["values", "gradients"])
def test_the_scan_at_chunk_256_with_one_group_is_the_recurrence(what):
    """Three chunks of 256 positions, every head reading the one B/C
    group."""
    args = scan_inputs(11, seq=3 * 256)
    chunked = (lambda *a: ssm.ssd_scan(*a, chunk_size=256))
    if what == "values":
        want = ssm.ssd_scan_sequential(*args)
        got = chunked(*args)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
        return
    loss = (lambda fn: (lambda *a: jnp.sum(jnp.sin(fn(*a)))))
    want = jax.grad(loss(ssm.ssd_scan_sequential), argnums=range(6))(*args)
    got = jax.grad(loss(chunked), argnums=range(6))(*args)
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale


# --- the family, its counts, the estimator --------------------------------------

def test_model_type_picks_this_family_before_layer_types():
    module = DecoderLM.from_config(CFG)
    assert [[(s.kind, s.norm, s.post_norm, s.scope) for s in layer.sublayers]
            for layer in module.layers] == [
        [(mixer, "input_layernorm", None, None),
         ("swiglu", "post_attention_layernorm", None, "mlp.dense")]
        for mixer in ("mamba2", "gqa", "mamba2")]
    sizes = sublayer_sizes(module)
    assert sizes["swiglu"] == dict(width=48) and module.tie_word_embeddings
    assert module.layer_windows is None and not module.mtp_layers
    assert {layer.residual_scale for layer in module.layers} == {0.22}
    assert (module.embed_scale, module.logits_scale) == (12.0, 8.0)
    assert sizes["gqa"]["sm_scale"] == 0.0625
    assert sizes["mamba2"]["chunk_size"] == 8
    # the afmoe family reads layer_types too, and would refuse the tie
    afmoe = {k: v for k, v in CFG.items() if k != "model_type"}
    with pytest.raises((ValueError, KeyError)):
        DecoderLM.from_config(afmoe)


@pytest.mark.parametrize("key,value,match", [
    ("num_local_experts", 8, "no expert layer"),
    ("position_embedding_type", "rope", "no position encoding"),
    ("layer_types", ["mamba", "shared_attention", "mamba"], "layer_types"),
    ("mamba_d_head", 4, "mamba_expand"),
    ("attention_bias", True, "no bias")])
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
    model = DecoderLM.from_config(dict(
        CFG, compute_dtype="bfloat16", num_hidden_layers=2))
    est = TPUEstimator(model, loss=model.loss(),
                       optimizer=AdamWeightDecay(lr=3e-3, weight_decay=0.1,
                                                 beta_2=0.95),
                       mesh=mesh, seed=0)
    ids = np.random.RandomState(6).randint(0, 96, (4, 16)).astype(np.uint16)
    stats = est.fit({"x": ids, "y": ids}, epochs=3, batch_size=2,
                    verbose=False)
    losses = [s["train_loss"] for s in stats]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert moe_counters(est.engine.extra_vars) == {}
    est.shutdown()


@pytest.mark.parametrize("kind", ["M", "*"])
def test_a_block_keeps_its_mlps_gate_and_up_across_the_remat(kind):
    """A Granite block (its mixer, then its MLP) under its policy keeps its
    MLP's gate and up products for the backward pass: two more saved values
    of (batch, seq, width), two fewer products in the gradient's program
    (``gate_proj`` and ``up_proj`` not run again), neither name marked again
    in the backward pass, and the gradients of the policy that keeps them
    not, to the bit."""
    model = DecoderLM.from_config(CFG)
    layer = model.layers[{"M": 0, "*": 1}[kind]]        # layer_types M * M
    assert layer.policy is decoder_lm._KEPT_WITH_FFN_PRODUCTS
    fields = dict(layer=layer, eps=model.rms_norm_eps, dtype=model.dtype)
    shape = (2, SEQ, CFG["hidden_size"])
    before = remat_block_sides(decoder_lm.Block, fields, shape,
                               decoder_lm._KEPT_ACROSS_REMAT)
    now = remat_block_sides(decoder_lm.Block, fields, shape,
                            decoder_lm._KEPT_WITH_FFN_PRODUCTS)
    assert now["dots"] == before["dots"] - 2
    product = ((2, SEQ, CFG["intermediate_size"]), jnp.dtype(jnp.float32))
    assert now["saved"].count(product) == before["saved"].count(product) + 2
    assert set(decoder_lm.FFN_PRODUCT_NAMES) <= before["recomputed"]
    assert not set(decoder_lm.FFN_PRODUCT_NAMES) & now["recomputed"]
    assert _bit_equal(now["grads"], before["grads"])


def test_the_gauge_reads_the_mlps_products_a_step_keeps():
    """``zoo_remat_kept_bytes`` after a gradient's trace of the bfloat16
    model: under each of ``ffn_gate`` and ``ffn_up`` blocks x tokens x width
    x 2 bytes, the attention block's flash results, no routing decision;
    every block under the one policy that keeps them."""
    model = DecoderLM.from_config(dict(CFG, compute_dtype="bfloat16"))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 96, (2, SEQ)),
                      jnp.uint16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)

    def loss(p):
        return jnp.mean(next_token_loss(ids, model.apply(p, ids)))

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    assert {id(e.params["policy"]) for e in equations(jaxpr)
            if e.primitive.name == "remat2"} == \
        {id(decoder_lm._KEPT_WITH_FFN_PRODUCTS)}
    kept = remat_kept_bytes()
    blocks, tokens = CFG["num_hidden_layers"], 2 * SEQ
    assert kept["ffn_gate"] == kept["ffn_up"] == \
        blocks * tokens * CFG["intermediate_size"] * 2
    # the attention block's output: its heads are the hidden width
    assert kept["flash_out"] == tokens * CFG["hidden_size"] * 2
    assert kept["moe_router_choice"] == 0
    # a second trace sets the same numbers; it does not add to them
    jax.make_jaxpr(jax.grad(loss))(params)
    assert remat_kept_bytes() == kept


def _leaves(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def _config():
    with open(os.path.join(BENCH, "configs",
                           "granite4_h_micro_vp8.json")) as f:
        return json.load(f)


def test_the_configurations_parameter_count_is_pinned():
    """772,160,448 parameters, 12.35 GB at 16 B a parameter: the cut of
    ISSUE 45, counted from shapes alone three ways; every width the catalog
    row's."""
    cfg = _config()
    for key, width in (("hidden_size", 2048), ("mamba_d_head", 64),
                       ("mamba_n_heads", 64), ("mamba_d_state", 128),
                       ("mamba_d_conv", 4), ("mamba_chunk_size", 256),
                       ("mamba_n_groups", 1), ("mamba_expand", 2),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 8),
                       ("shared_intermediate_size", 8192)):
        assert cfg[key] == width
    assert len(cfg["layer_types"]) == 40                # kept whole
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    mcfg = factory.model_config(cfg)
    assert mcfg["layer_types"] == ["mamba"] * 5 + ["attention"] + \
        ["mamba"] * 4
    n = ref.param_count(mcfg)
    assert n == 772_160_448 == work_granite.param_count(mcfg)
    assert abs(16 * n - 12.35e9) / 12.35e9 < 0.001
    module = DecoderLM.from_config(mcfg)
    # nine Mamba-2 mixers and one attention mixer, each then an MLP, every
    # branch at the residual scale 0.22
    assert [[s.kind for s in layer.sublayers] for layer in module.layers] \
        == [["mamba2", "swiglu"]] * 5 + [["gqa", "swiglu"]] + \
        [["mamba2", "swiglu"]] * 4
    assert {layer.residual_scale for layer in module.layers} == {0.22}
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert _leaves(shapes["params"]) == n


def test_the_catalog_rows_keys_build_the_whole_models_structure():
    """``from_config`` on the published keys alone: 40 blocks (36 Mamba-2,
    4 attention, each with its MLP) and the tied 100352-row table,
    3,191,396,096 parameters counted from shapes with no memory."""
    cfg = _config()
    whole = dict(cfg, **{k[:-len("_published")]: v for k, v in cfg.items()
                         if k.endswith("_published")})
    module = DecoderLM.from_config(whole)
    assert len(module.layers) == 40
    assert [i for i, layer in enumerate(module.layers)
            if layer.sublayers[0].kind == "gqa"] == [5, 15, 25, 35]
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))["params"]
    flat = {k: tuple(v.shape) for k, v in _flat(shapes).items()}
    assert flat["embed_tokens/embedding"] == (100352, 2048)
    assert flat["layers_0/mixer/in_proj/kernel"] == (2048, 4096 + 4352 + 64)
    assert flat["layers_5/self_attn/k_proj/kernel"] == (2048, 512)
    layer = (lambda i: sum(int(np.prod(s)) for k, s in flat.items()
                           if k.startswith(f"layers_{i}/")))
    assert (layer(0), layer(5)) == (76_182_976, 60_821_504)
    assert _leaves(shapes) == 3_191_396_096
