"""The decoder language model built from the ``sdar_moe`` family's keys
(grouped-query attention with q/k norms and RoPE on every layer, a
softmax-routed expert layer of which one rank holds a share, trained as a
block-diffusion LM over ``[x_t | x_0]``) against the benchmark's plain float32
reference, at small widths on the CPU; the noise the step draws; the eight
shares of an expert layer; the configuration's parameter count; the model
trained through ``TPUEstimator.fit`` on arrays."""

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

from reference import sdar_moe as ref                           # noqa: E402
from harness import spec, work_bd                               # noqa: E402

from analytics_zoo_tpu.parallel.expert_parallel import (        # noqa: E402
    held_experts_ffn, route_softmax_topk)
from analytics_zoo_tpu.pipeline.api.keras.layers.decoder_lm import (  # noqa: E402
    MODEL_TYPES, NOISE_STATE, DecoderLM, block_diffusion_loss,
    block_diffusion_noise, moe_counters)
from test_attention import equations, pallas_kernels            # noqa: E402
from test_decoder_lm import _flat, _tree, sublayer_sizes        # noqa: E402

CFG = dict(
    model_type="sdar_moe", vocab_size=96, hidden_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=48, moe_intermediate_size=16, num_experts=16,
    experts_held=4, first_expert=4, num_experts_per_tok=4,
    num_hidden_layers=3, rope_theta=1e6, rms_norm_eps=1e-6,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    block_length=4, noise_eps=1e-3, noise_seed=2_147_483_646,
    compute_dtype="float32",
    init=dict(embedding_std=1.0, out_proj_scale=0.5, router_std=1.0))
SEQ = 32


@pytest.fixture(scope="module")
def sides():
    """Program and reference on the same seeded weights, ids and noise (the
    program's first training step draws step 0's): logits, the loss and
    every leaf's gradient."""
    model = DecoderLM.from_config(CFG)
    ids = np.random.RandomState(0).randint(0, 96, (2, SEQ)).astype(np.uint16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(ids[:1]))
    weights = ref.make_weights(CFG, 7)
    extra = {k: v for k, v in variables.items() if k != "params"}

    def loss_of(p):
        preds, new = model.apply({"params": p, **extra}, jnp.asarray(ids),
                                 train=True, mutable=list(extra))
        return jnp.mean(block_diffusion_loss(jnp.asarray(ids), preds)), \
            (preds, new)

    (loss, (preds, new)), grads = jax.jit(jax.value_and_grad(
        loss_of, has_aux=True))(_tree(weights))
    masked, prob = ref.noise(CFG, 0, len(ids), SEQ)
    ref_grad = jax.jit(jax.value_and_grad(
        lambda p, seq, m, pr: ref.sequence_loss(CFG, p, seq, m, pr),
        has_aux=True))
    ref_forward = jax.jit(lambda p, seq, m: ref.forward(CFG, p, seq, m))
    ref_losses, ref_grads, ref_logits = [], None, []
    for b, seq in enumerate(ids):
        (l, _), g = ref_grad(weights, jnp.asarray(seq), masked[b], prob[b])
        ref_losses.append(float(l))
        ref_grads = g if ref_grads is None else jax.tree.map(
            jnp.add, ref_grads, g)
        ref_logits.append(ref_forward(weights, jnp.asarray(seq), masked[b])[0])
    return dict(model=model, variables=variables, weights=weights, ids=ids,
                loss_of=loss_of, loss=float(loss), preds=preds, new=new,
                grads=_flat(grads), masked=masked, prob=prob,
                ref_loss=float(np.mean(ref_losses)),
                ref_grads={k: v / len(ids) for k, v in ref_grads.items()},
                ref_logits=ref_logits)


def test_program_tree_is_the_references(sides):
    shapes = {k: tuple(v.shape)
              for k, v in _flat(sides["variables"]["params"]).items()}
    assert shapes == {k: tuple(v) for k, v in ref.param_shapes(CFG).items()}
    # the MASK token's row, an untied head over the slice alone, q and k
    # norms over a head's width, no correction bias to keep
    assert shapes["embed_tokens/embedding"] == (97, 32)
    assert shapes["lm_head"] == (32, 96)
    assert shapes["layers_2/self_attn/k_norm/weight"] == (16,)
    assert set(sides["variables"]) == {"params", "moe_stats", NOISE_STATE}


def test_the_step_draws_the_references_noise_and_counts_itself(sides):
    """The loss's weights are m / p of the reference's draw for step 0, and
    the training step moved the module's own count on by one."""
    weight = np.asarray(sides["preds"][1])
    want = np.asarray(sides["masked"], np.float32) / np.asarray(sides["prob"])
    np.testing.assert_array_equal(weight > 0, np.asarray(sides["masked"]))
    # XLA may divide by multiplying with the reciprocal: an ulp apart
    np.testing.assert_allclose(weight, want, rtol=1e-6)
    assert int(sides["new"][NOISE_STATE]["steps"]) == 1


def test_the_noise_seed_is_the_steps_data_not_its_program():
    """Two seeds lower to one step program, so a run of another seed loads
    the compiled step instead of compiling its own: the seed is the
    ``noise_state``'s, set at init (the step above, which read it from
    there, drew the reference's noise)."""
    ids = jnp.zeros((1, SEQ), jnp.int32)
    texts = []
    for seed in (3, 2_147_483_646):
        model = DecoderLM.from_config(dict(CFG, noise_seed=seed))
        variables = model.init(jax.random.PRNGKey(0), ids)
        assert int(variables[NOISE_STATE]["seed"]) == seed
        state = [k for k in variables if k != "params"]
        step = jax.jit(lambda v, x, m=model: m.apply(v, x, train=True,
                                                    mutable=state))
        texts.append(step.lower(variables, ids).as_text())
    assert texts[0] == texts[1]


def test_logits_match_reference(sides):
    """Within 2e-4 relative (2e-5 absolute): both sides float32 at the
    highest precision, the program's scores summed a flash tile at a time
    and its experts' rows sorted, another order of summation; bfloat16's
    rounding (4e-3) is twenty times that."""
    for b, want in enumerate(sides["ref_logits"]):
        np.testing.assert_allclose(np.asarray(sides["preds"][0][b]),
                                   np.asarray(want), rtol=2e-4, atol=2e-5)


def test_loss_matches_reference(sides):
    """A mean over positions of float32 cross-entropies: 1e-5 relative."""
    assert sides["loss"] == pytest.approx(sides["ref_loss"], rel=1e-5)


def test_every_leafs_gradient_matches_reference(sides):
    """Within 2e-4 of the leaf's largest entry: float32 on both sides at the
    highest precision, the program's scores in the flash kernel's tiles
    (another order of summation) and its experts' rows sorted."""
    assert set(sides["grads"]) == set(sides["ref_grads"])
    for name, want in sides["ref_grads"].items():
        got = np.asarray(sides["grads"][name])
        scale = float(jnp.abs(want).max()) + 1e-12
        assert float(np.abs(got - np.asarray(want)).max()) <= 2e-4 * scale, \
            name


@pytest.mark.parametrize("fault", ref.FAULTS + ("half_labels",))
def test_the_references_faults_are_another_model(sides, fault):
    """The faults the cell's readings plant in the reference are another
    model: the program's logits (or, for half the labels, its loss) fall
    outside the tolerances they match the true reference within, so the
    comparison fails."""
    cfg = dict(CFG, reference_label_positions=SEQ // 2) \
        if fault == "half_labels" else dict(CFG, reference_fault=fault)
    seq, m, pr = (jnp.asarray(sides["ids"][0]), sides["masked"][0],
                  sides["prob"][0])
    if fault == "half_labels":
        faulty, _ = ref.sequence_loss(cfg, sides["weights"], seq, m, pr)
        loss, _ = ref.sequence_loss(CFG, sides["weights"], seq, m, pr)
        assert abs(float(faulty) - float(loss)) > 1e-3 * float(loss)
        return
    logits, _ = ref.forward(cfg, sides["weights"], seq, m)
    got = np.asarray(sides["preds"][0][0])
    gap = np.abs(got - np.asarray(logits))
    assert float((gap - 2e-4 * np.abs(np.asarray(logits))).max()) > 1e-3


def test_each_block_takes_the_block_diffusion_kernels(sides):
    """Two flash kernels a block (forward, fused backward: the remat policy
    keeps the forward's results), at 2 x SEQ positions with k and v at
    their own two heads (after the tile list the grid walks), and the
    block-diffusion call sites counted."""
    import analytics_zoo_tpu.ops.attention as attn
    before = (attn._BD_COMPUTED.value, attn._BD_NEEDED.value)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sides["loss_of"](p)[0]))(
        _tree(sides["weights"])).jaxpr
    flash = sorted(n for n in pallas_kernels(jaxpr) if "flash" in n)
    assert flash == sorted(3 * ["_flash_kernel", "_flash_bwd_fused_kernel"])
    forward = [e for e in equations(jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["jaxpr"].debug_info.func_name == "_flash_kernel"]
    for e in forward:
        walk, q, k = (x.aval.shape for x in e.invars[:3])
        assert len(walk) == 1 and walk[0] % 4 == 0
        assert q == (2 * 4, 2 * SEQ, 16) and k == (2 * 2, 2 * SEQ, 16)
    computed = attn._BD_COMPUTED.value - before[0]
    needed = attn._BD_NEEDED.value - before[1]
    # counted as traced: a block's forward twice (the remat traces it again
    # for the backward pass, whose kernel the policy then leaves out) and
    # its backward once, L^2 + L B entries a head each time
    assert needed == 3 * 3 * 2 * 4 * (SEQ * SEQ + SEQ * 4)
    assert needed <= computed < 3 * needed


def _cells_configuration():
    """The block-diffusion cell's configuration file and the model's keys
    its factory makes of it."""
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b_ep8.json")) as f:
        cfg = json.load(f)
    factory = spec.load_py(os.path.join(BENCH, cfg["factory"]))
    return cfg, factory.model_config(cfg)


def test_the_cells_own_blocks_walk_only_the_masks_tiles():
    """At the cell's own 8192 tokens (16384 positions), 32 query heads on 4
    kv heads of 128, bfloat16, traced on shapes (nothing computed; one
    block, a small vocabulary): the forward's grid walks a head's 80 of the
    256 1024-wide tiles (a noisy q tile's diagonal and up to 8 clean
    tiles), the backward's a head's 288 of the 1024 512-wide tiles, and no
    step more; the kv head's 8 x 16384 x 128 of dQ do not fit
    VMEM at once, so it goes a query head at a time (``fused_by_head``);
    the score entries computed are 1.25 times those the mask keeps in the
    forward, 1.125 in the backward (counted as traced: the forward twice,
    1.21 together)."""
    import analytics_zoo_tpu.ops.attention as attn
    model = DecoderLM.from_config(dict(_cells_configuration()[1],
                                       num_hidden_layers=1, vocab_size=256))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    extra = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                         {k: v for k, v in shapes.items() if k != "params"})
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.uint16)
    before = (attn._BACKWARD_FUSED_BY_HEAD.value, attn._BD_COMPUTED.value,
              attn._BD_NEEDED.value)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, ids: jnp.mean(
        block_diffusion_loss(ids, model.apply({"params": p, **extra},
                                              ids)))))(
        shapes["params"], ids).jaxpr
    assert [n for n in pallas_kernels(jaxpr) if "flash" in n] == \
        ["_flash_kernel", "_flash_bwd_fused_kernel"]
    grids = [tuple(e.params["grid_mapping"].grid) for e in equations(jaxpr)
             if e.primitive.name == "pallas_call"
             and "flash" in e.params["jaxpr"].debug_info.func_name]
    assert grids == [(32, 80), (4, 8, 288)]
    assert attn._BACKWARD_FUSED_BY_HEAD.value == before[0] + 1
    ratio = (attn._BD_COMPUTED.value - before[1]) / (
        attn._BD_NEEDED.value - before[2])
    assert ratio == pytest.approx((2 * 80 * 1024 ** 2 + 288 * 512 ** 2)
                                  / (3 * (8192 ** 2 + 8192 * 4)))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that all 8 ranks give (16 of 128 experts each) are
    the uncut reference's expert layer: the softmax over all 128, its
    top-8, renormalised."""
    rng = np.random.RandomState(3)
    n, d, f, e = 64, 16, 8, 128
    mk = lambda *s: jnp.asarray(rng.randn(*s) * .3, jnp.float32)  # noqa: E731
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    p = {"m/gate": mk(d, e), "m/experts_gate_proj": mk(e, d, f),
         "m/experts_up_proj": mk(e, d, f), "m/experts_down_proj": mk(e, f, d)}
    cfg = dict(CFG, hidden_size=d, moe_intermediate_size=f, num_experts=e,
               experts_held=e, first_expert=0, num_experts_per_tok=8)
    whole, want_idx = ref.expert_layer(cfg, p, "m", x, None)
    idx, gates = route_softmax_topk(x, p["m/gate"], top_k=8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
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
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seed", [7, 2_147_483_660])
def test_the_rank_holds_its_balanced_share_of_the_routed_rows(seed):
    """The seeded weights give the held experts exactly one of the MASK
    row's top-k in every layer (k * held / E = 1), the others from the
    lower half of its ranking, and, each embedding row routed by its own
    logits and weighted by its share of a step, 16 / 128 of the routed
    rows, at the cell's shapes as at the tests': a permutation of the
    draw's columns, the same when the router is made alone."""
    _, mcfg = _cells_configuration()
    logits = jax.random.normal(jax.random.PRNGKey(seed % 1000),
                               (18993, 128))
    order = np.asarray(ref.balanced_share_order(mcfg, logits,
                                                jax.random.PRNGKey(1)))
    assert sorted(order) == list(range(128))
    rank = np.argsort(np.argsort(-np.asarray(logits[-1])))  # 0 is the best
    held = sorted(rank[order[:16]])
    assert held[0] == 0 and held[1] >= 64
    top = np.asarray(jax.lax.top_k(logits, 8)[1])
    share = ref.token_shares(mcfg)
    assert abs(share[-1] - 0.25) < 1e-3 and abs(share.sum() - 1) < 1e-5
    load = np.zeros(128)
    np.add.at(load, top, np.broadcast_to(share[:, None], top.shape))
    assert abs(load[order[:16]].sum() - 1.0) < 0.002     # 8 x 16 / 128
    weights = ref.make_weights(CFG, seed)
    z = ref._sizes(CFG)
    for name in ref.block_names(CFG):
        x = ref.rms_norm(weights["embed_tokens/embedding"][z["v"]],
                         weights[f"{name}/post_attention_layernorm/weight"],
                         CFG["rms_norm_eps"])
        top = np.argsort(-np.asarray(x @ weights[f"{name}/mlp/gate"]))
        here = set(range(z["first"], z["first"] + z["held"]))
        assert len(here & set(top[:z["k"]].tolist())) == 1
    alone = ref.make_weights(CFG, seed, only=["layers_1/mlp/gate"])
    np.testing.assert_array_equal(np.asarray(alone["layers_1/mlp/gate"]),
                                  np.asarray(weights["layers_1/mlp/gate"]))


def test_the_noise_is_fresh_each_step_and_reproducible():
    """A pure function of (seed, step): the same key draws the same mask,
    the next step another; p is one value a block, between eps and 1; over
    many draws about half the positions are masked (E[p] = 0.5 + eps / 2);
    the reference draws the same bits for the same step."""
    key = lambda step: jax.random.fold_in(                     # noqa: E731
        jax.random.PRNGKey(CFG["noise_seed"]), step)
    m0, p0 = block_diffusion_noise(key(0), 2, 4096, 4)
    m0b, _ = block_diffusion_noise(key(0), 2, 4096, 4)
    m1, _ = block_diffusion_noise(key(1), 2, 4096, 4)
    assert bool(jnp.all(m0 == m0b)) and not bool(jnp.all(m0 == m1))
    blocks = np.asarray(p0).reshape(2, -1, 4)
    assert np.all(blocks == blocks[..., :1])
    assert float(p0.min()) >= 1e-3 and float(p0.max()) <= 1.0
    share = np.mean([float(jnp.mean(block_diffusion_noise(
        key(s), 4, 8192, 4)[0])) for s in range(8)])
    assert abs(share - 0.5) < 0.02
    for step in (0, 1, 2):
        want_m, want_p = ref.noise(CFG, step, 2, 4096)
        got_m, got_p = block_diffusion_noise(key(step), 2, 4096, 4)
        assert bool(jnp.all(got_m == want_m)) and bool(jnp.all(
            got_p == want_p))


def test_from_config_reads_the_published_key_names():
    cfg, _ = _cells_configuration()
    catalog = {k: cfg[k] for k in (
        "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act",
        "hidden_size", "intermediate_size", "max_position_embeddings",
        "max_window_layers", "mlp_only_layers", "model_type",
        "moe_intermediate_size", "norm_topk_prob", "num_attention_heads",
        "num_experts_per_tok", "num_key_value_heads", "rms_norm_eps",
        "rope_scaling", "rope_theta", "sliding_window", "tie_word_embeddings",
        "use_sliding_window")}
    model = DecoderLM.from_config(dict(catalog, num_experts=128,
                                       num_hidden_layers=48,
                                       vocab_size=151936))
    assert model.diffusion_block == 4 and model.noise_eps == 1e-3
    sizes = sublayer_sizes(model)
    assert sizes["gqa"] == dict(
        num_heads=32, num_kv_heads=4, head_dim=128, rope_theta=1e6,
        eps=1e-6, gated=False, qk_norm=True, block_diffusion=4)
    assert sizes["experts"]["scoring"] == "softmax"
    assert sizes["experts"]["n_routed_experts"] == 128 == \
        sizes["experts"]["experts_held"]
    assert sizes["experts"]["n_shared_experts"] == 0
    # every block an expert layer behind pre-norms, no MTP module
    assert {tuple((s.kind, s.norm, s.post_norm) for s in layer.sublayers)
            for layer in model.layers} == {(
                ("gqa", "input_layernorm", None),
                ("experts", "post_attention_layernorm", None))}
    assert len(model.layers) == 48 and not model.mtp_layers


@pytest.mark.parametrize("key,value,match", [
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("norm_topk_prob", False, "softmax"),
    ("use_sliding_window", True, "window"),
    ("tie_word_embeddings", True, "untied"),
    ("block_length", 3, "power of two")])
def test_from_config_refuses_what_the_model_does_not_compute(key, value,
                                                             match):
    with pytest.raises(ValueError, match=match):
        DecoderLM.from_config(dict(CFG, **{key: value}))


def test_an_unknown_model_type_is_refused_by_name():
    """A config that names a family this model does not build is refused
    with the six it does, not read as the DeepSeek-V3 family's keys."""
    with pytest.raises(ValueError, match="six families") as err:
        DecoderLM.from_config(dict(CFG, model_type="qwen3_moe"))
    for name in MODEL_TYPES:
        assert name in str(err.value)
    assert len(MODEL_TYPES) == 6


def test_trains_through_the_estimator_on_arrays():
    from analytics_zoo_tpu import init_orca_context
    from analytics_zoo_tpu.orca.learn.estimator import TPUEstimator
    from analytics_zoo_tpu.orca.learn.optimizers import AdamWeightDecay
    from analytics_zoo_tpu.parallel.mesh import create_mesh
    ctx = init_orca_context("local")
    mesh = create_mesh({"dp": 1}, devices=ctx.devices[:1])
    model = DecoderLM.from_config(dict(CFG, compute_dtype="bfloat16"))
    est = TPUEstimator(model, loss=model.loss(),
                       optimizer=AdamWeightDecay(lr=3e-3, weight_decay=0.1,
                                                 beta_2=0.95),
                       mesh=mesh, seed=0)
    ids = np.random.RandomState(6).randint(0, 96, (8, SEQ)).astype(np.uint16)
    stats = est.fit({"x": ids, "y": ids}, epochs=3, batch_size=4,
                    verbose=False)
    losses = [s["train_loss"] for s in stats]
    assert np.all(np.isfinite(losses))
    counters = moe_counters(est.engine.extra_vars)
    assert counters["moe_steps"] == 6 and counters["moe_dropped_rows"] == 0
    extra = jax.device_get(est.engine.extra_vars)
    assert int(extra[NOISE_STATE]["steps"]) == 6
    assert "router_state" not in extra
    est.shutdown()


def test_the_configurations_parameter_count_is_pinned():
    """645,625,344 parameters, 10.33 GB at 16 B a parameter: the
    configuration's cut, counted three ways; every width the catalog row's."""
    cfg, mcfg = _cells_configuration()
    for key, width in (("hidden_size", 2048), ("num_attention_heads", 32),
                       ("num_key_value_heads", 4), ("head_dim", 128),
                       ("intermediate_size", 6144),
                       ("moe_intermediate_size", 768),
                       ("num_experts_per_tok", 8)):
        assert cfg[key] == width
    assert mcfg["num_experts"] == 128 and mcfg["experts_held"] == 16
    assert mcfg["vocab_size"] == 18992 and mcfg["num_hidden_layers"] == 6
    n = ref.param_count(mcfg)
    assert n == 645_625_344 == work_bd.param_count(mcfg)
    assert abs(16 * n - 10.33e9) / 10.33e9 < 0.001
    module = DecoderLM.from_config(mcfg)
    # six expert layers
    assert [[s.kind for s in layer.sublayers] for layer in module.layers] \
        == [["gqa", "experts"]] * 6
    shapes = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.uint16)))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes["params"])) == n
