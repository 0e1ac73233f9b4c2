"""Plain reference of NVIDIA-Nemotron-3-Super (the ``nemotron_h`` family: a
block is ONE mixer behind one norm, by ``hybrid_override_pattern`` a Mamba-2
mixer, plain grouped-query attention or a latent-space expert layer; an MTP
module built from its own pattern) as one rank of its deployment holds it:
forward, loss, gradients and the first AdamW steps in float32 ``jax.numpy`` at
the highest matmul precision. No kernel, no chunked scan, no sorting trick, no
import of the program.

Equations (ISSUE 41; the keys are ``config.json``'s, what they leave open is
the family's public modelling code and reports, listed under ``assumed`` in
the configuration's file):

* model: ``x = Emb(ids)``; the blocks ``x = x + Mixer_l(RMSNorm_l(x))``;
  RMSNorm; an untied head;
* ``M``: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC))``, the causal
  depthwise convolution written as the sum over its taps; ``[x' | B | C] =
  xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the state by
  the recurrence as it is written, a position at a time (``lax.scan`` over
  t): ``S_t = exp(dt_t A) S_{t-1} + dt_t x'_t B_t^T``, ``y_t = S_t C_t + D
  x'_t``, a head reading its group's B and C, the state running over the
  whole packed sequence; ``y = RMSNorm_grouped(y * silu(z))`` over each
  group's channels, times a weight; ``y W_out``;
* ``*``: ``q = x W_q`` (heads x d), ``k, v`` (kv heads x d), query head i
  reading kv head ``i // (heads / kv heads)`` (k and v repeated by indexing),
  ``softmax_causal(q k^T / sqrt(d)) v W_o``; no bias, norm, gate or position
  encoding. The scores are materialised, a head and a block of query rows at
  a time, the mask written out;
* ``E``: ``s = sigmoid(x W_r)`` over all experts from the hidden x; the
  chosen are the top-k of ``s + b``; ``gate = s[chosen] / sum(s[chosen]) *
  routed_scaling_factor``; ``u = x W_down_latent``; ``r = sum_i gate_i W2_i
  relu(W1_i u)^2`` over the chosen experts THAT ARE HELD HERE (every held
  expert applied to every token and weighted by its gate); ``r W_up_latent +
  W2_s relu(W1_s x)^2``. After a step ``b_i += gamma * sign(mean load -
  load_i)``;
* MTP: ``h' = W_eh [RMSNorm(h) | RMSNorm(Emb(tok_{t+1}))]``, the blocks of
  ``mtp_hybrid_override_pattern``, a norm, the main head, predicting
  ``tok_{t+2}``; its last position is fed token 0 and left out of the loss;
* loss: per sequence the mean over labelled positions of the main head's
  cross-entropy plus ``lambda`` times the MTP head's (a block of positions'
  logits at a time); mean over sequences.

The share (``mixer_parallel_size`` t, rank r; ``experts_held`` from
``first_expert``): the reference is given the same one. Its Mamba-2 mixers
have ``mamba_num_heads / t`` heads and ``n_groups / t`` groups, its attention
``num_attention_heads / t`` query heads and ``max(num_key_value_heads / t,
1)`` kv heads, and the partial sums of their output projections go on to the
next layer as they are, like the held experts' part.

Departures from the published description, each for memory alone: the
recurrence's ``lax.scan`` is nested (blocks of ``ROWS`` positions, each
rebuilt in the backward pass: 8192 kept states of a layer would be 4.3 GB);
blocks, heads and held experts are rebuilt likewise.

Parameters are a flat dict ``name -> array`` whose names are the program's
tree paths joined by "/" (``layers_1/mixer/in_proj/kernel``).

``quant`` is the control's hook, as in ``reference/nn.py``: applied to both
operands and to the result of every matrix product, and, the scan having no
matrix product here, to ``x'``, ``B`` and ``C`` entering it and to ``y``
leaving it. The faults the readings plant ride in the configuration:
``reference_fault`` (``state_reset_at_chunks``: the state set to zero where a
chunk of ``chunk_size`` positions begins, what a chunked scan that loses its
carry computes; ``relu_not_squared``: every expert's activation a plain
ReLU) and ``reference_label_positions`` (only a sequence's first positions
carry a label: what half of a one-sequence batch is).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what is no model's own: a product through the control's hook, RMSNorm, the
# router, the recipe's rate, the host-side norms of a tree's leaves
from .joyai_llm_flash import (_mm, diff_norms, leaf_norms,  # noqa: F401
                              learning_rate, rms_norm, route)
from .nn import Quant

ROWS = 2048          # query rows, and head positions, worked on at a time
SCAN_ROWS = 128      # positions of the recurrence kept between rebuilds
FAULTS = ("state_reset_at_chunks", "relu_not_squared")


# --- shapes and seeded weights -------------------------------------------------

def _sizes(cfg: dict) -> dict:
    e = int(cfg["n_routed_experts"])
    t = int(cfg.get("mixer_parallel_size", 1))
    layers = int(cfg["num_hidden_layers"])
    hidden = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    return dict(
        v=int(cfg["vocab_size"]), h=hidden, heads=heads // t,
        kv=max(int(cfg["num_key_value_heads"]) // t, 1),
        d=int(cfg.get("head_dim", hidden // heads)),
        m_heads=int(cfg["mamba_num_heads"]) // t,
        m_dim=int(cfg["mamba_head_dim"]), groups=int(cfg["n_groups"]) // t,
        state=int(cfg["ssm_state_size"]), taps=int(cfg["conv_kernel"]),
        chunk=int(cfg["chunk_size"]),
        f=int(cfg["moe_intermediate_size"]),
        latent=int(cfg["moe_latent_size"]),
        shared=int(cfg["moe_shared_expert_intermediate_size"])
        if cfg.get("n_shared_experts", 0) else 0,
        e=e, held=int(cfg.get("experts_held", e)),
        first=int(cfg.get("first_expert", 0)),
        k=int(cfg["num_experts_per_tok"]), layers=layers,
        kinds=tuple(cfg["hybrid_override_pattern"])[:layers],
        mtp_kinds=tuple(cfg.get("mtp_hybrid_override_pattern", ""))
        if mtp else (),
        eps=float(cfg.get("layer_norm_epsilon", 1e-5)))


def block_names(cfg: dict) -> List[Tuple[str, str]]:
    """``(block's name, its mixer's letter)`` in the order the model applies
    them, the MTP module's blocks last."""
    z = _sizes(cfg)
    return [(f"layers_{i}", k) for i, k in enumerate(z["kinds"])] + \
        [(f"mtp_layers_{i}", k) for i, k in enumerate(z["mtp_kinds"])]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = _sizes(cfg)
    inner = z["m_heads"] * z["m_dim"]
    conv_dim = inner + 2 * z["groups"] * z["state"]
    s: Dict[str, Tuple[int, ...]] = {
        "embed_tokens/embedding": (z["v"], z["h"]),
        "lm_head": (z["h"], z["v"]),
        "norm/weight": (z["h"],)}
    for name, kind in block_names(cfg):
        s[f"{name}/norm/weight"] = (z["h"],)
        if kind == "M":
            m = f"{name}/mixer"
            s[f"{m}/in_proj/kernel"] = (z["h"], inner + conv_dim
                                        + z["m_heads"])
            s[f"{m}/conv1d_weight"] = (z["taps"], conv_dim)
            s[f"{m}/conv1d_bias"] = (conv_dim,)
            s[f"{m}/A_log"] = (z["m_heads"],)
            s[f"{m}/dt_bias"] = (z["m_heads"],)
            s[f"{m}/D"] = (z["m_heads"],)
            s[f"{m}/norm_weight"] = (inner,)
            s[f"{m}/out_proj/kernel"] = (inner, z["h"])
        elif kind == "*":
            a = f"{name}/self_attn"
            s[f"{a}/q_proj/kernel"] = (z["h"], z["heads"] * z["d"])
            s[f"{a}/k_proj/kernel"] = (z["h"], z["kv"] * z["d"])
            s[f"{a}/v_proj/kernel"] = (z["h"], z["kv"] * z["d"])
            s[f"{a}/o_proj/kernel"] = (z["heads"] * z["d"], z["h"])
        else:
            m = f"{name}/mlp"
            s[f"{m}/gate"] = (z["h"], z["e"])
            s[f"{m}/latent_down_proj/kernel"] = (z["h"], z["latent"])
            s[f"{m}/latent_up_proj/kernel"] = (z["latent"], z["h"])
            s[f"{m}/experts_up_proj"] = (z["held"], z["latent"], z["f"])
            s[f"{m}/experts_down_proj"] = (z["held"], z["f"], z["latent"])
            if z["shared"]:
                s[f"{m}/shared_experts/up_proj/kernel"] = (z["h"],
                                                           z["shared"])
                s[f"{m}/shared_experts/down_proj/kernel"] = (z["shared"],
                                                             z["h"])
    if z["mtp_kinds"]:
        s["mtp_hnorm/weight"] = (z["h"],)
        s["mtp_enorm/weight"] = (z["h"],)
        s["mtp_eh_proj/kernel"] = (2 * z["h"], z["h"])
        s["mtp_norm/weight"] = (z["h"],)
    return s


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(v)) for v in param_shapes(cfg).values())


_OUT = ("o_proj/kernel", "out_proj/kernel", "down_proj/kernel",
        "experts_down_proj", "latent_up_proj/kernel")


def make_weights(cfg: dict, seed: int, only: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
    """The benchmark's float32 weights from ``seed``, made on the device
    (``assumed`` in the configuration's file). Matrices are normal with
    standard deviation ``1 / sqrt(fan_in)`` (every projection back to the
    residual stream or the latent space ``out_proj_scale`` times that, the
    router ``router_std / sqrt(hidden)``, the embedding ``embedding_std``);
    norm weights ``1 + 0.1 n``; the convolution's taps normal with ``1 /
    sqrt(taps)``, its bias 0; ``A_log`` the log of a uniform draw in [1, 16];
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    [``time_step_min``, ``time_step_max``]; ``D`` 1. ``only`` makes just
    those leaves (each the same as in the whole tree)."""
    shapes = param_shapes(cfg)
    init = cfg.get("init", {})
    names = sorted(shapes)
    wanted = set(names if only is None else only)
    dt_lo = math.log(float(cfg.get("time_step_min", 1e-3)))
    dt_hi = math.log(float(cfg.get("time_step_max", 1e-1)))

    def leaf(key, name, shape):
        leafname = name.rsplit("/", 1)[-1]
        if leafname == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                              16.0))
        if leafname == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, dt_lo,
                                            dt_hi))
            return dt + jnp.log(-jnp.expm1(-dt))
        if leafname == "D":
            return jnp.ones(shape, jnp.float32)
        if leafname == "conv1d_bias":
            return jnp.zeros(shape, jnp.float32)
        n = jax.random.normal(key, shape, jnp.float32)
        if leafname in ("weight", "norm_weight"):
            return 1.0 + 0.1 * n
        if leafname == "conv1d_weight":
            return n * np.float32(1.0 / math.sqrt(shape[0]))
        if name == "embed_tokens/embedding":
            return n * np.float32(init.get("embedding_std", 1.0))
        if leafname == "gate":
            return n * np.float32(init.get("router_std", 1.0)
                                  / math.sqrt(shape[0]))
        scale = init.get("out_proj_scale", 1.0) if name.endswith(_OUT) \
            else 1.0
        return n * np.float32(scale / math.sqrt(shape[-2]))

    def build(key):
        return {name: leaf(jax.random.fold_in(key, i), name, shapes[name])
                for i, name in enumerate(names) if name in wanted}

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


# --- the layers ----------------------------------------------------------------

def _q(x, quant: Quant):
    return x if quant is None else quant(x)


def mamba2(cfg: dict, p: Dict, m: str, x, quant: Quant):
    """x: (seq, hidden) of one sequence."""
    z = _sizes(cfg)
    s = x.shape[0]
    h, pd, g, n, taps = (z["m_heads"], z["m_dim"], z["groups"], z["state"],
                         z["taps"])
    inner, r = h * pd, h // g
    reset = z["chunk"] if cfg.get("reference_fault") == \
        "state_reset_at_chunks" else 0
    zxbcdt = _mm(x, p[f"{m}/in_proj/kernel"], quant)
    gate, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], -1)
    # the convolution as the sum over its taps: tap k reads t - (taps-1) + k
    w = p[f"{m}/conv1d_weight"]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = p[f"{m}/conv1d_bias"] + sum(padded[k:k + s] * w[k]
                                       for k in range(taps))
    xs, bm, cm = jnp.split(jax.nn.silu(conv), [inner, inner + g * n], -1)
    dt = jax.nn.softplus(dt + p[f"{m}/dt_bias"]).reshape(s, g, r)
    a = -jnp.exp(p[f"{m}/A_log"]).reshape(g, r)
    xs = _q(xs, quant).reshape(s, g, r, pd)
    bm, cm = _q(bm, quant).reshape(s, g, n), _q(cm, quant).reshape(s, g, n)

    def step(state, inp):
        x_t, dt_t, b_t, c_t, t = inp
        if reset:
            state = jnp.where(t % reset == 0, 0.0, state)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.sum(state * c_t[:, None, None, :], -1)

    rows = min(SCAN_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are no whole number of blocks of "
                         f"{rows}")

    def some_positions(state, block):
        return lax.scan(step, state, block)

    blocked = jax.tree.map(
        lambda t: t.reshape((s // rows, rows) + t.shape[1:]),
        (xs, dt, bm, cm, jnp.arange(s)))
    _, y = lax.scan(jax.checkpoint(some_positions),
                    jnp.zeros((g, r, pd, n), jnp.float32), blocked)
    y = y.reshape(s, g, r, pd) + p[f"{m}/D"].reshape(g, r, 1) * xs
    y = _q(y, quant).reshape(s, g, inner // g) * \
        jax.nn.silu(gate).reshape(s, g, inner // g)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + z["eps"])
    return _mm(y.reshape(s, inner) * p[f"{m}/norm_weight"],
               p[f"{m}/out_proj/kernel"], quant)


def attention(cfg: dict, p: Dict, a: str, x, quant: Quant):
    """x: (seq, hidden) of one sequence."""
    z = _sizes(cfg)
    s, d = x.shape[0], z["d"]
    q = _mm(x, p[f"{a}/q_proj/kernel"], quant).reshape(s, z["heads"], d)
    k = _mm(x, p[f"{a}/k_proj/kernel"], quant).reshape(s, z["kv"], d)
    v = _mm(x, p[f"{a}/v_proj/kernel"], quant).reshape(s, z["kv"], d)
    # each query head's key/value head, repeated by indexing
    shared = jnp.arange(z["heads"]) // (z["heads"] // z["kv"])
    k, v = k[:, shared], v[:, shared]
    rows = min(ROWS, s)
    j = jnp.arange(s)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv                     # (s, d) each

        def some_rows(args):
            qb, t0 = args                    # (rows, d), the first row's t
            t = t0 + jnp.arange(rows)[:, None]
            scores = _mm(qb, kh.T, quant) / math.sqrt(d)
            scores = jnp.where(t >= j, scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), vh, quant)

        out = lax.map(jax.checkpoint(some_rows),
                      (qh.reshape(s // rows, rows, d),
                       jnp.arange(0, s, rows)))
        return out.reshape(s, d)

    # a head at a time, recomputed in the backward pass
    out = lax.map(jax.checkpoint(one_head),
                  tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
    out = jnp.swapaxes(out, 0, 1).reshape(s, z["heads"] * d)
    return _mm(out, p[f"{a}/o_proj/kernel"], quant)


def relu2_mlp(cfg: dict, x, up, down, quant: Quant):
    hidden = jax.nn.relu(_mm(x, up, quant))
    if cfg.get("reference_fault") != "relu_not_squared":
        hidden = jnp.square(hidden)
    return _mm(hidden, down, quant)


def experts_part(cfg: dict, p: Dict, m: str, u, idx, gates, quant: Quant,
                 first: Optional[int] = None, held: Optional[int] = None):
    """The routed part, in the latent space, that the experts ``first ..
    first + held - 1`` give (by default the configuration's share): every
    held expert in turn applied to every token's latent row ``u`` and
    weighted by the token's gate for it, which is 0 where the token did not
    choose it. ``p``'s stacks hold those experts in that order."""
    z = _sizes(cfg)
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    w1, w2 = p[f"{m}/experts_up_proj"], p[f"{m}/experts_down_proj"]

    def one_expert(y, e):
        w = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        return y + relu2_mlp(cfg, u, w1[e], w2[e], quant) * w[:, None], None

    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                    jnp.arange(held))
    return y


def expert_layer(cfg: dict, p: Dict, m: str, x, bias, quant: Quant):
    """x: (tokens, hidden). Returns the layer's output and the router's
    choices (for the bias update and for the comparison of choices)."""
    idx, gates = route(cfg, x, p[f"{m}/gate"], bias)
    u = _mm(x, p[f"{m}/latent_down_proj/kernel"], quant)
    y = _mm(experts_part(cfg, p, m, u, idx, gates, quant),
            p[f"{m}/latent_up_proj/kernel"], quant)
    if _sizes(cfg)["shared"]:
        s = f"{m}/shared_experts"
        y = y + relu2_mlp(cfg, x, p[f"{s}/up_proj/kernel"],
                          p[f"{s}/down_proj/kernel"], quant)
    return y, idx


def block(cfg: dict, p: Dict, name: str, kind: str, x, bias, quant: Quant):
    h = rms_norm(x, p[f"{name}/norm/weight"], _sizes(cfg)["eps"])
    if kind == "M":
        return x + mamba2(cfg, p, f"{name}/mixer", h, quant), None
    if kind == "*":
        return x + attention(cfg, p, f"{name}/self_attn", h, quant), None
    y, idx = expert_layer(cfg, p, f"{name}/mlp", h, bias, quant)
    return x + y, idx


def hidden_states(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """One sequence: ids (seq,) -> ``(the final norm's output (seq, hidden),
    the MTP module's or None, choices)``; ``biases`` maps an expert block's
    name to its correction bias (absent: zero); ``choices`` maps it to the
    experts chosen."""
    z = _sizes(cfg)
    ids = ids.astype(jnp.int32)

    def run(name, kind, x):
        fn = (lambda pp, xx, bb: block(cfg, pp, name, kind, xx, bb, quant))
        if remat:
            fn = jax.checkpoint(fn)
        return fn(p, x, biases.get(name))

    choices = {}
    names = block_names(cfg)
    x = p["embed_tokens/embedding"][ids]
    for name, kind in names[:z["layers"]]:
        x, idx = run(name, kind, x)
        if kind == "E":
            choices[name] = idx
    out = rms_norm(x, p["norm/weight"], z["eps"])
    if not z["mtp_kinds"]:
        return out, None, choices
    nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
    merged = jnp.concatenate(
        [rms_norm(x, p["mtp_hnorm/weight"], z["eps"]),
         rms_norm(p["embed_tokens/embedding"][nxt], p["mtp_enorm/weight"],
                  z["eps"])], axis=-1)
    h = _mm(merged, p["mtp_eh_proj/kernel"], quant)
    for name, kind in names[z["layers"]:]:
        h, idx = run(name, kind, h)
        if kind == "E":
            choices[name] = idx
    return out, rms_norm(h, p["mtp_norm/weight"], z["eps"]), choices


def forward(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
            remat: bool = False):
    """``(logits (seq, vocab), the MTP head's or None, choices)`` of one
    sequence."""
    h, h_mtp, choices = hidden_states(cfg, p, biases, ids, quant, remat)
    return (_mm(h, p["lm_head"], quant),
            None if h_mtp is None else _mm(h_mtp, p["lm_head"], quant),
            choices)


def _head_nll(cfg, p, h, ids, shift: int, quant: Quant):
    """The mean over the labelled positions (all that have a token ``shift``
    ahead; with ``reference_label_positions`` the first so many) of ``-log
    softmax(h[t] W_head)[ids[t + shift]]``, a block of positions' logits at
    a time."""
    s = ids.shape[0]
    labelled = min(int(cfg.get("reference_label_positions", s)), s - shift)
    labels = jnp.concatenate([ids[shift:], jnp.zeros((shift,), jnp.int32)])
    weight = (jnp.arange(s) < labelled).astype(jnp.float32)
    rows = min(ROWS, s)

    def some_positions(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(_mm(hb, p["lm_head"], quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0] * wb)

    parts = lax.map(jax.checkpoint(some_positions),
                    (h.reshape(s // rows, rows, -1),
                     labels.reshape(s // rows, rows),
                     weight.reshape(s // rows, rows)))
    return jnp.sum(parts) / labelled


def sequence_loss(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """``(loss, (main CE, MTP CE, choices))`` of one sequence."""
    ids = ids.astype(jnp.int32)
    h, h_mtp, choices = hidden_states(cfg, p, biases, ids, quant, remat)
    main = _head_nll(cfg, p, h, ids, 1, quant)
    if h_mtp is None:
        return main, (main, jnp.float32(0.0), choices)
    mtp = _head_nll(cfg, p, h_mtp, ids, 2, quant)
    return main + float(cfg.get("mtp_loss_weight", 0.3)) * mtp, \
        (main, mtp, choices)


# --- the first training steps --------------------------------------------------

def first_steps(cfg: dict, weights: Dict, batches: Sequence,
                quant: Quant = None, rows: Optional[int] = None,
                drop_mtp: bool = False) -> Dict:
    """``len(batches)`` AdamW steps (decoupled weight decay on every leaf,
    gradients clipped by their global norm) from ``weights`` (which the
    steps consume: the caller keeps no other reference), each batch a
    host array of ids (sequences, seq), one sequence at a time with the
    gradients summed. Returns what `correct` compares: each step's loss and
    its two heads' cross-entropies, the norm of every leaf of the first
    (clipped) gradient, the norm of every leaf's change after the last step
    and, kept on the device, those two trees and the first step's choices.

    ``rows`` and ``drop_mtp`` are the token driver's faults (only the first
    ``rows`` sequences of a batch; the MTP head's loss left out); this
    model's ride in ``cfg`` (the module's docstring)."""
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]
    gamma = float(cfg.get("bias_update_rate", 1e-3))
    n_experts = _sizes(cfg)["e"]
    moe_blocks = [n for n, kind in block_names(cfg) if kind == "E"]
    run_cfg = dict(cfg, mtp_loss_weight=0.0) if drop_mtp else cfg

    def seq_grad(params, biases, ids):
        (loss, (main, mtp, choices)), g = jax.value_and_grad(
            lambda p: sequence_loss(run_cfg, p, biases, ids, quant,
                                    remat=True), has_aux=True)(params)
        load = {k: jnp.bincount(v.reshape(-1), length=n_experts)
                for k, v in choices.items()}
        return g, loss, main, mtp, load, choices

    jgrad = jax.jit(seq_grad)

    def update(params, mu, nu, gsum, n_seq, step, lr):
        g = {k: v / n_seq for k, v in gsum.items()}
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
        g = {k: v * scale for k, v in g.items()}
        t = step + 1
        mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * nu[k] + (1 - b2) * jnp.square(g[k]) for k in g}
        new = {}
        for k in g:
            m_hat = mu[k] / (1 - b1 ** t)
            v_hat = nu[k] / (1 - b2 ** t)
            new[k] = params[k] - lr * (m_hat / (jnp.sqrt(v_hat) + eps)
                                       + wd * params[k])
        return new, mu, nu, g

    jupdate = jax.jit(update, donate_argnums=(0, 1, 2, 3))
    jadd = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                   donate_argnums=(0,))
    params = weights
    # Adam's second moment waits on the host between updates: with it, the
    # parameters, the first moment and a sequence's gradient, a chip's
    # memory would not hold a sequence's activations too
    mu = nu = None
    biases = {k: jnp.zeros((n_experts,), jnp.float32) for k in moe_blocks}
    losses, main_losses, mtp_losses = [], [], []
    grad1 = choices1 = None
    for step, ids in enumerate(batches):
        ids = np.asarray(ids)
        if rows is not None:
            ids = ids[:rows]
        gsum = None
        tot = {k: 0.0 for k in ("loss", "main", "mtp")}
        load = {k: jnp.zeros((n_experts,), jnp.int32) for k in moe_blocks}
        kept = []
        for seq in ids:
            g, loss, main, mtp, ld, ch = jgrad(params, biases,
                                               jnp.asarray(seq))
            gsum = g if gsum is None else jadd(gsum, g)
            del g
            tot["loss"] += float(loss)
            tot["main"] += float(main)
            tot["mtp"] += float(mtp)
            load = {k: load[k] + ld[k] for k in load}
            if step == 0:
                kept.append(ch)
        n_seq = len(ids)
        losses.append(tot["loss"] / n_seq)
        main_losses.append(tot["main"] / n_seq)
        mtp_losses.append(tot["mtp"] / n_seq)
        zeros = (lambda: jax.tree.map(jnp.zeros_like, params))
        params, mu, nu, g = jupdate(
            params, zeros() if mu is None else mu,
            zeros() if nu is None else jax.device_put(nu), gsum,
            jnp.float32(n_seq), step,
            jnp.float32(learning_rate(opt, step)))
        del gsum
        biases = {k: biases[k] + gamma * jnp.sign(
            jnp.mean(load[k].astype(jnp.float32))
            - load[k].astype(jnp.float32)) for k in biases}
        if step == 0:
            grad1 = jax.device_get(g)
            choices1 = {k: np.stack([np.asarray(c[k]) for c in kept])
                        for k in moe_blocks}
        del g
        if step + 1 < len(batches):
            nu = jax.device_get(nu)
    del mu, nu
    return {"losses": losses, "main_losses": main_losses,
            "mtp_losses": mtp_losses, "grad1_norm": leaf_norms(grad1),
            "grad1": grad1, "params": params, "choices1": choices1,
            "biases": jax.device_get(biases)}


def change_since_start(cfg: dict, seed: int, params: Dict) -> Dict:
    """``params - make_weights(cfg, seed)`` fetched to the host, a block's
    leaves at a time: the start's weights are made again and never all
    beside the parameters."""
    groups: Dict[str, List[str]] = {}
    for name in params:
        groups.setdefault(name.split("/")[0], []).append(name)
    sub = jax.jit(lambda a, b: {k: a[k].astype(jnp.float32) - b[k]
                                for k in b})
    out = {}
    for names in groups.values():
        start = make_weights(cfg, seed, only=names)
        out.update(jax.device_get(sub({k: params[k] for k in names}, start)))
    return out
