"""Plain reference of Trinity-Mini (the ``afmoe`` family: gated grouped-query
attention with q/k norms, sliding-window and global layers, sandwich norms, a
sigmoid-routed expert layer with a shared expert) as one rank of an
expert-parallel deployment holds it: forward, loss, gradients and the first
AdamW steps in float32 ``jax.numpy`` at the highest matmul precision. No
kernel, no sorting trick, no import of the program.

Equations (ISSUE 39; the keys are ``config.json``'s, what they leave open is
the family's public modelling code, listed under ``assumed`` in the
configuration's file):

* model: ``x = Emb(ids) sqrt(hidden)`` (``mup_enabled``); the blocks; RMSNorm;
  an untied head;
* block: ``x = x + N_post_attn(Attn(N_in(x)))``; ``x = x + N_post_mlp(FFN(
  N_pre_mlp(x)))``;
* attention: ``q = x W_q`` (heads x d), ``k = x W_k``, ``v = x W_v`` (kv heads
  x d), ``g = x W_g`` (heads x d); RMSNorm over each head's d on q and k; on
  ``sliding_attention`` layers rotate-half RoPE and the mask ``0 <= t - j <
  sliding_window``; ``full_attention`` layers are causal with no position
  encoding; query head i reads kv head ``i // (heads / kv heads)`` (k and v
  repeated by indexing); ``out = (softmax(q k^T / sqrt(d)) v * sigmoid(g))
  W_o``. The scores are materialised, a head and a block of query rows at a
  time, the mask written out;
* expert layer: ``s = sigmoid(x W_r)``; the chosen are the top-k of ``s + b``;
  ``gate = s[chosen] / sum(s[chosen]) * route_scale``; ``y = sum_i gate_i
  F_i(x)`` over the chosen experts THAT ARE HELD HERE (every held expert
  applied to every token and weighted by its gate), plus the shared expert;
  ``F`` a SwiGLU. After a step ``b_i += load_balance_coeff * sign(mean load -
  load_i)``;
* loss: per sequence the mean over labelled positions of the head's
  cross-entropy (a block of positions' logits at a time); mean over
  sequences.

Parameters are a flat dict ``name -> array`` whose names are the program's
tree paths joined by "/" (``layers_1/self_attn/q_proj/kernel``).

``quant`` is the control's hook, as in ``reference/nn.py``: applied to both
operands and to the result of every matrix product. The faults the readings
plant ride in the configuration: ``reference_fault`` (``sliding_as_causal``:
the sliding layers' window left out of the mask; ``rope_on_global``: RoPE on
the global layers too) and ``reference_label_positions`` (only a sequence's
first positions carry a label: what half of a one-sequence batch is).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what is no model's own: a product through the control's hook, RMSNorm, a
# SwiGLU, the recipe's rate, the host-side norms of a tree's leaves
from .joyai_llm_flash import (_mm, diff_norms, leaf_norms,  # noqa: F401
                              learning_rate, rms_norm, swiglu)
from .nn import HIGHEST, Quant

ROWS = 2048          # query rows, and head positions, worked on at a time


# --- shapes and seeded weights -------------------------------------------------

def _sizes(cfg: dict) -> dict:
    e = int(cfg["num_experts"])
    heads = int(cfg["num_attention_heads"])
    layers = int(cfg["num_hidden_layers"])
    return dict(
        v=int(cfg["vocab_size"]), h=int(cfg["hidden_size"]), heads=heads,
        kv=int(cfg.get("num_key_value_heads", heads)),
        d=int(cfg["head_dim"]), ffn=int(cfg["intermediate_size"]),
        f=int(cfg["moe_intermediate_size"]), e=e,
        held=int(cfg.get("experts_held", e)),
        first=int(cfg.get("first_expert", 0)),
        k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg.get("num_shared_experts", 0)), layers=layers,
        dense=int(cfg.get("num_dense_layers", 0)),
        kinds=tuple(cfg["layer_types"])[:layers],
        window=int(cfg["sliding_window"]),
        embed_scale=math.sqrt(int(cfg["hidden_size"]))
        if cfg.get("mup_enabled") else 1.0)


def block_names(cfg: dict) -> List[Tuple[str, bool]]:
    """``(block's name, has experts)`` in the order the model applies them."""
    z = _sizes(cfg)
    return [(f"layers_{i}", i >= z["dense"]) for i in range(z["layers"])]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = _sizes(cfg)
    s: Dict[str, Tuple[int, ...]] = {
        "embed_tokens/embedding": (z["v"], z["h"]),
        "lm_head": (z["h"], z["v"]),
        "norm/weight": (z["h"],)}
    for name, moe in block_names(cfg):
        for n in ("input_layernorm", "post_attention_layernorm",
                  "pre_mlp_layernorm", "post_mlp_layernorm"):
            s[f"{name}/{n}/weight"] = (z["h"],)
        a = f"{name}/self_attn"
        s[f"{a}/q_proj/kernel"] = (z["h"], z["heads"] * z["d"])
        s[f"{a}/k_proj/kernel"] = (z["h"], z["kv"] * z["d"])
        s[f"{a}/v_proj/kernel"] = (z["h"], z["kv"] * z["d"])
        s[f"{a}/gate_proj/kernel"] = (z["h"], z["heads"] * z["d"])
        s[f"{a}/o_proj/kernel"] = (z["heads"] * z["d"], z["h"])
        s[f"{a}/q_norm/weight"] = (z["d"],)
        s[f"{a}/k_norm/weight"] = (z["d"],)
        m = f"{name}/mlp"
        if not moe:
            s[f"{m}/gate_proj/kernel"] = (z["h"], z["ffn"])
            s[f"{m}/up_proj/kernel"] = (z["h"], z["ffn"])
            s[f"{m}/down_proj/kernel"] = (z["ffn"], z["h"])
            continue
        s[f"{m}/gate"] = (z["h"], z["e"])
        s[f"{m}/experts_gate_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_up_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_down_proj"] = (z["held"], z["f"], z["h"])
        if z["shared"]:
            w = z["f"] * z["shared"]
            s[f"{m}/shared_experts/gate_proj/kernel"] = (z["h"], w)
            s[f"{m}/shared_experts/up_proj/kernel"] = (z["h"], w)
            s[f"{m}/shared_experts/down_proj/kernel"] = (w, z["h"])
    return s


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(v)) for v in param_shapes(cfg).values())


def make_weights(cfg: dict, seed: int, only: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
    """The benchmark's float32 weights from ``seed``, made on the device.
    Matrices are normal with the standard deviation the configuration's
    ``init`` gives for their kind (``assumed`` in the configuration's file:
    fan-in scaled; the embedding's so that the SCALED embedding has
    ``embedding_std``); norm weights ``1 + 0.1 n``. ``only`` makes just
    those leaves (each the same as in the whole tree)."""
    shapes = param_shapes(cfg)
    init = cfg.get("init", {})
    names = sorted(shapes)
    wanted = set(names if only is None else only)
    embed_scale = _sizes(cfg)["embed_scale"]

    def std(name, shape):
        if name == "embed_tokens/embedding":
            return init.get("embedding_std", 1.0) / embed_scale
        if name.endswith("/gate"):
            return init.get("router_std", 1.0) / math.sqrt(shape[0])
        fan_in = shape[-2]
        scale = init.get("out_proj_scale", 1.0) if name.endswith(
            ("o_proj/kernel", "down_proj/kernel", "experts_down_proj")) \
            else 1.0
        return scale / math.sqrt(fan_in)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            if name not in wanted:
                continue
            shape = shapes[name]
            n = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (1.0 + 0.1 * n) if name.endswith("/weight") \
                else n * np.float32(std(name, shape))
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


# --- the layers ----------------------------------------------------------------

def rope(x, theta: float):
    """x: (seq, heads, d); the pairs ``(x[i], x[i + d/2])`` rotated by ``pos
    * theta**(-2i/d)`` (rotate-half)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(cfg: dict, p: Dict, a: str, kind: str, x, quant: Quant):
    """x: (seq, hidden) of one sequence; ``kind`` the layer's entry of
    ``layer_types``."""
    z = _sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    fault = cfg.get("reference_fault")
    s, d = x.shape[0], z["d"]
    sliding = kind == "sliding_attention"
    q = _mm(x, p[f"{a}/q_proj/kernel"], quant).reshape(s, z["heads"], d)
    k = _mm(x, p[f"{a}/k_proj/kernel"], quant).reshape(s, z["kv"], d)
    v = _mm(x, p[f"{a}/v_proj/kernel"], quant).reshape(s, z["kv"], d)
    gate = _mm(x, p[f"{a}/gate_proj/kernel"], quant)
    q = rms_norm(q, p[f"{a}/q_norm/weight"], eps)
    k = rms_norm(k, p[f"{a}/k_norm/weight"], eps)
    if sliding or fault == "rope_on_global":
        q, k = rope(q, theta), rope(k, theta)
    # each query head's key/value head, repeated by indexing
    shared = jnp.arange(z["heads"]) // (z["heads"] // z["kv"])
    k, v = k[:, shared], v[:, shared]
    window = z["window"] if sliding and fault != "sliding_as_causal" else s
    rows = min(ROWS, s)
    j = jnp.arange(s)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv                     # (s, d) each

        def some_rows(args):
            qb, t0 = args                    # (rows, d), the first row's t
            t = t0 + jnp.arange(rows)[:, None]
            scores = _mm(qb, kh.T, quant) / math.sqrt(d)
            seen = (t - j >= 0) & (t - j < window)
            scores = jnp.where(seen, scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), vh, quant)

        out = lax.map(jax.checkpoint(some_rows),
                      (qh.reshape(s // rows, rows, d),
                       jnp.arange(0, s, rows)))
        return out.reshape(s, d)

    # a head at a time, recomputed in the backward pass
    out = lax.map(jax.checkpoint(one_head),
                  tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
    out = jnp.swapaxes(out, 0, 1).reshape(s, z["heads"] * d)
    return _mm(out * jax.nn.sigmoid(gate), p[f"{a}/o_proj/kernel"], quant)


def route(cfg: dict, x, router, bias):
    """``(idx (tokens, k), gates (tokens, k))`` over ALL experts."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    _, idx = lax.top_k(scores if bias is None else scores + bias,
                       int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / jnp.sum(chosen, -1, keepdims=True) * float(
        cfg.get("route_scale", 1.0))
    return idx, gates


def experts_part(cfg: dict, p: Dict, m: str, x, idx, gates, quant: Quant,
                 first: Optional[int] = None, held: Optional[int] = None):
    """The routed part of the layer that the experts ``first .. first + held
    - 1`` give (by default the configuration's share): every held expert in
    turn applied to every token and weighted by the token's gate for it,
    which is 0 where the token did not choose it. ``p``'s stacks hold the
    experts ``first .. first + held - 1`` in that order."""
    z = _sizes(cfg)
    first = z["first"] if first is None else first
    held = z["held"] if held is None else held
    wg, wu, wd = (p[f"{m}/experts_gate_proj"], p[f"{m}/experts_up_proj"],
                  p[f"{m}/experts_down_proj"])

    def one_expert(y, e):
        w = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        return y + swiglu(x, wg[e], wu[e], wd[e], quant) * w[:, None], None

    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                    jnp.arange(held))
    return y


def expert_layer(cfg: dict, p: Dict, m: str, x, bias, quant: Quant):
    """x: (tokens, hidden). Returns the layer's output and the router's
    choices (for the bias update and for the comparison of choices)."""
    z = _sizes(cfg)
    idx, gates = route(cfg, x, p[f"{m}/gate"], bias)
    y = experts_part(cfg, p, m, x, idx, gates, quant)
    if z["shared"]:
        s = f"{m}/shared_experts"
        y = y + swiglu(x, p[f"{s}/gate_proj/kernel"], p[f"{s}/up_proj/kernel"],
                       p[f"{s}/down_proj/kernel"], quant)
    return y, idx


def block(cfg: dict, p: Dict, name: str, kind: str, moe: bool, x, bias,
          quant: Quant):
    eps = float(cfg["rms_norm_eps"])

    def norm(which, t):
        return rms_norm(t, p[f"{name}/{which}/weight"], eps)

    x = x + norm("post_attention_layernorm", attention(
        cfg, p, f"{name}/self_attn", kind, norm("input_layernorm", x), quant))
    h = norm("pre_mlp_layernorm", x)
    m = f"{name}/mlp"
    if moe:
        y, idx = expert_layer(cfg, p, m, h, bias, quant)
    else:
        y, idx = swiglu(h, p[f"{m}/gate_proj/kernel"],
                        p[f"{m}/up_proj/kernel"],
                        p[f"{m}/down_proj/kernel"], quant), None
    return x + norm("post_mlp_layernorm", y), idx


def hidden_states(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """One sequence: ids (seq,) -> ``(the final norm's output (seq, hidden),
    choices)``; ``biases`` maps an expert block's name to its correction
    bias (absent: zero); ``choices`` maps it to the experts chosen."""
    z = _sizes(cfg)
    ids = ids.astype(jnp.int32)

    def run(name, kind, moe, x):
        fn = (lambda pp, xx, bb: block(cfg, pp, name, kind, moe, xx, bb,
                                       quant))
        if remat:
            fn = jax.checkpoint(fn)
        return fn(p, x, biases.get(name))

    choices = {}
    x = p["embed_tokens/embedding"][ids] * np.float32(z["embed_scale"])
    for (name, moe), kind in zip(block_names(cfg), z["kinds"]):
        x, idx = run(name, kind, moe, x)
        if moe:
            choices[name] = idx
    return rms_norm(x, p["norm/weight"], float(cfg["rms_norm_eps"])), choices


def forward(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
            remat: bool = False):
    """``(logits (seq, vocab), choices)`` of one sequence."""
    h, choices = hidden_states(cfg, p, biases, ids, quant, remat)
    return _mm(h, p["lm_head"], quant), choices


def sequence_loss(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """``(loss, choices)`` of one sequence: the mean over its labelled
    positions (all but the last; with ``reference_label_positions`` the
    first so many) of ``-log softmax(logits[t])[ids[t + 1]]``, a block of
    positions' logits at a time."""
    ids = ids.astype(jnp.int32)
    s = ids.shape[0]
    h, choices = hidden_states(cfg, p, biases, ids, quant, remat)
    labelled = int(cfg.get("reference_label_positions", s - 1))
    labels = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
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
    return jnp.sum(parts) / labelled, choices


# --- the first training steps --------------------------------------------------

def first_steps(cfg: dict, weights: Dict, batches: Sequence,
                quant: Quant = None, rows: Optional[int] = None,
                drop_mtp: bool = False) -> Dict:
    """``len(batches)`` AdamW steps (decoupled weight decay on every leaf,
    gradients clipped by their global norm) from ``weights`` (which the
    steps consume: the caller keeps no other reference), each batch a
    host array of ids (sequences, seq), one sequence at a time with the
    gradients summed. Returns what `correct` compares: each step's loss,
    the norm of every leaf of the first (clipped) gradient, the norm of
    every leaf's change after the last step and, kept on the device, those
    two trees and the first step's choices.

    ``rows`` plants a fault: only the first ``rows`` sequences of a batch
    (the token driver's signature; ``drop_mtp`` is its other fault, and
    this model has no such head). The faults of this model ride in ``cfg``
    (the module's docstring)."""
    if drop_mtp:
        raise ValueError("no multi-token-prediction head to leave out")
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]
    gamma = float(cfg.get("load_balance_coeff", 1e-3))
    n_experts = _sizes(cfg)["e"]
    moe_blocks = [n for n, moe in block_names(cfg) if moe]

    def seq_grad(params, biases, ids):
        (loss, choices), g = jax.value_and_grad(
            lambda p: sequence_loss(cfg, p, biases, ids, quant, remat=True),
            has_aux=True)(params)
        load = {k: jnp.bincount(v.reshape(-1), length=n_experts)
                for k, v in choices.items()}
        return g, loss, load, choices

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
    losses = []
    grad1 = choices1 = None
    for step, ids in enumerate(batches):
        ids = np.asarray(ids)
        if rows is not None:
            ids = ids[:rows]
        gsum, total = None, 0.0
        load = {k: jnp.zeros((n_experts,), jnp.int32) for k in moe_blocks}
        kept = []
        for seq in ids:
            g, loss, ld, ch = jgrad(params, biases, jnp.asarray(seq))
            gsum = g if gsum is None else jadd(gsum, g)
            del g
            total += float(loss)
            load = {k: load[k] + ld[k] for k in load}
            if step == 0:
                kept.append(ch)
        n_seq = len(ids)
        losses.append(total / n_seq)
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
    return {"losses": losses, "grad1_norm": leaf_norms(grad1),
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
