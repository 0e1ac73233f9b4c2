"""Plain reference of JoyAI-LLM-Flash (a DeepSeek-V3-family decoder: MLA, a
sigmoid-routed expert layer with a shared expert, one multi-token-prediction
module) as one rank of an expert-parallel deployment holds it: forward, loss,
gradients and the first AdamW steps in float32 ``jax.numpy`` at the highest
matmul precision. No kernel, no sorting trick, no import of the program.

Equations (ISSUE 35 section A; the family's report for MTP):

* block: ``x = x + MLA(RMSNorm(x))``; ``x = x + FFN(RMSNorm(x))``;
* MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` (heads x (nope | rope));
  ``[c_kv | k_r] = x W_kva``; ``[k_nope | v] = RMSNorm(c_kv) W_kvb``; RoPE
  over adjacent pairs on q's rope part and on ``k_r`` (shared by the heads);
  ``P = softmax_causal(q k^T / sqrt(d_nope + d_rope))``; ``(P v) W_o``. The
  scores are materialised, one sequence at a time;
* expert layer: ``s = sigmoid(x W_r)``; the chosen are the top-k of ``s + b``;
  ``g = s[chosen] / sum(s[chosen]) * scale``; ``y = sum_i g_i F_i(x)`` over
  the chosen experts THAT ARE HELD HERE (a dense gather of each token through
  them), plus the shared expert; ``F`` a SwiGLU. After a step
  ``b_i += gamma * sign(mean load - load_i)``;
* MTP: ``h' = W_eh [RMSNorm(h_t) | RMSNorm(Emb(tok_{t+1}))]``, one more
  block, a norm, the main embedding and head, predicting ``tok_{t+2}``; its
  last position is fed token 0 and left out of the loss;
* loss: per sequence the mean over labelled positions of the main head's
  cross-entropy plus ``lambda`` times the MTP head's; mean over sequences.

Parameters are a flat dict ``name -> array`` whose names are the program's
tree paths joined by "/" (``layers_1/self_attn/q_a_proj/kernel``).

``quant`` is the control's hook, as in ``reference/nn.py``: applied to both
operands and to the result of every matrix product.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .nn import HIGHEST, Quant


# --- shapes and seeded weights -------------------------------------------------

def _sizes(cfg: dict) -> dict:
    held = int(cfg.get("experts_held", cfg["n_routed_experts"]))
    return dict(
        v=int(cfg["vocab_size"]), h=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]), ql=int(cfg["q_lora_rank"]),
        kl=int(cfg["kv_lora_rank"]), dn=int(cfg["qk_nope_head_dim"]),
        dr=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        ffn=int(cfg["intermediate_size"]),
        f=int(cfg["moe_intermediate_size"]),
        e=int(cfg["n_routed_experts"]), held=held,
        first=int(cfg.get("first_expert", 0)),
        k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg.get("n_shared_experts", 0)),
        layers=int(cfg["num_hidden_layers"]),
        dense=int(cfg.get("first_k_dense_replace", 0)),
        mtp=int(cfg.get("num_nextn_predict_layers", 0)))


def block_names(cfg: dict) -> List[Tuple[str, bool]]:
    """``(block's name, has experts)`` in the order the model applies them."""
    z = _sizes(cfg)
    out = [(f"layers_{i}", i >= z["dense"]) for i in range(z["layers"])]
    if z["mtp"]:
        out.append(("mtp_block", True))
    return out


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = _sizes(cfg)
    s: Dict[str, Tuple[int, ...]] = {
        "embed_tokens/embedding": (z["v"], z["h"]),
        "lm_head": (z["h"], z["v"]),
        "norm/weight": (z["h"],)}
    for name, moe in block_names(cfg):
        a = f"{name}/self_attn"
        s[f"{name}/input_layernorm/weight"] = (z["h"],)
        s[f"{name}/post_attention_layernorm/weight"] = (z["h"],)
        s[f"{a}/q_a_proj/kernel"] = (z["h"], z["ql"])
        s[f"{a}/q_a_layernorm/weight"] = (z["ql"],)
        s[f"{a}/q_b_proj/kernel"] = (z["ql"], z["heads"] * (z["dn"] + z["dr"]))
        s[f"{a}/kv_a_proj_with_mqa/kernel"] = (z["h"], z["kl"] + z["dr"])
        s[f"{a}/kv_a_layernorm/weight"] = (z["kl"],)
        s[f"{a}/kv_b_proj/kernel"] = (z["kl"], z["heads"] * (z["dn"] + z["dv"]))
        s[f"{a}/o_proj/kernel"] = (z["heads"] * z["dv"], z["h"])
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
    if z["mtp"]:
        s["mtp_hnorm/weight"] = (z["h"],)
        s["mtp_enorm/weight"] = (z["h"],)
        s["mtp_eh_proj/kernel"] = (2 * z["h"], z["h"])
        s["mtp_norm/weight"] = (z["h"],)
    return s


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(v)) for v in param_shapes(cfg).values())


def make_weights(cfg: dict, seed: int, only: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
    """The benchmark's float32 weights from ``seed``, made on the device.
    Matrices are normal with the standard deviation the configuration's
    ``init`` gives for their kind (``assumed`` in the configuration's file:
    fan-in scaled, so that every block's output is of the size of its input
    and the logits are of order one); norm weights ``1 + 0.1 n``. ``only``
    makes just those leaves (each the same as in the whole tree)."""
    shapes = param_shapes(cfg)
    init = cfg.get("init", {})
    names = sorted(shapes)
    wanted = set(names if only is None else only)

    def std(name, shape):
        if name == "embed_tokens/embedding":
            return init.get("embedding_std", 1.0)
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

def _mm(x, w, quant: Quant):
    if quant is not None:
        x, w = quant(x), quant(w)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y if quant is None else quant(y)


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x: (seq, heads, d); adjacent pairs rotated by ``pos * theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def mla(cfg: dict, p: Dict, a: str, x, quant: Quant):
    """x: (seq, hidden) of one sequence."""
    z = _sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = x.shape[0]
    c_q = rms_norm(_mm(x, p[f"{a}/q_a_proj/kernel"], quant),
                   p[f"{a}/q_a_layernorm/weight"], eps)
    q = _mm(c_q, p[f"{a}/q_b_proj/kernel"], quant).reshape(
        s, z["heads"], z["dn"] + z["dr"])
    ckv = _mm(x, p[f"{a}/kv_a_proj_with_mqa/kernel"], quant)
    c_kv = rms_norm(ckv[:, :z["kl"]], p[f"{a}/kv_a_layernorm/weight"], eps)
    k_r = rope(ckv[:, z["kl"]:].reshape(s, 1, z["dr"]), theta)
    kv = _mm(c_kv, p[f"{a}/kv_b_proj/kernel"], quant).reshape(
        s, z["heads"], z["dn"] + z["dv"])
    q = jnp.concatenate([q[..., :z["dn"]], rope(q[..., z["dn"]:], theta)], -1)
    k = jnp.concatenate([kv[..., :z["dn"]],
                         jnp.broadcast_to(k_r, (s, z["heads"], z["dr"]))], -1)
    v = kv[..., z["dn"]:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                     # (s, d), (s, d), (s, dv)
        scores = _mm(qh, kh.T, quant) / math.sqrt(z["dn"] + z["dr"])
        scores = jnp.where(causal, scores, -jnp.inf)
        return _mm(jax.nn.softmax(scores, axis=-1), vh, quant)

    # a head at a time (its scores are seq x seq), recomputed in the
    # backward pass
    out = lax.map(jax.checkpoint(one_head),
                  tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v)))
    out = jnp.swapaxes(out, 0, 1).reshape(s, z["heads"] * z["dv"])
    return _mm(out, p[f"{a}/o_proj/kernel"], quant)


def swiglu(x, gate, up, down, quant: Quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(cfg: dict, x, router, bias):
    """``(idx (tokens, k), gates (tokens, k))`` over ALL experts."""
    scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    _, idx = lax.top_k(scores if bias is None else scores + bias,
                       int(cfg["num_experts_per_tok"]))
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / jnp.sum(chosen, -1, keepdims=True) * float(
        cfg.get("routed_scaling_factor", 1.0))
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
        # every token through this expert; its weight is the token's gate
        # for it, 0 where the token did not choose it
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


def block(cfg: dict, p: Dict, name: str, moe: bool, x, bias, quant: Quant):
    eps = float(cfg["rms_norm_eps"])
    x = x + mla(cfg, p, f"{name}/self_attn",
                rms_norm(x, p[f"{name}/input_layernorm/weight"], eps), quant)
    h = rms_norm(x, p[f"{name}/post_attention_layernorm/weight"], eps)
    m = f"{name}/mlp"
    if not moe:
        return x + swiglu(h, p[f"{m}/gate_proj/kernel"],
                          p[f"{m}/up_proj/kernel"],
                          p[f"{m}/down_proj/kernel"], quant), None
    y, idx = expert_layer(cfg, p, m, h, bias, quant)
    return x + y, idx


def forward(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
            remat: bool = False):
    """One sequence: ids (seq,) -> ``(logits, mtp_logits, choices)``;
    ``biases`` maps an expert block's name to its correction bias (absent:
    zero);
    ``choices`` maps it to the experts chosen, (seq, k)."""
    z = _sizes(cfg)
    eps = float(cfg["rms_norm_eps"])
    ids = ids.astype(jnp.int32)

    def run(name, moe, x):
        fn = (lambda pp, xx, bb: block(cfg, pp, name, moe, xx, bb, quant))
        if remat:
            fn = jax.checkpoint(fn)
        return fn(p, x, biases.get(name))

    choices = {}
    x = p["embed_tokens/embedding"][ids]
    for name, moe in block_names(cfg)[:z["layers"]]:
        x, idx = run(name, moe, x)
        if moe:
            choices[name] = idx
    logits = _mm(rms_norm(x, p["norm/weight"], eps), p["lm_head"], quant)
    if not z["mtp"]:
        return logits, None, choices
    nxt = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
    merged = jnp.concatenate(
        [rms_norm(x, p["mtp_hnorm/weight"], eps),
         rms_norm(p["embed_tokens/embedding"][nxt], p["mtp_enorm/weight"],
                  eps)], axis=-1)
    h = _mm(merged, p["mtp_eh_proj/kernel"], quant)
    h, idx = run("mtp_block", True, h)
    choices["mtp_block"] = idx
    mtp_logits = _mm(rms_norm(h, p["mtp_norm/weight"], eps), p["lm_head"],
                     quant)
    return logits, mtp_logits, choices


def _nll(logits, ids, shift: int):
    s = ids.shape[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp[:s - shift], ids[shift:, None], -1)
    return -jnp.sum(picked) / (s - shift)


def sequence_loss(cfg: dict, p: Dict, biases: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """``(loss, (main CE, MTP CE, choices))`` of one sequence."""
    ids = ids.astype(jnp.int32)
    logits, mtp_logits, choices = forward(cfg, p, biases, ids, quant, remat)
    main = _nll(logits, ids, 1)
    if mtp_logits is None:
        return main, (main, jnp.float32(0.0), choices)
    mtp = _nll(mtp_logits, ids, 2)
    return main + float(cfg.get("mtp_loss_weight", 0.3)) * mtp, \
        (main, mtp, choices)


# --- the first training steps --------------------------------------------------

def learning_rate(opt: dict, step: int) -> float:
    """The recipe's rate at a 0-based step: ``peak * (step + 1) / warmup``
    up to the peak, constant after."""
    return opt["peak_lr"] * min(step + 1, opt["warmup_steps"]) \
        / opt["warmup_steps"]


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

    ``rows`` and ``drop_mtp`` plant the faults the readings use: only the
    first ``rows`` sequences of a batch; the MTP head's loss left out."""
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]
    gamma = float(cfg.get("bias_update_rate", 1e-3))
    n_experts = _sizes(cfg)["e"]
    moe_blocks = [n for n, moe in block_names(cfg) if moe]
    lam = 0.0 if drop_mtp else float(cfg.get("mtp_loss_weight", 0.3))
    run_cfg = dict(cfg, mtp_loss_weight=lam)

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
    # parameters, the first moment, the summed gradient and one sequence's
    # gradient, a chip's memory would not hold a sequence's activations too
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


def _norm(a: np.ndarray) -> float:
    a = np.asarray(a, np.float32).reshape(-1)
    chunks = np.array_split(a, max(1, -(-a.size // 2 ** 20)))
    return float(np.sqrt(sum(float(np.dot(c, c)) for c in chunks)))


def leaf_norms(tree: Dict) -> Dict[str, float]:
    """Norms of host arrays, leaf by leaf (float32 products over a million
    elements at a time, summed in float64)."""
    return {k: _norm(v) for k, v in tree.items()}


def diff_norms(a: Dict, b: Dict) -> Dict[str, float]:
    """Per leaf, the norm of the difference of two sides' host trees."""
    return {k: _norm(np.asarray(a[k], np.float32)
                     - np.asarray(b[k], np.float32)) for k in b}
