"""Plain reference of SDAR-30B-A3B (the ``sdar_moe`` family: a Qwen3-MoE-style
decoder with grouped-query attention, q/k norms and rotate-half RoPE on every
layer, every block a softmax-routed expert layer, trained as a block-diffusion
language model) as one rank of an expert-parallel deployment holds it:
forward, loss, gradients and the first AdamW steps in float32 ``jax.numpy``
at the highest matmul precision. No kernel, no sorting trick, no import of
the program.

Equations (the keys are ``config.json``'s, what they leave open,
the block length and the noise schedule among them, is listed under
``assumed`` in the configuration's file). A sequence x_0 of L tokens, blocks
``blk(i) = (i mod L) // B``:

* noise, step n of training: ``key = fold_in(PRNGKey(noise_seed), n)``,
  ``(k_t, k_m) = split(key)``; ``t = U(k_t, (batch, L / B))``, ``p = (1 -
  eps) t + eps`` repeated over each block's B positions, ``m = U(k_m,
  (batch, L)) < p``; ``x_t[i] = MASK if m_i else x_0[i]``, MASK the
  embedding's extra row ``vocab_size``;
* model: ``z = [x_t | x_0]`` (2L ids) at positions ``[0..L-1 | 0..L-1]``;
  ``x = Emb(z)``; the blocks; RMSNorm of the noisy half; an untied head;
* block: ``x = x + Attn(N_in(x))``; ``x = x + MoE(N_post(x))``;
* attention: ``q = x W_q`` (heads x d), ``k = x W_k``, ``v = x W_v`` (kv
  heads x d); RMSNorm over each head's d on q and k, then rotate-half RoPE;
  query head i reads kv head ``i // (heads / kv heads)`` (k and v repeated by
  indexing); ``out = softmax(q k^T / sqrt(d) + M_bd) v W_o`` with M_bd's
  four quadrants written out: noisy query to noisy key where the blocks are
  equal, noisy query to clean key where the key's block is earlier, clean to
  clean where it is not later, clean to noisy never. The scores are
  materialised, a head and a block of query rows at a time;
* expert layer: ``P = softmax(x W_r)`` over ALL experts; the chosen are the
  top-k of P; ``gate = P[chosen] / sum(P[chosen])``; ``y = sum_i gate_i
  F_i(x)`` over the chosen experts THAT ARE HELD HERE (every held expert
  applied to every position and weighted by its gate); ``F`` a SwiGLU;
* loss: per sequence ``(1/L) sum_i (m_i / p_i) CE(logits_i, x_0[i])`` (no
  shift: a masked position scores its own token), a block of positions'
  logits at a time; mean over sequences.

Parameters are a flat dict ``name -> array`` whose names are the program's
tree paths joined by "/" (``layers_1/self_attn/q_proj/kernel``).

``quant`` is the control's hook, as in ``reference/nn.py``. The faults the
readings plant ride in the configuration: ``reference_fault``
(``bd_mask_as_causal``: the 2L positions under a plain causal mask;
``noisy_block_causal``: causal inside each noisy block;
``clean_positions_continue``: the clean copy at positions L..2L-1) and
``reference_label_positions`` (only a sequence's first positions carry a
loss: what half of a one-sequence batch is).
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
FAULTS = ("bd_mask_as_causal", "noisy_block_causal",
          "clean_positions_continue")


# --- shapes and seeded weights -------------------------------------------------

def _sizes(cfg: dict) -> dict:
    e = int(cfg["num_experts"])
    heads = int(cfg["num_attention_heads"])
    return dict(
        v=int(cfg["vocab_size"]), h=int(cfg["hidden_size"]), heads=heads,
        kv=int(cfg.get("num_key_value_heads", heads)),
        d=int(cfg["head_dim"]), f=int(cfg["moe_intermediate_size"]), e=e,
        held=int(cfg.get("experts_held", e)),
        first=int(cfg.get("first_expert", 0)),
        k=int(cfg["num_experts_per_tok"]),
        layers=int(cfg["num_hidden_layers"]),
        block=int(cfg.get("block_length", 4)),
        noise_eps=float(cfg.get("noise_eps", 1e-3)))


def block_names(cfg: dict) -> List[str]:
    return [f"layers_{i}" for i in range(_sizes(cfg)["layers"])]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = _sizes(cfg)
    s: Dict[str, Tuple[int, ...]] = {
        "embed_tokens/embedding": (z["v"] + 1, z["h"]),     # + MASK
        "lm_head": (z["h"], z["v"]),
        "norm/weight": (z["h"],)}
    for name in block_names(cfg):
        for n in ("input_layernorm", "post_attention_layernorm"):
            s[f"{name}/{n}/weight"] = (z["h"],)
        a = f"{name}/self_attn"
        s[f"{a}/q_proj/kernel"] = (z["h"], z["heads"] * z["d"])
        s[f"{a}/k_proj/kernel"] = (z["h"], z["kv"] * z["d"])
        s[f"{a}/v_proj/kernel"] = (z["h"], z["kv"] * z["d"])
        s[f"{a}/o_proj/kernel"] = (z["heads"] * z["d"], z["h"])
        s[f"{a}/q_norm/weight"] = (z["d"],)
        s[f"{a}/k_norm/weight"] = (z["d"],)
        m = f"{name}/mlp"
        s[f"{m}/gate"] = (z["h"], z["e"])
        s[f"{m}/experts_gate_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_up_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_down_proj"] = (z["held"], z["f"], z["h"])
    return s


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(v)) for v in param_shapes(cfg).values())


def make_weights(cfg: dict, seed: int, only: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
    """The benchmark's float32 weights from ``seed``, made on the device.
    Matrices are normal with the standard deviation the configuration's
    ``init`` gives for their kind (``assumed`` in the configuration's file:
    fan-in scaled, the outputs' projections at ``out_proj_scale``, the
    router at ``router_std``, the embedding at ``embedding_std``); norm
    weights ``1 + 0.1 n``. Each router's columns are then relabelled by
    ``balanced_share_order`` (the experts are exchangeable at init, so this
    chooses which of them the rank holds). ``only`` makes just those leaves
    (each the same as in the whole tree)."""
    shapes = param_shapes(cfg)
    init = cfg.get("init", {})
    names = sorted(shapes)
    wanted = set(names if only is None else only)

    def std(name, shape):
        if name == "embed_tokens/embedding":
            return init.get("embedding_std", 1.0)
        if name.endswith("/gate"):
            return init.get("router_std", 1.0) / math.sqrt(shape[0])
        scale = init.get("out_proj_scale", 1.0) if name.endswith(
            ("o_proj/kernel", "experts_down_proj")) else 1.0
        return scale / math.sqrt(shape[-2])

    def leaf(key, name):
        shape = shapes[name]
        n = jax.random.normal(jax.random.fold_in(key, names.index(name)),
                              shape, jnp.float32)
        return (1.0 + 0.1 * n) if name.endswith("/weight") \
            else n * np.float32(std(name, shape))

    def build(key):
        out = {name: leaf(key, name) for name in names if name in wanted}
        emb = leaf(key, "embed_tokens/embedding")
        for i, name in enumerate(block_names(cfg)):
            router = f"{name}/mlp/gate"
            if router not in wanted:
                continue
            x = rms_norm(emb, leaf(
                key, f"{name}/post_attention_layernorm/weight"),
                float(cfg["rms_norm_eps"]))
            out[router] = out[router][:, balanced_share_order(
                cfg, jnp.matmul(x, out[router], precision=HIGHEST),
                jax.random.fold_in(key, len(names) + i))]
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


def token_shares(cfg: dict) -> np.ndarray:
    """Each embedding row's expected share of a step's 2L positions: the
    MASK row (the last) half the noisy copy's mean masked share, E[p] / 2;
    the rest spread over the ids but EOS (0) as the token traffic draws them,
    the id r with weight ``r ** -s`` (``init.token_zipf_s``, 0 if absent:
    every id alike)."""
    z = _sizes(cfg)
    s = float(cfg.get("init", {}).get("token_zipf_s", 0.0))
    mask = ((1.0 - z["noise_eps"]) / 2 + z["noise_eps"]) / 2
    ids = np.arange(1, z["v"], dtype=np.float64) ** -s
    return np.concatenate([[0.0], (1.0 - mask) * ids / ids.sum(),
                           [mask]]).astype(np.float32)


def balanced_share_order(cfg: dict, logits, key):
    """The order of a router's columns that gives the rank its balanced
    share of a step's routed rows, from every embedding row's logits
    (v + 1, E), the MASK row last: each row routes to its top-k, weighted
    by ``token_shares``.

    A trained router is near balance; a drawn one is not, and which of its
    experts a rank holds moved the rank's routed rows, and so its step
    time, with the seed. A quarter of a step's positions are MASK: they
    share one embedding and route alike. So the held columns ``first ..
    first + held - 1`` get the MASK row's n = round(k * held / E) best
    experts (the expected number) and ``held - n`` from the lower half of
    its ranking, so that neither the attention's shift of a position's
    router input nor the router's drift in training (AdamW moves a logit by
    up to lr x sum |x| a step, 0.8 of a logit's unit spread over a run's 51
    steps, where the top-8 of 128 begins 1.5 above the middle) carries one
    into the MASK row's top-k; these start as a draw from ``key`` and are
    swapped, one pair at a time, for others until the held experts'
    expected rows are ``held / E`` of all. The other columns keep the
    rest."""
    z = _sizes(cfg)
    e, held, first, k = z["e"], z["held"], z["first"], z["k"]
    n = int(k * held / e + 0.5)
    m = held - n
    top = lax.top_k(logits, k)[1]
    share = jnp.asarray(token_shares(cfg))
    load = jnp.zeros(e).at[top].add(jnp.broadcast_to(share[:, None],
                                                     top.shape))
    by_mask = jnp.argsort(-logits[-1])
    start = max(k, min(e // 2, e - m))
    pool = jax.random.permutation(key, by_mask[start:])
    lp = load[pool]
    want = held / e * jnp.sum(load) - jnp.sum(load[by_mask[:n]])

    def swap(_, sel):
        gap = want - jnp.sum(jnp.where(sel, lp, 0.0))
        # err[i, j]: the gap left with pool[i] given back and pool[j] held
        err = jnp.where(sel[:, None] & ~sel[None, :],
                        jnp.abs(gap - lp[None, :] + lp[:, None]), jnp.inf)
        at = jnp.argmin(err)
        i, j = at // lp.size, at % lp.size
        return jnp.where(err.reshape(-1)[at] < jnp.abs(gap),
                         sel.at[i].set(False).at[j].set(True), sel)

    sel = lax.fori_loop(0, 4 * held, swap, jnp.arange(lp.size) < m)
    pool = pool[jnp.argsort(~sel, stable=True)]       # the held ones first
    chosen = jnp.concatenate([by_mask[:n], pool[:m]])
    rest = jnp.concatenate([by_mask[n:start], pool[m:]])
    return jnp.concatenate([rest[:first], chosen, rest[first:]])


# --- the noise -----------------------------------------------------------------

def noise(cfg: dict, step: int, sequences: int, length: int):
    """``(m (sequences, length) bool, p (sequences, length) float32)`` of
    training step ``step`` (0-based) of a batch of ``sequences``."""
    z = _sizes(cfg)
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(cfg.get("noise_seed", 0))), step)
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (sequences, length // z["block"]),
                           jnp.float32)
    p = jnp.repeat((1.0 - z["noise_eps"]) * t + z["noise_eps"], z["block"],
                   axis=1)
    return jax.random.uniform(k_m, (sequences, length), jnp.float32) < p, p


# --- the layers ----------------------------------------------------------------

def rope(x, theta: float, positions):
    """x: (seq, heads, d); the pairs ``(x[i], x[i + d/2])`` rotated by ``pos
    * theta**(-2i/d)`` (rotate-half), ``pos`` from ``positions`` (seq,)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def visible(cfg: dict, i, j, length: int):
    """The block-diffusion mask's entries for query positions ``i`` and key
    positions ``j`` of ``[x_t | x_0]`` (broadcast against each other), or
    the planted fault's."""
    fault = cfg.get("reference_fault")
    if fault == "bd_mask_as_causal":
        return j <= i
    b = _sizes(cfg)["block"]
    q_noisy, k_noisy = i < length, j < length
    qb, kb = (i % length) // b, (j % length) // b
    own = qb == kb
    if fault == "noisy_block_causal":
        own = own & (j <= i)
    return (q_noisy & k_noisy & own) | (q_noisy & ~k_noisy & (kb < qb)) | \
        (~q_noisy & ~k_noisy & (kb <= qb))


def attention(cfg: dict, p: Dict, a: str, x, quant: Quant):
    """x: (2L, hidden), the noisy copy then the clean one."""
    z = _sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s, d = x.shape[0], z["d"]
    length = s // 2
    q = _mm(x, p[f"{a}/q_proj/kernel"], quant).reshape(s, z["heads"], d)
    k = _mm(x, p[f"{a}/k_proj/kernel"], quant).reshape(s, z["kv"], d)
    v = _mm(x, p[f"{a}/v_proj/kernel"], quant).reshape(s, z["kv"], d)
    q = rms_norm(q, p[f"{a}/q_norm/weight"], eps)
    k = rms_norm(k, p[f"{a}/k_norm/weight"], eps)
    pos = jnp.arange(s)
    if cfg.get("reference_fault") != "clean_positions_continue":
        pos = pos % length
    q, k = rope(q, theta, pos), rope(k, theta, pos)
    # each query head's key/value head, repeated by indexing
    shared = jnp.arange(z["heads"]) // (z["heads"] // z["kv"])
    k, v = k[:, shared], v[:, shared]
    rows = min(ROWS, length)
    j = jnp.arange(s)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv                     # (s, d) each

        def some_rows(args):
            qb, t0 = args                    # (rows, d), the first row's i
            i = t0 + jnp.arange(rows)[:, None]
            scores = _mm(qb, kh.T, quant) / math.sqrt(d)
            scores = jnp.where(visible(cfg, i, j, length), scores, -jnp.inf)
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


def route(cfg: dict, x, router):
    """``(idx (tokens, k), gates (tokens, k))`` over ALL experts: the
    softmax over every expert's logit, its top-k, renormalised."""
    probs = jax.nn.softmax(jnp.matmul(x, router, precision=HIGHEST), axis=-1)
    chosen, idx = lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def experts_part(cfg: dict, p: Dict, m: str, x, idx, gates, quant: Quant,
                 first: Optional[int] = None, held: Optional[int] = None):
    """The routed part of the layer that the experts ``first .. first + held
    - 1`` give (by default the configuration's share): every held expert in
    turn applied to every position and weighted by the position's gate for
    it, which is 0 where the position did not choose it. ``p``'s stacks
    hold the experts ``first .. first + held - 1`` in that order."""
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


def expert_layer(cfg: dict, p: Dict, m: str, x, quant: Quant):
    """x: (tokens, hidden). Returns the layer's output and the router's
    choices (for the comparison of choices)."""
    idx, gates = route(cfg, x, p[f"{m}/gate"])
    return experts_part(cfg, p, m, x, idx, gates, quant), idx


def block(cfg: dict, p: Dict, name: str, x, quant: Quant):
    eps = float(cfg["rms_norm_eps"])

    def norm(which, t):
        return rms_norm(t, p[f"{name}/{which}/weight"], eps)

    x = x + attention(cfg, p, f"{name}/self_attn",
                      norm("input_layernorm", x), quant)
    y, idx = expert_layer(cfg, p, f"{name}/mlp",
                          norm("post_attention_layernorm", x), quant)
    return x + y, idx


def hidden_states(cfg: dict, p: Dict, ids, masked, quant: Quant = None,
                  remat: bool = False):
    """One sequence: ids (L,) and its noise's ``masked`` (L,) -> ``(the
    final norm's output over the noisy half (L, hidden), choices)``;
    ``choices`` maps a block's name to the experts chosen at its 2L
    positions."""
    z = _sizes(cfg)
    ids = ids.astype(jnp.int32)
    zids = jnp.concatenate([jnp.where(masked, z["v"], ids), ids])

    def run(name, x):
        fn = (lambda pp, xx: block(cfg, pp, name, xx, quant))
        if remat:
            fn = jax.checkpoint(fn)
        return fn(p, x)

    choices = {}
    x = p["embed_tokens/embedding"][zids]
    for name in block_names(cfg):
        x, choices[name] = run(name, x)
    return rms_norm(x[:ids.shape[0]], p["norm/weight"],
                    float(cfg["rms_norm_eps"])), choices


def forward(cfg: dict, p: Dict, ids, masked, quant: Quant = None,
            remat: bool = False):
    """``(logits (L, vocab) of the noisy half, choices)`` of one sequence."""
    h, choices = hidden_states(cfg, p, ids, masked, quant, remat)
    return _mm(h, p["lm_head"], quant), choices


def sequence_loss(cfg: dict, p: Dict, ids, masked, prob, quant: Quant = None,
                  remat: bool = False):
    """``(loss, choices)`` of one sequence and its noise: ``sum_i (m_i /
    p_i) CE(logits_i, ids_i)`` over its labelled positions (all; with
    ``reference_label_positions`` the first so many), over their count, a
    block of positions' logits at a time."""
    ids = ids.astype(jnp.int32)
    s = ids.shape[0]
    h, choices = hidden_states(cfg, p, ids, masked, quant, remat)
    labelled = int(cfg.get("reference_label_positions", s))
    weight = masked.astype(jnp.float32) / prob * (
        jnp.arange(s) < labelled).astype(jnp.float32)
    rows = min(ROWS, s)

    def some_positions(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(_mm(hb, p["lm_head"], quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0] * wb)

    parts = lax.map(jax.checkpoint(some_positions),
                    (h.reshape(s // rows, rows, -1), ids.reshape(s // rows, rows),
                     weight.reshape(s // rows, rows)))
    return jnp.sum(parts) / labelled, choices


# --- the first training steps --------------------------------------------------

def first_steps(cfg: dict, weights: Dict, batches: Sequence,
                quant: Quant = None, rows: Optional[int] = None,
                drop_mtp: bool = False) -> Dict:
    """``len(batches)`` AdamW steps (decoupled weight decay on every leaf,
    gradients clipped by their global norm) from ``weights`` (which the
    steps consume), each batch a host array of ids (sequences, L) with the
    noise of its step, one sequence at a time with the gradients summed.
    Returns what `correct` compares: each step's loss, the norm of every
    leaf of the first (clipped) gradient, the norm of every leaf's change
    after the last step and, kept on the device, those two trees and the
    first step's choices.

    ``rows`` plants a fault: only the first ``rows`` sequences of a batch
    (their noise as the whole batch's draw gave it). This model has no MTP
    head (``drop_mtp``) and no correction bias. The faults of the mechanism
    ride in ``cfg`` (the module's docstring)."""
    if drop_mtp:
        raise ValueError("no multi-token-prediction head to leave out")
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    def seq_grad(params, ids, masked, prob):
        (loss, choices), g = jax.value_and_grad(
            lambda p: sequence_loss(cfg, p, ids, masked, prob, quant,
                                    remat=True), has_aux=True)(params)
        return g, loss, choices

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
    # Adam's second moment waits on the host between updates, as in
    # reference/trinity_mini.py
    mu = nu = None
    losses = []
    grad1 = choices1 = None
    for step, ids in enumerate(batches):
        ids = np.asarray(ids)
        masked, prob = noise(cfg, step, ids.shape[0], ids.shape[1])
        if rows is not None:
            ids, masked, prob = ids[:rows], masked[:rows], prob[:rows]
        gsum, total, kept = None, 0.0, []
        for seq, m, pr in zip(ids, masked, prob):
            g, loss, ch = jgrad(params, jnp.asarray(seq), m, pr)
            gsum = g if gsum is None else jadd(gsum, g)
            del g
            total += float(loss)
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
        if step == 0:
            grad1 = jax.device_get(g)
            choices1 = {k: np.stack([np.asarray(c[k]) for c in kept])
                        for k in block_names(cfg)}
        del g
        if step + 1 < len(batches):
            nu = jax.device_get(nu)
    del mu, nu
    return {"losses": losses, "grad1_norm": leaf_norms(grad1),
            "grad1": grad1, "params": params, "choices1": choices1,
            "biases": {}}


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
