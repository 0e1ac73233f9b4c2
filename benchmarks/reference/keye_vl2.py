"""Plain reference of Keye-VL-2.0-30B-A3B's language model (the ``KeyeVL2``
family: a Qwen3-MoE-style decoder with DeepSeek Sparse Attention's lightning
indexer and MRoPE) as one rank of an expert-parallel deployment holds it,
trained in DSA's sparse stage: forward, both losses, gradients and the first
AdamW steps in float32 ``jax.numpy`` at the highest matmul precision. No
kernel, no bit trick, no import of the program.

Equations (the keys are ``config.json``'s; what they leave open is listed
under ``assumed`` in the configuration's file). A sequence of L tokens:

* model: ``x = Emb(ids)``; the blocks; RMSNorm; an untied head;
* block: ``x = x + SA(N_in(x))``; ``x = x + MoE(N_post(x))``;
* attention: ``q = h W_q`` (heads x d), ``k = h W_k``, ``v = h W_v`` (kv
  heads x d); RMSNorm over each head's d on q and k; MRoPE: frequency pair
  i of d/2 rotated by the temporal position for i < 16, the height's for 16
  <= i < 40, the width's after (``mrope_section``), all three the index in
  the sequence for text; query head i reads kv head ``i // (heads / kv
  heads)``;
* indexer, on ``hd = stop_gradient(h)``: ``q^I = hd W_qI`` (16 x 64), ``k^I
  = LayerNorm(hd W_kI)`` (one head of 64), ``w = hd W_w / sqrt(16 * 64)``;
  rotate-half RoPE over the whole 64 of q^I and k^I; ``I[t, s] = sum_j
  w[t, j] ReLU(q^I[t, j] . k^I[s])`` for s <= t;
* selection: ``S_t = lax.top_k`` of row t's causal scores, ``min(topk, t +
  1)`` of them (the top_k of the row with the keys after t at -inf, the
  -inf ones dropped), the reference's own, made from its own scores;
* attention: ``softmax_{s in S_t}(q k^T / sqrt(d)) v W_o``, a dense masked
  softmax; a block of query rows at a time (its selection, then its heads
  one after another, its part of L_I), recomputed in the backward pass;
* indexer loss: ``p_t = stop_gradient(mean_h softmax_{S_t}(q_h k_h^T /
  sqrt(d)))``, ``L_I = mean_t KL(p_t || softmax_{S_t} I_t)``, meaned over
  the layers;
* expert layer: ``reference/sdar_moe.py``'s (softmax over ALL experts, top-k,
  renormalised, the held experts' part);
* loss: ``(1/(L-1)) sum_t CE(logits_t, ids[t+1]) + lambda L_I``, a block of
  positions' logits at a time; mean over sequences.

Parameters are a flat dict ``name -> array`` whose names are the program's
tree paths joined by "/" (``layers_1/self_attn/indexer_q_proj/kernel``).

``quant`` is the control's hook, as in ``reference/nn.py``: every
product's operands and result go through it, the indexer's score products
among them, so the control selects from its own rounded scores. The faults
the readings plant ride in the configuration's ``reference_fault``:
``dense_attention`` (every causal key in place of the selection, the KL over
them too), ``no_causal_clip`` (the top-k over all keys, those after the
query among them), ``indexer_loss_dropped`` (``L_I`` left out of the loss)
and ``kl_target_head0`` (the target head 0's probabilities alone).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# what is no model's own: a product through the control's hook, RMSNorm,
# rotate-half RoPE, the expert layer, the recipe's rate, the host-side norms
from .joyai_llm_flash import (_mm, diff_norms, leaf_norms,  # noqa: F401
                              learning_rate, rms_norm)
from .nn import HIGHEST, Quant
from .sdar_moe import experts_part, rope, route

ROWS = 512           # query rows worked on at a time
KEY_BINS = 128       # spans of the keys each query's selection is counted
                     # in, as the program's
FAULTS = ("dense_attention", "no_causal_clip", "indexer_loss_dropped",
          "kl_target_head0")


# --- shapes and seeded weights -------------------------------------------------

def _sizes(cfg: dict) -> dict:
    e = int(cfg["num_experts"])
    heads = int(cfg["num_attention_heads"])
    sa = cfg["sa_config"]
    return dict(
        v=int(cfg["vocab_size"]), h=int(cfg["hidden_size"]), heads=heads,
        kv=int(cfg.get("num_key_value_heads", heads)),
        d=int(cfg["head_dim"]), f=int(cfg["moe_intermediate_size"]), e=e,
        held=int(cfg.get("experts_held", e)),
        first=int(cfg.get("first_expert", 0)),
        k=int(cfg["num_experts_per_tok"]),
        layers=int(cfg["num_hidden_layers"]),
        hi=int(sa["indexer_num_heads"]), di=int(sa["indexer_head_dim"]),
        topk=int(sa["topk"]),
        sections=tuple(int(n) for n in (cfg.get("rope_scaling") or {}).get(
            "mrope_section", ())))


def block_names(cfg: dict) -> List[str]:
    return [f"layers_{i}" for i in range(_sizes(cfg)["layers"])]


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    z = _sizes(cfg)
    s: Dict[str, Tuple[int, ...]] = {
        "embed_tokens/embedding": (z["v"], z["h"]),
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
        s[f"{a}/indexer_q_proj/kernel"] = (z["h"], z["hi"] * z["di"])
        s[f"{a}/indexer_k_proj/kernel"] = (z["h"], z["di"])
        s[f"{a}/indexer_k_norm/scale"] = (z["di"],)
        s[f"{a}/indexer_k_norm/bias"] = (z["di"],)
        s[f"{a}/indexer_weights_proj/kernel"] = (z["h"], z["hi"])
        m = f"{name}/mlp"
        s[f"{m}/gate"] = (z["h"], z["e"])
        s[f"{m}/experts_gate_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_up_proj"] = (z["held"], z["h"], z["f"])
        s[f"{m}/experts_down_proj"] = (z["held"], z["f"], z["h"])
    return s


def param_count(cfg: dict) -> int:
    return sum(int(np.prod(v)) for v in param_shapes(cfg).values())


def token_shares(cfg: dict) -> np.ndarray:
    """Each embedding row's expected share of a step's positions: the ids
    but EOS (0) as the token traffic draws them, the id r with weight ``r **
    -s`` (``init.token_zipf_s``, 0 if absent: every id alike)."""
    z = _sizes(cfg)
    s = float(cfg.get("init", {}).get("token_zipf_s", 0.0))
    ids = np.arange(1, z["v"], dtype=np.float64) ** -s
    return np.concatenate([[0.0], ids / ids.sum()]).astype(np.float32)


def balanced_order(cfg: dict, logits, key):
    """The order of a router's columns that gives the rank its balanced
    share of a step's routed rows, from every embedding row's logits (v,
    E), each row routed to its top-k and weighted by ``token_shares``: the
    held columns ``first .. first + held - 1`` start as a draw from ``key``
    and are swapped, one pair at a time, for others until their expected
    rows are ``held / E`` of all (``reference/sdar_moe.py
    balanced_share_order``'s search, with no MASK row to place first)."""
    z = _sizes(cfg)
    e, held, first, k = z["e"], z["held"], z["first"], z["k"]
    top = lax.top_k(logits, k)[1]
    share = jnp.asarray(token_shares(cfg))
    load = jnp.zeros(e).at[top].add(jnp.broadcast_to(share[:, None],
                                                     top.shape))
    pool = jax.random.permutation(key, e)
    lp = load[pool]
    want = held / e * jnp.sum(load)

    def swap(_, sel):
        gap = want - jnp.sum(jnp.where(sel, lp, 0.0))
        err = jnp.where(sel[:, None] & ~sel[None, :],
                        jnp.abs(gap - lp[None, :] + lp[:, None]), jnp.inf)
        at = jnp.argmin(err)
        i, j = at // lp.size, at % lp.size
        return jnp.where(err.reshape(-1)[at] < jnp.abs(gap),
                         sel.at[i].set(False).at[j].set(True), sel)

    sel = lax.fori_loop(0, 4 * held, swap, jnp.arange(e) < held)
    pool = pool[jnp.argsort(~sel, stable=True)]       # the held ones first
    chosen, rest = pool[:held], pool[held:]
    return jnp.concatenate([rest[:first], chosen, rest[first:]])


def make_weights(cfg: dict, seed: int, only: Optional[Sequence[str]] = None
                 ) -> Dict[str, jnp.ndarray]:
    """The benchmark's float32 weights from ``seed``, made on the device:
    matrices normal with std ``1 / sqrt(fan_in)`` (the attention's output
    and the experts' down projections at ``init.out_proj_scale`` of it, the
    router at ``init.router_std``), the embedding at ``init.embedding_std``,
    norm weights and the indexer's LayerNorm scale ``1 + 0.1 n``, its bias
    ``0.1 n``; each router's columns relabelled by ``balanced_order``.
    ``only`` makes just those leaves (each the same as in the whole tree)."""
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
        if name.endswith(("/weight", "/scale")):
            return 1.0 + 0.1 * n
        if name.endswith("/bias"):
            return 0.1 * n
        return n * np.float32(std(name, shape))

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
            out[router] = out[router][:, balanced_order(
                cfg, jnp.matmul(x, out[router], precision=HIGHEST),
                jax.random.fold_in(key, len(names) + i))]
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))


# --- the layers ----------------------------------------------------------------

def mrope(x, theta: float, positions, sections):
    """x: (seq, heads, d); the pairs ``(x[i], x[i + d/2])`` rotated by
    ``pos_i * theta**(-2i/d)``, ``pos_i`` the row of ``positions`` (3, seq)
    whose section holds pair i (sections in turn over the d/2 pairs)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    which = np.concatenate([np.full(n, i) for i, n in enumerate(sections)])
    ang = positions.astype(jnp.float32)[which].T * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _einsum(spec: str, a, b, quant: Quant):
    """``jnp.einsum`` through the control's hook, as ``_mm``."""
    if quant is not None:
        a, b = quant(a), quant(b)
    y = jnp.einsum(spec, a, b, precision=HIGHEST)
    return y if quant is None else quant(y)


def indexer_scores(q_i, w, k_i, quant: Quant = None):
    """``I[t, s]`` of a block of queries: q_i (rows, hi, di), w (rows, hi),
    k_i (seq, di) -> (rows, seq), every key (the caller masks)."""
    s = _einsum("qjd,sd->qjs", q_i, k_i, quant)
    return _einsum("qjs,qj->qs", jax.nn.relu(s), w, quant)


def select_rows(cfg: dict, q_i, w, k_i, t0, quant: Quant = None):
    """The selected keys of a block of queries from position ``t0``, (rows,
    seq) bool: ``lax.top_k`` of each one's causal scores, or the planted
    fault's. Not differentiated."""
    z = _sizes(cfg)
    fault = cfg.get("reference_fault")
    rows, seq = q_i.shape[0], k_i.shape[0]
    i = t0 + jnp.arange(rows)[:, None]
    j = jnp.arange(seq)[None, :]
    if fault == "dense_attention":
        return j <= i
    scores = lax.stop_gradient(indexer_scores(q_i, w, k_i, quant))
    if fault != "no_causal_clip":
        scores = jnp.where(j <= i, scores, -jnp.inf)
    vals, idx = lax.top_k(scores, min(z["topk"], seq))
    # of a query with fewer keys than top-k, the -inf ones are none
    return jnp.zeros((rows, seq), bool).at[
        jnp.arange(rows)[:, None], idx].set(vals > -jnp.inf)


def attention(cfg: dict, p: Dict, a: str, x, quant: Quant):
    """x: (seq, hidden). Returns the layer's output, its ``L_I`` and how
    many keys each query chose in each span of the keys (``key_bins``)."""
    z = _sizes(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s, d = x.shape[0], z["d"]
    q = _mm(x, p[f"{a}/q_proj/kernel"], quant).reshape(s, z["heads"], d)
    k = _mm(x, p[f"{a}/k_proj/kernel"], quant).reshape(s, z["kv"], d)
    v = _mm(x, p[f"{a}/v_proj/kernel"], quant).reshape(s, z["kv"], d)
    q = rms_norm(q, p[f"{a}/q_norm/weight"], eps)
    k = rms_norm(k, p[f"{a}/k_norm/weight"], eps)
    pos = jnp.arange(s)
    if z["sections"]:
        three = jnp.broadcast_to(pos, (3, s))
        q, k = (mrope(t, theta, three, z["sections"]) for t in (q, k))
    else:
        q, k = rope(q, theta, pos), rope(k, theta, pos)
    # the indexer, on the detached input
    hd = lax.stop_gradient(x)
    q_i = _mm(hd, p[f"{a}/indexer_q_proj/kernel"], quant).reshape(
        s, z["hi"], z["di"])
    k_i = layer_norm(_mm(hd, p[f"{a}/indexer_k_proj/kernel"], quant),
                     p[f"{a}/indexer_k_norm/scale"],
                     p[f"{a}/indexer_k_norm/bias"], eps)
    w = _mm(hd, p[f"{a}/indexer_weights_proj/kernel"], quant) \
        / math.sqrt(z["hi"] * z["di"])
    q_i = rope(q_i, theta, pos)
    k_i = rope(k_i[:, None], theta, pos)[:, 0]
    # each query head's key/value head, picked by indexing in its turn
    shared = jnp.arange(z["heads"]) // (z["heads"] // z["kv"])
    kh, vh = (jnp.swapaxes(t, 0, 1) for t in (k, v))
    rows, bins = min(ROWS, s), min(KEY_BINS, s)

    def some_rows(args):
        """A block of queries: their selection, their attention output
        (a head at a time), their part of L_I and their keys' counts."""
        qb, qib, wb, t0 = args
        keep = select_rows(cfg, qib, wb, k_i, t0, quant)

        def head(_, qg):
            qh, g = qg
            sc = jnp.where(keep, _mm(qh, kh[g].T, quant) / math.sqrt(d),
                           -jnp.inf)
            return None, _mm(jax.nn.softmax(sc, axis=-1), vh[g], quant)

        _, out = lax.scan(jax.checkpoint(head), None,
                          (jnp.swapaxes(qb, 0, 1), shared))
        l_i = indexer_rows_loss(cfg, qb, kh, shared, qib, wb, k_i, keep,
                                quant)
        return jnp.swapaxes(out, 0, 1), l_i, jnp.sum(
            keep.reshape(rows, bins, s // bins), -1, dtype=jnp.int32)

    n = s // rows
    out, parts, chosen = lax.map(jax.checkpoint(some_rows), (
        q.reshape(n, rows, z["heads"], d),
        q_i.reshape(n, rows, *q_i.shape[1:]), w.reshape(n, rows, -1),
        jnp.arange(0, s, rows)))
    out = _mm(out.reshape(s, z["heads"] * d), p[f"{a}/o_proj/kernel"], quant)
    l_i = jnp.float32(0.0) if cfg.get("reference_fault") == \
        "indexer_loss_dropped" else jnp.sum(parts) / s
    return out, l_i, chosen.reshape(s, bins)


def indexer_rows_loss(cfg: dict, qb, kh, shared, qib, wb, k_i, keep,
                      quant: Quant):
    """A block of queries' part of ``L_I``: the sum over them of ``KL(p_t ||
    softmax_{S_t} I_t)``, ``p_t`` the heads' mean attention over S_t (or
    head 0's alone, the planted fault), not differentiated. ``kh`` the kv
    heads' keys, ``shared`` each query head's."""
    heads, d = qb.shape[1], qb.shape[2]
    qs, ks = lax.stop_gradient(qb), lax.stop_gradient(kh)
    fault = cfg.get("reference_fault")

    def head(total, qg):
        qh, g = qg
        sc = jnp.where(keep, _mm(qh, ks[g].T, quant) / math.sqrt(d),
                       -jnp.inf)
        return total + jax.nn.softmax(sc, axis=-1), None

    zeros = jnp.zeros(keep.shape, jnp.float32)
    if fault == "kl_target_head0":
        target, _ = head(zeros, (qs[:, 0], shared[0]))
    else:
        target, _ = lax.scan(head, zeros, (jnp.swapaxes(qs, 0, 1), shared))
        target = target / heads
    target = lax.stop_gradient(target)
    logq = jax.nn.log_softmax(jnp.where(
        keep, indexer_scores(qib, wb, k_i, quant), -jnp.inf), axis=-1)
    plogp = jnp.where(target > 0, target * jnp.log(
        jnp.where(target > 0, target, 1.0)), 0.0)
    return jnp.sum(jnp.where(keep, plogp - target * logq, 0.0))


def expert_layer(cfg: dict, p: Dict, m: str, x, quant: Quant):
    """x: (tokens, hidden). Returns the layer's output and the router's
    choices (for the comparison of choices)."""
    idx, gates = route(cfg, x, p[f"{m}/gate"])
    return experts_part(cfg, p, m, x, idx, gates, quant), idx


def block(cfg: dict, p: Dict, name: str, x, quant: Quant):
    eps = float(cfg["rms_norm_eps"])

    def norm(which, t):
        return rms_norm(t, p[f"{name}/{which}/weight"], eps)

    y, l_i, hist = attention(cfg, p, f"{name}/self_attn",
                             norm("input_layernorm", x), quant)
    x = x + y
    y, idx = expert_layer(cfg, p, f"{name}/mlp",
                          norm("post_attention_layernorm", x), quant)
    return x + y, l_i, idx, hist


def sequence_loss(cfg: dict, p: Dict, ids, quant: Quant = None,
                  remat: bool = False):
    """``(loss, (lm_loss, L_I, choices, hists))`` of one sequence:
    ``CE(logits_t, ids[t+1])`` over its positions with a label, a block of
    positions' logits at a time, plus ``indexer_loss_weight`` times the
    layers' mean ``L_I``; ``choices`` and ``hists`` map a block's name to
    the experts chosen at its positions and its selection's key bins."""
    ids = ids.astype(jnp.int32)
    s = ids.shape[0]

    def run(name, x):
        fn = (lambda pp, xx: block(cfg, pp, name, xx, quant))
        if remat:
            fn = jax.checkpoint(fn)
        return fn(p, x)

    choices, hists, l_is = {}, {}, []
    x = p["embed_tokens/embedding"][ids]
    for name in block_names(cfg):
        x, l_i, choices[name], hists[name] = run(name, x)
        l_is.append(l_i)
    h = rms_norm(x, p["norm/weight"], float(cfg["rms_norm_eps"]))
    rows = min(ROWS, s)
    labels = jnp.pad(ids[1:], (0, 1))
    weight = (jnp.arange(s) < s - 1).astype(jnp.float32)

    def some_positions(args):
        hb, lb, wb = args
        logp = jax.nn.log_softmax(_mm(hb, p["lm_head"], quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], -1)[:, 0] * wb)

    parts = lax.map(jax.checkpoint(some_positions),
                    (h.reshape(s // rows, rows, -1),
                     labels.reshape(s // rows, rows),
                     weight.reshape(s // rows, rows)))
    lm = jnp.sum(parts) / (s - 1)
    l_i = sum(l_is) / len(l_is)
    total = lm + float(cfg.get("indexer_loss_weight", 1.0)) * l_i
    return total, (lm, l_i, choices, hists)


# --- the first training steps --------------------------------------------------

def first_steps(cfg: dict, weights: Dict, batches: Sequence,
                quant: Quant = None, rows: Optional[int] = None,
                drop_mtp: bool = False) -> Dict:
    """``len(batches)`` AdamW steps (decoupled weight decay on every leaf,
    gradients clipped by their global norm) from ``weights`` (which the
    steps consume), each batch a host array of ids (sequences, L), one
    sequence at a time with the gradients summed. Returns what `correct`
    compares: each step's loss and its two parts (``lm_losses``,
    ``indexer_losses``), the norm of every leaf of the first (clipped)
    gradient, the norm of every leaf's change after the last step and,
    kept on the device, those two trees, the first step's choices and its
    selection's key bins (``selection1``, summed over the sequences).

    ``rows`` plants a fault: only the first ``rows`` sequences of a batch.
    This model has no MTP head (``drop_mtp``) and no correction bias. The
    faults of the mechanism ride in ``cfg`` (the module's docstring)."""
    if drop_mtp:
        raise ValueError("no multi-token-prediction head to leave out")
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    def seq_grad(params, ids):
        (loss, aux), g = jax.value_and_grad(
            lambda pp: sequence_loss(cfg, pp, ids, quant, remat=True),
            has_aux=True)(params)
        return g, loss, aux

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
    mu = nu = None
    losses, lm_losses, indexer_losses = [], [], []
    grad1 = choices1 = selection1 = None
    for step, ids in enumerate(batches):
        ids = np.asarray(ids)
        if rows is not None:
            ids = ids[:rows]
        gsum, parts, kept = None, np.zeros(3), []
        for seq in ids:
            g, loss, (lm, l_i, ch, hist) = jgrad(params, jnp.asarray(seq))
            gsum = g if gsum is None else jadd(gsum, g)
            del g
            parts += (float(loss), float(lm), float(l_i))
            if step == 0:
                kept.append((ch, hist))
        n_seq = len(ids)
        losses.append(parts[0] / n_seq)
        lm_losses.append(parts[1] / n_seq)
        indexer_losses.append(parts[2] / n_seq)
        zeros = (lambda: jax.tree.map(jnp.zeros_like, params))
        params, mu, nu, g = jupdate(
            params, zeros() if mu is None else mu,
            zeros() if nu is None else jax.device_put(nu), gsum,
            jnp.float32(n_seq), step,
            jnp.float32(learning_rate(opt, step)))
        del gsum
        if step == 0:
            grad1 = jax.device_get(g)
            choices1 = {k: np.stack([np.asarray(c[k]) for c, _ in kept])
                        for k in block_names(cfg)}
            selection1 = {k: np.sum([np.asarray(h[k]) for _, h in kept],
                                    axis=0) for k in block_names(cfg)}
        del g
        if step + 1 < len(batches):
            nu = jax.device_get(nu)
    del mu, nu
    return {"losses": losses, "lm_losses": lm_losses,
            "indexer_losses": indexer_losses,
            "grad1_norm": leaf_norms(grad1), "grad1": grad1,
            "params": params, "choices1": choices1,
            "selection1": selection1, "biases": {}}


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
