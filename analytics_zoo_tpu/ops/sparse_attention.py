"""Learned sparse attention's own operations (the sparse stage of DeepSeek
Sparse Attention): the lightning indexer's scores, the exact top-k selection
of keys a query makes from them, and the indexer's loss, a KL divergence to
the main attention's head-summed probabilities over the selected keys.

A sequence of L positions, the indexer's h^I heads of d^I (x: the
sublayer's normed input, detached):

* ``q^I = x W_qI`` (h^I x d^I a position), ``k^I = LayerNorm(x W_kI)`` (one
  key head of d^I), ``w = x W_w / sqrt(h^I d^I)``, RoPE on both (the
  caller's);
* ``I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])`` for s <= t, in
  float32;
* ``S_t``: the ``min(top_k, t + 1)`` keys of the largest ``I[t, s]``, exact,
  ties to the lower index (as ``lax.top_k``), one set for every head of the
  query, packed as ``ops/attention.py pack_selection`` lays it out for the
  selected-key flash kernel;
* ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])`` with ``p_t`` the
  main attention's probabilities over S_t summed over its heads and divided
  by their number, not differentiated.

Everything works a chunk of ``rows`` queries at a time (the configuration's
``q_chunk_size``): no (L, L) array of scores is ever made, the chunk's
(rows, L) ones are. The indexer's work lies under the named scope
``sa.indexer``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .attention import LOG2_E, pack_selection, unpack_selection

INDEXER_SCOPE = "sa.indexer"
# the indexer's gradients, made beside its loss (``indexer_kl``), as
# ``checkpoint_name`` marks them: a remat policy that keeps them does not
# run the loss's chunks a second time in the backward pass
INDEXER_GRAD_NAME = "sa_indexer_grads"
# the packed selection, as the caller marks it: kept, the selection is not
# made again in the backward pass
SELECTION_NAME = "sa_selection"


def _chunk_scores(q_i, w, k_i, t0):
    """The indexer's scores of a chunk of queries from position ``t0``:
    q_i (B, rows, h^I, d^I), w (B, rows, h^I) float32, k_i (B, L, d^I) ->
    (B, rows, L) float32, -inf at the keys after each query."""
    s = jnp.einsum("bqjd,bsd->bqjs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(s), w,
                        preferred_element_type=jnp.float32)
    rows, length = scores.shape[1:]
    t = t0 + lax.broadcasted_iota(jnp.int32, (rows, length), 0)
    return jnp.where(lax.broadcasted_iota(jnp.int32, (rows, length), 1) <= t,
                     scores, -jnp.inf)


def _order_keys(scores):
    """float32 -> uint32 keys in the floats' total order (-0.0 below 0.0,
    as ``lax.top_k`` ranks them)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | jnp.uint32(2 ** 31), ~bits)


def topk_mask(scores, need):
    """The ``need`` (...) largest entries of each row of ``scores`` (...,
    L), ties to the lower index: exactly ``lax.top_k``'s selection, with no
    sort. The row's threshold, its need-th largest value, is built bit by
    bit on order-preserving uint32 keys (32 counts over the row); the
    entries above it are taken, and of those equal to it the first ones."""
    key = _order_keys(scores)
    need = jnp.broadcast_to(need, scores.shape[:-1])

    def bit(i, thr):
        cand = thr | lax.shift_left(jnp.uint32(1), (31 - i).astype(
            jnp.uint32))
        count = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= need, cand, thr)

    thr = lax.fori_loop(0, 32, bit, jnp.zeros(need.shape, jnp.uint32))
    above = key > thr[..., None]
    equal = key == thr[..., None]
    left = need - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                             <= left[..., None]))


def _chunks(length, rows):
    if length % rows or rows % 32:
        raise ValueError(f"{length} positions in chunks of {rows} (a "
                         f"multiple of 32 that divides them)")
    return length // rows


def indexer_select(q_i, w, k_i, top_k: int, rows: int):
    """The packed selection (B, L / 32, L) uint32 of each query's
    ``min(top_k, t + 1)`` keys of the largest indexer score, a chunk of
    ``rows`` queries at a time. Not differentiated."""
    q_i, w, k_i = (lax.stop_gradient(a) for a in (q_i, w, k_i))
    b, length = q_i.shape[:2]
    n = _chunks(length, rows)

    def chunk(c):
        t0 = c * rows
        scores = _chunk_scores(lax.dynamic_slice_in_dim(q_i, t0, rows, 1),
                               lax.dynamic_slice_in_dim(w, t0, rows, 1),
                               k_i, t0)
        need = jnp.minimum(top_k, t0 + 1 + jnp.arange(rows, dtype=jnp.int32))
        return pack_selection(topk_mask(scores, need), rows)

    with jax.named_scope(INDEXER_SCOPE):
        words = lax.map(chunk, jnp.arange(n, dtype=jnp.int32))
        return jnp.moveaxis(words, 0, 1).reshape(b, length // 32, length)


def selection_key_bins(bits, rows: int, bins: int):
    """How many keys each query selected in each of ``bins`` spans of
    equal width over the keys: (B, L, bins) int32 from the packed selection
    (B, L / 32, L), a chunk of ``rows`` queries at a time."""
    b, _, length = bits.shape
    n = _chunks(length, rows)

    def chunk(c):
        words = lax.dynamic_slice_in_dim(bits, c * (rows // 32), rows // 32,
                                         1)
        keep = unpack_selection(words, rows)
        return jnp.sum(keep.reshape(b, rows, bins, length // bins), -1,
                       dtype=jnp.int32)

    out = lax.map(chunk, jnp.arange(n, dtype=jnp.int32))
    return jnp.moveaxis(out, 0, 1).reshape(b, length, bins)


def _chunk_target(q2, k, lse, keep):
    """The KL's target on a chunk: (1/H) sum_h exp2(q2_h . k_g(h) - lse_h)
    at the selected entries, 0 elsewhere. q2 (B, rows, H, d) the main
    queries times ``sm_scale * log2 e`` (as the flash kernel takes them), k
    (B, L, H_kv, d), lse (B, H, rows) the flash kernel's base-2 logsumexp,
    keep (B, rows, L). A kv head's group of query heads at a time."""
    b, rows, heads, d = q2.shape
    kv = k.shape[2]
    qg = q2.reshape(b, rows, kv, heads // kv, d)
    lg = lse.reshape(b, kv, heads // kv, rows)
    total = jnp.zeros(keep.shape, jnp.float32)
    for g in range(kv):
        s = jnp.einsum("bqnd,bsd->bnqs", qg[:, :, g], k[:, :, g],
                       preferred_element_type=jnp.float32)
        s = jnp.where(keep[:, None], s - lg[:, g, :, :, None], -jnp.inf)
        total = total + jnp.sum(jnp.exp2(s), axis=1)
    return total / heads


def _chunk_kl(scores, keep, p):
    """Each row's ``KL(p || softmax over the selected of scores)`` and its
    gradient in the scores, 0 off the selection."""
    logits = jnp.where(keep, scores, -jnp.inf)
    logq = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    kl = jnp.sum(jnp.where(keep, plogp - p * logq, 0.0), axis=-1)
    return kl, jnp.where(keep, jnp.exp(logq) - p, 0.0)


def _chunk_indexer_grads(q_i, w, k_i, d_scores):
    """The scores' gradient ``d_scores`` (B, rows, L) carried back to the
    chunk's q^I and w and to every k^I: ``(dq (B, rows, h^I, d^I), dw (B,
    rows, h^I), dk (B, L, d^I))`` in float32, the products in the compute
    dtype with float32 sums."""
    s = jnp.einsum("bqjd,bsd->bqjs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    dw = jnp.einsum("bqs,bqjs->bqj", d_scores, jax.nn.relu(s),
                    preferred_element_type=jnp.float32)
    ds = jnp.where(s > 0, d_scores[:, :, None, :] * w[..., None], 0.0
                   ).astype(q_i.dtype)
    dq = jnp.einsum("bqjs,bsd->bqjd", ds, k_i,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bqjs,bqjd->bsd", ds, q_i,
                    preferred_element_type=jnp.float32)
    return dq, dw, dk


def _kl_and_grads(rows, sm_scale, q_i, w, k_i, q, k, lse, bits):
    """``L_I`` of each sequence (B,) and its gradients in q_i, w and k_i,
    one pass over the chunks."""
    b, length = q_i.shape[:2]
    n = _chunks(length, rows)
    q2 = q * jnp.asarray(sm_scale * LOG2_E, q.dtype)

    def chunk(dk, c):
        t0 = c * rows

        def part(a, axis=1, size=rows, at=t0):
            return lax.dynamic_slice_in_dim(a, at, size, axis)
        qc, wc = part(q_i), part(w)
        keep = unpack_selection(part(bits, size=rows // 32,
                                     at=c * (rows // 32)), rows)
        scores = _chunk_scores(qc, wc, k_i, t0)
        p = _chunk_target(part(q2), k, part(lse, axis=2), keep)
        kl, d_scores = _chunk_kl(scores, keep, p)
        dq, dw, dk_c = _chunk_indexer_grads(qc, wc, k_i, d_scores / length)
        return dk + dk_c, (jnp.sum(kl, axis=-1), dq, dw)

    dk, (kl, dq, dw) = lax.scan(
        chunk, jnp.zeros(k_i.shape, jnp.float32),
        jnp.arange(n, dtype=jnp.int32))
    dq = jnp.moveaxis(dq, 0, 1).reshape(q_i.shape)
    dw = jnp.moveaxis(dw, 0, 1).reshape(w.shape)
    return jnp.sum(kl, axis=0) / length, (dq, dw, dk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _indexer_kl(rows, sm_scale, q_i, w, k_i, q, k, lse, bits):
    return _kl_and_grads(rows, sm_scale, q_i, w, k_i, q, k, lse, bits)[0]


def _indexer_kl_fwd(rows, sm_scale, q_i, w, k_i, q, k, lse, bits):
    """The loss and, in the same pass, its gradients for a cotangent of 1
    (each sequence's loss reads its own sequence alone), named for a remat
    policy to keep."""
    value, grads = _kl_and_grads(rows, sm_scale, q_i, w, k_i, q, k, lse,
                                 bits)
    grads = tuple(checkpoint_name(d.astype(a.dtype), INDEXER_GRAD_NAME)
                  for d, a in zip(grads, (q_i, w, k_i)))
    return value, grads


def _indexer_kl_bwd(rows, sm_scale, grads, g):
    del rows, sm_scale
    return tuple(d * g.reshape((-1,) + (1,) * (d.ndim - 1)).astype(d.dtype)
                 for d in grads) + (None, None, None, None)


_indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


def indexer_kl(q_i, w, k_i, q, k, lse, bits, *, sm_scale: float, rows: int):
    """``L_I`` of each sequence, (B,) float32: the mean over its queries of
    ``KL(p_t || softmax_{s in S_t} I[t, s])``. q_i (B, L, h^I, d^I), w (B, L,
    h^I) float32, k_i (B, L, d^I) the indexer's (differentiated); q (B, L,
    H, d), k (B, L, H_kv, d) the main attention's queries and keys, lse (B,
    H, L) its base-2 logsumexp over the selection (``flash_attention(...,
    mask="selected", return_lse=True)``), bits the packed selection: the
    target, not differentiated."""
    with jax.named_scope(INDEXER_SCOPE):
        return _indexer_kl(rows, float(sm_scale), q_i, w, k_i,
                           *(lax.stop_gradient(a) for a in (q, k, lse)),
                           bits)
