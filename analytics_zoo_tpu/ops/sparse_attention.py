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

The selection works a chunk of ``rows`` queries at a time (the
configuration's ``q_chunk_size``): no (L, L) array of scores is ever made,
the chunk's (rows, L) ones are. The loss and its gradients are one Pallas
kernel over the causal (rows, rows) tiles (``_kl_kernel``), whose score
tiles never leave VMEM. The indexer's work lies under the named scope
``sa.indexer``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from . import attention as _attention
from .attention import (
    _DQ_LAST, _FIRST, _HEAD, _K, _Q, _SCOPED_VMEM_BYTES, LOG2_E, NEG_INF,
    _causal_walk, _count_grid, _launch, _sel_keep, _selection_spec,
    _selection_words, _walk_at, _walk_flag, pack_selection, unpack_selection)

INDEXER_SCOPE = "sa.indexer"
# the indexer's gradients, made beside its loss (``indexer_kl``), as
# ``checkpoint_name`` marks them: a remat policy that keeps them does not
# run the loss's kernel a second time in the backward pass
INDEXER_GRAD_NAME = "sa_indexer_grads"
# the packed selection, as the caller marks it: kept, the selection is not
# made again in the backward pass
SELECTION_NAME = "sa_selection"
# the KL pass's launches in ``zoo_attention_grid_steps_total``
SA_KL = "sa_kl"


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


def _kl_kernel(walk, words_ref, qi_ref, w_ref, ki_ref, q_ref, k_ref, lse_ref,
               kl_ref, dq_ref, dw_ref, dk_ref, m_ref, l_ref, *, length):
    """One sequence's KL pass, grid (batch, entries of
    ``_causal_walk(n, "q", group=2)``): each q tile's causal k tiles twice,
    the entry's head row the sweep. Sweep 0 carries the running max and sum
    of the indexer's logits over the selected keys, and at the q tile's
    diagonal leaves their logsumexp in ``m_ref``. Sweep 1 makes the
    indexer's per-head products again, once, kept for the logits and their
    backward; the target from the main queries and keys, the KL terms, the
    logits' gradient and the indexer's backward: dq^I and dw into the q
    tile's output blocks, dk^I into the sequence's, resident over the walk
    (a tile's made (d^I, block), the products' natural layout, and
    transposed into place).
    ``refs``: the tile's selection words; q^I (1, h^I, block, d^I), w (1,
    block, h^I) float32, k^I (1, block, d^I); the main queries times
    ``sm_scale * log2 e`` (1, H, block, d), keys (1, H_kv, block, d) and
    base-2 logsumexp (1, block, H); the outputs each query's KL (1, block, 1),
    dq^I, dw, dk^I (1, L, d^I), all float32; the scratch."""
    import jax.experimental.pallas as pl

    e = pl.program_id(1)
    sweep = _walk_at(walk, e, _HEAD)
    block, heads, hi = lse_ref.shape[1], lse_ref.shape[2], w_ref.shape[2]
    group = heads // k_ref.shape[1]
    keep = _sel_keep(words_ref[0], (block, block))
    ki, w = ki_ref[0], w_ref[0]

    def products(a, b):
        return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def scores():
        return (products(qi_ref[0, j], ki) for j in range(hi))

    def logits(s):
        total = jnp.zeros((block, block), jnp.float32)
        for j, s_j in enumerate(s):
            total += jax.nn.relu(s_j) * w[:, j:j + 1]
        return total

    @pl.when(e == 0)
    def _init_dk():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    @pl.when(_walk_flag(walk, e, _FIRST))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        for ref in (l_ref, kl_ref, dq_ref, dw_ref):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(sweep == 0)
    def _normaliser():
        x = jnp.where(keep, logits(scores()), NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
        l_new = l_ref[:, :1] * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.exp(x - m_new), axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(_walk_flag(walk, e, _DQ_LAST))
        def _lse():
            m_ref[...] += jnp.log(l_ref[...])

    @pl.when(sweep == 1)
    def _loss_and_grads():
        s = list(scores())
        logq = logits(s) - m_ref[:, :1]
        lse = lse_ref[0]
        total = jnp.zeros((block, block), jnp.float32)
        for h in range(heads):
            total += jnp.exp2(products(q_ref[0, h], k_ref[0, h // group])
                              - lse[:, h:h + 1])
        p = jnp.where(keep, total / heads, 0.0)
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        kl_ref[0] += jnp.sum(jnp.where(keep, plogp - p * logq, 0.0), axis=-1,
                             keepdims=True)
        d = jnp.where(keep, jnp.exp(logq) - p, 0.0) / length
        lane = lax.broadcasted_iota(jnp.int32, (block, hi), 1)
        dw = jnp.zeros((block, hi), jnp.float32)
        dk = jnp.zeros((dk_ref.shape[2], block), jnp.float32)
        for j, s_j in enumerate(s):
            q_j = qi_ref[0, j]
            dw += jnp.where(lane == j, jnp.sum(d * jax.nn.relu(s_j), axis=-1,
                                               keepdims=True), 0.0)
            ds = jnp.where(s_j > 0, d * w[:, j:j + 1], 0.0).astype(q_j.dtype)
            dq_ref[0, j] += jnp.dot(ds, ki, preferred_element_type=jnp.float32)
            dk += lax.dot_general(q_j, ds, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dw_ref[0] += dw
        rows = pl.ds(pl.multiple_of(_walk_at(walk, e, _K) * block, block),
                     block)
        dk_ref[0, rows, :] += dk.T


def _vmem_bytes(shape, dtype):
    """A block's bytes in VMEM, its last two dims padded to whole tiles."""
    size = jnp.dtype(dtype).itemsize
    *lead, r, c = shape
    return math.prod(lead) * -(-r // (32 // size)) * (32 // size) * \
        -(-c // 128) * 128 * size


def _kl_and_grads(block, sm_scale, q_i, w, k_i, q, k, lse, bits):
    """``L_I`` of each sequence (B,) and its gradients in q_i, w and k_i,
    float32: one launch of :func:`_kl_kernel` over the causal tiles of side
    ``block``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, length, hi, di = q_i.shape
    heads, d = q.shape[2:]
    kv = k.shape[2]
    walk = _causal_walk(_chunks(length, block), "q", group=2)
    grid = (b, walk.shape[0] // 4)
    _count_grid(SA_KL, grid, math.prod(grid))
    q2 = q * jnp.asarray(sm_scale * LOG2_E, q.dtype)
    operands = (_selection_words(bits), jnp.moveaxis(q_i, 2, 1), w, k_i,
                jnp.moveaxis(q2, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(lse, 1, 2))

    def spec(shape, tile, lead=0):
        """A block of ``shape`` at the entry's q (``tile`` _Q) or k tile,
        ``lead`` whole dims between the batch and the sequence."""
        def index(bi, e, walk):
            return (bi, *(0,) * lead, _walk_at(walk, e, tile),
                    *(0,) * (len(shape) - lead - 1))
        return pl.BlockSpec((1, *shape), index)

    in_specs = [_selection_spec(block, block, 1),
                spec((hi, block, di), _Q, 1), spec((block, hi), _Q),
                spec((block, di), _K), spec((heads, block, d), _Q, 1),
                spec((kv, block, d), _K, 1), spec((block, heads), _Q)]
    out_specs = [spec((block, 1), _Q), spec((hi, block, di), _Q, 1),
                 spec((block, hi), _Q),
                 pl.BlockSpec((1, length, di), lambda bi, *_: (bi, 0, 0))]
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct(s, f32) for s in (
        (b, length, 1), (b, hi, length, di), (b, length, hi),
        (b, length, di))]
    scratch = [pltpu.VMEM((block, 128), f32) for _ in range(2)]   # m, l
    # the blocks, double-buffered, and sweep 1's kept (block, block) products
    held = 2 * sum(_vmem_bytes(s.block_shape, a.dtype) for s, a in zip(
        in_specs + out_specs, operands + tuple(out_shape))) + \
        hi * _vmem_bytes((block, block), f32)
    interpret = _attention._interpret()
    kl, dq, dw, dk = _launch(
        functools.partial(_kl_kernel, length=length), operands, walk,
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        # dk^I is carried across the whole walk, a q tile's state across
        # its entries: only the batch is parallel
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_SCOPED_VMEM_BYTES + held),
        interpret=interpret)
    return jnp.sum(kl[..., 0], axis=-1) / length, (
        jnp.moveaxis(dq, 1, 2), dw, dk)


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
