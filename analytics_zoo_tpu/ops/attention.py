"""Fused attention ops: reference MHA, a Pallas TPU flash-attention kernel,
and the blockwise-softmax update that ring attention builds on.

The reference framework's attention is plain materialised-scores attention
inside its BERT/Transformer layers (reference: pyzoo/zoo/pipeline/api/keras/
layers/self_attention.py:386, zoo/.../keras/layers/BERT.scala:402) and it has
no long-context path at all (SURVEY.md §2.3). Here attention is a first-class
op: the flash kernel keeps scores in VMEM a (block_q, block_k) tile at a time
so the MXU stays busy and HBM never sees the S×S matrix.

Shapes follow (batch, seq, heads, head_dim) throughout.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..obs.registry import REGISTRY as _REGISTRY

logger = logging.getLogger("analytics_zoo_tpu")

# the O(S^2) path on the chip is never silent
_REFERENCE_ON_TPU = _REGISTRY.counter(
    "zoo_attention_reference_on_tpu_total",
    "flash_attention call sites traced on a TPU that fell through to "
    "mha_reference (sequence not tileable, or causal with s_q > s_k)")

# which backward a run compiled: one fused launch a call site (the group's
# whole dQ in VMEM, or a query head's at a time), or the dQ and dK/dV pair
# (neither within ``_FUSED_BWD_DQ_BYTES``)
_BACKWARD_PATHS = _REGISTRY.counter(
    "zoo_attention_backward_total",
    "flash_attention backward call sites traced, by the kernels they "
    "launch: fused (dQ beside dK/dV, one launch), fused_by_head (one "
    "launch, a query head of the group at a time) or two_kernel",
    labelnames=("path",))
_BACKWARD_FUSED = _BACKWARD_PATHS.labels(path="fused")
_BACKWARD_FUSED_BY_HEAD = _BACKWARD_PATHS.labels(path="fused_by_head")
_BACKWARD_TWO_KERNEL = _BACKWARD_PATHS.labels(path="two_kernel")

# what a windowed call site's grids compute against what its mask needs
_WINDOW_TILES = _REGISTRY.counter(
    "zoo_attention_window_tiles_total",
    "(q, k) tiles of windowed flash_attention call sites traced, forward and "
    "backward kernels, each at its own tile size: visited (tiles the grids "
    "compute) and needed (tiles that hold an entry inside the window)",
    labelnames=("kind",))
_TILES_VISITED = _WINDOW_TILES.labels(kind="visited")
_TILES_NEEDED = _WINDOW_TILES.labels(kind="needed")

NEG_INF = -1e30
LOG2_E = 1.4426950408889634      # the flash kernel softmaxes in base 2
# the forward kernel's output and logsumexp, as ``checkpoint_name`` marks
# them in ``_flash_fwd``: a remat policy that saves these names runs the
# forward kernel once a step, not again in the backward pass
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")
MIB = 2 ** 20
# Mosaic's default scoped VMEM: what every flash kernel's tiles fit today
_SCOPED_VMEM_BYTES = 16 * MIB
# The fused backward holds whole gradients in VMEM, each a float32
# accumulator and the double-buffered output block, lanes padded to 128: the
# dQ of all the query heads of a (batch, kv head), or one query head's dQ
# and the kv head's dK and dV. Up to this many bytes of them (three eighths
# of a v5e's 128 MiB) dQ rides the dK/dV launch: the MLA cell's 8192 x 192
# in bf16 takes 16 MiB, 4 query heads a kv head at 8192 x 128 take 32, and
# 8 a kv head at 16384 x 128 (128 MiB of dQ) go a head at a time in 3 x 16.
# Longer sequences keep the dQ kernel of their own.
_FUSED_BWD_DQ_BYTES = 48 * MIB


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  *, causal: bool = False, sm_scale: Optional[float] = None,
                  bias: Optional[jax.Array] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Plain materialised-scores attention. q,k: (B, S, H, D); v: (B, S, H,
    D) or a head size of its own. k and v may have fewer heads than q
    (grouped queries: each run of H / H_kv query heads shares one, repeated
    here); ``window`` keeps, of a causal mask, the keys ``0 <= t - j <
    window`` of query position t."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if bias is not None:
        logits = logits + bias
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - window)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def blockwise_update(q, k_blk, v_blk, acc, m, l, *, sm_scale,
                     q_positions=None, k_positions=None, causal=False):
    """One online-softmax accumulation step against a K/V block.

    q: (B, Sq, H, D); k_blk/v_blk: (B, Sk, H, D); acc: (B, Sq, H, D) f32;
    m, l: (B, Sq, H) f32 running max / normaliser. Returns updated (acc, m, l).
    This is the building block shared by ring attention
    (parallel/ring_attention.py) and any host-side blockwise fallback.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * sm_scale
    if causal:
        if q_positions is None:
            q_positions = jnp.arange(q.shape[1])
        if k_positions is None:
            k_positions = jnp.arange(k_blk.shape[1])
        mask = q_positions[:, None] >= k_positions[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_bhq = jnp.moveaxis(m, -1, 1)                       # (B, H, Sq)
    m_new = jnp.maximum(m_bhq, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m_bhq - m_new)                  # (B, H, Sq)
    l_new = jnp.moveaxis(l, -1, 1) * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    acc_new = acc * jnp.moveaxis(correction, 1, -1)[..., None] + pv
    return acc_new, jnp.moveaxis(m_new, 1, -1), jnp.moveaxis(l_new, 1, -1)


def blockwise_finalize(acc, l):
    """Normalise the accumulator once all K/V blocks are folded in."""
    return acc / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        block_k: int = 512) -> jax.Array:
    """Exact attention as a lax.scan over K/V blocks with the online
    softmax — numerically identical to ``mha_reference`` but the S×S score
    matrix never materializes (peak activation O(S·block_k) per head).

    Each scan step is wrapped in ``jax.checkpoint``, so the backward pass
    recomputes score tiles instead of storing them. Memory accounting
    (honest version): the (Sq, Sk) score matrix never materializes, but
    differentiating the scan still stores the (Sq, D) accumulator carry
    per K block — peak residuals O(Sq * D * Sk / block_k), an
    ~(block_k / D)x reduction vs materialized f32 scores (8x at D=64,
    block_k=512), not fully linear. For truly linear-in-S training memory
    shard the sequence instead (parallel/ring_attention.py). Historical
    note: this was the flash backward through round 3; round 4 replaced it
    with dedicated Pallas backward kernels (``_flash_bwd``) whose tiles stay
    in VMEM — blockwise_attention remains as the ring-attention building
    block and a host-portable exact-attention fallback."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    bk = block_k
    while s_k % bk:
        bk //= 2
        if bk < 8:
            bk = s_k
            break
    n_blocks = s_k // bk
    k_blocks = jnp.moveaxis(k.reshape(b, n_blocks, bk, h, d), 1, 0)
    v_blocks = jnp.moveaxis(v.reshape(b, n_blocks, bk, h, d), 1, 0)
    # bottom-right-aligned causal mask, matching mha_reference's
    # tril(k=s_k-s_q): with fewer queries than keys (decode), the last
    # query attends to every key
    q_pos = jnp.arange(s_q) + (s_k - s_q)

    @jax.checkpoint
    def step(carry, inputs):
        acc, m, l = carry
        k_blk, v_blk, k0 = inputs
        acc, m, l = blockwise_update(
            q, k_blk, v_blk, acc, m, l, sm_scale=sm_scale,
            causal=causal, q_positions=q_pos,
            k_positions=k0 + jnp.arange(bk))
        return (acc, m, l), None

    init = (jnp.zeros((b, s_q, h, d), jnp.float32),
            jnp.full((b, s_q, h), NEG_INF, jnp.float32),
            jnp.zeros((b, s_q, h), jnp.float32))
    starts = jnp.arange(n_blocks) * bk
    (acc, m, l), _ = lax.scan(step, init, (k_blocks, v_blocks, starts))
    return blockwise_finalize(acc, l).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU flash-attention kernel
# ---------------------------------------------------------------------------

def _visible(q_pos, k_pos, window):
    """The causal mask, and inside it the window's ``t - j < window``."""
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return keep


def _tile_class(q_pos0, k_start, block_q, block_k, window):
    """``(active, masked)`` of a causal (block_q, block_k) tile whose first
    row is query position ``q_pos0`` and whose first column is key
    ``k_start``: active unless wholly above the diagonal; masked where the
    diagonal or, with a window, its far edge crosses the tile (the tile's
    last row sees its first key no more). Interior tiles do no mask work.
    Tiles wholly beyond a window's far edge are never walked (``_k_band``,
    ``_q_band``)."""
    active = q_pos0 + block_q - 1 >= k_start
    masked = q_pos0 < k_start + block_k - 1
    if window is not None:
        masked |= q_pos0 + block_q - 1 - k_start >= window
    return active, masked


def _k_band(qi, block_q, block_k, q_offset, window, xp=jnp):
    """First and last k block that hold a key q tile ``qi`` sees through
    its window; every block between them holds one too. Traced values, or
    numpy's with ``xp=numpy``."""
    first = xp.maximum(q_offset + qi * block_q - (window - 1), 0) // block_k
    last = xp.maximum((q_offset + (qi + 1) * block_q - 1) // block_k, 0)
    return first, last


def _k_start(qi, step, block_q, block_k, q_offset, window):
    """First key of the k block a k-innermost kernel works on at ``step`` of
    q tile ``qi``: every block in turn, or with a window the band's."""
    if window is not None:
        step = step + _k_band(qi, block_q, block_k, q_offset, window)[0]
    return step * block_k


def _q_band(ki, block_q, block_k, q_offset, window, num_q, xp=jnp):
    """First and last q tile that hold a query which sees a key of k block
    ``ki`` through its window."""
    first = xp.maximum((ki * block_k - q_offset) // block_q, 0)
    last = xp.minimum(
        ((ki + 1) * block_k - 1 + window - 1 - q_offset) // block_q,
        num_q - 1)
    return first, last


def _window_tiles(s_q, s_k, block_q, block_k, window):
    """Static counts of a windowed call's tile grid: ``k_steps`` / ``q_steps``
    (the widest band: the grids' innermost extent by k and by q),
    ``by_k`` / ``by_q`` (tiles a head's k-innermost / q-innermost grid
    computes) and ``needed`` (tiles that hold an entry inside the window,
    counted from the mask itself)."""
    off, nq, nk = s_k - s_q, s_q // block_q, s_k // block_k
    qi, ki = np.arange(nq), np.arange(nk)
    k_first, k_last = _k_band(qi, block_q, block_k, off, window, np)
    q_first, q_last = _q_band(ki, block_q, block_k, off, window, nq, np)
    by_k = k_last - k_first + 1
    by_q = np.maximum(q_last - q_first + 1, 0)
    q0 = off + qi[:, None] * block_q         # a tile's first query position
    k0 = ki[None, :] * block_k
    needed = (q0 + block_q - 1 >= k0) & (q0 - (k0 + block_k - 1) < window)
    return {"k_steps": int(by_k.max()), "q_steps": int(by_q.max()),
            "by_k": int(by_k.sum()), "by_q": int(by_q.sum()),
            "needed": int(needed.sum())}


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q,
                  block_k, num_k_blocks, causal, head_dim, q_offset=0,
                  with_lse=False, ones_column=True, window=None):
    """Grid = (batch*heads, num_q_blocks, num_k_blocks); the k dim is innermost
    so (acc, m) scratch carries the online softmax across k iterations.
    With ``with_lse`` the kernel also emits the log2-domain logsumexp
    (m + log2 l) per q row, which the Pallas backward consumes.

    With a ``window`` the k dim walks a q tile's band alone:
    ``num_k_blocks`` is the widest band's blocks, step j is the block
    ``_k_band(...)[0] + j``, and the steps past the diagonal's block are
    the shorter bands' padding (no compute; the index map names the
    diagonal's block again, so nothing is fetched). A row whose first
    blocks are all outside its window carries m = NEG_INF and p = 1
    through them; its first block with a visible key rescales that to
    exactly 0.

    The softmax normaliser l = sum(p) comes one of two ways, by v's head
    size (``_flash_forward`` decides):

    - ``ones_column``: ``v_ref`` arrives AUGMENTED with a trailing ones
      column, so the p @ v product leaves l in its last output column and
      acc's last column carries it (the rescale correction applies to it
      identically). Free where d_v is no multiple of 128 (the BERT and
      Transformer layers' 64): the product's N dim pads to the next 128
      lanes either way, and the row reduction it replaces is a (block_q,
      block_k) VPU/XLU pass.
    - otherwise (d_v a multiple of 128: MLA's 128) the column would cost the
      product a whole MXU pass of its own (N 129 pads to 256), so v goes in
      as it is and l is a running float32 row sum in scratch beside m."""
    import jax.experimental.pallas as pl  # local import keeps module cpu-safe

    if with_lse:
        lse_ref, *rest = rest
    if ones_column:
        acc_ref, m_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        if not ones_column:
            l_ref[...] = jnp.zeros_like(l_ref)

    q_start = q_idx * block_q
    k_start = _k_start(q_idx, k_idx, block_q, block_k, q_offset, window)

    def _compute(masked):
        # matmuls keep the input dtype (bf16 inputs hit the MXU at full
        # rate) with f32 accumulation; softmax state is always f32.
        # q arrives PRE-SCALED by sm_scale*log2(e) (_flash_forward), so the
        # scores are already in the log2 domain: one fewer (block_q,
        # block_k) multiply per tile, and exp2 instead of exp.
        q = q_ref[0]                                     # (block_q, D)
        k = k_ref[0]                                     # (block_k, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            # bottom-right aligned (q_offset = s_k - s_q), matching
            # mha_reference's tril(k=s_k-s_q), _lse_pass and _flash_bwd —
            # the fwd/bwd pair must mask identically or causal s_q != s_k
            # gradients would be silently wrong (round-3 advisor finding).
            q_pos = (q_offset + q_start +
                     lax.broadcasted_iota(jnp.int32, s.shape, 0))
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(_visible(q_pos, k_pos, window), s, NEG_INF)
        m_prev = m_ref[:, :1]                            # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)                          # (block_q, block_k)
        correction = jnp.exp2(m_prev - m_new)            # (block_q, 1)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        if not ones_column:
            l_ref[...] = jnp.broadcast_to(
                l_ref[:, :1] * correction +
                jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        v = v_ref[0]                          # (block_k, D_v [+ 1 of ones])
        acc_ref[...] = (acc_ref[...] * correction +
                        jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32))

    if causal:
        # Three tile classes: fully masked (skip), diagonal (mask), and
        # interior (q_pos >= k_pos everywhere — no mask work: the two
        # iotas + compare + select are (block_q, block_k) VPU passes that
        # would otherwise run on every tile).
        active, diagonal = _tile_class(q_offset + q_start, k_start, block_q,
                                       block_k, window)
        pl.when(active & diagonal)(lambda: _compute(True))
        pl.when(active & jnp.logical_not(diagonal))(lambda: _compute(False))
    else:
        _compute(False)

    @pl.when(k_idx == num_k_blocks - 1)
    def _finalize():
        acc = acc_ref[...]
        l = acc[:, head_dim:head_dim + 1] if ones_column else l_ref[:, :1]
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc[:, :head_dim] / l).astype(o_ref.dtype)
        if with_lse:
            # p_ij = exp2(s2_ij - L2_i) with L2 = m + log2 l (log2 domain)
            lse_ref[0] = m_ref[:, :1] + jnp.log2(l)


@functools.lru_cache(maxsize=1)
def _mosaic_params():
    """Grid dimension semantics of the forward, dQ and dK/dV kernels: dims
    0/1 (batch*heads and the non-carry sequence dim) are parallel, the
    innermost dim carries online-softmax / accumulator state and must stay
    ordered. Parallel dims let Mosaic overlap the next tile's DMA with the
    current tile's compute instead of treating the whole grid as one
    sequential loop. (The fused backward carries dQ across both sequence
    dims, by head dK and dV across the group too, and states its own.)"""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def varying_axes(*arrays) -> frozenset:
    """Union of the arrays' shard_map varying-axes sets (empty outside
    shard_map)."""
    out = frozenset()
    for a in arrays:
        out |= jax.typeof(a).vma
    return out


def mark_varying(x, vma):
    """Lift ``x`` to vary over every axis of ``vma`` it does not vary over
    yet (device-invariant zeros, a replicated q in cross-attention)."""
    missing = tuple(frozenset(vma) - jax.typeof(x).vma)
    return lax.pcast(x, missing, to="varying") if missing else x


def _kv_row(group):
    """Index-map form of "the k/v row a q row reads": q rows are (batch,
    head) pairs flattened, k/v rows (batch, kv head) pairs, and a run of
    ``group`` query heads shares one kv head, so the row is ``bh // group``
    and no copy of k or v is made for the group."""
    return (lambda bh: bh) if group == 1 else (lambda bh: bh // group)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   with_lse=False, window=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    kv_row = _kv_row(h // h_kv)
    # v's head size may differ from q's and k's (MLA: q.k over 192, v of
    # 128): the p @ v product, the accumulator and the output take d_v
    d_v = v.shape[-1]
    # (B, S, H, D) -> (B*H, S, D): each grid row owns one head's sequence.
    # q is pre-scaled into the log2 domain for the kernel's exp2 softmax
    # (see _flash_kernel); one multiply here replaces one per k-tile.
    qf = (q * jnp.asarray(sm_scale * LOG2_E, q.dtype))
    qf = jnp.moveaxis(qf, 2, 1).reshape(b * h, s_q, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * h_kv, s_k, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * h_kv, s_k, d_v)
    # ones column: p @ [v | 1] yields the softmax normaliser in the last
    # output column on the MXU, where that column is free: not at a d_v
    # that already fills whole 128-lane tiles (see _flash_kernel)
    ones_column = d_v % 128 != 0
    if ones_column:
        vf = jnp.concatenate(
            [vf, jnp.ones((b * h_kv, s_k, 1), vf.dtype)], axis=-1)
    d_acc = vf.shape[-1]

    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    num_q = s_q // block_q
    num_k = s_k // block_k
    if window is not None:
        # the k dim walks each q tile's band: its widest band's blocks
        tiles = _window_tiles(s_q, s_k, block_q, block_k, window)
        num_k = tiles["k_steps"]
        _TILES_VISITED.inc(b * h * tiles["by_k"])
        _TILES_NEEDED.inc(b * h * tiles["needed"])

    grid = (b * h, num_q, num_k)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        num_k_blocks=num_k, causal=causal, head_dim=d_v,
        q_offset=s_k - s_q, with_lse=with_lse, ones_column=ones_column,
        window=window)
    # Under shard_map (e.g. Ulysses sequence parallelism) the output must
    # declare which mesh axes it varies over. Use the union of the inputs'
    # varying sets and lift any less-varying input up to it so mixed-vma
    # call sites (e.g. cross-attention with replicated q) still compile.
    vma = varying_axes(qf, kf, vf)
    qf, kf, vf = (mark_varying(a, vma) for a in (qf, kf, vf))
    out_shape = [jax.ShapeDtypeStruct((b * h, s_q, d_v), q.dtype, vma=vma)]
    out_specs = [pl.BlockSpec((1, block_q, d_v),
                              lambda bh, qi, ki: (bh, qi, 0))]
    if with_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32, vma=vma))
        out_specs.append(pl.BlockSpec((1, block_q, 1),
                                      lambda bh, qi, ki: (bh, qi, 0)))
    if causal:
        # fully-masked steps (k block entirely above the diagonal) skip
        # compute via pl.when; re-referencing the last ACTIVE k block
        # keeps the block index unchanged across the masked tail of each
        # q row so Mosaic can elide those steps' k/v DMA. Measured
        # neutral-to-slightly-positive on the dev v5e (the skipped-step
        # cost there is grid sequencing, not DMA) — kept because it can
        # only reduce memory traffic.
        q_off = s_k - s_q

        def k_index(bh, qi, ki):
            # clamp at 0: with s_q > s_k (negative q_off) a fully-masked
            # leading q block would otherwise compute a NEGATIVE last
            # active block and issue a negative-index k/v DMA
            last = jnp.maximum((q_off + (qi + 1) * block_q - 1) // block_k,
                               0)
            if window is not None:
                ki = ki + _k_band(qi, block_q, block_k, q_off, window)[0]
            return (kv_row(bh), jnp.minimum(ki, last), 0)
    else:
        def k_index(bh, qi, ki):
            return (kv_row(bh), ki, 0)

    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), k_index),
            pl.BlockSpec((1, block_k, d_acc), k_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d_acc), jnp.float32),    # acc [| l column]
            pltpu.VMEM((block_q, 128), jnp.float32),      # m
        ] + ([] if ones_column else
             [pltpu.VMEM((block_q, 128), jnp.float32)]),  # l
        # bh/q grid dims carry no state between steps — declaring them
        # parallel lets Mosaic double-buffer the next tile's DMA behind
        # this tile's compute; only the k dim (online-softmax carry) is
        # order-dependent
        compiler_params=None if interpret else _mosaic_params(),
        interpret=interpret,
    )(qf, kf, vf)
    out = jnp.moveaxis(res[0].reshape(b, h, s_q, d_v), 1, 2)
    if with_lse:
        return out, res[1]
    return out


def _interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: on the CPU backend
    only (tests, the simulated mesh). On a TPU they compile through Mosaic;
    no other backend has a lowering for them."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"the Pallas kernels have no lowering for platform {platform!r}")
    return platform == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k, window):
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          _interpret(), window=window)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, window=None):
    """The forward kernel, with its two results named
    (``FLASH_RESIDUAL_NAMES``) for a caller's remat policy: the output
    (B, S_q, H, d_v), which is both the primal result and a residual, and
    the log2-domain logsumexp, kept as the kernel wrote it, the (B*H, S_q,
    1) column the backward kernels read. Kept as (B*H, S_q) instead it
    needs a relayout between sublanes and lanes on each side, which on a
    v5e compiled to 334 MB more executable for six blocks of 2 x 8192 x 32
    heads, a slower step and no less memory (PERF.md, PR 36). Outside a
    remat whose policy reads the names they are identities."""
    interpret = _interpret()
    out, lse = _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                              interpret, with_lse=True, window=window)
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _bwd_tile(masked, q2, k, v, g, L, D, q_offset, q_start, k_start, cd,
              window=None):
    """Shared (block_q, block_k) backward tile: rebuild P from (q2, k, L),
    then ds = P*(dP - D). All matmuls keep the input dtype (bf16 rides the
    MXU) with f32 accumulation; returns (p, ds) in compute dtype ``cd``."""
    s2 = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if masked:
        q_pos = (q_offset + q_start +
                 lax.broadcasted_iota(jnp.int32, s2.shape, 0))
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, s2.shape, 1)
        s2 = jnp.where(_visible(q_pos, k_pos, window), s2, NEG_INF)
    p = jnp.exp2(s2 - L)                             # true softmax probs
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - D)).astype(cd)
    return p.astype(cd), ds


def _flash_bwd_dq_kernel(q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref, dq_ref,
                         acc_ref, *, sm_scale, block_q, block_k,
                         num_k_blocks, causal, q_offset, cd, window=None):
    """dQ pass: grid (batch*heads, num_q, num_k), k innermost; the dq tile
    accumulates across k iterations in VMEM scratch — no (S, S) tensor
    ever reaches HBM (the round-3 pure-JAX backward streamed every P/dS
    tile through HBM between the dot_generals, which bounded fwd+bwd at
    ~1.4x materialized; tiles resident in VMEM are the FA-2 design). Runs
    only where not even one query head's gradients fit the fused kernel's
    VMEM budget (``_flash_bwd``). With a ``window`` the k dim walks the q
    tile's band, as in ``_flash_kernel``."""
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_idx * block_q
    k_start = _k_start(q_idx, k_idx, block_q, block_k, q_offset, window)

    def _compute(masked):
        _, ds = _bwd_tile(masked, q2_ref[0], k_ref[0], v_ref[0],
                          g_ref[0], L_ref[0], D_ref[0], q_offset, q_start,
                          k_start, cd, window)
        acc_ref[...] += jnp.dot(ds, k_ref[0],
                                preferred_element_type=jnp.float32)

    if causal:
        active, diagonal = _tile_class(q_offset + q_start, k_start, block_q,
                                       block_k, window)
        pl.when(active & diagonal)(lambda: _compute(True))
        pl.when(active & jnp.logical_not(diagonal))(lambda: _compute(False))
    else:
        _compute(False)

    @pl.when(k_idx == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q,
                          block_k, num_q_blocks, causal, q_offset, cd,
                          dq=None, window=None, group=1, seq_q_blocks=None,
                          by_head=False):
    """dK/dV pass: grid (batch*kv heads, num_k, group * num_q), the
    innermost dim walking the q tiles of each of the ``group`` query heads
    that share this kv head in turn, so dK and dV are summed over the
    group where they are made; both accumulators live in VMEM scratch.
    dv += P^T g and dk += dS^T q2 are expressed as dot_generals contracting
    the q (sublane) dim. q2 is the log2-prescaled q, so dk carries a
    1/log2(e) correction at finalize. With a ``window``, ``num_q_blocks``
    is the widest band's tiles and step j of a head is the tile
    ``_q_band(...)[0] + j``, the steps past the band's last tile padding;
    ``seq_q_blocks`` is then the sequence's tiles.

    ``dq`` is the fused kernel's (see ``_flash_bwd_fused_kernel``): given,
    each tile's dS also goes into dQ. ``by_head`` is its grid for a group
    whose whole dQ does not fit VMEM: (batch*kv heads, group, num_k,
    num_q), a query head at a time. For a fixed k block the tiles still
    arrive head by head and, inside a head, q tile by q tile (``step``), but
    between two heads every other k block passes: dK and dV accumulate in
    scratch for the whole kv sequence, at the k block's rows, and their
    output blocks are the kv head's whole sequence."""
    import jax.experimental.pallas as pl

    k_at, out_k_at = Ellipsis, 0      # all of dK's and dV's scratch and block
    if by_head:
        head, k_idx, q_idx = (pl.program_id(i) for i in (1, 2, 3))
        step = head * num_q_blocks + q_idx
        dq_step = q_idx                  # a head's dQ begins with the head
        # of the whole kv sequence's, this k block's rows
        k_at = (pl.ds(pl.multiple_of(k_idx * block_k, block_k), block_k),
                slice(None))
        out_k_at = (0, *k_at)
    else:
        k_idx, step = pl.program_id(1), pl.program_id(2)
        if group == 1:
            head, q_idx = 0, step
        else:
            head, q_idx = step // num_q_blocks, step % num_q_blocks
        dq_step = step                   # the group's with the kv head

    @pl.when(step == 0)
    def _init():
        dk_acc[k_at] = jnp.zeros((block_k, dk_acc.shape[-1]), dk_acc.dtype)
        dv_acc[k_at] = jnp.zeros((block_k, dv_acc.shape[-1]), dv_acc.dtype)

    k_start = k_idx * block_k
    if window is not None:
        first, last = _q_band(k_idx, block_q, block_k, q_offset, window,
                              seq_q_blocks)
        q_idx = first + q_idx
    q_start = q_idx * block_q
    if dq is not None:
        dq_ref, dq_acc, sm_scale, num_k_blocks = dq
        rows = pl.ds(pl.multiple_of(q_start, block_q), block_q)
        # this step's query head's rows of the tile, in the accumulator
        # (one head's: (s_q, d)) and in the output block, (group, s_q, d)
        # or by head (1, s_q, d)
        acc_at = (rows, slice(None)) if group == 1 or by_head else \
            (head, rows, slice(None))
        out_at = (0 if by_head else head, rows, slice(None))

        def _write_dq():
            dq_ref[out_at] = (dq_acc[acc_at] * sm_scale).astype(dq_ref.dtype)

        if window is None:
            @pl.when(k_idx == 0)
            def _init_dq():
                dq_acc[acc_at] = jnp.zeros((block_q, dq_acc.shape[-1]),
                                           dq_acc.dtype)
        else:
            # a band's walk does not pass every q tile under the first k
            # block: the whole accumulator is cleared as its kv head (by
            # head: its query head) begins
            @pl.when((k_idx == 0) & (dq_step == 0))
            def _init_dq():
                dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        g = g_ref[0]
        p, ds = _bwd_tile(masked, q2_ref[0], k_ref[0], v_ref[0],
                          g, L_ref[0], D_ref[0], q_offset, q_start,
                          k_start, cd, window)
        dv_acc[k_at] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[k_at] += jax.lax.dot_general(
            ds, q2_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dq is not None:
            dq_acc[acc_at] += jnp.dot(ds, k_ref[0],
                                      preferred_element_type=jnp.float32)

    if causal:
        active, diagonal = _tile_class(q_offset + q_start, k_start, block_q,
                                       block_k, window)
        if window is not None:
            active = q_idx <= last       # past it: the band's padding steps
        pl.when(active & diagonal)(lambda: _compute(True))
        pl.when(active & jnp.logical_not(diagonal))(lambda: _compute(False))
        if window is not None and dq is not None:
            # a q tile's rows are whole once its diagonal's k block, the
            # last of its band, has passed them
            pl.when(active & (k_idx == _k_band(
                q_idx, block_q, block_k, q_offset, window)[1]))(_write_dq)
    else:
        _compute(False)

    @pl.when(step == group * num_q_blocks - 1)
    def _finalize():
        dk_ref[out_k_at] = (dk_acc[k_at] *
                            (1.0 / LOG2_E)).astype(dk_ref.dtype)
        dv_ref[out_k_at] = dv_acc[k_at].astype(dv_ref.dtype)

    if dq is not None and window is None:
        # a q tile's rows are whole once the last k block has passed them,
        # masked tiles too: written a tile at a time, never the whole
        # sequence in one statement
        pl.when(k_idx == num_k_blocks - 1)(_write_dq)


def _flash_bwd_fused_kernel(q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref,
                            dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                            *, sm_scale, num_k_blocks, **tiles):
    """The whole backward in one launch: the dK/dV pass (same grid, q
    innermost) whose every tile also adds dS . k into a float32 accumulator
    for ALL the queries that share this (batch, kv head), (group, s_q, d)
    in VMEM scratch, at the tile's rows. Each (block_q, block_k) score
    tile, its exp2 and dP are rebuilt once a step, not once in each of two
    kernels. ``dq_ref`` is the whole (group, s_q, d) output block, indexed
    by batch*kv heads alone. With ``by_head`` (among ``tiles``) the grid
    takes the group's query heads one after the other, the accumulator and
    ``dq_ref`` are one head's (s_q, d), and dK and dV wait in VMEM for the
    group's last head. Either way a fixed q tile's contributions arrive in
    ascending k order, as in ``_flash_bwd_dq_kernel``, and a fixed k
    block's in ``_flash_bwd_dkv_kernel``'s: the three gradients equal the
    pair's to the bit."""
    _flash_bwd_dkv_kernel(q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref, dk_ref,
                          dv_ref, dk_acc, dv_acc,
                          dq=(dq_ref, dq_acc, sm_scale, num_k_blocks),
                          **tiles)


def _fused_bwd_dq_bytes(s_q: int, d: int, dtype) -> int:
    """VMEM the fused backward takes to hold ``s_q`` rows of a gradient
    whole (dQ of a kv head's group: the sequence times the group; by head:
    one head's dQ, and likewise dK and dV at their own rows and widths):
    the float32 accumulator and the output block, which the pipeline
    buffers twice; lanes pad to 128."""
    lanes = -(-d // 128) * 128
    return s_q * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def _bwd_tile_sizes(s_q: int, s_k: int, block_q: int, block_k: int):
    """Backward tile sizes: the backward keeps ~4 (bq, bk) f32 tiles +
    operands live per grid step; at 1024x1024 those are past Mosaic's
    default 16 MiB of scoped VMEM (``_SCOPED_VMEM_BYTES``: all the pair's
    kernels have, and what the fused ones leave for tiles when they raise
    the limit by the gradients they hold), so halve down to <=512. An ODD
    user block > 512 that divides S halves to a non-divisor and would
    silently drop the trailing rows of dq/dk/dv (round-4 advisor) — re-fit
    via gcd with 512 (the largest power-of-two tile <= 512 that divides
    S)."""
    bq, bk = min(block_q, s_q), min(block_k, s_k)
    while bq > 512:
        bq //= 2
    while bk > 512:
        bk //= 2
    if s_q % bq:
        bq = math.gcd(s_q, 512)
    if s_k % bk:
        bk = math.gcd(s_k, 512)
    return bq, bk


def _flash_bwd(causal, sm_scale, block_q, block_k, window, res, g):
    """FlashAttention-2-style Pallas backward consuming the forward's
    log2-domain logsumexp. Every (block_q, block_k) P/dS tile lives and
    dies in VMEM — the previous pure-JAX backward streamed each of its
    ~6 (b, h, S, S)-shaped intermediates through HBM between dot_generals
    (~13 GB per step at S=4096), which bounded fwd+bwd at ~1.4x
    materialized attention on a v5e chip.

    Three grids around one tile body, chosen by the bytes each would hold
    whole in VMEM (``_fused_bwd_dq_bytes``) against ``_FUSED_BWD_DQ_BYTES``:

    1. ``fused``: the dQ of all of a (batch, kv head)'s queries fits (the
       sequence times the group): ``_flash_bwd_fused_kernel`` on the dK/dV
       grid, k and v fetched once a kv head.
    2. ``fused_by_head``: that is past the budget (8 query heads a kv head
       at 16384 positions of 128 are 128 MiB), but one query head's dQ and
       the kv head's dK and dV are not (3 x 16 MiB there): the same kernel
       on the grid (batch*kv heads, group, k blocks, q tiles). A head's dQ
       is finished and written before the next head begins; dK and dV,
       summed over the group, stay in scratch until its last head.
    3. ``two_kernel``: else a dQ kernel (k innermost) and the dK/dV kernel
       (q innermost), each rebuilding the score tiles.

    All three give the same bits and count themselves in
    ``zoo_attention_backward_total``. k and v keep their own head count
    throughout, and with a ``window`` every grid walks bands
    (``_window_tiles``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse = res
    interpret = _interpret()
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    kv_row = _kv_row(group)
    d_v = v.shape[-1]            # g, o, dv carry v's head size; dq, dk q's
    bq, bk = _bwd_tile_sizes(s_q, s_k, block_q, block_k)
    nq, nk = s_q // bq, s_k // bk
    bh, bh_kv = b * h, b * h_kv
    cd = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    # innermost extents: every tile, or with a window the widest band's
    nq_in, nk_in, tiles_w = nq, nk, None
    if window is not None:
        tiles_w = _window_tiles(s_q, s_k, bq, bk, window)
        nq_in, nk_in = tiles_w["q_steps"], tiles_w["k_steps"]

    def flat(a):                                 # (B,S,H,D) -> (B*H,S,D)
        return jnp.moveaxis(a, 2, 1).reshape(b * a.shape[2], a.shape[1],
                                             a.shape[-1])

    q2 = flat(q * jnp.asarray(sm_scale * LOG2_E, q.dtype))
    kf, vf, gf, of = flat(k), flat(v), flat(g.astype(q.dtype)), flat(o)
    # D_i = sum_d g*o — one elementwise pass; (bh, s_q, 1) so the kernels
    # load it sublane-oriented (per-q-row, broadcast along k lanes)
    D = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                axis=-1, keepdims=True)

    vma = varying_axes(q2, kf, vf, gf, lse, D)
    operands = tuple(mark_varying(a, vma) for a in (q2, kf, vf, gf, lse, D))
    off = s_k - s_q
    tiles = dict(block_q=bq, block_k=bk, causal=causal, q_offset=off,
                 cd=cd, window=window)
    dkv_tiles = dict(tiles, num_q_blocks=nq_in, group=group,
                     seq_q_blocks=nq)

    def q_tile(row, ki, qi):
        """Block index of the q tile at step ``qi`` under k block ``ki``."""
        if window is not None:
            first, last = _q_band(ki, bq, bk, off, window, nq)
            return (row, jnp.minimum(first + qi, last), 0)
        if causal:
            # the q tiles wholly above a k block's diagonal are skipped
            # (pl.when): they name the first active tile again, so the
            # block index does not change and nothing is fetched for them,
            # as _flash_forward's k_index does for its masked tail. Here
            # it pays: a skipped tile's q2 and g are (bq, d + d_v) of DMA
            # with no compute to hide behind (the fused launch 37.3 ->
            # 29.4 ms, the dK/dV launch 30.7 -> 22.4, at 2 x 32 heads x
            # 8192 x 192/128 on a v5e; PERF.md, PR 38)
            first = jnp.maximum((ki * bk - off) // bq, 0)
            return (row, jnp.maximum(qi, first), 0)
        return (row, qi, 0)

    # the dK/dV and fused kernels walk (bh_kv, nk, group * nq_in), q innermost
    def q_index(bhi, ki, st):
        if group == 1:
            return q_tile(bhi, ki, st)
        return q_tile(bhi * group + st // nq_in, ki, st % nq_in)
    by_q = [pl.BlockSpec((1, bq, w), q_index)
            for w in (d, d_v, 1, 1)]                     # q2, g, lse, D
    by_k = [pl.BlockSpec((1, bk, w), lambda bhi, ki, qi: (bhi, ki, 0))
            for w in (d, d_v)]                           # k | dk, v | dv
    dkv_in_specs = [by_q[0], *by_k, *by_q[1:]]
    dq_shape = jax.ShapeDtypeStruct((bh, s_q, d), q.dtype, vma=vma)
    dkv_shapes = [jax.ShapeDtypeStruct((bh_kv, s_k, d), k.dtype, vma=vma),
                  jax.ShapeDtypeStruct((bh_kv, s_k, d_v), v.dtype, vma=vma)]
    dkv_scratch = [pltpu.VMEM((bk, d), jnp.float32),
                   pltpu.VMEM((bk, d_v), jnp.float32)]

    # the path, by the bytes a launch would hold whole in VMEM: the group's
    # dQ; else one query head's dQ and the kv head's dK and dV
    dq_bytes = _fused_bwd_dq_bytes(group * s_q, d, q.dtype)
    by_head_bytes = (_fused_bwd_dq_bytes(s_q, d, q.dtype) +
                     _fused_bwd_dq_bytes(s_k, d, k.dtype) +
                     _fused_bwd_dq_bytes(s_k, d_v, v.dtype))
    fused = dq_bytes <= _FUSED_BWD_DQ_BYTES
    by_head = not fused and by_head_bytes <= _FUSED_BWD_DQ_BYTES
    one_launch = fused or by_head
    if tiles_w is not None:
        passes = 1 if one_launch else 2
        _TILES_NEEDED.inc(bh * tiles_w["needed"] * passes)
        _TILES_VISITED.inc(bh * (tiles_w["by_q"] + (
            0 if one_launch else tiles_w["by_k"])))
    if one_launch:
        if fused:
            _BACKWARD_FUSED.inc()
            grid, in_specs, held = (bh_kv, nk, group * nq_in), dkv_in_specs, \
                dq_bytes
            out_specs = [pl.BlockSpec((group, s_q, d),
                                      lambda bhi, ki, qi: (bhi, 0, 0)),
                         *by_k]
            scratch = [pltpu.VMEM(
                (s_q, d) if group == 1 else (group, s_q, d), jnp.float32),
                *dkv_scratch]
        else:
            _BACKWARD_FUSED_BY_HEAD.inc()
            # a query head at a time, its dQ the output block; dK and dV
            # whole in scratch and as output blocks, at the kv head's row
            grid, held = (bh_kv, group, nk, nq_in), by_head_bytes
            seq_q = [pl.BlockSpec((1, bq, w), lambda bhi, hd, ki, qi: q_tile(
                bhi * group + hd, ki, qi)) for w in (d, d_v, 1, 1)]
            seq_k = [pl.BlockSpec((1, bk, w),
                                  lambda bhi, hd, ki, qi: (bhi, ki, 0))
                     for w in (d, d_v)]
            in_specs = [seq_q[0], *seq_k, *seq_q[1:]]
            out_specs = [pl.BlockSpec((1, s_q, d), lambda bhi, hd, ki, qi: (
                bhi * group + hd, 0, 0))] + [
                pl.BlockSpec((1, s_k, w),
                             lambda bhi, hd, ki, qi: (bhi, 0, 0))
                for w in (d, d_v)]
            scratch = [pltpu.VMEM((rows, w), jnp.float32)
                       for rows, w in ((s_q, d), (s_k, d), (s_k, d_v))]
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, sm_scale=sm_scale,
                num_k_blocks=nk, by_head=by_head, **dkv_tiles),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=[dq_shape, *dkv_shapes],
            scratch_shapes=scratch,
            # the held gradients are carried across the sequence dims (by
            # head, dK and dV across the group too): only batch*kv heads is
            # parallel, and the scoped limit grows by what they take
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",) + ("arbitrary",) * (
                    len(grid) - 1),
                vmem_limit_bytes=_SCOPED_VMEM_BYTES + held),
            interpret=interpret,
        )(*operands)
    else:
        _BACKWARD_TWO_KERNEL.inc()
        # --- dQ: grid (bh, nq, nk_in), k innermost -------------------------
        if causal:
            # likewise the k blocks past a q tile's diagonal name the last
            # active one again (clamped at 0 as in _flash_forward)
            def k_index(bhi, qi, ki):
                last = jnp.maximum((off + (qi + 1) * bq - 1) // bk, 0)
                if window is not None:
                    ki = ki + _k_band(qi, bq, bk, off, window)[0]
                return (kv_row(bhi), jnp.minimum(ki, last), 0)
        else:
            def k_index(bhi, qi, ki):
                return (kv_row(bhi), ki, 0)
        dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_kernel, sm_scale=sm_scale, num_k_blocks=nk_in,
                **tiles),
            grid=(bh, nq, nk_in),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda bhi, qi, ki: (bhi, qi, 0)),
                pl.BlockSpec((1, bk, d), k_index),
                pl.BlockSpec((1, bk, d_v), k_index),
                pl.BlockSpec((1, bq, d_v), lambda bhi, qi, ki: (bhi, qi, 0)),
                pl.BlockSpec((1, bq, 1), lambda bhi, qi, ki: (bhi, qi, 0)),
                pl.BlockSpec((1, bq, 1), lambda bhi, qi, ki: (bhi, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda bhi, qi, ki: (bhi, qi, 0)),
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=None if interpret else _mosaic_params(),
            interpret=interpret,
        )(*operands)
        # --- dK/dV: grid (bh_kv, nk, group * nq_in), q innermost -----------
        dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_dkv_kernel, **dkv_tiles),
            grid=(bh_kv, nk, group * nq_in),
            in_specs=dkv_in_specs,
            out_specs=by_k,
            out_shape=dkv_shapes,
            scratch_shapes=dkv_scratch,
            compiler_params=None if interpret else _mosaic_params(),
            interpret=interpret,
        )(*operands)

    def unflat(a, s_len):
        return jnp.moveaxis(a.reshape(b, -1, s_len, a.shape[-1]), 1, 2)

    return unflat(dq, s_q), unflat(dk, s_k), unflat(dv, s_k)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention over (B, S, H, D); ``v`` may have a head size of its
    own (q and k (B, S, H, d_qk), v and the output (B, S, H, d_v)), as
    latent attention's training form has. k and v may have fewer heads
    than q (grouped queries: each run of H / H_kv query heads reads one kv
    head, through the kernels' index maps, with no copy of k or v for the
    group; dK and dV are summed over it inside the kernel). ``window``
    (causal only) keeps the keys ``0 <= t - j < window`` of query position
    t: the kernels' grids walk each tile's band and nothing outside it is
    fetched or computed; ``zoo_attention_window_tiles_total`` counts the
    tiles. Uses the Pallas kernel when the
    sequence tiles evenly (compiled on a TPU, interpret mode on the CPU
    backend), else the reference path — which on a TPU is logged and
    counted (``zoo_attention_reference_on_tpu_total``), never silent.

    Under differentiation the forward kernel's output and logsumexp are
    named ``FLASH_RESIDUAL_NAMES`` (see ``_flash_fwd``): inside a
    ``jax.checkpoint`` / ``nn.remat`` with
    ``save_only_these_names(*FLASH_RESIDUAL_NAMES)`` the backward kernels
    read the first launch's results and the forward kernel is not run
    again; under any other policy, or none, the names do nothing.

    Default 1024x1024 forward tiles: round-4 sweep on a v5e chip at
    S=4096/D=64-128 measured 1024x1024 fastest of {256..2048}x{512,1024}
    (bigger tiles amortize the per-tile softmax state and keep the MXU
    fed; 2048-wide tiles spill VMEM and regress). The backward caps its
    tiles at 512 internally (``_bwd_tile_sizes``: its working set is ~4
    score tiles) and is one launch, dQ accumulated beside dK/dV, wherever
    the dQ of a kv head's queries fits VMEM beside them, whole or, with
    the kv head's dK and dV, a query head at a time; where not even that is
    within ``_FUSED_BWD_DQ_BYTES`` it is a dQ launch and a dK/dV launch on
    the same tiles (``_flash_bwd``). fit_block below shrinks tiles for
    short/odd sequences."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s_q, s_k = q.shape[1], k.shape[1]
    if q.shape[2] % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads cannot share "
                         f"{k.shape[2]} key and {v.shape[2]} value heads")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal mask's: causal=True and "
                             "window >= 1")
        if window >= s_k:
            window = None                # every causal key is inside it

    def fit_block(s, want):
        # largest tile <= want that divides the sequence, so raising the
        # default never diverts a divisible-by-128 length off the kernel
        # (materializing O(S^2) scores) just because S % want != 0
        for cand in (want, 1024, 512, 256, 128, 64, 32, 16, 8):
            if cand <= want and s % cand == 0:
                return cand
        return None

    bq = fit_block(s_q, min(block_q, s_q))
    bk = fit_block(s_k, min(block_k, s_k))
    # causal s_q < s_k (decode-style) rides the kernel: fwd/bwd both mask
    # bottom-right aligned. s_q > s_k would leave some q rows with no
    # visible key (all -inf) — keep those on the reference path.
    interpret = _interpret()
    if bq is None or bk is None or (causal and s_q > s_k):
        if not interpret:
            _REFERENCE_ON_TPU.inc()
            logger.warning(
                "flash_attention: no kernel tile fits q%s k%s causal=%s; "
                "materializing O(S^2) scores through mha_reference on the "
                "TPU", tuple(q.shape), tuple(k.shape), causal)
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window)
    if interpret and varying_axes(q, k, v):
        # Interpret-mode pallas under shard_map: the HLO interpreter's
        # grid dynamic_slice rejects varying operands with invariant
        # indices for some (non-causal) shapes. The compiled kernel
        # handles vma (the union logic in _flash_forward); the CPU backend
        # uses the reference math.
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window)
    return _flash_attention(q, k, v, causal, sm_scale, bq, bk, window)
