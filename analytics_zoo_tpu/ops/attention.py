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

# what a block-diffusion call site's grids compute against what its mask keeps
_BD_ENTRIES = _REGISTRY.counter(
    "zoo_attention_bd_entries_total",
    "score entries of block-diffusion flash_attention call sites traced, "
    "forward and backward kernels, each at its own tile size: computed (the "
    "entries of the tiles the grids walk) and needed (the entries the mask "
    "keeps)", labelnames=("kind",))
_BD_COMPUTED = _BD_ENTRIES.labels(kind="computed")
_BD_NEEDED = _BD_ENTRIES.labels(kind="needed")
BLOCK_DIFFUSION = "block_diffusion"

# what a selected-key call site's walks compute against what its selection
# keeps: the selection is data, so it is read from a step's outputs
# (``selection_entries``) by whoever runs the step, not counted as traced
SA_ENTRIES = _REGISTRY.counter(
    "zoo_attention_sa_entries_total",
    "score entries of selected-key flash_attention call sites, a head and a "
    "forward pass, as the steps read so far computed them: computed (the "
    "entries of the tiles the walk visits) and needed (the selected "
    "entries)", labelnames=("kind",))
SELECTED = "selected"

# how much of a masked call site's grids is steps that compute nothing
_GRID_STEPS = _REGISTRY.counter(
    "zoo_attention_grid_steps_total",
    "grid steps of masked flash_attention call sites traced, forward and "
    "backward kernels, by mask (causal, window, block_diffusion): issued "
    "(the steps of the launches' grids) and computed (the steps whose tile "
    "holds an entry of the mask)", labelnames=("mask", "kind"))

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


def block_diffusion_mask(seq: int, block: int) -> np.ndarray:
    """The block-diffusion mask over ``seq`` positions ``[x_t | x_0]`` (a
    noisy copy of a sequence of ``seq / 2`` tokens, then the clean one), as a
    (seq, seq) boolean array, True where query i sees key j. With ``L = seq /
    2`` and ``blk(i) = (i mod L) // block``: a noisy query sees its own
    noisy block (both ways) and the clean blocks before its own; a clean
    query sees the clean blocks up to its own; no clean query sees a noisy
    key."""
    half = seq // 2
    pos = np.arange(seq)
    blk, noisy = (pos % half) // block, pos < half
    qb, kb = blk[:, None], blk[None, :]
    qn, kn = noisy[:, None], noisy[None, :]
    return (qn & kn & (qb == kb)) | (qn & ~kn & (kb < qb)) | \
        (~qn & ~kn & (kb <= qb))


def pack_selection(keep: jax.Array, rows: int) -> jax.Array:
    """A selection (B, S_q, S_k) bool, True where query t attends to key s,
    packed as (B, S_q / 32, S_k) uint32: the queries in groups of ``rows``
    (a kernel tile's height, a multiple of 32), and bit j of word w of a
    group is its row ``j * rows / 32 + w``, so that a tile's (rows / 32,
    block_k) words unpack into its (rows, block_k) entries by shifts and a
    stack along sublanes, never across lanes."""
    b, s_q, s_k = keep.shape
    r = rows // 32
    planes = keep.reshape(b, s_q // rows, 32, r, s_k).astype(jnp.uint32)
    shift = jnp.arange(32, dtype=jnp.uint32)[:, None, None]
    return jnp.sum(planes << shift, axis=2, dtype=jnp.uint32).reshape(
        b, s_q // 32, s_k)


def unpack_selection(bits: jax.Array, rows: int) -> jax.Array:
    """:func:`pack_selection`'s inverse: (B, S_q / 32, S_k) -> (B, S_q, S_k)
    bool."""
    b, w, s_k = bits.shape
    r = rows // 32
    words = bits.reshape(b, w // r, 1, r, s_k)
    planes = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None, None]) & 1
    return planes.reshape(b, w * 32, s_k).astype(bool)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  *, causal: bool = False, sm_scale: Optional[float] = None,
                  bias: Optional[jax.Array] = None,
                  window: Optional[int] = None, mask: Optional[str] = None,
                  block: Optional[int] = None,
                  selection: Optional[jax.Array] = None) -> jax.Array:
    """Plain materialised-scores attention. q,k: (B, S, H, D); v: (B, S, H,
    D) or a head size of its own. k and v may have fewer heads than q
    (grouped queries: each run of H / H_kv query heads shares one, repeated
    here); ``window`` keeps, of a causal mask, the keys ``0 <= t - j <
    window`` of query position t; ``mask="block_diffusion"`` (not causal)
    keeps :func:`block_diffusion_mask`'s entries for blocks of ``block``;
    ``mask="selected"`` the entries of ``selection``, packed in groups of
    ``block`` rows (:func:`pack_selection`)."""
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
        keep = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        if window is not None:
            keep &= ~jnp.tril(jnp.ones((s_q, s_k), dtype=bool),
                              k=s_k - s_q - window)
        logits = jnp.where(keep, logits, NEG_INF)
    elif mask == BLOCK_DIFFUSION:
        logits = jnp.where(block_diffusion_mask(logits.shape[-1], block),
                           logits, NEG_INF)
    elif mask == SELECTED:
        logits = jnp.where(unpack_selection(selection, block)[:, None],
                           logits, NEG_INF)
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


def _bd_k_walk(qi, block_q, block_k, half, block, xp=jnp):
    """The k blocks that q tile ``qi`` of a ``[noisy | clean]`` sequence of
    ``2 * half`` positions sees under the block-diffusion mask, as ``(first,
    n_diag, n_prefix)``: a noisy tile's own noisy keys are the ``n_diag``
    blocks from ``first`` on (none for a clean tile); then come the
    ``n_prefix`` clean blocks from the first on (the clean blocks before the
    tile's last row's block, or up to it for a clean tile). Tiles never
    straddle the two halves, and ``block`` divides them."""
    noisy = qi * block_q < half
    q0 = qi * block_q - xp.where(noisy, 0, half)   # its first row, in its half
    first = q0 // block_k
    n_diag = xp.where(noisy, (q0 + block_q - 1) // block_k - first + 1, 0)
    end = q0 + block_q - xp.where(noisy, block, 0)  # past its last clean key
    return first, n_diag, -(-end // block_k)


def _bd_k_block(qi, step, block_q, block_k, half, block):
    """``(k block, active)`` of ``step`` of q tile ``qi``'s walk (numpy):
    the walk's diagonal blocks, then its clean ones; a step past its last is
    inactive."""
    first, n_diag, n_prefix = _bd_k_walk(qi, block_q, block_k, half, block,
                                         np)
    return np.where(step < n_diag, first + step,
                    half // block_k + step - n_diag), \
        step < n_diag + n_prefix


def _bd_q_walk(ki, block_q, block_k, half, block, xp=jnp):
    """The q tiles that see a key of k block ``ki``: ``(first, n_noisy,
    first_clean, n_clean)``, the noisy tiles ``first ..`` then the clean
    tiles ``first_clean ..`` (absolute tile indices). A noisy block is seen
    by the noisy tiles over its own positions; a clean block by the noisy
    tiles whose last row's block lies past it and by the clean tiles from
    its own on."""
    nqh = half // block_q
    noisy = ki * block_k < half
    k0 = ki * block_k - xp.where(noisy, 0, half)
    first = xp.where(noisy, k0 // block_q, (k0 + block) // block_q)
    n_noisy = xp.where(noisy, (k0 + block_k - 1) // block_q - k0 // block_q
                       + 1, xp.maximum(nqh - first, 0))
    n_clean = xp.where(noisy, 0, nqh - k0 // block_q)
    return first, n_noisy, nqh + k0 // block_q, n_clean


def _bd_q_block(ki, step, block_q, block_k, half, block):
    """``(q tile, active)`` of ``step`` of k block ``ki``'s walk, as
    :func:`_bd_k_block` for k."""
    first, n_noisy, first_clean, n_clean = _bd_q_walk(ki, block_q, block_k,
                                                      half, block, np)
    return np.where(step < n_noisy, first + step,
                    first_clean + step - n_noisy), step < n_noisy + n_clean


def _bd_tiles(seq, block_q, block_k, half, block):
    """Static counts of a block-diffusion call's walks: ``k_steps`` /
    ``q_steps`` (the longest walk by k and by q), ``by_k`` / ``by_q``
    (tiles a head's walks by q tile / by k block compute: a tile list's
    entries, ``_bd_walk``) and ``needed`` (the score entries the mask keeps:
    L^2 + L * block a head, L = half)."""
    qi, ki = np.arange(seq // block_q), np.arange(seq // block_k)
    _, n_diag, n_prefix = _bd_k_walk(qi, block_q, block_k, half, block, np)
    _, n_noisy, _, n_clean = _bd_q_walk(ki, block_q, block_k, half, block, np)
    by_k, by_q = n_diag + n_prefix, n_noisy + n_clean
    return {"k_steps": int(by_k.max()), "q_steps": int(by_q.max()),
            "by_k": int(by_k.sum()), "by_q": int(by_q.sum()),
            "needed": half * half + half * block}


# a tile list's rows (``_bd_walk``), and the bits of its flags row
_Q, _K, _HEAD, _FLAGS = range(4)
_FIRST, _LAST, _DQ_LAST = 1, 2, 4


def _bd_walk(seq, block_q, block_k, half, block, by, group=1):
    """The tile list a block-diffusion grid walks, built at trace time: the
    (q tile, k block) pairs that hold an entry of the mask, in the order
    the kernels take them, so the grid's innermost extent is the list's
    length and no step of it computes nothing. ``by="q"``: each q tile's k
    blocks (:func:`_bd_k_block`), for the k-innermost kernels (the forward,
    the dQ kernel); ``by="k"``: each k block's q tiles
    (:func:`_bd_q_block`), for the q-innermost ones, the ``group`` query
    heads of a kv head one after the other under each k block.

    One flat int32 array of four rows, ``[q tile | k block | head | flags]``
    (:func:`_walk_at`), the kernels' scalar-prefetch operand: ``_FIRST`` /
    ``_LAST`` mark the first and last entry of a q tile's walk (``by="q"``)
    or of a k block's over all the heads (``by="k"``); ``_DQ_LAST`` the
    entries whose k block is the last of the q tile's own walk, after which
    its dQ rows are whole."""
    t = _bd_tiles(seq, block_q, block_k, half, block)
    if by == "q":
        run, step = np.meshgrid(np.arange(seq // block_q),
                                np.arange(t["k_steps"]), indexing="ij")
        k, active = _bd_k_block(run, step, block_q, block_k, half, block)
        q = run
    else:
        run, step = np.meshgrid(np.arange(seq // block_k),
                                np.arange(t["q_steps"]), indexing="ij")
        q, active = _bd_q_block(run, step, block_q, block_k, half, block)
        k = run
    diag0, n_diag, n_prefix = _bd_k_walk(np.arange(seq // block_q), block_q,
                                         block_k, half, block, np)
    last_k = np.where(n_prefix > 0, half // block_k + n_prefix - 1,
                      diag0 + n_diag - 1)
    return _walk_rows(q[active], k[active], run[active], last_k, group)


def _causal_walk(n, by, group=1):
    """The tile list a selected-key grid walks, built at trace time: every
    (q tile, k block) on or below the diagonal of ``n`` square tiles, in
    :func:`_bd_walk`'s order, rows and flags (``by="q"``: each q tile's k
    blocks ascending; ``by="k"``: each k block's q tiles, the ``group`` query
    heads in turn). Each entry is masked by the selection's bits."""
    tri = np.tri(n, dtype=bool)
    run, other = np.nonzero(tri if by == "q" else tri.T)
    q, k = (run, other) if by == "q" else (other, run)
    return _walk_rows(q, k, run, np.arange(n), group)


def _walk_rows(q, k, run, last_k, group):
    """A tile list's four rows from its entries in walk order (``run`` the
    q tile or k block each belongs to), each run once a head of the
    ``group`` in turn; ``last_k`` each q tile's last k block."""
    runs = np.split(np.arange(run.size), np.flatnonzero(np.diff(run)) + 1)
    at = np.concatenate([r for r in runs for _ in range(group)])
    head = np.concatenate([np.full(r.size, hd) for r in runs
                           for hd in range(group)])
    q, k, run = q[at], k[at], run[at]
    first = np.diff(run, prepend=-1) != 0
    last = np.append(first[1:], True)
    flags = _FIRST * first + _LAST * last + _DQ_LAST * (k == last_k[q])
    return np.concatenate([q, k, head, flags]).astype(np.int32)


def _walk_at(walk, e, row):
    """Row ``row`` (``_Q``, ``_K``, ``_HEAD``, ``_FLAGS``) of entry ``e`` of
    a tile list (:func:`_bd_walk`), read from its scalar-prefetch ref in a
    kernel body or an index map."""
    return walk[row * (walk.shape[0] // 4) + e]


def _walk_flag(walk, e, bit):
    """Whether entry ``e`` of a tile list carries the flag ``bit``."""
    return jnp.bitwise_and(_walk_at(walk, e, _FLAGS), bit) != 0


def selection_entries(bits, block: int):
    """``(computed, needed)`` score entries of a selected-key call a head
    and a forward pass, float32: the entries of the causal tiles its walk
    visits (``block`` square) and the selected ones (on the device)."""
    n = bits.shape[-1] // block
    needed = jnp.sum(lax.population_count(bits).astype(jnp.float32))
    return jnp.float32(n * (n + 1) // 2 * block * block), needed


def _sel_keep(words, shape):
    """A (rows, block_k) tile's selected entries from its (rows / 32,
    block_k) int32 words (:func:`pack_selection`): each bit plane shifted
    down and the 32 planes stacked along sublanes."""
    planes = jnp.broadcast_to(words[None], (32, *words.shape))
    shift = lax.broadcasted_iota(jnp.int32, planes.shape, 0)
    return (lax.shift_right_logical(planes, shift) & 1).reshape(shape) != 0


def _sel_masked(bits_ref):
    """A selected-key tile's mask, from its words in ``bits_ref``."""
    return lambda shape: _sel_keep(bits_ref[0], shape)


def _split_walk(refs, bd, sel=False):
    """A kernel's tile-list ref, its selection's ref and its other refs:
    with ``bd`` or ``sel`` the list is the launch's scalar-prefetch operand,
    the first of its refs; with ``sel`` the packed selection is the
    second."""
    if sel:
        return refs[0], refs[1], refs[2:]
    return (refs[0], None, refs[1:]) if bd is not None else \
        (None, None, refs)


def _launch(kernel, operands, walk=None, *, grid, in_specs, out_specs,
            scratch_shapes, **call):
    """``pallas_call`` of ``kernel`` over ``operands``. Given a tile list
    ``walk`` (:func:`_bd_walk`) the grid walks it: the list is the first
    operand, prefetched into scalar memory, where every index map (given it
    after the grid's indices) and the kernel body (the first of its refs)
    read it."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    specs = dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                 scratch_shapes=scratch_shapes)
    if walk is not None:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
        operands = (mark_varying(jnp.asarray(walk),
                                 varying_axes(*operands)), *operands)
    return pl.pallas_call(kernel, **specs, **call)(*operands)


def _tile_counts(s_q, s_k, block_q, block_k, causal, window, bd):
    """``(mask, tiles)`` of a call at these tile sizes: the mask's name in
    ``zoo_attention_grid_steps_total`` and its static counts
    (:func:`_window_tiles`, :func:`_bd_tiles`; a causal mask's ``by_k`` /
    ``by_q`` alone, the tiles on or below the diagonal); ``(None, None)``
    for a call with no mask."""
    if bd is not None:
        return BLOCK_DIFFUSION, _bd_tiles(s_q, block_q, block_k, *bd)
    if window is not None:
        return "window", _window_tiles(s_q, s_k, block_q, block_k, window)
    if not causal:
        return None, None
    last = (s_k - s_q + (np.arange(s_q // block_q) + 1) * block_q - 1) \
        // block_k
    n = int(np.sum(last + 1))
    return "causal", {"by_k": n, "by_q": n}


def _count_grid(mask, grid, computed):
    """A launch of a masked call site: its grid's steps (issued) and the
    steps whose tile computes (computed)."""
    _GRID_STEPS.labels(mask=mask, kind="issued").inc(math.prod(grid))
    _GRID_STEPS.labels(mask=mask, kind="computed").inc(computed)


def _bd_keep(shape, q0, k0, lim, block, diag):
    """A (block_q, block_k) tile's visible entries, its first query ``q0``
    and first key ``k0`` counted in their halves: in a noisy key tile
    (``diag``) the query's own block; in a clean one the keys before the
    query's block start plus ``lim`` (0 for a noisy query, ``block`` for a
    clean one). ``block`` is a power of two."""
    q = q0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    k = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    start = q - jnp.bitwise_and(q, block - 1)
    if diag:
        return (k >= start) & (k < start + block)
    return k < start + lim


def _bd_compute(compute, q_start, k_start, block_k, bd):
    """Run ``compute(masked)`` on a tile of a block-diffusion tile list
    (every entry holds an entry of the mask): with the diagonal's mask on a
    noisy key tile, with the prefix's where the edge of the visible keys
    crosses a clean tile, unmasked inside it."""
    import jax.experimental.pallas as pl
    half, block = bd
    q_noisy, diag = q_start < half, k_start < half
    q0 = q_start - jnp.where(q_noisy, 0, half)
    k0 = k_start - jnp.where(diag, 0, half)
    lim = jnp.where(q_noisy, 0, block)
    interior = jnp.logical_not(diag) & (k0 + block_k <= q0 + lim)

    def masked(on_diag):
        return lambda shape: _bd_keep(shape, q0, k0, lim, block, on_diag)

    pl.when(diag)(lambda: compute(masked(True)))
    pl.when(jnp.logical_not(diag) & jnp.logical_not(interior))(
        lambda: compute(masked(False)))
    pl.when(interior)(lambda: compute(False))


def _flash_kernel(*refs, block_q, block_k, num_k_blocks, causal, head_dim,
                  q_offset=0, with_lse=False, ones_column=True, window=None,
                  bd=None, sel=False):
    """Grid = (batch*heads, num_q_blocks, num_k_blocks); the k dim is innermost
    so (acc, m) scratch carries the online softmax across k iterations.
    ``refs``: q, k, v, the output [, the logsumexp], the scratch. With
    ``with_lse`` the kernel also emits the log2-domain logsumexp
    (m + log2 l) per q row, which the Pallas backward consumes.

    With a ``window`` the k dim walks a q tile's band alone:
    ``num_k_blocks`` is the widest band's blocks, step j is the block
    ``_k_band(...)[0] + j``, and the steps past the diagonal's block are
    the shorter bands' padding (no compute; the index map names the
    diagonal's block again, so nothing is fetched). A row whose first
    blocks are all outside its window carries m = NEG_INF and p = 1
    through them; its first block with a visible key rescales that to
    exactly 0. With ``bd`` (``(half, block)``: the block-diffusion mask) the
    grid is (batch*heads, tiles) and step e is entry e of a tile list
    (``_bd_walk(by="q")``, the first of ``refs``): each q tile's diagonal
    and clean blocks in turn, every step a tile that computes. With ``sel``
    the list is a selected-key call's (``_causal_walk(by="q")``), the packed
    selection's tile the second of ``refs``, and each entry is masked by
    its bit (a row's first tiles may hold none of its keys, as a window's
    band may).

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

    walk, bits, (q_ref, k_ref, v_ref, o_ref, *rest) = _split_walk(refs, bd,
                                                                  sel)
    if with_lse:
        lse_ref, *rest = rest
    if ones_column:
        acc_ref, m_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    if walk is not None:
        e = pl.program_id(1)
        q_idx = _walk_at(walk, e, _Q)
        first = _walk_flag(walk, e, _FIRST)
    else:
        q_idx = pl.program_id(1)
        k_idx = pl.program_id(2)
        first = k_idx == 0

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        if not ones_column:
            l_ref[...] = jnp.zeros_like(l_ref)

    q_start = q_idx * block_q
    if walk is not None:
        k_start = _walk_at(walk, e, _K) * block_k
    else:
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
        if callable(masked):     # a block-diffusion or selected tile's
            s = jnp.where(masked(s.shape), s, NEG_INF)
        elif masked:
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

    if sel:
        _compute(_sel_masked(bits))
    elif bd is not None:
        _bd_compute(_compute, q_start, k_start, block_k, bd)
    elif causal:
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

    @pl.when(_walk_flag(walk, e, _LAST) if walk is not None
             else k_idx == num_k_blocks - 1)
    def _finalize():
        acc = acc_ref[...]
        l = acc[:, head_dim:head_dim + 1] if ones_column else l_ref[:, :1]
        l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc[:, :head_dim] / l).astype(o_ref.dtype)
        if with_lse:
            # p_ij = exp2(s2_ij - L2_i) with L2 = m + log2 l (log2 domain)
            lse_ref[0] = m_ref[:, :1] + jnp.log2(l)


@functools.lru_cache(maxsize=None)
def _mosaic_params(dims=3):
    """Grid dimension semantics of the forward, dQ and dK/dV kernels: dims
    0/1 (batch*heads and the non-carry sequence dim) are parallel, the
    innermost dim carries online-softmax / accumulator state and must stay
    ordered. Parallel dims let Mosaic overlap the next tile's DMA with the
    current tile's compute instead of treating the whole grid as one
    sequential loop. (The fused backward carries dQ across both sequence
    dims, by head dK and dV across the group too, and states its own; a
    tile list's grid has ``dims=2``: the list carries the state.)"""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (dims - 1) + ("arbitrary",))


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


def _selection_words(selection):
    """The packed selection as the kernels read it: int32, whose shifts
    Mosaic lowers, with the same bits."""
    return lax.bitcast_convert_type(selection, jnp.int32)


def _selection_spec(block_q, block_k, heads, lead=1):
    """The packed selection's tile for a tile-list entry: the words of its
    q tile's rows and k block's keys, in the row's sequence of the batch (a
    grid's first index over ``heads`` rows a sequence; ``lead`` grid
    indices before the list's)."""
    import jax.experimental.pallas as pl

    def index(row, *rest):
        e, walk = rest[lead - 1], rest[-1]
        return (row // heads, _walk_at(walk, e, _Q), _walk_at(walk, e, _K))
    return pl.BlockSpec((1, block_q // 32, block_k), index)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                   with_lse=False, window=None, bd=None, selection=None):
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
    mask, tiles = _tile_counts(s_q, s_k, block_q, block_k, causal, window,
                               bd)
    if window is not None:
        # the k dim walks each q tile's band: its widest band's blocks
        num_k = tiles["k_steps"]
        _TILES_VISITED.inc(b * h * tiles["by_k"])
        _TILES_NEEDED.inc(b * h * tiles["needed"])
    grid = (b * h, num_q, num_k)
    walk = None
    if bd is not None:
        # the grid walks a tile list: each q tile's diagonal and clean blocks
        grid = (b * h, tiles["by_k"])
        _BD_COMPUTED.inc(b * h * tiles["by_k"] * block_q * block_k)
        _BD_NEEDED.inc(b * h * tiles["needed"])
        walk = _bd_walk(s_q, block_q, block_k, *bd, by="q")
    sel = selection is not None
    if sel:
        # each q tile's causal k blocks, every entry masked by its bits
        walk = _causal_walk(s_q // block_q, "q")
        grid = (b * h, walk.shape[0] // 4)
    if mask is not None:
        _count_grid(mask, grid, b * h * tiles["by_k"])

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        num_k_blocks=num_k, causal=causal, head_dim=d_v,
        q_offset=s_k - s_q, with_lse=with_lse, ones_column=ones_column,
        window=window, bd=bd, sel=sel)
    # Under shard_map (e.g. Ulysses sequence parallelism) the output must
    # declare which mesh axes it varies over. Use the union of the inputs'
    # varying sets and lift any less-varying input up to it so mixed-vma
    # call sites (e.g. cross-attention with replicated q) still compile.
    vma = varying_axes(qf, kf, vf)
    qf, kf, vf = (mark_varying(a, vma) for a in (qf, kf, vf))
    if walk is not None:
        # an index map is given the tile list after the grid's indices
        def q_index(bh, e, walk):
            return (bh, _walk_at(walk, e, _Q), 0)
    else:
        def q_index(bh, qi, ki):
            return (bh, qi, 0)
    out_shape = [jax.ShapeDtypeStruct((b * h, s_q, d_v), q.dtype, vma=vma)]
    out_specs = [pl.BlockSpec((1, block_q, d_v), q_index)]
    if with_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32, vma=vma))
        out_specs.append(pl.BlockSpec((1, block_q, 1), q_index))
    if walk is not None:
        def k_index(bh, e, walk):
            return (kv_row(bh), _walk_at(walk, e, _K), 0)
    elif causal:
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

    operands, in_specs = (qf, kf, vf), [
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_k, d), k_index),
        pl.BlockSpec((1, block_k, d_acc), k_index),
    ]
    if sel:
        operands = (_selection_words(selection), *operands)
        in_specs = [_selection_spec(block_q, block_k, h)] + in_specs
    res = _launch(
        kernel, operands, walk,
        grid=grid,
        in_specs=in_specs,
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
        compiler_params=None if interpret else _mosaic_params(len(grid)),
        interpret=interpret,
    )
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k, window,
                     bd):
    return _flash_forward(q, k, v, causal, sm_scale, block_q, block_k,
                          _interpret(), window=window, bd=bd)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, window=None,
               bd=None):
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
                              interpret, with_lse=True, window=window,
                              bd=bd)
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _bwd_tile(masked, q2, k, v, g, L, D, q_offset, q_start, k_start, cd,
              window=None):
    """Shared (block_q, block_k) backward tile: rebuild P from (q2, k, L),
    then ds = P*(dP - D). All matmuls keep the input dtype (bf16 rides the
    MXU) with f32 accumulation; returns (p, ds) in compute dtype ``cd``.
    ``masked``: False, True (the causal mask and window) or a block-diffusion
    tile's ``keep(shape)``."""
    s2 = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if callable(masked):
        s2 = jnp.where(masked(s2.shape), s2, NEG_INF)
    elif masked:
        q_pos = (q_offset + q_start +
                 lax.broadcasted_iota(jnp.int32, s2.shape, 0))
        k_pos = k_start + lax.broadcasted_iota(jnp.int32, s2.shape, 1)
        s2 = jnp.where(_visible(q_pos, k_pos, window), s2, NEG_INF)
    p = jnp.exp2(s2 - L)                             # true softmax probs
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = (p * (dp - D)).astype(cd)
    return p.astype(cd), ds


def _flash_bwd_dq_kernel(*refs, sm_scale, block_q, block_k, num_k_blocks,
                         causal, q_offset, cd, window=None, bd=None,
                         sel=False):
    """dQ pass: grid (batch*heads, num_q, num_k), k innermost; the dq tile
    accumulates across k iterations in VMEM scratch — no (S, S) tensor
    ever reaches HBM (the round-3 pure-JAX backward streamed every P/dS
    tile through HBM between the dot_generals, which bounded fwd+bwd at
    ~1.4x materialized; tiles resident in VMEM are the FA-2 design). Runs
    only where not even one query head's gradients fit the fused kernel's
    VMEM budget (``_flash_bwd``). ``refs``: q2, k, v, g, L, D, dq, the
    scratch. With a ``window`` the k dim walks the q tile's band; with
    ``bd`` or ``sel`` the grid walks a tile list (``_bd_walk(by="q")``,
    ``_causal_walk(by="q")``), as in ``_flash_kernel``."""
    import jax.experimental.pallas as pl

    walk, bits, (q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref, dq_ref,
                 acc_ref) = _split_walk(refs, bd, sel)
    if walk is not None:
        e = pl.program_id(1)
        q_idx = _walk_at(walk, e, _Q)
        first = _walk_flag(walk, e, _FIRST)
    else:
        q_idx = pl.program_id(1)
        k_idx = pl.program_id(2)
        first = k_idx == 0

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_idx * block_q
    if walk is not None:
        k_start = _walk_at(walk, e, _K) * block_k
    else:
        k_start = _k_start(q_idx, k_idx, block_q, block_k, q_offset, window)

    def _compute(masked):
        _, ds = _bwd_tile(masked, q2_ref[0], k_ref[0], v_ref[0],
                          g_ref[0], L_ref[0], D_ref[0], q_offset, q_start,
                          k_start, cd, window)
        acc_ref[...] += jnp.dot(ds, k_ref[0],
                                preferred_element_type=jnp.float32)

    if sel:
        _compute(_sel_masked(bits))
    elif bd is not None:
        _bd_compute(_compute, q_start, k_start, block_k, bd)
    elif causal:
        active, diagonal = _tile_class(q_offset + q_start, k_start, block_q,
                                       block_k, window)
        pl.when(active & diagonal)(lambda: _compute(True))
        pl.when(active & jnp.logical_not(diagonal))(lambda: _compute(False))
    else:
        _compute(False)

    @pl.when(_walk_flag(walk, e, _LAST) if walk is not None
             else k_idx == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, block_q, block_k, num_q_blocks, causal,
                          q_offset, cd, dq=None, window=None, group=1,
                          seq_q_blocks=None, by_head=False, bd=None,
                          sel=False):
    """dK/dV pass: grid (batch*kv heads, num_k, group * num_q), the
    innermost dim walking the q tiles of each of the ``group`` query heads
    that share this kv head in turn, so dK and dV are summed over the
    group where they are made; both accumulators live in VMEM scratch.
    ``refs``: q2, k, v, g, L, D, dk, dv, their accumulators.
    dv += P^T g and dk += dS^T q2 are expressed as dot_generals contracting
    the q (sublane) dim. q2 is the log2-prescaled q, so dk carries a
    1/log2(e) correction at finalize. With a ``window``, ``num_q_blocks``
    is the widest band's tiles and step j of a head is the tile
    ``_q_band(...)[0] + j``, the steps past the band's last tile padding;
    ``seq_q_blocks`` is then the sequence's tiles. With ``bd`` the grid is
    (batch*kv heads, tiles) and step e is entry e of a tile list
    (``_bd_walk(by="k", group=group)``, the first of ``refs``): each k
    block's q tiles (:func:`_bd_q_block`), a query head's after another's;
    by head (below) the list is one head's and the grid (batch*kv heads,
    group, tiles). With ``sel`` the list is a selected-key call's
    (``_causal_walk(by="k")``) and the packed selection's tile the second of
    ``refs``.

    ``dq`` is the fused kernel's (see ``_flash_bwd_fused_kernel``): given,
    each tile's dS also goes into dQ. ``by_head`` is its grid for a group
    whose whole dQ does not fit VMEM: (batch*kv heads, group, num_k,
    num_q), a query head at a time. For a fixed k block the tiles still
    arrive head by head and, inside a head, q tile by q tile (``step``), but
    between two heads every other k block passes: dK and dV accumulate in
    scratch for the whole kv sequence, at the k block's rows, and their
    output blocks are the kv head's whole sequence."""
    import jax.experimental.pallas as pl

    walk, bits, (q2_ref, k_ref, v_ref, g_ref, L_ref, D_ref, dk_ref, dv_ref,
                 dk_acc, dv_acc) = _split_walk(refs, bd, sel)
    k_at, out_k_at = Ellipsis, 0      # all of dK's and dV's scratch and block
    if walk is not None:
        # a head's (by head) or the group's dQ begins with the list
        e = dq_step = pl.program_id(2 if by_head else 1)
        k_idx, q_idx = _walk_at(walk, e, _K), _walk_at(walk, e, _Q)
        # the k block's first and last entry over the group
        begins, ends = _walk_flag(walk, e, _FIRST), _walk_flag(walk, e, _LAST)
        if by_head:
            head = pl.program_id(1)
            begins &= head == 0
            ends &= head == group - 1
        else:
            head = _walk_at(walk, e, _HEAD) if group > 1 else 0
    elif by_head:
        head, k_idx, q_idx = (pl.program_id(i) for i in (1, 2, 3))
        step = head * num_q_blocks + q_idx
        dq_step = q_idx                  # a head's dQ begins with the head
    else:
        k_idx, step = pl.program_id(1), pl.program_id(2)
        if group == 1:
            head, q_idx = 0, step
        else:
            head, q_idx = step // num_q_blocks, step % num_q_blocks
        dq_step = step                   # the group's with the kv head
    if by_head:
        # of the whole kv sequence's, this k block's rows
        k_at = (pl.ds(pl.multiple_of(k_idx * block_k, block_k), block_k),
                slice(None))
        out_k_at = (0, *k_at)

    @pl.when(begins if walk is not None else step == 0)
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

        if window is None and walk is None:
            @pl.when(k_idx == 0)
            def _init_dq():
                dq_acc[acc_at] = jnp.zeros((block_q, dq_acc.shape[-1]),
                                           dq_acc.dtype)
        else:
            # a band's (or a block-diffusion) walk does not pass every q
            # tile under the first k block: the whole accumulator is cleared
            # as its kv head (by head: its query head) begins
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

    if walk is not None:
        if sel:
            _compute(_sel_masked(bits))
        else:
            _bd_compute(_compute, q_start, k_start, block_k, bd)
        if dq is not None:
            # a q tile's rows are whole once the last k block of its walk
            # has passed them
            pl.when(_walk_flag(walk, e, _DQ_LAST))(_write_dq)
    elif causal:
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

    @pl.when(ends if walk is not None else step == group * num_q_blocks - 1)
    def _finalize():
        dk_ref[out_k_at] = (dk_acc[k_at] *
                            (1.0 / LOG2_E)).astype(dk_ref.dtype)
        dv_ref[out_k_at] = dv_acc[k_at].astype(dv_ref.dtype)

    if dq is not None and window is None and walk is None:
        # a q tile's rows are whole once the last k block has passed them,
        # masked tiles too: written a tile at a time, never the whole
        # sequence in one statement
        pl.when(k_idx == num_k_blocks - 1)(_write_dq)


def _flash_bwd_fused_kernel(*refs, sm_scale, num_k_blocks, **tiles):
    """The whole backward in one launch: the dK/dV pass (same grid, q
    innermost) whose every tile also adds dS . k into a float32 accumulator
    for ALL the queries that share this (batch, kv head), (group, s_q, d)
    in VMEM scratch, at the tile's rows. Each (block_q, block_k) score
    tile, its exp2 and dP are rebuilt once a step, not once in each of two
    kernels. ``refs``: [the tile list,] q2, k, v, g, L, D, dq, dk, dv,
    their accumulators. ``dq_ref`` is the whole (group, s_q, d) output
    block, indexed by batch*kv heads alone. With ``by_head`` (among
    ``tiles``) the grid takes the group's query heads one after the other,
    the accumulator and ``dq_ref`` are one head's (s_q, d), and dK and dV
    wait in VMEM for the group's last head. Either way a fixed q tile's
    contributions arrive in ascending k order, as in
    ``_flash_bwd_dq_kernel``, and a fixed k block's in
    ``_flash_bwd_dkv_kernel``'s: the three gradients equal the pair's to
    the bit."""
    *ins, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    _flash_bwd_dkv_kernel(*ins, dk_ref, dv_ref, dk_acc, dv_acc,
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


def _flash_bwd(causal, sm_scale, block_q, block_k, window, bd, res, g,
               selection=None):
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
    (``_window_tiles``). With ``bd`` every grid walks a tile list instead
    (``_bd_walk``): ``fused`` (batch*kv heads, the group's tiles),
    ``fused_by_head`` (batch*kv heads, group, a head's tiles), the pair's
    dQ (batch*heads, tiles by q tile) and dK/dV (batch*kv heads, the
    group's tiles by k block). With a ``selection`` (the packed selected
    keys, square tiles) they walk the causal tiles (``_causal_walk``),
    every entry masked by its bit."""
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
    mask, tiles_m = _tile_counts(s_q, s_k, bq, bk, causal, window, bd)
    # innermost extents: every tile, or with a window the widest band's
    nq_in, nk_in = nq, nk
    if window is not None:
        nq_in, nk_in = tiles_m["q_steps"], tiles_m["k_steps"]
    sel = selection is not None
    walked = bd is not None or sel
    if sel:
        # the tiles on or below the diagonal
        tiles_m = {"by_q": nq * (nq + 1) // 2, "by_k": nq * (nq + 1) // 2}

    def tile_list(by, heads=1):
        """The tile list a launch walks (``bd``, ``sel``), else None."""
        if sel:
            return _causal_walk(nq, by, heads)
        return None if bd is None else _bd_walk(s_q, bq, bk, *bd, by=by,
                                                group=heads)

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
                 cd=cd, window=window, bd=bd, sel=sel)
    if sel:
        operands = (_selection_words(selection), *operands)
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

    if walked:
        # the dK/dV and fused kernels walk (bh_kv, the group's tiles): each
        # entry names its q tile, k block and query head
        def q_index(bhi, e, walk):
            row = bhi if group == 1 else \
                bhi * group + _walk_at(walk, e, _HEAD)
            return (row, _walk_at(walk, e, _Q), 0)

        def kv_index(bhi, e, walk):
            return (bhi, _walk_at(walk, e, _K), 0)
    else:
        # the dK/dV and fused kernels walk (bh_kv, nk, group * nq_in), q
        # innermost
        def q_index(bhi, ki, st):
            if group == 1:
                return q_tile(bhi, ki, st)
            return q_tile(bhi * group + st // nq_in, ki, st % nq_in)

        def kv_index(bhi, ki, qi):
            return (bhi, ki, 0)
    by_q = [pl.BlockSpec((1, bq, w), q_index)
            for w in (d, d_v, 1, 1)]                     # q2, g, lse, D
    by_k = [pl.BlockSpec((1, bk, w), kv_index)
            for w in (d, d_v)]                           # k | dk, v | dv
    dkv_in_specs = [by_q[0], *by_k, *by_q[1:]]
    if sel:
        dkv_in_specs = [_selection_spec(bq, bk, h_kv)] + dkv_in_specs
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
    if window is not None or bd is not None:
        passes = 1 if one_launch else 2
        visited = tiles_m["by_q"] + (0 if one_launch else tiles_m["by_k"])
        if bd is not None:
            _BD_NEEDED.inc(bh * tiles_m["needed"] * passes)
            _BD_COMPUTED.inc(bh * bq * bk * visited)
        else:
            _TILES_NEEDED.inc(bh * tiles_m["needed"] * passes)
            _TILES_VISITED.inc(bh * visited)
    # the q-innermost grids: (bh_kv, nk, group * nq_in), or a tile list's
    dkv_grid = (bh_kv, nk, group * nq_in) if not walked else \
        (bh_kv, group * tiles_m["by_q"])
    if one_launch:
        if fused:
            _BACKWARD_FUSED.inc()
            grid, in_specs, held = dkv_grid, dkv_in_specs, dq_bytes
            tiles_in = tile_list("k", group)
            # the group's whole dQ, whatever the later grid indices
            out_specs = [pl.BlockSpec((group, s_q, d),
                                      lambda bhi, *_: (bhi, 0, 0)),
                         *by_k]
            scratch = [pltpu.VMEM(
                (s_q, d) if group == 1 else (group, s_q, d), jnp.float32),
                *dkv_scratch]
        else:
            _BACKWARD_FUSED_BY_HEAD.inc()
            # a query head at a time, its dQ the output block; dK and dV
            # whole in scratch and as output blocks, at the kv head's row
            grid, held = (bh_kv, group, nk, nq_in), by_head_bytes
            tiles_in = tile_list("k")
            if walked:
                # a head walks the tile list, one head's
                grid = (bh_kv, group, tiles_m["by_q"])
                seq_q = [pl.BlockSpec((1, bq, w), lambda bhi, hd, e, walk: (
                    bhi * group + hd, _walk_at(walk, e, _Q), 0))
                    for w in (d, d_v, 1, 1)]
                seq_k = [pl.BlockSpec((1, bk, w), lambda bhi, hd, e, walk: (
                    bhi, _walk_at(walk, e, _K), 0)) for w in (d, d_v)]
            else:
                seq_q = [pl.BlockSpec((1, bq, w),
                                      lambda bhi, hd, ki, qi: q_tile(
                                          bhi * group + hd, ki, qi))
                         for w in (d, d_v, 1, 1)]
                seq_k = [pl.BlockSpec((1, bk, w),
                                      lambda bhi, hd, ki, qi: (bhi, ki, 0))
                         for w in (d, d_v)]
            in_specs = [seq_q[0], *seq_k, *seq_q[1:]]
            if sel:
                in_specs = [_selection_spec(bq, bk, h_kv, lead=2)] + in_specs
            out_specs = [pl.BlockSpec((1, s_q, d), lambda bhi, hd, *_: (
                bhi * group + hd, 0, 0))] + [
                pl.BlockSpec((1, s_k, w), lambda bhi, *_: (bhi, 0, 0))
                for w in (d, d_v)]
            scratch = [pltpu.VMEM((rows, w), jnp.float32)
                       for rows, w in ((s_q, d), (s_k, d), (s_k, d_v))]
        if mask is not None:
            _count_grid(mask, grid, bh * tiles_m["by_q"])
        dq, dk, dv = _launch(
            functools.partial(
                _flash_bwd_fused_kernel, sm_scale=sm_scale,
                num_k_blocks=nk, by_head=by_head, **dkv_tiles),
            operands, tiles_in,
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
        )
    else:
        _BACKWARD_TWO_KERNEL.inc()
        # --- dQ: grid (bh, nq, nk_in), k innermost, or a tile list's -------
        if walked:
            dq_grid = (bh, tiles_m["by_k"])

            def row_index(bhi, e, walk):
                return (bhi, _walk_at(walk, e, _Q), 0)

            def k_index(bhi, e, walk):
                return (kv_row(bhi), _walk_at(walk, e, _K), 0)
        else:
            dq_grid = (bh, nq, nk_in)

            def row_index(bhi, qi, ki):
                return (bhi, qi, 0)
            if causal:
                # likewise the k blocks past a q tile's diagonal name the
                # last active one again (clamped at 0 as in _flash_forward)
                def k_index(bhi, qi, ki):
                    last = jnp.maximum((off + (qi + 1) * bq - 1) // bk, 0)
                    if window is not None:
                        ki = ki + _k_band(qi, bq, bk, off, window)[0]
                    return (kv_row(bhi), jnp.minimum(ki, last), 0)
            else:
                def k_index(bhi, qi, ki):
                    return (kv_row(bhi), ki, 0)
        if mask is not None:
            _count_grid(mask, dq_grid, bh * tiles_m["by_k"])
            _count_grid(mask, dkv_grid, bh * tiles_m["by_q"])
        dq = _launch(
            functools.partial(
                _flash_bwd_dq_kernel, sm_scale=sm_scale, num_k_blocks=nk_in,
                **tiles),
            operands, tile_list("q"),
            grid=dq_grid,
            in_specs=([_selection_spec(bq, bk, h)] if sel else []) + [
                pl.BlockSpec((1, bq, d), row_index),
                pl.BlockSpec((1, bk, d), k_index),
                pl.BlockSpec((1, bk, d_v), k_index),
                pl.BlockSpec((1, bq, d_v), row_index),
                pl.BlockSpec((1, bq, 1), row_index),
                pl.BlockSpec((1, bq, 1), row_index),
            ],
            out_specs=pl.BlockSpec((1, bq, d), row_index),
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=None if interpret else _mosaic_params(
                len(dq_grid)),
            interpret=interpret,
        )
        # --- dK/dV: grid (bh_kv, nk, group * nq_in), q innermost -----------
        dk, dv = _launch(
            functools.partial(_flash_bwd_dkv_kernel, **dkv_tiles),
            operands, tile_list("k", group),
            grid=dkv_grid,
            in_specs=dkv_in_specs,
            out_specs=by_k,
            out_shape=dkv_shapes,
            scratch_shapes=dkv_scratch,
            compiler_params=None if interpret else _mosaic_params(
                len(dkv_grid)),
            interpret=interpret,
        )

    def unflat(a, s_len):
        return jnp.moveaxis(a.reshape(b, -1, s_len, a.shape[-1]), 1, 2)

    return unflat(dq, s_q), unflat(dk, s_k), unflat(dv, s_k)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_selected(q, k, v, bits, sm_scale, block):
    return _flash_forward(q, k, v, False, sm_scale, block, block,
                          _interpret(), with_lse=True, selection=bits)


def _flash_selected_fwd(q, k, v, bits, sm_scale, block):
    """As :func:`_flash_fwd`, the output and the logsumexp both results
    (a caller reads the logsumexp under ``stop_gradient``), and the packed
    selection a residual beside them."""
    out, lse = _flash_forward(q, k, v, False, sm_scale, block, block,
                              _interpret(), with_lse=True, selection=bits)
    out = checkpoint_name(out, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return (out, lse), (q, k, v, out, lse, bits)


def _flash_selected_bwd(sm_scale, block, res, g):
    *res, bits = res
    dq, dk, dv = _flash_bwd(False, sm_scale, block, block, None, None,
                            tuple(res), g[0], selection=bits)
    return dq, dk, dv, np.zeros(bits.shape, jax.dtypes.float0)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    window: Optional[int] = None, mask: Optional[str] = None,
                    block: Optional[int] = None,
                    selection: Optional[jax.Array] = None,
                    return_lse: bool = False):
    """Flash attention over (B, S, H, D); ``v`` may have a head size of its
    own (q and k (B, S, H, d_qk), v and the output (B, S, H, d_v)), as
    latent attention's training form has. k and v may have fewer heads
    than q (grouped queries: each run of H / H_kv query heads reads one kv
    head, through the kernels' index maps, with no copy of k or v for the
    group; dK and dV are summed over it inside the kernel). ``window``
    (causal only) keeps the keys ``0 <= t - j < window`` of query position
    t: the kernels' grids walk each tile's band and nothing outside it is
    fetched or computed; ``zoo_attention_window_tiles_total`` counts the
    tiles. ``mask="block_diffusion"`` (not causal; s_q == s_k) reads the
    sequence as ``[x_t | x_0]``, a noisy copy and the clean copy of a
    sequence of S / 2, and keeps :func:`block_diffusion_mask`'s entries for
    blocks of ``block`` (a power of two): the grids walk, for each q tile,
    its own noisy diagonal and the clean tiles up to its own, a list of
    those tiles and no step more, and a tile does mask arithmetic only
    where the mask crosses it; ``zoo_attention_bd_entries_total`` counts
    the score entries computed and needed. ``mask="selected"`` (not
    causal; s_q == s_k) keeps, for each query, the keys of ``selection``,
    one set for all of its heads, none after the query: (B, S / 32, S)
    uint32, packed in groups of ``block`` rows (:func:`pack_selection`;
    ``block`` a multiple of 32, at most 512, that divides S, is the tiles'
    side). Its grids walk a tile list of the causal tiles, every entry
    masked by its bit, and no gathered key or value is written.
    ``return_lse`` gives ``(out, lse)``, ``lse`` (B, H, S) the rows'
    logsumexp in base 2 (over the scores times ``sm_scale``), not
    differentiated. A masked call's grid steps,
    issued and computed, are in ``zoo_attention_grid_steps_total``. Uses
    the Pallas kernel when the
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
    if mask == SELECTED:
        b, s, h = q.shape[:3]
        if causal or window is not None or s_q != s_k or selection is None \
                or not block or block % 32 or block > 512 or s % block or \
                selection.shape != (b, s // 32, s):
            raise ValueError(
                f"the selected-key mask takes a (batch, S / 32, S) packed "
                f"selection in groups of block rows (a multiple of 32, at "
                f"most 512, that divides S), and no causal mask or window: "
                f"q {s_q}, k {s_k}, block {block}, selection "
                f"{None if selection is None else selection.shape}")
        out, lse = _flash_selected(q, k, v, selection, sm_scale, int(block))
        if not return_lse:
            return out
        return out, lax.stop_gradient(lse.reshape(b, h, s))
    if return_lse or selection is not None:
        raise ValueError("a selection and a logsumexp are the selected-key "
                         "mask's alone")
    bd = None
    if mask is not None:
        if mask != BLOCK_DIFFUSION:
            raise ValueError(f"mask {mask!r}: the named masks are "
                             f"{BLOCK_DIFFUSION!r} and {SELECTED!r}")
        if causal or window is not None or s_q != s_k or s_q % 2 or \
                not block or (s_q // 2) % block:
            raise ValueError(
                f"the block-diffusion mask takes [x_t | x_0] of two equal "
                f"halves whose length the block divides, and no causal mask "
                f"or window: q {s_q}, k {s_k}, block {block}")
        bd = (s_q // 2, int(block))

    def fit_block(s, want):
        # largest tile <= want that divides the sequence, so raising the
        # default never diverts a divisible-by-128 length off the kernel
        # (materializing O(S^2) scores) just because S % want != 0
        for cand in (want, 1024, 512, 256, 128, 64, 32, 16, 8):
            if cand <= want and s % cand == 0:
                return cand
        return None

    if bd is None:
        bq = fit_block(s_q, min(block_q, s_q))
        bk = fit_block(s_k, min(block_k, s_k))
    else:
        # a tile lies in one half, whole blocks in it, and stays so as the
        # backward halves it: powers of two the block divides
        bq, bk = (fit_block(bd[0], min(want, bd[0]))
                  for want in (block_q, block_k))
        if any(t is None or t & (t - 1) or t % bd[1] for t in (bq, bk)):
            bq = None
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
                             window=window, mask=mask, block=block)
    if interpret and varying_axes(q, k, v):
        # Interpret-mode pallas under shard_map: the HLO interpreter's
        # grid dynamic_slice rejects varying operands with invariant
        # indices for some (non-causal) shapes. The compiled kernel
        # handles vma (the union logic in _flash_forward); the CPU backend
        # uses the reference math.
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window, mask=mask, block=block)
    return _flash_attention(q, k, v, causal, sm_scale, bq, bk, window, bd)
