"""Mamba-2's state-space mixer core: the causal depthwise convolution in front
of it and the selective scan, in its chunked matrix form (SSD, "state-space
duality": Dao & Gu 2024).

A head h of group g carries a (head_dim, state) matrix along the sequence::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D_h x_t

with ``A_h < 0`` a scalar a head, ``dt_t > 0`` a scalar a head and position,
``B_t`` and ``C_t`` (state,) vectors that the heads of a group share. Written
out over a chunk of Q positions that starts with the state ``S_in``::

    y_i = sum_{j <= i} (C_i . B_j) exp(a_i - a_j) dt_j x_j  +  exp(a_i) S_in C_i

``a`` the running sum of ``dt A`` inside the chunk: the first term is a
masked (Q, Q) product a chunk (``(C B^T * L) (dt x)``, ``L`` the decay's
lower-triangular matrix), the second reads the state the chunk starts with,
which a recurrence over the chunks' own end states gives. Products take
operands of the compute dtype and accumulate in float32; the decays, their
running sums and the carried state are float32.

On a TPU :func:`ssd_scan` runs that as two Pallas kernels with their own
backward rule (:func:`_ssd_kernels`): a forward that walks the chunks in
order and a backward that walks them in reverse, each carrying the heads'
states (their gradients) in VMEM from chunk to chunk and building each
chunk's (Q, Q) decays in VMEM, head by head: nothing of shape (Q, Q) reaches
HBM. The forward leaves the states each chunk starts with (float32) for the
backward. Elsewhere (the CPU's tests) it is the same algorithm in
``jax.numpy`` (:func:`_ssd_chunked`), differentiated by JAX, which
materialises every chunk's decays. The path a call site takes is counted
(``zoo_ssm_scan_path_total``).

Off the tiling (a sequence that is no whole number of chunks) the scan is the
literal recurrence, a ``lax.scan`` over positions (:func:`ssd_scan_sequential`).
On a TPU that is logged and counted (``zoo_ssm_sequential_scan_on_tpu_total``),
never silent.

Everything of the scan runs under the ``ssm.scan`` named scope and the
convolution under ``ssm.conv``, backward passes included.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import attention as _attention
from ..obs.registry import REGISTRY as _REGISTRY

logger = logging.getLogger("analytics_zoo_tpu")

# the position-by-position path on the chip is never silent
_SEQUENTIAL_ON_TPU = _REGISTRY.counter(
    "zoo_ssm_sequential_scan_on_tpu_total",
    "ssd_scan call sites traced on a TPU that fell to the recurrence over "
    "positions (a sequence that is no whole number of chunks)")

_SCAN_PATHS = _REGISTRY.counter(
    "zoo_ssm_scan_path_total",
    "ssd_scan call sites traced, by the path they take: kernel (the Pallas "
    "forward and backward), chunked (jax.numpy, differentiated by JAX) or "
    "sequential (the recurrence over positions)",
    labelnames=("path",))

_NT = (((1,), (1,)), ((), ()))            # a @ b^T
_TN = (((0,), (0,)), ((), ()))            # a^T @ b


def causal_conv1d(x: jax.Array, weight: jax.Array, bias: jax.Array
                  ) -> jax.Array:
    """Depthwise causal convolution along the sequence: ``y_t = bias +
    sum_k weight[k] x_{t - (K - 1) + k}``, positions before the sequence
    zero. x: (batch, seq, channels); weight: (K, channels); bias:
    (channels,). K shifted copies, multiplied and added."""
    with jax.named_scope("ssm.conv"):
        taps, seq = weight.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        y = bias.astype(x.dtype)
        for k in range(taps):
            y = y + padded[:, k:k + seq] * weight[k].astype(x.dtype)
        return y


def ssd_scan_sequential(x, dt, a, b, c, d) -> jax.Array:
    """The recurrence as it is written, a position at a time, in float32
    (:func:`ssd_scan`'s arguments)."""
    bsz, _, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    x32 = x.astype(jnp.float32).reshape(bsz, -1, g, r, p)
    dt32 = dt.astype(jnp.float32).reshape(bsz, -1, g, r)
    a = a.astype(jnp.float32).reshape(g, r)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp        # (B,g,r,p) (B,g,r) (B,g,n) (B,g,n)
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + jnp.einsum("bgrp,bgn->bgrpn", x_t * dt_t[..., None], b_t)
        return state, jnp.einsum("bgrpn,bgn->bgrp", state, c_t)

    along = (lambda t: jnp.moveaxis(t, 1, 0))
    _, y = lax.scan(step, jnp.zeros((bsz, g, r, p, n), jnp.float32),
                    (along(x32), along(dt32), along(b.astype(jnp.float32)),
                     along(c.astype(jnp.float32))))
    y = jnp.moveaxis(y, 0, 1) + x32 * d.astype(jnp.float32).reshape(g, r, 1)
    return y.reshape(x.shape).astype(x.dtype)


def _ssd_chunked(x, dt, a, b, c, d, chunk: int) -> jax.Array:
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, nc = h // g, s // chunk
    f32, cdt = jnp.float32, x.dtype
    dot = (lambda spec, *ops: jnp.einsum(spec, *ops,
                                         preferred_element_type=f32))
    xc = x.reshape(bsz, nc, chunk, g, r, p)
    bc = b.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)
    # heads before positions: the (chunk, chunk) matrices are the minor dims
    dtc = jnp.transpose(dt.astype(f32).reshape(bsz, nc, chunk, g, r),
                        (0, 1, 3, 4, 2))                 # (B,nc,g,r,Q)
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(g, r, 1), axis=-1)
    # inside a chunk: (C B^T * L) (dt x)
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (B,nc,g,r,Q,Q)
    cb = dot("bcign,bcjgn->bcgij", cc, bc)
    w = cb[:, :, :, None] * decay * dtc[..., None, :]
    y = dot("bcgrij,bcjgrp->bcigrp", w.astype(cdt), xc)
    # each chunk's own end state, from zero: sum_j exp(a_Q - a_j) dt_j x_j B_j^T
    to_end = jnp.transpose(jnp.exp(cum[..., -1:] - cum) * dtc,
                           (0, 1, 4, 2, 3))              # (B,nc,Q,g,r)
    own = dot("bcjgrp,bcjgn->bcgrpn",
              (xc.astype(f32) * to_end[..., None]).astype(cdt), bc)
    # between chunks: the state each chunk starts with

    def carry(state, inp):
        own_c, decay_c = inp
        return state * decay_c[..., None, None] + own_c, state

    _, entering = lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)              # (B,nc,g,r,p,n)
    y = y + dot("bcign,bcgrpn->bcigrp", cc, entering.astype(cdt)) \
        * jnp.transpose(jnp.exp(cum), (0, 1, 4, 2, 3))[..., None]
    y = y + xc.astype(f32) * d.astype(f32).reshape(g, r, 1)
    return y.reshape(bsz, s, h, p).astype(cdt)


# --- the kernels -------------------------------------------------------------
#
# Both kernels run on the grid (batch, chunks, head blocks): a grid step is one
# chunk of ``heads`` heads of one group, their x as the (Q, heads * head_dim)
# columns of (batch, seq, heads * head_dim), dt as rows of (batch, heads,
# seq). Every head's state (its gradient, backward) stays in VMEM scratch from
# one chunk to the next, transposed: (state, heads * head_dim), the heads side
# by side along lanes as in x. The chunk's (Q, Q) matrices are built and used a
# head at a time; everything of shape (Q, head_dim) is handled a lane group at
# a time: the heads that share 128 lanes (two of 64), each head's per-position
# factors spread over its own lanes, and each head's (Q, Q) product taking the
# group's lanes with the other heads' zeroed, so that no head's columns ever
# move between lanes. The forward's (Q, Q) matrices stand as the masked
# C B^T; the backward's as their transposes (row j, column i), so that every
# (Q, Q) product takes its operands as they lie.

def _running_sum(v, axis: int, reverse: bool = False):
    """The inclusive running sum along ``axis`` of a 2-D float32 value, from
    the end with ``reverse``: log2(size) rotations and adds."""
    from jax.experimental.pallas import tpu as pltpu
    size = v.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, v.shape, axis)
    k = 1
    while k < size:
        if reverse:           # v[i + k] reaches i
            v = v + jnp.where(idx < size - k, pltpu.roll(v, size - k, axis),
                              0.0)
        else:                 # v[i - k] reaches i
            v = v + jnp.where(idx >= k, pltpu.roll(v, k, axis), 0.0)
        k *= 2
    return v


def _chunk_decays(dt_r, a_col):
    """A chunk's running sums of dt A, for ``heads`` heads: as rows (heads, Q)
    and as columns (Q, heads), dt as columns, and the chunk's last sum
    (1, heads)."""
    cum_r = _running_sum(dt_r * a_col, 1)
    cum_c = cum_r.T
    return cum_r, cum_c, dt_r.T, cum_c[-1:, :]


def _lane_groups(heads: int, p: int):
    """Heads a lane group holds and its lanes: the heads that fill 128 lanes
    (at most ``heads``)."""
    per = max(1, min(heads, 128 // p))
    return per, per * p


def _spread(cols, first: int, per: int, seg):
    """(Q, per * p): column ``first + j`` of ``cols`` over head j's lanes
    (``seg``: each lane's head in the group)."""
    out = jnp.broadcast_to(cols[:, first:first + 1],
                           (cols.shape[0], seg.shape[1]))
    for j in range(1, per):
        out = jnp.where(seg == j, cols[:, first + j:first + j + 1], out)
    return out


def _lane_row(vals, heads: int, p: int):
    """(1, heads * p): ``vals(h)`` (a (1, 1) value or a scalar) over head h's
    lanes."""
    lane_head = lax.broadcasted_iota(jnp.int32, (1, heads * p), 1) // p
    out = jnp.broadcast_to(vals(0), (1, heads * p))
    for h in range(1, heads):
        out = jnp.where(lane_head == h, vals(h), out)
    return out


def _scan_fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref,
                     *rest, heads: int, head_dim: int, with_states: bool):
    import jax.experimental.pallas as pl
    if with_states:
        states_ref, state_ref, xt_ref = rest
    else:
        state_ref, xt_ref = rest
    f32, cdt, p = jnp.float32, x_ref.dtype, head_dim
    per, width = _lane_groups(heads, p)
    blk = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[blk] = jnp.zeros(state_ref.shape[1:], f32)

    dt_r = dt_ref[0]
    cum_r, cum_c, dt_c, last = _chunk_decays(dt_r, a_ref[...])
    bm, cm, xs, d = b_ref[0], c_ref[0], x_ref[0], d_ref[...]
    q = xs.shape[0]
    s_in = state_ref[blk]                                  # (n, heads * p)
    if with_states:
        states_ref[0, 0] = s_in
    cb = lax.dot_general(cm, bm, _NT, preferred_element_type=f32)
    inter = jnp.dot(cm, s_in.astype(cdt),
                    preferred_element_type=f32)            # (Q, heads * p)
    causal = (lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= lax.broadcasted_iota(jnp.int32, (q, q), 1))
    seg = lax.broadcasted_iota(jnp.int32, (1, width), 1) // p
    e_c = jnp.exp(cum_c)
    to_end = jnp.exp(last - cum_c) * dt_c
    d_row = _lane_row(lambda h: d[h, 0], heads, p)
    # exp(a_i - a_j) dt_j as one exponential: a_j - log dt_j by rows
    from_r = cum_r - jnp.log(dt_r)
    for first in range(0, heads, per):
        lanes = slice(first * p, first * p + width)
        x_g = xs[:, lanes]
        y = inter[:, lanes] * _spread(e_c, first, per, seg) \
            + x_g.astype(f32) * d_row[:, lanes]
        for j in range(per):
            h = first + j
            x_h = x_g if per == 1 else jnp.where(seg == j, x_g, 0)
            w = cb * jnp.exp(jnp.where(causal, cum_c[:, h:h + 1]
                                       - from_r[h:h + 1, :], -jnp.inf))
            y = y + jnp.dot(w.astype(cdt), x_h, preferred_element_type=f32)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        xt_ref[:, lanes] = (x_g.astype(f32)
                            * _spread(to_end, first, per, seg)).astype(cdt)
    # the chunk's own end state, every head at once: sum_j t_j B_j x_j^T
    own = lax.dot_general(bm, xt_ref[...], _TN, preferred_element_type=f32)
    state_ref[blk] = (s_in * _lane_row(lambda h: jnp.exp(last[:, h:h + 1]),
                                       heads, p) + own)


def _scan_bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, s_ref, dy_ref,
                     dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                     ds_ref, acc_b_ref, acc_c_ref, xt_ref, dye_ref,
                     ddt_c_ref, dcum_c_ref, dcum_r_ref, dd_c_ref,
                     *, heads: int, head_dim: int, group_heads: int):
    import jax.experimental.pallas as pl
    f32, cdt, p = jnp.float32, x_ref.dtype, head_dim
    per, width = _lane_groups(heads, p)
    blk = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[blk] = jnp.zeros(ds_ref.shape[1:], f32)

    @pl.when(blk * heads % group_heads == 0)
    def _():
        acc_b_ref[...] = jnp.zeros(acc_b_ref.shape, f32)
        acc_c_ref[...] = jnp.zeros(acc_c_ref.shape, f32)

    dt_r = dt_ref[0]
    cum_r, cum_c, dt_c, last = _chunk_decays(dt_r, a_ref[...])
    bm, cm, xs, dys, d = b_ref[0], c_ref[0], x_ref[0], dy_ref[0], d_ref[...]
    q = xs.shape[0]
    s_in = s_ref[0, 0]                     # the state the chunk starts with
    ds = ds_ref[blk]                       # d(the state it ends with)
    cbt = lax.dot_general(bm, cm, _NT, preferred_element_type=f32)
    z = jnp.dot(cm, s_in.astype(cdt),
                preferred_element_type=f32)                # (Q, heads * p)
    bds = jnp.dot(bm, ds.astype(cdt),
                  preferred_element_type=f32)              # (Q, heads * p)
    causal_t = (lax.broadcasted_iota(jnp.int32, (q, q), 1)
                >= lax.broadcasted_iota(jnp.int32, (q, q), 0))
    last_row = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    seg = lax.broadcasted_iota(jnp.int32, (1, width), 1) // p
    e_c = jnp.exp(cum_c)
    ends = jnp.exp(last - cum_c)
    to_end = ends * dt_c
    d_row = _lane_row(lambda h: d[h, 0], heads, p)
    # d/d(last) of exp(last) S_in, each head's sum over its lanes
    ds_s = jnp.sum(ds * s_in, axis=0, keepdims=True)       # (1, heads * p)
    # exp(a_i - a_j) dt_j as one exponential: a_j - log dt_j by columns
    from_c = cum_c - jnp.log(dt_c)
    dcbt = jnp.zeros((q, q), f32)
    for first in range(0, heads, per):
        lanes = slice(first * p, first * p + width)
        x_g, dy_g = xs[:, lanes], dys[:, lanes]
        x32, dy32 = x_g.astype(f32), dy_g.astype(f32)
        t_g = _spread(to_end, first, per, seg)
        e_g = _spread(e_c, first, per, seg)
        bds_g = bds[:, lanes]
        dx = dy32 * d_row[:, lanes] + bds_g * t_g
        xb, dyz, dyx = x32 * bds_g, dy32 * z[:, lanes], dy32 * x32
        for j in range(per):
            h = first + j
            mine = seg == j
            only = ((lambda v: v) if per == 1 else
                    (lambda v, mine=mine: jnp.where(mine, v, 0)))
            dt_j, t_h = dt_c[:, h:h + 1], to_end[:, h:h + 1]
            # row j, column i: L[i, j] dt_j and W[i, j] = (C B^T)[i, j]
            # L[i, j] dt_j; dl = dW L dt is d(C B^T), m = dW * W
            ltd = jnp.exp(jnp.where(causal_t, cum_r[h:h + 1, :]
                                    - from_c[:, h:h + 1], -jnp.inf))
            dx = dx + jnp.dot((cbt * ltd).astype(cdt), only(dy_g),
                              preferred_element_type=f32)
            dl = lax.dot_general(only(x_g), dy_g, _NT,
                                 preferred_element_type=f32) * ltd
            dcbt = dcbt + dl
            m = dl * cbt
            m_rows = jnp.sum(m, axis=1, keepdims=True)
            # d(dt A)'s running sums: +i of each (i, j) decay, -j of it; t_j's
            # -j and the chunk's last; the read of the entering state
            dcum_r_ref[h:h + 1, :] = jnp.sum(m, axis=0, keepdims=True)
            v = jnp.sum(only(xb), axis=1, keepdims=True)
            d_last = (jnp.sum(v * t_h, axis=0, keepdims=True)
                      + jnp.exp(last[:, h:h + 1]) * jnp.sum(
                          only(ds_s[:, lanes]), axis=1, keepdims=True))
            dcum_c_ref[:, h:h + 1] = (
                e_c[:, h:h + 1] * jnp.sum(only(dyz), axis=1, keepdims=True)
                - m_rows - v * t_h + jnp.where(last_row, d_last, 0.0))
            # dt_j's own factor: sum_i dW L (C B^T), m's row over dt_j (a dt
            # that is 0 has none: softplus's own gradient is 0 there)
            ddt_c_ref[:, h:h + 1] = (
                jnp.where(dt_j > 0, m_rows / dt_j, 0.0)
                + v * ends[:, h:h + 1])
            dd_c_ref[:, h:h + 1] = jnp.sum(only(dyx), axis=1, keepdims=True)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        xt_ref[:, lanes] = (x32 * t_g).astype(cdt)
        dye_ref[:, lanes] = (dy32 * e_g).astype(cdt)
    # the gradient of the state the chunk starts with, every head at once
    upd = lax.dot_general(cm, dye_ref[...], _TN, preferred_element_type=f32)
    ds_ref[blk] = ds * _lane_row(lambda h: jnp.exp(last[:, h:h + 1]),
                                 heads, p) + upd
    dcbt = dcbt.astype(cdt)
    acc_b_ref[...] += (
        jnp.dot(dcbt, cm, preferred_element_type=f32)
        + lax.dot_general(xt_ref[...], ds.astype(cdt), _NT,
                          preferred_element_type=f32))
    acc_c_ref[...] += (
        lax.dot_general(dcbt, bm, _TN, preferred_element_type=f32)
        + lax.dot_general(dye_ref[...], s_in.astype(cdt), _NT,
                          preferred_element_type=f32))

    @pl.when((blk + 1) * heads % group_heads == 0)
    def _():
        db_ref[0] = acc_b_ref[...].astype(db_ref.dtype)
        dc_ref[0] = acc_c_ref[...].astype(dc_ref.dtype)

    g = _running_sum(dcum_r_ref[...] + dcum_c_ref[...].T, 1, reverse=True)
    ddt_ref[0] = ddt_c_ref[...].T + a_ref[...] * g
    da_ref[0, 0] = jnp.sum(dt_r * g, axis=1, keepdims=True)
    dd_ref[0, 0] = jnp.sum(dd_c_ref[...].T, axis=1, keepdims=True)


def _vmem_bytes(heads: int, h: int, q: int, p: int, n: int,
                itemsize: int) -> int:
    """What the backward's grid step holds in VMEM (the larger of the two
    kernels'): double-buffered blocks, the scratch (every head's state
    gradient among it), and the live temporaries (~six (Q, Q) float32
    matrices and the (Q, heads * p) products), lanes padded to 128."""
    cols, lanes = heads * p, 128
    blocks = (3 * q * cols * itemsize + cols * n * 4 + 4 * q * n * itemsize
              + 2 * heads * q * 4 + 4 * heads * lanes * 4)
    scratch = (h * p * n * 4 + 2 * q * n * 4 + 2 * q * cols * itemsize
               + 3 * q * lanes * 4 + heads * q * 4)
    live = 6 * q * q * 4 + 2 * q * cols * 4 + 2 * cols * n * 4
    return 2 * blocks + scratch + live


def _heads_per_block(h: int, r: int, q: int, s: int, p: int, n: int,
                     itemsize: int, aligned: bool = True) -> Optional[int]:
    """The most heads of one group a grid step takes whose step fits
    Mosaic's scoped VMEM (``_vmem_bytes``); with ``aligned`` (compiled for a
    TPU) also blocks the (8, 128) tiling takes: x's columns whole lane tiles,
    the dt rows whole sublane tiles, a chunk whole lane tiles, a group's B
    and C whole lane tiles, heads that share or fill lane tiles. None if no
    block does."""
    if aligned and ((q % 128 and q != s) or (n % 128 and r != h)
                    or (128 % p and p % 128)):
        return None
    best = None
    for heads in range(1, r + 1):
        if r % heads or _vmem_bytes(heads, h, q, p, n, itemsize) > \
                _attention._SCOPED_VMEM_BYTES:
            continue
        if aligned and heads != h and (heads * p % 128 or heads % 8):
            continue
        best = heads
    return best


def _specs(bsz: int, s: int, h: int, p: int, g: int, n: int, chunk: int,
           heads: int, reverse: bool):
    """The block specs both kernels share, on the grid (batch, chunks, head
    blocks): x, dt, A, D, B, C; chunks from the last with ``reverse``."""
    import jax.experimental.pallas as pl
    r, nc = h // g, s // chunk
    at = ((lambda c: nc - 1 - c) if reverse else (lambda c: c))

    def group(k):                      # the B/C group of head block k
        return k * heads // r
    return at, [
        pl.BlockSpec((1, chunk, heads * p), lambda i, c, k: (i, at(c), k)),
        pl.BlockSpec((1, heads, chunk), lambda i, c, k: (i, k, at(c))),
        pl.BlockSpec((heads, 1), lambda i, c, k: (k, 0)),
        pl.BlockSpec((heads, 1), lambda i, c, k: (k, 0)),
        pl.BlockSpec((1, chunk, n), lambda i, c, k: (i, at(c), group(k))),
        pl.BlockSpec((1, chunk, n), lambda i, c, k: (i, at(c), group(k))),
    ]


def _kernel_operands(*arrays):
    """The kernels' views of x, dt, A, B, C, D and any further operands: x
    (B, S, H * P), dt (B, H, S) float32, A and D (H, 1) float32, B and C
    (B, S, G * N); and the mesh axes they vary over under ``shard_map``,
    every operand lifted to them (as the flash kernels do)."""
    x, dt, a, b, c, d = arrays[:6]
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    f32 = jnp.float32
    ops = (x.reshape(bsz, s, h * p), jnp.swapaxes(dt.astype(f32), 1, 2),
           a.astype(f32).reshape(h, 1), d.astype(f32).reshape(h, 1),
           b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n)) + arrays[6:]
    vma = _attention.varying_axes(*ops)
    return [_attention.mark_varying(o, vma) for o in ops], vma


def _compiler_params(interpret: bool):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    # the chunk and head-block dims carry the states and the groups' sums
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _scan_forward(x, dt, a, b, c, d, chunk: int, heads: int,
                  with_states: bool, interpret: bool):
    """y, and with ``with_states`` the float32 states each chunk starts
    with, transposed: (B, chunks, N, H * P). A ``jax.jit``, as is the
    backward: a step lowers each kernel once for all its mixers of a shape,
    not once a call site."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = s // chunk
    ops, vma = _kernel_operands(x, dt, a, b, c, d)
    _, in_specs = _specs(bsz, s, h, p, g, n, chunk, heads, reverse=False)
    out_specs = [in_specs[0]]
    out_shape = [jax.ShapeDtypeStruct((bsz, s, h * p), x.dtype, vma=vma)]
    if with_states:
        out_specs.append(pl.BlockSpec((1, 1, n, heads * p),
                                      lambda i, c, k: (i, c, 0, k)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, n, h * p),
                                              jnp.float32, vma=vma))
    res = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, heads=heads, head_dim=p,
                          with_states=with_states),
        grid=(bsz, nc, h // heads),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((h // heads, n, heads * p), jnp.float32),
            pltpu.VMEM((chunk, heads * p), x.dtype)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ops)
    y = res[0].reshape(x.shape)
    return (y, res[1]) if with_states else (y, None)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _scan_backward(x, dt, a, b, c, d, states, dy, chunk: int, heads: int,
                   interpret: bool):
    """The six gradients, from the forward's entering states."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc, f32 = s // chunk, jnp.float32
    ops, vma = _kernel_operands(x, dt, a, b, c, d, states,
                                dy.reshape(bsz, s, h * p))
    at, in_specs = _specs(bsz, s, h, p, g, n, chunk, heads, reverse=True)
    per_head = pl.BlockSpec((1, 1, heads, 1),
                            lambda i, c, k: (i, at(c), k, 0))
    in_specs += [pl.BlockSpec((1, 1, n, heads * p),
                              lambda i, c, k: (i, at(c), 0, k)),
                 in_specs[0]]
    dxf, ddt, da, dd, db, dc = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, heads=heads, head_dim=p,
                          group_heads=h // g),
        grid=(bsz, nc, h // heads),
        in_specs=in_specs,
        out_specs=[in_specs[0], in_specs[1], per_head, per_head,
                   in_specs[4], in_specs[5]],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                   for shape, dtype in (((bsz, s, h * p), x.dtype),
                                        ((bsz, h, s), f32),
                                        ((bsz, nc, h, 1), f32),
                                        ((bsz, nc, h, 1), f32),
                                        ((bsz, s, g * n), b.dtype),
                                        ((bsz, s, g * n), c.dtype))],
        scratch_shapes=[
            pltpu.VMEM((h // heads, n, heads * p), f32),   # state gradients
            pltpu.VMEM((chunk, n), f32),                   # the group's dB
            pltpu.VMEM((chunk, n), f32),                   # and dC
            pltpu.VMEM((chunk, heads * p), x.dtype),       # t x, by head
            pltpu.VMEM((chunk, heads * p), x.dtype),       # exp(a) dy
            pltpu.VMEM((chunk, heads), f32),               # d dt, columns
            pltpu.VMEM((chunk, heads), f32),               # d(dt A) sums
            pltpu.VMEM((heads, chunk), f32),               # and as rows
            pltpu.VMEM((chunk, heads), f32)],              # dy . x
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*ops)
    return (dxf.reshape(x.shape), jnp.swapaxes(ddt, 1, 2).astype(dt.dtype),
            jnp.sum(da, axis=(0, 1, 3)).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            jnp.sum(dd, axis=(0, 1, 3)).astype(d.dtype))


def _block_for(x, b, chunk: int, interpret: bool) -> Optional[int]:
    """``_heads_per_block`` at a call site's shapes."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    return _heads_per_block(h, h // g, chunk, s, p, n, x.dtype.itemsize,
                            aligned=not interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernels(x, dt, a, b, c, d, chunk: int) -> jax.Array:
    """The chunked scan as the Pallas kernels (interpreted on the CPU)."""
    interpret = _attention._interpret()
    return _scan_forward(x, dt, a, b, c, d, chunk,
                         _block_for(x, b, chunk, interpret), False,
                         interpret)[0]


def _ssd_kernels_fwd(x, dt, a, b, c, d, chunk):
    interpret = _attention._interpret()
    y, states = _scan_forward(x, dt, a, b, c, d, chunk,
                              _block_for(x, b, chunk, interpret), True,
                              interpret)
    return y, (x, dt, a, b, c, d, states)


def _ssd_kernels_bwd(chunk, res, dy):
    x, dt, a, b, c, d, states = res
    interpret = _attention._interpret()
    return _scan_backward(x, dt, a, b, c, d, states, dy, chunk,
                          _block_for(x, b, chunk, interpret), interpret)


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _scan_path(x, b, chunk: int) -> str:
    """Which implementation a call site takes: ``sequential`` off the
    tiling; ``kernel`` where the Pallas kernels compile (a TPU: the flash
    kernels' own test) and a head block fits; else ``chunked``."""
    if x.shape[1] % chunk:
        return "sequential"
    try:
        compiled = not _attention._interpret()
    except NotImplementedError:
        compiled = False
    if compiled and _block_for(x, b, chunk, False) is not None:
        return "kernel"
    return "chunked"


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk_size: int) -> jax.Array:
    """The selective scan over the whole sequence, state zero before it.

    x: (batch, seq, heads, head_dim); dt: (batch, seq, heads), positive; a:
    (heads,), negative; b, c: (batch, seq, groups, state), head h reading
    group ``h // (heads / groups)``; d: (heads,). Returns y of x's shape and
    dtype. In chunks of ``chunk_size`` where the sequence is a whole number
    of them (a shorter sequence is one chunk), else position by position."""
    seq = x.shape[1]
    chunk = min(chunk_size, seq)
    with jax.named_scope("ssm.scan"):
        path = _scan_path(x, b, chunk)
        _SCAN_PATHS.labels(path=path).inc()
        if path == "sequential":
            if jax.default_backend() == "tpu":
                _SEQUENTIAL_ON_TPU.inc()
                logger.warning(
                    "ssd_scan: %d positions are no whole number of chunks of "
                    "%d; scanning position by position on the TPU", seq,
                    chunk)
            return ssd_scan_sequential(x, dt, a, b, c, d)
        if path == "kernel":
            return _ssd_kernels(x, dt, a, b, c, d, chunk)
        return _ssd_chunked(x, dt, a, b, c, d, chunk)
