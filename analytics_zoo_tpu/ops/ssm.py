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
which a recurrence over the chunks' own end states gives. :func:`ssd_scan`
computes that in ``jax.numpy``: the products take operands of the compute
dtype and accumulate in float32; the decays, their running sums and the
carried state are float32. Differentiated by JAX; no kernel.

Off the tiling (a sequence that is no whole number of chunks) the scan is the
literal recurrence, a ``lax.scan`` over positions (:func:`ssd_scan_sequential`).
On a TPU that is logged and counted (``zoo_ssm_sequential_scan_on_tpu_total``),
never silent.

Everything of the scan runs under the ``ssm.scan`` named scope and the
convolution under ``ssm.conv``, backward passes included.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.registry import REGISTRY as _REGISTRY

logger = logging.getLogger("analytics_zoo_tpu")

# the position-by-position path on the chip is never silent
_SEQUENTIAL_ON_TPU = _REGISTRY.counter(
    "zoo_ssm_sequential_scan_on_tpu_total",
    "ssd_scan call sites traced on a TPU that fell to the recurrence over "
    "positions (a sequence that is no whole number of chunks)")


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
        if seq % chunk:
            if jax.default_backend() == "tpu":
                _SEQUENTIAL_ON_TPU.inc()
                logger.warning(
                    "ssd_scan: %d positions are no whole number of chunks of "
                    "%d; scanning position by position on the TPU", seq,
                    chunk)
            return ssd_scan_sequential(x, dt, a, b, c, d)
        return _ssd_chunked(x, dt, a, b, c, d, chunk)
