"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context path at all — its max-sequence handling is
plain attention inside BERT/Transformer layers and scale-out is batch-dim only
(SURVEY.md §2.3, §5; reference: pyzoo/.../layers/self_attention.py:386,
zoo/.../keras/layers/BERT.scala:402). Here sequence parallelism is first-class:
the ``sp`` mesh axis shards the sequence dimension, and these two strategies
turn a local S/sp shard into exact global attention:

* **ring attention** — K/V shards rotate around the sp ring via ``ppermute``
  (one ICI hop per step) while each device folds every visiting block into an
  online-softmax accumulator (ops/attention.py:blockwise_update). Peak memory
  is O(S_local) per device; comm is overlapped by XLA's async collectives.
* **Ulysses** — ``all_to_all`` re-shards from sequence-sharded to head-sharded,
  runs ordinary (flash) attention on full sequences for H/sp heads, and
  re-shards back. Cheaper comm volume when heads >= sp.

Both are pure jnp + lax collectives inside the jitted step, so they are
differentiable end-to-end (ppermute/all_to_all have transpose rules) and XLA
schedules the collectives on ICI.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import (
    blockwise_finalize, blockwise_update, flash_attention, mark_varying,
    mha_reference, varying_axes)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = False,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Exact global attention over sequence shards. Must run under an
    ``axis_name`` mapped axis (shard_map / jit-with-mesh). q,k,v are the local
    shards (B, S_local, H, D); the global sequence is the sp-axis concat.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape

    q_positions = idx * s_local + jnp.arange(s_local)
    # Accumulators must carry the inputs' varying-axes type (shard_map vma
    # typing) or the scan carry is rejected: mark the device-invariant
    # zeros as varying over every manual axis the inputs vary over.
    vma = varying_axes(q, k)
    _vary = partial(mark_varying, vma=vma)
    acc = _vary(jnp.zeros((b, s_local, h, d), jnp.float32))
    m = _vary(jnp.full((b, s_local, h), -jnp.inf, jnp.float32))
    l = _vary(jnp.zeros((b, s_local, h), jnp.float32))

    def step(carry, i):
        k_blk, v_blk, acc, m, l = carry
        # After i forward rotations each device holds the shard that
        # originated on rank (idx - i) mod n.
        src = jnp.mod(idx - i, n)
        k_positions = src * s_local + jnp.arange(s_local)
        acc, m, l = blockwise_update(
            q, k_blk, v_blk, acc, m, l, sm_scale=sm_scale,
            q_positions=q_positions, k_positions=k_positions, causal=causal)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, acc, m, l), None

    (_, _, acc, m, l), _ = lax.scan(step, (k, v, acc, m, l),
                                    jnp.arange(n))
    return blockwise_finalize(acc, l).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = False,
                      sm_scale: Optional[float] = None,
                      use_flash: bool = True) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): re-shard
    (B, S/sp, H, D) -> (B, S, H/sp, D), attend locally, re-shard back.
    Requires H % sp_size == 0."""
    n = lax.axis_size(axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by sp size ({n})")
    # split heads across the axis, gather sequence
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=2,
                  concat_axis=1, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    attend = flash_attention if use_flash else mha_reference
    out = attend(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    # split sequence back, gather heads
    return lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                          concat_axis=2, tiled=True)


def sequence_sharded_attention(mesh: Mesh, q, k, v, *, strategy: str = "ring",
                               causal: bool = False,
                               sm_scale: Optional[float] = None) -> jax.Array:
    """Convenience wrapper: shard (B, S, H, D) along the mesh's sp axis on the
    sequence dim (and dp on batch) and run the chosen strategy via shard_map.
    Inside a model's jitted train step, call ring_attention/ulysses_attention
    directly under the step's shard_map instead."""
    if strategy not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel strategy {strategy!r}")
    fn = ring_attention if strategy == "ring" else ulysses_attention
    spec = P("dp", "sp", None, None)

    @jax.shard_map(mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def _run(ql, kl, vl):
        return fn(ql, kl, vl, axis_name="sp", causal=causal,
                  sm_scale=sm_scale)

    return _run(q, k, v)
