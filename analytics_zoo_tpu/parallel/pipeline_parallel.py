"""GPipe-style pipeline parallelism over a ``pp`` mesh axis.

Beyond-parity axis (the reference scales only in the batch dimension,
SURVEY §2.3): a stack of S homogeneous stages (e.g. transformer blocks)
is sharded over pp ranks — S may be a MULTIPLE of the pp size, in which
case each rank runs its contiguous block of S/pp stages back to back per
tick — the batch is split into M microbatches, and activations flow
rank→rank over ICI via ``ppermute`` inside a ``lax.scan`` of
M + pp - 1 ticks (the classic GPipe schedule; bubble fraction
(pp-1)/(M+pp-1)). Everything is differentiable — ``ppermute``'s
transpose is the reverse rotation — so one ``jax.grad`` over the
pipelined forward trains all stages.

Schedule note (GPipe vs 1F1B): reverse-mode AD of the scanned forward
yields GPipe's all-forwards-then-all-backwards order, whose peak
activation memory grows with M. ``remat=True`` (default) wraps each
stage application in ``jax.checkpoint`` so the scan stores only
stage INPUTS and recomputes internals during the backward — the GPipe
paper's own configuration, bringing residuals to O(M) microbatch
activations per rank. A true 1F1B schedule would cap that at O(pp)
in-flight microbatches instead of O(M) — but under XLA's SPMD model it
is a net loss here: every rank executes one traced program, so the
per-tick "this rank does a forward OR a backward" choice lowers to
predicated execution of BOTH branches; a hand-scheduled 1F1B scan
(2(M+pp-1) ticks × predicated fwd+vjp per tick) costs ~1.5x the FLOPs
of GPipe+remat to save ~(M/pp)x on activations alone, while params +
optimizer state dominate memory at scale. GPipe+remat is therefore this
framework's training schedule by design, not omission; the remaining
tradeoff is: bubble (pp-1)/(M+pp-1) shrinks with M while activation
residuals grow with M.

Functional surface (flax-module-agnostic):

    stacked = stack_stage_params([init_stage(rng_i) for i in range(S)])
    y = pipeline_apply(stage_fn, stacked, x, mesh=mesh, microbatches=M)

``stage_fn(params_one_stage, x_mb) -> y_mb`` must be shape-preserving in
the batch dims (the pipeline carries a single activation buffer).
``stacked`` has a leading stage axis sharded over ``pp``; everything else
(input, output) is replicated across ``pp`` and may be sharded over
``dp``/``tp`` by the caller's outer machinery as usual.
"""

from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage: List[Any]) -> Any:
    """[stage_pytree, ...] -> one pytree with a leading stage axis."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *per_stage)


def stage_sharding(mesh: Mesh, stacked: Any, axis: str = "pp") -> Any:
    """NamedShardings placing the leading stage axis on ``axis``."""
    def shard(leaf):
        spec = P(axis, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(shard, stacked)


def pipeline_apply(stage_fn: Callable, stacked_params: Any, x: jax.Array,
                   *, mesh: Mesh, microbatches: int,
                   axis: str = "pp", remat: bool = True) -> jax.Array:
    """Run ``x`` through S pipelined stages; returns the final stage's
    output, replicated across the ``pp`` axis.

    x: (B, ...) with B % microbatches == 0. The stacked params' leading
    stage axis S must be a multiple of mesh.shape[axis]; each rank runs
    its contiguous block of S/pp stages sequentially per tick.
    ``remat=True`` checkpoints each stage application so the backward
    recomputes stage internals instead of storing them (see module
    docstring for the schedule/memory tradeoff)."""
    pp = mesh.shape[axis]
    leading = {l.shape[0] for l in jax.tree_util.tree_leaves(stacked_params)}
    if len(leading) != 1:
        raise ValueError(
            f"stacked params disagree on the stage axis: {sorted(leading)}")
    s_total = leading.pop()
    if s_total % pp:
        raise ValueError(
            f"stage count {s_total} must be a multiple of the '{axis}' "
            f"mesh axis size {pp} — shard_map would otherwise silently "
            "slice away stages")
    b = x.shape[0]
    if b % microbatches:
        raise ValueError(f"batch {b} not divisible by microbatches "
                         f"{microbatches}")
    mb = b // microbatches
    xs = x.reshape(microbatches, mb, *x.shape[1:])
    apply_stage = jax.checkpoint(stage_fn) if remat else stage_fn

    def pp_body(params, xs_local):
        # params: this rank's contiguous block of S/pp stages
        rank = lax.axis_index(axis)
        ticks = microbatches + pp - 1
        zero = jnp.zeros_like(xs_local[0])

        def run_block(p_block, inp):
            # apply this rank's stages in order (scan over the leading
            # per-rank stage axis; a single stage still goes through it)
            def body(c, p):
                return apply_stage(p, c), None
            out, _ = lax.scan(body, inp, p_block)
            return out

        def tick(carry, t):
            recv, outs = carry
            # rank 0 injects microbatch t (while t < M); later ranks
            # consume what the previous rank sent last tick
            feed_idx = jnp.minimum(t, microbatches - 1)
            inject = lax.dynamic_index_in_dim(xs_local, feed_idx, 0,
                                              keepdims=False)
            inp = jnp.where(rank == 0,
                            jnp.where(t < microbatches, inject, zero),
                            recv)
            out = run_block(params, inp)
            # rotate activations one rank forward
            perm = [(i, (i + 1) % pp) for i in range(pp)]
            recv_next = lax.ppermute(out, axis, perm)
            # last rank banks microbatch t-(pp-1) when it's live
            out_idx = t - (pp - 1)
            live = jnp.logical_and(rank == pp - 1, out_idx >= 0)
            outs = lax.cond(
                live,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            return (recv_next, outs), None

        init = (zero, jnp.zeros_like(xs_local))
        (_, outs), _ = lax.scan(tick, init, jnp.arange(ticks))
        # replicate the last rank's banked outputs across pp: every other
        # rank holds zeros, so a psum broadcasts without a gather
        mask = jnp.where(lax.axis_index(axis) == pp - 1, 1.0, 0.0)
        return lax.psum(outs * mask.astype(outs.dtype), axis)

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), stacked_params)
    # activations are replicated across pp (P()); dp/tp sharding of the
    # batch composes at the caller's jit level as usual
    fn = jax.shard_map(
        pp_body, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    outs = fn(stacked_params, xs)
    return outs.reshape(b, *x.shape[1:])
