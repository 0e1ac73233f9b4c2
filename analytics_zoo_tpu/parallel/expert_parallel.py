"""Expert parallelism: Switch/GShard-style MoE with all-to-all dispatch
over an ``ep`` mesh axis.

Beyond-parity axis (the reference is data-parallel only, SURVEY §2.3).
The GShard/Switch recipe, TPU-native: tokens are data-sharded over
``ep``; a replicated router picks ``top_k`` experts per token from E
total experts (E = m × ep, m experts resident per rank); each rank packs
its token-choices into an (E, C, d) capacity buffer, one
``lax.all_to_all`` rotates expert-major buffers so each rank receives
exactly the tokens routed to ITS m experts, the local experts run
(vmapped over m), and a second ``all_to_all`` returns outputs to their
source ranks where the gate probabilities scale them. Tokens beyond an
expert's capacity C are dropped (standard Switch behaviour) — with
``capacity_factor`` high enough nothing drops and the layer equals the
dense gather-per-token-through-its-experts computation exactly
(tests/test_expert_parallel.py).

Routing: ``top_k=1`` is Switch (gate = raw top-1 prob); ``top_k=2`` is
GShard-style with the chosen experts' gates renormalized to sum to 1.

Everything is differentiable: the router trains through the gate
scaling, experts through the dispatched tokens; the Switch load-balance
auxiliary loss (over first-choice assignments) is returned alongside the
output.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_expert_params(per_expert) -> Any:
    """[expert_pytree, ...] -> one pytree with a leading expert axis."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *per_expert)


def expert_sharding(mesh: Mesh, stacked: Any, axis: str = "ep") -> Any:
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, P(axis, *([None] * (l.ndim - 1)))),
        stacked)


def moe_apply(expert_fn: Callable, expert_params: Any,
              router_weights: jax.Array, x: jax.Array, *, mesh: Mesh,
              capacity_factor: float = 1.25, top_k: int = 1,
              axis: str = "ep") -> Tuple[jax.Array, jax.Array]:
    """Top-k mixture of experts over ``ep``.

    expert_fn(params_one_expert, tokens) -> tokens (shape-preserving);
    expert_params: stacked with leading axis E, where E is a multiple of
    mesh.shape[axis] (E // ep experts live on each rank — contiguous
    blocks, matching ``expert_sharding``'s leading-axis layout);
    router_weights: (d, E), replicated; x: (N, d) with N % ep == 0,
    sharded (or shardable) over ``axis`` on dim 0; top_k in (1, 2).

    Returns (y, aux_loss): y (N, d); aux_loss is the Switch load-balance
    term over first-choice assignments (E * sum_e fraction_e *
    mean_prob_e), which is 1.0 at perfect balance — add
    ``alpha * aux_loss`` to the training loss.
    """
    ep = mesh.shape[axis]
    leading = {l.shape[0]
               for l in jax.tree_util.tree_leaves(expert_params)}
    if len(leading) != 1:
        raise ValueError(
            f"stacked expert params disagree on the expert axis: {leading}")
    e_count = leading.pop()
    if e_count % ep:
        raise ValueError(
            f"expert count {e_count} must be a multiple of the '{axis}' "
            f"mesh axis size {ep}")
    m = e_count // ep                       # experts per rank
    if router_weights.shape[-1] != e_count:
        raise ValueError(
            f"router_weights last dim {router_weights.shape[-1]} must "
            f"equal the expert count {e_count} (one logit per expert)")
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 (Switch) or 2 (GShard), "
                         f"got {top_k}")
    if top_k > e_count:
        raise ValueError(f"top_k={top_k} with only {e_count} experts")
    n, d = x.shape
    if n % ep:
        raise ValueError(f"token count {n} not divisible by ep={ep}")
    local_n = n // ep
    # expected tokens per expert = top_k * local_n * ep / E = top_k *
    # local_n / m per rank-expert... capacity is per (expert, source rank)
    capacity = max(1, int(math.ceil(
        capacity_factor * top_k * local_n / e_count)))

    def ep_body(params, router_w, x_local):
        # this rank's m experts (contiguous leading slice)
        logits = x_local @ router_w                     # (ln, E)
        probs = jax.nn.softmax(logits, axis=-1)

        if top_k == 1:
            expert_idx = jnp.argmax(probs, axis=-1)[None]       # (1, ln)
            gates = jnp.take_along_axis(
                probs, expert_idx[0][:, None], axis=-1).T        # (1, ln)
        else:
            topv, topi = lax.top_k(probs, 2)            # (ln, 2)
            denom = jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
            gates = (topv / denom).T                    # (2, ln) renorm
            expert_idx = topi.T                         # (2, ln)

        # flatten the (choice, token) pairs into one virtual token stream
        # so capacity ranks are assigned jointly across choices
        flat_idx = expert_idx.reshape(-1)               # (k*ln,)
        flat_gate = gates.reshape(-1)
        onehot = jax.nn.one_hot(flat_idx, e_count, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot       # 1-based ranks
        pos = jnp.sum(pos, axis=-1) - 1                 # (k*ln,) 0-based
        keep = pos < capacity                           # overflow drops
        pos_c = jnp.clip(pos, 0, capacity - 1)

        # scatter token-choices into the (E, C, d) dispatch buffer
        xk = jnp.broadcast_to(x_local, (top_k,) + x_local.shape)
        xk = xk.reshape(-1, d)                          # (k*ln, d)
        buf = jnp.zeros((e_count, capacity, d), x_local.dtype)
        buf = buf.at[flat_idx, pos_c].add(
            jnp.where(keep[:, None], xk, 0.0))

        # exchange: expert-major -> source-rank-major on the owning rank.
        # buf (E, C, d) = (ep, m, C, d) groups; tiled all_to_all over dim0
        # hands rank r every other rank's (m, C, d) block for r's experts
        recv = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=True)               # (ep*m, C, d)
        recv = recv.reshape(ep, m, capacity, d)
        recv = jnp.moveaxis(recv, 1, 0)                 # (m, ep, C, d)
        recv = recv.reshape(m, ep * capacity, d)
        out = jax.vmap(expert_fn)(params, recv)         # m local experts
        out = out.reshape(m, ep, capacity, d)
        out = jnp.moveaxis(out, 0, 1)                   # (ep, m, C, d)
        out = out.reshape(e_count, capacity, d)
        back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                              tiled=True)               # (E, C, d) home

        # gather each surviving token-choice's output, gate-scale, and
        # sum the k choices; dropped choices contribute zero (standard
        # Switch residual handles them)
        yk = back[flat_idx, pos_c]
        yk = jnp.where(keep[:, None], yk * flat_gate[:, None], 0.0)
        y = yk.reshape(top_k, -1, d).sum(0)             # (ln, d)

        # Switch load-balance aux over FIRST choices: fraction of tokens
        # per expert x mean router prob per expert, averaged GLOBALLY
        frac = lax.pmean(jnp.mean(
            jax.nn.one_hot(expert_idx[0], e_count, dtype=x_local.dtype),
            axis=0), axis)
        mean_p = lax.pmean(jnp.mean(probs, axis=0), axis)
        aux = e_count * jnp.sum(frac * mean_p)
        return y, aux

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis, *([None] * (l.ndim - 1))), expert_params)
    fn = jax.shard_map(ep_body, mesh=mesh,
                       in_specs=(param_specs, P(), P(axis)),
                       out_specs=(P(axis), P()),
                       check_vma=False)
    y, aux = fn(expert_params, router_weights, x)
    return y, aux
