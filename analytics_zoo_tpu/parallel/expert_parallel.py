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

The second half of the file is the expert layer of the DeepSeek-V3 family
as ONE rank of an expert-parallel deployment runs it: :func:`route_noaux_tc`
scores every token over ALL experts (sigmoid scores, selection by score plus
a correction bias, the chosen scores renormalised and scaled), or
:func:`route_softmax_topk` does (softmax over all experts, top-k, the chosen
probabilities renormalised: the Qwen3-MoE form), and
:func:`held_experts_ffn` is told which contiguous range of experts it holds,
sorts the token-choices routed to them by expert and runs grouped matrix
products over those rows alone. No capacity, no dropped token: rows beyond
the static buffer are worked off in further chunks, and inside a chunk the
gather and the scatter-add move the rows the router sent, rounded up to a
sub-block, not the buffer's size. What the experts held elsewhere would add is
not computed here; on one chip the layer runs without its exchange.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import attention as _attention


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


# ---------------------------------------------------------------------------
# One rank's share of a sigmoid-routed, bias-balanced expert layer
# ---------------------------------------------------------------------------

# the name under which a block's rematerialisation policy keeps the routing
# decision: the backward pass reads the forward's choice, it does not select
# a second time
ROUTER_CHOICE_NAME = "moe_router_choice"


def _chosen_mask(idx: jax.Array, n_experts: int) -> jax.Array:
    """``hot[n, j, e] = (idx[n, j] == e)``: never materialised, each use
    below is one compare-and-reduce fusion over N x top_k x E entries."""
    return idx[:, :, None] == lax.broadcasted_iota(
        jnp.int32, (1, 1, n_experts), 2)


@jax.custom_vjp
def _take_chosen(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """``scores[n, idx[n, j]]`` as a compare-and-sum over the expert axis
    (exact: one non-zero term), where a gather would move N x top_k scalars
    one by one."""
    hot = _chosen_mask(idx, scores.shape[-1])
    return jnp.sum(jnp.where(hot, scores[:, None, :], 0), axis=-1)


def _take_chosen_fwd(scores, idx):
    # the scores come along for their static shape alone (the sigmoid's own
    # rule keeps them already)
    return _take_chosen(scores, idx), (scores, idx)


def _take_chosen_bwd(res, g):
    # the transpose written out (exact too: a row's choices are distinct),
    # so that XLA is not left to scatter-add N x top_k scalars
    scores, idx = res
    hot = _chosen_mask(idx, scores.shape[-1])
    return jnp.sum(jnp.where(hot, g[:, :, None], 0), axis=1), None


_take_chosen.defvjp(_take_chosen_fwd, _take_chosen_bwd)


def route_noaux_tc(x: jax.Array, router_w: jax.Array, bias: jax.Array, *,
                   top_k: int, scaling: float = 1.0
                   ) -> Tuple[jax.Array, jax.Array]:
    """Auxiliary-loss-free routing (``scoring_func: sigmoid``, ``topk_method:
    noaux_tc`` with one group): ``s = sigmoid(x W_r)`` in float32 over ALL
    experts; the ``top_k`` experts of a token are the largest ``s + bias``
    (``lax.top_k``'s choice: ties to the lower index); their gates are
    ``s[chosen] / sum(s[chosen]) * scaling``. ``bias`` only steers the choice
    and takes no gradient. The choice carries :data:`ROUTER_CHOICE_NAME` for a
    rematerialisation policy to keep.

    x: (N, d); router_w: (d, E); bias: (E,). Returns ``(idx (N, top_k) int32,
    gates (N, top_k) float32)``."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    router_w.astype(jnp.float32)))
    _, idx = lax.top_k(lax.stop_gradient(scores + bias.astype(jnp.float32)),
                       top_k)
    idx = checkpoint_name(idx.astype(jnp.int32), ROUTER_CHOICE_NAME)
    chosen = _take_chosen(scores, idx)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, gates * scaling


def route_softmax_topk(x: jax.Array, router_w: jax.Array, *, top_k: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """Softmax routing with the chosen probabilities renormalised
    (``norm_topk_prob``, no bias, no group): the logits ``r = x W_r`` in
    float32 over ALL experts; the ``top_k`` experts of a token are its
    largest logits (``lax.top_k``'s choice: ties to the lower index); their
    gates are ``softmax(r)[C] / sum_C softmax(r)``, computed as the equal
    ``softmax(r[C])``, which has the same gradient. The choice carries
    :data:`ROUTER_CHOICE_NAME` for a rematerialisation policy to keep.

    x: (N, d); router_w: (d, E). Returns ``(idx (N, top_k) int32, gates (N,
    top_k) float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32))
    _, idx = lax.top_k(lax.stop_gradient(logits), top_k)
    idx = checkpoint_name(idx.astype(jnp.int32), ROUTER_CHOICE_NAME)
    return idx, jax.nn.softmax(_take_chosen(logits, idx), axis=-1)


def expert_load(idx: jax.Array, n_experts: int) -> jax.Array:
    """The step's token-choices for each of ALL experts, (n_experts,) int32:
    a compare-and-sum like the chosen scores', where ``jnp.bincount`` lowers
    to a scatter of every choice."""
    return jnp.sum(_chosen_mask(idx, n_experts), axis=(0, 1),
                   dtype=jnp.int32)


def noaux_bias_update(bias: jax.Array, load: jax.Array, rate: float
                      ) -> jax.Array:
    """The correction bias after a step: ``b_i += rate * sign(mean load -
    load_i)`` with ``load_i`` the step's token-choices for expert i
    (:func:`expert_load`), so an overloaded expert is chosen less and an idle
    one more."""
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load).astype(bias.dtype)


def _fit_tile(size: int, want: int) -> int:
    for cand in (want, 1024, 768, 512, 384, 256, 128):
        if cand <= want and size % cand == 0:
            return cand
    return size


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas_gmm(lhs, rhs, sizes, interpret):
    return _pallas_gmm_fwd(lhs, rhs, sizes, interpret)[0]


def _megablox():
    # the package's own name `gmm` is its differentiable wrapper, which
    # takes no trailing group: the kernels' module is imported by path
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_tiling(m: int, k: int, n: int):
    return (_fit_tile(m, 512), _fit_tile(k, 1024), _fit_tile(n, 1024))


def _pallas_gmm_fwd(lhs, rhs, sizes, interpret):
    mblx = _megablox()
    m, k = lhs.shape
    out = mblx.gmm(lhs, rhs, sizes, lhs.dtype,
                   _gmm_tiling(m, k, rhs.shape[-1]),
                   group_offset=jnp.int32(0), interpret=interpret)
    return out, (lhs, rhs, sizes)


def _pallas_gmm_bwd(interpret, res, g):
    mblx = _megablox()
    lhs, rhs, sizes = res
    m, k = lhs.shape
    n = rhs.shape[-1]
    tiling = _gmm_tiling(m, k, n)
    d_lhs = mblx.gmm(g, rhs, sizes, lhs.dtype, (tiling[0], tiling[2],
                                                tiling[1]),
                     group_offset=jnp.int32(0), transpose_rhs=True,
                     interpret=interpret)
    d_rhs = mblx.tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype, tiling,
                      group_offset=jnp.int32(0),
                      num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array
                   ) -> jax.Array:
    """Rows of ``lhs`` (M, k), sorted by group, each times its group's
    matrix of ``rhs`` (G, k, n). ``sizes`` has G + 1 entries summing to M:
    the last counts trailing rows that belong to no group here, whose
    output is zero. megablox's tiled Pallas kernel, which visits only the
    tiles that hold rows: compiled on a TPU, interpreted elsewhere."""
    return _pallas_gmm(lhs, rhs, sizes.astype(jnp.int32),
                       _attention._interpret())


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _sub_rows(chunk_rows: int, tile: int) -> int:
    """Rows of one sub-block of a chunk's row movement: about an eighth of
    the chunk, a whole number of the grouped product's row tiles, and a
    divisor of the chunk, so no sub-block straddles its end."""
    tiles = chunk_rows // tile
    return tile * max(k for k in range(1, -(-tiles // 8) + 1)
                      if tiles % k == 0)


# A chunk's row movement follows the rows it was sent, rounded up to a
# sub-block: ``lax.switch`` picks, by the number of sub-blocks that hold a
# routed row (which only the device knows), the branch that moves that many
# rows in ONE gather or scatter-add. XLA's row scatter-add costs about a
# millisecond before its first row at these shapes (so a loop over sub-blocks
# loses what it skips) and 2.5 MiB of program a branch (so the scatter-adds
# take sub-blocks twice the gathers': their rows cost less than their
# branches). ``choice`` is the chunk's slice of the sorted token-choices
# (``token * top_k + k``). The switches are not differentiated: each
# direction has its rule, which takes the same rows (the gather's transpose
# is a scatter-add, the combine's a gather). The rules open the
# ``moe.experts`` scope themselves, so the backward's operations are counted
# where the forward's are.

def _for_routed_rows(rows, sub, total, move, *args):
    """``move(m, *args)`` for ``m`` = ``rows`` rounded up to whole sub-blocks
    of ``sub`` rows (``total`` at most): a branch for each such ``m``."""
    sizes = tuple(range(0, total, sub)) + (total,)
    return lax.switch(jnp.minimum(-(-rows // sub), len(sizes) - 1),
                      [functools.partial(move, m) for m in sizes], *args)


def _pad_rows(a, total):
    return jnp.pad(a, ((0, total - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


# The branches are jitted at module level: a layer's switch then calls the
# program another layer (or pass) already traced and lowered for the same
# ``m`` and shapes, where tracing every branch anew took seconds of set-up.

@functools.partial(jax.jit, static_argnums=(0, 1))
def _fetch(top_k, m, x, choice):
    """``x``'s rows for the first ``m`` token-choices, zeros after them."""
    got = x.at[choice[:m] // top_k].get(mode="promise_in_bounds")
    return _pad_rows(got, choice.shape[0]), jnp.int32(m)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _add_rows(top_k, m, y, update, choice):
    """``y[token of choice[p]] += update[p]`` for ``p < m``."""
    return y.at[choice[:m] // top_k].add(update[:m],
                                         mode="promise_in_bounds")


def _live_gates(gates, choice, rows):
    """``(live, g)`` of the first ``len(choice)`` rows of a chunk: which are
    routed here (past the last routed row the choices are of experts held
    elsewhere) and their gates, 0 where not live."""
    live = jnp.arange(choice.shape[0]) < rows
    return live, jnp.where(
        live, gates.at[choice].get(mode="promise_in_bounds"), 0.0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pull_combine(top_k, m, d_y, out, gates, choice, rows):
    """The combine's transpose over the first ``m`` rows: ``(d_out, zeros
    past them; d_gates)``."""
    live, g = _live_gates(gates, choice[:m], rows)
    got = d_y.at[choice[:m] // top_k].get(mode="promise_in_bounds")
    d_g = jnp.where(live, jnp.sum(got * out[:m].astype(jnp.float32), -1), 0.0)
    return (_pad_rows((got * g[:, None]).astype(out.dtype), out.shape[0]),
            jnp.zeros_like(gates).at[choice[:m]].add(
                d_g, mode="promise_in_bounds"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gather_rows(x, choice, rows, subs, top_k, n):
    return _gather_rows_fwd(x, choice, rows, subs, top_k, n)[0]


def _gather_rows_fwd(x, choice, rows, subs, top_k, n):
    """``(buf, moved)``: ``buf[p] = x[token of choice[p]]`` for the ``moved``
    rows ``p`` of the sub-blocks that hold a row ``p < rows``, zeros after
    them. ``subs``: the gathers' and the scatter-adds' sub-block."""
    del n
    return (_for_routed_rows(rows, subs[0], choice.shape[0],
                             functools.partial(_fetch, top_k), x, choice),
            (choice, rows))


def _gather_rows_bwd(subs, top_k, n, res, ct):
    choice, rows = res
    d_buf = ct[0]
    with jax.named_scope("moe.experts"):
        d_x = _for_routed_rows(
            rows, subs[1], choice.shape[0],
            functools.partial(_add_rows, top_k),
            jnp.zeros((n, d_buf.shape[1]), d_buf.dtype), d_buf, choice)
    return d_x, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine_rows(y, out, gates, choice, rows, subs, top_k):
    return _combine_rows_fwd(y, out, gates, choice, rows, subs, top_k)[0]


def _combine_rows_fwd(y, out, gates, choice, rows, subs, top_k):
    """``y[token of choice[p]] += float32(out[p]) * gates[choice[p]]`` over
    the sub-blocks that hold a row ``p < rows``; ``gates`` is (N * top_k,)
    float32. The update is float32 and so is the sum, in the float32 ``y``
    (the whole chunk's update is formed outside the branches, where it
    fuses with the product's own last pass)."""
    update = out.astype(jnp.float32) * _live_gates(gates, choice,
                                                   rows)[1][:, None]
    y = _for_routed_rows(rows, subs[1], choice.shape[0],
                         functools.partial(_add_rows, top_k), y, update,
                         choice)
    return y, (out, gates, choice, rows)


def _combine_rows_bwd(subs, top_k, res, d_y):
    out, gates, choice, rows = res
    with jax.named_scope("moe.experts"):
        d_out, d_gates = _for_routed_rows(
            rows, subs[0], choice.shape[0],
            functools.partial(_pull_combine, top_k), d_y, out, gates, choice,
            rows)
    return d_y, d_out, d_gates, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def held_experts_ffn(x: jax.Array, idx: jax.Array, gates: jax.Array,
                     w_gate: Optional[jax.Array], w_up: jax.Array,
                     w_down: jax.Array, *, first_expert: int, n_experts: int,
                     activation: Callable = jax.nn.silu
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The part of ``y_t = sum_i g_ti F_i(x_t)`` that the experts
    ``first_expert .. first_expert + E_held - 1`` give. The expert's form
    follows its stacks: with ``w_gate`` ``F(x) = (act(x W_gate) * x W_up)
    W_down`` (a SwiGLU at the default ``activation``), with ``w_gate`` None
    ``F(x) = act(x W_up) W_down``.

    x: (N, d); idx, gates: (N, top_k) from the router over all ``n_experts``;
    w_gate, w_up: (E_held, d, f); w_down: (E_held, f, d). The token-choices
    routed here are sorted by expert (a stable sort: tokens ascending inside
    an expert) and worked off in chunks of a static number of rows, each a
    gather, a grouped product a stack and a gate-weighted scatter-add into
    the float32 result. The first chunk (twice the rows a balanced router sends
    here) always runs; the further ones, up to the worst case
    of every choice of every token landing here, run only while rows are
    left and are rebuilt in the backward pass, so imbalance costs time and
    never a token.

    What the first chunk moves follows the rows it was sent, not its size:
    the gather and the scatter-add, and their transposes in the backward
    pass, take its rows up to the end of the sub-block that holds its last
    routed row (an eighth of the chunk for the gathers, a quarter for the
    scatter-adds), in one operation each. The gathered buffer is zeros past
    it, and the grouped products, which run once a chunk over the whole
    buffer, skip those tiles. An overflow's chunks move whole chunks.

    Returns ``(y (N, d) float32, counters)``: ``local_rows`` (token-choices
    routed here), ``moved_rows`` (rows the gathers fetched: the first
    chunk's rows rounded up to a sub-block, an overflow's chunks whole,
    counted from the branches that ran), ``rows_max_over_mean`` (largest
    held expert's rows over the held experts' mean), ``dropped_rows``
    (routed here and not computed: 0 by construction, counted from what the
    chunks did)."""
    with jax.named_scope("moe.experts"):
        return _held_experts_ffn(x, idx, gates, w_gate, w_up, w_down,
                                 first_expert, n_experts, activation)


def _chunk(c, y, x, gates, ws, plan, static):
    """Chunk ``c`` of the sorted token-choices added into ``y``: ``(y, rows
    it held, rows its gather fetched)``."""
    order, starts, ends = plan
    chunk_rows, subs, top_k, act = static
    lo = c * chunk_rows
    choice = lax.dynamic_slice(order, (lo,), (chunk_rows,))
    here = (jnp.clip(ends, lo, lo + chunk_rows)
            - jnp.clip(starts, lo, lo + chunk_rows))
    rows = jnp.sum(here)
    sizes_c = jnp.concatenate([here, (chunk_rows - rows)[None]])
    xs, moved = _gather_rows(x, choice, rows, subs, top_k, x.shape[0])
    wg, wu, wd = ws
    if wg is None:
        h = act(grouped_matmul(xs, wu, sizes_c))
    else:
        h = (act(grouped_matmul(xs, wg, sizes_c))
             * grouped_matmul(xs, wu, sizes_c))
    out = grouped_matmul(h, wd, sizes_c)
    return _combine_rows(y, out, gates, choice, rows, subs, top_k), rows, moved


# The chunks after the first, while rows are left: a loop whose trip count
# follows the rows routed here. Its backward rule walks the same chunks and
# rebuilds each before transposing it, so an overflow holds one chunk's
# intermediates at a time and a step without one pays for neither direction.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _further_chunks(y, x, gates, ws, plan, static):
    return _further_chunks_fwd(y, x, gates, ws, plan, static)[0]


def _chunks_with_rows(plan, static):
    return -(-plan[2][-1] // static[0])


def _further_chunks_fwd(y, x, gates, ws, plan, static):
    """``(y, rows the chunks held, rows their gathers fetched)``."""
    def body(c, carry):
        y, done, moved = carry
        y, rows, fetched = _chunk(c, y, x, gates, ws, plan, static)
        return y, done + rows, moved + fetched

    out = lax.fori_loop(1, _chunks_with_rows(plan, static), body,
                        (y, jnp.int32(0), jnp.int32(0)))
    return out, (x, gates, ws, plan)


def _further_chunks_bwd(static, res, ct):
    x, gates, ws, plan = res
    d_y = ct[0]

    def body(c, acc):
        # a chunk adds into y: its transpose does not depend on that y
        _, pull = jax.vjp(
            lambda *args: _chunk(c, jnp.zeros_like(d_y), *args, plan,
                                 static)[0], x, gates, ws)
        return jax.tree.map(jnp.add, acc, pull(d_y))

    with jax.named_scope("moe.experts"):
        acc = lax.fori_loop(1, _chunks_with_rows(plan, static), body,
                            jax.tree.map(jnp.zeros_like, (x, gates, ws)))
    return (d_y, *acc, None)


_further_chunks.defvjp(_further_chunks_fwd, _further_chunks_bwd)


def _held_experts_ffn(x, idx, gates, w_gate, w_up, w_down, first_expert,
                      n_experts, act):
    n, d = x.shape
    top_k = idx.shape[1]
    e_held = w_up.shape[0]
    local = idx.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < e_held), local, e_held)
    # held experts first, by expert; elsewhere: one trailing group
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[None, :] == jnp.arange(e_held)[:, None], axis=1,
                    dtype=jnp.int32)
    local_rows = jnp.sum(sizes)
    ends = jnp.cumsum(sizes)

    worst = n * min(top_k, e_held)
    share = 2 * n * top_k * e_held // n_experts
    tile = 512 if share >= 512 else 8
    chunk_rows = min(_round_up(max(share, 1), tile), _round_up(worst, tile))
    n_chunks = -(-worst // chunk_rows)
    order = jnp.pad(order, (0, max(n_chunks * chunk_rows - n * top_k, 0)))
    args = (x, gates.reshape(-1),
            tuple(None if w is None else w.astype(x.dtype)
                  for w in (w_gate, w_up, w_down)),
            (order, ends - sizes, ends))
    sub = _sub_rows(chunk_rows, tile)
    y, done, moved = _chunk(0, jnp.zeros((n, d), jnp.float32), *args,
                            (chunk_rows, (sub, 2 * sub), top_k, act))
    if n_chunks > 1:
        # an overflow's chunks are full but for the last: they move whole
        # chunks
        y, held, fetched = _further_chunks(
            y, *args, (chunk_rows, (chunk_rows, chunk_rows), top_k, act))
        done, moved = done + held, moved + fetched
    mean = jnp.maximum(local_rows.astype(jnp.float32) / e_held, 1e-9)
    return y, {"local_rows": local_rows, "moved_rows": moved,
               "rows_max_over_mean": jnp.max(sizes).astype(jnp.float32) / mean,
               "dropped_rows": local_rows - done}
