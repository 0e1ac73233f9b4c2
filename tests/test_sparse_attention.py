"""Learned sparse attention's operations: the exact top-k selection against
``lax.top_k`` bit for bit, ties included; the packed selection; the
selected-key flash kernels (interpret mode) against a dense masked softmax,
forward and backward, on every backward path, and against the plain causal
kernels where every causal key is selected; the tile lists they walk; the
entries they count; the indexer's KL loss and its gradients, one kernel
over the causal tiles, against plain autodiff; the kernels lowered for the
chip."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import analytics_zoo_tpu.ops.attention as attn
from analytics_zoo_tpu.ops import sparse_attention as sa
from analytics_zoo_tpu.ops.attention import (
    SELECTED, flash_attention, mha_reference, pack_selection,
    selection_entries, unpack_selection)
from test_attention import equations, pallas_kernels


def _top_k_rows(scores, need):
    """``lax.top_k``'s selection of each row, as a boolean mask."""
    rows = []
    for row, n in zip(np.asarray(scores), np.asarray(need)):
        idx = np.asarray(lax.top_k(jnp.asarray(row), int(n))[1])
        keep = np.zeros(row.shape, bool)
        keep[idx] = True
        rows.append(keep)
    return np.stack(rows)


@pytest.mark.parametrize("kind", ["distinct", "ties", "zeros", "infinite"])
def test_the_selection_is_top_ks_bit_for_bit(kind):
    """The bisection's selection is ``lax.top_k``'s on the same scores:
    distinct values, heavy ties (halves of small integers), a row of -0.0
    and 0.0 (``lax.top_k`` ranks -0.0 below 0.0), rows that end in -inf
    (the keys after a query); every need from 1 to the row."""
    rng = np.random.RandomState(["distinct", "ties", "zeros",
                                 "infinite"].index(kind))
    scores = rng.randn(8, 96).astype(np.float32)
    if kind == "ties":
        scores = np.round(scores * 3) / 2
    elif kind == "zeros":
        scores[:, ::2] = -0.0
        scores[:, 1::2] = 0.0
        scores[:, 40:50] = 1.0
    elif kind == "infinite":
        scores[:, 60:] = -np.inf
    need = np.array([1, 2, 7, 31, 48, 60, 95, 96], np.int32)
    if kind == "infinite":
        need = np.minimum(need, 60)
    got = np.asarray(sa.topk_mask(jnp.asarray(scores), jnp.asarray(need)))
    np.testing.assert_array_equal(got, _top_k_rows(scores, need))
    np.testing.assert_array_equal(got.sum(-1), need)


@pytest.mark.parametrize("rows", [32, 64])
def test_the_packed_selection_round_trips(rows):
    keep = np.random.RandomState(rows).rand(2, 128, 96) < 0.3
    bits = pack_selection(jnp.asarray(keep), rows)
    assert bits.shape == (2, 4, 96) and bits.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_selection(bits, rows)),
                                  keep)
    # bit j of word w of a group is the group's row j * rows / 32 + w
    r = rows // 32
    word = int(np.asarray(bits)[1, 2 * r - 1, 5])     # group 1, word r - 1
    assert all(bool(word >> j & 1) == keep[1, rows + j * r + r - 1, 5]
               for j in range(32))


def _selection(b, s, share, seed, empty_block=None):
    """A causal selection, every query's own key among it; with
    ``empty_block`` (q tile, k block, side) one off-diagonal tile that
    holds no selected entry."""
    rng = np.random.RandomState(seed)
    keep = (rng.rand(b, s, s) < share) & np.tri(s, dtype=bool)
    keep |= np.eye(s, dtype=bool)
    if empty_block is not None:
        qi, ki, side = empty_block
        keep[:, qi * side:(qi + 1) * side, ki * side:(ki + 1) * side] = False
    return jnp.asarray(keep)


def _gqa(b, s, h, hk, d, seed):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))  # noqa: E731
    return (mk(b, s, h, d) * 0.5, mk(b, s, hk, d) * 0.5, mk(b, s, hk, d),
            mk(b, s, h, d))


_BUDGETS = {"fused": None, "fused_by_head": "by_head", "two_kernel": 0}


@pytest.mark.parametrize("path,h,hk", [
    ("fused", 4, 2), ("fused", 2, 2), ("fused_by_head", 4, 2),
    ("two_kernel", 4, 2), ("two_kernel", 4, 4)])
def test_the_selected_key_kernels_are_a_dense_masked_softmax(monkeypatch,
                                                             path, h, hk):
    """Forward and the three gradients of ``mask="selected"`` (4 x 4 tiles
    of 32, a tile with no selected entry among them, grouped query heads)
    against ``mha_reference`` with the same entries kept, on each backward
    path: two kernels a call site where dQ rides the dK/dV launch, three
    where it does not."""
    b, s, d, block = 2, 128, 16, 32
    q, k, v, w = _gqa(b, s, h, hk, d, seed=h + hk)
    keep = _selection(b, s, 0.3, seed=1, empty_block=(2, 1, block))
    bits = pack_selection(keep, block)
    budget = _BUDGETS[path]
    if budget == "by_head":
        budget = (attn._fused_bwd_dq_bytes(s, d, q.dtype)
                  + 2 * attn._fused_bwd_dq_bytes(s, d, k.dtype))
    if budget is not None:
        monkeypatch.setattr(attn, "_FUSED_BWD_DQ_BYTES", budget)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, mask=SELECTED,
                                          selection=bits, block=block) * w)

    got = flash_attention(q, k, v, mask=SELECTED, selection=bits,
                          block=block)
    want = mha_reference(q, k, v, mask=SELECTED, selection=bits, block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    grad = jax.grad(loss(flash_attention), (0, 1, 2))
    kernels = pallas_kernels(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    assert kernels == ["_flash_kernel"] + (
        ["_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"]
        if path == "two_kernel" else ["_flash_bwd_fused_kernel"])
    for g, r in zip(grad(q, k, v),
                    jax.grad(loss(mha_reference), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_every_causal_key_selected_is_the_causal_kernel():
    """With every causal key selected the selected-key kernels give the
    plain causal ones' output and gradients."""
    b, s, h, hk, d, block = 1, 128, 4, 2, 16, 32
    q, k, v, w = _gqa(b, s, h, hk, d, seed=5)
    bits = pack_selection(jnp.broadcast_to(jnp.tri(s, dtype=bool),
                                           (b, s, s)), block)

    def run(selected):
        def loss(q, k, v):
            out = flash_attention(q, k, v, mask=SELECTED, selection=bits,
                                  block=block) if selected else \
                flash_attention(q, k, v, causal=True, block_q=block,
                                block_k=block)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return out, grads

    (out_s, g_s), (out_c, g_c) = run(True), run(False)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_c),
                               rtol=1e-5, atol=1e-5)
    for a, c in zip(g_s, g_c):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)


def test_the_logsumexp_is_the_selected_rows_and_not_differentiated():
    b, s, h, hk, d, block = 1, 64, 2, 1, 16, 32
    q, k, v, _ = _gqa(b, s, h, hk, d, seed=2)
    keep = _selection(b, s, 0.5, seed=3)
    bits = pack_selection(keep, block)
    out, lse = flash_attention(q, k, v, mask=SELECTED, selection=bits,
                               block=block, return_lse=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // hk, 2)) \
        / np.sqrt(d)
    want = jax.scipy.special.logsumexp(
        jnp.where(keep[:, None], scores, -jnp.inf), axis=-1) / np.log(2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, mask=SELECTED, selection=bits, block=block,
        return_lse=True)[1]))(q)
    assert not np.any(np.asarray(g))


def test_the_walks_visit_every_causal_tile():
    """The lists a selected-key grid walks: by q tile, each tile's causal
    k blocks ascending; by k block, each block's q tiles a query head after
    another; a q tile's dQ rows are whole after its diagonal block; the
    entries counted are the walked tiles' and the selected ones."""
    b, s, block = 1, 128, 32
    keep = _selection(b, s, 0.0, seed=0)              # the diagonal alone
    keep = keep.at[:, 100, 5].set(True)               # tile (3, 0)
    bits = pack_selection(keep, block)
    walk = attn._causal_walk(4, "q").reshape(4, -1)
    q, k, head, flags = walk
    assert list(zip(q, k)) == [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1),
                               (2, 2), (3, 0), (3, 1), (3, 2), (3, 3)]
    runs = flags & (attn._FIRST | attn._LAST)
    assert list(runs[:6]) == [attn._FIRST | attn._LAST, attn._FIRST,
                              attn._LAST, attn._FIRST, 0, attn._LAST]
    by_k = attn._causal_walk(4, "k", group=2).reshape(4, -1)
    q, k, head, flags = by_k
    assert by_k.shape[1] == 20
    assert list(zip(k[:10], head[:10], q[:10])) == [
        (0, 0, 0), (0, 0, 1), (0, 0, 2), (0, 0, 3), (0, 1, 0), (0, 1, 1),
        (0, 1, 2), (0, 1, 3), (1, 0, 1), (1, 0, 2)]
    assert [bool(f & attn._DQ_LAST) for f in flags] == list(k == q)
    computed, needed = selection_entries(bits, block)
    assert float(computed) == 10 * block * block and float(needed) == s + 1


def test_the_key_bins_count_each_querys_selected_keys_by_span():
    """``selection_key_bins``: each query's selected keys counted in spans
    of equal width over the keys, from the packed selection."""
    b, s, rows, bins = 2, 128, 64, 8
    keep = _selection(b, s, 0.3, seed=7)
    got = sa.selection_key_bins(pack_selection(keep, rows), rows, bins)
    want = np.asarray(keep).reshape(b, s, bins, s // bins).sum(-1)
    assert got.shape == (b, s, bins) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


def test_a_bad_selected_call_is_refused():
    q, k, v, _ = _gqa(1, 64, 2, 1, 16, seed=0)
    bits = pack_selection(_selection(1, 64, 0.5, seed=0), 32)
    for kwargs in ({"block": 48}, {"block": 32, "causal": True},
                   {"block": 32, "selection": None},
                   {"block": 64, "selection": bits[:, :1]}):
        with pytest.raises(ValueError, match="selected-key"):
            flash_attention(q, k, v, mask=SELECTED,
                            **dict({"selection": bits}, **kwargs))
    with pytest.raises(ValueError, match="selected-key"):
        flash_attention(q, k, v, causal=True, return_lse=True)


def _plain_indexer(q_i, w, k_i, q, k, keep):
    """The indexer's loss by plain autodiff, each sequence's."""
    scores = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(
        jnp.einsum("bqjd,bsd->bqjs", q_i, k_i)), w)
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    h = q.shape[2]
    att = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, h // k.shape[2], 2)
                     ) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[:, None], att, -jnp.inf), -1).mean(1)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    return jnp.sum(jnp.where(keep, plogp - p * logq, 0.0), -1).mean(-1)


def test_the_indexer_selects_and_learns_as_plain_autodiff_does():
    """``indexer_select`` is each causal row's top-k of the indexer's
    scores (fewer keys than k: all of them); ``indexer_kl``'s value and
    gradients are plain autodiff's, each sequence's loss scaled by its own
    cotangent; the target's inputs get no gradient."""
    b, s, hi, di, h, hk, d, rows, top = 2, 128, 4, 16, 4, 2, 32, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    q_i = jax.random.normal(ks[0], (b, s, hi, di))
    k_i = jax.random.normal(ks[1], (b, s, di))
    w = jax.random.normal(ks[2], (b, s, hi))
    q, k, v, _ = _gqa(b, s, h, hk, d, seed=4)
    bits = sa.indexer_select(q_i, w, k_i, top, rows)
    keep = unpack_selection(bits, rows)
    scores = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(
        jnp.einsum("bqjd,bsd->bqjs", q_i, k_i)), w)
    causal = jnp.where(jnp.tri(s, dtype=bool), scores, -jnp.inf)
    need = np.minimum(top, np.arange(s) + 1)
    for seq in range(b):
        np.testing.assert_array_equal(
            np.asarray(keep[seq]), _top_k_rows(causal[seq], need))
    _, lse = flash_attention(q, k, v, mask=SELECTED, selection=bits,
                             block=rows, return_lse=True)
    cot = jnp.array([1.0, 2.5])

    def ours(q_i, w, k_i, q, k):
        return jnp.sum(cot * sa.indexer_kl(q_i, w, k_i, q, k, lse, bits,
                                           sm_scale=d ** -0.5, rows=rows))

    def plain(q_i, w, k_i, q, k):
        return jnp.sum(cot * _plain_indexer(q_i, w, k_i, q, k, keep))

    np.testing.assert_allclose(float(ours(q_i, w, k_i, q, k)),
                               float(plain(q_i, w, k_i, q, k)), rtol=1e-5)
    got = jax.grad(ours, (0, 1, 2, 3, 4))(q_i, w, k_i, q, k)
    want = jax.grad(plain, (0, 1, 2))(q_i, w, k_i, q, k)
    for g, r in zip(got[:3], want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-6)
    assert not np.any(np.asarray(got[3])) and not np.any(np.asarray(got[4]))


def test_the_selected_key_kernels_lower_to_mosaic_for_tpu(monkeypatch):
    """Cross-lowered for platform tpu at 512-wide tiles (the cell's): one
    custom call forward, the forward and the fused backward for the
    gradient; the selection's words unpack in the kernel."""
    monkeypatch.setattr(attn, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.bfloat16)
    bits = jax.ShapeDtypeStruct((1, 32, 1024), jnp.uint32)

    def fwd(q, k, v, bits):
        return flash_attention(q, k, v, mask=SELECTED, selection=bits,
                               block=512)

    def grad(q, k, v, bits):
        return jax.grad(lambda *a: fwd(*a, bits).astype(jnp.float32).sum(),
                        (0, 1, 2))(q, k, v)

    for fn, calls in ((fwd, 1), (grad, 2)):
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            q, kv, kv, bits).mlir_module()
        assert text.count("tpu_custom_call") == calls


def test_the_kl_kernel_lowers_to_mosaic_for_tpu(monkeypatch):
    """Cross-lowered for platform tpu at the cell's tile (512) and indexer
    widths (16 heads of 64), bf16: the loss and its gradients are one
    custom call, under the ``sa.indexer`` scope."""
    monkeypatch.setattr(attn, "_interpret", lambda: False)
    s, bf = 1024, jnp.bfloat16
    shapes = [((1, s, 16, 64), bf), ((1, s, 16), jnp.float32),
              ((1, s, 64), bf), ((1, s, 8, 128), bf), ((1, s, 2, 128), bf),
              ((1, 8, s), jnp.float32), ((1, s // 32, s), jnp.uint32)]

    def grad(q_i, w, k_i, q, k, lse, bits):
        return jax.grad(lambda *a: jnp.sum(sa.indexer_kl(
            *a, q, k, lse, bits, sm_scale=128 ** -0.5, rows=512)),
            (0, 1, 2))(q_i, w, k_i)

    exported = jax.export.export(jax.jit(grad), platforms=["tpu"])(
        *(jax.ShapeDtypeStruct(*a) for a in shapes))
    text = exported.mlir_module()
    assert text.count("tpu_custom_call") == 1
    call, = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert "_kl_kernel" in call
    # the op's name, which the profiler's tf_op stat carries, is under
    # the scope
    loc = re.search(r"loc\((#loc\d+)\)\s*$", call).group(1)
    name, = [line for line in text.splitlines()
             if line.startswith(loc + " ")]
    assert "sa.indexer)/pallas_call" in name


def test_the_kernels_walk_the_causal_tiles_with_the_words_beside():
    """A selected-key call's launches walk the causal tiles' static list,
    the selection's words the operand after it."""
    b, s, h, hk, d, block = 1, 128, 2, 1, 16, 32
    q, k, v, w = _gqa(b, s, h, hk, d, seed=0)
    bits = pack_selection(_selection(b, s, 0.3, seed=0), block)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, mask=SELECTED, selection=bits, block=block) * w)))(q).jaxpr
    launches = [e for e in equations(jaxpr)
                if e.primitive.name == "pallas_call"]
    assert [tuple(e.params["grid_mapping"].grid) for e in launches] == [
        (h, 10), (hk, 2 * 10)]
    for e in launches:
        walk, words = (x.aval for x in e.invars[:2])
        assert walk.shape == (4 * e.params["grid_mapping"].grid[1],)
        assert words.shape == (b, s // 32, s) and words.dtype == jnp.int32


def _indexer_case(b, s, top, seed, block=32, underflow=False):
    """The indexer's and the main attention's inputs of a toy layer (4
    indexer heads of 16; 4 query heads on 2 kv heads of 16), the selection
    ``indexer_select`` makes from them, the flash kernel's logsumexp over
    it and the selection unpacked. With ``underflow`` query 5's every head
    scores key 2 so low that the target's entry there is 0 in float32:
    an early row selects all of its keys, that one among them."""
    hi, di, h, hk, d = 4, 16, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_i = jax.random.normal(ks[0], (b, s, hi, di))
    k_i = jax.random.normal(ks[1], (b, s, di))
    w = jax.random.normal(ks[2], (b, s, hi))
    q, k, _, _ = _gqa(b, s, h, hk, d, seed=seed)
    if underflow:
        u = np.ones(d, np.float32) / np.sqrt(d)
        q = q.at[:, 5].set(40 * u)
        k = k.at[:, 2].set(-40 * u)
    bits = sa.indexer_select(q_i, w, k_i, top, block)
    _, lse = flash_attention(q, k, k, mask=SELECTED, selection=bits,
                             block=block, return_lse=True)
    return (q_i, w, k_i, q, k, lse, bits), unpack_selection(bits, block)


@pytest.mark.parametrize("s,top,underflow", [
    (64, 24, False), (64, 48, False), (128, 24, False), (128, 48, False),
    (128, 48, True)])
def test_the_kl_kernel_is_plain_autodiff_of_the_dense_loss(s, top,
                                                           underflow):
    """``_kl_and_grads``, one kernel over 2 or 4 causal tiles of 32 with
    two sequences: each sequence's ``L_I`` and the gradients of their sum
    in q^I, w and k^I are plain autodiff's of the dense float32 loss, with
    ``top`` under and over the tile's side (the early rows hold fewer keys
    than ``top``), and with a selected entry whose target is 0."""
    b, d = 2, 16
    args, keep = _indexer_case(b, s, top, seed=s + top, underflow=underflow)
    q_i, w, k_i, q, k, lse, bits = args
    if underflow:
        att = jnp.einsum("bhd,bhd->bh", q[:, 5],
                         jnp.repeat(k[:, 2], 2, axis=1)) / np.sqrt(d)
        assert np.all(np.asarray(keep[:, 5, 2]))
        assert not np.any(np.asarray(jnp.exp2(att * attn.LOG2_E
                                              - lse[:, :, 5])))
    value, grads = sa._kl_and_grads(32, d ** -0.5, *args)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(_plain_indexer(*a, q, k, keep)), (0, 1, 2))(
        q_i, w, k_i)
    np.testing.assert_allclose(np.asarray(value),
                               np.asarray(_plain_indexer(q_i, w, k_i, q, k,
                                                         keep)),
                               rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(value)), float(want), rtol=1e-5)
    for g, r in zip(grads, want_grads):
        assert g.shape == r.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-6)


def test_the_kl_kernel_walks_each_q_tiles_causal_tiles_twice():
    """One launch a call: grid (batch, 2 x n(n+1)/2 entries) over
    ``_causal_walk(n, "q", group=2)``, the tile list its first operand and
    the selection's words beside it; ``zoo_attention_grid_steps_total``
    counts every step under ``sa_kl``, issued and computed alike."""
    b, s, block = 1, 128, 32
    n = s // block
    args, _ = _indexer_case(b, s, 24, seed=0)
    before = _grid_steps_of(sa.SA_KL)
    jaxpr = jax.make_jaxpr(lambda *a: sa._kl_and_grads(block, 0.25, *a))(
        *args).jaxpr
    after = _grid_steps_of(sa.SA_KL)
    launches = [e for e in equations(jaxpr)
                if e.primitive.name == "pallas_call"]
    assert pallas_kernels(jaxpr) == ["_kl_kernel"]
    assert tuple(launches[0].params["grid_mapping"].grid) == (b, n * (n + 1))
    walk, words = (x.aval for x in launches[0].invars[:2])
    assert walk.shape == (4 * n * (n + 1),)
    assert words.shape == (b, s // 32, s) and words.dtype == jnp.int32
    assert {kind: after[kind] - before[kind] for kind in after} == {
        "issued": 2 * n * (n + 1) // 2, "computed": 2 * n * (n + 1) // 2}


def _grid_steps_of(mask):
    return {kind: attn._GRID_STEPS.labels(mask=mask, kind=kind).value
            for kind in ("issued", "computed")}
