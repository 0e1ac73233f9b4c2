"""The Mamba-2 scan's Pallas kernels (``ops/ssm.py``), interpreted on the
CPU at small shapes: the forward and all six gradients against the literal
recurrence in float32 and against the chunked ``jax.numpy`` form, over
groups, heads a group, chunk sizes, one chunk and several, and head blocks
smaller than a group; a rematerialised mixer's gradients the kernels' as the
chunked form's; the head block each hybrid cell's shape takes; the path
counter. What the kernels cost is the chip's to say
(``scripts/ssd_scan_probe.py``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.ops import ssm
from analytics_zoo_tpu.pipeline.api.keras.layers import decoder_lm


def scan_inputs(seed, bsz, seq, h, g, p=8, n=16):
    rs = np.random.RandomState(seed)
    f = (lambda *s: jnp.asarray(rs.randn(*s), jnp.float32))
    # small dt |A|: what position 0 wrote is still there chunks later
    dt = jax.nn.softplus(f(bsz, seq, h) - 3.0)
    a = -jnp.exp(jnp.asarray(rs.rand(h), jnp.float32))
    return f(bsz, seq, h, p), dt, a, f(bsz, seq, g, n), f(bsz, seq, g, n), \
        f(h)


def loss_of(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a)))


def assert_close(got, want, tol):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= tol * scale


def kernels(chunk):
    return functools.partial(ssm._ssd_kernels, chunk=chunk)


@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("heads_per_group", [1, 4])
@pytest.mark.parametrize("groups", [1, 2])
def test_kernels_are_the_recurrence_and_the_chunked_form(
        groups, heads_per_group, chunk, chunks):
    h = groups * heads_per_group
    args = scan_inputs(7 + chunk + chunks, 2, chunk * chunks, h, groups)
    want = jax.jit(ssm.ssd_scan_sequential)(*args)
    got = jax.jit(kernels(chunk))(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert_close(got, want, 1e-5)
    assert_close(got, ssm._ssd_chunked(*args, chunk), 1e-5)
    grads = jax.jit(jax.grad(loss_of(kernels(chunk)), argnums=range(6)))(
        *args)
    for name, fn in (
            ("recurrence", ssm.ssd_scan_sequential),
            ("chunked", functools.partial(ssm._ssd_chunked, chunk=chunk))):
        want_g = jax.jit(jax.grad(loss_of(fn), argnums=range(6)))(*args)
        for k, (gk, wk) in enumerate(zip(grads, want_g)):
            assert gk.shape == wk.shape, (name, k)
            assert_close(gk, wk, 1e-4)


@pytest.mark.parametrize("heads", [1, 2])
def test_head_blocks_smaller_than_a_group_sum_its_b_and_c(heads):
    """Four heads of one group in blocks of ``heads``: the group's dB and dC
    are summed over blocks that follow one another on the grid."""
    chunk = 16
    args = scan_inputs(31 + heads, 2, 3 * chunk, 4, 1)
    y, states = ssm._scan_forward(*args, chunk, heads, True, True)
    dy = jnp.cos(y)                      # the cotangent of loss_of's sin
    got = ssm._scan_backward(*args, states, dy, chunk, heads, True)
    assert_close(y, ssm.ssd_scan_sequential(*args), 1e-5)
    want = jax.grad(loss_of(ssm.ssd_scan_sequential), argnums=range(6))(
        *args)
    for gk, wk in zip(got, want):
        assert_close(gk, wk, 1e-4)


@pytest.mark.parametrize("chunk", [16, 32])
def test_a_checkpointed_mixer_has_the_chunked_forms_gradients(
        chunk, monkeypatch):
    """A ``Mamba2Mixer`` under the blocks' remat policy: the kernels'
    residuals (the entering states) are rebuilt in the backward pass, and
    every gradient, the parameters' and the input's, is the chunked
    form's."""
    mixer = decoder_lm.Mamba2Mixer(
        num_heads=4, head_dim=8, n_groups=1, state_size=16, conv_kernel=4,
        chunk_size=chunk, eps=1e-5, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 64, 24), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(0), x)

    def grads(path):
        monkeypatch.setattr(ssm, "_scan_path", lambda *a: path)
        apply = jax.checkpoint(lambda p, v: mixer.apply(p, v),
                               policy=decoder_lm._KEPT_ACROSS_REMAT)
        return jax.grad(lambda p, v: jnp.sum(jnp.sin(apply(p, v))),
                        argnums=(0, 1))(params, x)

    got, want = grads("kernel"), grads("chunked")
    for gk, wk in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert_close(gk, wk, 1e-5)


@pytest.mark.parametrize("cell, h, q", [
    ("granite4_h_micro", 64, 256), ("nemotron3_super", 16, 128)])
def test_each_cells_head_block_fits_the_scoped_vmem(cell, h, q):
    """One group, heads of 64, state 128, 8192 positions, bf16: the block
    takes whole lane tiles of x and whole sublane tiles of dt, and what its
    step holds fits 16 MiB."""
    heads = ssm._heads_per_block(h, h, q, 8192, 64, 128, 2)
    assert heads == 16
    assert heads * 64 % 128 == 0 and heads % 8 == 0 and h % heads == 0
    assert ssm._vmem_bytes(heads, h, q, 64, 128, 2) <= 16 * 2 ** 20
    assert ssm._vmem_bytes(2 * heads, h, q, 64, 128, 2) > 16 * 2 ** 20 \
        or 2 * heads > h


@pytest.mark.parametrize("seq, path", [
    (64, "chunked"), (48, "chunked"), (44, "sequential")])
def test_the_path_a_call_site_takes_is_counted(seq, path):
    """On the CPU a whole number of chunks (or a sequence shorter than one)
    takes the chunked form, else the recurrence; each call site traced
    counts once under its path, and the kernels' only on a TPU."""
    args = scan_inputs(5, 1, seq, 4, 2)
    counts = {k: ssm._SCAN_PATHS.labels(path=k).value
              for k in ("kernel", "chunked", "sequential")}
    ssm.ssd_scan(*args, chunk_size=16 if seq != 48 else 64)
    for k, before in counts.items():
        assert ssm._SCAN_PATHS.labels(path=k).value == before + (k == path)


def test_the_kernel_path_is_taken_where_the_kernels_compile(monkeypatch):
    """Where the flash kernels compile (``attention._interpret`` False: a
    TPU) a tileable scan takes the kernels and counts ``kernel``."""
    from analytics_zoo_tpu.ops import attention
    x = jnp.zeros((1, 512, 16, 64), jnp.bfloat16)
    b = jnp.zeros((1, 512, 1, 128), jnp.bfloat16)
    monkeypatch.setattr(attention, "_interpret", lambda: False)
    assert ssm._scan_path(x, b, 128) == "kernel"
    assert ssm._scan_path(x[:, :500], b[:, :500], 128) == "sequential"
    # a chunk that is no whole lane tile does not tile on the chip, nor do
    # two groups of 64 states, nor heads of 96
    assert ssm._scan_path(x[:, :480], b[:, :480], 96) == "chunked"
    assert ssm._scan_path(x, b.reshape(1, 512, 2, 64), 128) == "chunked"
    assert ssm._scan_path(x[..., :48].reshape(1, 512, 8, 96), b, 128) \
        == "chunked"
    monkeypatch.setattr(attention, "_interpret", lambda: True)
    assert ssm._scan_path(x, b, 128) == "chunked"
