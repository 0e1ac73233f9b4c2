"""Plain float32 building blocks of the reference models.

Straightforward ``jax.numpy``/``lax`` at the highest matmul precision, written
from the papers' equations. Nothing here imports the program under test.

``quant`` is the control's hook (see PERF.md, "How correct is decided"): a
function applied to both operands and to the result of every convolution and
dense product, as the program's bfloat16 applies to its own. The reference
passes ``None``; the control passes a rounding through an 8-bit float, the
precision next below the bfloat16 the configurations state.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
# channel statistics of ImageNet on the 0..255 scale (torchvision's constants)
PIXEL_MEAN = (123.675, 116.28, 103.53)
PIXEL_STD = (58.395, 57.12, 57.375)

Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8_quant(x):
    """Round a tensor through float8 e4m3 (3 bits of mantissa; bfloat16 has
    7) with one scale for the tensor, as 8-bit training recipes do, so that
    nothing overflows or underflows; the gradient passes straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(rounded - x)


def normalise(images_uint8):
    x = images_uint8.astype(jnp.float32)
    return (x - jnp.asarray(PIXEL_MEAN, jnp.float32)) / jnp.asarray(
        PIXEL_STD, jnp.float32)


def same_pad(size: int, k: int, stride: int) -> Tuple[int, int]:
    """TensorFlow's 'SAME': the output is ceil(size/stride) wide and the odd
    cell of padding goes after."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def conv(x, w, stride: int = 1, pad="SAME", quant: Quant = None):
    """NHWC convolution with an HWIO kernel, no bias."""
    if pad == "SAME":
        pad = (same_pad(x.shape[1], w.shape[0], stride),
               same_pad(x.shape[2], w.shape[1], stride))
    if quant is not None:
        x, w = quant(x), quant(w)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y if quant is None else quant(y)


def dense(x, w, b, quant: Quant = None):
    if quant is not None:
        x, w = quant(x), quant(w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return (y if quant is None else quant(y)) + b


def batch_norm_train(x, scale, bias, eps: float):
    """Training-mode batch normalisation over batch and space (Ioffe and
    Szegedy 2015, algorithm 1); returns the batch's mean and biased variance
    beside the output."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + eps) * scale + bias
    return y, mean, var


def max_pool(x, k: int, stride: int, pad):
    if pad == "SAME":
        pad = (same_pad(x.shape[1], k, stride), same_pad(x.shape[2], k, stride))
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1), (1, stride, stride, 1),
        ((0, 0), tuple(pad[0]), tuple(pad[1]), (0, 0)))


def softmax_cross_entropy(logits, labels):
    """Mean over the batch of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None], -1)
    return -jnp.mean(picked)


def learning_rate(opt: dict, global_batch: int, steps_per_epoch: int,
                  step: int) -> float:
    """The recipe's rate at a 0-based step: linear warm-up from 0 to the peak
    over ``warmup_epochs``, then polynomial decay to 0 over ``decay_epochs``."""
    peak = opt["peak_lr_per_256"] * global_batch / 256
    warm = opt["warmup_epochs"] * steps_per_epoch
    if step < warm:
        return peak * step / warm
    span = opt["decay_epochs"] * steps_per_epoch
    frac = min(step - warm, span) / span
    return peak * (1.0 - frac) ** opt["decay_power"]


def sgd_momentum(params: Dict, trace: Dict, grads: Dict, lr, momentum: float):
    """m <- momentum*m + g ; p <- p - lr*m (Sutskever et al. 2013, as SGD with
    momentum is commonly implemented, no dampening, no Nesterov)."""
    trace = {k: momentum * trace[k] + grads[k] for k in params}
    params = {k: params[k] - lr * trace[k] for k in params}
    return params, trace


# --- seeded weights, made on the device in one jitted call ------------------

def make_weights(shapes: Dict[str, Tuple[int, ...]], seed: int,
                 scale_by_suffix: Optional[Dict[str, float]] = None) -> Dict:
    """Float32 weights for ``shapes`` (name -> shape) from ``seed``: kernels
    He-normal over their fan-in, BatchNorm scales m (1 + 0.1 n) with m = 1
    unless ``scale_by_suffix`` gives another mean for the name's ending,
    BatchNorm and dense biases 0.1 n. One jitted call; the same seed gives
    the same weights on any device count."""
    names = sorted(shapes)
    by_suffix = scale_by_suffix or {}

    def mean_scale(name):
        return next((m for suffix, m in by_suffix.items()
                     if name.endswith(suffix)), 1.0)

    def init(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            n = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name.endswith("/kernel"):
                fan_in = int(np.prod(shape[:-1]))
                out[name] = n * np.float32(np.sqrt(2.0 / fan_in))
            elif name.endswith("/scale"):
                out[name] = np.float32(mean_scale(name)) * (1.0 + 0.1 * n)
            else:
                out[name] = 0.1 * n
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
