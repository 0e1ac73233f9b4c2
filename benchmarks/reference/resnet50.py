"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1), plain float32.

Bottleneck blocks [3, 4, 6, 3] at widths 64..512 (x4 out), the stride of a
down-sampling block in its 3x3 convolution ("v1.5"), a projection shortcut
only where the shape changes, batch normalisation after every convolution,
global average pooling and a 1000-way dense head. Parameters are a flat dict
``name -> array``; names end in ``/kernel``, ``/scale`` or ``/bias``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import nn


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    f0, exp = cfg["num_filters"], cfg["bottleneck_expansion"]
    shapes = {"stem/conv/kernel": (7, 7, 3, f0)}
    _bn(shapes, "stem/bn", f0)
    cin = f0
    for i, n in enumerate(cfg["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(n):
            p = f"stage{i + 1}/block{j}"
            shapes[f"{p}/conv1/kernel"] = (1, 1, cin, f)
            shapes[f"{p}/conv2/kernel"] = (3, 3, f, f)
            shapes[f"{p}/conv3/kernel"] = (1, 1, f, f * exp)
            for k, c in (("bn1", f), ("bn2", f), ("bn3", f * exp)):
                _bn(shapes, f"{p}/{k}", c)
            if j == 0:
                shapes[f"{p}/proj/kernel"] = (1, 1, cin, f * exp)
                _bn(shapes, f"{p}/proj_bn", f * exp)
            cin = f * exp
    shapes["head/kernel"] = (cin, cfg["num_classes"])
    shapes["head/bias"] = (cfg["num_classes"],)
    return shapes


def _bn(shapes, prefix, c):
    shapes[f"{prefix}/scale"] = (c,)
    shapes[f"{prefix}/bias"] = (c,)


def forward(cfg: dict, params: Dict, images_uint8, quant: nn.Quant = None):
    """Training-mode forward pass: uint8 NHWC images -> logits."""
    eps = cfg["bn_epsilon"]

    def cbr(x, conv_name, bn_name, stride=1, pad="SAME", relu=True):
        y = nn.conv(x, params[f"{conv_name}/kernel"], stride, pad, quant)
        y, _, _ = nn.batch_norm_train(y, params[f"{bn_name}/scale"],
                                      params[f"{bn_name}/bias"], eps)
        return jax.nn.relu(y) if relu else y

    def block(x, p, stride, project):
        y = cbr(x, f"{p}/conv1", f"{p}/bn1")
        y = cbr(y, f"{p}/conv2", f"{p}/bn2", stride)
        y = cbr(y, f"{p}/conv3", f"{p}/bn3", relu=False)
        if project:
            x = cbr(x, f"{p}/proj", f"{p}/proj_bn", stride, relu=False)
        return jax.nn.relu(x + y)

    x = nn.normalise(images_uint8)
    x = cbr(x, "stem/conv", "stem/bn", 2, ((3, 3), (3, 3)))
    x = nn.max_pool(x, 3, 2, ((1, 1), (1, 1)))
    for i, n in enumerate(cfg["stage_sizes"]):
        for j in range(n):
            stride = 2 if i > 0 and j == 0 else 1
            # rematerialised block by block so that float32 activations of
            # the timed batch fit beside nothing else on the chip
            x = jax.checkpoint(block, static_argnums=(1, 2, 3))(
                x, f"stage{i + 1}/block{j}", stride, j == 0)
    x = jnp.mean(x, axis=(1, 2))
    return nn.dense(x, params["head/kernel"], params["head/bias"], quant)
