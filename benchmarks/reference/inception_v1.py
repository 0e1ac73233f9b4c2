"""Inception-v1 / GoogLeNet (Szegedy et al. 2014, arXiv:1409.4842, Table 1),
plain float32, in the variant the configuration states: batch normalisation
after every convolution, no auxiliary heads, no local response normalisation,
'SAME' padding. Parameters are a flat dict ``name -> array``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import nn

_BRANCHES = ("b1", "b2_reduce", "b2", "b3_reduce", "b3", "b4_proj")


def _convs(cfg: dict):
    """(name, kernel side, in channels, out channels) of every convolution."""
    yield "stem", 7, 3, 64
    yield "reduce", 1, 64, 64
    yield "stem2", 3, 64, 192
    cin = 192
    for b in cfg["blocks"]:
        if b[0] == "pool":
            continue
        name, one, (r3, c3), (r5, c5), proj = b
        p = f"inception_{name}"
        yield f"{p}/b1", 1, cin, one
        yield f"{p}/b2_reduce", 1, cin, r3
        yield f"{p}/b2", 3, r3, c3
        yield f"{p}/b3_reduce", 1, cin, r5
        yield f"{p}/b3", 5, r5, c5
        yield f"{p}/b4_proj", 1, cin, proj
        cin = one + c3 + c5 + proj


def param_shapes(cfg: dict) -> Dict[str, Tuple[int, ...]]:
    shapes = {}
    cout = 0
    for name, k, cin, cout_ in _convs(cfg):
        shapes[f"{name}/conv/kernel"] = (k, k, cin, cout_)
        shapes[f"{name}/bn/scale"] = (cout_,)
        shapes[f"{name}/bn/bias"] = (cout_,)
    b = [b for b in cfg["blocks"] if b[0] != "pool"][-1]
    cout = b[1] + b[2][1] + b[3][1] + b[4]
    shapes["head/kernel"] = (cout, cfg["num_classes"])
    shapes["head/bias"] = (cfg["num_classes"],)
    return shapes


def forward(cfg: dict, params: Dict, images_uint8, quant: nn.Quant = None):
    eps = cfg["bn_epsilon"]

    def cbr(x, name, stride=1):
        y = nn.conv(x, params[f"{name}/conv/kernel"], stride, "SAME", quant)
        y, _, _ = nn.batch_norm_train(y, params[f"{name}/bn/scale"],
                                      params[f"{name}/bn/bias"], eps)
        return jax.nn.relu(y)

    def block(x, p):
        b1 = cbr(x, f"{p}/b1")
        b2 = cbr(cbr(x, f"{p}/b2_reduce"), f"{p}/b2")
        b3 = cbr(cbr(x, f"{p}/b3_reduce"), f"{p}/b3")
        b4 = cbr(nn.max_pool(x, 3, 1, "SAME"), f"{p}/b4_proj")
        return jnp.concatenate([b1, b2, b3, b4], axis=-1)

    x = nn.normalise(images_uint8)
    x = nn.max_pool(cbr(x, "stem", 2), 3, 2, "SAME")
    x = cbr(cbr(x, "reduce"), "stem2")
    x = nn.max_pool(x, 3, 2, "SAME")
    for b in cfg["blocks"]:
        if b[0] == "pool":
            x = nn.max_pool(x, 3, 2, "SAME")
        else:
            x = jax.checkpoint(block, static_argnums=(1,))(
                x, f"inception_{b[0]}")
    x = jnp.mean(x, axis=(1, 2))
    return nn.dense(x, params["head/kernel"], params["head/bias"], quant)
