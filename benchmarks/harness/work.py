"""FLOPs and bytes that a model's convolutions and dense layers require,
counted from shapes alone.

The counts are the yardstick's, not the compiler's: they come from the layer
list in a configuration's JSON file (rows of ``conv`` and ``dense``), so they
stay the same whatever program implements the layers. One multiply-add is two
FLOPs. The backward pass of a layer is two products of the forward's size
(the gradient of its input and the gradient of its weights); a layer whose
input is the image (``"input_grad": false``) needs only the second.

A row of the list::

    {"op": "conv", "name": "s1.b0.c1", "in_hw": 56, "cin": 64, "cout": 64,
     "k": 1, "stride": 1, "repeat": 1, "bn": true}
    {"op": "dense", "name": "head", "in": 2048, "out": 1000, "bias": true}

``in_hw`` is the square input's side and the output's side is
``ceil(in_hw / stride)`` (every convolution of the models here pads so).
``repeat`` counts identical layers.
"""

from __future__ import annotations

import json
import math
import os
from typing import Dict, Iterable, List

PASSES = ("fwd", "bwd_input", "bwd_weight")


def load_peaks(device_kind: str, path: str | None = None) -> Dict[str, float]:
    """The chip's published peaks by exact ``device_kind``; an unknown kind
    is an error, never a default."""
    path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}; "
                       f"add it to {path} with its source")
    return table[device_kind]


def _conv_pass(row: dict, batch: int, dtype_bytes: int) -> Dict[str, dict]:
    k, s = int(row["k"]), int(row.get("stride", 1))
    hin = int(row["in_hw"])
    hout = math.ceil(hin / s)
    cin, cout = int(row["cin"]), int(row["cout"])
    macs = batch * hout * hout * cout * k * k * cin
    x = batch * hin * hin * cin * dtype_bytes
    y = batch * hout * hout * cout * dtype_bytes
    w = k * k * cin * cout * dtype_bytes
    out = {"fwd": {"flops": 2 * macs, "bytes": x + w + y},
           "bwd_weight": {"flops": 2 * macs, "bytes": x + y + w}}
    if row.get("input_grad", True):
        out["bwd_input"] = {"flops": 2 * macs, "bytes": y + w + x}
    return out


def _dense_pass(row: dict, batch: int, dtype_bytes: int) -> Dict[str, dict]:
    fin, fout = int(row["in"]), int(row["out"])
    macs = batch * fin * fout
    x, y, w = (batch * fin * dtype_bytes, batch * fout * dtype_bytes,
               fin * fout * dtype_bytes)
    out = {"fwd": {"flops": 2 * macs, "bytes": x + w + y},
           "bwd_weight": {"flops": 2 * macs, "bytes": x + y + w}}
    if row.get("input_grad", True):
        out["bwd_input"] = {"flops": 2 * macs, "bytes": y + w + x}
    return out


_OPS = {"conv": _conv_pass, "dense": _dense_pass}


def layer_passes(row: dict, batch: int, dtype_bytes: int) -> Dict[str, dict]:
    """FLOPs and least bytes of one layer's forward and backward products,
    for ONE of its ``repeat`` copies."""
    try:
        fn = _OPS[row["op"]]
    except KeyError:
        raise ValueError(f"work.py counts {sorted(_OPS)}; the layer list "
                         f"has op {row.get('op')!r}") from None
    return fn(row, batch, dtype_bytes)


def forward_macs_per_sample(layers: Iterable[dict]) -> int:
    return sum(int(r.get("repeat", 1))
               * layer_passes(r, 1, 1)["fwd"]["flops"] // 2 for r in layers)


def train_flops_per_sample(layers: Iterable[dict]) -> int:
    """Forward and backward FLOPs one sample requires; nothing recomputed."""
    return sum(int(r.get("repeat", 1)) * p["flops"]
               for r in layers for p in layer_passes(r, 1, 1).values())


def param_count(layers: Iterable[dict]) -> int:
    """Parameters the list implies: kernels, a BatchNorm's scale and bias
    after a convolution marked ``bn``, a dense layer's bias."""
    n = 0
    for r in layers:
        rep = int(r.get("repeat", 1))
        if r["op"] == "conv":
            k = int(r["k"])
            n += rep * (k * k * int(r["cin"]) * int(r["cout"])
                        + (2 * int(r["cout"]) if r.get("bn") else 0))
        else:
            n += rep * (int(r["in"]) * int(r["out"])
                        + (int(r["out"]) if r.get("bias") else 0))
    return n


def min_step_seconds(layers: List[dict], batch: int, dtype_bytes: int,
                     peaks: Dict[str, float]) -> Dict[str, float]:
    """The least time one chip could take for the list's products on a batch
    of ``batch``: per layer and per product the larger of FLOPs over the peak
    rate and bytes over the HBM rate, summed. Also says how much of that sum
    the memory bound sets."""
    total = mem_bound = 0.0
    for r in layers:
        rep = int(r.get("repeat", 1))
        for p in layer_passes(r, batch, dtype_bytes).values():
            tf = p["flops"] / peaks["bf16_flops_per_s"]
            tb = p["bytes"] / peaks["hbm_bytes_per_s"]
            total += rep * max(tf, tb)
            if tb > tf:
                mem_bound += rep * tb
    return {"seconds": total, "memory_bound_seconds": mem_bound}
