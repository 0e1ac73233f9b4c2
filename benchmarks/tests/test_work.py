"""work.py against the papers, and the configurations' layer lists pinned: no
later PR moves a roofline or an MFU by editing a model file."""

import json
import os

import pytest

from harness import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def layers(config):
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        return json.load(f)["layers"]


# forward multiply-adds per 224x224 image, parameters, train FLOPs per image
PINNED = {
    # He et al. 2015, Table 1: "3.8 x 10^9 FLOPs" (multiply-adds) for the
    # 50-layer net with the stride in the first 1x1; with the stride in the
    # 3x3 (v1.5) it is 4.09 G. 25.6 M parameters.
    "resnet50_imagenet": (4_089_184_256, 25_557_032, 24_299_077_632),
    # Szegedy et al. 2014, Table 1 sums to about 1.5 G multiply-adds
    # ("1.5 billion multiply-adds at inference time"); this variant (no
    # auxiliary heads, a BatchNorm per convolution) has 7.0 M parameters.
    "inception_v1_imagenet": (1_582_671_872, 7_005_832, 9_260_003_328),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_counts_are_pinned(config):
    macs, params, flops = PINNED[config]
    rows = layers(config)
    assert work.forward_macs_per_sample(rows) == macs
    assert work.param_count(rows) == params
    assert work.train_flops_per_sample(rows) == flops


def test_counts_are_near_the_papers():
    assert work.forward_macs_per_sample(layers("resnet50_imagenet")) \
        == pytest.approx(4.1e9, rel=0.01)
    assert work.param_count(layers("resnet50_imagenet")) \
        == pytest.approx(25.6e6, rel=0.005)
    assert work.forward_macs_per_sample(layers("inception_v1_imagenet")) \
        == pytest.approx(1.5e9, rel=0.06)
    assert work.param_count(layers("inception_v1_imagenet")) \
        == pytest.approx(7.0e6, rel=0.005)


def test_train_flops_are_three_forwards_less_the_image_gradient():
    for config in PINNED:
        rows = layers(config)
        stem = next(r for r in rows if not r.get("input_grad", True))
        stem_fwd = work.layer_passes(stem, 1, 1)["fwd"]["flops"]
        assert work.train_flops_per_sample(rows) == \
            3 * 2 * work.forward_macs_per_sample(rows) - stem_fwd


def test_conv_pass_by_hand():
    # 3x3, stride 2, 8x8x4 -> 4x4x16, batch 2, two bytes an element
    p = work.layer_passes({"op": "conv", "in_hw": 8, "cin": 4, "cout": 16,
                           "k": 3, "stride": 2}, 2, 2)
    macs = 2 * 4 * 4 * 16 * 9 * 4
    x, y, w = 2 * 8 * 8 * 4 * 2, 2 * 4 * 4 * 16 * 2, 9 * 4 * 16 * 2
    assert p["fwd"] == {"flops": 2 * macs, "bytes": x + w + y}
    assert set(p) == {"fwd", "bwd_input", "bwd_weight"}
    assert all(v["flops"] == 2 * macs for v in p.values())


def test_dense_pass_and_unknown_op():
    p = work.layer_passes({"op": "dense", "in": 10, "out": 4}, 3, 2)
    assert p["fwd"]["flops"] == 2 * 3 * 10 * 4
    assert p["fwd"]["bytes"] == (3 * 10 + 10 * 4 + 3 * 4) * 2
    with pytest.raises(ValueError):
        work.layer_passes({"op": "attention"}, 1, 2)


def test_min_step_seconds_takes_the_larger_bound_per_product():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    row = {"op": "dense", "in": 10, "out": 4, "input_grad": False}
    p = work.layer_passes(row, 3, 2)
    want = sum(max(v["flops"] / 100.0, v["bytes"] / 10.0) for v in p.values())
    got = work.min_step_seconds([row], 3, 2, peaks)
    assert got["seconds"] == pytest.approx(want)
    assert 0 < got["memory_bound_seconds"] <= got["seconds"]


def test_peaks_known_kind_and_unknown_kind():
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
