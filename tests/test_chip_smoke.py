"""The chip contract, checked where there is no chip.

``chip_smoke.py`` must refuse to run anywhere but on a TPU, and what it does
there must be runnable code: its stage bodies take their sizes as arguments,
so the same functions run here at toy sizes on the 8-device CPU mesh (the
Mosaic custom-call counts are the one thing only the chip can show)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_chip_smoke_refuses_the_cpu():
    """JAX_PLATFORMS=cpu: non-zero exit at stage 0, naming the platform,
    no result line, nothing compiled (seconds)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "platform cpu, not tpu" in proc.stderr
    assert '"stage": "device", "ok": false' in proc.stdout
    assert '"ok": true' not in proc.stdout
    assert '"stage": "train"' not in proc.stdout


def test_cluster_mode_tpu_raises_on_the_cpu_mesh():
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    stop_orca_context()
    with pytest.raises(RuntimeError, match="platform cpu, not tpu"):
        init_orca_context(cluster_mode="tpu")


def test_a_failing_stage_fails_the_run(capsys):
    def boom():
        chip_smoke.check(False, "observed something wrong")

    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run_stage("boom", boom)
    assert '"ok": false' in capsys.readouterr().out


def test_stage_kernel_toy():
    # interpret mode; D=128 and the Mosaic lowering itself are
    # test_flash_attention_lowers_to_mosaic_for_tpu's
    out = chip_smoke.stage_kernel(
        seq=128, head_dims=(64,), heads=2, layer_seq=128, layer_hidden=128,
        layer_heads=2, expect_custom_calls=False)
    assert out["reference_fallthroughs"] == 0
    assert set(out) >= {"d64_full", "d64_causal", "layer"}


def test_stage_serve_toy(orca_context):
    # 8 devices: buckets are multiples of 8, so 16 is the second one
    out = chip_smoke.stage_serve(model_type="ssd_tiny", image_size=64,
                                 batch_size=16, n_single=2, n_burst=1,
                                 max_detections=20)
    assert out["requests"] == out["answers_ok"] == 18
    assert out["devices"] == 8
    assert out["batch_failures"] == 0 and out["serving_programs"] >= 2


def test_stage_train_and_multichip_toy(orca_context, tmp_path):
    """stage_multichip runs stage_train on the mesh it is given and then
    looks at where batch, parameters and predictions landed."""
    out = chip_smoke.stage_multichip(
        str(tmp_path), orca_context.mesh, "cpu", steps=2, depth=18,
        num_classes=10, image_size=40, crop=32, per_chip_batch=2,
        sync_steps=1, ring_rows=16, ring_features=32, ring_steps=3)
    assert out["devices"] == 8 and out["global_batch"] == 16
    assert len(out["losses"]) == 2 and out["h2d_bytes"] > 0
    assert out["cross_check_steps"] == 2 and out["compile_fallbacks"] == 0
    assert out["ring"]["steps"] == 3
    assert out["params_replicated_on"] == 8
    assert out["batch_shard_rows"] == 2
    assert out["all_reduce_in_step"] > 0
    assert out["predict_sharded_over"] == 8
    # the synthetic shards are removed again
    assert not [n for n in os.listdir(tmp_path) if n.startswith("imagenet")]


def test_peak_flops_table_is_keyed_by_device_kind():
    """An unknown TPU kind is an error, the CPU an explicit None — never a
    silent 0.0 that turns an MFU into a division by zero or a None."""
    from types import SimpleNamespace as Dev

    import jax

    from analytics_zoo_tpu.orca.learn.utils import (PEAK_BF16_FLOPS,
                                                    peak_bf16_flops)
    assert peak_bf16_flops(jax.devices()[0]) is None
    assert peak_bf16_flops(Dev(platform="tpu", device_kind="TPU v5 lite")) \
        == PEAK_BF16_FLOPS["TPU v5 lite"] == 197e12
    # v5p is not "v5 lite": the old substring match gave it v5e's peak
    assert peak_bf16_flops(Dev(platform="tpu", device_kind="TPU v5p")) \
        == 459e12
    with pytest.raises(ValueError, match="TPU v9 turbo"):
        peak_bf16_flops(Dev(platform="tpu", device_kind="TPU v9 turbo"))
