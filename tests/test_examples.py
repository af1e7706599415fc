"""Example-suite integration tests: replay each reference recipe family at
1-epoch smoke scale (SURVEY.md §4's '1-epoch cheap run' formalized)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # acceptance tier: replays/convergence, minutes not seconds

EXAMPLES = os.path.join(os.path.dirname(__file__), os.pardir, "examples")

SMOKE = [
    "--epochs", "1",
    "--batch-size", "16",
    "--train-samples", "48",
    "--eval-samples", "16",
    "--image-size", "16",
]


def run_example(script: str, *extra: str, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script), *SMOKE,
         "--workdir", str(tmp_path), *extra],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert proc.returncode == 0, (
        f"{script} failed\n--- stdout ---\n{proc.stdout[-2000:]}"
        f"\n--- stderr ---\n{proc.stderr[-3000:]}"
    )
    return proc.stdout


def test_distributor_mnist(tmp_path):
    out = run_example(
        "01_distributor_mnist.py",
        "--num-processes", "1", "--simulate-devices", "2",
        tmp_path=tmp_path,
    )
    assert "finished" in out


def test_distributor_cifar(tmp_path):
    out = run_example(
        "01_distributor_cifar_resnet.py",
        "--num-processes", "1", "--simulate-devices", "2",
        tmp_path=tmp_path,
    )
    assert "1 epoch:" in out and "demo_pred" in out


@pytest.mark.parametrize("stage", ["2", "3"])
def test_deepspeed_zero(tmp_path, stage):
    out = run_example(
        "02_deepspeed_zero_cifar_resnet.py",
        "--zero-stage", stage, "--num-processes", "1",
        "--simulate-devices", "2", "--fsdp", "2",
        tmp_path=tmp_path,
    )
    assert f"'stage': {stage}" in out


def test_composer_trainer(tmp_path):
    out = run_example("03_composer_cifar_resnet.py", tmp_path=tmp_path)
    assert "demo:" in out


def test_accelerate_loop(tmp_path):
    out = run_example("04_accelerate_cifar.py", tmp_path=tmp_path)
    assert "epoch 0" in out


def test_ray_trainer(tmp_path):
    out = run_example(
        "05_ray_fashion_mnist.py",
        "--num-workers", "1", "--simulate-devices", "2",
        tmp_path=tmp_path,
    )
    assert "reloaded checkpoint from epoch 0" in out


def test_tiny_imagenet_streaming(tmp_path):
    # the MDS-equivalent recipe: shards written by the driver, streamed
    # remote->local inside 2 real worker processes, ResNet50 smoke-scale
    out = run_example(
        "01a_distributor_tiny_imagenet_streaming.py",
        "--num-processes", "2", "--simulate-devices", "1",
        "--image-size", "32", "--num-classes", "20",
        tmp_path=tmp_path,
    )
    assert "spot_preds" in out
    # shards really exist on disk ("remote") and in the worker cache
    assert (tmp_path / "tiny_imagenet_tfs" / "train" / "index.json").exists()
    assert (tmp_path / "stream_cache" / "host0" / "train" / "index.json").exists()


def test_imagenet1k_zero_config(tmp_path):
    # ImageNet-1K-shaped ZeRO-3 + grad accum at crash-test scale (tiny
    # sample count, true 1000-class head)
    out = run_example(
        "02a_deepspeed_zero_imagenet1k.py",
        "--zero-stage", "3", "--num-processes", "1",
        "--simulate-devices", "2", "--fsdp", "2",
        "--grad-accum", "2", "--image-size", "64",
        tmp_path=tmp_path,
    )
    assert "'stage': 3" in out and "'grad_accum': 2" in out


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_lm_sequence_parallel(tmp_path, attn):
    # dp x sp mesh on 2 virtual devices: seq axis gets both
    out = run_example(
        "06_lm_sequence_parallel.py",
        "--attn", attn, "--seq-shards", "2", "--seq-len", "64",
        "--heads", "4", "--layers", "1",
        tmp_path=tmp_path,
    )
    assert f"attn={attn}" in out


def test_vit_classifier_with_tp(tmp_path):
    out = run_example(
        "07_vit_classifier.py",
        "--tp", "2", "--layers", "2", "--hidden-dim", "32", "--heads", "4",
        "--simulate-devices", "2",
        tmp_path=tmp_path,
    )
    assert "tp=2" in out


def test_lm_composed_plan_change_story(tmp_path):
    # ISSUE-18 acceptance: TP=2 x PP=2 x ZeRO-1 fit chaos-killed mid-run,
    # resumed from the same checkpoints under DP x fsdp ZeRO-3 + int8 —
    # one reshard, full step count, zero recompiles/AOT fallbacks
    out = run_example(
        "06_lm_sequence_parallel.py",
        "--composed", "--simulate-devices", "8",
        "--epochs", "2",  # overrides SMOKE's 1: the story needs >= 4 steps
        "--seq-len", "64", "--heads", "4", "--layers", "2",
        tmp_path=tmp_path,
    )
    assert "chaos-killed at step" in out
    assert "resumed across the plan change" in out
    assert "steps 6/6 reshards=1 recompiles=0 aot_fallbacks=0" in out


def test_lm_moe_sequence_parallel(tmp_path):
    # SP + MoE blocks (2 devices only fit one sharded axis: seq here)
    out = run_example(
        "06_lm_sequence_parallel.py",
        "--attn", "ring", "--seq-shards", "2", "--seq-len", "64",
        "--heads", "4", "--layers", "1",
        "--moe-experts", "2", "--expert-shards", "1",
        tmp_path=tmp_path,
    )
    assert "attn=ring" in out


def test_lm_moe_expert_parallel(tmp_path):
    # real expert axis: both devices on expert -> moe_rules shard w_in/w_out
    out = run_example(
        "06_lm_sequence_parallel.py",
        "--attn", "full", "--seq-shards", "1", "--seq-len", "64",
        "--heads", "4", "--layers", "1",
        "--moe-experts", "2", "--expert-shards", "2",
        tmp_path=tmp_path,
    )
    assert "attn=full" in out


def test_export_serving_roundtrip(tmp_path):
    """09: train -> export -> serve from nothing but the artifact."""
    out = run_example(
        "09_export_serving.py",
        "--serve-batch", "8", "--ema", "0.9",
        tmp_path=tmp_path,
    )
    assert "finished" in out and "ms/batch" in out
    assert (tmp_path / "model.shlo").exists()


def test_export_serving_from_torch_fixture(tmp_path):
    """09 --from-torch: a torchvision-format .pt straight to an artifact."""
    fixture = os.path.join(
        os.path.dirname(__file__), "fixtures", "resnet18_tv_w4.pt"
    )
    out = run_example(
        "09_export_serving.py",
        "--from-torch", fixture, "--serve-batch", "4",
        tmp_path=tmp_path,
    )
    assert "exported torch checkpoint (width=4)" in out
    assert "finished" in out
