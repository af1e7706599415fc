"""The end-to-end data-fed benchmark (benchmarks/bench_e2e.py) emits a
valid record: volume build -> StreamingDataset/MDSDataset -> DataLoader ->
DevicePrefetcher -> train step, with stall attribution.  This is the
driver-shaped contract (one JSON line) for the SURVEY §7 "input pipeline
feeding HBM" measurement."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
@pytest.mark.parametrize("fmt,extra", [
    ("tfs", []),
    ("mds", []),
    ("tfs", ["--uint8-input"]),  # raw-bytes H2D + fused on-device normalize
])
def test_bench_e2e_emits_record(fmt, extra, tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_e2e.py"),
         "--format", fmt, "--images", "48", "--batch", "8", "--steps", "2",
         "--size", "32", "--workers", "1",
         "--volume-dir", str(tmp_path / "vol")] + extra,
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "resnet50_e2e_data_fed_images_per_sec_per_chip"
    assert rec["value"] > 0
    assert rec["format"] == fmt
    assert rec["synthetic_images_per_sec_per_chip"] > 0
    assert 0.0 <= rec["input_stall_pct"] <= 100.0
    assert 0.0 <= rec["host_input_wait_frac"] <= 1.0


@pytest.mark.parametrize("extra", [[], ["--uint8-input"]])
def test_producer_ceiling_null_consumer_smoke(extra, tmp_path):
    """--consumer null: the producer-ceiling record lands on ANY host —
    no jax, no chip — with per-worker rates and zero steady-state ring
    allocations (ISSUE 2 acceptance).  Fast enough for tier-1: the mode
    skips model build/compile entirely."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "bench_e2e.py"),
         "--consumer", "null", "--workers", "1,2", "--images", "48",
         "--batch", "8", "--size", "32", "--seconds", "0.6",
         "--volume-dir", str(tmp_path / "vol")] + extra,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "input_producer_ceiling_images_per_sec"
    assert rec["value"] > 0
    assert set(rec["per_workers"]) == {"1", "2"}
    assert all(v > 0 for v in rec["per_workers"].values())
    assert rec["cores_to_feed_chip"] > 0
    assert all(v == 0 for v in rec["steady_state_ring_allocs"].values()), rec
    assert rec["uint8_input"] == ("--uint8-input" in extra)
