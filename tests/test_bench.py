"""bench.py: one process, no CPU arm, a peak table that refuses devices
it does not know — plus the helpers the benchmarks/ scripts import."""

import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(_ROOT, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_an_accelerator():
    """A CPU run must fail, not print a device metric: no JSON record."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs an accelerator" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_kernel_check_refuses_without_a_tpu():
    """Off the chip every op dispatches to its own oracle: the on-chip
    acceptance script must fail there, not pass vacuously."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", "check_kernels_tpu.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"pass"' not in proc.stdout


def test_peak_table_is_exact_and_refuses_unknown_devices(bench):
    v5e = bench.device_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    # exact device_kind, not a substring match
    for kind in ("TPU v5", "tpu v5 lite", "TPU v5 lite pod", "cpu", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            bench.device_peaks(kind)


def test_reads_no_bench_knobs():
    with open(os.path.join(_ROOT, "bench.py")) as f:
        assert "TPUFRAME_BENCH_" not in f.read()


def test_cost_analysis_reads_the_compiled_program(bench):
    compiled = jax.jit(lambda a: a @ a).lower(jnp.ones((64, 64))).compile()
    flops, bytes_accessed = bench.cost_analysis(compiled)
    assert flops and flops > 0
    assert bytes_accessed is None or bytes_accessed > 0
