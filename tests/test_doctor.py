"""`python -m tpuframe` environment doctor: the CLI face of the
reference's setup bootstrap report (`setup/00_setup.py:105-123` prints
worker/GPU topology); ours must emit one parseable JSON report and—
critically—never hang on a wedged backend."""

import json
import os
import subprocess
import sys

from tpuframe import doctor


def test_report_shape_on_cpu(monkeypatch):
    # the probe subprocess inherits env: pin CPU, same as the CLI test below
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rec = doctor.report(probe_timeout_s=60)
    assert rec["tpuframe"]
    assert rec["devices"]["backend"] == "cpu"
    assert rec["devices"]["device_count"] >= 1
    assert "mesh_hint" in rec and "DP" in rec["mesh_hint"]
    nat = rec["native_extensions"]
    assert isinstance(nat["built"], list)
    for key in ("toolchain_available", "zstd_codec", "jpeg_decoder"):
        assert isinstance(nat[key], bool), key
    assert rec["optional_deps"]["msgpack"]  # hard dep, must resolve


def test_memory_section_verdict_and_one_liner(monkeypatch, tmp_path):
    """The doctor's memory section: knob state, persisted compiled
    records, a fits/doesn't-fit verdict, and the paste-ready estimator
    one-liner (which must actually run)."""
    from tpuframe.track import memory as tmem

    monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("TPUFRAME_MEMORY_BUDGET_MB", "1000")
    # earlier test modules leave in-memory records behind; a fresh dict
    # (auto-restored) keeps the executable count deterministic
    monkeypatch.setattr(tmem, "_EXECUTABLES", {})

    class _Stats:
        argument_size_in_bytes = 500 * 1024 * 1024
        temp_size_in_bytes = 100 * 1024 * 1024
        output_size_in_bytes = 0
        alias_size_in_bytes = 0

    class _Compiled:
        def memory_analysis(self):
            return _Stats()

    tmem.record_executable_memory(_Compiled(), "train/step")
    sec = doctor.memory_section()
    assert sec["knobs"]["TPUFRAME_MEMORY_BUDGET_MB"] == 1000.0
    assert sec["executables"] == 1
    assert sec["peak_known_mb"] == 600.0
    assert sec["budget_mb"] == 1000.0
    assert sec["verdict"].startswith("fits")
    # the one-liner is advertised as paste-ready: hold it to that
    cmd = sec["estimate"].split(" ", 2)
    assert cmd[0] == "python" and cmd[1] == "-c"
    proc = subprocess.run(
        [sys.executable, "-c", cmd[2].strip('"')],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    assert "params" in proc.stdout


def test_compile_section_follows_a_cache_placed_from_outside(
        monkeypatch, tmp_path):
    """The doctor reports the directory the program would really use:
    jax's own variable over the tpuframe knob."""
    from tpuframe.compile import cache as cc

    # as in a fresh `python -m tpuframe`: no cache enabled in this process
    monkeypatch.setitem(cc._STATE, "dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", str(tmp_path / "knob"))
    assert doctor.compile_section()["dir"] == str(tmp_path / "placed")
    monkeypatch.setenv("TPUFRAME_COMPILE_CACHE", "0")
    assert doctor.compile_section()["dir"] is None


def test_probe_never_hangs_on_wedged_backend(monkeypatch):
    """jax.devices() hanging forever (a wedged backend, or a chip another
    process holds): the probe must time out and return a diagnosis that
    names both causes, not hang."""
    monkeypatch.setattr(doctor, "_PROBE_SRC", "import time; time.sleep(60)")
    rec = doctor.probe_devices(timeout_s=0.5)
    assert "wedged" in rec["error"]
    assert "holds the chip" in rec["error"]


def test_cli_emits_parseable_json():
    proc = subprocess.run(
        [sys.executable, "-m", "tpuframe", "--probe-timeout", "60"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    rec = json.loads(proc.stdout)
    assert rec["devices"]["backend"] == "cpu"
