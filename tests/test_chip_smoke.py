"""chip_smoke.py off the chip: it must refuse — non-zero, a named reason,
no result object — and its explicit CPU rehearsal must pass at tiny size
with interpret-mode kernels without ever printing the result object."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, cwd=REPO, script=SMOKE, **env):
    # the smoke holds the cache to its prescribed place; the suite's own
    # cache knob (conftest) must not ride into it
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "TPUFRAME_COMPILE_CACHE")}
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, capture_output=True,
        text=True, timeout=600, env={**base, "JAX_PLATFORMS": "cpu", **env},
    )


def _result_lines(stdout):
    return [l for l in stdout.splitlines() if l.startswith("{") and '"ok"' in l]


def test_refuses_without_an_accelerator():
    proc = _run()
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not _result_lines(proc.stdout)


def test_refuses_outside_a_checkout(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(cwd=str(tmp_path), script=alone, PYTHONPATH="")
    assert proc.returncode != 0
    assert "tpuframe is not importable" in proc.stderr
    assert not _result_lines(proc.stdout)


def test_rehearsal_passes_on_two_virtual_devices():
    """The same entry points as the chip run (initialize -> plan -> uint8
    loader -> Trainer.fit) with the kernels in interpret mode and the
    batch sharded over two devices; every check but the Mosaic one."""
    proc = _run("--rehearsal",
                XLA_FLAGS="--xla_force_host_platform_device_count=2")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "FAIL" not in proc.stdout
    for name in ("pallas_mode", "compile_cache_dir", "first_loss_vs_reference",
                 "precompile", "no_degraded_phase",
                 "kernel_verdict_cross_entropy", "params_replicated",
                 "batch_sharded"):
        assert f"PASS {name}" in proc.stdout, name
    body = [l for l in proc.stdout.splitlines() if l.strip()]
    assert all(l.startswith("[rehearsal]") for l in body[:-1])
    last = json.loads(body[-1])
    assert last == {"rehearsal": True, "passed": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    assert not _result_lines(proc.stdout)
