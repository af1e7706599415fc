"""The chip tools under ``benchmarks/`` and what points a reader at them:
a tool that finds no TPU fails, every registry kernel has a check on the
chip or a reason it needs none, and no document or doctor section names a
script the tree does not hold."""

import importlib.util
import inspect
import os
import subprocess
import sys

import pytest

from tpuframe.ops.registry import OPS_REGISTRY

_ROOT = os.path.join(os.path.dirname(__file__), os.pardir)

#: the documents held to the tree (CHANGES.md, ROADMAP.md, SURVEY.md and
#: ISSUE.md are histories: they name what went)
DOCUMENTS = (
    "README.md", "PERF.md", "OBSERVABILITY.md", "SERVE.md", "FAULT.md",
    "AUTOTUNE.md", "LINT.md", "MIGRATION.md", "PARITY.md",
    "benchmarks/README.md", "examples/README.md",
    ".claude/skills/verify/SKILL.md",
)


@pytest.fixture(scope="module")
def kernel_check():
    """``benchmarks/check_kernels_tpu.py`` as a module (it imports no jax
    until ``main()``)."""
    spec = importlib.util.spec_from_file_location(
        "check_kernels_tpu_under_test",
        os.path.join(_ROOT, "benchmarks", "check_kernels_tpu.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("tool", ["check_kernels_tpu", "check_offload_tpu"])
def test_refuses_without_a_tpu(tool):
    """Off the chip every op dispatches to its own oracle and no memory is
    ``pinned_host``: an on-chip acceptance script must fail there, not pass
    vacuously or print a record."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks", f"{tool}.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("op", sorted(OPS_REGISTRY))
def test_every_registry_kernel_has_a_chip_check(kernel_check, op):
    """A kernel lands with a registry row AND a ``--only`` section that
    holds it to its oracle on the chip; a row without one says why."""
    covered = {o for _, ops in kernel_check.SECTIONS.values() for o in ops}
    excused = dict(kernel_check.NO_CHIP_CHECK)
    assert covered | set(excused) <= set(OPS_REGISTRY), "a section names no registry row"
    assert (op in covered) != (op in excused), (
        f"{op}: one section of check_kernels_tpu.SECTIONS, or one reason in NO_CHIP_CHECK"
    )
    assert op in covered or excused[op].strip()


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_in_the_tree(doc, scripts_not_in_tree):
    with open(os.path.join(_ROOT, doc)) as f:
        assert scripts_not_in_tree(f.read()) == []


def test_doctor_names_only_files_in_the_tree(scripts_not_in_tree):
    """Every section that needs no backend: a paste-ready command is one
    the operator can run."""
    from tpuframe import doctor

    sections = {
        name: fn() for name, fn in inspect.getmembers(doctor, inspect.isfunction)
        if name.endswith("_section")
    }
    assert len(sections) >= 14
    assert scripts_not_in_tree(sections) == []
