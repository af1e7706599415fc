"""Test harness: force an 8-device virtual CPU platform before JAX's backend
initializes.

SURVEY.md §4: the TPU-world answer to "test multi-node without a cluster" is
``--xla_force_host_platform_device_count``.  All tests run against 8 virtual
CPU devices so every mesh/sharding path is exercised without TPU hardware.
``simulate_cpu_devices`` overrides both the env and the live jax config.
"""

import json
import os
import re
import tempfile

import jax
import pytest

from tpuframe.core.runtime import simulate_cpu_devices

simulate_cpu_devices(8)

# tests place compile caches in their own tmp dirs; a cache placed from
# outside (which tpuframe.compile.cache honors over any other directory)
# would override every one of them
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# ... and everything else (workers and example subprocesses included)
# shares ONE cache that outlives the checkout: a fresh checkout's
# <checkout>/.cache/xla starts cold, and a cold suite compiles for twice
# the tier-1 time limit.  The knob places it; tests of the default path
# delete the knob.
os.environ.setdefault(
    "TPUFRAME_COMPILE_CACHE",
    os.path.join(tempfile.gettempdir(), "tpuframe_scratch", "compile_cache"),
)


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a script a document or the doctor hands its reader: anything under
#: ``benchmarks/``, anything called ``bench*.py``, the smoke
_SCRIPT_NAME = re.compile(
    r"(?<![\w/.-])(benchmarks/[\w./*-]*|bench\w*\.py|chip_smoke\.py)")


@pytest.fixture(scope="session")
def scripts_not_in_tree():
    """``f(text_or_section) -> list``: the scripts it names that the tree
    does not hold (a bare ``bench*.py`` may live under ``benchmarks/``)."""

    def missing(named) -> list[str]:
        text = named if isinstance(named, str) else json.dumps(named)
        names = {m.rstrip(".-") for m in _SCRIPT_NAME.findall(text)}
        return sorted(
            n for n in names
            if not any(os.path.exists(os.path.join(_REPO, d, n))
                       for d in ("", "benchmarks"))
        )

    return missing


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    from tpuframe.core import MeshSpec

    return MeshSpec(data=2, fsdp=2, model=2).build()


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def compiled_backend(monkeypatch):
    """Pin the dispatch plane's view of the backend to what one TPU chip
    says (kernels compile, one device), so a test reads the path an op
    picks there off a traced or TPU-lowered program.  Nothing that holds
    a compiled kernel can *run* under it."""
    from tpuframe.ops import dispatch

    monkeypatch.setattr(dispatch, "pallas_mode", lambda: "compiled")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
