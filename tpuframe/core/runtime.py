"""Process/runtime initialization and device-mesh construction.

TPU-native replacement for the reference's process-group bootstrap:

- ``dist.init_process_group("nccl")``
  (`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:269`)
  becomes :func:`initialize` — ``jax.distributed.initialize`` for multi-host
  rendezvous over DCN, with collectives compiled into XLA programs over ICI.
- The torchrun env contract (``RANK``/``LOCAL_RANK``/``WORLD_SIZE`` read at
  `01_basic_torch_distributor.py:271-272`,
  `/root/reference/02_deepspeed/01_cifar_deepspeed_resnet.py:213-216`) maps to
  the coordinator env contract honoured here (``TPUFRAME_COORDINATOR`` /
  ``MASTER_ADDR:MASTER_PORT``, ``WORLD_SIZE`` = host processes, ``RANK``).
- The cluster-topology probe (`/root/reference/setup/00_setup.py:105-113`, a
  Spark map job counting GPUs) becomes plain ``jax.device_count()`` /
  ``jax.local_device_count()`` — the TPU runtime already knows its topology.

Parallelism is expressed on a named :class:`jax.sharding.Mesh`; axis names are
the framework-wide vocabulary used by every PartitionSpec in tpuframe.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

# Framework-wide mesh-axis vocabulary.  Order below is the physical layout
# order (outermost -> innermost): axes that carry the most traffic (model/TP)
# sit innermost so their collectives ride nearest-neighbour ICI links.
PIPELINE_AXIS = "pipe"
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
SEQUENCE_AXIS = "seq"
EXPERT_AXIS = "expert"
MODEL_AXIS = "model"

AXIS_ORDER = (PIPELINE_AXIS, DATA_AXIS, FSDP_AXIS, SEQUENCE_AXIS, EXPERT_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape; ``-1`` on at most one axis means "all remaining".

    >>> MeshSpec(data=-1).build()          # pure data parallel
    >>> MeshSpec(data=-1, model=2).build() # DP x TP
    >>> MeshSpec(data=2, fsdp=2, model=2)  # DP x ZeRO-3 x TP on 8 chips
    """

    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    seq: int = 1
    expert: int = 1
    model: int = 1

    def sizes(self) -> dict[str, int]:
        return {
            PIPELINE_AXIS: self.pipe,
            DATA_AXIS: self.data,
            FSDP_AXIS: self.fsdp,
            SEQUENCE_AXIS: self.seq,
            EXPERT_AXIS: self.expert,
            MODEL_AXIS: self.model,
        }

    def resolve(self, n_devices: int) -> dict[str, int]:
        """Concrete axis sizes for ``n_devices``, filling one ``-1`` axis."""
        sizes = self.sizes()
        bad = {n: s for n, s in sizes.items() if s != -1 and s < 1}
        if bad:
            raise ValueError(f"mesh axis sizes must be -1 or >= 1, got {bad}")
        wildcard = [name for name, size in sizes.items() if size == -1]
        if len(wildcard) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wildcard}")
        fixed = int(np.prod([s for s in sizes.values() if s != -1]))
        if wildcard:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} are visible"
            )
        return sizes

    def build(self, devices: Sequence[jax.Device] | None = None) -> Mesh:
        """Construct a named Mesh over ``devices`` (default: all devices).

        Axis types are ``Auto`` (GSPMD propagation): tpuframe's ParallelPlan
        constrains inputs/outputs and lets the partitioner place every
        intermediate — jax 0.9's ``make_mesh`` default of ``Explicit`` would
        instead demand a sharding proof per op.
        """
        devices = list(devices) if devices is not None else jax.devices()
        sizes = self.resolve(len(devices))
        shape = tuple(sizes[name] for name in AXIS_ORDER)
        axis_types = (jax.sharding.AxisType.Auto,) * len(AXIS_ORDER)
        if devices == jax.devices():
            # jax.make_mesh picks an ICI-friendly physical ordering.
            return jax.make_mesh(shape, AXIS_ORDER, axis_types=axis_types)
        grid = np.asarray(devices).reshape(shape)
        return Mesh(grid, AXIS_ORDER, axis_types=axis_types)

    @classmethod
    def from_config(cls, cfg: Mapping[str, int]) -> "MeshSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown mesh axes {sorted(unknown)}; known: {sorted(known)}")
        return cls(**{k: int(v) for k, v in cfg.items()})

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "MeshSpec":
        """The concrete spec of an already-built mesh (every axis fixed,
        no wildcard) — the starting point for re-deriving a spec over a
        different world size (:meth:`shrink_to`)."""
        return cls.from_config(
            {name: int(size) for name, size in mesh.shape.items()}
        )

    def shrink_to(self, n_devices: int, *, elastic_axis: str = DATA_AXIS) -> "MeshSpec":
        """The equivalent spec for a smaller/larger world: ``elastic_axis``
        (default ``data``) absorbs the size change, every other axis keeps
        its layout.  Raises when the fixed axes no longer fit — losing a
        host out of a TP/PP group cannot be absorbed by data parallelism,
        and silently reshaping model parallelism would change the program.
        """
        sizes = dict(self.sizes())
        wildcard = [n for n, s in sizes.items() if s == -1 and n != elastic_axis]
        if wildcard:
            raise ValueError(
                f"shrink_to needs a fully-resolved spec (use "
                f"MeshSpec.from_mesh on the built mesh); axis {wildcard} "
                "is still a wildcard"
            )
        sizes[elastic_axis] = -1
        fixed = int(np.prod([s for n, s in sizes.items() if n != elastic_axis]))
        if n_devices < 1 or n_devices % fixed:
            raise ValueError(
                f"cannot rebuild mesh for {n_devices} device(s): the fixed "
                f"axes {({n: s for n, s in sizes.items() if n != elastic_axis and s > 1})} "
                f"need a multiple of {fixed} — shrink in units of whole "
                f"{elastic_axis}-groups or lower min_world_size no further"
            )
        return MeshSpec.from_config(sizes)


@dataclasses.dataclass
class Runtime:
    """Everything a train function needs to know about where it is running."""

    mesh: Mesh
    spec: MeshSpec
    process_index: int
    process_count: int
    platform: str

    @property
    def is_main(self) -> bool:
        return self.process_index == 0

    @property
    def device_count(self) -> int:
        return self.mesh.devices.size

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding on this runtime's mesh, e.g. ``rt.sharding("data")``."""
        return NamedSharding(self.mesh, P(*spec))

    def data_sharding(self) -> NamedSharding:
        """Batch-dimension sharding over every data-ish axis (data+fsdp)."""
        return NamedSharding(self.mesh, P((DATA_AXIS, FSDP_AXIS)))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


_CURRENT: Runtime | None = None


def initialize(
    mesh: MeshSpec | Mapping[str, int] | None = None,
    *,
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    platform: str | None = None,
    debug: bool | None = None,
) -> Runtime:
    """Initialize the distributed runtime and build the global mesh.

    Single-process (one host, N local chips) needs no arguments.  Multi-host
    needs a coordinator (≈ ``MASTER_ADDR:MASTER_PORT`` rendezvous in the
    reference's torchrun contract); values fall back to env vars
    ``TPUFRAME_COORDINATOR`` (or ``MASTER_ADDR``+``MASTER_PORT``),
    ``WORLD_SIZE``/``TPUFRAME_NUM_PROCESSES``, ``RANK``/``TPUFRAME_PROCESS_ID``.

    ``debug=True`` (or env ``TPUFRAME_DEBUG=1``) is the XLA counterpart of
    the reference's CUDA debug env block (`setup/00_setup.py:66-67,117-123`
    — ``CUDA_LAUNCH_BLOCKING``/``TORCH_DISTRIBUTED_DEBUG``): enables
    ``jax_debug_nans`` (first NaN raises at the op that produced it,
    de-optimizing like launch-blocking does) and ``jax_disable_most_optimizations``
    for deterministic, debuggable compiles.  Leave off for performance runs.
    """
    global _CURRENT

    # TPUFRAME_COMMS_ASYNC: merge the latency-hiding-scheduler /
    # async-collective-fusion flags into XLA_FLAGS FIRST — XLA reads
    # them at backend init, and everything below (distributed init,
    # mesh build) can trigger that.  The resolver is platform-gated
    # without importing a backend (asking jax would initialize it), and
    # returns the empty set on CPU where the flags would abort the
    # compiler; restart-only semantics, like every comms knob.
    _apply_comms_async_flags()

    if debug is None:
        debug = os.environ.get("TPUFRAME_DEBUG", "").strip().lower() not in (
            "", "0", "false", "no", "off",
        )
    if debug:
        global _DEBUG_FLAGS_SET
        jax.config.update("jax_debug_nans", True)
        jax.config.update("jax_disable_most_optimizations", True)
        _DEBUG_FLAGS_SET = True

    # persistent compilation cache: on by default (opt out with
    # TPUFRAME_COMPILE_CACHE=0) so every process that initializes a
    # runtime — driver, launch worker, supervised restart — compiles
    # against the same host-shared cache.  Enabled before any mesh/jit
    # work so even the first compile of this process is cacheable.
    from tpuframe.compile import cache as _compile_cache

    # the compile records (span log) need no cache: a process that opted
    # out of it still sees every trace, lowering and compile by name
    _compile_cache.install_listeners()
    _compile_cache.enable_from_env()

    coordinator_address = coordinator_address or _env_coordinator()
    if num_processes is None:
        num_processes = _env_int("TPUFRAME_NUM_PROCESSES", "WORLD_SIZE")
    if process_id is None:
        process_id = _env_int("TPUFRAME_PROCESS_ID", "RANK")

    multi_host = (num_processes or 1) > 1
    if multi_host or (coordinator_address and num_processes is not None):
        # A half-specified multi-host config must fail loudly, not degrade to
        # N independent rank-0 processes all claiming main-process duties.
        if not coordinator_address or num_processes is None or process_id is None:
            raise ValueError(
                "multi-host init requires coordinator_address, num_processes and "
                f"process_id (got coordinator={coordinator_address!r}, "
                f"num_processes={num_processes!r}, process_id={process_id!r}); "
                "set TPUFRAME_COORDINATOR/MASTER_ADDR, WORLD_SIZE and RANK"
            )
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    if isinstance(mesh, Mapping):
        mesh = MeshSpec.from_config(mesh)
    spec = mesh or MeshSpec()
    built = spec.build()
    _CURRENT = Runtime(
        mesh=built,
        spec=spec,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        platform=platform or jax.default_backend(),
    )
    logger.info(
        "tpuframe runtime: %d device(s) on %s, mesh %s, process %d/%d",
        _CURRENT.device_count,
        _CURRENT.platform,
        dict(zip(built.axis_names, built.devices.shape)),
        _CURRENT.process_index,
        _CURRENT.process_count,
    )
    return _CURRENT


def current_runtime(auto_init: bool = True) -> Runtime:
    """The active Runtime; lazily initializes a default one if allowed."""
    global _CURRENT
    if _CURRENT is None:
        if not auto_init:
            raise RuntimeError("tpuframe runtime not initialized; call core.initialize()")
        initialize()
    return _CURRENT


_DEBUG_FLAGS_SET = False


def reset_runtime() -> None:
    """Drop the cached Runtime (tests / re-init with a different mesh).

    Clears the debug-mode jax flags only when ``initialize(debug=True)``
    set them — flags the user enabled directly are left alone."""
    global _CURRENT, _DEBUG_FLAGS_SET
    _CURRENT = None
    if _DEBUG_FLAGS_SET:
        jax.config.update("jax_debug_nans", False)
        jax.config.update("jax_disable_most_optimizations", False)
        _DEBUG_FLAGS_SET = False


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    """Rank-0 discipline gate, used by track/ and ckpt/ (the reference checks
    ``global_rank == 0`` before every MLflow/checkpoint call, e.g.
    `/root/reference/01_torch_distributor/01_basic_torch_distributor.py:236-237`)."""
    return jax.process_index() == 0


def _apply_comms_async_flags() -> None:
    """Merge the ``TPUFRAME_COMMS_ASYNC`` flag set into ``XLA_FLAGS``
    (idempotent: flags already present are not duplicated).  No-op when
    the knob is off or the platform resolves no flags."""
    from tpuframe.parallel.comms_env import comms_async_flags

    wanted = comms_async_flags()
    if not wanted:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    missing = [f for f in wanted if f.split("=")[0] not in flags]
    if missing:
        os.environ["XLA_FLAGS"] = " ".join([flags] + missing).strip()


def simulate_cpu_devices(n: int = 8) -> None:
    """Force ``n`` virtual CPU devices (multi-chip simulation).

    Must run before JAX initializes its backends — typically at the top of a
    test conftest or as env config of a spawned worker.  This is the TPU-world
    answer to "test multi-node without a cluster" (SURVEY.md §4).  Overrides
    any pre-existing device-count flag or platform selection.
    """
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "--xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", flag, flags)
    else:
        flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    # jax may already be imported (it is, by this module); the env var alone is
    # then too late for jax.config's import-time default.
    jax.config.update("jax_platforms", "cpu")


def _env_coordinator() -> str | None:
    addr = os.environ.get("TPUFRAME_COORDINATOR")
    if addr:
        return addr
    host = os.environ.get("MASTER_ADDR")
    if host:
        port = os.environ.get("MASTER_PORT", "29500")
        return f"{host}:{port}"
    return None


def _env_int(*names: str) -> int | None:
    for name in names:
        value = os.environ.get(name)
        if value is not None:
            return int(value)
    return None
