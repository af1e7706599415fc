"""Kernel dispatch: compiled Pallas on TPU, jnp reference elsewhere.

Every op in tpuframe.ops has two implementations with identical
semantics; tests assert they match (with ``interpret=True`` running the
real kernel code on CPU).  Which one runs is :func:`resolve_interpret`'s
answer from what the process can observe (the backend, the device
count, a manual region, the caller's ``shardable``, an explicit
``interpret=``) plus the op's own shape rule where it has one, and
nothing that lives outside the tree: a dispatch rule changes by a PR
that a benchmark cell measures.  Env knobs:

- ``TPUFRAME_DISABLE_PALLAS=1`` forces the reference path everywhere —
  the escape hatch when a kernel misbehaves on a new compiler version.
- ``TPUFRAME_PALLAS_INTERPRET=1`` runs the kernels in Pallas interpret
  mode on any backend — how ``dryrun_multichip`` exercises the sharded
  kernel paths on virtual CPU devices.

Every distinct decision fires one ``ops/kernel_verdict`` event, so a
run's JSONL says which form of each op its step took.

Multi-chip: a ``pl.pallas_call`` lowers to a custom call the GSPMD
partitioner cannot split, so ops invoke their kernels *per shard* under
``jax.shard_map`` when the caller supplies a mesh (the pattern proven by
``ops/ring_attention.py``).  Without a mesh, the kernel only engages in
single-device processes; multi-device callers that don't pass a mesh get
the jnp reference path, which XLA shards natively.
"""

from __future__ import annotations

import os

import jax

_FALSY = {"", "0", "false", "no", "off"}


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in _FALSY


def pallas_mode() -> str | None:
    """How kernels should run: ``"compiled"`` | ``"interpret"`` | None.

    ``None`` means use the jnp reference path.  Interpret mode wins over
    the disable flag being absent on CPU so tests/dryruns can exercise
    the real kernel code anywhere.
    """
    if _env_truthy("TPUFRAME_DISABLE_PALLAS"):
        return None
    if _env_truthy("TPUFRAME_PALLAS_INTERPRET"):
        return "interpret"
    if jax.default_backend() == "tpu":
        return "compiled"
    return None


#: (op, shape class, decision, source) whose verdict event already
#: fired — one loud event per distinct decision, not one per trace
_VERDICT_EMITTED: set[tuple] = set()


def _emit_verdict(op: str, shape_cls: str | None, *, enable: bool,
                  source: str, **attrs) -> None:
    """One ``ops/kernel_verdict`` event per distinct (op, shape class,
    decision).  ``source`` is ``"forced"`` (an explicit ``interpret=``
    or ``TPUFRAME_DISABLE_PALLAS``) or ``"default"`` (the engage rule);
    ``attrs`` is what the op says of the form its kernels take for that
    shape class (`blockwise_attention`: ``operands_in_place`` /
    ``operands_copied``)."""
    key = (op, shape_cls, enable, source)
    if key in _VERDICT_EMITTED:
        return
    _VERDICT_EMITTED.add(key)
    try:
        from tpuframe.track.telemetry import get_telemetry

        get_telemetry().event(
            "ops/kernel_verdict", op=op, shape_class=shape_cls,
            enable=bool(enable), source=source, mode=pallas_mode(), **attrs,
        )
    except Exception:
        pass  # telemetry must never take dispatch down


def use_pallas() -> bool:
    """True when Pallas kernels run for a mesh-less (single-shard) call."""
    mode = pallas_mode()
    if mode is None:
        return False
    return mode == "interpret" or jax.device_count() == 1


def inside_shard_map() -> bool:
    """True when tracing inside an existing shard_map/manual region.

    Nesting a second ``shard_map`` there crashes ("context mesh should
    match"); but a bare kernel call IS the per-shard invocation already,
    so ops should drop their mesh and engage directly.  This is what
    lets mesh-reading modules (FusedLayerNorm inside a TransformerLM)
    compose with shard_map-based steps like
    ``make_train_step(grad_compression=...)``.
    """
    am = jax.sharding.get_abstract_mesh()
    return jax.sharding.AxisType.Manual in am.axis_types


def effective_mesh(mesh):
    """The mesh an op should actually shard over: ``None`` inside a
    manual region (the caller's shard_map already consumed it — run the
    bare per-shard form), the given mesh otherwise.  Every mesh-taking
    op routes its mesh through here so the no-nesting invariant is
    structural, not per-op boilerplate."""
    return None if inside_shard_map() else mesh


def _engage(interpret: bool | None, shardable: bool) -> bool | None:
    if interpret is not None:
        return interpret
    mode = pallas_mode()
    if mode is None:
        return None
    if (
        mode == "compiled"
        and jax.device_count() > 1
        and not shardable
        and not inside_shard_map()
    ):
        return None
    return mode == "interpret"


def resolve_interpret(interpret: bool | None, shardable: bool, *,
                      op: str | None = None,
                      shape_class: str | None = None,
                      engaged_attrs: dict | None = None) -> bool | None:
    """Shared op-level engage decision.

    Returns the interpret flag to use, or None meaning "run the jnp
    reference path".  An explicit ``interpret`` always wins.  Auto mode
    engages the kernel when the backend compiles it (TPU) and either the
    process is single-device, the caller can invoke it per-shard under
    ``shard_map`` (``shardable``), or we are ALREADY per-shard inside a
    manual region — a bare pallas custom call inside a plain multi-device
    jit is the one placement that would force operand replication.

    Ops that pass their ``op`` (and optionally a ``shape_class``) leave
    one ``ops/kernel_verdict`` event per distinct decision, with
    ``engaged_attrs`` on it where the kernels engage.
    """
    decision = _engage(interpret, shardable)
    if op is not None:
        forced = interpret is not None or _env_truthy("TPUFRAME_DISABLE_PALLAS")
        _emit_verdict(op, shape_class, enable=decision is not None,
                      source="forced" if forced else "default",
                      **((engaged_attrs or {}) if decision is not None else {}))
    return decision


def batch_sharding_info(mesh, batch_axes, leading_size: int):
    """-> (axes, n_shards, shardable) for sharding ``leading_size`` rows
    over the ``batch_axes`` of ``mesh`` (mesh may be None)."""
    if batch_axes is None:
        from tpuframe.core.runtime import DATA_AXIS, FSDP_AXIS

        batch_axes = (DATA_AXIS, FSDP_AXIS)
    mesh = effective_mesh(mesh)
    if mesh is None:
        return (), 1, False
    axes = tuple(a for a in batch_axes if a in mesh.shape and mesh.shape[a] > 1)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    shardable = bool(axes) and leading_size > 0 and leading_size % n == 0
    return axes, n, shardable


def pad_to(x: int, multiple: int) -> int:
    return (x + multiple - 1) // multiple * multiple
