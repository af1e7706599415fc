"""Kernel dispatch: compiled Pallas on TPU, jnp reference elsewhere.

Every op in tpuframe.ops has two implementations with identical
semantics; tests assert they match (with ``interpret=True`` running the
real kernel code on CPU).  Env knobs:

- ``TPUFRAME_DISABLE_PALLAS=1`` forces the reference path everywhere —
  the escape hatch when a kernel misbehaves on a new compiler version.
- ``TPUFRAME_PALLAS_INTERPRET=1`` runs the kernels in Pallas interpret
  mode on any backend — how ``dryrun_multichip`` exercises the sharded
  kernel paths on virtual CPU devices.
- ``TPUFRAME_KERNELS=auto|on|off`` is the measured layer above those
  engage rules: ``auto`` (default) consults the persisted kernel ledger
  (``ops/ledger.py`` — A/B-priced per backend + shape class, never
  committed slower), ``on`` bypasses the ledger, ``off`` forces the
  reference path everywhere.  Every distinct decision fires one loud
  ``ops/kernel_verdict`` event, so a trace of a misdispatched run says
  which verdict (and whose measurement) chose the path.

An op may decline its own kernel from what it sees in its input before
any of these is asked: ``normalize_images`` keeps an NHWC image batch in
its own layout as plain jnp wherever a kernel could run (``source="layout"``
on its verdict event), because the kernel's flat view of such an array is
a physical re-layout on the chip that the ledger's stand-alone A/B does
not price.

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


def kernels_mode() -> str:
    """``TPUFRAME_KERNELS``: ``"auto"`` | ``"on"`` | ``"off"``."""
    from tpuframe.ops.ledger import kernels_mode as _mode

    return _mode()


#: (op, shape_class) pairs whose verdict event already fired — one loud
#: event per distinct decision, not one per trace
_VERDICT_EMITTED: set[tuple] = set()

#: process cache for the persisted ledger: (dir, backend, signature) ->
#: KernelLedger | None.  The store is consulted at trace time, so the
#: read must be one dict lookup after the first call.
_LEDGER_CACHE: dict[tuple, object] = {}


def _reset_kernel_cache() -> None:
    """Drop the per-process ledger/verdict caches (tests; call after
    re-pricing so new verdicts take effect without a restart)."""
    _VERDICT_EMITTED.clear()
    _LEDGER_CACHE.clear()


def _cached_ledger(*, backend: str | None = None, signature: str | None = None):
    """The persisted :class:`~tpuframe.ops.ledger.KernelLedger` for this
    (host, backend, signature), loaded once per process, or None."""
    from tpuframe.ops import ledger as _ledger

    b = backend or jax.default_backend()
    sig = signature or _ledger.DEFAULT_SIGNATURE
    key = (_ledger.ledger_dir(), b, sig)
    if key not in _LEDGER_CACHE:
        _LEDGER_CACHE[key] = _ledger.load_ledger(
            _ledger.default_host(), b, sig)
    return _LEDGER_CACHE[key]


def _emit_verdict(op: str, shape_cls: str | None, *, enable: bool,
                  source: str, **extra) -> None:
    """One ``ops/kernel_verdict`` event per distinct (op, shape class,
    decision), plus the ledger hit/miss counters.  ``source="layout"`` is
    an op that saw in its input's shape that its kernel's view of it is a
    physical re-layout and kept the jnp form (``ops/normalize.py``): the
    ledger was not asked, so neither counter moves."""
    key = (op, shape_cls, enable, source)
    if key in _VERDICT_EMITTED:
        return
    _VERDICT_EMITTED.add(key)
    try:
        from tpuframe.track.telemetry import get_telemetry

        tele = get_telemetry()
        if source != "layout":
            tele.registry.counter(
                "ops/ledger_hit" if source == "ledger" else "ops/ledger_miss"
            ).inc()
        tele.event(
            "ops/kernel_verdict", op=op, shape_class=shape_cls,
            enable=bool(enable), source=source,
            mode=kernels_mode(), **extra,
        )
    except Exception:
        pass  # telemetry must never take dispatch down


def kernel_enabled(op: str, shape_class: str | None = None) -> bool:
    """Should ``op``'s kernel engage for this shape class?

    ``TPUFRAME_KERNELS=off`` -> False everywhere; ``on`` -> True
    (backend capability still gates via ``pallas_mode``); ``auto`` ->
    the persisted ledger's A/B verdict when one exists for this
    (backend, shape class), else True — pre-ledger behavior is the
    default, the ledger only ever *removes* kernels it measured slower.
    """
    mode = kernels_mode()
    if mode == "off":
        _emit_verdict(op, shape_class, enable=False, source="forced")
        return False
    if mode == "on":
        _emit_verdict(op, shape_class, enable=True, source="forced")
        return True
    led = _cached_ledger()
    v = led.verdict(op, shape_class) if led is not None and shape_class \
        else None
    if v is None and led is not None and shape_class is None:
        # shape-agnostic consult: any recorded verdict for the op
        classes = getattr(led, "verdicts", {}).get(op) or {}
        v = next(iter(classes.values()), None)
    if v is not None and "enable" in v:
        _emit_verdict(op, shape_class, enable=bool(v["enable"]),
                      source="ledger")
        return bool(v["enable"])
    _emit_verdict(op, shape_class, enable=True, source="default")
    return True


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


def resolve_interpret(interpret: bool | None, shardable: bool, *,
                      op: str | None = None,
                      shape_class: str | None = None) -> bool | None:
    """Shared op-level engage decision.

    Returns the interpret flag to use, or None meaning "run the jnp
    reference path".  An explicit ``interpret`` always wins.  Auto mode
    engages the kernel when the backend compiles it (TPU) and either the
    process is single-device, the caller can invoke it per-shard under
    ``shard_map`` (``shardable``), or we are ALREADY per-shard inside a
    manual region — a bare pallas custom call inside a plain multi-device
    jit is the one placement that would force operand replication.

    Ops that pass their ``op`` (and optionally a ``shape_class``) get
    the measured layer on top: ``TPUFRAME_KERNELS=off`` forces the
    reference, and ``auto`` consults the persisted ledger verdict via
    :func:`kernel_enabled` — a kernel the ledger priced slower for this
    shape class stays off.
    """
    if interpret is not None:
        return interpret
    if op is not None and not kernel_enabled(op, shape_class):
        return None
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
