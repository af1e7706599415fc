"""Compile spine: persistent XLA compilation cache, AOT warm-start,
recompile-proof step shapes.

Time-to-first-step and restart latency are headline metrics for a
production training system, not footnotes: every cold start, eval
switch, and supervised restart otherwise pays a full XLA trace+compile
on the hot path.  Two modules:

- ``compile.cache``      — jax's persistent compilation cache behind the
  ``TPUFRAME_COMPILE_CACHE`` knob, size-capped keep-K eviction, and
  monitoring listeners that surface every compile in tpuframe telemetry:
  hits, misses and real backend compiles as counters, and one span-log
  record a phase of every compile request with its program's name
  (``compile/jax_trace``, ``compile/jax_lower``, ``compile/jax_backend``).
- ``compile.precompile`` — batch-signature derivation from the loader
  spec, AOT ``lower().compile()`` of the train/eval steps (the Trainer
  overlaps it with loader spin-up in a background thread), and the
  :class:`~tpuframe.compile.precompile.ShapeGuard` that makes any
  runtime recompile a loud ``compile/recompile`` event instead of a
  silent 100x slowdown.

``compile.cache`` never imports jax at module level (the doctor and the
remote launcher read its knob list from wedged-backend processes);
exports here are lazy for the same reason.
"""

# tpuframe-lint: stdlib-only

from tpuframe.compile.cache import (
    COMPILE_ENV_VARS,
    cache_dir_from_env,
    cache_info,
    compile_label,
    disable,
    enable,
    enable_from_env,
    enabled_dir,
    trim,
)

_LAZY = {
    "ShapeGuard": "tpuframe.compile.precompile",
    "abstract_state": "tpuframe.compile.precompile",
    "batch_signature": "tpuframe.compile.precompile",
    "format_signature": "tpuframe.compile.precompile",
    "loader_batch_template": "tpuframe.compile.precompile",
    "precompile_call": "tpuframe.compile.precompile",
    "precompile_step": "tpuframe.compile.precompile",
}

__all__ = [
    "COMPILE_ENV_VARS",
    "ShapeGuard",
    "abstract_state",
    "batch_signature",
    "cache_dir_from_env",
    "cache_info",
    "compile_label",
    "disable",
    "enable",
    "enable_from_env",
    "enabled_dir",
    "format_signature",
    "loader_batch_template",
    "precompile_call",
    "precompile_step",
    "trim",
]


def __getattr__(name):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module 'tpuframe.compile' has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + list(_LAZY)))
