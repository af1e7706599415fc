"""Invariant linter: AST-enforced contracts the test suite can't see.

Eight PRs of spine-building accumulated load-bearing invariants that
existed only as docstring prose: which modules are contractually
stdlib-only (the doctor/telemetry/fault stack must import while jax is
wedged), which ``TPUFRAME_*`` knobs ship to workers through which
``*_ENV_VARS`` list, which telemetry names have schema rows in
OBSERVABILITY.md, which chaos sites are declared in
``fault.chaos.CHAOS_SITES``, and which hot-path functions must not
silently sync device→host.  This package machine-checks all of it by
parsing the tree (``ast`` + ``tokenize`` — the pass itself is
stdlib-only and never imports jax, numpy, or any tpuframe module that
does), so every one of those invariants is a failing tier-1 test the
moment a future PR drifts.

Run it::

    python -m tpuframe.lint              # human-readable, exit 0 clean / 3 findings
    python -m tpuframe.lint --json       # machine-readable findings
    python -m tpuframe.lint --knobs --json   # reconciled knob inventory
                                             # (the core/config registry seam)

Rule families (catalog with fix hints in LINT.md):

- **JF** (``lint.imports``) — jax-free contract: a module marked
  ``# tpuframe-lint: stdlib-only`` may import, at module level, only the
  stdlib and other marked modules — verified over the real import graph
  including package ``__init__`` execution, not just the file.
- **KN** (``lint.knobs``) — knob accounting: every literal
  ``TPUFRAME_*`` env read is declared in exactly one ``*_ENV_VARS``
  list, every entry is read somewhere, every shipped list is aggregated
  by ``launch.remote.all_env_vars()``, and every knob is documented.
- **TS** (``lint.schema``) — telemetry schema drift: span/event/counter/
  gauge/histogram name literals exist in the OBSERVABILITY/FAULT/SERVE
  schema docs, and documented names still exist in code.
- **HP** (``lint.hazards``) — hot-path hazards: un-spanned device→host
  syncs, Python branching on traced values, and donation of
  possibly-aliased buffers, in functions reachable from the jitted
  step/serve paths.
- **CS** (``lint.sites``) — chaos-site registry: every fired injection
  site is declared in ``fault.chaos.CHAOS_SITES`` and documented in
  FAULT.md, and every declared site is actually instrumented.
- **OP** (``lint.ops_registry``) — kernel dispatch registry: every
  ``ops/`` kernel module is declared in ``ops.registry.OPS_REGISTRY``
  with a resolvable entry point and an existing parity test, so a
  kernel can't ship undispatched or untested.

Suppression: inline ``# tpuframe-lint: disable=RULE`` on the finding's
line, or a ``--suppressions`` file (``RULE:file-glob[:substr]`` per
line).  The repo's own acceptance test (``tests/test_lint.py``) runs the
full pass over ``tpuframe/`` and asserts zero unsuppressed findings.
"""

# tpuframe-lint: stdlib-only

from tpuframe.lint.driver import LintResult, Repo, load_repo, run_lint
from tpuframe.lint.report import Finding, Suppressions, render_json, render_text

__all__ = [
    "Finding",
    "LintResult",
    "Repo",
    "Suppressions",
    "load_repo",
    "render_json",
    "render_text",
    "run_lint",
]
