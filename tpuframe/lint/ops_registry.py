"""OP rules — the kernel dispatch registry vs the ``ops/`` modules.

``ops.registry.OPS_REGISTRY`` is the closed list of dispatchable
kernels, each with its entry-point symbol and the parity test that pins
kernel == jnp oracle.  A kernel module absent from the registry is
invisible to the doctor and the diagnosis's name map; a registry row
whose parity test doesn't exist is an untested dispatch claim.  Rules:

- **OP001** — an ``ops/`` kernel module missing from ``OPS_REGISTRY``
  (the dispatch plumbing itself — ``dispatch``, ``registry``, the
  package ``__init__`` — is exempt).
- **OP002** — a registry row whose ``parity_test``
  (``tests/file.py::[Class::]test_name``) points at a missing file or
  a test function that isn't defined there.
- **OP003** — a registry row whose ``module``/``symbol``/``reference``
  doesn't resolve to a definition in the scanned tree.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import ast
import os

from tpuframe.lint.driver import Repo
from tpuframe.lint.report import Finding

RULES = {
    "OP001": "ops/ kernel module not declared in OPS_REGISTRY",
    "OP002": "OPS_REGISTRY parity test missing or undefined",
    "OP003": "OPS_REGISTRY module/symbol does not resolve",
}

#: dispatch plumbing, not kernels — exempt from OP001
_PLUMBING = ("dispatch", "registry")


def _registry_module(repo: Repo) -> str | None:
    for name in repo.files:
        if name.endswith(".ops.registry"):
            return name
    return None


def _const(node) -> object:
    return node.value if isinstance(node, ast.Constant) else None


def declared_ops(repo: Repo) -> dict[str, dict]:
    """op -> {field: value, "line": decl line}, from the OPS_REGISTRY
    dict literal (string/None fields only — tuples are skipped)."""
    mod = _registry_module(repo)
    if mod is None:
        return {}
    out: dict[str, dict] = {}
    for node in ast.walk(repo.files[mod].tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        t = node.targets[0]
        if not (isinstance(t, ast.Name) and t.id == "OPS_REGISTRY"):
            continue
        if not isinstance(node.value, ast.Dict):
            continue
        for k, v in zip(node.value.keys, node.value.values):
            op = _const(k)
            if not isinstance(op, str) or not isinstance(v, ast.Dict):
                continue
            entry: dict = {"line": k.lineno}
            for fk, fv in zip(v.keys, v.values):
                field = _const(fk)
                if isinstance(field, str):
                    entry[field] = _const(fv)
            out[op] = entry
    return out


def _defined_symbols(repo: Repo, module: str) -> set[str]:
    src = repo.files.get(module)
    if src is None:
        return set()
    out: set[str] = set()
    for node in src.nodes:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out.add(node.name)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _parity_test_finding(repo: Repo, op: str, entry: dict,
                         registry_rel: str) -> Finding | None:
    ref = entry.get("parity_test")
    line = entry["line"]
    if not isinstance(ref, str) or "::" not in ref:
        return Finding(
            rule="OP002", file=registry_rel, line=line,
            message=(
                f"OPS_REGISTRY[{op!r}] parity_test must be "
                "'tests/file.py::[Class::]test_name', got "
                f"{ref!r}"
            ),
            hint="point it at the kernel-vs-oracle parity test",
        )
    path, _, rest = ref.partition("::")
    test_name = rest.split("::")[-1]
    abspath = os.path.join(repo.docs_root, path)
    if not os.path.exists(abspath):
        return Finding(
            rule="OP002", file=registry_rel, line=line,
            message=(
                f"OPS_REGISTRY[{op!r}] parity test file {path!r} does "
                "not exist"
            ),
            hint="write the parity test (kernel output == jnp oracle)",
        )
    try:
        with open(abspath, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        text = ""
    if f"def {test_name}" not in text:
        return Finding(
            rule="OP002", file=registry_rel, line=line,
            message=(
                f"OPS_REGISTRY[{op!r}] names parity test "
                f"{test_name!r} but {path} defines no such test"
            ),
            hint=f"define `def {test_name}` in {path} (or fix the row)",
        )
    return None


def check(repo: Repo) -> list[Finding]:
    registry_mod = _registry_module(repo)
    if registry_mod is None:
        return []
    registry_src = repo.files[registry_mod]
    declared = declared_ops(repo)
    registered_modules = {
        e.get("module") for e in declared.values()
    }
    findings: list[Finding] = []

    # OP001: every ops/ kernel module is in the registry
    ops_pkg = registry_mod.rsplit(".", 1)[0]  # "<package>.ops"
    for module, src in sorted(repo.files.items()):
        if not module.startswith(ops_pkg + "."):
            continue
        leaf = module.rsplit(".", 1)[-1]
        if leaf in _PLUMBING or leaf.startswith("_"):
            continue
        if module not in registered_modules:
            findings.append(Finding(
                rule="OP001", file=src.rel, line=1,
                message=(
                    f"ops kernel module {module!r} is not declared in "
                    "ops.registry.OPS_REGISTRY"
                ),
                hint=(
                    "add a registry row (module, symbol, reference, "
                    "parity_test) so the op is dispatchable and tested"
                ),
            ))

    for op, entry in sorted(declared.items()):
        line = entry["line"]
        module = entry.get("module")
        if not isinstance(module, str) or module not in repo.files:
            findings.append(Finding(
                rule="OP003", file=registry_src.rel, line=line,
                message=(
                    f"OPS_REGISTRY[{op!r}] module {module!r} is not in "
                    "the scanned tree"
                ),
                hint="fix the module path (stale registry row?)",
            ))
        else:
            symbols = _defined_symbols(repo, module)
            for field in ("symbol", "reference"):
                sym = entry.get(field)
                if sym is None:
                    continue  # reference=None: kernel is its own oracle
                if sym not in symbols:
                    findings.append(Finding(
                        rule="OP003", file=registry_src.rel, line=line,
                        message=(
                            f"OPS_REGISTRY[{op!r}] {field} {sym!r} is "
                            f"not defined in {module}"
                        ),
                        hint="fix the registry row or define the symbol",
                    ))
        f = _parity_test_finding(repo, op, entry, registry_src.rel)
        if f is not None:
            findings.append(f)
    return findings
