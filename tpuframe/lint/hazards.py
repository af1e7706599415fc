"""HP rules — hot-path hazards in the jitted step / serve paths.

Seeded from ``train/step.py`` and ``serve/engine.py`` (every function
defined there) and expanded over a name-resolved intra-repo call graph,
this family flags the three hazard classes that cost real incidents:

- **HP001** — an *un-spanned* device→host sync (``.item()``,
  ``.block_until_ready()``, ``np.asarray``/``np.array`` on device data,
  ``jax.device_get``) in a hot-path host function.  A sync inside a
  ``with ...span(...)`` block is measured and therefore allowed — the
  contract is "syncs on the hot path must be attributable", exactly how
  ``serve/infer`` wraps its backend call and ``data/h2d`` wraps transfer
  completion.
- **HP002** — Python-value branching on traced values inside functions
  that are jit-traced (``if jnp.mean(loss) > k:`` style), plus
  ``.item()``/``float()``/``int()`` concretization of traced
  expressions — the recompile/abort hazards the runtime ShapeGuard only
  catches after they've already cost a compile.
- **HP003** — ``jax.jit(..., donate_argnums=...)`` donating a
  batch-/buffer-shaped parameter: donated buffers that a
  ``BatchBufferPool`` lease or an orbax restore may still alias corrupt
  the heap (the PR-5 ``_rebuffer`` incident class).  Donating the
  train-state position is the sanctioned pattern and is not flagged.

The call graph is syntactic (simple-name resolution, common/ambiguous
names skipped) and the traced-value analysis is a conservative taint
pass — both err toward silence on idiomatic code; a finding here is
worth reading, and ``# tpuframe-lint: disable=HP00x`` with a
justification is the waiver channel when the sync is deliberate.
Expansion stops at ``stdlib-only`` modules: code that contractually
cannot import jax or numpy holds no device arrays and no tracers, so
the graph doesn't contaminate through a trace-time config/telemetry
read into unrelated host code.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import ast
import dataclasses

from tpuframe.lint.driver import HOT_PATH_SEEDS, Repo, SourceFile
from tpuframe.lint.report import Finding

RULES = {
    "HP001": "un-spanned device->host sync in a hot-path function",
    "HP002": "python branching/concretization on traced values in jitted code",
    "HP003": "donate_argnums on a possibly-aliased batch/buffer argument",
}

#: attribute calls that synchronize device->host
_SYNC_ATTRS = ("item", "block_until_ready")
#: numpy functions that materialize (and therefore sync) device arrays
_NP_SYNC = ("asarray", "array")
#: call names whose argument becomes a traced function
_TRACERS = ("jit", "shard_map", "pmap", "vmap", "grad", "value_and_grad",
            "scan", "checkpoint", "remat")
#: parameter names that suggest input/buffer data (the aliasing hazard);
#: state-like names are the sanctioned donation target
_BATCHY_PARAMS = ("batch", "batches", "x", "xs", "inputs", "images",
                  "data", "payload", "arrays", "buffers", "lease")
#: attributes of traced values that are static under tracing
_STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "sharding", "aval")
#: calls whose result is host-static even on traced arguments
_STATIC_CALLS = ("len", "isinstance", "hasattr", "getattr", "type", "bool")
#: simple names too common to resolve through the call graph
_AMBIGUOUS = ("get", "put", "run", "start", "stop", "close", "read",
              "write", "update", "main", "save", "restore", "check",
              "add", "pop", "append", "items", "keys", "values", "join",
              "wait", "set", "clear", "release", "acquire", "format")


@dataclasses.dataclass
class FuncInfo:
    module: str
    rel: str
    qualname: str
    node: ast.FunctionDef | ast.AsyncFunctionDef
    calls: set[str]


def _collect_functions(repo: Repo) -> dict[str, list[FuncInfo]]:
    """simple name -> every definition of it in the tree."""
    by_name: dict[str, list[FuncInfo]] = {}
    for src in repo.files.values():
        stack: list[tuple[ast.AST, str]] = [(src.tree, "")]
        while stack:
            node, prefix = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    calls = {
                        (n.func.attr if isinstance(n.func, ast.Attribute)
                         else n.func.id)
                        for n in ast.walk(child)
                        if isinstance(n, ast.Call)
                        and isinstance(n.func, (ast.Attribute, ast.Name))
                    }
                    info = FuncInfo(src.module, src.rel, qual, child, calls)
                    by_name.setdefault(child.name, []).append(info)
                    stack.append((child, f"{qual}."))
                elif isinstance(child, ast.ClassDef):
                    stack.append((child, f"{prefix}{child.name}."))
    return by_name


def _seed_functions(repo: Repo, by_name) -> list[FuncInfo]:
    seeds = []
    seed_modules = {
        f"{repo.package}.{suffix}" for suffix in HOT_PATH_SEEDS
    }
    for infos in by_name.values():
        seeds.extend(i for i in infos if i.module in seed_modules)
    return seeds


def _reachable(seeds, by_name, stop_modules=frozenset()) -> set[int]:
    """ids of FuncInfos reachable from the seeds over the name graph.

    ``stop_modules`` (the stdlib-only set) is a contamination boundary:
    a module that contractually cannot import jax or numpy holds no
    device arrays and no tracers, so neither hazard class can propagate
    through it — expanding past it only manufactures false positives
    (e.g. a trace-time registry read name-resolving into every
    ``from_dict`` in the tree)."""
    seen: set[int] = set()
    work = list(seeds)
    while work:
        info = work.pop()
        if id(info) in seen:
            continue
        seen.add(id(info))
        if info.module in stop_modules:
            continue  # host-only code: don't expand through it
        for name in info.calls:
            if name in _AMBIGUOUS or name.startswith("__"):
                continue
            targets = by_name.get(name, ())
            if len(targets) > 3:
                continue  # too ambiguous to resolve by name
            work.extend(targets)
    return seen


def _traced_roots(repo: Repo, by_name) -> list[FuncInfo]:
    """Local defs passed to jit/shard_map/scan/... anywhere in the tree,
    plus defs decorated with a tracer."""
    roots: list[FuncInfo] = []
    for src in repo.files.values():
        local = {
            i.node: i
            for infos in by_name.values()
            for i in infos
            if i.module == src.module
        }
        local_by_name: dict[str, list[FuncInfo]] = {}
        for i in local.values():
            local_by_name.setdefault(i.node.name, []).append(i)
        for node in src.nodes:
            if isinstance(node, ast.Call):
                func = node.func
                attr = func.attr if isinstance(func, ast.Attribute) else (
                    func.id if isinstance(func, ast.Name) else None
                )
                if attr not in _TRACERS:
                    continue
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        roots.extend(local_by_name.get(arg.id, ()))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    attr = d.attr if isinstance(d, ast.Attribute) else (
                        d.id if isinstance(d, ast.Name) else None
                    )
                    if attr in _TRACERS and node.name in local_by_name:
                        roots.extend(local_by_name[node.name])
    return roots


def _numpy_aliases(src: SourceFile) -> set[str]:
    out = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _is_static(node: ast.AST) -> bool:
    """Host-static even when its operands are traced (shape/dtype reads,
    len(), isinstance(), constants)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_static(node.value)
    if isinstance(node, ast.Call):
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None
        )
        return name in _STATIC_CALLS
    if isinstance(node, ast.BinOp):
        return _is_static(node.left) and _is_static(node.right)
    return False


class _TaintedUse(ast.NodeVisitor):
    """Does this expression *use the value of* a tainted name (param-derived
    traced data), excluding statically-known projections?"""

    def __init__(self, tainted: set[str]):
        self.tainted = tainted
        self.hit = False

    def visit_Name(self, node: ast.Name):
        if node.id in self.tainted:
            self.hit = True

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return  # .shape/.ndim/... of anything is static
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        f = node.func
        name = f.id if isinstance(f, ast.Name) else (
            f.attr if isinstance(f, ast.Attribute) else None
        )
        if name in _STATIC_CALLS:
            return
        self.generic_visit(node)


def _uses_tainted(node: ast.AST, tainted: set[str]) -> bool:
    v = _TaintedUse(tainted)
    v.visit(node)
    return v.hit


def _check_traced(info: FuncInfo, src: SourceFile) -> list[Finding]:
    """HP002 inside one traced function."""
    findings = []
    fn = info.node
    tainted = {a.arg for a in fn.args.args} - {"self"}
    for node in ast.walk(fn):
        # propagate taint through simple assignments
        if isinstance(node, ast.Assign) and _uses_tainted(node.value, tainted):
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        tainted.add(n.id)
        elif isinstance(node, (ast.If, ast.While)):
            test = node.test
            for cmp_ in ast.walk(test):
                if not (isinstance(cmp_, ast.Compare) and len(cmp_.ops) == 1):
                    continue
                if not isinstance(cmp_.ops[0], (ast.Lt, ast.LtE, ast.Gt,
                                                ast.GtE, ast.Eq, ast.NotEq)):
                    continue
                sides = (cmp_.left, cmp_.comparators[0])
                params = {a.arg for a in fn.args.args}
                for a, b in (sides, sides[::-1]):
                    # bare *parameters* are excluded (static config like
                    # `train=` flags); values *derived* from params are
                    # the traced-branch hazard
                    if (isinstance(b, ast.Constant)
                            and isinstance(b.value, (int, float))
                            and not _is_static(a)
                            and not (isinstance(a, ast.Name) and a.id in params)
                            and _uses_tainted(a, tainted)):
                        findings.append(Finding(
                            rule="HP002", file=src.rel, line=cmp_.lineno,
                            message=(
                                f"python branch on a traced value inside "
                                f"jitted {info.qualname!r} — under jit this "
                                "aborts tracing or forces per-value "
                                "recompiles"
                            ),
                            hint=(
                                "use jnp.where / lax.cond on device, or "
                                "read the value outside the jitted region"
                            ),
                        ))
                        break
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "item":
                findings.append(Finding(
                    rule="HP002", file=src.rel, line=node.lineno,
                    message=(
                        f".item() inside jitted {info.qualname!r} "
                        "concretizes a tracer"
                    ),
                    hint="keep the value on device; materialize after the step",
                ))
            elif (isinstance(f, ast.Name) and f.id in ("float", "int")
                    and node.args
                    and isinstance(node.args[0], (ast.Call, ast.Subscript))
                    and not _is_static(node.args[0])
                    and _uses_tainted(node.args[0], tainted)):
                findings.append(Finding(
                    rule="HP002", file=src.rel, line=node.lineno,
                    message=(
                        f"{f.id}() on a traced expression inside jitted "
                        f"{info.qualname!r} concretizes a tracer"
                    ),
                    hint="keep it a jnp scalar; convert on the host side",
                ))
    return findings


class _HostSyncVisitor(ast.NodeVisitor):
    """HP001 inside one hot-path host function: flag syncs not lexically
    under a ``with ...span(...)`` statement."""

    def __init__(self, info: FuncInfo, src: SourceFile, np_aliases: set[str]):
        self.info = info
        self.src = src
        self.np_aliases = np_aliases
        self.span_depth = 0
        self.findings: list[Finding] = []

    def visit_With(self, node: ast.With):
        spanned = any(
            isinstance(item.context_expr, ast.Call)
            and isinstance(item.context_expr.func, ast.Attribute)
            and item.context_expr.func.attr in ("span", "guard")
            for item in node.items
        )
        for item in node.items:
            self.visit(item.context_expr)
        if spanned:
            self.span_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        if spanned:
            self.span_depth -= 1

    def _flag(self, node: ast.AST, what: str):
        self.findings.append(Finding(
            rule="HP001", file=self.src.rel, line=node.lineno,
            message=(
                f"{what} in hot-path function {self.info.qualname!r} "
                "outside any telemetry span — an invisible device->host "
                "sync on the step/serve path"
            ),
            hint=(
                "wrap it in `with get_telemetry().span('<layer>/<activity>')`"
                " so the wait is attributed (or move it off the hot path; "
                "justify deliberate cases with "
                "'# tpuframe-lint: disable=HP001')"
            ),
        ))

    def visit_Call(self, node: ast.Call):
        if self.span_depth == 0:
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr in _SYNC_ATTRS:
                    self._flag(node, f".{f.attr}()")
                elif (f.attr in _NP_SYNC
                        and isinstance(f.value, ast.Name)
                        and f.value.id in self.np_aliases | {"np"}):
                    self._flag(node, f"{f.value.id}.{f.attr}()")
                elif f.attr == "device_get":
                    self._flag(node, "jax.device_get()")
        self.generic_visit(node)

    # don't descend into nested defs: they're separate graph nodes
    def visit_FunctionDef(self, node):
        pass

    visit_AsyncFunctionDef = visit_FunctionDef


def _check_donation(repo: Repo, by_name) -> list[Finding]:
    """HP003: jit calls donating batch-/buffer-named parameters."""
    findings = []
    for src in repo.files.values():
        local: dict[str, ast.FunctionDef] = {}
        for infos in by_name.values():
            for i in infos:
                if i.module == src.module:
                    local.setdefault(i.node.name, i.node)
        for node in src.nodes:
            if not (isinstance(node, ast.Call) and node.args):
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None
            )
            if attr != "jit":
                continue
            donate = next(
                (kw.value for kw in node.keywords
                 if kw.arg == "donate_argnums"), None,
            )
            target = node.args[0]
            if donate is None or not isinstance(target, ast.Name):
                continue
            fn = local.get(target.id)
            if fn is None:
                continue
            nums = [
                e.value for e in ast.walk(donate)
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            ]
            params = [a.arg for a in fn.args.args]
            for n in nums:
                if n < len(params) and params[n] in _BATCHY_PARAMS:
                    findings.append(Finding(
                        rule="HP003", file=src.rel, line=node.lineno,
                        message=(
                            f"donate_argnums donates parameter "
                            f"{params[n]!r} of {target.id!r} — input "
                            "buffers may still be aliased by a "
                            "BatchBufferPool lease or an orbax restore "
                            "(the PR-5 _rebuffer heap-corruption class)"
                        ),
                        hint=(
                            "donate only the state position; re-home "
                            "restored/pooled buffers (ckpt._rebuffer / "
                            "pool release) before donating them"
                        ),
                    ))
    return findings


def check(repo: Repo) -> list[Finding]:
    by_name = _collect_functions(repo)
    seeds = _seed_functions(repo, by_name)
    if not seeds:
        return _check_donation(repo, by_name)
    host_only = frozenset(
        m for m, src in repo.files.items() if src.stdlib_only
    )
    reachable_ids = _reachable(seeds, by_name, host_only)
    traced_roots = _traced_roots(repo, by_name)
    traced_ids = _reachable(traced_roots, by_name, host_only)

    findings: list[Finding] = []
    all_infos = [i for infos in by_name.values() for i in infos]
    np_alias_cache: dict[str, set[str]] = {}
    for info in all_infos:
        src = repo.files[info.module]
        if id(info) in traced_ids:
            findings.extend(_check_traced(info, src))
        elif id(info) in reachable_ids:
            if info.module not in np_alias_cache:
                np_alias_cache[info.module] = _numpy_aliases(src)
            v = _HostSyncVisitor(info, src, np_alias_cache[info.module])
            for stmt in info.node.body:
                v.visit(stmt)
            findings.extend(v.findings)
    findings.extend(_check_donation(repo, by_name))
    return findings
