"""Repo model + orchestration for the invariant linter.

One parse per file: :func:`load_repo` walks a package tree, parses every
``.py`` into an AST, extracts the ``# tpuframe-lint:`` directives with
``tokenize`` (real comments only — the same text inside a docstring is
prose, not policy), and loads the schema docs from the repo root.  The
rule families (``lint.imports`` / ``knobs`` / ``schema`` / ``hazards`` /
``sites`` / ``ops_registry``) are pure functions over that model, so
the whole pass costs one tree walk + six AST passes — cheap enough for
tier-1 and the doctor.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import ast
import dataclasses
import io
import os
import tokenize
from typing import Iterable

from tpuframe.lint.report import Finding, Suppressions, split_suppressed

#: docs the schema/knob/site rules cross-check, looked up in the repo root
#: (the package dir's parent); a missing doc skips the rules that need it
#: (an installed wheel has no OBSERVABILITY.md — the pass still runs the
#: pure-code rules there)
DOC_FILES = ("OBSERVABILITY.md", "FAULT.md", "SERVE.md", "PERF.md")

#: hot-path seed modules (suffix match under the scanned package): every
#: function defined here, plus everything reachable from them, is "hot"
HOT_PATH_SEEDS = ("train.step", "serve.engine")


@dataclasses.dataclass
class SourceFile:
    """One parsed module + its lint directives."""

    rel: str                       # path relative to the repo root
    path: str                      # absolute path
    module: str                    # dotted module name ("tpuframe.track.telemetry")
    text: str
    tree: ast.Module
    stdlib_only: bool              # carries "# tpuframe-lint: stdlib-only"
    disabled: dict[int, set[str]]  # line -> disabled rule ids ({"all"} = any)
    directive_lines: dict[int, str]  # line -> raw directive (e.g. "not-shipped")
    _nodes: list | None = None

    @property
    def nodes(self) -> list[ast.AST]:
        """Flattened AST, walked once and shared by every rule family
        (the pass's dominant cost is repeated ast.walk otherwise)."""
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    def rule_disabled(self, rule: str, line: int) -> bool:
        d = self.disabled.get(line, ())
        return rule in d or "all" in d


@dataclasses.dataclass
class Repo:
    """Everything the rule families look at."""

    package_root: str            # absolute dir of the scanned package
    package: str                 # its import name ("tpuframe")
    docs_root: str               # where the schema docs live
    files: dict[str, SourceFile]          # keyed by module name
    docs: dict[str, str]                  # doc filename -> text

    def doc_line(self, doc: str, needle: str) -> int:
        """1-based line of the first occurrence of ``needle`` in ``doc``
        (0 when absent) — so doc-side findings anchor to a real line."""
        text = self.docs.get(doc, "")
        pos = text.find(needle)
        return text.count("\n", 0, pos) + 1 if pos >= 0 else 0


def _parse_directives(text: str) -> tuple[bool, dict, dict]:
    """Extract ``# tpuframe-lint:`` directives from real COMMENT tokens."""
    stdlib_only = False
    disabled: dict[int, set[str]] = {}
    directive_lines: dict[int, str] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(text).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            body = tok.string.lstrip("#").strip()
            if not body.startswith("tpuframe-lint:"):
                continue
            directive = body[len("tpuframe-lint:"):].strip()
            line = tok.start[0]
            directive_lines[line] = directive
            if directive == "stdlib-only":
                stdlib_only = True
            elif directive.startswith("disable="):
                rules = {r.strip() for r in
                         directive[len("disable="):].split(",") if r.strip()}
                disabled.setdefault(line, set()).update(rules)
            # other directives (e.g. "not-shipped") are consumed by the
            # rule that defines them, via directive_lines
    except tokenize.TokenError:
        pass  # a syntactically broken file already fails ast.parse loudly
    return stdlib_only, disabled, directive_lines


def _module_name(package: str, rel_to_pkg: str) -> str:
    parts = rel_to_pkg.split(os.sep)
    if parts[-1] == "__init__.py":
        parts = parts[:-1]
    else:
        parts[-1] = parts[-1][: -len(".py")]
    return ".".join([package] + [p for p in parts if p])


def load_repo(package_dir: str | None = None,
              docs_dir: str | None = None) -> Repo:
    """Parse a package tree into a :class:`Repo`.

    Defaults scan the installed ``tpuframe`` package with docs from its
    parent directory (= the repo root in a source checkout).  Tests point
    this at fixture trees — any directory whose basename is the package
    name works.
    """
    if package_dir is None:
        import tpuframe

        package_dir = os.path.dirname(os.path.abspath(tpuframe.__file__))
    package_dir = os.path.abspath(package_dir)
    package = os.path.basename(package_dir)
    docs_root = os.path.abspath(docs_dir) if docs_dir else os.path.dirname(package_dir)

    files: dict[str, SourceFile] = {}
    for dirpath, dirnames, filenames in os.walk(package_dir):
        dirnames[:] = sorted(
            d for d in dirnames
            if not d.startswith(".") and d != "__pycache__"
        )
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel_to_pkg = os.path.relpath(path, package_dir)
            rel = os.path.join(package, rel_to_pkg)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            tree = ast.parse(text, filename=rel)
            stdlib_only, disabled, directive_lines = _parse_directives(text)
            module = _module_name(package, rel_to_pkg)
            files[module] = SourceFile(
                rel=rel, path=path, module=module, text=text, tree=tree,
                stdlib_only=stdlib_only, disabled=disabled,
                directive_lines=directive_lines,
            )

    docs = {}
    for doc in DOC_FILES:
        p = os.path.join(docs_root, doc)
        if os.path.exists(p):
            with open(p, encoding="utf-8") as f:
                docs[doc] = f.read()
    return Repo(package_root=package_dir, package=package,
                docs_root=docs_root, files=files, docs=docs)


@dataclasses.dataclass
class LintResult:
    findings: list[Finding]
    suppressed_count: int
    files_scanned: int
    rules_run: int

    def rule_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for f in self.findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        return dict(sorted(counts.items()))


def _apply_inline_disables(repo: Repo, findings: Iterable[Finding]) -> tuple[list, int]:
    by_rel = {f.rel: f for f in repo.files.values()}
    kept, dropped = [], 0
    for f in findings:
        src = by_rel.get(f.file)
        if src is not None and src.rule_disabled(f.rule, f.line):
            dropped += 1
        else:
            kept.append(f)
    return kept, dropped


def run_lint(
    package_dir: str | None = None,
    docs_dir: str | None = None,
    suppressions: Suppressions | str | None = None,
) -> LintResult:
    """The full pass: load, run every rule family, apply suppressions."""
    from tpuframe.lint import (
        hazards, imports, knobs, ops_registry, schema, sites,
    )

    repo = load_repo(package_dir, docs_dir)
    families = (imports, knobs, schema, sites, hazards, ops_registry)
    findings: list[Finding] = []
    rules_run = 0
    for family in families:
        rules_run += len(family.RULES)
        findings.extend(family.check(repo))
    findings.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    findings, inline_dropped = _apply_inline_disables(repo, findings)
    if isinstance(suppressions, str):
        suppressions = Suppressions.load(suppressions)
    findings, file_dropped = split_suppressed(findings, suppressions)
    return LintResult(
        findings=findings,
        suppressed_count=inline_dropped + len(file_dropped),
        files_scanned=len(repo.files),
        rules_run=rules_run,
    )
