"""Invariant linter: per-rule fixture proofs + the repo-wide acceptance gate.

Two fixture trees under ``tests/fixtures/lint/``:

- ``clean/`` — a miniature spine-shaped package where every contract
  holds; each rule family is proven to stay quiet on idiomatic code
  (spanned syncs, static-attribute branching, state-position donation,
  declared+documented knobs/sites/names).
- ``dirty/`` — one seeded violation per rule; each rule is proven to
  fire, at the right file, with the right id.

Plus the two tests that make the linter a tier-1 gate: the real
``tpuframe/`` tree must produce **zero unsuppressed findings**, and
seeding a violation into a fixture copy of a real module must flip the
pass red.  The linter itself is stdlib-only, so this file never needs
jax — it stays cheap even under a wedged backend.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest

from tpuframe.lint import Suppressions, run_lint
from tpuframe.lint.__main__ import main as lint_main
from tpuframe.lint.knobs import knob_inventory
from tpuframe.lint.driver import load_repo

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")
CLEAN = os.path.join(FIXTURES, "clean", "tpuframe")
DIRTY = os.path.join(FIXTURES, "dirty", "tpuframe")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REAL_PKG = os.path.join(REPO_ROOT, "tpuframe")


def _rules(result):
    return {f.rule for f in result.findings}


def _by_rule(result):
    out = {}
    for f in result.findings:
        out.setdefault(f.rule, []).append(f)
    return out


# -- the tier-1 acceptance gate ----------------------------------------------


def test_repo_tree_has_zero_findings():
    """THE invariant gate: every contract the linter enforces holds on
    the merged tree, with no suppressions file at all."""
    result = run_lint(REAL_PKG, REPO_ROOT)
    assert not result.findings, "invariant drift:\n" + "\n".join(
        f.format() for f in result.findings
    )
    # and the pass actually looked at the tree
    assert result.files_scanned > 50
    assert result.rules_run == 20


def test_seeded_violation_in_real_module_flips_red(tmp_path):
    """Copy the real package, seed one stray heavy import into the
    telemetry module (contractually stdlib-only), and the pass must go
    red — the acceptance criterion that future drift fails tier-1."""
    pkg = tmp_path / "tpuframe"
    shutil.copytree(
        REAL_PKG, pkg,
        ignore=shutil.ignore_patterns("__pycache__", "*.so", "_native"),
    )
    tele = pkg / "track" / "telemetry.py"
    tele.write_text(tele.read_text() + "\nimport numpy\n")
    result = run_lint(str(pkg), REPO_ROOT)
    assert any(
        f.rule == "JF001" and f.file.endswith("track/telemetry.py")
        for f in result.findings
    ), [f.format() for f in result.findings]


# -- per-rule fixtures --------------------------------------------------------


def test_clean_fixture_is_quiet():
    result = run_lint(CLEAN)
    assert not result.findings, "\n".join(f.format() for f in result.findings)


@pytest.fixture(scope="module")
def dirty():
    return run_lint(DIRTY)


def test_dirty_fixture_fires_every_rule_family(dirty):
    assert _rules(dirty) == {
        "JF001", "JF002",
        "KN001", "KN002", "KN003", "KN004", "KN005", "KN006", "KN007",
        "TS001", "TS002",
        "CS001", "CS002", "CS003",
        "HP001", "HP002", "HP003",
        "OP001", "OP002", "OP003",
    }


def test_jaxfree_rules_fire_at_the_marked_module(dirty):
    by = _by_rule(dirty)
    (jf1,) = by["JF001"]
    assert jf1.file == "tpuframe/bad_stdlib.py" and "numpy" in jf1.message
    (jf2,) = by["JF002"]
    assert "tpuframe.heavy" in jf2.message


def test_knob_rules_name_the_right_knobs(dirty):
    by = _by_rule(dirty)
    assert "TPUFRAME_ORPHAN" in by["KN001"][0].message
    assert "TPUFRAME_DUP" in by["KN002"][0].message
    assert "TPUFRAME_DEAD" in by["KN003"][0].message
    assert "A_ENV_VARS" in by["KN004"][0].message
    assert {f.message.split("'")[1] for f in by["KN005"]} == {
        "TPUFRAME_DUP", "TPUFRAME_DEAD",
    }


def test_domain_rule_fires_on_undomained_lists(dirty):
    """The dirty fixture's knob lists carry no *_ENV_DOMAINS siblings —
    every one of them is a KN007 missing-domain finding."""
    by = _by_rule(dirty)
    assert any("_ENV_DOMAINS" in f.message for f in by["KN007"])


def test_domain_rule_entry_granularity(tmp_path):
    """KN007 at entry level: a knob without an entry, an invalid entry,
    and a stale entry for an undeclared knob each fire individually."""
    pkg = _clean_copy(tmp_path)
    (pkg / "spine.py").write_text(
        "import os\n"
        "S_ENV_VARS = (  # tpuframe-lint: not-shipped\n"
        "    'TPUFRAME_S_A', 'TPUFRAME_S_B',\n"
        ")\n"
        "S_ENV_DOMAINS = {\n"
        "    'TPUFRAME_S_A': {'type': 'int'},\n"  # no apply -> invalid
        # TPUFRAME_S_B has no entry at all
        "    'TPUFRAME_S_GONE': {'type': 'bool', 'apply': 'live'},\n"
        "}\n"
        "def reads():\n"
        "    return (os.environ.get('TPUFRAME_S_A'),\n"
        "            os.environ.get('TPUFRAME_S_B'))\n"
    )
    result = run_lint(str(pkg), str(tmp_path))
    msgs = [f.message for f in result.findings if f.rule == "KN007"]
    assert any("TPUFRAME_S_B" in m and "no entry" in m for m in msgs)
    assert any("TPUFRAME_S_A" in m and "invalid" in m for m in msgs)
    assert any("TPUFRAME_S_GONE" in m and "stale" in m for m in msgs)


def test_real_tree_domains_cover_every_knob():
    """The autotuner's contract: every declared knob on the real tree
    carries a valid domain (type + apply, range/choices where typed),
    and the inventory exposes it."""
    rows = knob_inventory(load_repo(REAL_PKG, REPO_ROOT))
    missing = [r["name"] for r in rows if r["lists"] and not r["domain"]]
    assert not missing
    by_name = {r["name"]: r for r in rows}
    ga = by_name["TPUFRAME_GRAD_ACCUM"]["domain"]
    assert ga["type"] == "int" and ga["apply"] == "restart"
    dt = by_name["TPUFRAME_LOADER_TRANSFER_DTYPE"]["domain"]
    assert tuple(dt["choices"]) == ("uint8", "float32")
    guard = by_name["TPUFRAME_AUTOTUNE_GUARD"]["domain"]
    assert guard["apply"] == "live" and tuple(guard["range"]) == (0.5, 1.0)


def test_schema_rules_fire_both_directions(dirty):
    by = _by_rule(dirty)
    assert "train/mystery" in by["TS001"][0].message
    ts2 = by["TS002"][0]
    assert "train/gone" in ts2.message and ts2.file == "OBSERVABILITY.md"
    assert ts2.line > 0  # anchored to the doc line that names it


def test_chaos_site_rules(dirty):
    by = _by_rule(dirty)
    assert "rogue" in by["CS001"][0].message
    assert "declared_unfired" in by["CS002"][0].message
    assert "undocumented_site" in by["CS003"][0].message


def test_hotpath_rules(dirty):
    by = _by_rule(dirty)
    assert "block_until_ready" in by["HP001"][0].message
    assert "traced value" in by["HP002"][0].message
    assert "batch" in by["HP003"][0].message
    # every HP finding lands in the hot-path seed module
    assert all(
        f.file == "tpuframe/train/step.py"
        for rule in ("HP001", "HP002", "HP003") for f in by[rule]
    )


def test_ops_registry_rules(dirty):
    by = _by_rule(dirty)
    # OP001 names the unregistered kernel module, anchored there
    (op1,) = by["OP001"]
    assert "rogue_kernel" in op1.message
    assert op1.file == "tpuframe/ops/rogue_kernel.py"
    # OP002/OP003 anchor at the stale registry row in registry.py
    (op2,) = by["OP002"]
    assert "test_listed.py" in op2.message
    assert op2.file == "tpuframe/ops/registry.py"
    (op3,) = by["OP003"]
    assert "fused_listed" in op3.message
    assert op3.file == "tpuframe/ops/registry.py"


def test_hotpath_negatives_stay_quiet():
    """The clean fixture exercises the idioms the rules must NOT flag:
    spanned syncs, static-attribute branching, state donation."""
    result = run_lint(CLEAN)
    assert not [f for f in result.findings if f.rule.startswith("HP")]


def test_hazard_graph_stops_at_stdlib_only_modules(tmp_path):
    """Regression: the syntactic call graph must not propagate
    traced-rootedness THROUGH stdlib-only modules.  A traced step that
    consults host-side config at trace time (env knobs, the kernel
    ledger) reaches stdlib-only code by name; that code contractually
    cannot hold tracers, so its own callees must not inherit hazard
    taint — without the boundary, every branch-on-value in pure host
    helpers lights up as HP002."""
    pkg = _clean_copy(tmp_path)
    (pkg / "hostcfg.py").write_text(
        '"""Host-side config consulted at trace time."""\n'
        "# tpuframe-lint: stdlib-only\n"
        "import os\n\n\n"
        "def _clampf(v):\n"
        "    scaled = v * 2.0  # derived value: the taint pass tracks it\n"
        "    if scaled > 3.0:  # host float branch: fine, it's host code\n"
        "        return 1.5\n"
        "    return v\n\n\n"
        "def gate_scale():\n"
        "    return _clampf(float(os.environ.get('APP_SCALE', '1')))\n"
    )
    step = pkg / "train" / "step.py"
    step.write_text(
        step.read_text().replace(
            "        loss = jnp.mean(x)\n",
            "        from tpuframe.hostcfg import gate_scale\n"
            "        loss = jnp.mean(x) * gate_scale()\n",
        )
    )
    result = run_lint(str(pkg), str(tmp_path))
    assert not [f for f in result.findings if f.rule.startswith("HP")], \
        "\n".join(f.format() for f in result.findings)

    # differential proof the boundary is load-bearing: drop the
    # stdlib-only contract and the same helper IS flagged
    cfg = pkg / "hostcfg.py"
    cfg.write_text(cfg.read_text().replace(
        "# tpuframe-lint: stdlib-only\n", ""))
    result = run_lint(str(pkg), str(tmp_path))
    assert any(
        f.rule == "HP002" and f.file.endswith("hostcfg.py")
        for f in result.findings
    ), "\n".join(f.format() for f in result.findings)


def _clean_copy(tmp_path):
    """A mutable copy of the clean fixture (tree + docs)."""
    pkg = tmp_path / "tpuframe"
    shutil.copytree(CLEAN, pkg)
    for doc in ("OBSERVABILITY.md", "FAULT.md", "SERVE.md", "PERF.md"):
        shutil.copy(os.path.join(FIXTURES, "clean", doc), tmp_path)
    return pkg


def test_with_suppress_import_still_counts_as_module_level(tmp_path):
    """`with contextlib.suppress(ImportError): import numpy` executes at
    import time — JF001 must see through the with-block."""
    pkg = _clean_copy(tmp_path)
    (pkg / "sneaky.py").write_text(
        "# tpuframe-lint: stdlib-only\nimport contextlib\n"
        "with contextlib.suppress(ImportError):\n    import numpy\n"
    )
    result = run_lint(str(pkg), str(tmp_path))
    assert any(f.rule == "JF001" and f.file == "tpuframe/sneaky.py"
               for f in result.findings)


def test_unrelated_bare_site_helper_is_not_a_chaos_firing(tmp_path):
    """A module's own `site(url)` helper must not register spurious chaos
    sites — bare-name firer calls count only when imported from
    fault.chaos."""
    pkg = _clean_copy(tmp_path)
    (pkg / "web.py").write_text(
        "def site(url):\n    return url\n\n"
        "x = site('https://example.com/page')\n"
    )
    result = run_lint(str(pkg), str(tmp_path))
    assert not [f for f in result.findings if f.rule.startswith("CS")]


def test_doctor_lint_section_survives_undecodable_file(tmp_path, monkeypatch):
    """One non-UTF8 file in the tree degrades the doctor's lint section
    to an error entry instead of crashing the whole report."""
    import tpuframe.doctor as doctor
    import tpuframe.lint.driver as driver

    pkg = _clean_copy(tmp_path)
    (pkg / "_stray.py").write_bytes("x = 'caf\xe9'\n".encode("latin-1"))
    orig = driver.load_repo
    monkeypatch.setattr(
        driver, "load_repo",
        lambda *a, **k: orig(str(pkg), str(tmp_path)),
    )
    sec = doctor.lint_section()
    assert "error" in sec and sec["cmd"] == "python -m tpuframe.lint --json"


# -- suppression semantics ----------------------------------------------------


def test_inline_disable_is_per_line(dirty):
    # TPUFRAME_WAIVED carries `# tpuframe-lint: disable=KN001` and must
    # be absorbed; TPUFRAME_ORPHAN (same rule, two lines up) must not be
    assert dirty.suppressed_count >= 1
    msgs = [f.message for f in dirty.findings]
    assert any("TPUFRAME_ORPHAN" in m for m in msgs)
    assert not any("TPUFRAME_WAIVED" in m for m in msgs)


def test_suppressions_file_semantics(tmp_path):
    supp = tmp_path / "supp.txt"
    supp.write_text(
        "# justified: fixture exercises the orphan-knob finding\n"
        "KN001:tpuframe/knobs.py:TPUFRAME_ORPHAN\n"
        "HP*:tpuframe/train/*.py\n"  # rule is exact-or-*; HP* matches nothing
    )
    result = run_lint(DIRTY, suppressions=str(supp))
    rules = _rules(result)
    assert "KN001" not in rules          # glob+substr entry absorbed it
    assert "HP001" in rules              # 'HP*' is not a rule id -> no match
    assert result.suppressed_count >= 2  # file entry + the inline disable

    wild = tmp_path / "wild.txt"
    wild.write_text("*:tpuframe/train/step.py\n")
    result = run_lint(DIRTY, suppressions=str(wild))
    assert not any(f.file == "tpuframe/train/step.py" for f in result.findings)

    with pytest.raises(ValueError):
        Suppressions.parse("just-a-rule-no-colon\n")


# -- CLI contract -------------------------------------------------------------


def test_cli_exit_codes_and_json_shape(capsys):
    assert lint_main(["--root", CLEAN]) == 0
    capsys.readouterr()

    assert lint_main(["--root", DIRTY, "--json"]) == 3
    out = json.loads(capsys.readouterr().out)
    assert set(out) == {"findings", "counts", "suppressed", "files_scanned",
                        "rules_run", "clean"}
    assert out["clean"] is False
    assert out["counts"]["KN001"] == 1
    f = out["findings"][0]
    assert set(f) == {"rule", "file", "line", "message", "hint"}

    assert lint_main(["--root", DIRTY, "--suppressions",
                      "/nonexistent/supp.txt"]) == 2


def test_cli_repo_default_is_clean(capsys):
    """`python -m tpuframe.lint` with no args on this checkout: exit 0."""
    assert lint_main(["--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["clean"] is True


# -- the --knobs registry seam ------------------------------------------------


def test_knob_inventory_shape(capsys):
    assert lint_main(["--root", CLEAN, "--knobs", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    rows = {r["name"]: r for r in out["knobs"]}
    tele = rows["TPUFRAME_TELEMETRY_DIR"]
    assert tele["lists"] == ["tpuframe.track.telemetry.OBSERVABILITY_ENV_VARS"]
    assert tele["shipped"] is True
    assert tele["reads"] and tele["docs"]
    rank = rows["TPUFRAME_PROCESS_ID"]
    assert rank["shipped"] is False  # contract list, not-shipped marker


def test_real_tree_inventory_is_reconciled():
    """On the real tree every knob has a declaring list — the input
    contract for the future core/config typed registry migration."""
    rows = knob_inventory(load_repo(REAL_PKG, REPO_ROOT))
    assert len(rows) >= 45
    undeclared = [r["name"] for r in rows if not r["lists"]]
    assert not undeclared
    # defaults are recovered where the read site had a parseable one
    by_name = {r["name"]: r for r in rows}
    assert by_name["TPUFRAME_HEALTH_WINDOW"]["defaults"] == [16]
