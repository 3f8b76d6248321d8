"""mlapi-lint as a tier-1 gate (tools/lint/, r16).

Three layers, mirroring what the linter promises:

- **Clean tree.** ``run_rules`` over the real repo config reports
  zero unsuppressed findings — the invariants the rules encode
  (donation discipline, lock discipline, seam ordering/coverage,
  router purity, metrics consistency, test hygiene, and the r19
  concurrency layer: lock-order acyclicity, thread-context
  placement, terminal-frame wait discipline) HOLD on the current
  tree, and any PR that breaks one fails here with a ``file:line``.
  The MLA007 artifact (``tools/lint/lockorder.json``) is
  additionally pinned byte-for-byte — the runtime witness loads it,
  so staleness would enforce a stale order.
- **Fixtures.** Each rule is negative-tested against a minimal repro
  of the historical bug it mechanizes (``tests/lint_fixtures/``,
  one module per rule). The contract is exact: the finding set must
  EQUAL the ``# EXPECT(MLA0xx)`` marker set — every marked line
  flagged, nothing else flagged — so both missed detections and
  false positives fail.
- **Machinery.** Inline suppressions and the baseline file require
  justifications, stale baseline entries fail loudly, the CLI exits
  0/1/2, ``--format=github`` emits Actions annotations, and the
  whole run never imports jax (pure AST — the property that keeps it
  <15 s and CI-anywhere).

The lint fixtures are EXCLUDED from the clean-tree scan (they are
deliberate violations) and are not collected by pytest (no ``test_``
file prefix).
"""

from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from tools.lint import load_project, run_rules  # noqa: E402
from tools.lint.baseline import (  # noqa: E402
    SuppressionError,
    apply_suppressions,
)
from tools.lint.config import Config  # noqa: E402

FIXTURES = "tests/lint_fixtures/"

_EXPECT_RE = re.compile(r"EXPECT\((MLA\d{3}(?:\s*,\s*MLA\d{3})*)\)")


def fixture_config(**overrides) -> Config:
    base = dict(
        root=REPO_ROOT,
        py_globs=(f"{FIXTURES}**/*.py",),
        exclude_prefixes=(),
        faults_module=f"{FIXTURES}prod/fx_faults.py",
        latency_stats_module=f"{FIXTURES}prod/fx_app.py",
        production_prefix=f"{FIXTURES}prod/",
        serving_prefix=f"{FIXTURES}prod/",
        test_prefix=f"{FIXTURES}t/",
        doc_files=(f"{FIXTURES}fx_docs.md",),
        async_pure_modules=(f"{FIXTURES}prod/fx_router.py",),
        baseline_file=f"{FIXTURES}fx_baseline.txt",
    )
    base.update(overrides)
    return Config(**base)


def expected_markers(proj) -> set[tuple[str, int, str]]:
    """(file, line, rule) for every EXPECT marker in the fixture
    set — python comments and doc-file lines alike."""
    out: set[tuple[str, int, str]] = set()
    for sf in proj.files:
        for line_no, comment in sf.comments.items():
            m = _EXPECT_RE.search(comment)
            if m:
                for rule in re.split(r"\s*,\s*", m.group(1)):
                    out.add((sf.path, line_no, rule))
    for path, text in proj.docs.items():
        for i, line in enumerate(text.splitlines(), 1):
            m = _EXPECT_RE.search(line)
            if m:
                for rule in re.split(r"\s*,\s*", m.group(1)):
                    out.add((path, i, rule))
    return out


# --- the gate: the real tree is clean ---------------------------------


def test_clean_tree_zero_findings():
    cfg = Config()
    proj = load_project(cfg)
    findings = run_rules(proj, cfg)
    reported, _ = apply_suppressions(proj, cfg, findings)
    assert reported == [], "\n" + "\n".join(
        f.render() for f in reported
    )


# --- fixtures: every rule catches its historical bug exactly ----------


def test_fixtures_flag_exactly_the_marked_lines():
    cfg = fixture_config()
    proj = load_project(cfg)
    assert len(proj.files) >= 7, "fixture set went missing"
    findings = run_rules(proj, cfg)
    reported, suppressed = apply_suppressions(proj, cfg, findings)
    # No duplicate reports: each violation is charged to exactly one
    # frame (the nested-closure double-report class).
    keys = [(f.rule, f.file, f.line, f.message) for f in reported]
    assert len(keys) == len(set(keys)), "duplicate findings"
    got = {(f.rule, f.file, f.line) for f in reported}
    want = {(r, f, ln) for (f, ln, r) in expected_markers(proj)}
    missed = want - got
    extra = got - want
    assert not missed, f"rules MISSED marked repros: {sorted(missed)}"
    assert not extra, f"rules over-flagged (false positives): {sorted(extra)}"
    # Every rule has at least one fixture repro.
    assert {r for (r, _, _) in got} == {
        "MLA001", "MLA002", "MLA003", "MLA004", "MLA005", "MLA006",
        "MLA007", "MLA008", "MLA009",
    }
    # Both suppression paths were exercised: the inline allow and the
    # baseline entry each swallowed one fx_locks violation.
    sup = {(f.rule, f.symbol) for f in suppressed}
    assert ("MLA002", "PagePool.allowed_bump") in sup
    assert ("MLA002", "PagePool.baselined_bump") in sup


def test_stale_baseline_entry_fails_loudly(tmp_path):
    stale = tmp_path / "baseline.txt"
    stale.write_text(
        "MLA002 tests/lint_fixtures/prod/fx_locks.py::PagePool.gone "
        "-- excuses code that no longer exists\n"
    )
    cfg = fixture_config(baseline_file=str(stale))
    proj = load_project(cfg)
    findings = run_rules(proj, cfg)
    try:
        apply_suppressions(proj, cfg, findings)
    except SuppressionError as e:
        assert "stale" in str(e)
    else:
        raise AssertionError("stale baseline entry was not rejected")


def test_baseline_requires_justification(tmp_path):
    bad = tmp_path / "baseline.txt"
    bad.write_text(
        "MLA002 tests/lint_fixtures/prod/fx_locks.py::PagePool.x --\n"
    )
    cfg = fixture_config(baseline_file=str(bad))
    proj = load_project(cfg)
    try:
        apply_suppressions(proj, cfg, run_rules(proj, cfg))
    except SuppressionError as e:
        assert "malformed" in str(e)
    else:
        raise AssertionError("justification-less entry was accepted")


# --- MLA007 artifact ---------------------------------------------------


def test_lockorder_artifact_roundtrip():
    """The committed tools/lint/lockorder.json IS the freshly
    recomputed graph — byte-for-byte. A PR that changes lock scopes
    without regenerating the artifact fails here (the runtime
    witness loads this file as the allowed order, so a stale file
    would enforce a stale order)."""
    from tools.lint.rules.lockorder import render_artifact

    cfg = Config()
    proj = load_project(cfg)
    committed = (REPO_ROOT / cfg.lockorder_artifact).read_text()
    assert committed == render_artifact(proj, cfg), (
        "lockorder.json is stale — regenerate: python -m tools.lint "
        "--lockorder-out tools/lint/lockorder.json"
    )


def test_lockorder_artifact_is_cycle_free_with_total_order():
    import json

    from tools.lint.rules.lockorder import find_cycles

    doc = json.loads(
        (REPO_ROOT / Config().lockorder_artifact).read_text()
    )
    edges = {(e["before"], e["after"]) for e in doc["edges"]}
    assert find_cycles(edges) == []
    # Acyclic graphs always emit a usable total order, and it must
    # respect every edge.
    order = doc["order"]
    assert order is not None
    pos = {n: i for i, n in enumerate(order)}
    for a, b in edges:
        assert pos[a] < pos[b], (a, b)
    # The edge the serving stack actually carries (drop_entry under
    # the prefix registry lock) is present — the graph is not
    # vacuously empty.
    assert ("PrefixCache", "PagePool") in edges


def test_find_cycles_unit():
    from tools.lint.rules.lockorder import find_cycles

    assert find_cycles({("A", "B"), ("B", "A")}) == [["A", "B"]]
    assert find_cycles({("A", "A")}) == [["A"]]
    assert find_cycles({("A", "B"), ("B", "C")}) == []


# --- CLI + purity ------------------------------------------------------


def test_cli_exit_codes_and_jax_purity(tmp_path):
    """The CI entry point: ``python -m tools.lint`` exits 0 on the
    clean tree, the ``--rule`` filter and ``--lockorder-out`` flag
    work, and the analysis never imports jax (pure AST — the <15 s
    CPU-only property). One subprocess checks all of it."""
    out = tmp_path / "lockorder.json"
    code = (
        "import sys\n"
        "from pathlib import Path\n"
        "from tools.lint.__main__ import main\n"
        "rc = main([])\n"
        "assert rc == 0, f'lint reported findings: rc={rc}'\n"
        "rc = main(['--rule', 'MLA007', '--lockorder-out', "
        f"{str(out)!r}])\n"
        "assert rc == 0, f'MLA007 reported findings: rc={rc}'\n"
        f"assert 'PrefixCache' in Path({str(out)!r}).read_text()\n"
        "assert main(['--rule', 'MLA999']) == 2\n"
        "assert 'jax' not in sys.modules, 'linter imported jax'\n"
        "print('LINT_OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "LINT_OK" in proc.stdout


def test_github_annotation_format():
    from tools.lint import Finding

    f = Finding(
        rule="MLA002", file="mlapi_tpu/serving/x.py", line=7,
        message="boom", symbol="C.m",
    )
    assert f.render_github() == (
        "::error file=mlapi_tpu/serving/x.py,line=7,title=MLA002::boom"
    )
    assert "x.py:7" in f.render()
