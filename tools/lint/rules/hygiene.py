"""MLA006 — tier-1 test hygiene: no wall-clock assertions.

The ADVICE r05 flake class: a test that asserts on ELAPSED TIME
(``assert elapsed < 1.0``) encodes the speed of one machine into a
correctness suite that runs on a drifting shared box — the r14/r15
tier-1 runs brushed the 870 s window for exactly that kind of
environmental reason. The repo's documented alternative is counter-
based asserts (engine/scheduler counters, fault counts, trace
contents), which are deterministic at any machine speed.

Flags, in tier-1 test files (functions NOT marked ``slow`` or
``heavy`` — soak tests may time themselves):

- an ``assert`` whose comparison reads a wall-clock source directly
  (``time.time()``, ``time.perf_counter()``, ``time.monotonic()``,
  ``loop.time()``), or
- an ``assert`` whose comparison reads a variable assigned from an
  expression containing such a call (one-level lexical taint — the
  ``t0 = perf_counter(); ...; assert loop.time() - t0 < X`` shape
  and its named-elapsed variants).

Wait bounds stay legal: bounding how long a test WAITS is fine,
asserting how long the code TOOK is the flake. Lexically, a wait
bound compares a clock against a clock-derived deadline
(``assert loop.time() < deadline`` where ``deadline = loop.time() +
10``) — BOTH sides clock-tainted — while the flake shape compares a
clock-derived elapsed against a plain constant (``assert elapsed <
1.0``). Only the mixed comparison is flagged.
"""

from __future__ import annotations

import ast

from tools.lint import Finding
from tools.lint.rules import common

_CLOCK_ATTRS = frozenset({"time", "perf_counter", "monotonic",
                          "process_time"})
_EXEMPT_MARKS = ("slow", "heavy")


def _is_clock_call(node) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _CLOCK_ATTRS:
        return True
    if isinstance(f, ast.Name) and f.id in _CLOCK_ATTRS:
        return True  # from time import perf_counter
    return False


def _module_exempt(tree) -> bool:
    """Module-level ``pytestmark`` includes slow/heavy."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "pytestmark":
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Attribute) and (
                            sub.attr in _EXEMPT_MARKS
                        ):
                            return True
    return False


class TestHygieneRule:
    id = "MLA006"
    title = "no wall-clock assertions outside slow/heavy tests"

    def run(self, proj, cfg):
        findings: list[Finding] = []
        for sf in proj.files:
            if not sf.path.startswith(cfg.test_prefix) or sf.tree is None:
                continue
            if _module_exempt(sf.tree):
                continue
            for func in sf.tree.body:
                if not isinstance(
                    func, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                marks = common.decorator_names(func)
                if any(
                    m.endswith(f"mark.{x}")
                    for m in marks for x in _EXEMPT_MARKS
                ):
                    continue
                findings.extend(self._check_function(sf, func))
        return findings

    def _check_function(self, sf, func):
        tainted: set[str] = set()
        for node in ast.walk(func):
            if isinstance(node, ast.Assign) and any(
                _is_clock_call(sub) for sub in ast.walk(node.value)
            ):
                for t in node.targets:
                    els = t.elts if isinstance(t, ast.Tuple) else [t]
                    for el in els:
                        if isinstance(el, ast.Name):
                            tainted.add(el.id)
        findings = []
        for node in ast.walk(func):
            if not isinstance(node, ast.Assert):
                continue
            hit = self._wallclock_compare(node.test, tainted)
            if hit:
                findings.append(Finding(
                    rule=self.id, file=sf.path, line=node.lineno,
                    message=(
                        f"wall-clock assertion ({hit}) in a tier-1 "
                        f"test — encodes one machine's speed; assert "
                        f"on engine/scheduler counters instead, or "
                        f"mark the test slow/heavy (ADVICE r05 flake "
                        f"class)"
                    ),
                    symbol=sf.symbol_at(node.lineno),
                ))
        return findings

    @staticmethod
    def _wallclock_compare(test, tainted) -> str | None:
        def side_taint(expr) -> str | None:
            for sub in ast.walk(expr):
                if _is_clock_call(sub):
                    return "a clock read"
                if isinstance(sub, ast.Name) and sub.id in tainted:
                    return f"`{sub.id}` (assigned from a clock)"
            return None

        for node in ast.walk(test):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left, *node.comparators]
            taints = [side_taint(s) for s in sides]
            hits = [t for t in taints if t is not None]
            # All-sides-tainted = a wait bound (clock vs clock-derived
            # deadline): legal. Mixed = elapsed-vs-constant: the flake.
            if hits and len(hits) < len(sides):
                return f"compares {hits[0]} against a plain bound"
        return None
