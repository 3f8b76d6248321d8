"""MLA005 — metrics-registry consistency.

The ``/metrics`` block is the repo's observable contract: BENCH
blocks, the router's fleet sums, the README/DESIGN tables, and a
dozen tests all navigate by counter NAME. Names are plain strings
assembled in four different places (app.py's snapshot block, the
router's relabeler, registry.counter calls, the LatencyStats summary
loop), so a rename — or a test asserting a counter that was never
exported — compiles fine and fails only at scrape time, or worse,
silently scrapes a key that is always absent.

Sets computed per run:

- **Exported**: string keys stored into ``snap["counters"]``/
  ``snap["gauges"]``; constant args of ``registry.counter(...)`` /
  ``registry.histogram(...)``; every metric-shaped string constant
  inside a function named ``metrics`` (the endpoint builders); plus
  the dynamic families — ``generate.<k>`` for each LatencyStats
  summary key (the f-string export loop), and the configured
  dynamic prefixes (``replica.``/``router.``/``http.`` — relabeled
  or route-labeled at runtime).
- **Scraped**: metric-shaped strings in tests/.
- **Documented**: metric-shaped tokens in README.md / DESIGN.md.

Checks: every scraped and every documented name must be satisfied by
the exported set — exactly, as a prefix of an exported name (tests
filter on prefixes like ``generate.sched_``), or under a dynamic
prefix. Findings anchor at the scrape/doc line, because that is
where the drift is fixable.
"""

from __future__ import annotations

import ast
import re

from tools.lint import Finding
from tools.lint.config import DYNAMIC_METRIC_PREFIXES, METRIC_NAME_RE
from tools.lint.rules import common

_NAME_RE = re.compile(METRIC_NAME_RE)
# `batcher.py::_collect_loop` / `router.py` are file references that
# happen to share a metric family's prefix — never metric names.
_FILE_LOOKALIKE_RE = re.compile(r"^\w+\.py(?:\b|$)")


def _metric_tokens(text: str):
    for name in _NAME_RE.findall(text):
        if not _FILE_LOOKALIKE_RE.match(name):
            yield name


def _string_constants(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node


def _exported_names(serving_files, latency_sf) -> set[str]:
    names: set[str] = set()
    for sf in serving_files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            # snap["counters"]["generate.x"] = ...
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.slice, ast.Constant)
                        and isinstance(t.slice.value, str)
                        and isinstance(t.value, ast.Subscript)
                        and isinstance(t.value.slice, ast.Constant)
                        and t.value.slice.value in ("counters", "gauges")
                    ):
                        names.add(t.slice.value)
            # registry.counter("x") / registry.histogram("x")
            if isinstance(node, ast.Call):
                chain = common.attr_chain(node.func)
                if (
                    chain
                    and chain[-1] in ("counter", "histogram")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    names.add(node.args[0].value)
            # any metric-shaped constant inside a `metrics` builder
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and node.name == "metrics":
                for const in _string_constants(node):
                    names.update(_metric_tokens(const.value))
    # Dynamic family: the f"generate.{k}" LatencyStats export loop.
    if latency_sf is not None and latency_sf.tree is not None:
        for node in ast.walk(latency_sf.tree):
            if (
                isinstance(node, ast.ClassDef)
                and node.name == "LatencyStats"
            ):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Dict):
                        for k in sub.keys:
                            if isinstance(k, ast.Constant) and (
                                isinstance(k.value, str)
                            ):
                                names.add(f"generate.{k.value}")
    return names


def _satisfied(name: str, exported: set[str]) -> bool:
    if name.startswith(DYNAMIC_METRIC_PREFIXES):
        return True
    if name in exported:
        return True
    # A scraped/documented PREFIX (family filters, README's
    # `generate.shed_` rows, brace shorthand truncated at `{`) is
    # satisfied by an exported name under it — but only at a real
    # name boundary (`_`, `.`, or a digit, the brace-expansion
    # shapes). Without the boundary check, a typo'd scrape that is a
    # strict character prefix of a real name (`...restore_hit` for
    # `...restore_hits`) would silently pass.
    for e in exported:
        if e.startswith(name):
            nxt = e[len(name)]
            if (
                name.endswith(("_", "."))
                or nxt in "_."
                or nxt.isdigit()
            ):
                return True
    return False


class MetricsRule:
    id = "MLA005"
    title = "scraped/documented metric names must be exported"

    def run(self, proj, cfg):
        serving = proj.matching(cfg.serving_prefix)
        exported = _exported_names(
            serving, proj.get(cfg.latency_stats_module)
        )
        if not exported:
            return []  # nothing exports metrics in this scan set
        findings: list[Finding] = []

        # Scrapes: tests.
        for sf in proj.matching(cfg.test_prefix):
            if sf.tree is None:
                continue
            seen: set[tuple[str, int]] = set()
            for const in _string_constants(sf.tree):
                for name in _metric_tokens(const.value):
                    key = (name, const.lineno)
                    if key in seen or _satisfied(name, exported):
                        continue
                    seen.add(key)
                    findings.append(Finding(
                        rule=self.id, file=sf.path, line=const.lineno,
                        message=(
                            f"scraped metric {name!r} matches no "
                            f"exported counter/gauge (and no exported "
                            f"name extends it) — the scrape reads a "
                            f"key that will never exist"
                        ),
                        symbol=sf.symbol_at(const.lineno),
                    ))
        # Docs: README / DESIGN tables must not drift.
        for path, text in proj.docs.items():
            for i, line in enumerate(text.splitlines(), 1):
                for name in _metric_tokens(line):
                    if _satisfied(name, exported):
                        continue
                    findings.append(Finding(
                        rule=self.id, file=path, line=i,
                        message=(
                            f"documented metric {name!r} matches no "
                            f"exported counter/gauge — the doc table "
                            f"has drifted from the code"
                        ),
                    ))
        return findings
