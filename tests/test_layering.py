"""The package graph points one way.

``utils, text < parallel < ops < models < checkpoint, datasets, config
< train, serving``: a package imports only packages of a lower tier.
The trainer and the server are peers: the order would let the server
import the trainer, but what both need belongs below both, so neither
imports the other. An AST walk over every ``import`` of a package (the
lazy ones inside functions too) holds it to that. ``__main__.py``
files are composition roots: they may reach anywhere.

The upward edges the tree still has are listed in ``KNOWN_UPWARD`` by
file and target, each under the ROADMAP debt that owns it. A listed
edge that no longer exists fails the test, so the list only shrinks.
"""

import ast
from pathlib import Path

import pytest

PACKAGE = Path(__file__).resolve().parents[1] / "mlapi_tpu"

TIERS = (
    ("utils", "text"),
    ("parallel",),
    ("ops",),
    ("models",),
    ("checkpoint", "datasets", "config"),
    ("train", "serving"),
)
TIER_OF = {name: i for i, tier in enumerate(TIERS) for name in tier}

# (files, imported module, the debt that owns the edge)
KNOWN_UPWARD = (
    (
        ("ops/speculative.py",),
        "mlapi_tpu.models.gpt",
        "D15: the library loop builds on the program factories of "
        "models/gpt.py; move it beside them",
    ),
    (
        ("parallel/mesh.py", "parallel/layout.py"),
        "mlapi_tpu.ops.quant",
        "D15: the quant-leaf predicate; give parallel/ one without "
        "importing ops.quant",
    ),
)


def _imports(path: Path):
    """Every ``mlapi_tpu`` module a file imports. ``from mlapi_tpu
    import x`` counts as ``mlapi_tpu.x``."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"relative import in {path}"
            if node.module == "mlapi_tpu":
                names = [f"mlapi_tpu.{a.name}" for a in node.names]
            else:
                names = [node.module]
        else:
            continue
        yield from (n for n in names if n.startswith("mlapi_tpu."))


def _upward_edges(package: str) -> set:
    """``(file, module)`` for each import in ``package`` of a package
    that is not of a lower tier."""
    edges = set()
    for path in sorted((PACKAGE / package).rglob("*.py")):
        if path.name == "__main__.py":
            continue
        rel = path.relative_to(PACKAGE).as_posix()
        for module in _imports(path):
            target = module.split(".")[1]
            if target == package or target not in TIER_OF:
                continue
            if TIER_OF[target] >= TIER_OF[package]:
                edges.add((rel, module))
    return edges


@pytest.mark.parametrize("package", [
    "utils", "text", "parallel", "ops", "models", "checkpoint",
    "datasets", "train", "serving",
])
def test_package_imports_only_lower_tiers(package):
    listed = {
        (f, module)
        for files, module, _ in KNOWN_UPWARD
        for f in files
        if f.startswith(package + "/")
    }
    found = _upward_edges(package)
    assert found - listed == set(), (
        f"{package} imports a package that is not below it; move the "
        f"code down, or the import into a __main__.py"
    )
    assert listed - found == set(), (
        "a listed upward edge is gone: take it out of KNOWN_UPWARD"
    )
