"""The documents an owner reads first name only what exists.

For ``README.md`` and the verify skill: every backticked path is in
the tree, and every ``--flag`` shown on a ``python -m mlapi_tpu.train``
or ``python -m mlapi_tpu.serving`` command line is an option of that
parser. A document that cites a deleted file or flag as evidence is
worse than one that cites nothing.
"""

import ast
import functools
import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
DOCS = ("README.md", ".claude/skills/verify/SKILL.md")
SUFFIXES = (".py", ".md", ".json")


def _ignored_dirs() -> tuple:
    """The directories ``.gitignore`` lists: what building, testing
    and running leave behind is neither searched nor expected."""
    lines = (ROOT / ".gitignore").read_text().splitlines()
    return tuple(ln.strip() for ln in lines if ln.strip().endswith("/"))


@functools.cache
def _tree():
    """``(relative paths of every file and directory, file names)``
    under the root, ignored directories pruned."""
    ignored = _ignored_dirs() + (".git/",)
    paths, names = set(), set()
    for here, dirs, files in os.walk(ROOT):
        rel = Path(here).relative_to(ROOT)
        dirs[:] = [
            d for d in dirs
            if f"{d}/" not in ignored
            and f"{(rel / d).as_posix()}/" not in ignored
        ]
        paths.update((rel / name).as_posix() for name in dirs + files)
        names.update(files)
    return paths, names


def _prose(text: str) -> str:
    """The document without its fenced blocks (a fence's backticks
    would pair with the prose's)."""
    return re.sub(r"^```.*?^```", "", text, flags=re.S | re.M)


def _path_tokens(text: str, top_dirs: tuple):
    """Backticked words that read as paths: they start with a
    top-level directory or end in a source suffix. Routes (a leading
    ``/``), flags, globs and placeholders are not paths."""
    for span in re.findall(r"`([^`\n]+)`", _prose(text)):
        for word in span.split():
            word = word.split("::")[0].strip("(),;:'\"")
            word = re.sub(r":\d+(-\d+)?$", "", word)
            if (not word or word[0] in "/-"
                    or re.search(r"[<>*{}$…=]|\.\.\.", word)):
                continue
            if word.startswith(top_dirs) or word.endswith(SUFFIXES):
                yield word


@pytest.mark.parametrize("doc", DOCS)
def test_every_backticked_path_exists(doc):
    paths, names = _tree()
    # Directories of the root and of the package: ``serving/engine.py``
    # is how the documents name ``mlapi_tpu/serving/engine.py``.
    top_dirs = tuple(
        f"{d.name}/" for base in (ROOT, ROOT / "mlapi_tpu")
        for d in base.iterdir()
        if d.is_dir() and d.relative_to(ROOT).as_posix() in paths
    )
    ignored = _ignored_dirs()
    missing = []
    for token in sorted(set(_path_tokens((ROOT / doc).read_text(), top_dirs))):
        clean = token.rstrip("/")
        if f"{clean}/".startswith(ignored):
            continue
        found = (
            clean in paths
            or f"mlapi_tpu/{clean}" in paths
            or ("/" not in clean and clean in names)
        )
        if not found:
            missing.append(token)
    assert missing == [], f"{doc} names paths that are not in the tree"


def _parser_flags(module: str) -> set:
    """The ``--options`` a CLI's ``__main__.py`` adds to its parser,
    read from the source: nothing is imported or run."""
    source = ROOT / "mlapi_tpu" / module / "__main__.py"
    flags = {"--help"}
    for node in ast.walk(ast.parse(source.read_text())):
        if (isinstance(node, ast.Call)
                and getattr(node.func, "attr", None) == "add_argument"):
            flags.update(
                a.value for a in node.args
                if isinstance(a, ast.Constant) and isinstance(a.value, str)
                and a.value.startswith("--")
            )
    return flags


@pytest.mark.parametrize("doc", DOCS)
def test_every_flag_on_a_cli_line_is_an_option(doc):
    text = (ROOT / doc).read_text().replace("\\\n", " ")
    known = {m: _parser_flags(m) for m in ("train", "serving")}
    unknown = []
    for line in text.splitlines():
        for module, rest in re.findall(
            r"python -m mlapi_tpu\.(train|serving)\b([^|;&`]*)", line
        ):
            unknown += [
                f"{module} {flag}"
                for flag in re.findall(r"(?<![\w-])--[a-z][\w-]*", rest)
                if flag not in known[module]
            ]
    assert unknown == [], f"{doc} shows flags the parser does not take"
