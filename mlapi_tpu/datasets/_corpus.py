"""The documentation corpus shared by the doc-driven datasets.

``docs_clf`` (the config-5 classification proxy) and ``docs_text``
(the LM / speculation anchors) read the SAME four prose files, and
both must default to the commit-pinned snapshot in ``docs_corpus/``
so their published numbers reproduce from a clean checkout — the live
repo docs grow every round, which silently sank the r04 docsclf
headline's held-out margin from ~0.19 to ~0.07 (VERDICT r04 weak #2).
This module is the ONE place that knows the file list, the snapshot
location, the flat-vs-repo layout fallback, and the provenance
string, so the two datasets cannot drift apart.
"""

from __future__ import annotations

from pathlib import Path

# The corpus files, in repo layout. The frozen snapshot stores each at
# the top level (flat); resolve_doc() tries both. The snapshot's
# manifest covers exactly these four names, so the list stays as it
# is; the repo root no longer holds a ``BASELINE.md``, and
# ``root="live"`` simply has no such class/file (both loaders skip an
# absent file).
DOC_SOURCES = (
    "README.md",
    "SURVEY.md",
    "BASELINE.md",
    "docs/DESIGN.md",
)


def repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


def frozen_corpus() -> Path:
    """The commit-pinned snapshot directory (provenance and sha256s in
    its ``MANIFEST.json``)."""
    return Path(__file__).resolve().parent / "docs_corpus"


def resolve_root(root: str | None) -> Path:
    """``None`` → the frozen snapshot; ``"live"`` → the repo's current
    (growing) docs; anything else → a user directory holding the
    corpus files (flat or repo-layout)."""
    if root is None:
        return frozen_corpus()
    if root == "live":
        return repo_root()
    return Path(root)


def resolve_doc(base: Path, rel: str) -> Path | None:
    """Find one corpus file under ``base``: repo layout first, then
    the flat layout the snapshot (and any user-supplied flat dir)
    uses. ``None`` when absent — callers decide whether a missing
    class/file is fatal."""
    p = base / rel
    if p.exists():
        return p
    flat = base / Path(rel).name
    if flat.exists():
        return flat
    return None


def live_markdown_docs(base: Path) -> list[Path]:
    """Every ``docs/*.md`` under ``base`` beyond ``DOC_SOURCES``,
    sorted by name.

    ``docs_text``'s live mode follows the repo's documentation as it
    GROWS: the pre-unification loader globbed ``docs/*.md``, and the
    shared ``DOC_SOURCES`` list (frozen-snapshot compatible) names
    only ``docs/DESIGN.md`` — without this, new design docs would
    silently drop out of live LM corpora (ADVICE r05 #2).
    ``docs_clf`` must NOT use this: its classes are the fixed
    ``DOC_SOURCES`` files, one label per file."""
    known = {Path(rel).name for rel in DOC_SOURCES}
    return sorted(
        p for p in (base / "docs").glob("*.md") if p.name not in known
    )


def corpus_provenance(base: Path) -> str:
    """The provenance string measurements carry in
    ``extras["corpus"]``: the frozen snapshot reports its pinned
    commit, anything else reports the path it read.

    A frozen claim is VERIFIED, not trusted, twice over (ADVICE r05
    #1):

    - the manifest must cover EXACTLY the ``DOC_SOURCES`` basenames —
      a foreign or empty ``MANIFEST.json`` (no ``files``, extra
      files, missing files) previously passed its per-file loop
      vacuously and labeled arbitrary user content ``frozen@?``; such
      a directory is just a user corpus and reports ``live:<path>``;
    - every covered file must hash to its recorded sha256, otherwise
      the published accuracies would silently stop reproducing while
      still reporting ``frozen@...`` — the exact failure mode the
      snapshot exists to eliminate. Corruption raises; it must not
      degrade to a quiet "live" label.
    """
    mf = base / "MANIFEST.json"
    if not mf.exists():
        return f"live:{base}"
    import hashlib
    import json

    manifest = json.loads(mf.read_text())
    files = manifest.get("files", {})
    if set(files) != {Path(rel).name for rel in DOC_SOURCES}:
        # Not OUR snapshot manifest — whatever wrote it, this dir's
        # contents are unpinned as far as the framework is concerned.
        return f"live:{base}"
    for name, meta in files.items():
        p = base / name
        digest = (
            hashlib.sha256(p.read_bytes()).hexdigest()
            if p.exists() else "<missing>"
        )
        if digest != meta.get("sha256"):
            raise ValueError(
                f"frozen corpus snapshot is corrupted: {name} hashes "
                f"to {digest[:12]}…, MANIFEST.json records "
                f"{str(meta.get('sha256'))[:12]}… — restore "
                f"datasets/docs_corpus/ from git before trusting any "
                f"measurement"
            )
    commit = manifest.get("source_commit", "?")
    return f"frozen@{commit}"
