"""The documents describe the tree that holds them.

A document that names a file the tree no longer has, or a switch no
code reads, teaches its reader another system.  Each case below is one
document and one check; the sources of truth are the tracked files.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "ARCHITECTURE.md", "PARITY.md", "artifacts/README.md")

_BACKTICKED = re.compile(r"`([^`\n]+)`")
_PREFIXED = re.compile(
    r"(?<![\w./-])((?:eksml_tpu|tools|tests|benchmark|charts|infra"
    r"|container[\w-]*)/[\w./-]+\.(?:py|sh|json|yaml|tf))\b")
_BARE = re.compile(r"(?<![\w./<>{}*-])([\w-]+\.(?:py|sh))\b")
_VARIABLE = re.compile(r"\bEKSML_[A-Z_]+")

# bare names that are not this tree's: files of the reference repository
# that PARITY.md sets this tree's equivalents beside
REFERENCE_REPO_FILES = {"set-cluster.sh"}

# where a variable a document names has to be read
_READERS = ("eksml_tpu/", "tools/", "container", "chip_smoke.py")


def _spans(doc):
    """What the document sets as code: inline spans, and the lines of
    its fenced blocks (the commands it tells its reader to run)."""
    spans, fenced = [], False
    with open(os.path.join(REPO, doc)) as f:
        for line in f:
            if line.lstrip().startswith("```"):
                fenced = not fenced
            elif fenced:
                spans.append(line.rstrip("\n"))
            else:
                spans += _BACKTICKED.findall(line)
    return spans


@pytest.mark.parametrize("doc", DOCS)
def test_directory_prefixed_paths_exist(doc, tracked_files):
    """Anywhere in the text: a path with its directory is a claim about
    this tree whether or not it is set as code."""
    with open(os.path.join(REPO, doc)) as f:
        named = set(_PREFIXED.findall(f.read()))
    missing = sorted(p for p in named if p not in tracked_files)
    assert not missing, f"{doc} names files the tree does not hold"


@pytest.mark.parametrize("doc", DOCS)
def test_bare_script_names_are_tracked_files(doc, tracked_files):
    basenames = {os.path.basename(p) for p in tracked_files}
    named = {m for span in _spans(doc) for m in _BARE.findall(span)}
    missing = sorted(named - basenames - REFERENCE_REPO_FILES)
    assert not missing, f"{doc} names scripts the tree does not hold"


@pytest.fixture(scope="module")
def variables_read(tracked_files):
    read = set()
    for path in tracked_files:
        if path.startswith(_READERS):
            with open(os.path.join(REPO, path), errors="ignore") as f:
                read.update(_VARIABLE.findall(f.read()))
    return read


@pytest.mark.parametrize("doc", DOCS)
def test_named_variables_are_read(doc, variables_read):
    """A name that ends in ``_`` (``EKSML_TRACE_*``) stands for a
    family: some variable that is read begins with it."""
    with open(os.path.join(REPO, doc)) as f:
        named = set(_VARIABLE.findall(f.read()))
    unread = sorted(
        v for v in named
        if not (any(r.startswith(v) for r in variables_read)
                if v.endswith("_") else v in variables_read))
    assert not unread, f"{doc} names variables nothing reads"


def _as_regex(pattern):
    """``bench_rung_{512_b1,1344_b4}.json``, ``serve_r{N}.json``,
    ``perf_pred_<rung>_<precision>.json``, ``roi_*.json`` -> a regex."""
    out = []
    for piece in re.split(r"(\{[^}]*\}|<[^>]*>|\*)", pattern):
        if piece.startswith("{") and "," in piece:
            out.append("(?:%s)" % "|".join(
                re.escape(p) for p in piece[1:-1].split(",")))
        elif piece.startswith(("{", "<")) or piece == "*":
            out.append(".+")
        else:
            out.append(re.escape(piece))
    return re.compile("".join(out))


def test_artifacts_table_and_directory_name_the_same_files(tracked_files):
    rows = {}
    with open(os.path.join(REPO, "artifacts/README.md")) as f:
        for line in f:
            if line.startswith("| `"):
                for name in _BACKTICKED.findall(line.split("|")[1]):
                    rows[name] = _as_regex(name)
    assert rows, "artifacts/README.md lost its table"
    files = sorted(os.path.basename(p) for p in tracked_files
                   if p.startswith("artifacts/")
                   and p != "artifacts/README.md")
    unlisted = [f for f in files
                if not any(r.fullmatch(f) for r in rows.values())]
    assert not unlisted, "files the table does not name"
    absent = [name for name, r in rows.items()
              if not any(r.fullmatch(f) for f in files)]
    assert not absent, "rows that name no file of the tree"
