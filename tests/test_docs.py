"""The documents name files that exist.

Static (file text and `os.path`, no import of the package): for
`README.md`, `tests/README.md` and every file of `docs/`, each path in
backticks that can be resolved must resolve — a path from the
repository's root (`tests/test_serving.py`, `PERF.md`), a path inside the
package written from one of its sub-packages (`serving/server.py`,
`ops/`), or a bare `name.py` / `NAME.md` / `NAME.json` standing alone
between its backticks, which must be the name of some file in the tree
(inside a command line a bare name may be the reader's own file).  A
document that still sends its reader to a deleted script, record or
module fails here.
"""
import functools
import os
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = ROOT / "deepspeed_tpu"
DOCS = ["README.md", "tests/README.md"] + sorted(
    f"docs/{p.name}" for p in (ROOT / "docs").glob("*.md"))

_NOT_SOURCE = {".git", ".cache", "__pycache__", "chiprun_out", "_scratch"}
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"\w[\w.\-/*]*(?:\.py|\.md|\.jsonl?)|\w[\w.\-/]*/")
# bare names worth holding: scripts and modules, and the root's records
_BARE = re.compile(r"\w+\.py|[A-Z][A-Z0-9_]*\.(?:md|jsonl?)")


@functools.cache
def _basenames():
    names = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _NOT_SOURCE]
        names.update(files)
    return names


def _paths(span):
    """The checkable paths of one backticked span."""
    words = span.split()
    for word in words:
        # tests/test_x.py::test_y, serving/server.py:120, a trailing comma
        word = re.sub(r"(::.*|:\d[\d\-]*|[.,;:)]+)$", "", word.lstrip("("))
        if _PATH.fullmatch(word) and (
                "/" in word or (len(words) == 1 and _BARE.fullmatch(word))):
            yield word


def _missing(token):
    head = token.split("/", 1)[0]
    if "/" not in token:
        return token not in _basenames()
    if (ROOT / head).exists():
        base = ROOT
    elif (PACKAGE / head).is_dir():
        base = PACKAGE
    else:
        return False            # not a path of this repository
    return not (list(base.glob(token.rstrip("/"))) if "*" in token
                else (base / token).exists())


@pytest.mark.parametrize("doc", DOCS)
def test_paths_a_document_names_exist(doc):
    gone = sorted({
        path
        for span in _BACKTICKED.findall((ROOT / doc).read_text())
        for path in _paths(span)
        if _missing(path)})
    assert not gone, f"{doc} names paths that do not exist: {gone}"
