"""The living documents name only what exists.

Two checks over each root document a user or a later builder is sent
to (histories are exempt: CHANGES.md, ROADMAP.md, SURVEY.md, PAPER.md,
PAPERS.md, SNIPPETS.md record what was):

(a) every ``TPUDL_*`` name in the text is a declared knob
    (:data:`tpudl.analysis.knobs.KNOB_NAMES`); a name ending in ``_``
    (``TPUDL_FLIGHT_*``) is a family and must be some knob's prefix;
(b) every back-ticked word ending in ``.py``, ``.sh`` or ``.cpp``, a
    ``:line`` or ``::test`` suffix stripped, is a file of this tree:
    the word is the whole path from the root, or the tail of one
    (``zoo/moe.py``, ``lm_train.py``). Placeholders and globs
    (``configs/<name>.py``, ``*.py``) are skipped.

A document that fails is mended; the patterns stay.
"""

import os
import re

import pytest

from tpudl.analysis import KNOB_NAMES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PERF.md", "ANALYSIS.md", "COMPILE.md",
             "CONCURRENCY.md", "DATA.md", "FAULTS.md", "JOBS.md",
             "OBSERVABILITY.md", "PIPELINE.md", "SERVE.md", "TEXT.md"]

_KNOB = re.compile(r"TPUDL_[A-Z0-9_]+")
_SPAN = re.compile(r"`([^`\n]+)`")
_FILE = re.compile(r"^([\w./\-]+\.(?:py|sh|cpp))(?::.*)?$")


def _read(name):
    with open(os.path.join(REPO, name), encoding="utf-8") as f:
        return f.read()


@pytest.fixture(scope="module")
def tree_files():
    """Every file of the checkout as ``/``-joined path from the root;
    hidden directories (``.git``, the builder's scratch trees) and the
    chip tool's output are no part of it."""
    out = []
    for base, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        rel = os.path.relpath(base, REPO).replace(os.sep, "/")
        out.extend(f if rel == "." else f"{rel}/{f}" for f in files)
    return out


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_knobs_named_are_declared(doc):
    unknown = sorted(
        name for name in set(_KNOB.findall(_read(doc)))
        if name not in KNOB_NAMES
        and not (name.endswith("_")
                 and any(k.startswith(name) for k in KNOB_NAMES)))
    assert unknown == [], f"{doc} names knobs no code reads: {unknown}"


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_files_named_exist(doc, tree_files):
    named = set()
    for span in _SPAN.findall(_read(doc)):
        for word in span.split():
            m = _FILE.match(word.strip("()[],;\"'"))
            if m:
                named.add(m.group(1).lstrip("./"))
    missing = sorted(
        path for path in named
        if not any(f == path or f.endswith("/" + path)
                   for f in tree_files))
    assert missing == [], f"{doc} names files that are not there: {missing}"
