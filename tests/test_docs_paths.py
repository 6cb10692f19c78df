"""README.md and docs/*.md name only files that exist.

Every token of a document that looks like a path of this repository has
to resolve under the checkout, so that deleting a file takes the
sentences that send a reader to it along.  Pure file reading: no jax.
"""
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

_EXT = r"\.(?:py|md|json|jsonl|toml|txt|cc|cpp|h|sh)"
_TOP = r"(?:tools|paddle_tpu|paddle|benchmark|docs|tests|examples)"
# `dir/.../name.ext` anywhere in the text, and a directory under one of
# the repository's top-level packages (`benchmark/references/`)
_SLASHED = re.compile(
    rf"(?<![\w/.:~-])((?:[\w.-]+/)+[\w.-]+{_EXT}|{_TOP}/(?:[\w.-]+/)*)"
    r"(?![\w/-])")
# a bare `name.ext` counts only inside backticks: prose is full of
# dotted words that are no files
_TICKED = re.compile(r"`([^`\n]+)`")
_BARE = re.compile(rf"^[\w.-]+{_EXT}$")
_LINK = re.compile(r"\]\(([^)#\s]+)(?:#[^)\s]*)?\)")

# what building, testing and running leave behind (.gitignore)
_GENERATED = (".jax_cache/", "chiprun_out/", ".bench_out/", ".export/")


@functools.cache
def _package_files():
    """Every file under paddle_tpu/, as a '/'-joined path from the root."""
    return frozenset(
        os.path.relpath(os.path.join(here, f), ROOT).replace(os.sep, "/")
        for here, _, files in os.walk(os.path.join(ROOT, "paddle_tpu"))
        for f in files)


def _resolves(token, doc):
    """A token names a file when it is a path from the root, from the
    document's own directory, or the tail of a file of the package (the
    docs write `ops/pallas/flash_attention.py`, `pipeline.py`)."""
    return (os.path.exists(os.path.join(ROOT, token))
            or os.path.exists(os.path.join(ROOT, os.path.dirname(doc), token))
            or any(p.endswith("/" + token) for p in _package_files()))


def _tokens(text):
    for m in _SLASHED.finditer(text):
        yield m.group(1)
    for span in _TICKED.findall(text):
        for piece in span.split():
            piece = piece.strip(",;:()")
            if _BARE.match(piece):
                yield piece
    for m in _LINK.finditer(text):
        if "://" not in m.group(1):
            yield m.group(1)


def _skipped(token):
    return (any(c in token for c in "<*{")
            or any(g in token for g in _GENERATED))


@pytest.fixture(autouse=True)
def _seed():
    """In conftest's place: nothing here needs a backend or a seed."""
    yield


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_files_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    tokens = sorted({t for t in _tokens(text) if not _skipped(t)})
    assert tokens, f"{doc}: the patterns found no path at all"
    missing = [t for t in tokens if not _resolves(t, doc)]
    assert not missing, f"{doc} names files that are not there: {missing}"
