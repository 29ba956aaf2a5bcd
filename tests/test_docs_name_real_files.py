"""Every file a document names exists in the checkout.

README.md and docs/*.md cite the repo's files as evidence and as
how-to; a path that points at nothing sends the reader to a story the
tree no longer tells.  Checked from the repo root with os.path / glob:
the tests may run on a tree without .git."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md"))

_DIRS = ("paddle_tpu/", "tools/", "tests/", "benchmark/", "docs/")
_ROOT_NAME = re.compile(r"^[\w.*?\[\]-]+\.(py|json|md)$")
# names the documents give to files the program writes at run time
# (flight-bundle members, the HBM ledger's snapshot, the checkpoint's
# layout manifest) and to the user's own script: none is a file of the repo
_NOT_IN_THE_REPO = {"meta.json", "trace.json", "compilestats.json",
                    "memory.json", "layout.manifest.json", "train.py"}


def cited_paths(text):
    """The back-quoted repo paths of ``text``: those under a source
    directory, and root-level ``*.py`` / ``*.json`` / ``*.md`` names.
    ``path::test``, ``path:line`` and a command's arguments are cut."""
    for quoted in re.findall(r"`([^`\n]+)`", text):
        words = quoted.split()
        if not words:
            continue
        path = re.sub(r":[\d,-]+$", "", words[0].split("::")[0])
        path = path.rstrip(".,;:)")
        if path.startswith(_DIRS) or (_ROOT_NAME.match(path)
                                      and path not in _NOT_IN_THE_REPO):
            yield path


def test_the_reader_finds_paths_and_globs():
    text = ("see `tools/lint.py --passes x`, `tests/test_a.py::TestB`, "
            "`paddle_tpu/ops/registry.py:78`, `BENCH_r*.json`, `bench.py`, "
            "`meta.json`, `registry.flash_blocks`, `docs/`")
    assert list(cited_paths(text)) == [
        "tools/lint.py", "tests/test_a.py", "paddle_tpu/ops/registry.py",
        "BENCH_r*.json", "bench.py", "docs/"]


@pytest.mark.parametrize("doc", DOCS)
def test_every_cited_file_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        cited = sorted(set(cited_paths(f.read())))
    missing = [p for p in cited if not glob.glob(os.path.join(REPO, p))]
    assert not missing, f"{doc} names files that do not exist: {missing}"
