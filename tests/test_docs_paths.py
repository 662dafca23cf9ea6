"""A document names only files that exist: every back-ticked token of
README.md, PERF.md and docs/*.md that is written as a path into this
repository is found on disk, and a bare `name.py` / `.json` / `.jsonl` /
`.md` is a file at the top level or the name of a file in one of the
trees (`serve.py` for `benchmark/kinds/serve.py`); a path that starts
with a sub-package of `paddle_tpu/` (`ops/pallas/flash_attention.py`) is
looked up there.  It is what keeps a deleted tool or module out of the
documents.  ROADMAP.md names files still to be
written and is not held to it."""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
TREES = ("paddle_tpu/", "benchmark/", "scripts/", "tests/", "docs/",
         "examples/")
BARE_NAME = re.compile(r"^[\w.-]+\.(py|json|jsonl|md)$")

SUBPACKAGES = tuple(
    name + "/" for name in sorted(os.listdir(os.path.join(REPO, "paddle_tpu")))
    if os.path.isdir(os.path.join(REPO, "paddle_tpu", name)))


def named_paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        token = token.split("::")[0].strip()
        token = re.sub(r":[\d,:-]+$", "", token)        # :line, :from-to
        if not token or re.search(r"[*<{\s]", token):
            continue
        if token.startswith(TREES) or BARE_NAME.match(token):
            yield token
        elif token.startswith(SUBPACKAGES) and re.search(
                r"\.(py|cpp|json|md)$", token):
            yield "paddle_tpu/" + token


@functools.lru_cache(maxsize=None)
def file_names():
    names = set(os.listdir(REPO))
    for tree in TREES:
        for _, _, files in os.walk(os.path.join(REPO, tree)):
            names.update(files)
    return names


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        names = sorted(set(named_paths(f.read())))
    assert names, f"{doc} names no path: the scan is broken"
    known = file_names()
    missing = [n for n in names
               if not (os.path.exists(os.path.join(REPO, n))
                       or ("/" not in n and n in known))]
    assert not missing, f"{doc} names files that do not exist: {missing}"
