"""The documents name files that exist.

README.md, ARCHITECTURE.md, COMPONENTS.md, docs/*.md and the CI workflow
send a reader (or a runner) to paths and scripts of this repo; a file
deleted or moved without its mentions leaves them pointing at nothing.
PERF.md, ROADMAP.md and CHANGES.md are history and are not held to it."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md", "ARCHITECTURE.md", "COMPONENTS.md"]
    + sorted(os.path.relpath(p, REPO)
             for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
    + [".github/workflows/ci.yml"]
)

# a path under one of the repo's directories, `*` allowed (tools/*_smoke.py)
PATH = re.compile(
    r"(?<![\w/.*-])((?:seldon_core_tpu|tools|tests|benchmark|docs|deploy|native|examples)"
    r"/[\w./*-]*[\w/*])"
)
# a script named without its directory: bench.py, continuous.py, smoke.sh
BARE = re.compile(r"(?<![\w/.*-])([A-Za-z_]\w*\.(?:py|sh))\b")
# such a script that a step or a reader is told to run: from the root
RUN = re.compile(r"\b(?:python3?|bash)\s+([\w-]+\.(?:py|sh))\b")

# made by a build or written by the reader, not kept in the tree
NOT_IN_THE_TREE = ("native/build/", "native/gen/")
THE_READERS_OWN = {"MyModel.py"}


@functools.lru_cache(maxsize=None)
def _tree_basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        if root == REPO:
            # scratch copies and outputs (.gitignore) hold stale files
            dirs[:] = [d for d in dirs
                       if not d.startswith(("_", ".")) and d != "chiprun_out"]
        names.update(files)
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = []
    for path in sorted(set(PATH.findall(text))):
        if path.startswith(NOT_IN_THE_TREE):
            continue
        if not glob.glob(os.path.join(REPO, path)):
            missing.append(path)
    for script in sorted(set(RUN.findall(text)) - THE_READERS_OWN):
        if not os.path.exists(os.path.join(REPO, script)):
            missing.append(f"run from the root: {script}")
    basenames = _tree_basenames()
    for name in sorted(set(BARE.findall(text)) - THE_READERS_OWN):
        if name not in basenames:
            missing.append(name)
    assert not missing, f"{document} names files that do not exist: {missing}"
