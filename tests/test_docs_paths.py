"""Every file a document names exists.

One case per document (``README.md`` and each ``docs/*.md``): every
repo-relative path in backticks, or after ``python`` / ``python3``, that
ends in ``.py``, ``.json``, ``.md``, ``.sh`` or ``.cc``, and every relative
markdown link target, must be in the tree. A deletion that leaves a
document pointing at what it removed fails here, not in a reader's shell.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)

_PATH = r"[\w.*/\-]*[\w*]\.(?:py|json|md|sh|cc)\b"
_IN_TICKS = re.compile(r"`([^`\n]+)`")
_TOKEN = re.compile(r"(?<![\w.*/\-<>{}$])" + _PATH)
_AFTER_PYTHON = re.compile(r"\bpython3?\s+(?:-\w+\s+)*(" + _PATH + ")")
_LINK = re.compile(r"\]\(([^)\s]+)\)")

# The tree a document may name: the root's own files and the directories
# the repo commits (build and run leftovers beside them are not it).
_TREE_DIRS = (
    "horovod_tpu", "tests", "tools", "csrc", "docs", "examples", "benchmark",
)

# Paths a document names that are not files of the tree, each with why.
ALLOWED = {
    # The user's own script in a launch line.
    "train.py",
    # Written by a run, into a directory the run chooses.
    "merged.json", "trace_driver.json", "manifest.json",
    # The reference project's files, in README.md's table that maps its
    # layers onto this repo's and in docs/elastic.md's test mapping.
    "mpi_controller.cc", "gloo_controller.cc", "ops/operation_manager.cc",
    "common/elastic.py", "test_elastic_driver.py",
}
# The same project's tree cited by its full path (``SURVEY.md`` maps it).
_REFERENCE_TREE = "horovod/"


@pytest.fixture(scope="module")
def tree():
    files = [f for f in os.listdir(REPO) if os.path.isfile(os.path.join(REPO, f))]
    for top in _TREE_DIRS:
        for d, subdirs, names in os.walk(os.path.join(REPO, top)):
            subdirs[:] = [s for s in subdirs if s != "__pycache__"]
            files += [os.path.relpath(os.path.join(d, n), REPO) for n in names]
    return files


def _exists(path, tree):
    """``path`` is a file of the tree, or the tail of one (a document about
    the package writes ``ops/fusion.py``); ``*`` matches as in a shell."""
    path = os.path.normpath(path)
    pattern = re.compile(
        "(?:^|.*/)" + re.escape(path).replace(r"\*", "[^/]*") + "$"
    )
    return any(pattern.match(f) for f in tree)


def named_paths(text):
    """(path, how it was named) for every path the rules above cover."""
    found = []
    for span in _IN_TICKS.findall(text):
        found += [(m.group(0), "backticks") for m in _TOKEN.finditer(span)]
    found += [(m.group(1), "python") for m in _AFTER_PYTHON.finditer(text)]
    for target in _LINK.findall(text):
        if re.match(r"[a-z]+:", target) or target.startswith("#"):
            continue
        found.append((target.split("#")[0], "link"))
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc, tree):
    text = open(os.path.join(REPO, doc)).read()
    doc_dir = os.path.dirname(doc)
    missing = sorted({
        f"{path} ({how})"
        for path, how in named_paths(text)
        if not path.startswith(("/", _REFERENCE_TREE))
        and path not in ALLOWED
        and not (
            os.path.exists(os.path.join(REPO, doc_dir, path))
            if how == "link" else _exists(path, tree)
        )
    })
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
