"""The records agree with the tree: a document cites files that exist, the
repository's root holds one yardstick's declaration and no measurement of a
past installation, and the option registry lists what the package reads.

``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` hold history (what a past PR
deleted is named there on purpose) and are left out.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

# a path under one of the repository's directories, up to its last name
# (a line number, an anchor or a test id after it is not part of the path)
_PATH = re.compile(r"(?<![\w/.-])((?:mxnet_tpu|tools|tests|benchmark|docs|"
                   r"example)/[\w./*<>-]*[\w/*>])")
# a file named without a directory: a script, or a root record in capitals
_BARE = re.compile(r"(?<![\w/.-])([A-Za-z_][\w-]*\.py|"
                   r"[A-Z][A-Z0-9_]*\.(?:md|json|jsonl|txt))(?![\w/])")


@pytest.fixture(scope="module")
def tree_names():
    """Every file name in the tree, whatever its directory."""
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("build", "chiprun_out", "__pycache__")]
        names.update(files)
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_files_that_exist(document, tree_names):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    cited = {m.group(1) for m in _PATH.finditer(text)}
    missing = sorted(p for p in cited if "*" not in p and "<" not in p
                     and not os.path.exists(os.path.join(REPO, p)))
    # a script may be named relative to the directory the text is about;
    # a record in capitals is a file at the root or a sibling under docs/
    for name in {m.group(1) for m in _BARE.finditer(text)}:
        if name.endswith(".py"):
            found = name in tree_names
        else:
            found = any(os.path.exists(os.path.join(REPO, d, name))
                        for d in ("", "docs"))
        if not found:
            missing.append(name)
    assert not missing, "%s cites what is not in the tree: %s" % (
        document, missing)


def test_root_holds_no_measurement_but_the_benchmarks_declaration():
    """Numbers live in ``PERF_LEDGER.jsonl`` (the driver's) and ``PERF.md``;
    a JSON file at the root is a measurement somebody will quote."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        left_behind = {line.strip() for line in f}   # a tool's, never ours
    found = sorted(n for n in os.listdir(REPO)
                   if n.endswith(".json") and not n.startswith(".")
                   and n not in left_behind)
    assert found == ["BENCHMARK.json"]


def test_every_registered_variable_is_read_by_the_package():
    """``mxnet_tpu/env.py`` is the package's registry, not a root script's:
    each name it lists is read somewhere under ``mxnet_tpu/`` (the one
    testing knob by ``tests/conftest.py``, as its entry says)."""
    from mxnet_tpu import env
    sources = [os.path.join(REPO, "tests", "conftest.py")]
    for root, _, files in os.walk(os.path.join(REPO, "mxnet_tpu")):
        sources += [os.path.join(root, n) for n in files
                    if n.endswith(".py") and n != "env.py"]
    text = "\n".join(open(p).read() for p in sources)
    unread = sorted(n for n in env.VARIABLES if n not in text)
    assert not unread, "registered in env.py and read nowhere: %s" % unread
