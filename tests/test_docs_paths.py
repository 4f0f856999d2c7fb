"""Every living document names only files and ``make`` targets that exist.

A case per document a new owner reads to learn how the tree is built, run
and measured.  ``CHANGES.md``, ``PERF.md``, ``ROADMAP.md`` and ``SURVEY.md``
are history or plans and may name what is gone."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where the repo's own Python lives (a document may name a file by any
#: tail of its path: ``solver/tpu.py`` for ``karpenter_tpu/solver/tpu.py``)
SOURCE_DIRS = ("karpenter_tpu", "benchmarks", "scripts", "tests", "deploy")
DOCUMENTS = sorted(
    ["README.md", "Makefile", ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, ROOT) for pat in ("deploy/*.yaml", "docs/*.md")
       for p in glob.glob(os.path.join(ROOT, pat))])

PY_PATH = re.compile(r"(?<![\w/.-])((?:[\w.-]+/)*[\w-]+\.py)\b")
#: a target as a document writes one: in backticks or at the start of a
#: command line — not the verb of a sentence
MAKE_TARGET = re.compile(r"(?:`|^\s*(?:\$ )?)make ((?:-n )?[a-z][a-z0-9-]*)",
                         re.M)


@pytest.fixture(scope="module")
def path_tails():
    """Every tail of every Python file's path: ``a/b/c.py``, ``b/c.py``,
    ``c.py``."""
    files = {f for f in os.listdir(ROOT) if f.endswith(".py")}
    for top in SOURCE_DIRS:
        for folder, _dirs, names in os.walk(os.path.join(ROOT, top)):
            files.update(os.path.relpath(os.path.join(folder, n), ROOT)
                         for n in names if n.endswith(".py"))
    return {"/".join(parts[i:]) for parts in (p.split("/") for p in files)
            for i in range(len(parts))}


@pytest.fixture(scope="module")
def make_targets():
    with open(os.path.join(ROOT, "Makefile")) as f:
        return set(re.findall(r"^([a-z][a-z0-9-]*):", f.read(), re.M))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document, path_tails, make_targets):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    # a committed tree unpacked under the gitignored _checkout/<name>/
    paths = {re.sub(r"^_checkout/[\w-]+/", "", p)
             for p in PY_PATH.findall(text)}
    gone = sorted(paths - path_tails)
    assert not gone, f"{document} names Python files that do not exist: {gone}"
    targets = {t.replace("-n ", "") for t in MAKE_TARGET.findall(text)}
    assert targets <= make_targets, (
        f"{document} names make targets the Makefile lacks: "
        f"{sorted(targets - make_targets)}")
