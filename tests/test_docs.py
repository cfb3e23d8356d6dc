"""The documents name files that exist.

A reader of ``README.md``, ``CLAUDE.md``, ``COVERAGE.md`` or the verify
skill is told to run or open paths of this repository. Every such path —
the script after ``python``, and anything under ``scripts/``, ``results/``,
``tests/`` or ``benchmark/`` — has to be there, so that a deleted file takes
its recipes with it. A glob or a placeholder (``tests/test_*.py``,
``scripts/foo_<x>.py``, ``results/{a,b}/``) is held to its directory. A path
that follows ``/``, ``-``, ``.``, ``<`` or a word character is the tail of a
longer one (``distributed_pytorch_example_tpu/native/tests/...``,
``<root>/results/...``), not a path from the repository's root, and is not
looked at.
"""

import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (
    "README.md", "CLAUDE.md", "COVERAGE.md", ".claude/skills/verify/SKILL.md",
)

_RUN = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_OPEN = re.compile(r"(?<![\w./<-])((?:scripts|results|tests|benchmark)/[\w./-]*)")


def _named_paths(text):
    paths = {m.group(1) for m in _RUN.finditer(text)}
    for m in _OPEN.finditer(text):
        path = m.group(1)
        if text[m.end():m.end() + 1] in ("*", "<", "{"):
            path = os.path.dirname(path)  # the pattern's fixed part
        paths.add(path.rstrip(".-"))
    return sorted(paths)


def test_a_glob_or_placeholder_is_held_to_its_directory():
    text = "run tests/test_*.py, scripts/foo_<x>.py and results/{a,b}/c.json."
    assert _named_paths(text) == ["results", "scripts", "tests"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO_ROOT, document), encoding="utf-8") as f:
        paths = _named_paths(f.read())
    assert paths, document
    missing = [
        p for p in paths if not os.path.exists(os.path.join(REPO_ROOT, p))
    ]
    assert not missing, f"{document} names paths that are not there: {missing}"
