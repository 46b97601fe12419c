"""The documents that tell a user what to run name only files that exist.

``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are left out on purpose: they
record history and name files that went.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "distributed_deep_learning_tpu"
DOCS = ["README.md", "PARITY.md", "examples/README.md",
        ".claude/skills/verify/SKILL.md"]

#: named on purpose, and not ours: the reference repository's entry point and
#: the file name models are published under
NOT_OURS = {"CNN/main.py", "config.json"}

#: a back-ticked token that starts with a repo path: optional ``python``
#: in front, ``::name``, ``:line`` or arguments behind
PATH = re.compile(r"`(?:python3? )?([\w./-]+\.(?:py|json|md))(?=[`: ])")


def _exists(doc: str, path: str) -> bool:
    """As the documents write them: from the repo root, from the package
    (``serve/load.py``), from the document's own directory, or a bare test
    or example file name."""
    roots = ["", PKG, os.path.dirname(doc), "tests", "examples"]
    return any(os.path.exists(os.path.join(REPO, root, path))
               for root in roots)


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(PATH.findall(f.read())))
    assert named, f"{doc} names no file: the pattern has gone blind"
    missing = [p for p in named
               if not p.startswith("/") and p not in NOT_OURS
               and not _exists(doc, p)]
    assert not missing, missing
