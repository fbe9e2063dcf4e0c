"""The documents name files that exist.

Every backticked token of a checked document that looks like a path (it
holds a ``/`` or ends in a source or record suffix) must exist relative
to the repo root or to ``paddlebox_tpu/`` (the documents write
``train/step.py`` for short). A path that is history is written
``git show <commit>:<path>``, which holds a space and is skipped.
Histories (ROADMAP.md, CHANGES.md, SURVEY.md) and the benchmark's own
files are not checked."""

import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

DOCS = ["README.md", "PERF.md", "PARITY.md", "docs/PERFORMANCE.md",
        "docs/DESIGN_NOTES.md", "docs/OBSERVABILITY.md", "docs/SERVING.md",
        "docs/ONLINE.md", "docs/RESILIENCE.md", "docs/STORAGE.md",
        "docs/MIGRATION.md"]

_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".cpp")
_NOT_A_PATH = set(" *<{$")


def named_paths(text):
    """(line number, path) for each backticked token the rule reads as a
    path, cut at its first ``:`` (``file.py:12``, ``file.py::test``)."""
    out = []
    for lineno, line in enumerate(text.splitlines(), 1):
        for tok in re.findall(r"`([^`\n]+)`", line):
            if _NOT_A_PATH & set(tok) or tok.startswith(("/", "http")):
                continue
            path = tok.split(":", 1)[0]
            if "/" in path or path.endswith(_SUFFIXES):
                out.append((lineno, path))
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as fh:
        found = named_paths(fh.read())
    assert found, f"{doc} names no path at all: the rule reads nothing"
    missing = [
        f"{doc}:{lineno}: {path}" for lineno, path in found
        if not os.path.exists(os.path.join(REPO, path))
        and not os.path.exists(os.path.join(REPO, "paddlebox_tpu", path))]
    assert not missing, "\n".join(missing)
