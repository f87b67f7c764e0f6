"""A document names no file that is gone: every back-quoted (or, in
``pyproject.toml``, marker-cited) path that ends in ``.py``, ``.sh``,
``.json`` or ``.jsonl`` exists in the checkout."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# where a document's short paths are rooted (`runtime/engine.py` is the package's)
ROOTS = ("", "deepspeed_tpu", "tests/unit", "tools", "benchmarks")
PATH = r"[\w./<>*{},-]*\w\.(?:py|sh|jsonl|json)\b"
# the reference implementation's own files (PARITY.md's first column names
# them too), URLs, and what a run writes
NOT_OURS = ("deepspeed/", "/", "manifest.json", "resilience_report.json")


def _cited(doc):
    text = open(os.path.join(REPO, doc)).read()
    if doc.endswith(".toml"):
        return set(re.findall(PATH, text))
    text = re.sub(r"(?m)^\|[^|]*\|", "|", text)
    return {m for span in re.findall(r"`([^`\n]+)`", text) for m in re.findall(PATH, span)}


def _exists(path):
    if any(c in path for c in "<>*{}"):          # a pattern, not a file
        return True
    if "/" not in path:                          # a bare name: anywhere under the roots
        return any(path in files for root in ROOTS[1:]
                   for _, _, files in os.walk(os.path.join(REPO, root))
                   ) or os.path.exists(os.path.join(REPO, path))
    return any(os.path.exists(os.path.join(REPO, root, path)) for root in ROOTS)


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md", "BASELINE.md", "pyproject.toml"])
def test_a_document_names_no_file_that_is_gone(doc):
    gone = sorted(p for p in _cited(doc) if not p.startswith(NOT_OURS) and not _exists(p))
    assert not gone, f"{doc} names files that are not in the checkout: {gone}"
