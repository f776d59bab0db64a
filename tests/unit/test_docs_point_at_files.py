"""The documents a newcomer reads first may only point at files that
exist: every back-ticked repository path in them is checked, so the next
deletion cannot leave a pointer behind."""

import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
DOCS = ["README.md", os.path.join(".claude", "skills", "verify", "SKILL.md")]

# a back-ticked token is a path when it is made of path characters only
# and names a file (a known extension) or a directory (a trailing slash);
# ``file.py:12`` and ``file.py::test`` point at the file
_TOKEN = re.compile(r"`([^`\s]+)`")
_PATH = re.compile(r"^[\w.\-][\w./\-]*$")
_FILE = re.compile(r"\.(md|py|json|jsonl|ini|cpp|sh|txt)$")
# where a path may start: the checkout, or the package (``serving/engine.py``)
_BASES = ("", "deepspeed_tpu")
# names of what a run leaves behind, not of anything git tracks
_MADE_AT_RUN_TIME = {"signatures.json"}


def _ignored():
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as fh:
        return {ln.strip().rstrip("/") for ln in fh if ln.strip()}


def _paths(text):
    for token in _TOKEN.findall(text):
        token = re.sub(r":{1,2}[\w\[\]\-]+$", "", token)
        if _PATH.match(token) and (token.endswith("/") or _FILE.search(token)):
            yield token


@pytest.mark.parametrize("doc", DOCS)
def test_every_backticked_path_exists(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    ignored = _ignored()
    found = sorted(set(_paths(text)))
    assert found, f"{doc} names no path at all: the pattern is broken"
    missing = [
        p for p in found
        if p not in _MADE_AT_RUN_TIME and p.rstrip("/") not in ignored
        and not any(os.path.exists(os.path.join(REPO, base, p))
                    for base in _BASES)]
    assert not missing, f"{doc} points at what is not there: {missing}"
