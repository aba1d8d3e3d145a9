"""The user-facing documents and the Makefile name only files that exist.

A document that sends a reader to a deleted script is how the repo came to
answer "how fast is it" twice; these cases fail the moment a path named in
backticks (or by a Makefile recipe) stops existing.
"""

import os
import re
import subprocess

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     os.pardir, os.pardir))
DOCS = ("README.md", "docs/LINT.md", "docs/OBSERVABILITY.md",
        "docs/PARITY.md", "docs/RESILIENCE.md")

# what a save writes into a checkpoint tag: named in the documents as a
# format, never present in a checkout
_WRITTEN_AT_RUN_TIME = {"MANIFEST.json"}
_EXT = r"(?:py|md|json|jsonl)"
_WORD = re.compile(r"[A-Za-z0-9_./-]+")


def _tracked():
    out = subprocess.run(["git", "ls-files"], cwd=_REPO, capture_output=True,
                         text=True)
    if out.returncode == 0 and out.stdout.strip():
        return set(out.stdout.split())
    found = set()                      # an export without .git: walk it
    for dp, dn, fn in os.walk(_REPO):
        dn[:] = [d for d in dn if not d.startswith(".") and d != "__pycache__"]
        found.update(os.path.relpath(os.path.join(dp, f), _REPO) for f in fn)
    return found


_FILES = _tracked()
_BASENAMES = {os.path.basename(f) for f in _FILES}
_TOP = {f.split("/", 1)[0] for f in _FILES if "/" in f}
_PKG_TOP = {f.split("/")[1] for f in _FILES
            if f.startswith("deepspeed_tpu/") and f.count("/") >= 2}


def _exists(rel):
    return os.path.exists(os.path.join(_REPO, rel))


def _missing(word):
    """None when ``word`` is not a repo path or names something that
    exists; otherwise the path it should have been."""
    word = word.strip(".,;:()")
    word = re.sub(r"(::[\w\[\]-]+)+$", "", word)       # pytest selectors
    word = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", word)   # line numbers
    if not word or not _WORD.fullmatch(word) or word.startswith(("/", ".")):
        return None
    if "/" in word:
        first = word.split("/", 1)[0]
        if first in _TOP:
            return None if _exists(word) else word
        if first in _PKG_TOP:                      # `serving/engine.py`
            rel = "deepspeed_tpu/" + word
            return None if _exists(rel) else rel
        return None                                # a URL path, a run's output
    if not re.fullmatch(r"[\w.-]+\." + _EXT, word) \
            or word in _WRITTEN_AT_RUN_TIME:
        return None
    if word.endswith(".py"):                       # a bare `engine.py`
        return None if word in _BASENAMES else word
    if re.match(r"[A-Z]{2}", word):                # `BENCHMARK.json`, `PERF.md`
        return None if _exists(word) else word
    return None


def _named_paths(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        if any(c in span for c in "<>*{}$"):       # patterns and placeholders
            continue
        if span.startswith("(R)"):                 # the reference repo's file
            continue
        for word in span.split():
            yield word


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_existing_paths(doc):
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        text = f.read()
    missing = sorted({m for m in map(_missing, _named_paths(text)) if m})
    assert not missing, f"{doc} names files that do not exist: {missing}"


def test_makefile_recipes_name_only_existing_files():
    missing = set()
    with open(os.path.join(_REPO, "Makefile"), encoding="utf-8") as f:
        for line in f:
            if not line.startswith("\t"):
                continue                           # recipes only
            for word in line.split():
                if re.fullmatch(r"[\w./-]+\." + _EXT, word) or word.endswith("/"):
                    if not _exists(word):
                        missing.add(word)
    assert not missing, f"Makefile recipes name missing files: {sorted(missing)}"
