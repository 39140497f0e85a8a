"""The documents that say what the system *is* name only files that are
in the tree.

``README.md``, ``docs/DESIGN.md`` and ``PERF.md`` §1–5 describe the
program as it stands; a path they quote under ``tools/``, ``runs/``,
``tests/``, ``diff3d_tpu/`` or ``benchmark/`` (or a ``python -m`` module
of the repo) must exist.  ``PERF.md`` §6–7 are history and plans and may
name files that are gone or not yet written; ``ROADMAP.md`` is rewritten
by sessions that run no tests.  Paths relative to a package
(``ops/attention.py``) or to a run's own directory
(``<workdir>/profile/...``) are not repo-relative and are not checked.
"""

import glob
import itertools
import os
import re

import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PATH = re.compile(
    r"(?<![\w/.<>~-])"
    r"((?:tools|tests|diff3d_tpu)/[\w/{},.*-]*\.py"
    r"|(?:runs|benchmark)/[\w/{},.*-]+)")
_MODULE = re.compile(r"python3? -m ((?:tools|diff3d_tpu|benchmark)[\w.]*)")
_BRACES = re.compile(r"\{([^{}]*)\}")


def _expand(path):
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py`` (shell brace lists)."""
    m = _BRACES.search(path)
    if m is None:
        return [path]
    return list(itertools.chain.from_iterable(
        _expand(path[:m.start()] + alt + path[m.end():])
        for alt in m.group(1).split(",")))


#: document -> the heading its checked part ends before (None: all of it)
_DOCUMENTS = {"README.md": None, "docs/DESIGN.md": None,
              "PERF.md": r"^## 6\."}


def _cited(text):
    cited = set()
    for m in _PATH.finditer(text):
        cited.update(_expand(m.group(1).rstrip(".,")))
    for m in _MODULE.finditer(text):
        cited.add(m.group(1).replace(".", "/"))
    return cited


def _exists(path):
    full = os.path.join(_REPO_ROOT, path)
    if "*" in path:
        return bool(glob.glob(full, recursive=True))
    return os.path.exists(full) or os.path.exists(full + ".py")


@pytest.mark.parametrize("doc", sorted(_DOCUMENTS))
def test_documents_cite_files_that_exist(doc):
    with open(os.path.join(_REPO_ROOT, doc)) as f:
        text = f.read()
    if _DOCUMENTS[doc] is not None:
        text = text[:re.search(_DOCUMENTS[doc], text, re.M).start()]
    cited = _cited(text)
    assert cited, f"{doc}: the path pattern found nothing to check"
    missing = sorted(p for p in cited if not _exists(p))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
