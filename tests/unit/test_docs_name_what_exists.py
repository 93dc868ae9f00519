"""A document names only files that are in the tree: ``README.md`` and the
files of ``docs/`` may name, in backticks or after ``python ``, a path under
``deepspeed_tpu/``, ``benchmark/``, ``tests/`` or ``docs/`` (a path from the
package's root, ``serving/router.py``, counts as one under ``deepspeed_tpu/``),
or a top-level ``*.py`` / ``*.json``; each has to exist. The history files (``CHANGES.md``,
``PERF.md``, ``ROADMAP.md``) name what was and are no case of this test."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(path, REPO)
    for path in glob.glob(os.path.join(REPO, "docs", "*.md")))

_FENCED = re.compile(r"^```.*?^```", re.S | re.M)
# the first word of a backticked span: ``a/b.py`` and ``a/b.py --option``
_QUOTED = re.compile(r"`([^`\s]+)[^`]*`")
_RUN = re.compile(r"\bpython3? +(?:-\S+ +)*([\w./-]+\.py)\b")
_UNDER = re.compile(r"^(?:deepspeed_tpu|benchmark|tests|docs)/[\w./-]+$")
# ``serving/router.py``: a path from the package's root
_PACKAGES = "|".join(sorted(
    name for name in os.listdir(os.path.join(REPO, "deepspeed_tpu"))
    if os.path.isdir(os.path.join(REPO, "deepspeed_tpu", name))
    and not name.startswith("_")))
_IN_PACKAGE = re.compile(rf"^(?:{_PACKAGES})/[\w./-]+\.py$")
_TOP_LEVEL = re.compile(r"^[\w-]+\.(?:py|json)$")


def _named_paths(text: str) -> set[str]:
    names = set(_RUN.findall(text))
    for word in _QUOTED.findall(_FENCED.sub("", text)):
        # ``tests/unit/test_x.py::TestY`` and ``file.py:12`` name the file
        names.add(re.split(r"::|:\d", word)[0].rstrip(".,;:"))
    return {"deepspeed_tpu/" + n if _IN_PACKAGE.match(n) else n for n in names
            if _UNDER.match(n) or _TOP_LEVEL.match(n) or _IN_PACKAGE.match(n)}


_BASENAMES = {
    name
    for top in ("deepspeed_tpu", "benchmark", "tests")
    for _, _, files in os.walk(os.path.join(REPO, top)) for name in files}


def _exists(name: str) -> bool:
    if os.path.exists(os.path.join(REPO, name)):
        return True
    if "/" not in name:
        # a bare ``router.py`` is short for a module the text around it places
        return name in _BASENAMES
    # ``benchmark/trace_reduce.recording`` names a function of a module
    module, _, attribute = name.rpartition(".")
    return attribute.isidentifier() and os.path.exists(
        os.path.join(REPO, module + ".py"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_is_in_the_tree(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    assert sorted(n for n in _named_paths(text) if not _exists(n)) == []
