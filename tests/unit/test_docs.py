"""The documents cite only what exists.

For ``README.md`` and every file under ``docs/``: a backticked path ending
in ``.py`` that starts with one of the repo's source directories, or has no
directory at all (a file at the repo's root), exists; and every keyword of
a ``ServeConfig(...)`` snippet is a field.  Paths with another first
directory (``ops/dispatch.py`` as shorthand, the reference repo's own in
``docs/MIGRATION.md``) are not checked.  A stale citation is fixed in the
document, not excused here.
"""

import dataclasses
import os
import re

import pytest

from cloud_tpu.serving import ServeConfig

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SOURCE_DIRS = ("cloud_tpu/", "scripts/", "benchmarks/", "tests/",
               "examples/")
DOCUMENTS = ["README.md"] + sorted(
    f"docs/{name}" for name in os.listdir(os.path.join(REPO, "docs"))
    if name.endswith(".md"))

_CODE_SPAN = re.compile(r"`([^`\n]+)`")
#: ``path.py``, ``path.py:12-40`` or ``path.py::test_name``.
_PY_PATH = re.compile(r"^([\w./-]+\.py)(?::.*)?$")
_KEYWORD = re.compile(r"(\w+)\s*=(?!=)")


def _cited_paths(text):
    for span in _CODE_SPAN.findall(text):
        for word in span.split():
            match = _PY_PATH.match(word.strip("(),;"))
            if match:
                yield match.group(1)


def _serve_config_keywords(text):
    """Keywords at the top level of every ``ServeConfig(...)`` call."""
    for start in re.finditer(r"ServeConfig\(", text):
        depth, begin = 1, start.end()
        top_level = []
        for i in range(begin, len(text)):
            char = text[i]
            if char in "([{":
                depth += 1
            elif char in ")]}":
                depth -= 1
                if depth == 0:
                    break
            top_level.append(char if depth == 1 else " ")
        yield from _KEYWORD.findall("".join(top_level))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_doc_cites_only_what_exists(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    missing = sorted({
        path for path in _cited_paths(text)
        if (path.startswith(SOURCE_DIRS) or "/" not in path)
        and not os.path.exists(os.path.join(REPO, path))})
    assert missing == [], f"{document} cites files that do not exist"
    fields = {field.name for field in dataclasses.fields(ServeConfig)}
    unknown = sorted(set(_serve_config_keywords(text)) - fields)
    assert unknown == [], f"{document} passes ServeConfig no such field"
