"""Static span-name contract check: code vs docs/observability.md.

The "What is instrumented" table in docs/observability.md claims to be
the COMPLETE span-name contract.  This script makes that claim
enforceable without running anything:

* **code side** — every ``span("...")`` / ``record_span("...")`` /
  ``@traced(name="...")`` string literal in ``cloud_tpu/**/*.py``
  (including local wrappers like collectives' ``_span``;
  f-string placeholders normalize ``{site}`` -> ``<site>`` to match the
  docs' parameterized rows);
* **doc side** — every backticked ``layer/name`` token inside the
  instrumentation table's rows.

A span recorded in code but missing from the table fails (undocumented
instrumentation), and a token documented but absent from code fails
(ghost documentation) — bidirectional, so the table can never silently
rot in either direction.  Two explicit escape hatches:

* ``GAUGE_TOKENS`` — metric names the table mentions alongside their
  spans (gauges, not spans; they must still exist as literals in code);
* ``VARIABLE_SPANS`` — span names the trainer builds conditionally
  (``compute_span = "step/first_compile" if ...``), invisible to the
  call-site grep but still required to exist as string literals.

Wired as a fast tier-1 test in tests/unit/test_monitoring.py — pure
stdlib, no imports of the package under test, runs in milliseconds.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Set

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC_PATH = os.path.join(REPO, "docs", "observability.md")

#: Metric (gauge/distribution) names the docs table mentions next to
#: the spans they accompany.  Not spans — but they must exist as string
#: literals in the scanned files, so a renamed gauge still fails here.
GAUGE_TOKENS = {
    "serve/spec_accept_rate",
}

#: Span names assigned to a variable before the ``span(...)`` call
#: (the trainer's first-dispatch/fused-window switch), so the call-site
#: regex cannot see them.  Still required to exist as string literals.
VARIABLE_SPANS = {
    "step/first_compile",
    "step/fused_compute",
}

#: span("name" / record_span("name" / _span("name" — \w*span also
#: matches private wrappers; \s* spans newlines for multiline calls.
_CALL_RE = re.compile(r'\b\w*span\(\s*f?"([^"\n]+/[^"\n]+)"')
_TRACED_RE = re.compile(r'\btraced\(\s*name="([^"\n]+)"')
#: Backticked `layer/name` tokens in the docs table (`<param>` rows
#: included; `=`/`.` excluded so attribute examples and file paths
#: never count as span names).
_DOC_TOKEN_RE = re.compile(r"`([a-z0-9_]+/[a-z0-9_<>]+)`")
_PLACEHOLDER_RE = re.compile(r"\{(\w+)\}")


def _python_files() -> List[str]:
    files = []
    for root, _dirs, names in os.walk(os.path.join(REPO, "cloud_tpu")):
        files.extend(
            os.path.join(root, n) for n in names if n.endswith(".py")
        )
    return sorted(files)


def code_spans() -> Dict[str, Set[str]]:
    """``{span_name: {relative files recording it}}`` from the code."""
    spans: Dict[str, Set[str]] = {}
    for path in _python_files():
        with open(path, encoding="utf-8") as f:
            source = f.read()
        rel = os.path.relpath(path, REPO)
        for pattern in (_CALL_RE, _TRACED_RE):
            for name in pattern.findall(source):
                name = _PLACEHOLDER_RE.sub(r"<\1>", name)
                spans.setdefault(name, set()).add(rel)
    return spans


def doc_tokens() -> Set[str]:
    """Backticked span tokens from the instrumentation table rows."""
    with open(DOC_PATH, encoding="utf-8") as f:
        lines = f.read().splitlines()
    tokens: Set[str] = set()
    in_table = False
    for line in lines:
        if line.startswith("| layer | spans |"):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                in_table = False
                continue
            tokens.update(_DOC_TOKEN_RE.findall(line))
    return tokens


def _literal_exists(name: str) -> bool:
    needle = f'"{name}"'
    return any(
        needle in open(path, encoding="utf-8").read()
        for path in _python_files()
    )


def main(argv=None) -> int:
    del argv
    spans = code_spans()
    documented = doc_tokens()
    if not documented:
        print("check_spans: no instrumentation table found in "
              f"{os.path.relpath(DOC_PATH, REPO)}", file=sys.stderr)
        return 1

    failures = []
    for name in sorted(set(spans) - documented):
        failures.append(
            f"undocumented span {name!r} (recorded in "
            f"{', '.join(sorted(spans[name]))}) — add it to the "
            "docs/observability.md instrumentation table"
        )
    ghost = documented - set(spans) - GAUGE_TOKENS - VARIABLE_SPANS
    for name in sorted(ghost):
        failures.append(
            f"documented span {name!r} is recorded nowhere in "
            "cloud_tpu/ — remove the table row or the "
            "allowlist entry it needs"
        )
    for name in sorted((GAUGE_TOKENS | VARIABLE_SPANS) & documented):
        if not _literal_exists(name):
            failures.append(
                f"allowlisted token {name!r} no longer appears as a "
                "string literal anywhere — it was renamed or removed"
            )

    if failures:
        for failure in failures:
            print(f"check_spans: {failure}", file=sys.stderr)
        return 1
    print(
        f"check_spans: {len(spans)} span name(s) in code, "
        f"{len(documented)} documented token(s) — in sync"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
