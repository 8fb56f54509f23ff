"""ISSUE 26's nine per-layer metrics: each file, through the reader it
names, on spans written by hand with a known answer, and on the outcome of
the tiny CPU serve run (a number, never ``None``).  CPU only."""

import importlib
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import context, manifest, peaks  # noqa: E402

CHAT = "baichuan-7b-l16.chat-open"
DOCS = "baichuan-7b-l16.docs-saturated"
#: Two passes in a window that opens at 100 s: 0.30 s with two inserts
#: (0.04 + 0.02) and a chunk of 0.22, then 0.24 s with a chunk of 0.23;
#: a pass and an insert of set-up that no metric may count; three first
#: tokens after 0.1, 0.2 and 0.6 s; 50 of 200 row-steps in use.
SPANS = [
    ("serve/pass", 90.0, 0.5, {}), ("serve/prefill", 90.1, 0.3, {}),
    ("serve/pass", 100.0, 0.30, {}), ("serve/prefill", 100.01, 0.04, {}),
    ("serve/prefill", 100.05, 0.02, {}), ("serve/chunk", 100.075, 0.22, {}),
    ("serve/pass", 100.30, 0.24, {}), ("serve/chunk", 100.305, 0.23, {}),
    ("serve/ttft", 100.0, 0.1, {}), ("serve/ttft", 100.1, 0.2, {}),
    ("serve/ttft", 100.2, 0.6, {}),
]
STATS = {"kv_row_steps_in_use": 50, "kv_row_steps_reserved": 200}
BY_HAND = {
    "pass_p50_ms": 270.0,                       # midway between 240 and 300
    "pass_p95_ms": 297.0,
    "prefill_ms_per_pass.chat": 30.0,           # 60 ms over 2 passes
    "prefill_ms_per_pass.docs": 30.0,
    "pass_host_ms.chat": 15.0,                  # (540 - 60 - 450) / 2
    "pass_host_ms.docs": 15.0,
    "engine_ttft_p95_ms": 560.0,                # 200 + 0.9 x (600 - 200)
    "kv_rows_in_use_pct.chat": 25.0,
    "kv_rows_in_use_pct.docs": 25.0,
}
CELL_OF = {name: DOCS if name.endswith(".docs") else CHAT
           for name in BY_HAND}


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")


def _read(name, outcome):
    spec = manifest.Cell(CELL_OF[name]).layer_metrics[name]
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(spec["args"], outcome, peaks.peaks_for("TPU v5 lite"))


def _outcome(spans, stats):
    return context.Outcome(
        window_start=100.0, window_s=10.0, end_to_end={}, attempted=1,
        failed=0, checks=[], memory_peak_bytes=0, spans=spans, stats=stats)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_metric_on_spans_written_by_hand(name):
    assert _read(name, _outcome(SPANS, STATS)) == pytest.approx(
        BY_HAND[name])
    # A program without the span or the counter (the parent commit):
    # nothing to read, nothing returned, nothing raised.
    older = [s for s in SPANS if s[0] in ("serve/prefill", "serve/chunk")]
    assert _read(name, _outcome(older, {})) is None


@pytest.fixture(scope="module")
def tiny_runs():
    from cloud_tpu.monitoring import tracing

    os.environ["CLOUD_TPU_FLASH_FORCE_INTERPRET"] = "1"
    outcomes = {}
    for cell_name in (CHAT, DOCS):
        cell = manifest.Cell(cell_name, tiny=True)
        with tracing.collecting():
            outcomes[cell_name], _, _ = bench_run.drive(
                cell, 2 ** 31 + 26, 2.0, 0, process_start=time.perf_counter(),
                check=False)
    return outcomes


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_metric_on_the_tiny_serve_run(name, tiny_runs):
    outcome = tiny_runs[CELL_OF[name]]
    value = _read(name, outcome)
    assert value is not None and value >= 0.0
    if name.startswith("kv_rows_in_use_pct"):
        assert 0.0 < value <= 100.0
    if name.startswith("pass_host_ms"):
        # What a pass is made of adds up: host = pass - prefill - chunk.
        per_pass = {kind: _read(f"{kind}.{name.rsplit('.', 1)[1]}", outcome)
                    for kind in ("prefill_ms_per_pass", "pass_host_ms")}
        passes = [s[2] for s in outcome.spans if s[0] == "serve/pass"
                  and outcome.window_start <= s[1]
                  <= outcome.window_start + outcome.window_s]
        assert sum(per_pass.values()) <= 1e3 * sum(passes) / len(passes)
