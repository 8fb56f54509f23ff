"""Span tracing tests: nesting/parentage in the Chrome-trace dump,
registry integration, the submit-to-first-step composite gauge after a
local run() smoke test, disabled-mode overhead, and the report CLI.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cloud_tpu import monitoring
from cloud_tpu.monitoring import metrics
from cloud_tpu.monitoring import report as report_lib
from cloud_tpu.monitoring import tracing


@pytest.fixture(autouse=True)
def clean_state():
    monitoring.reset()
    tracing.disable()
    tracing._reset_submit_state_for_tests()
    yield
    monitoring.reset()
    tracing.disable()
    tracing._reset_submit_state_for_tests()


class TestSpans:
    def test_nested_spans_parentage_and_durations(self, tmp_path):
        with tracing.collecting():
            with tracing.span("outer", stage="demo"):
                time.sleep(0.02)
                with tracing.span("inner"):
                    time.sleep(0.01)
            with tracing.span("sibling"):
                pass
            path = tracing.dump_timeline(str(tmp_path / "timeline.json"))

        doc = json.loads((tmp_path / "timeline.json").read_text())
        events = {
            e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"
        }
        outer, inner, sib = events["outer"], events["inner"], events["sibling"]
        # Parentage: inner is a child of outer; siblings are roots.
        assert inner["args"]["parent_id"] == outer["args"]["span_id"]
        assert outer["args"]["parent_id"] == 0
        assert sib["args"]["parent_id"] == 0
        # Durations (µs): each covers its sleep; inner nests inside outer.
        assert outer["dur"] >= 30_000
        assert inner["dur"] >= 10_000
        assert inner["dur"] <= outer["dur"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        # Attributes ride along.
        assert outer["args"]["stage"] == "demo"
        assert path == str(tmp_path / "timeline.json")

    def test_spans_record_registry_distributions(self):
        with tracing.collecting():
            with tracing.span("phase/a"):
                pass
            with tracing.span("phase/a"):
                pass
        dists = monitoring.snapshot()["distributions"]
        assert dists["span/phase/a"]["count"] == 2

    def test_exception_marks_span_and_propagates(self):
        with tracing.collecting() as col:
            with pytest.raises(RuntimeError):
                with tracing.span("boom"):
                    raise RuntimeError("x")
            (event,) = col.events()
        assert event["args"]["error"] == "RuntimeError"

    def test_decorator_names_and_nests(self):
        @tracing.traced
        def leaf():
            return 42

        @tracing.traced(name="custom/parent")
        def parent():
            return leaf()

        assert parent() == 42  # disabled: plain passthrough
        with tracing.collecting() as col:
            assert parent() == 42
            events = {e["name"]: e for e in col.events()}
        assert "custom/parent" in events
        (leaf_name,) = [n for n in events if n.endswith("leaf")]
        assert (
            events[leaf_name]["args"]["parent_id"]
            == events["custom/parent"]["args"]["span_id"]
        )

    def test_threads_get_independent_stacks(self):
        import threading

        with tracing.collecting() as col:
            with tracing.span("main_root"):
                t = threading.Thread(
                    target=lambda: tracing.span("worker_root").__enter__().__exit__(None, None, None)
                )
                t.start()
                t.join()
            events = {e["name"]: e for e in col.events()}
        # The worker's span must NOT parent onto the main thread's stack.
        assert events["worker_root"]["args"]["parent_id"] == 0
        assert events["worker_root"]["tid"] != events["main_root"]["tid"]

    def test_ring_buffer_evicts_but_aggregates_stay_exact(self):
        with tracing.collecting(capacity=10) as col:
            for _ in range(25):
                with tracing.span("tick"):
                    pass
            assert len(col.events()) == 10
            assert col.evicted == 15
            assert col.aggregates()["tick"]["count"] == 25


class TestDisabledMode:
    def test_disabled_span_is_shared_noop(self):
        assert tracing.span("anything") is tracing.span("other")
        assert not tracing.enabled()

    def test_disabled_span_overhead_under_10us(self):
        # The contract instrumentation relies on: a disabled span is one
        # function call + a None check (~0.5 µs observed).  10 µs bound
        # absorbs CI noise; a regression to real work (allocation, clock
        # reads, registry hits) lands well above it.
        n = 20_000
        with tracing.span("warm"):  # noqa: F841 - warm the code path
            pass
        start = time.perf_counter()
        for _ in range(n):
            with tracing.span("hot"):
                pass
        per_span = (time.perf_counter() - start) / n
        assert per_span < 10e-6, f"{per_span * 1e6:.2f}µs per disabled span"

    def test_disabled_spans_touch_no_registry(self):
        with tracing.span("ghost"):
            pass
        snap = monitoring.snapshot()
        assert not any(k.startswith("span/") for k in snap["distributions"])


class TestSubmitToFirstStep:
    def test_gauge_after_local_run_smoke(self, tmp_path, monkeypatch):
        """Acceptance: run/submit_to_first_step_seconds appears in a
        registry snapshot after a local run() smoke test + first step."""
        import jax
        import jax.numpy as jnp
        import optax

        import cloud_tpu
        from cloud_tpu.training.data import ArrayDataset
        from cloud_tpu.training.trainer import Trainer

        monkeypatch.setenv("GOOGLE_CLOUD_PROJECT", "proj")
        monkeypatch.delenv(tracing.ENV_SUBMIT_TS, raising=False)
        # A leaked in-container guard would make run() return before it
        # arms the submit mark; this test measures the local path.
        monkeypatch.delenv("CLOUD_TPU_RUNNING_REMOTELY", raising=False)
        tracing.enable()  # collector on: spans land in the registry too
        script = tmp_path / "train.py"
        script.write_text("pass")
        report = cloud_tpu.run(entry_point=str(script), dry_run=True)
        assert not report.submitted

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            loss = jnp.mean((pred - batch["y"]) ** 2)
            return loss, {"loss": loss}

        data = ArrayDataset(
            {
                "x": np.ones((8, 3), np.float32),
                "y": np.zeros((8, 1), np.float32),
            },
            batch_size=4,
        )
        trainer = Trainer(
            loss_fn, optax.sgd(0.1),
            init_fn=lambda rng: {"w": jnp.zeros((3, 1))},
        )
        trainer.init_state(jax.random.PRNGKey(0))
        trainer.fit(data, epochs=1)

        snap = monitoring.snapshot()
        assert tracing.SUBMIT_TO_FIRST_STEP_GAUGE in snap["gauges"]
        assert snap["gauges"][tracing.SUBMIT_TO_FIRST_STEP_GAUGE] > 0
        # The run() pipeline phases landed as span distributions too.
        assert "span/run/validate" in snap["distributions"]
        assert "span/run/plan" in snap["distributions"]
        # ... and the trainer's phase spans.
        assert "span/step/first_compile" in snap["distributions"]
        assert "span/step/data" in snap["distributions"]
        assert "span/step/callbacks" in snap["distributions"]
        # Recorded once per submit mark: a second fit must not re-publish.
        monitoring.reset()
        trainer.fit(data, epochs=1)
        assert (
            tracing.SUBMIT_TO_FIRST_STEP_GAUGE
            not in monitoring.snapshot()["gauges"]
        )

    def test_env_stamp_beats_local_mark(self, monkeypatch):
        monkeypatch.setenv(tracing.ENV_SUBMIT_TS, str(time.time() - 100.0))
        tracing.mark_submit()
        elapsed = tracing.record_submit_to_first_step()
        assert elapsed == pytest.approx(100.0, abs=5.0)

    def test_nothing_pending_records_nothing(self):
        assert tracing.record_submit_to_first_step() is None
        assert (
            tracing.SUBMIT_TO_FIRST_STEP_GAUGE
            not in monitoring.snapshot()["gauges"]
        )

    def test_startup_script_carries_submit_ts(self):
        from cloud_tpu.core import deploy

        script = deploy.startup_script(
            "img:1", coordinator_address="c:8476", num_processes=1,
            process_id_base=0, submit_ts=1234.5,
        )
        assert "-e CLOUD_TPU_SUBMIT_TS=1234.5" in script
        script = deploy.startup_script(
            "img:1", coordinator_address="c:8476", num_processes=1,
            process_id_base=0,
        )
        assert "CLOUD_TPU_SUBMIT_TS" not in script


class TestReport:
    def _dump(self, tmp_path):
        with tracing.collecting():
            for _ in range(3):
                with tracing.span("build"):
                    time.sleep(0.002)
            with tracing.span("deploy"):
                # Well over the three builds even where a loaded machine
                # oversleeps each of them by milliseconds.
                time.sleep(0.05)
            return tracing.dump_timeline(str(tmp_path / "t.json"))

    def test_rows_aggregate_per_name(self, tmp_path):
        path = self._dump(tmp_path)
        report = report_lib.TraceReport.from_file(path)
        rows = {r["name"]: r for r in report.rows()}
        assert rows["build"]["count"] == 3
        assert rows["deploy"]["count"] == 1
        assert rows["deploy"]["total_s"] >= 0.05
        # deploy (50ms) outweighs build (3x2ms): sorted first.
        assert report.rows()[0]["name"] == "deploy"
        assert 0 < rows["deploy"]["pct_wall"] <= 100.0

    def test_cli_prints_table(self, tmp_path):
        path = self._dump(tmp_path)
        proc = subprocess.run(
            [sys.executable, "-m", "cloud_tpu.monitoring.report", path],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
        )
        assert proc.returncode == 0, proc.stderr
        assert "deploy" in proc.stdout and "% wall" in proc.stdout

    def test_cli_handles_missing_file(self):
        assert report_lib.main(["/nope/missing.json"]) == 2

    def _dump_with_serving(self, tmp_path):
        with tracing.collecting():
            with tracing.span("step/compute"):
                time.sleep(0.002)
            now = time.perf_counter()
            # Cross-thread queue waits land via record_span; the compute
            # phases are ordinary context-manager spans.
            tracing.record_span("serve/queue_wait", now - 0.05, now)
            tracing.record_span("serve/queue_wait", now - 0.01, now)
            with tracing.span("serve/prefill"):
                time.sleep(0.004)
            with tracing.span("serve/chunk"):
                time.sleep(0.008)
            return tracing.dump_timeline(str(tmp_path / "serve.json"))

    def test_serving_breakdown_rows(self, tmp_path):
        path = self._dump_with_serving(tmp_path)
        report = report_lib.TraceReport.from_file(path)
        rows = report.serving_rows()
        # Request order, not cost order; the training span is excluded.
        assert [r["name"] for r in rows] == [
            "serve/queue_wait", "serve/prefill", "serve/chunk",
        ]
        assert rows[0]["count"] == 2  # both queue waits aggregated
        assert abs(sum(r["pct_serve"] for r in rows) - 100.0) < 1e-6
        # Queue wait (60ms recorded) dominates prefill+decode (~12ms).
        assert rows[0]["pct_serve"] > 50.0

    def test_serving_breakdown_rendered(self, tmp_path):
        path = self._dump_with_serving(tmp_path)
        rendered = report_lib.TraceReport.from_file(path).render()
        assert "serving breakdown" in rendered
        assert "% serve" in rendered
        assert "serve/queue_wait" in rendered

    def test_no_serving_section_without_serve_spans(self, tmp_path):
        rendered = report_lib.TraceReport.from_file(
            self._dump(tmp_path)
        ).render()
        assert "serving breakdown" not in rendered


class TestRecordSpan:
    def test_lands_in_timeline_aggregates_and_metrics(self):
        metrics.reset()
        with tracing.collecting() as collector:
            start = time.perf_counter()
            tracing.record_span("serve/queue_wait", start, start + 0.25,
                                bucket=32)
        agg = collector.aggregates()["serve/queue_wait"]
        assert agg["count"] == 1
        assert abs(agg["total_seconds"] - 0.25) < 1e-6
        event = collector.events()[-1]
        assert event["name"] == "serve/queue_wait"
        assert event["ph"] == "X"
        assert event["args"]["bucket"] == 32
        assert "span/serve/queue_wait" in metrics.snapshot()["distributions"]

    def test_noop_when_disabled(self):
        tracing.disable()
        metrics.reset()
        now = time.perf_counter()
        tracing.record_span("serve/queue_wait", now - 1.0, now)
        assert "span/serve/queue_wait" not in metrics.snapshot()[
            "distributions"
        ]

    def test_negative_interval_clamps_to_zero(self):
        with tracing.collecting() as collector:
            now = time.perf_counter()
            tracing.record_span("serve/queue_wait", now, now - 5.0)
        agg = collector.aggregates()["serve/queue_wait"]
        assert agg["total_seconds"] == 0.0


class TestXprofMirroring:
    def test_span_mirrors_as_trace_annotation_when_flagged(self, monkeypatch):
        entered = []

        class FakeAnnotation:
            def __init__(self, name, **kwargs):
                self.name = name

            def __enter__(self):
                entered.append(("enter", self.name))
                return self

            def __exit__(self, *exc):
                entered.append(("exit", self.name))
                return False

        import jax

        monkeypatch.setattr(
            jax.profiler, "TraceAnnotation", FakeAnnotation
        )
        with tracing.collecting():
            with tracing.span("quiet"):
                pass
            tracing.xprof_trace_started()
            try:
                with tracing.span("mirrored"):
                    pass
            finally:
                tracing.xprof_trace_stopped()
            with tracing.span("quiet2"):
                pass
        assert entered == [("enter", "mirrored"), ("exit", "mirrored")]


class TestTraceContext:
    """The propagatable request identity (ISSUE 16) and its default-off
    contract: no collector, no context — the field rides inert."""

    def test_disabled_mints_nothing(self):
        assert tracing.new_trace_context() is None

    def test_enabled_mints_unique_process_scoped_ids(self):
        with tracing.collecting():
            a = tracing.new_trace_context()
            b = tracing.new_trace_context(parent_id=7)
        assert a is not None and b is not None
        assert a.trace_id != b.trace_id
        # Process-scoped prefix: merged multi-process timelines can
        # never collide two requests onto one id.
        assert a.trace_id.startswith(f"{os.getpid():x}-")
        assert a.parent_id == 0
        assert b.parent_id == 7

    def test_context_is_a_frozen_identity(self):
        import dataclasses

        with tracing.collecting():
            ctx = tracing.new_trace_context()
        with pytest.raises(dataclasses.FrozenInstanceError):
            ctx.trace_id = "rewritten"


class TestLanes:
    """Timeline lanes: synthetic pid rows so fleet replicas sharing one
    process (and one collector) render as separate Perfetto lanes."""

    def test_register_lane_allocates_labelled_rows_above_pid_range(self):
        a = tracing.register_lane("replica a")
        b = tracing.register_lane("replica b")
        assert a != b
        assert min(a, b) >= tracing._LANE_BASE  # never collides with an OS pid
        assert tracing.lane_label(a) == "replica a"
        assert tracing.lane_label(os.getpid()) is None

    def test_thread_lane_stamps_event_pid(self):
        lane = tracing.register_lane("laned replica")
        with tracing.collecting() as col:
            with tracing.span("unlaned"):
                pass
            tracing.set_thread_lane(lane)
            try:
                with tracing.span("laned"):
                    pass
                now = time.perf_counter()
                tracing.record_span("laned_record", now - 0.001, now)
            finally:
                tracing.set_thread_lane(None)  # thread-local: reset for peers
            with tracing.span("after_reset"):
                pass
        events = {e["name"]: e for e in col.events()}
        assert events["unlaned"]["pid"] == os.getpid()
        assert events["laned"]["pid"] == lane
        assert events["laned_record"]["pid"] == lane
        assert events["after_reset"]["pid"] == os.getpid()


class TestSnapshotAndMerge:
    """snapshot() + merge_timelines(): the Fleet.dump_timeline building
    blocks — one consistent cut per collector, epoch-normalized onto a
    single wall with labelled pid lanes."""

    def test_snapshot_is_one_consistent_cut(self):
        with tracing.collecting(capacity=2) as col:
            for _ in range(3):
                with tracing.span("tick"):
                    pass
            snap = col.snapshot()
            assert set(snap) == {"epoch", "events", "evicted"}
            assert snap["epoch"] == col.epoch
            assert len(snap["events"]) == 2
            assert snap["evicted"] == 1
            snap["events"].clear()  # a copy, not a view of the buffer
            assert len(col.events()) == 2

    def test_merge_normalizes_epochs_and_labels_lanes(self, tmp_path):
        event = {"name": "w", "ph": "X", "ts": 1000.0, "dur": 5.0,
                 "tid": 1, "args": {}}
        sources = [
            {"label": "fleet", "epoch": 100.0,
             "events": [dict(event, pid=111)], "pid": 111},
            # Born 0.5s later on its own monotonic clock; 3 events
            # already evicted from its ring buffer.
            {"label": "replica 0", "epoch": 100.5,
             "events": [dict(event, pid=222)], "pid": 222, "evicted": 3},
        ]
        path = tracing.merge_timelines(
            sources, str(tmp_path / "merged.json")
        )
        assert path == str(tmp_path / "merged.json")
        doc = json.loads((tmp_path / "merged.json").read_text())
        spans = {e["pid"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert spans[111]["ts"] == pytest.approx(1000.0)
        # The later epoch shifts by the offset against the EARLIEST one.
        assert spans[222]["ts"] == pytest.approx(1000.0 + 0.5e6)
        lanes = {
            m["pid"]: m["args"]["name"] for m in doc["traceEvents"]
            if m["ph"] == "M" and m["name"] == "process_name"
        }
        assert lanes == {111: "fleet", 222: "replica 0"}
        assert doc["otherData"]["evicted_events"] == 3


class TestRequestStitching:
    """report.py's trace-id machinery (ISSUE 16): per-request lifecycle
    stitching, the fleet TTFT decomposition, the --trace drill-down,
    and graceful degradation on partial/untraced timelines."""

    def _traced_dump(self, tmp_path):
        """A hand-built two-request timeline with known milestone gaps.

        t1 lives a full fleet lifecycle (route -> engine queue ->
        prefill -> shared chunk + verify -> terminal).  t2 only ever
        appears in the shared chunk's slot map — the shape left behind
        when the ring buffer evicted its early spans.
        """
        with tracing.collecting():
            base = time.perf_counter()
            tracing.record_span("fleet/route", base, base + 0.010,
                                trace_id="t1", replica=0, attempt=1,
                                queue_s=0.050)
            tracing.record_span("serve/queue_wait", base + 0.010,
                                base + 0.030, trace_id="t1")
            tracing.record_span("serve/prefill", base + 0.030,
                                base + 0.050, trace_id="t1")
            tracing.record_span("serve/chunk", base + 0.050, base + 0.060,
                                traces={"0": "t1", "1": "t2"})
            tracing.record_span("serve/verify", base + 0.060, base + 0.062,
                                traces={"0": "t1"}, accepted=3)
            tracing.record_span("serve/request", base, base + 0.080,
                                trace_id="t1", ttft_s=0.070, tokens=4)
            return tracing.dump_timeline(str(tmp_path / "traced.json"))

    def test_request_summary_stitches_full_and_partial_rows(self, tmp_path):
        report = report_lib.TraceReport.from_file(self._traced_dump(tmp_path))
        summary = report.request_summary()
        assert set(summary) == {"t1", "t2"}
        t1 = summary["t1"]
        assert t1["complete"] and t1["routes"] == 1 and t1["failovers"] == 0
        assert t1["queue_s"] == pytest.approx(0.050)
        assert t1["route_s"] == pytest.approx(0.010, abs=1e-4)
        assert t1["engine_queue_s"] == pytest.approx(0.020, abs=1e-4)
        assert t1["prefill_s"] == pytest.approx(0.020, abs=1e-4)
        assert t1["swapin_s"] == 0.0
        assert t1["chunks"] == 1
        assert t1["spec_accepted"] == 3  # batch-level verify credit
        assert t1["ttft_s"] == pytest.approx(0.070)
        # fleet TTFT = fleet queue + routing + engine TTFT.
        assert t1["fleet_ttft_s"] == pytest.approx(0.130, abs=1e-3)
        assert t1["latency_s"] == pytest.approx(0.080, abs=1e-4)
        assert t1["tokens"] == 4 and not t1["shed"]
        # t2 rode one shared chunk and nothing else survived: the row
        # degrades instead of crashing or vanishing.
        t2 = summary["t2"]
        assert not t2["complete"] and t2["chunks"] == 1
        assert t2["routes"] == 0 and t2["queue_s"] is None
        assert t2["ttft_s"] is None

    def test_ttft_decomposition_shares(self, tmp_path):
        report = report_lib.TraceReport.from_file(self._traced_dump(tmp_path))
        decomposition = report.ttft_decomposition()
        # Only t1 has a terminal span; t2 cannot decompose.
        assert decomposition["requests"] == 1
        assert decomposition["ttft_p50_s"] == pytest.approx(0.130, abs=1e-3)
        assert decomposition["ttft_p99_s"] == pytest.approx(0.130, abs=1e-3)
        shares = decomposition["shares"]
        assert set(shares) == set(report_lib.TraceReport.TTFT_COMPONENTS)
        total = 0.130
        assert shares["queue"]["p50"] == pytest.approx(0.070 / total, abs=1e-2)
        assert shares["route"]["p50"] == pytest.approx(0.010 / total, abs=1e-2)
        assert shares["swapin"]["p50"] == 0.0
        assert shares["prefill"]["p50"] == pytest.approx(
            0.020 / total, abs=1e-2
        )
        # first_decode is the remainder after the attributable phases.
        assert shares["first_decode"]["p50"] == pytest.approx(
            0.030 / total, abs=1e-2
        )

    def test_render_includes_traced_sections(self, tmp_path):
        rendered = report_lib.TraceReport.from_file(
            self._traced_dump(tmp_path)
        ).render()
        assert "traced requests: 2 · 1 complete" in rendered
        assert "TTFT decomposition" in rendered
        assert "first_decode" in rendered

    def test_render_trace_and_cli_drilldown(self, tmp_path, capsys):
        path = self._traced_dump(tmp_path)
        rendered = report_lib.TraceReport.from_file(path).render_trace("t1")
        assert "trace t1: 6 span(s)" in rendered
        assert "fleet/route" in rendered and "serve/request" in rendered
        assert "routes 1" in rendered and "4 tokens" in rendered
        assert "3 spec-accepted tokens" in rendered
        assert report_lib.main([path, "--trace", "t1"]) == 0
        assert "fleet/route" in capsys.readouterr().out
        assert report_lib.main([path, "--trace", "zzz"]) == 2
        assert "not found" in capsys.readouterr().err

    def test_untraced_timeline_degrades_to_none(self, tmp_path):
        with tracing.collecting():
            with tracing.span("serve/prefill"):
                pass
            path = tracing.dump_timeline(str(tmp_path / "plain.json"))
        report = report_lib.TraceReport.from_file(path)
        assert report.request_summary() is None
        assert report.ttft_decomposition() is None
        assert report.render_trace("t1") is None
        rendered = report.render()
        assert "traced requests" not in rendered
        assert "TTFT decomposition" not in rendered

    def test_untraced_terminal_span_is_not_a_qos_class(self, tmp_path):
        # A traced FIFO engine emits serve/request WITHOUT a priority
        # attribute; it must never surface as a phantom QoS class.
        with tracing.collecting():
            now = time.perf_counter()
            tracing.record_span("serve/request", now - 0.01, now,
                                trace_id="t1", ttft_s=0.005, tokens=2)
            path = tracing.dump_timeline(str(tmp_path / "fifo.json"))
        report = report_lib.TraceReport.from_file(path)
        assert report.qos_summary() is None
        assert "QoS classes" not in report.render()

    def test_evicted_early_spans_still_stitch_the_terminal(self, tmp_path):
        # Ring-buffer churn drops t1's route span; the summary row
        # degrades (routes 0, queue None) but stays complete, and the
        # per-name aggregates remain exact (satellite: eviction
        # coverage).
        with tracing.collecting(capacity=3) as col:
            base = time.perf_counter()
            tracing.record_span("fleet/route", base, base + 0.010,
                                trace_id="t1", queue_s=0.050)
            for _ in range(40):
                with tracing.span("churn"):
                    pass
            tracing.record_span("serve/request", base, base + 0.080,
                                trace_id="t1", ttft_s=0.070, tokens=4)
            assert col.evicted >= 1
            assert col.aggregates()["churn"]["count"] == 40
            assert col.aggregates()["fleet/route"]["count"] == 1
            path = tracing.dump_timeline(str(tmp_path / "evicted.json"))
        summary = report_lib.TraceReport.from_file(path).request_summary()
        row = summary["t1"]
        assert row["complete"] and row["ttft_s"] == pytest.approx(0.070)
        assert row["routes"] == 0 and row["queue_s"] is None
        assert row["fleet_ttft_s"] == pytest.approx(0.070)  # nothing to add
