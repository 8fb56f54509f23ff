"""Tuner tests: converters, engine, local study service (incl. true
multi-process distributed tuning), Vizier REST semantics with fakes, and a
CloudTuner search over a real (tiny) Trainer.

Pattern parity: reference tuner/tests/unit (utils_test, optimizer_client_test
429/409 handling, tuner_test) and the multiprocessing distributed-tuning
integration rig (tuner_integration_test.py:283-296).
"""

import multiprocessing
import os
import time

import numpy as np
import pytest

from cloud_tpu.tuner import (
    CloudOracle,
    CloudTuner,
    HyperParameters,
    LocalStudyService,
    Objective,
    Trial,
    TrialStatus,
    Tuner,
    vizier_utils,
)
from cloud_tpu.tuner.engine import RandomSearchOracle
from cloud_tpu.tuner.vizier_client import VizierStudyService
from cloud_tpu.utils.api_client import ApiError


class TestHyperParameters:
    def test_register_and_defaults(self):
        hp = HyperParameters()
        lr = hp.Float("lr", 1e-4, 1e-1, sampling="log")
        units = hp.Int("units", 32, 128, step=32)
        act = hp.Choice("act", ["relu", "gelu"])
        flag = hp.Boolean("flag")
        assert lr == 1e-4 and units == 32 and act == "relu" and flag is False
        assert [s.name for s in hp.space] == ["lr", "units", "act", "flag"]

    def test_sampling_respects_bounds(self):
        hp = HyperParameters()
        hp.Float("lr", 1e-4, 1e-1, sampling="log")
        hp.Int("units", 32, 128, step=32)
        import random

        for _ in range(50):
            values = hp.sample(random.Random())
            assert 1e-4 <= values["lr"] <= 1e-1
            assert values["units"] in (32, 64, 96, 128)

    def test_copy_with_values(self):
        hp = HyperParameters()
        hp.Float("lr", 0.1, 1.0)
        hp2 = hp.copy_with_values({"lr": 0.5})
        assert hp2.get("lr") == 0.5
        assert hp.get("lr") == 0.1


class TestVizierConverters:
    def test_study_config_round_trip(self):
        hp = HyperParameters()
        hp.Float("lr", 1e-4, 1e-1, sampling="log")
        hp.Int("units", 32, 512)
        hp.Int("stepped", 2, 8, step=2)
        hp.Choice("act", ["relu", "gelu"])
        hp.Boolean("flag")
        config = vizier_utils.make_study_config(Objective("accuracy", "max"), hp)
        assert config["metrics"] == [{"metric": "accuracy", "goal": "MAXIMIZE"}]
        types = {p["parameter"]: p["type"] for p in config["parameters"]}
        assert types == {
            "lr": "DOUBLE", "units": "INTEGER", "stepped": "DISCRETE",
            "act": "CATEGORICAL", "flag": "CATEGORICAL",
        }
        lr = next(p for p in config["parameters"] if p["parameter"] == "lr")
        assert lr["scaleType"] == "UNIT_LOG_SCALE"

        back = vizier_utils.convert_study_config_to_hps(config)
        names = {s.name for s in back.space}
        assert names == {"lr", "units", "stepped", "act", "flag"}

    def test_coerce_values_restores_native_types(self):
        hp = HyperParameters()
        hp.Choice("hidden", [64, 128])  # numeric Choice -> DISCRETE doubles
        hp.Choice("act", ["relu", "gelu"])
        hp.Int("units", 32, 512)
        hp.Boolean("flag")
        out = vizier_utils.coerce_values(
            hp,
            {"hidden": 64.0, "act": "gelu", "units": 48.0, "flag": "False"},
        )
        assert out == {"hidden": 64, "act": "gelu", "units": 48, "flag": False}
        assert type(out["hidden"]) is int

    def test_trial_to_values(self):
        trial = {
            "name": "projects/p/locations/r/studies/s/trials/7",
            "parameters": [
                {"parameter": "lr", "floatValue": 0.01},
                {"parameter": "units", "intValue": "64"},
                {"parameter": "act", "stringValue": "gelu"},
            ],
        }
        assert vizier_utils.convert_vizier_trial_to_values(trial) == {
            "lr": 0.01, "units": 64, "act": "gelu",
        }


class FakeTrainer:
    """Quadratic objective: loss = (lr - 0.3)^2, reported per epoch."""

    def __init__(self, lr):
        self.lr = lr
        self.stop_training = False

    def fit(self, *, epochs=1, callbacks=(), **kw):
        for epoch in range(epochs):
            logs = {"loss": (self.lr - 0.3) ** 2 + 0.01 * epoch}
            for cb in callbacks:
                cb.on_epoch_end(epoch, logs, self)
            if self.stop_training:
                break


class TestEngine:
    def test_random_search_finds_good_lr(self):
        hp = HyperParameters()
        hp.Float("lr", 0.0, 1.0)
        oracle = RandomSearchOracle(Objective("loss", "min"), hp,
                                    max_trials=30, seed=0)
        tuner = Tuner(lambda h: FakeTrainer(h.get("lr")), oracle)
        tuner.search(epochs=1)
        best = tuner.get_best_hyperparameters(1)[0]
        assert abs(best.get("lr") - 0.3) < 0.15
        assert len(oracle.trials) == 30

    def test_infeasible_trials_are_recorded(self):
        hp = HyperParameters()
        hp.Float("lr", 0.0, 1.0)
        oracle = RandomSearchOracle(Objective("loss", "min"), hp, max_trials=3)

        def broken(h):
            raise RuntimeError("boom")

        tuner = Tuner(broken, oracle)
        tuner.search(epochs=1)
        assert all(
            t.status == TrialStatus.INFEASIBLE for t in oracle.trials.values()
        )


def _study_config():
    hp = HyperParameters()
    hp.Float("lr", 0.0, 1.0)
    return vizier_utils.make_study_config(Objective("loss", "min"), hp)


class TestLocalStudyService:
    def test_exhaustion(self, tmp_path):
        svc = LocalStudyService("s1", str(tmp_path), max_trials=2)
        svc.create_or_load_study(_study_config())
        assert svc.get_suggestion("w0") is not None
        assert svc.get_suggestion("w1") is not None
        assert svc.get_suggestion("w0") is None

    def test_trial_lifecycle(self, tmp_path):
        svc = LocalStudyService("s2", str(tmp_path), max_trials=5)
        svc.create_or_load_study(_study_config())
        trial_id, values = svc.get_suggestion("w0")
        assert 0.0 <= values["lr"] <= 1.0
        svc.report_intermediate(trial_id, 0, 0.5)
        svc.complete_trial(trial_id, 0.5)
        trials = svc.list_trials()
        assert trials[0]["status"] == "COMPLETED"
        assert trials[0]["final"] == 0.5

    def test_median_stopping(self, tmp_path):
        svc = LocalStudyService("s3", str(tmp_path), max_trials=10)
        svc.create_or_load_study(_study_config())
        ids = [svc.get_suggestion(f"w{i}")[0] for i in range(5)]
        # four good trials, one bad
        for tid in ids[:4]:
            svc.report_intermediate(tid, 0, 0.1)
        svc.report_intermediate(ids[4], 0, 5.0)
        assert svc.should_stop(ids[4]) is True
        assert svc.should_stop(ids[0]) is False


def _worker(args):
    directory, worker_id = args
    svc = LocalStudyService("dist", directory, max_trials=12)
    svc.create_or_load_study(_study_config())
    oracle = CloudOracle(svc, objective="loss",
                         hyperparameters=_hp(), max_trials=12)
    tuner = Tuner(lambda h: FakeTrainer(h.get("lr")), oracle,
                  tuner_id=f"tuner{worker_id}")
    _wait_for_all_workers(directory + "-ready", worker_id)
    tuner.search(epochs=1)
    return len(oracle.trials)


def _wait_for_all_workers(ready_dir, worker_id, workers=4, timeout=60.0):
    """Start searching together: on a loaded machine the first two
    processes of the pool would else finish the whole budget before the
    last two are up, and "every worker took part" measures the load."""
    os.makedirs(ready_dir, exist_ok=True)
    open(os.path.join(ready_dir, str(worker_id)), "w").close()
    deadline = time.monotonic() + timeout
    while (len(os.listdir(ready_dir)) < workers
           and time.monotonic() < deadline):
        time.sleep(0.01)


def _hp():
    hp = HyperParameters()
    hp.Float("lr", 0.0, 1.0)
    return hp


class TestDistributedTuning:
    def test_four_workers_share_one_study(self, tmp_path):
        """True multi-process distributed tuning over one study file
        (reference simulated exactly this with a Pool of 4)."""
        with multiprocessing.Pool(4) as pool:
            counts = pool.map(_worker, [(str(tmp_path), i) for i in range(4)])
        svc = LocalStudyService("dist", str(tmp_path), max_trials=12)
        trials = svc.list_trials()
        assert len(trials) == 12  # budget respected globally, no dupes
        assert sum(counts) == 12
        assert all(t["status"] == "COMPLETED" for t in trials)
        # every worker's client_id appears (work actually distributed)
        assert len({t["client_id"] for t in trials}) == 4


class FakeSession:
    def __init__(self, script):
        self.script = list(script)  # (method_substr, response_or_exc)
        self.calls = []

    def _dispatch(self, method, url, body=None, params=None):
        self.calls.append((method, url, body, params))
        if not self.script:
            return {}
        matcher, response = self.script.pop(0)
        assert matcher in f"{method} {url}", (matcher, method, url)
        if isinstance(response, Exception):
            raise response
        return response

    def post(self, url, body=None, params=None):
        return self._dispatch("POST", url, body, params)

    def get(self, url, params=None):
        return self._dispatch("GET", url, None, params)

    def delete(self, url):
        return self._dispatch("DELETE", url)


class TestVizierClient:
    def _service(self, script):
        return VizierStudyService(
            "proj", "us-central1", "study1",
            session=FakeSession(script), sleeper=lambda s: None,
        )

    def test_create_or_load_handles_409(self):
        svc = self._service([
            ("POST", ApiError(409, "exists")),
            ("GET", {"name": "studies/study1"}),
        ])
        svc.create_or_load_study(_study_config())  # no raise

    def test_create_propagates_other_errors(self):
        svc = self._service([("POST", ApiError(500, "boom"))])
        with pytest.raises(ApiError):
            svc.create_or_load_study(_study_config())

    def test_suggestion_with_lro_poll(self):
        svc = self._service([
            ("trials:suggest", {"name": "operations/op1", "done": False}),
            ("GET", {"name": "operations/op1", "done": True,
                     "response": {"trials": [{
                         "name": ".../trials/3",
                         "parameters": [{"parameter": "lr", "floatValue": 0.2}],
                     }]}}),
        ])
        trial_id, values = svc.get_suggestion("w0")
        assert trial_id == "3"
        assert values == {"lr": 0.2}

    def test_429_means_exhausted(self):
        svc = self._service([("trials:suggest", ApiError(429, "exhausted"))])
        assert svc.get_suggestion("w0") is None

    def test_early_stop_true_stops_trial(self):
        svc = self._service([
            (":checkEarlyStoppingState",
             {"name": "op", "done": True, "response": {"shouldStop": True}}),
            (":stop", {}),
        ])
        assert svc.should_stop("5") is True

    def test_complete_with_final_measurement(self):
        # A worker that created the study knows the objective name and must
        # stamp it on the final measurement (Measurement.Metric requires it).
        session = FakeSession([("studies", {}), (":complete", {})])
        svc = VizierStudyService("p", "r", "s", session=session,
                                 sleeper=lambda s: None)
        svc.create_or_load_study(_study_config())
        svc.complete_trial("7", 0.42)
        _, url, body, _ = session.calls[-1]
        assert url.endswith("trials/7:complete")
        assert body == {
            "finalMeasurement": {
                "metrics": [{"metric": "loss", "value": 0.42}]
            }
        }

    def test_measurement_metric_name_fetched_when_study_loaded(self):
        # A worker that only loaded the study fetches the objective name
        # from the study config once, then reuses it.
        session = FakeSession([
            ("GET", {"studyConfig": _study_config()}),
            (":addMeasurement", {}),
            (":addMeasurement", {}),
        ])
        svc = VizierStudyService("p", "r", "s", session=session,
                                 sleeper=lambda s: None)
        svc.report_intermediate("7", 1, 0.9)
        svc.report_intermediate("7", 2, 0.8)
        gets = [c for c in session.calls if c[0] == "GET"]
        assert len(gets) == 1
        _, _, body, _ = session.calls[-1]
        assert body["measurement"]["metrics"] == [
            {"metric": "loss", "value": 0.8}
        ]


class TestCloudTunerEndToEnd:
    def test_search_with_local_service(self, tmp_path):
        svc = LocalStudyService("e2e", str(tmp_path), max_trials=8, seed=7)
        tuner = CloudTuner(
            lambda h: FakeTrainer(h.get("lr")),
            svc,
            objective="loss",
            hyperparameters=_hp(),
            max_trials=8,
        )
        tuner.search(epochs=2)
        best = tuner.get_best_hyperparameters(1)
        assert best, "no completed trials"
        assert 0.0 <= best[0].get("lr") <= 1.0
        assert all(
            t["status"] == "COMPLETED" for t in svc.list_trials()
        )

    def test_type_fidelity_through_service(self, tmp_path):
        """Boolean/Int/Fixed survive the lossy study-config wire format."""
        hp = HyperParameters()
        hp.Boolean("use_bias")
        hp.Int("units", 2, 8, step=2)
        hp.Fixed("tag", 42)
        hp.Float("lr", 0.0, 1.0)
        svc = LocalStudyService("types", str(tmp_path), max_trials=6, seed=1)
        oracle = CloudOracle(svc, objective="loss", hyperparameters=hp,
                             max_trials=6)
        seen_bools = set()
        for _ in range(6):
            trial = oracle.create_trial("t0")
            assert isinstance(trial.hyperparameters.get("use_bias"), bool)
            assert isinstance(trial.hyperparameters.get("units"), int)
            assert trial.hyperparameters.get("tag") == 42
            assert isinstance(trial.hyperparameters.get("lr"), float)
            seen_bools.add(trial.hyperparameters.get("use_bias"))
        assert seen_bools == {True, False}  # both values actually explored

    def test_study_config_xor_objective(self, tmp_path):
        svc = LocalStudyService("x", str(tmp_path))
        with pytest.raises(ValueError, match="not both"):
            CloudOracle(svc, objective="loss", hyperparameters=_hp(),
                        study_config=_study_config())
        with pytest.raises(ValueError, match="objective and hyperparameters"):
            CloudOracle(svc)
