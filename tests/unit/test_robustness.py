"""Fault-tolerance tests: retries, chaos injection, deadlines, drains.

The ISSUE 7 contracts, each pinned by a fast deterministic test (the
end-to-end chaos composition lives in scripts/check_chaos.py, wired
below as the slow harness):

* ``utils.retries.RetryPolicy`` — typed transient-vs-permanent
  classification, attempt/elapsed budgets, Retry-After floors, jittered
  backoff, ``retry/*`` span accounting.
* ``utils.faults`` — deterministic nth/every-k triggers,
  raise/hang/corrupt modes, env propagation to children, no-nesting.
* ``utils.api_client`` — 429/5xx and transport errors become typed
  ``ApiTransientError`` (absorbed by session retries); permanent 4xx
  fails fast, untouched.
* serving — queued requests past their ``deadline_s`` shed with
  ``DeadlineExceededError`` before occupying a slot, survivors keep
  token parity with per-request generate(); a hung dispatch trips the
  watchdog, fails live slots typed, flips ``health()`` unhealthy.
* preemption drain — a real SIGTERM mid-fit checkpoints within one
  dispatch window and a fresh Trainer resumes from it.
* ``training.checkpoint`` — a crashed periodic save doesn't kill the
  fit; a corrupt latest checkpoint is quarantined and the restore WALKS
  BACK to the newest intact step (ISSUE 9's durable-resume contract —
  the full lineage/manifold/data-resume suite lives in
  tests/unit/test_durability.py); only when no candidate survives does
  resume log "starting fresh" and return False.
"""

import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cloud_tpu.monitoring import tracing
from cloud_tpu.utils import api_client, faults, retries

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    """No test may leave a fault plan (or its env export) behind."""
    yield
    faults._clear_for_tests()
    os.environ.pop(faults.ENV_FAULT_PLAN, None)


# --- RetryPolicy ----------------------------------------------------------


class TestRetryPolicy:
    def _policy(self, sleeps, **kw):
        kw.setdefault("max_attempts", 4)
        kw.setdefault("initial_backoff_s", 1.0)
        kw.setdefault("jitter", False)
        kw.setdefault("sleep", sleeps.append)
        return retries.RetryPolicy(**kw)

    def test_transient_retried_until_success(self):
        sleeps = []
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise api_client.ApiTransientError(503, "blip")
            return "done"

        policy = self._policy(sleeps)
        assert policy.call(flaky, name="t") == "done"
        assert len(calls) == 3
        assert sleeps == [1.0, 2.0]  # exponential, jitter off

    def test_permanent_fails_fast(self):
        sleeps = []
        calls = []

        def denied():
            calls.append(1)
            raise api_client.ApiError(403, "forbidden")

        with pytest.raises(api_client.ApiError, match="403"):
            self._policy(sleeps).call(denied)
        assert len(calls) == 1 and sleeps == []

    def test_attempt_budget_exhausted_raises_last(self):
        policy = self._policy([], max_attempts=3, sleep=lambda _s: None)
        calls = []

        def always():
            calls.append(1)
            raise api_client.ApiTransientError(500, f"#{len(calls)}")

        with pytest.raises(api_client.ApiTransientError, match="#3"):
            policy.call(always)
        assert len(calls) == 3

    def test_retry_after_floors_backoff(self):
        sleeps = []

        def throttled():
            if not sleeps:
                raise api_client.ApiTransientError(
                    429, "slow down", retry_after=7.5
                )
            return "ok"

        assert self._policy(sleeps).call(throttled) == "ok"
        assert sleeps == [7.5]  # server hint beats the 1.0s curve

    def test_max_elapsed_budget_refuses_to_sleep_past(self):
        # Backoff would be 10s; a 0.01s budget must give up instead.
        policy = self._policy(
            [], initial_backoff_s=10.0, max_elapsed_s=0.01,
            sleep=lambda _s: pytest.fail("must not sleep past the budget"),
        )
        calls = []

        def always():
            calls.append(1)
            raise api_client.ApiTransientError(503, "x")

        with pytest.raises(api_client.ApiTransientError):
            policy.call(always)
        assert len(calls) == 1

    def test_jitter_deterministic_and_bounded(self):
        import random

        policy = retries.RetryPolicy(
            initial_backoff_s=4.0, rng=random.Random(7)
        )
        values = [policy.backoff_s(0) for _ in range(20)]
        assert all(0.0 <= v <= 4.0 for v in values)  # full jitter
        assert len(set(values)) > 1  # actually random
        replay = retries.RetryPolicy(
            initial_backoff_s=4.0, rng=random.Random(7)
        )
        assert values == [replay.backoff_s(0) for _ in range(20)]

    def test_span_records_attempts_and_outcome(self):
        def flaky(state=[]):
            state.append(1)
            if len(state) < 2:
                raise api_client.ApiTransientError(503, "x")
            return "ok"

        with tracing.collecting() as collector:
            self._policy([], sleep=lambda _s: None).call(flaky, name="probe")
        spans = [e for e in collector.events()
                 if e["name"] == "retry/probe"]
        assert len(spans) == 1
        assert spans[0]["args"]["attempts"] == 2
        assert spans[0]["args"]["outcome"] == "ok"

    def test_first_try_success_records_no_span(self):
        with tracing.collecting() as collector:
            self._policy([]).call(lambda: "ok", name="quiet")
        assert not [e for e in collector.events()
                    if e["name"].startswith("retry/")]

    def test_jittered_interval_bounds(self):
        values = [retries.jittered(10.0) for _ in range(50)]
        assert all(8.0 <= v <= 12.0 for v in values)
        assert len(set(values)) > 1


# --- faults ---------------------------------------------------------------


class TestFaults:
    def test_nth_trigger_fires_once_typed(self):
        plan = [{"site": "api.request", "mode": "raise",
                 "error": "transient", "nth": 2}]
        with faults.inject(plan) as active:
            assert faults.fault_point("api.request", "a") == "a"
            with pytest.raises(api_client.ApiTransientError):
                faults.fault_point("api.request")
            assert faults.fault_point("api.request", "c") == "c"
        assert active.fired() == {"api.request": 1}
        assert active.calls() == {"api.request": 3}

    def test_times_bounds_every_call_mode(self):
        plan = [{"site": "s", "times": 2}]
        with faults.inject(plan) as active:
            for _ in range(2):
                with pytest.raises(faults.FaultInjected):
                    faults.fault_point("s")
            faults.fault_point("s")  # budget spent: clean
        assert active.fired() == {"s": 2}

    def test_every_k_trigger(self):
        plan = [{"site": "s", "every": 3, "times": 2}]
        fired = []
        with faults.inject(plan):
            for i in range(1, 10):
                try:
                    faults.fault_point("s")
                except faults.FaultInjected:
                    fired.append(i)
        assert fired == [3, 6]

    def test_hang_mode_sleeps(self):
        naps = []
        plan = [{"site": "s", "mode": "hang", "hang_s": 5.0, "nth": 1}]
        with faults.inject(plan):
            assert faults.fault_point("s", "x", sleep=naps.append) == "x"
        assert naps == [5.0]

    def test_corrupt_mode_replaces_result(self):
        plan = [{"site": "s", "mode": "corrupt", "value": -1, "nth": 1}]
        with faults.inject(plan):
            assert faults.fault_point("s", result="good") == -1
            assert faults.fault_point("s", result="good") == "good"

    def test_env_propagation_round_trip(self):
        plan = [{"site": "child.seam", "nth": 1}]
        with faults.inject(plan):
            raw = os.environ[faults.ENV_FAULT_PLAN]
            assert json.loads(raw) == plan
            # A "child process": fresh module state, install from env.
            faults._clear_for_tests()
            assert faults.maybe_install_from_env()
            with pytest.raises(faults.FaultInjected):
                faults.fault_point("child.seam")
        assert faults.ENV_FAULT_PLAN not in os.environ

    def test_nested_inject_rejected(self):
        with faults.inject([{"site": "a"}]):
            with pytest.raises(RuntimeError, match="already active"):
                with faults.inject([{"site": "b"}]):
                    pass

    def test_unserializable_plan_rejected_without_leaking(self):
        """A plan whose 'value' can't round-trip through JSON must fail
        BEFORE installation — not leave a plan installed forever with no
        __exit__ to remove it."""
        with pytest.raises(TypeError):
            faults.inject(
                [{"site": "s", "mode": "corrupt", "value": object()}]
            )
        assert faults.active_plan() is None
        with faults.inject([{"site": "s"}]):  # not "already active"
            pass

    def test_malformed_rules_rejected(self):
        for bad in (
            [{"mode": "raise"}],                      # no site
            [{"site": "s", "mode": "explode"}],       # unknown mode
            [{"site": "s", "nth": 1, "every": 2}],    # both triggers
            [{"site": "s", "bogus": 1}],              # unknown key
        ):
            with pytest.raises(ValueError):
                faults.FaultPlan(bad)

    def test_disabled_is_passthrough(self):
        assert faults.fault_point("anything", 42) == 42


# --- api_client typing + session retries ----------------------------------


class _ScriptedHttp:
    """requests.Session stand-in returning scripted (status, headers)."""

    class _Resp:
        def __init__(self, status, headers=None, payload=None):
            self.status_code = status
            self.headers = headers or {}
            self.text = f"status {status}"
            body = json.dumps(payload or {"ok": True}).encode()
            self.content = body

        def json(self):
            return json.loads(self.content)

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def request(self, method, url, headers=None, params=None, data=None):
        self.calls += 1
        item = self.script.pop(0)
        if isinstance(item, BaseException):
            raise item
        status, resp_headers = item if isinstance(item, tuple) else (item, {})
        return self._Resp(status, resp_headers)


class TestApiClientTyping:
    def _session(self, script, **policy_kw):
        policy_kw.setdefault("max_attempts", 4)
        policy_kw.setdefault("initial_backoff_s", 0.0)
        policy_kw.setdefault("sleep", lambda _s: None)
        return api_client.GcpApiSession(
            requests_session=_ScriptedHttp(script),
            retry=retries.RetryPolicy(**policy_kw),
        ), None

    def test_5xx_retried_to_success(self):
        session, _ = self._session([503, 502, 200])
        assert session.get("http://api/x") == {"ok": True}
        assert session._session.calls == 3

    def test_429_retry_after_header_honored(self):
        sleeps = []
        session = api_client.GcpApiSession(
            requests_session=_ScriptedHttp([(429, {"Retry-After": "3"}),
                                            200]),
            retry=retries.RetryPolicy(
                max_attempts=3, initial_backoff_s=0.0, jitter=False,
                sleep=sleeps.append,
            ),
        )
        assert session.get("http://api/x") == {"ok": True}
        assert sleeps == [3.0]

    def test_connection_error_wrapped_transient_and_retried(self):
        session, _ = self._session([ConnectionResetError("reset"), 200])
        assert session.get("http://api/x") == {"ok": True}

    def test_transport_error_escapes_typed_when_budget_spent(self):
        session, _ = self._session(
            [ConnectionResetError("r")] * 2, max_attempts=2,
        )
        with pytest.raises(api_client.ApiTransientError,
                           match="transport error"):
            session.get("http://api/x")

    def test_post_not_resent_after_ambiguous_transport_error(self):
        """A transport failure on a non-idempotent POST may have landed
        server-side: the session must surface it typed, NOT blindly
        re-send (a second Cloud Build, a double-completed trial)."""
        session, _ = self._session([ConnectionResetError("lost"), 200])
        with pytest.raises(api_client.ApiTransientError,
                           match="transport error"):
            session.post("http://api/x", body={"a": 1})
        assert session._session.calls == 1

    def test_post_5xx_response_still_retried(self):
        """A 429/5xx RESPONSE means the server answered without doing
        the work — POSTs stay retryable for those."""
        session, _ = self._session([503, 200])
        assert session.post("http://api/x", body={"a": 1}) == {"ok": True}
        assert session._session.calls == 2

    def test_permanent_4xx_fails_first_try(self):
        session, _ = self._session([404, 200])
        with pytest.raises(api_client.ApiError) as excinfo:
            session.get("http://api/x")
        assert not isinstance(excinfo.value, api_client.ApiTransientError)
        assert session._session.calls == 1

    def test_retry_none_single_attempt(self):
        session = api_client.GcpApiSession(
            requests_session=_ScriptedHttp([503, 200]), retry=None,
        )
        with pytest.raises(api_client.ApiTransientError):
            session.get("http://api/x")

    def test_fault_point_drives_session(self):
        """The chaos seam sits INSIDE the session, upstream of retries:
        injected 503s are absorbed exactly like real ones."""
        session, _ = self._session([200])
        plan = [{"site": "api.request", "mode": "raise",
                 "error": "transient", "times": 2}]
        with tracing.collecting() as collector:
            with faults.inject(plan) as active:
                assert session.get("http://api/x") == {"ok": True}
        assert active.fired() == {"api.request": 2}
        span = [e for e in collector.events()
                if e["name"] == "retry/api_request"][0]
        assert span["args"]["attempts"] == 3  # the acceptance number


# --- deploy consumes the policy -------------------------------------------


class TestDeployRetries:
    def _fixtures(self):
        from cloud_tpu.core import deploy, machine_config
        from cloud_tpu.parallel import planner

        tpu = machine_config.COMMON_MACHINE_CONFIGS["TPU"]
        return deploy, tpu, planner.plan_mesh(chief_config=tpu)

    def test_submit_survives_two_transient_failures(self):
        deploy, tpu, plan = self._fixtures()
        sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "unit"))
        from fakes import RecordingSession

        class Flaky(RecordingSession):
            failures = 2

            def post(self, url, body=None, params=None):
                if self.failures:
                    self.failures -= 1
                    raise api_client.ApiTransientError(503, "quota blip")
                return super().post(url, body=body, params=params)

        session = Flaky(responses=[{"name": "ops/1", "done": True},
                                   {"state": "READY"}])
        info = deploy.deploy_job(
            "img", tpu, 0, plan, session=session, project="p", zone="z",
            sleep=lambda _s: None,
        )
        assert info["job_id"].startswith("cloud-tpu-train-")
        posts = [c for c in session.calls if c[0] == "POST"]
        assert len(posts) == 1  # failures raised before recording

    def test_submit_gives_up_on_permanent_error(self):
        deploy, tpu, plan = self._fixtures()
        sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "unit"))
        from fakes import RecordingSession

        class Denied(RecordingSession):
            def post(self, url, body=None, params=None):
                self.calls.append(("POST", url, body, params))
                raise api_client.ApiError(403, "forbidden")

        with pytest.raises(api_client.ApiError, match="403"):
            deploy.deploy_job(
                "img", tpu, 0, plan, session=Denied(), project="p",
                zone="z", sleep=lambda _s: None,
            )

    def test_409_after_ambiguous_create_treated_as_created(self):
        """Create is not idempotent: when a retried POST gets 409
        ALREADY_EXISTS (the lost first attempt landed), the deploy must
        proceed to the READY await — not fail and roll back."""
        deploy, tpu, plan = self._fixtures()
        sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "unit"))
        from fakes import RecordingSession

        class AmbiguousCreate(RecordingSession):
            attempts = 0

            def post(self, url, body=None, params=None):
                self.attempts += 1
                if self.attempts == 1:
                    raise api_client.ApiTransientError(0, "response lost")
                raise api_client.ApiError(409, "ALREADY_EXISTS")

        session = AmbiguousCreate(responses=[{"state": "READY"}])
        info = deploy.deploy_job(
            "img", tpu, 0, plan, session=session, project="p", zone="z",
            sleep=lambda _s: None,
        )
        assert info["job_id"].startswith("cloud-tpu-train-")
        assert not [c for c in session.calls if c[0] == "DELETE"]

    def test_first_attempt_409_still_raises(self):
        """A 409 with NO preceding transient means a stale node from a
        caller-supplied job id: adopting it (READY, but running the OLD
        workload) would report success for a job that never started."""
        deploy, tpu, plan = self._fixtures()
        sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "unit"))
        from fakes import RecordingSession

        class StaleNode(RecordingSession):
            def post(self, url, body=None, params=None):
                self.calls.append(("POST", url, body, params))
                raise api_client.ApiError(409, "ALREADY_EXISTS")

        with pytest.raises(api_client.ApiError, match="409"):
            deploy.deploy_job(
                "img", tpu, 0, plan, session=StaleNode(), project="p",
                zone="z", sleep=lambda _s: None,
            )

    def test_ready_poll_retries_transient_blips(self):
        deploy, tpu, plan = self._fixtures()

        calls = []

        class BlippySession:
            def get(self, url, params=None):
                calls.append(url)
                if len(calls) == 1:
                    raise api_client.ApiTransientError(500, "hiccup")
                return {"state": "READY"}

        node = deploy._await_node_ready(
            BlippySession(), "projects/p/locations/z", "n0",
            sleep=lambda _s: None,
        )
        assert node == {"state": "READY"}
        assert len(calls) == 2


# --- serving: deadlines, watchdog, health ---------------------------------


@pytest.fixture(scope="module")
def model():
    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _direct(params, config, prompt, max_new_tokens):
    from cloud_tpu.models import generation

    return generation.generate(
        params, jnp.asarray(prompt[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=max_new_tokens,
        sample=generation.SampleConfig(temperature=0.0),
    )


class TestServingDeadlines:
    def test_expired_requests_shed_survivors_keep_parity(self, model):
        """The acceptance criterion: requests whose deadline expires
        while queued fail typed WITHOUT occupying a slot, and the
        survivors' greedy tokens stay identical to per-request
        generate() — shedding is invisible to the served."""
        from cloud_tpu.serving import (
            DeadlineExceededError, ServeConfig, ServingEngine,
        )

        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2,
        )
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 255, n).astype(np.int32)
                   for n in (3, 5, 8, 4)]
        engine = ServingEngine(params, config, serve, mesh=None,
                               start=False)
        doomed = engine.submit(prompts[0], deadline_s=0.005)
        survivors = [engine.submit(p) for p in prompts[1:]]
        time.sleep(0.05)  # expire the deadline while everything queues
        engine.start()
        with pytest.raises(DeadlineExceededError, match="shed"):
            doomed.result(timeout=120)
        results = [f.result(timeout=120) for f in survivors]
        engine.close()

        for prompt, result in zip(prompts[1:], results):
            want = _direct(params, config, prompt, 5)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        stats = engine.stats()
        assert stats["shed"] == 1
        assert stats["inserts"] == 3  # the shed request never got a slot
        assert stats["completed"] == 3

    def test_unexpired_deadline_serves_normally(self, model):
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=2,
        )
        prompt = np.asarray([5, 9, 17], np.int32)
        with ServingEngine(params, config, serve, mesh=None) as engine:
            result = engine.submit(prompt, deadline_s=120.0).result(
                timeout=120
            )
        want = _direct(params, config, prompt, 4)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )

    def test_bad_deadline_rejected(self, model):
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(max_new_tokens=4, prompt_buckets=(8,),
                            num_slots=1)
        engine = ServingEngine(params, config, serve, mesh=None,
                               start=False)
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(np.asarray([1, 2], np.int32), deadline_s=0)
        engine.close()

class TestDispatchWatchdog:
    def test_hung_chunk_fails_slots_and_marks_unhealthy(self, model):
        """A dispatch hang past dispatch_timeout_s must fail in-flight
        requests typed — within the budget, not after the hang — flip
        health() to unhealthy, and leave zero threads after close()."""
        from cloud_tpu.serving import (
            DispatchTimeoutError, ServeConfig, ServingEngine,
        )

        config, params = model
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, dispatch_timeout_s=1.0, warmup=True,
        )
        prompt = np.asarray([5, 9, 17, 2], np.int32)
        engine = ServingEngine(params, config, serve, mesh=None)
        # AOT-warm the whole grid and serve one request outside the
        # plan: the hang must race a dispatch, not the first compile
        # (which would trip the watchdog by itself).
        engine.wait_ready(timeout=300)
        engine.submit(prompt).result(timeout=300)
        assert engine.health()["healthy"] is True

        plan = [{"site": "serve.chunk", "mode": "hang", "hang_s": 3.0,
                 "nth": 1}]
        with faults.inject(plan):
            future = engine.submit(prompt)
            start = time.perf_counter()
            with pytest.raises(DispatchTimeoutError,
                               match="dispatch_timeout_s"):
                future.result(timeout=30)
            assert time.perf_counter() - start < 2.5  # budget, not hang
            health = engine.health()
            engine.close()
        assert health["healthy"] is False
        assert health["ready"] is False
        assert "dispatch_timeout" in health["reason"]
        assert engine.stats()["watchdog_timeouts"] == 1
        # The finite hang unwound inside close(): no engine thread left.
        leftover = [t for t in threading.enumerate()
                    if t.name.startswith("cloud-tpu-serve")]
        assert leftover == []

    def test_closed_engine_rejects_after_watchdog(self, model):
        from cloud_tpu.serving import (
            EngineClosedError, ServeConfig, ServingEngine,
        )

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=2, dispatch_timeout_s=1.0, warmup=True,
        )
        prompt = np.asarray([4, 7, 1], np.int32)
        engine = ServingEngine(params, config, serve, mesh=None)
        engine.wait_ready(timeout=300)
        engine.submit(prompt).result(timeout=300)
        plan = [{"site": "serve.chunk", "mode": "hang", "hang_s": 2.0,
                 "nth": 1}]
        with faults.inject(plan):
            failing = engine.submit(prompt)
            with pytest.raises(Exception):
                failing.result(timeout=30)
            with pytest.raises(EngineClosedError):
                engine.submit(prompt)
            engine.close()


# --- preemption drain -----------------------------------------------------


def _build_mnist_trainer(ckpt_dir=None, every=2):
    from cloud_tpu.models import mnist
    from cloud_tpu.training import data as data_lib
    from cloud_tpu.training.checkpoint import CheckpointCallback
    from cloud_tpu.training.trainer import Trainer

    cfg = mnist.MnistConfig(hidden_dim=16)
    tr = Trainer(
        functools.partial(mnist.loss_fn, config=cfg),
        optax.sgd(0.1),
        init_fn=functools.partial(mnist.init, config=cfg),
    )
    tr.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ds = data_lib.ArrayDataset(
        {"image": rng.normal(size=(48, 784)).astype(np.float32),
         "label": rng.integers(0, 10, 48).astype(np.int64)},
        batch_size=8,
    )
    cb = None
    if ckpt_dir is not None:
        cb = CheckpointCallback(ckpt_dir, every_n_steps=every)
    return tr, ds, cb


class TestPreemptionDrain:
    @pytest.fixture(autouse=True)
    def _clean_signal_state(self):
        from cloud_tpu.training import preemption

        preemption._reset_for_tests()
        yield
        preemption._reset_for_tests()

    def test_sigterm_checkpoints_within_one_window_and_resumes(
        self, tmp_path
    ):
        """The acceptance criterion: a real SIGTERM mid-fit produces a
        checkpoint at the very step the drain fired (lost work <= one
        dispatch window), and a fresh Trainer +
        CheckpointCallback(resume=True) resumes from it."""
        from cloud_tpu.training import preemption, trainer as trainer_lib
        from cloud_tpu.training.checkpoint import CheckpointManager

        assert preemption.install_sigterm_handler()
        ckpt = str(tmp_path / "drain")
        # Periodic saves far apart (every 100): ONLY the drain save can
        # produce the checkpoint the resume finds.
        tr, ds, cb = _build_mnist_trainer(ckpt, every=100)

        def preempt_at_step_3(step, logs, t):
            if step == 3:
                os.kill(os.getpid(), signal.SIGTERM)

        spy = trainer_lib.LambdaCallback(on_step_end=preempt_at_step_3)
        tr.fit(ds, epochs=2, callbacks=[cb, spy])
        # Signal delivered during step 3's callbacks; the boundary check
        # right after stops the loop: 6 steps/epoch were available but
        # only 3 ran — and the train-end save drained step 3's state.
        assert tr.drained is True
        assert int(tr.state.step) == 3
        assert CheckpointManager(ckpt).latest_step() == 3

        preemption.clear()
        tr2, ds2, cb2 = _build_mnist_trainer(ckpt, every=100)
        seen = []
        spy2 = trainer_lib.LambdaCallback(
            on_step_end=lambda step, logs, t: seen.append(step)
        )
        tr2.fit(ds2, epochs=1, callbacks=[cb2, spy2])
        assert seen[0] == 4  # resumed AFTER the drained step, not at 1
        assert int(tr2.state.step) == 9

    def test_drain_checks_window_boundaries_k_gt_1(self, tmp_path):
        """Fused K-step dispatch: the drain lands at the first WINDOW
        boundary after the event — at most K steps of work lost."""
        from cloud_tpu.training import preemption
        from cloud_tpu.training.checkpoint import CheckpointManager

        ckpt = str(tmp_path / "drain_k")
        tr, ds, cb = _build_mnist_trainer(ckpt, every=100)
        preemption.request_stop("test")
        tr.fit(ds, epochs=2, callbacks=[cb], steps_per_dispatch=2)
        # The event predates fit: the FIRST window (2 steps) completes,
        # then the boundary check drains.
        assert tr.drained is True
        assert int(tr.state.step) == 2
        assert CheckpointManager(ckpt).latest_step() == 2

    def test_drain_metrics_and_span(self):
        from cloud_tpu.monitoring import metrics as metrics_lib
        from cloud_tpu.training import preemption

        tr, ds, _ = _build_mnist_trainer()
        preemption.request_stop("unit test")
        before = metrics_lib.snapshot()["counters"].get("preempt/drains", 0)
        with tracing.collecting() as collector:
            tr.fit(ds, epochs=1)
        after = metrics_lib.snapshot()["counters"].get("preempt/drains", 0)
        assert after == before + 1
        drains = [e for e in collector.events()
                  if e["name"] == "preempt/drain"]
        assert len(drains) == 1
        assert drains[0]["args"]["reason"] == "unit test"

    def test_bootstrap_exits_with_preemption_status(self, tmp_path,
                                                    monkeypatch):
        """The distinct exit status: a drained bootstrap run exits 143
        so supervise_job's recreate path can tell 'checkpointed and
        yielded' from a crash."""
        from cloud_tpu.core import bootstrap

        script = tmp_path / "drainer.py"
        script.write_text(
            "from cloud_tpu.training import preemption\n"
            "preemption.request_stop('eviction notice')\n"
        )
        monkeypatch.setattr(sys, "argv", list(sys.argv))
        monkeypatch.delenv("CLOUD_TPU_RUNNING_REMOTELY", raising=False)
        try:
            with pytest.raises(SystemExit) as excinfo:
                bootstrap.main([f"--entry-point={script}"])
        finally:
            os.environ.pop(bootstrap.ENV_RUNNING_REMOTELY, None)
        assert excinfo.value.code == 143


# --- checkpoint robustness ------------------------------------------------


class TestCheckpointRobustness:
    def test_periodic_save_crash_survivable(self, tmp_path):
        """A crashed every-N save must not kill the fit; the trajectory
        is untouched and the train-end save still lands."""
        from cloud_tpu.monitoring import metrics as metrics_lib
        from cloud_tpu.training.checkpoint import CheckpointManager

        control, ds, _ = _build_mnist_trainer()
        control.fit(ds, epochs=1)

        ckpt = str(tmp_path / "crashy")
        tr, ds2, cb = _build_mnist_trainer(ckpt, every=2)
        before = metrics_lib.snapshot()["counters"].get(
            "checkpoint/save_failures", 0
        )
        plan = [{"site": "checkpoint.save", "mode": "raise", "nth": 1}]
        with faults.inject(plan) as active:
            tr.fit(ds2, epochs=1, callbacks=[cb])
        assert active.fired() == {"checkpoint.save": 1}
        assert int(tr.state.step) == 6  # ran to completion
        np.testing.assert_allclose(
            np.asarray(tr.state.params["hidden"]["kernel"]),
            np.asarray(control.state.params["hidden"]["kernel"]),
            atol=1e-6,
        )
        assert CheckpointManager(ckpt).latest_step() == 6
        after = metrics_lib.snapshot()["counters"].get(
            "checkpoint/save_failures", 0
        )
        assert after == before + 1

    def test_train_end_save_crash_retried_once(self, tmp_path):
        """The train-end save is the drain's one shot: a single crash
        gets one retry with a fresh manager, and the checkpoint still
        lands."""
        from cloud_tpu.training.checkpoint import CheckpointManager

        ckpt = str(tmp_path / "final")
        # every=100: the ONLY save is the train-end one — the injected
        # crash hits it directly.
        tr, ds, cb = _build_mnist_trainer(ckpt, every=100)
        plan = [{"site": "checkpoint.save", "mode": "raise", "nth": 1}]
        with faults.inject(plan) as active:
            tr.fit(ds, epochs=1, callbacks=[cb])
        assert active.fired() == {"checkpoint.save": 1}
        assert CheckpointManager(ckpt).latest_step() == 6

    def test_corrupt_latest_checkpoint_walks_back(self, tmp_path):
        """ISSUE 9's durable-resume contract: a corrupt latest
        checkpoint is quarantined and the restore walks back to the
        newest INTACT step instead of throwing away all progress."""
        from cloud_tpu.training.checkpoint import (
            CheckpointCallback, CheckpointManager, resume_trainer_state,
        )

        ckpt = str(tmp_path / "corrupt")
        tr, ds, cb = _build_mnist_trainer(ckpt, every=2)
        tr.fit(ds, epochs=1, callbacks=[cb])
        manager = CheckpointManager(ckpt)
        latest = manager.latest_step()
        assert latest == 6

        # Corrupt the latest step: garble every file under its dir so
        # the restore reads garbage instead of array data.
        step_dir = os.path.join(ckpt, str(latest))
        assert os.path.isdir(step_dir)
        for root, _dirs, files in os.walk(step_dir):
            for name in files:
                with open(os.path.join(root, name), "wb") as f:
                    f.write(b"\x00corrupt\xff" * 4)

        tr2, _, _ = _build_mnist_trainer()
        assert int(tr2.state.step) == 0
        ok = resume_trainer_state(tr2, CheckpointManager(ckpt))
        assert ok is True
        assert int(tr2.state.step) == 4  # the newest INTACT step
        # The corrupt step left the lineage (quarantined, not deleted).
        assert not os.path.isdir(step_dir)
        assert os.path.isdir(os.path.join(ckpt, "quarantine"))

        # And the callback path composes end to end: training resumes
        # from step 4 instead of dying (or restarting) at on_train_begin.
        cb2 = CheckpointCallback(ckpt, every_n_steps=100)
        tr3, ds3, _ = _build_mnist_trainer()
        tr3.fit(ds3, epochs=1, callbacks=[cb2])
        assert int(tr3.state.step) == 10  # resumed at 4, +6 steps

    def test_every_checkpoint_corrupt_starts_fresh(self, tmp_path, caplog):
        """Only when NO candidate survives does resume keep the old
        failure contract: log 'starting fresh', return False, never kill
        the job at startup."""
        import logging

        from cloud_tpu.training.checkpoint import (
            CheckpointManager, resume_trainer_state,
        )

        ckpt = str(tmp_path / "all_corrupt")
        tr, ds, cb = _build_mnist_trainer(ckpt, every=2)
        tr.fit(ds, epochs=1, callbacks=[cb])
        for step in CheckpointManager(ckpt).steps():
            step_dir = os.path.join(ckpt, str(step))
            for root, _dirs, files in os.walk(step_dir):
                for name in files:
                    with open(os.path.join(root, name), "wb") as f:
                        f.write(b"\x00corrupt\xff" * 4)

        tr2, _, _ = _build_mnist_trainer()
        fresh_kernel = np.asarray(tr2.state.params["hidden"]["kernel"])
        with caplog.at_level(logging.ERROR):
            ok = resume_trainer_state(tr2, CheckpointManager(ckpt))
        assert ok is False
        assert "starting fresh" in caplog.text
        # The trainer still holds its fresh, usable state.
        np.testing.assert_array_equal(
            np.asarray(tr2.state.params["hidden"]["kernel"]), fresh_kernel
        )

    def test_restore_fault_injection_falls_back(self, tmp_path):
        """An injected restore failure on the newest step no longer
        starts fresh: the walk-back quarantines it and lands on the
        older intact step.  The quarantine is load-bearing — a stale
        newer step left in the lineage would make orbax silently skip
        every save of the resumed run (save(step) not ahead of
        latest_step is a no-op)."""
        from cloud_tpu.training.checkpoint import (
            CheckpointManager, resume_trainer_state,
        )

        ckpt = str(tmp_path / "inj")
        tr, ds, cb = _build_mnist_trainer(ckpt, every=3)
        tr.fit(ds, epochs=1, callbacks=[cb])
        tr2, _, _ = _build_mnist_trainer()
        plan = [{"site": "checkpoint.restore", "nth": 1}]
        manager = CheckpointManager(ckpt)
        with faults.inject(plan):
            assert resume_trainer_state(tr2, manager) is True
        assert int(tr2.state.step) == 3
        assert not os.path.isdir(os.path.join(ckpt, "6"))
        assert os.path.isdir(os.path.join(ckpt, "quarantine"))  # forensics
        # The resumed run's next save must NOT be skipped by a stale
        # newer step: step 4 is now ahead of latest_step (3).
        assert manager.latest_step() == 3
        assert manager.save(4, tr2.state) is True
        manager.wait()
        assert manager.latest_step() == 4
        manager.close()


# --- report robustness section --------------------------------------------


class TestRobustnessReport:
    def _events(self):
        def span(name, args):
            return {"name": name, "ph": "X", "ts": 0.0, "dur": 10.0,
                    "pid": 1, "tid": 1, "args": args}

        return [
            span("retry/api_request", {"attempts": 3, "outcome": "ok"}),
            span("retry/api_request",
                 {"attempts": 4, "outcome": "gave_up"}),
            span("serve/shed", {"reason": "deadline"}),
            span("fault/serve.chunk", {"mode": "hang"}),
            span("preempt/drain", {"step": 3, "reason": "signal 15"}),
            span("step/compute", {}),
        ]

    def test_summary_aggregates(self):
        from cloud_tpu.monitoring.report import TraceReport

        summary = TraceReport(self._events()).robustness_summary()
        assert summary["retries"]["api_request"] == {
            "calls": 2, "attempts": 7, "gave_up": 1,
        }
        assert summary["shed"] == 1
        assert summary["faults"] == {"serve.chunk": 1}
        assert summary["drains"] == 1

    def test_render_has_robustness_section(self):
        from cloud_tpu.monitoring.report import TraceReport

        rendered = TraceReport(self._events()).render()
        assert "robustness (retries, shedding, faults, drains):" in rendered
        assert "retry/api_request: 2 retried call(s), 7 attempts" in rendered
        assert "1 gave up" in rendered
        assert "shed requests (deadline exceeded): 1" in rendered
        assert "injected fault serve.chunk: x1" in rendered
        assert "preemption drains: 1" in rendered

    def test_quiet_timeline_has_no_section(self):
        from cloud_tpu.monitoring.report import TraceReport

        report = TraceReport([{
            "name": "step/compute", "ph": "X", "ts": 0.0, "dur": 5.0,
            "pid": 1, "tid": 1, "args": {},
        }])
        assert report.robustness_summary() is None
        assert "robustness" not in report.render()


# --- the end-to-end chaos harness -----------------------------------------


@pytest.mark.slow
def test_check_chaos_script(tmp_path):
    """scripts/check_chaos.py end to end: injected submit 503s absorbed
    (attempts == 3), checkpoint-save crash survived with state parity,
    hung dispatch watchdogged with zero leaked threads."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "check_chaos.py"),
         f"--tmp-dir={tmp_path}"],
        capture_output=True, text=True, timeout=500,
        cwd=REPO_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, (proc.stdout or "") + (proc.stderr or "")
    summary = None
    for line in proc.stdout.splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        if record.get("phase") == "summary":
            summary = record
    assert summary is not None, proc.stdout[-500:]
    assert summary["ok"] is True
    assert summary["submit_attempts"] == 3
    assert summary["leaked_threads"] == []
