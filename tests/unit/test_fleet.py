"""Fleet tests: routing, failover, supervision, autoscaling, hygiene.

The load-bearing contracts (ISSUE 8):

* routing is health-aware — each request goes to the least-loaded ready
  replica, and a full or dead candidate fails over to the next one,
  bounded by the route ``RetryPolicy``;
* a request whose ``deadline_s`` expires at the fleet level is shed with
  a typed ``DeadlineExceededError`` BEFORE any replica submit, and
  failover never re-submits an expired request;
* an unhealthy replica is restarted by the supervisor and its admitted
  requests re-enter the fleet queue (nothing dropped);
* the autoscaler grows the fleet under sustained queue depth and drains
  it back (gracefully) when idle, within ``[min, max]``;
* a closed fleet owns zero live threads, and greedy outputs through a
  real-engine fleet are token-identical to per-request ``generate()``.

Most tests drive the fleet with duck-typed fake engines (the factory is
the whole coupling surface), so the scheduling logic is exercised
without compiles; one parity test runs real TINY engines end to end.
The full chaos run (mid-run replica kill, autoscale up AND down) lives
in scripts/check_fleet.py, wired here as a slow test.
"""

import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from cloud_tpu.fleet import (
    AutoscaleConfig,
    Fleet,
    FleetClosedError,
    FleetConfig,
    LeastLoadedRouter,
    QueueDepthAutoscaler,
    Replica,
    route_transient,
)
from cloud_tpu.serving import (
    DeadlineExceededError,
    DispatchTimeoutError,
    EngineClosedError,
    QueueFullError,
)
from cloud_tpu.utils import faults, retries

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Every thread a fleet may own while live (its own router/supervisor
#: plus whatever the replica engines own).
FLEET_THREAD_PREFIXES = (
    "cloud-tpu-fleet", "cloud-tpu-serve", "cloud-tpu-compile-ahead",
)


def _fleet_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith(FLEET_THREAD_PREFIXES)
    ]


def _fast_policy(**overrides):
    """A route policy with instant-ish real backoff so failover loops
    converge inside test timeouts."""
    args = dict(
        max_attempts=8, initial_backoff_s=0.01, max_backoff_s=0.05,
        classify=route_transient, jitter=False,
    )
    args.update(overrides)
    return retries.RetryPolicy(**args)


class FakeEngine:
    """Duck-typed ServingEngine: records submits, resolves on demand.

    ``auto=True`` resolves each future immediately (with a dict carrying
    the serving replica's identity, so routing is assertable from the
    result); ``auto=False`` parks futures until ``resolve_all`` /
    ``fail_all``.  ``max_queue`` makes submit raise ``QueueFullError``
    at the bound, the failover trigger.
    """

    def __init__(self, name, *, auto=True, max_queue=None):
        self.name = name
        self.auto = auto
        self.max_queue = max_queue
        self.healthy = True
        self.ready_override = None  # force ready False without a restart
        self.closed = False
        self.drained_close = None
        self.submits = []
        self.pending = []
        #: Tiered-prefix-cache schema (ISSUE 15): mutable so tests can
        #: simulate what a cache holds / loses across a failover.
        self.cached_prefixes = {}
        self.prefix_dram_blocks = 0
        self.prefix_dram_demotions = 0
        self._lock = threading.Lock()

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None):
        with self._lock:
            if self.closed:
                raise EngineClosedError(f"{self.name} closed")
            if self.max_queue is not None and (
                len(self.pending) >= self.max_queue
            ):
                raise QueueFullError(f"{self.name} full")
            self.submits.append({
                "prompt": np.asarray(prompt).tolist(),
                "max_new_tokens": max_new_tokens,
                "deadline_s": deadline_s,
            })
            future = Future()
            if self.auto:
                future.set_result({"served_by": self.name})
            else:
                self.pending.append(future)
            return future

    def resolve_all(self):
        with self._lock:
            pending, self.pending = self.pending, []
        for future in pending:
            future.set_result({"served_by": self.name})

    def fail_all(self, exc):
        with self._lock:
            pending, self.pending = self.pending, []
        for future in pending:
            future.set_exception(exc)

    def health(self):
        with self._lock:
            depth = len(self.pending)
            closed = self.closed
        ready = (
            self.ready_override if self.ready_override is not None
            else (self.healthy and not closed)
        )
        return {
            "healthy": self.healthy,
            "ready": ready,
            "live": self.healthy,
            "reason": None if self.healthy else f"{self.name} unhealthy",
            "closed": closed,
            "waiting": depth,
            "queue_depth": depth,
            "active_slots": 0,
            "num_slots": 4,
            # Sharded-serving schema: each fake is a 2-chip slice, so
            # fleet.health()'s total_chips aggregation is observable.
            "slice_shape": (2, 1),
            "slice_chips": 2,
            "orphaned_dispatches": 0,
            "last_dispatch_age_s": None,
            "cached_prefixes": dict(self.cached_prefixes),
            "prefix_dram_blocks": self.prefix_dram_blocks,
            "prefix_dram_demotions": self.prefix_dram_demotions,
        }

    def close(self, drain=True, timeout=None):
        with self._lock:
            self.closed = True
            self.drained_close = drain
            pending, self.pending = self.pending, []
        for future in pending:
            if drain:
                future.set_result({"served_by": self.name})
            else:
                future.set_exception(
                    EngineClosedError(f"{self.name} closed before dispatch")
                )


class _Factory:
    """Engine factory handing out prepared fakes (then fresh autos)."""

    def __init__(self, engines=()):
        self.prepared = list(engines)
        self.built = []
        self._lock = threading.Lock()

    def __call__(self):
        with self._lock:
            if self.prepared:
                engine = self.prepared.pop(0)
            else:
                engine = FakeEngine(f"auto{len(self.built)}")
            self.built.append(engine)
            return engine


def _quiet_config(**overrides):
    """A fleet config whose supervisor stays out of the way (long poll)
    and whose route policy converges fast."""
    args = dict(
        min_replicas=1, poll_interval_s=60.0, route_policy=_fast_policy(),
    )
    args.update(overrides)
    return FleetConfig(**args)


class TestRouterPolicy:
    def test_pick_least_loaded(self):
        light = FakeEngine("light")
        heavy = FakeEngine("heavy", auto=False)
        for _ in range(3):
            heavy.submit(np.asarray([1], np.int32))  # queue_depth 3
        replicas = [
            Replica(0, lambda: heavy),
            Replica(1, lambda: light),
        ]
        picked, health = LeastLoadedRouter().pick(replicas)
        assert picked.id == 1
        assert Replica.load_of(health) == 0

    def test_pick_skips_unready_and_excluded(self):
        router = LeastLoadedRouter()
        sick = FakeEngine("sick")
        sick.healthy = False
        fine = FakeEngine("fine")
        replicas = [Replica(0, lambda: sick), Replica(1, lambda: fine)]
        picked, _ = router.pick(replicas)
        assert picked.id == 1
        picked, health = router.pick(replicas, exclude={1})
        assert picked is None and health is None

    def test_ties_break_to_lowest_id(self):
        engines = [FakeEngine(f"e{i}") for i in range(3)]
        replicas = [
            Replica(i, lambda e=e: e) for i, e in enumerate(engines)
        ]
        picked, _ = LeastLoadedRouter().pick(replicas)
        assert picked.id == 0


class TestFleetRouting:
    def test_health_composes_slices_not_chips(self):
        """The fleet is N slices: health() sums each replica's
        slice_chips (2-chip fakes here) into total_chips, while the
        router's load signal stays request-counting — a wider slice is
        not a lighter replica."""
        engines = [FakeEngine("a"), FakeEngine("b")]
        fleet = Fleet(_Factory(engines), _quiet_config(min_replicas=2))
        try:
            health = fleet.health()
            assert health["total_chips"] == 4
            for snap in health["replicas"]:
                assert snap["slice_chips"] == 2
                assert Replica.load_of(snap) == 0  # unchanged load math
        finally:
            fleet.close()

    def test_routes_to_least_loaded_replica(self):
        busy = FakeEngine("busy", auto=False)
        idle = FakeEngine("idle")
        for _ in range(4):
            busy.submit(np.asarray([9], np.int32))
        factory = _Factory([busy, idle])
        fleet = Fleet(factory, _quiet_config(min_replicas=2))
        try:
            result = fleet.submit(np.asarray([1, 2, 3], np.int32)).result(
                timeout=10
            )
            assert result["served_by"] == "idle"
            stats = fleet.stats()
            assert stats["routed"] == {1: 1}
            assert stats["completed"] == 1
        finally:
            busy.resolve_all()
            fleet.close()

    def test_failover_on_queue_full(self):
        from cloud_tpu.monitoring import tracing

        full = FakeEngine("full", max_queue=0)
        spare = FakeEngine("spare", auto=False)
        # Tie on load: the router tries replica 0 first, which rejects.
        factory = _Factory([full, spare])
        with tracing.collecting() as collector:
            fleet = Fleet(factory, _quiet_config(min_replicas=2))
            try:
                future = fleet.submit(np.asarray([7], np.int32))
                spare_deadline = time.perf_counter() + 10
                while not spare.submits:
                    assert time.perf_counter() < spare_deadline
                    time.sleep(0.005)
                spare.resolve_all()
                assert future.result(timeout=10)["served_by"] == "spare"
                assert full.submits == []
                assert fleet.stats()["failovers"] >= 1
            finally:
                fleet.close()
        names = [e["name"] for e in collector.events()]
        assert "fleet/failover" in names
        assert "fleet/route" in names

    def test_deadline_preserved_across_the_hop(self):
        """The replica receives the REMAINING budget, not the original."""
        engine = FakeEngine("only")
        fleet = Fleet(_Factory([engine]), _quiet_config())
        try:
            fleet.submit(
                np.asarray([1], np.int32), deadline_s=5.0
            ).result(timeout=10)
            passed = engine.submits[0]["deadline_s"]
            assert passed is not None and 0 < passed <= 5.0
        finally:
            fleet.close()

    def test_caller_errors_fail_without_failover(self):
        """A bad request (replica raises ValueError) is the caller's
        bug: no failover, the error surfaces on the future."""

        class Picky(FakeEngine):
            def submit(self, prompt, **kwargs):
                raise ValueError("prompt too long")

        picky = Picky("picky")
        spare = FakeEngine("spare")
        fleet = Fleet(_Factory([picky, spare]), _quiet_config(
            min_replicas=2
        ))
        try:
            future = fleet.submit(np.asarray([1], np.int32))
            with pytest.raises(ValueError, match="too long"):
                future.result(timeout=10)
            assert spare.submits == []
        finally:
            fleet.close()


class TestCacheAwareFleetRouting:
    """ISSUE 15: the cost-model router composed with the fleet — live
    ``cached_prefixes`` summaries steer requests, a stale affinity map
    cannot override them after a failover, pre-affinity custom routers
    keep working, and the supervisor exports the DRAM-tier gauges."""

    def test_cost_model_follows_live_summary_not_stale_affinity(self):
        from cloud_tpu.serving.prefix_cache import affinity_key

        prompt = np.arange(1, 40, dtype=np.int32)
        key = affinity_key(prompt)
        first = FakeEngine("first")
        second = FakeEngine("second")
        first.cached_prefixes = {key: 64}
        router = LeastLoadedRouter(prefix_affinity=True, cache_alpha=0.5)
        fleet = Fleet(_Factory([first, second]), _quiet_config(
            min_replicas=2
        ), router=router)
        try:
            # Equal (zero) load: the summary credit decides, and the
            # fleet records the affinity on replica 0 after success.
            result = fleet.submit(prompt).result(timeout=10)
            assert result["served_by"] == "first"
            # The kill-and-rebuild story, distilled: replica 0's cache
            # is gone (restart), the prefix now lives on replica 1 (it
            # served the failover re-run).  The router reads the LIVE
            # summaries, so the stale key -> replica-0 affinity entry
            # must NOT keep attracting the crowd.
            first.cached_prefixes = {}
            second.cached_prefixes = {key: 64}
            result = fleet.submit(prompt).result(timeout=10)
            assert result["served_by"] == "second"
        finally:
            fleet.close()

    def test_pre_affinity_two_arg_router_still_works(self):
        """The ISSUE 15 satellite pin: a custom router with the
        ORIGINAL two-argument ``pick(replicas, exclude=())`` signature
        (no affinity_key, no priority, no record_affinity) routes a
        fleet that now passes cache/affinity hints."""

        class OldestRouter:
            def pick(self, replicas, exclude=()):
                excluded = set(exclude)
                for replica in replicas:
                    if replica.id in excluded and len(excluded) < len(
                        list(replicas)
                    ):
                        continue
                    health = replica.health()
                    if replica.routable(health):
                        return replica, health
                return None, None

        engine = FakeEngine("only")
        fleet = Fleet(_Factory([engine]), _quiet_config(),
                      router=OldestRouter())
        try:
            result = fleet.submit(
                np.asarray([1, 2, 3], np.int32)
            ).result(timeout=10)
            assert result["served_by"] == "only"
            assert fleet.stats()["completed"] == 1
        finally:
            fleet.close()

    def test_supervisor_exports_prefix_dram_gauges(self):
        from cloud_tpu.monitoring import metrics

        first = FakeEngine("first")
        second = FakeEngine("second")
        first.prefix_dram_blocks = 5
        first.prefix_dram_demotions = 7
        second.prefix_dram_blocks = 3
        second.prefix_dram_demotions = 2
        fleet = Fleet(_Factory([first, second]), _quiet_config(
            min_replicas=2
        ))
        try:
            fleet._supervise_once()
            gauges = metrics.snapshot()["gauges"]
            assert gauges["fleet/prefix_dram_blocks"] == 8
            assert gauges["fleet/prefix_dram_demotions"] == 9
        finally:
            fleet.close()


class TestFleetDeadlines:
    def test_expired_request_shed_before_any_replica_submit(self):
        """The satellite contract: a request whose deadline expires
        while fleet-queued fails typed with ZERO replica submits."""
        engine = FakeEngine("unroutable")
        engine.ready_override = False  # routable never; healthy, so the
        # supervisor (parked anyway) would not restart it
        fleet = Fleet(_Factory([engine]), _quiet_config())
        try:
            future = fleet.submit(
                np.asarray([1, 2], np.int32), deadline_s=0.05
            )
            with pytest.raises(DeadlineExceededError):
                future.result(timeout=10)
            assert engine.submits == []
            assert fleet.stats()["shed"] == 1
            assert fleet.stats()["failed"] == 0
        finally:
            fleet.close()

    def test_failover_never_resubmits_an_expired_request(self):
        first = FakeEngine("first", auto=False)
        second = FakeEngine("second", auto=False)
        fleet = Fleet(_Factory([first, second]), _quiet_config(
            min_replicas=2
        ))
        try:
            future = fleet.submit(
                np.asarray([3], np.int32), deadline_s=0.1
            )
            deadline = time.perf_counter() + 10
            while not first.submits:
                assert time.perf_counter() < deadline
                time.sleep(0.005)
            time.sleep(0.15)  # let the request's deadline pass in flight
            first.fail_all(DispatchTimeoutError("replica died"))
            with pytest.raises(DeadlineExceededError):
                future.result(timeout=10)
            assert second.submits == []
        finally:
            fleet.close()


class TestSupervision:
    def test_unhealthy_replica_restarted_and_request_reenters(self):
        """The supervision contract: the engine dies with a request in
        flight; the request re-enters the fleet queue and completes on
        the rebuilt replica — nothing dropped, restart counted."""
        sick = FakeEngine("sick", auto=False)
        factory = _Factory([sick])
        fleet = Fleet(factory, FleetConfig(
            min_replicas=1, poll_interval_s=0.02,
            route_policy=_fast_policy(
                initial_backoff_s=0.02, max_backoff_s=0.1,
            ),
        ))
        try:
            future = fleet.submit(np.asarray([5, 6], np.int32))
            deadline = time.perf_counter() + 10
            while not sick.submits:
                assert time.perf_counter() < deadline
                time.sleep(0.005)
            # The watchdog-style death: engine flips unhealthy and fails
            # its in-flight requests typed (the PR 6 seam).
            sick.healthy = False
            sick.fail_all(DispatchTimeoutError("dispatch hung"))
            result = future.result(timeout=30)
            assert result["served_by"] == "auto1"  # the rebuilt engine
            stats = fleet.stats()
            assert stats["restarts"] >= 1
            assert stats["failovers"] >= 1
            assert stats["completed"] == 1
            assert len(factory.built) == 2
            assert sick.drained_close is False  # killed, not drained
            assert fleet.replicas()[0].restarts >= 1
        finally:
            fleet.close()

    def test_failed_restart_retried_on_next_poll(self):
        """The fleet.replica_start chaos seam: a factory that fails once
        during restart leaves the replica dead for one poll, then the
        next poll's retry brings it back."""
        sick = FakeEngine("sick", auto=False)
        factory = _Factory([sick])
        # nth=2: the 1st replica_start call was construction; the 2nd is
        # the restart, which must fail exactly once.
        plan = [{"site": "fleet.replica_start", "mode": "raise", "nth": 2}]
        with faults.inject(plan) as active:
            fleet = Fleet(factory, FleetConfig(
                min_replicas=1, poll_interval_s=0.02,
                route_policy=_fast_policy(
                    max_attempts=12, initial_backoff_s=0.02,
                    max_backoff_s=0.1,
                ),
            ))
            try:
                future = fleet.submit(np.asarray([8], np.int32))
                deadline = time.perf_counter() + 10
                while not sick.submits:
                    assert time.perf_counter() < deadline
                    time.sleep(0.005)
                sick.healthy = False
                sick.fail_all(DispatchTimeoutError("dispatch hung"))
                assert future.result(timeout=30)["served_by"] == "auto1"
            finally:
                fleet.close()
        assert active.fired() == {"fleet.replica_start": 1}


class TestAutoscalerPolicy:
    def test_scales_up_on_sustained_queue_depth(self):
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=3, scale_up_queue_depth=2.0,
            window=3, cooldown=2,
        ))
        assert scaler.observe(queue_depth=6, ready_replicas=1) == "hold"
        assert scaler.observe(queue_depth=6, ready_replicas=1) == "hold"
        assert scaler.observe(queue_depth=6, ready_replicas=1) == "up"
        # Cooldown: the next two observations cannot fire.
        assert scaler.observe(queue_depth=9, ready_replicas=2) == "hold"
        assert scaler.observe(queue_depth=9, ready_replicas=2) == "hold"

    def test_one_burst_does_not_scale(self):
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=3, scale_up_queue_depth=4.0,
            window=3, cooldown=0,
        ))
        assert scaler.observe(queue_depth=100, ready_replicas=1) == "hold"
        assert scaler.observe(queue_depth=0, ready_replicas=1) == "hold"
        assert scaler.observe(queue_depth=0, ready_replicas=1) == "hold"

    def test_scales_down_only_after_sustained_idle(self):
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=3, idle_window=3, cooldown=0,
            window=2,
        ))
        for _ in range(2):
            assert scaler.observe(
                queue_depth=0, ready_replicas=2
            ) == "hold"
        assert scaler.observe(queue_depth=0, ready_replicas=2) == "down"
        # At the floor, idleness never fires.
        for _ in range(5):
            assert scaler.observe(
                queue_depth=0, ready_replicas=1
            ) == "hold"

    def test_busy_slots_block_scale_down(self):
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=2, idle_window=2, cooldown=0,
        ))
        for _ in range(4):
            assert scaler.observe(
                queue_depth=0, ready_replicas=2, occupancy=0.5
            ) == "hold"

    def test_config_validation(self):
        with pytest.raises(ValueError, match="min_replicas"):
            AutoscaleConfig(min_replicas=0)
        with pytest.raises(ValueError, match="max_replicas"):
            AutoscaleConfig(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError, match="max_replicas"):
            FleetConfig(min_replicas=3, max_replicas=1)
        with pytest.raises(ValueError, match="admission"):
            FleetConfig(admission="drop")


class TestFleetAutoscaling:
    def test_scales_up_under_backlog_and_drains_back_when_idle(self):
        """End to end through the fleet: saturated replicas push the
        queue up -> scale up; resolution + idleness -> graceful drain
        back to the floor."""
        factory = _Factory([FakeEngine("seed", auto=False, max_queue=1)])

        class CappedFactory:
            def __call__(self):
                engine = factory()
                engine.auto = False
                engine.max_queue = 1
                return engine

        fleet = Fleet(CappedFactory(), FleetConfig(
            min_replicas=1, max_replicas=3, poll_interval_s=0.02,
            route_policy=_fast_policy(
                max_attempts=50, initial_backoff_s=0.01,
                max_backoff_s=0.05,
            ),
            autoscale=AutoscaleConfig(
                scale_up_queue_depth=1.0, window=2, idle_window=3,
                cooldown=1,
            ),
        ))
        try:
            futures = [
                fleet.submit(np.asarray([i + 1], np.int32))
                for i in range(6)
            ]
            deadline = time.perf_counter() + 15
            while fleet.num_replicas() < 2:
                assert time.perf_counter() < deadline, fleet.stats()
                time.sleep(0.01)
            assert fleet.stats()["scale_ups"] >= 1
            # Serve everything out so the fleet goes idle.
            while not all(f.done() for f in futures):
                assert time.perf_counter() < deadline
                for engine in list(factory.built):
                    engine.resolve_all()
                time.sleep(0.01)
            for future in futures:
                assert "served_by" in future.result(timeout=5)
            while fleet.num_replicas() > 1:
                assert time.perf_counter() < deadline, fleet.stats()
                for engine in list(factory.built):
                    engine.resolve_all()
                time.sleep(0.01)
            stats = fleet.stats()
            assert stats["scale_downs"] >= 1
            # The drain runs on a helper thread: wait for it to land.
            while not any(
                e.closed and e.drained_close is True
                for e in factory.built
            ):
                assert time.perf_counter() < deadline, (
                    "scale-down must retire via graceful drain"
                )
                time.sleep(0.01)
        finally:
            fleet.close()
        assert not _fleet_threads()


class TestFleetClose:
    def test_close_resolves_everything_and_joins_threads(self):
        fleet = Fleet(_Factory(), _quiet_config())
        futures = [
            fleet.submit(np.asarray([i], np.int32)) for i in range(1, 4)
        ]
        fleet.close()
        for future in futures:
            assert "served_by" in future.result(timeout=5)
        assert fleet.stats()["completed"] == 3
        assert not _fleet_threads()
        with pytest.raises(FleetClosedError):
            fleet.submit(np.asarray([1], np.int32))

    def test_close_without_drain_fails_owed_requests_typed(self):
        engine = FakeEngine("held", auto=False)
        fleet = Fleet(_Factory([engine]), _quiet_config())
        future = fleet.submit(np.asarray([1, 2], np.int32))
        deadline = time.perf_counter() + 10
        while not engine.submits:
            assert time.perf_counter() < deadline
            time.sleep(0.005)
        fleet.close(drain=False)
        with pytest.raises((EngineClosedError, FleetClosedError)):
            future.result(timeout=5)
        assert not _fleet_threads()

    def test_drain_close_timeout_still_joins_threads(self):
        """A drain close whose budget runs out hard-fails the remainder
        typed instead of returning with a live router and futures that
        resolve later (the zero-live-threads contract holds)."""
        engine = FakeEngine("stuck", auto=False)
        fleet = Fleet(_Factory([engine]), _quiet_config())
        future = fleet.submit(np.asarray([1], np.int32))
        deadline = time.perf_counter() + 10
        while not engine.submits:
            assert time.perf_counter() < deadline
            time.sleep(0.005)
        extra = fleet.submit(np.asarray([2], np.int32))
        start = time.perf_counter()
        fleet.close(drain=True, timeout=0.5)
        assert time.perf_counter() - start < 5
        assert not _fleet_threads()
        for owed in (future, extra):
            with pytest.raises((EngineClosedError, FleetClosedError)):
                owed.result(timeout=5)

    def test_constructor_failure_closes_built_replicas(self):
        """A factory that fails replica 1 must not leak replica 0."""
        good = FakeEngine("good")

        class ExplodingFactory:
            calls = 0

            def __call__(self):
                self.calls += 1
                if self.calls == 1:
                    return good
                raise RuntimeError("no capacity for replica 1")

        with pytest.raises(RuntimeError, match="no capacity"):
            Fleet(ExplodingFactory(), _quiet_config(min_replicas=2))
        assert good.closed
        assert not _fleet_threads()

    def test_submit_validation(self):
        fleet = Fleet(_Factory(), _quiet_config())
        try:
            with pytest.raises(ValueError, match="1-D"):
                fleet.submit(np.zeros((2, 2), np.int32))
            with pytest.raises(ValueError, match="deadline_s"):
                fleet.submit(np.asarray([1], np.int32), deadline_s=0)
        finally:
            fleet.close()

    def test_reject_admission_raises_typed(self):
        engine = FakeEngine("slow", auto=False)
        # Never started: the queue holds, so the bound is deterministic.
        fleet = Fleet(_Factory([engine]), _quiet_config(
            max_queue=1, admission="reject",
        ), start=False)
        try:
            fleet.submit(np.asarray([1], np.int32))
            with pytest.raises(QueueFullError):
                fleet.submit(np.asarray([2], np.int32))
            assert fleet.stats()["rejected"] == 1
        finally:
            fleet.close(drain=False)


class TestFleetReport:
    def test_live_fleet_failover_lands_in_the_report(self):
        from cloud_tpu.monitoring import tracing
        from cloud_tpu.monitoring.report import TraceReport

        full = FakeEngine("full", max_queue=0)
        spare = FakeEngine("spare")
        with tracing.collecting() as collector:
            fleet = Fleet(_Factory([full, spare]), _quiet_config(
                min_replicas=2
            ))
            try:
                fleet.submit(np.asarray([4], np.int32)).result(timeout=10)
            finally:
                fleet.close()
            report = TraceReport(collector.events())
        summary = report.fleet_summary()
        assert summary is not None
        assert summary["failovers"] >= 1
        assert summary["replicas"][1]["requests"] == 1
        rendered = report.render()
        assert "fleet (routing, supervision, scaling):" in rendered
        assert "replica 1: 1 request(s)" in rendered

    def test_fleet_summary_aggregates_synthetic_spans(self):
        from cloud_tpu.monitoring.report import TraceReport

        def span(name, **args):
            return {"ph": "X", "ts": 0, "dur": 10, "name": name,
                    "args": args}

        report = TraceReport([
            span("fleet/route", replica=0, load=2, occupancy=0.5),
            span("fleet/route", replica=0, load=4, occupancy=0.7),
            span("fleet/route", replica=1, load=0, occupancy=0.2),
            span("fleet/failover", replica=0, error="QueueFullError"),
            span("fleet/restart", replica=0, reason="watchdog"),
            span("fleet/scale", direction="up", replicas=2),
            span("fleet/scale", direction="down", replicas=1),
            span("fleet/shed", reason="deadline"),
        ])
        summary = report.fleet_summary()
        assert summary["replicas"][0]["requests"] == 2
        assert summary["replicas"][0]["mean_load"] == 3.0
        assert summary["replicas"][1]["requests"] == 1
        assert summary["failovers"] == 1
        assert summary["restarts"] == 1
        assert summary["shed"] == 1
        assert summary["scale"] == {"up": 1, "down": 1}
        assert abs(summary["occupancy_spread"] - 0.4) < 1e-9
        rendered = report.render()
        assert "occupancy spread across replicas: 40.0%" in rendered

    def test_empty_timeline_does_not_crash(self):
        from cloud_tpu.monitoring.report import TraceReport

        report = TraceReport([])
        assert report.fleet_summary() is None
        assert isinstance(report.render(), str)

    def test_fleetless_timeline_has_no_fleet_section(self):
        from cloud_tpu.monitoring.report import TraceReport

        report = TraceReport([
            {"ph": "X", "ts": 0, "dur": 5, "name": "serve/chunk",
             "args": {}},
        ])
        assert report.fleet_summary() is None
        assert "fleet (routing" not in report.render()


class TracedFakeEngine(FakeEngine):
    """FakeEngine whose ``submit`` takes the ``trace`` kwarg and emits
    the terminal ``serve/request`` span on completion, like a real
    traced engine — the duck-typed seam the replica's signature probe
    flips on."""

    def __init__(self, name, **kwargs):
        super().__init__(name, **kwargs)
        self.traces = []

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None,
               trace=None):
        from cloud_tpu.monitoring import tracing

        self.traces.append(trace)
        future = super().submit(
            prompt, max_new_tokens=max_new_tokens, deadline_s=deadline_s,
        )
        if self.auto and trace is not None:
            now = time.perf_counter()
            tracing.record_span(
                "serve/request", now - 0.002, now,
                trace_id=trace.trace_id, ttft_s=0.001, tokens=2,
            )
        return future


class TestFleetTracing:
    """ISSUE 16: trace-context propagation through routing and
    failover, the signature probe, the ``traced`` stats key, and the
    merged fleet timeline."""

    def test_trace_survives_failover_and_stitches_one_lifecycle(
            self, tmp_path):
        from cloud_tpu.monitoring import tracing
        from cloud_tpu.monitoring.report import TraceReport

        # Replica 0 is always full: the least-loaded tie routes there
        # first (lowest id), fails over, and replica 1 completes.
        full = TracedFakeEngine("full", max_queue=0)
        ok = TracedFakeEngine("ok")
        factory = _Factory([full, ok])
        path = str(tmp_path / "fleet.json")
        with tracing.collecting():
            fleet = Fleet(factory, _quiet_config(min_replicas=2))
            try:
                result = fleet.submit(
                    np.asarray([1, 2], np.int32)
                ).result(timeout=30)
                assert result["served_by"] == "ok"
                # Both replicas advertise the probe, and the SAME
                # context object hopped with the request.
                assert all(r.accepts_trace for r in fleet.replicas())
                assert ok.traces and ok.traces[0] is not None
                stats = fleet.stats()
                assert stats["traced"] == 1
                assert stats["failovers"] == 1
                assert fleet.dump_timeline(path) == path
            finally:
                fleet.close()

        report = TraceReport.from_file(path)
        summary = report.request_summary()
        assert summary is not None and len(summary) == 1
        ((trace_id, row),) = summary.items()
        assert trace_id == ok.traces[0].trace_id
        # One stitched lifecycle: the failed attempt, the re-route, and
        # the terminal span all share the request's single identity.
        assert row["complete"]
        assert row["routes"] == 1  # only the ACCEPTED attempt routes
        assert row["failovers"] == 1
        assert row["ttft_s"] == pytest.approx(0.001, abs=1e-3)
        assert report.render_trace(trace_id) is not None

    def test_legacy_engine_without_trace_kwarg_still_routes_traced(self):
        from cloud_tpu.monitoring import tracing

        # Plain FakeEngine.submit has no trace kwarg (and no **kwargs):
        # the probe must gate forwarding so pre-trace engines keep
        # working, while the fleet's own spans still stamp the id.
        engine = FakeEngine("legacy")
        factory = _Factory([engine])
        with tracing.collecting() as collector:
            fleet = Fleet(factory, _quiet_config())
            try:
                assert not fleet.replicas()[0].accepts_trace
                result = fleet.submit(
                    np.asarray([3], np.int32)
                ).result(timeout=30)
                assert result["served_by"] == "legacy"
                assert fleet.stats()["traced"] == 1
            finally:
                fleet.close()
        routes = [
            e for e in collector.events() if e["name"] == "fleet/route"
        ]
        assert routes and "trace_id" in routes[0]["args"]
        assert isinstance(routes[0]["args"]["queue_s"], float)

    def test_tracing_off_is_inert_and_stats_schema_pinned(self):
        from cloud_tpu.monitoring import tracing

        assert not tracing.enabled()
        engine = TracedFakeEngine("quiet")
        fleet = Fleet(_Factory([engine]), _quiet_config())
        try:
            fleet.submit(np.asarray([4], np.int32)).result(timeout=30)
            # Schema pin: the key exists and stays zero — no context
            # was minted, and none reached the engine.
            assert fleet.stats()["traced"] == 0
            assert engine.traces == [None]
        finally:
            fleet.close()

    def test_dump_timeline_without_tracing_is_empty_but_valid(
            self, tmp_path):
        import json

        fleet = Fleet(_Factory([FakeEngine("a")]), _quiet_config())
        try:
            path = fleet.dump_timeline(str(tmp_path / "off.json"))
        finally:
            fleet.close()
        doc = json.loads(open(path).read())
        assert [e for e in doc["traceEvents"] if e["ph"] == "X"] == []


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


class TestRealEngineFleet:
    @pytest.mark.slow
    def test_churn_parity_across_two_replicas(self, model):
        """The acceptance criterion's healthy half: greedy outputs
        through a 2-replica fleet are token-identical to per-request
        generate(), whichever replica served each request.

        Slow tier: boots two real engines on a real model (~20s on the
        CPU rig); scripts/check_fleet.py asserts the same parity e2e
        (plus churn + failover), and the fake-engine fleet tests above
        keep routing/failover semantics pinned per-commit."""
        import jax.numpy as jnp

        from cloud_tpu.models import generation
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8, 16),
            num_slots=4, chunk_tokens=2,
        )

        def factory():
            return ServingEngine(params, config, serve, mesh=None)

        rng = np.random.default_rng(4)
        lens = (3, 8, 12, 5, 16, 2, 7, 9)
        budgets = (5, 2, 4, 1, 5, 3, 5, 2)
        prompts = [rng.integers(1, 255, n).astype(np.int32) for n in lens]
        fleet = Fleet(factory, FleetConfig(
            min_replicas=2, poll_interval_s=0.1,
        ))
        try:
            futures = []
            for i, prompt in enumerate(prompts):
                futures.append(
                    fleet.submit(prompt, max_new_tokens=budgets[i])
                )
                if i in (3, 6):
                    time.sleep(0.05)  # staggered arrivals mid-decode
            results = [f.result(timeout=120) for f in futures]
            stats = fleet.stats()
        finally:
            fleet.close()
        for prompt, budget, result in zip(prompts, budgets, results):
            want = generation.generate(
                params, jnp.asarray(prompt[None, :]),
                jnp.asarray([len(prompt)], np.int32), config,
                max_new_tokens=budget,
                sample=generation.SampleConfig(temperature=0.0),
            )
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
            assert result.num_generated == int(want["num_generated"][0])
            # Fleet latency is re-based to the fleet submit.
            assert result.latency_seconds > 0
        assert stats["completed"] == len(prompts)
        assert stats["failed"] == 0
        # Both replicas actually carried traffic on this workload.
        assert set(stats["routed"]) == {0, 1}
        assert not _fleet_threads()


@pytest.mark.slow
def test_check_fleet_script():
    """The CI fleet harness end to end: churn through CPU replicas with
    an injected mid-run replica kill (parity + failover + zero leaks)
    and a provable autoscale up/down cycle."""
    import json

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "check_fleet.py")],
        capture_output=True, text=True, timeout=900,
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
    assert summary["failovers"] >= 1
    assert summary["scale_ups"] >= 1 and summary["scale_downs"] >= 1
    assert summary["leaked_threads"] == []
