"""Multi-tenant QoS tests: policy, quotas, streaming, and the off-path.

The load-bearing contracts (ISSUE 14):

* with QoS OFF everything is byte-identical FIFO — priority tags are
  validated but inert, and every new ``health()``/``stats()`` key reads
  zero (pinned here for the fleet, in test_serving for both engine
  schedulers);
* with QoS ON, greedy outputs — streamed and non-streamed — stay
  token-identical to per-request ``generate()``: the scheduler reorders
  WHICH request gets a slot, never what the slot decodes;
* quotas and brownout shedding fail typed (``QuotaExceededError``,
  ``BrownoutShedError``) and class-ordered (batch sheds before
  interactive);
* a ``TokenStream``'s per-token view is exactly the final result row's
  prefix, and feeds are idempotent by index (failover re-runs resume,
  never duplicate).

Policy classes (``QosScheduler``, ``TokenBucket``, autoscaler/router
extensions) are tested pure; the engine tests run a real TINY model;
fleet tests use the duck-typed fake-engine pattern from test_fleet.
The end-to-end mixed-tenant chaos proof (interactive TTFT p99 beats
FIFO under a saturating batch tenant + replica kill) lives in
scripts/check_fleet.py phase 3, wired slow via test_fleet.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from cloud_tpu.fleet import Fleet, FleetConfig
from cloud_tpu.fleet.autoscaler import AutoscaleConfig, QueueDepthAutoscaler
from cloud_tpu.fleet.router import LeastLoadedRouter
from cloud_tpu.monitoring.report import TraceReport
from cloud_tpu.serving import (
    BrownoutShedError,
    PriorityClass,
    QosConfig,
    QosScheduler,
    QueueFullError,
    QuotaExceededError,
    ServeConfig,
    ServeResult,
    ServingEngine,
    TenantQuota,
    TokenBucket,
    TokenStream,
)
from cloud_tpu.serving.qos import brownout_victims, validate_priority

from tests.unit.test_fleet import (  # the duck-typed fleet rig
    FakeEngine,
    _Factory,
    _fleet_threads,
    _quiet_config,
)


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _direct_tokens(params, config, prompt, max_new_tokens):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    out = generation.generate(
        params, jnp.asarray(prompt[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=max_new_tokens,
        sample=generation.SampleConfig(temperature=0.0),
    )
    return np.asarray(out["tokens"])[0], int(out["num_generated"][0])


class TestTypedConstruction:
    """Every QoS knob fails typed at CONSTRUCTION, not deep in a
    scheduler thread (the ISSUE 14 typed-error satellite)."""

    def test_priority_class_validation(self):
        with pytest.raises(ValueError, match="weight"):
            PriorityClass(weight=0.0)
        with pytest.raises(ValueError, match="slo_s"):
            PriorityClass(slo_s=0.0)

    def test_tenant_quota_validation(self):
        with pytest.raises(ValueError, match="tokens_per_s"):
            TenantQuota(tokens_per_s=0.0, burst_tokens=10)
        with pytest.raises(ValueError, match="burst_tokens"):
            TenantQuota(tokens_per_s=1.0, burst_tokens=0)

    def test_qos_config_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            QosConfig(classes={})
        with pytest.raises(ValueError, match="default_priority"):
            QosConfig(default_priority="vip")
        with pytest.raises(ValueError, match="brownout_queue_depth"):
            QosConfig(brownout_queue_depth=0)
        with pytest.raises(ValueError, match="PriorityClass"):
            QosConfig(classes={"a": 1.0})
        with pytest.raises(ValueError, match="TenantQuota"):
            QosConfig(quotas={"t": 5})

    def test_resolve_priority(self):
        cfg = QosConfig()
        assert cfg.resolve_priority(None) == "standard"
        assert cfg.resolve_priority("batch") == "batch"
        with pytest.raises(ValueError, match="unknown priority"):
            cfg.resolve_priority("vip")

    def test_priority_without_qos_type_checked_only(self):
        """The FIFO path accepts ANY class name (a QoS fleet with
        custom classes legitimately forwards them to replicas whose
        own QoS is off — name-rejection there would fail every request
        of a valid deployment); only the type is enforced."""
        assert validate_priority(None) is None
        assert validate_priority("interactive") == "interactive"
        assert validate_priority("gold") == "gold"  # custom names pass
        with pytest.raises(ValueError, match="class name"):
            validate_priority(123)

    def test_shed_order_is_lowest_weight_first(self):
        assert QosConfig().shed_order() == [
            "batch", "standard", "interactive",
        ]
        custom = QosConfig(
            classes={
                "a": PriorityClass(weight=2.0),
                "b": PriorityClass(weight=0.5),
            },
            default_priority="a",
        )
        assert custom.shed_order() == ["b", "a"]

    def test_serve_config_qos_type_is_checked(self):
        with pytest.raises(ValueError, match="QosConfig"):
            ServeConfig(qos="interactive")

    def test_fleet_config_qos_typed(self):
        with pytest.raises(ValueError, match="QosConfig"):
            FleetConfig(qos={"interactive": 1})

    def test_error_types_are_distinct_runtime_errors(self):
        # route_transient and callers key on exact types: both must be
        # constructible from a message and neither a subclass of the
        # other.
        assert isinstance(QuotaExceededError("x"), RuntimeError)
        assert isinstance(BrownoutShedError("x"), RuntimeError)
        assert not isinstance(QuotaExceededError("x"), BrownoutShedError)
        assert not isinstance(BrownoutShedError("x"), QuotaExceededError)


class TestTokenBucket:
    def test_burst_then_refill(self):
        clock = {"t": 0.0}
        bucket = TokenBucket(
            TenantQuota(tokens_per_s=10.0, burst_tokens=30),
            clock=lambda: clock["t"],
        )
        assert bucket.try_acquire(30)  # the whole burst
        assert not bucket.try_acquire(1)
        clock["t"] = 2.0  # 20 tokens refilled
        assert bucket.available() == pytest.approx(20.0)
        assert bucket.try_acquire(20)
        clock["t"] = 100.0  # refill caps at the burst ceiling
        assert bucket.available() == pytest.approx(30.0)

    def test_charge_is_all_or_nothing(self):
        clock = {"t": 0.0}
        bucket = TokenBucket(
            TenantQuota(tokens_per_s=1.0, burst_tokens=10),
            clock=lambda: clock["t"],
        )
        assert not bucket.try_acquire(11)
        # The failed acquire charged nothing.
        assert bucket.try_acquire(10)

    def test_credit_refunds_capped_at_burst(self):
        clock = {"t": 0.0}
        bucket = TokenBucket(
            TenantQuota(tokens_per_s=1.0, burst_tokens=10),
            clock=lambda: clock["t"],
        )
        assert bucket.try_acquire(6)
        bucket.credit(6)
        assert bucket.available() == pytest.approx(10.0)
        bucket.credit(100)  # never refunds past the ceiling
        assert bucket.available() == pytest.approx(10.0)


class TestRequestCostAndShedPolicy:
    def test_request_cost_unbudgeted_is_never_free(self):
        cfg = QosConfig(unbudgeted_decode_cost=64)
        assert cfg.request_cost(10, 5) == 15
        assert cfg.request_cost(10, None) == 74
        with pytest.raises(ValueError, match="unbudgeted_decode_cost"):
            QosConfig(unbudgeted_decode_cost=-1)

    def test_brownout_victims_class_ordered_newest_first(self):
        class R:
            def __init__(self, priority, submitted):
                self.priority = priority
                self.submitted = submitted

        requests = [
            R("interactive", 1.0), R("batch", 2.0), R("batch", 3.0),
            R("standard", 4.0), R("batch", 5.0),
        ]
        cfg = QosConfig()
        # Excess 2: both from batch (lowest weight), newest first.
        victims = brownout_victims(requests, 2, cfg)
        assert [(v.priority, v.submitted) for v in victims] == [
            ("batch", 5.0), ("batch", 3.0),
        ]
        # Excess 4: batch exhausted, spills into standard — never
        # interactive while a lower class remains.
        victims = brownout_victims(requests, 4, cfg)
        assert [v.priority for v in victims] == [
            "batch", "batch", "batch", "standard",
        ]
        assert brownout_victims(requests, 0, cfg) == []


class TestQosScheduler:
    CFG = QosConfig()  # interactive w8/slo .25, standard w4/2, batch w1/30

    def test_edf_while_slack_remains(self):
        """Before saturation the earliest-expiring SLO wins — a LATER
        interactive arrival outranks an earlier batch one."""
        sched = QosScheduler(self.CFG)
        now = 10.0
        batch_key = sched.key("batch", submitted=9.0, now=now)
        inter_key = sched.key("interactive", submitted=9.9, now=now)
        assert inter_key < batch_key

    def test_expired_slack_clamps_to_fairness(self):
        """Once every SLO is blown, slack clamps to 0 and the weighted
        fairness debt decides — a class that consumed service yields to
        one that has not, weight-scaled."""
        sched = QosScheduler(self.CFG)
        now = 100.0
        # Both long expired: keys tie on slack=0, tie-break vservice.
        assert (sched.key("interactive", 0.0, now)
                < sched.key("batch", 0.0, now)) is False  # tie -> arrival
        sched.charge("interactive", 80)  # 80/8 = 10 virtual
        sched.charge("batch", 5)         # 5/1  = 5 virtual
        assert sched.key("batch", 0.0, now) < sched.key(
            "interactive", 0.0, now
        )
        assert sched.virtual_service() == {
            "interactive": 10.0, "standard": 0.0, "batch": 5.0,
        }

    def test_fifo_within_a_class(self):
        sched = QosScheduler(self.CFG)
        now = 100.0
        assert sched.key("batch", 1.0, now) < sched.key("batch", 2.0, now)

    class _R:
        def __init__(self, priority, submitted):
            self.priority = priority
            self.submitted = submitted

    def test_select_is_argmin_of_key(self):
        sched = QosScheduler(self.CFG)
        now = 10.0
        batch = self._R("batch", 9.0)
        inter = self._R("interactive", 9.9)
        assert sched.select([batch, inter], now) is inter
        assert sched.select([], now) is None

    def test_idle_class_cannot_hoard_fairness_credit(self):
        """The WFQ start-tag clamp: a class idle while another accrues
        virtual service is lifted to the virtual time when it returns,
        so an hour of interactive-only traffic does not let a late
        batch flood monopolize admission until its debt 'catches up'.
        A continuously-backlogged lagging class defines the virtual
        time itself, so its earned debt is never erased."""
        sched = QosScheduler(self.CFG)
        inter = self._R("interactive", 0.0)
        # Interactive serves alone for a long stretch (batch idle).
        for _ in range(10):
            sched.select([inter], 100.0)
            sched.charge("interactive", 80)  # 80/8 = 10 virtual each
        assert sched.virtual_service()["interactive"] == 100.0
        # Batch returns: its vservice is LIFTED to the virtual time
        # (the min-over-present at the last selection instant, 90 —
        # one pre-charge pop behind), not left at 0: the idle hoard is
        # bounded to ~one request's residual instead of 100 units.
        batch = self._R("batch", 50.0)
        picked = sched.select([inter, batch], 1000.0)
        assert sched.virtual_service()["batch"] == 90.0
        # The bounded residual buys batch ONE pop...
        assert picked is batch
        # ...after which one batch charge puts it past interactive and
        # service alternates by weight instead of batch monopolizing.
        sched.charge("batch", 80)  # 80/1 -> 170 > interactive's 100
        assert sched.select([inter, batch], 1000.0) is inter
        # The lagging-but-backlogged class's own debt is never erased:
        # interactive still reads its earned 100, not a clamp artifact.
        assert sched.virtual_service()["interactive"] == 100.0


class TestTokenStream:
    def _result(self, tokens):
        return ServeResult(
            tokens=np.asarray(tokens, np.int32),
            num_generated=len(tokens), bucket_len=8, batch_size=1,
            latency_seconds=0.1, ttft_seconds=0.05,
        )

    def test_feed_iterate_and_result(self):
        stream = TokenStream()
        stream.feed(0, 5)
        stream.feed(1, 7)
        future = Future()
        future.add_done_callback(stream._complete_from_future)
        future.set_result(self._result([5, 7, 9]))
        assert list(stream) == [5, 7, 9]  # done-callback back-fills 9
        assert stream.result(timeout=1).num_generated == 3
        assert stream.done()

    def test_feed_is_idempotent_by_index(self):
        """The failover contract: a deterministic re-run re-feeds from
        index 0 and must not duplicate; a gap must not reorder."""
        stream = TokenStream()
        stream.feed(0, 5)
        stream.feed(1, 7)
        stream.feed(0, 5)  # re-run restarts
        stream.feed(1, 7)
        stream.feed(5, 99)  # gap: dropped, never delivered out of order
        stream.feed(2, 9)
        assert stream.tokens_so_far() == [5, 7, 9]

    def test_failure_raises_after_delivered_tokens(self):
        stream = TokenStream()
        stream.feed(0, 5)
        future = Future()
        future.add_done_callback(stream._complete_from_future)
        future.set_exception(BrownoutShedError("shed"))
        seen = []
        with pytest.raises(BrownoutShedError):
            for token in stream:
                seen.append(token)
        assert seen == [5]
        with pytest.raises(BrownoutShedError):
            stream.result(timeout=1)


class TestEngineQos:
    """Real-engine contracts: class ordering, brownout, streaming
    identity — on a 1-layer TINY model, small budgets (fast tier)."""

    def test_interactive_jumps_the_queue_with_parity(self, model):
        """One decode slot, a queued batch flood, a late interactive
        arrival: with QoS the interactive request completes first —
        and every request still matches its direct generate() run."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,),
            num_slots=1, chunk_tokens=2, qos=QosConfig(),
        )
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(1, 255, 4).astype(np.int32) for _ in range(4)
        ]
        engine = ServingEngine(params, config, serve, start=False)
        order = []
        futures = []
        for i, prompt in enumerate(prompts[:3]):
            future = engine.submit(prompt, priority="batch")
            future.add_done_callback(
                lambda _f, i=i: order.append(f"batch{i}")
            )
            futures.append(future)
        inter = engine.submit(prompts[3], priority="interactive")
        inter.add_done_callback(lambda _f: order.append("interactive"))
        futures.append(inter)
        engine.start()
        results = [f.result(timeout=120) for f in futures]
        engine.close()
        assert order[0] == "interactive", order
        for prompt, result in zip(prompts, results):
            want, n = _direct_tokens(params, config, prompt, 4)
            np.testing.assert_array_equal(result.tokens, want)
            assert result.num_generated == n
        stats = engine.stats()
        assert stats["class_completed"] == {
            "interactive": 1, "standard": 0, "batch": 3,
        }
        assert stats["brownout_shed"] == 0

    def test_brownout_sheds_batch_first_typed(self, model):
        """Queue past the brownout depth: the excess sheds from the
        BATCH class (lowest weight), newest first, with a typed
        BrownoutShedError — the interactive requests all serve."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,),
            num_slots=1, chunk_tokens=1,
            qos=QosConfig(brownout_queue_depth=2),
        )
        rng = np.random.default_rng(1)
        engine = ServingEngine(params, config, serve, start=False)
        batch_futures = [
            engine.submit(
                rng.integers(1, 255, 4).astype(np.int32), priority="batch"
            )
            for _ in range(4)
        ]
        inter_futures = [
            engine.submit(
                rng.integers(1, 255, 4).astype(np.int32),
                priority="interactive",
            )
            for _ in range(2)
        ]
        engine.start()
        for future in inter_futures:
            future.result(timeout=120)  # every interactive serves
        shed = 0
        for future in batch_futures:
            try:
                future.result(timeout=120)
            except BrownoutShedError as exc:
                assert "brownout" in str(exc)
                shed += 1
        engine.close()
        # 6 queued, depth 2 -> 4 shed, all from the batch class.
        assert shed == 4
        stats = engine.stats()
        assert stats["brownout_shed"] == 4
        assert stats["class_shed"] == {
            "interactive": 0, "standard": 0, "batch": 4,
        }
        assert stats["shed"] == 4

    def test_streaming_identity_continuous(self, model):
        """stream=True yields, token for token, exactly the row the
        plain future (and direct generate()) produce — and the stream's
        result() is the same ServeResult."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,),
            num_slots=2, chunk_tokens=2,
        )
        prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
        with ServingEngine(params, config, serve) as engine:
            stream = engine.submit(prompt, stream=True)
            assert isinstance(stream, TokenStream)
            streamed = list(stream)
            result = stream.result(timeout=120)
            plain = engine.submit(prompt).result(timeout=120)
        want, n = _direct_tokens(params, config, prompt, 6)
        assert streamed == list(result.tokens[:result.num_generated])
        np.testing.assert_array_equal(result.tokens, want)
        np.testing.assert_array_equal(plain.tokens, want)
        assert result.num_generated == n

    def test_stream_failure_closes_typed(self, model):
        """A request that never dispatches (close without drain) fails
        its stream with the same typed error as its future."""
        from cloud_tpu.serving import EngineClosedError

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=1,
        )
        engine = ServingEngine(params, config, serve, start=False)
        stream = engine.submit(np.asarray([1, 2], np.int32), stream=True)
        engine.close(drain=False)
        with pytest.raises(EngineClosedError):
            list(stream)

    def test_priority_tag_inert_without_qos(self, model):
        """FIFO path: tags are type-checked, recorded, and inert — any
        class NAME is accepted (custom fleet classes must be
        forwardable to FIFO replicas) while the schedule and the
        schema stay byte-identical FIFO."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=1,
        )
        engine = ServingEngine(params, config, serve, start=False)
        with pytest.raises(ValueError, match="class name"):
            engine.submit(np.asarray([1], np.int32), priority=7)
        engine.submit(np.asarray([1], np.int32), priority="gold")
        future = engine.submit(
            np.asarray([1, 2], np.int32), priority="interactive"
        )
        assert engine.health()["class_backlog"] == {
            "interactive": 0, "standard": 0, "batch": 0,
        }
        engine.start()
        future.result(timeout=120)
        stats = engine.stats()
        engine.close()
        assert stats["class_completed"] == {
            "interactive": 0, "standard": 0, "batch": 0,
        }

    def test_custom_fleet_classes_over_fifo_engines_serve(self, model):
        """Regression (review finding): a QoS fleet with CUSTOM class
        names over plain FIFO ServingEngines must serve — the engine
        records the forwarded tag as inert instead of rejecting a name
        its default ladder never heard of (which typed-failed every
        request of a valid deployment)."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
        )
        custom = QosConfig(
            classes={"gold": PriorityClass(weight=4.0, slo_s=0.5),
                     "bronze": PriorityClass(weight=1.0, slo_s=10.0)},
            default_priority="bronze",
        )
        fleet = Fleet(
            lambda: ServingEngine(params, config, serve),
            _quiet_config(min_replicas=1, qos=custom,
                          poll_interval_s=60.0),
        )
        try:
            prompt = np.asarray([3, 1, 4], np.int32)
            result = fleet.submit(
                prompt, max_new_tokens=3, priority="gold"
            ).result(timeout=120)
            want, n = _direct_tokens(params, config, prompt, 3)
            np.testing.assert_array_equal(result.tokens, want)
            assert fleet.stats()["class_completed"]["gold"] == 1
        finally:
            fleet.close()
        assert not _fleet_threads()

    def test_qos_health_reports_class_backlog(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=1,
            qos=QosConfig(),
        )
        engine = ServingEngine(params, config, serve, start=False)
        engine.submit(np.asarray([1, 2], np.int32), priority="batch")
        engine.submit(np.asarray([3], np.int32), priority="batch")
        engine.submit(np.asarray([4], np.int32))  # default: standard
        health = engine.health()
        engine.close(drain=False)
        assert health["class_backlog"] == {
            "interactive": 0, "standard": 1, "batch": 2,
        }


class _QosFakeEngine(FakeEngine):
    """FakeEngine that understands the QoS submit surface: records the
    priority and feeds ``on_token`` before resolving (twice when asked,
    to prove the stream's failover-dedup)."""

    def __init__(self, name, *, tokens=(5, 7), double_feed=False):
        super().__init__(name)
        self.tokens = list(tokens)
        self.double_feed = double_feed
        self.priorities = []

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None,
               priority=None, on_token=None):
        self.priorities.append(priority)
        future = super().submit(
            prompt, max_new_tokens=max_new_tokens, deadline_s=deadline_s
        )
        if on_token is not None:
            feeds = 2 if self.double_feed else 1
            for _ in range(feeds):
                for i, token in enumerate(self.tokens):
                    on_token(i, token)
        return future


class TestFleetQos:
    def test_schema_zeros_when_qos_off(self):
        """ISSUE 14 schema pin at the FLEET surface: every new key
        exists and reads zero on a QoS-less fleet."""
        fleet = Fleet(_Factory([FakeEngine("a")]), _quiet_config())
        try:
            health = fleet.health()
            stats = fleet.stats()
            zeros = {"interactive": 0, "standard": 0, "batch": 0}
            assert health["class_backlog"] == zeros
            assert stats["quota_rejected"] == 0
            assert stats["brownout_shed"] == 0
            assert stats["class_completed"] == zeros
            assert stats["class_shed"] == zeros
        finally:
            fleet.close()
        assert not _fleet_threads()

    def test_quota_rejects_typed_before_queueing(self):
        engine = _QosFakeEngine("a")
        fleet = Fleet(_Factory([engine]), _quiet_config(qos=QosConfig(
            quotas={"flooder": TenantQuota(
                tokens_per_s=0.001, burst_tokens=10,
            )},
        )))
        try:
            prompt = np.arange(1, 5, dtype=np.int32)  # cost 4 + 4 = 8
            fleet.submit(
                prompt, max_new_tokens=4, tenant="flooder"
            ).result(timeout=10)
            with pytest.raises(QuotaExceededError, match="flooder"):
                fleet.submit(prompt, max_new_tokens=4, tenant="flooder")
            # Other tenants are unaffected (no quota configured).
            fleet.submit(
                prompt, max_new_tokens=4, tenant="other"
            ).result(timeout=10)
            stats = fleet.stats()
            assert stats["quota_rejected"] == 1
            assert stats["submitted"] == 2  # the rejected one never counted
        finally:
            fleet.close()

    def test_default_quota_binds_unlisted_tenants(self):
        fleet = Fleet(_Factory([_QosFakeEngine("a")]), _quiet_config(
            qos=QosConfig(default_quota=TenantQuota(
                tokens_per_s=0.001, burst_tokens=5,
            )),
        ))
        try:
            prompt = np.arange(1, 4, dtype=np.int32)  # cost 3 + 3 = 6
            with pytest.raises(QuotaExceededError):
                fleet.submit(prompt, max_new_tokens=3, tenant="anyone")
            # No tenant named: no bucket charged.
            fleet.submit(prompt, max_new_tokens=3).result(timeout=10)
        finally:
            fleet.close()

    def test_quota_refunded_when_admission_rejects(self):
        """A charge whose request is then refused admission never
        burns: tokens only pay for work the fleet accepted."""
        fleet = Fleet(
            _Factory([_QosFakeEngine("a")]),
            _quiet_config(
                max_queue=1, admission="reject",
                qos=QosConfig(quotas={"t": TenantQuota(
                    tokens_per_s=0.001, burst_tokens=100,
                )}),
            ),
            start=False,  # no router: the queue stays full
        )
        prompt = np.arange(1, 5, dtype=np.int32)  # cost 4 + 4 = 8
        fleet.submit(prompt, max_new_tokens=4)  # fills the queue
        with pytest.raises(QueueFullError):
            fleet.submit(prompt, max_new_tokens=4, tenant="t")
        bucket = fleet._tenant_bucket("t")
        assert bucket.available() == pytest.approx(100.0)  # refunded
        # And a quota rejection is NOT counted as a fleet rejection.
        assert fleet.stats()["rejected"] == 1
        assert fleet.stats()["quota_rejected"] == 0
        fleet.close(drain=False)

    def test_unbudgeted_submit_charges_default_cost(self):
        """Omitting max_new_tokens must not bypass the quota: the
        configured unbudgeted_decode_cost is charged instead."""
        fleet = Fleet(
            _Factory([_QosFakeEngine("a")]),
            _quiet_config(qos=QosConfig(
                unbudgeted_decode_cost=10,
                quotas={"t": TenantQuota(
                    tokens_per_s=0.001, burst_tokens=12,
                )},
            )),
        )
        try:
            prompt = np.arange(1, 4, dtype=np.int32)  # cost 3 + 10 = 13
            with pytest.raises(QuotaExceededError):
                fleet.submit(prompt, tenant="t")
        finally:
            fleet.close()

    def test_fairness_charged_once_across_failover_requeue(self):
        """A request popped, failed over, and popped again charges its
        class's fairness debt exactly once."""
        fleet = Fleet(
            _Factory([_QosFakeEngine("a")]),
            _quiet_config(qos=QosConfig()),
            start=False,
        )
        fleet.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=4,
                     priority="batch")
        with fleet._cond:
            request = fleet._pop_request_locked(time.perf_counter())
        first = fleet._qos_sched.virtual_service()["batch"]
        assert first == pytest.approx(8.0)  # (4 + 4) / weight 1
        with fleet._cond:
            fleet._queue.appendleft(request)  # the failover re-entry
            fleet._pop_request_locked(time.perf_counter())
        assert fleet._qos_sched.virtual_service()["batch"] == first
        fleet.close(drain=False)

    def test_unknown_priority_typed(self):
        fleet = Fleet(_Factory([_QosFakeEngine("a")]),
                      _quiet_config(qos=QosConfig()))
        try:
            with pytest.raises(ValueError, match="unknown priority"):
                fleet.submit(np.asarray([1], np.int32), priority="vip")
        finally:
            fleet.close()

    def test_priority_forwarded_to_engines(self):
        engine = _QosFakeEngine("a")
        fleet = Fleet(_Factory([engine]),
                      _quiet_config(qos=QosConfig()))
        try:
            fleet.submit(
                np.asarray([1], np.int32), priority="interactive"
            ).result(timeout=10)
            fleet.submit(np.asarray([2], np.int32)).result(timeout=10)
            assert engine.priorities == ["interactive", "standard"]
            stats = fleet.stats()
            assert stats["class_completed"] == {
                "interactive": 1, "standard": 1, "batch": 0,
            }
        finally:
            fleet.close()

    def test_stream_through_fleet_dedups_refeeds(self):
        """The fleet stream survives a double feed (the failover
        re-run footprint) without duplicates, and closes with the
        fleet-re-based result."""
        engine = _QosFakeEngine("a", tokens=(5, 7), double_feed=True)
        fleet = Fleet(_Factory([engine]), _quiet_config())
        try:
            stream = fleet.submit(np.asarray([1, 2], np.int32),
                                  stream=True)
            assert isinstance(stream, TokenStream)
            result = stream.result(timeout=10)
            assert result == {"served_by": "a"}  # fake result passthrough
            assert stream.tokens_so_far() == [5, 7]
        finally:
            fleet.close()

    def test_fleet_brownout_sheds_batch_first(self):
        """Queue held at the fleet (no router thread): the brownout
        pass sheds the excess from the batch class only, newest first,
        typed."""
        fleet = Fleet(
            _Factory([_QosFakeEngine("a")]),
            _quiet_config(qos=QosConfig(brownout_queue_depth=2)),
            start=False,  # no router: the queue is deterministic
        )
        futures = []
        for i in range(3):
            futures.append(fleet.submit(
                np.asarray([i + 1], np.int32), priority="batch"
            ))
        futures.append(fleet.submit(
            np.asarray([9], np.int32), priority="interactive"
        ))
        with fleet._cond:
            shed = fleet._shed_brownout_locked(time.perf_counter())
        assert shed == 2
        # Newest batch requests shed; oldest batch + interactive kept.
        assert futures[0].done() is False
        for future in futures[1:3]:
            with pytest.raises(BrownoutShedError):
                future.result(timeout=1)
        assert futures[3].done() is False
        stats = fleet.stats()
        assert stats["brownout_shed"] == 2
        assert stats["class_shed"] == {
            "interactive": 0, "standard": 0, "batch": 2,
        }
        fleet.close(drain=False)
        assert not _fleet_threads()

    def test_fleet_pops_by_qos_order(self):
        """With QoS armed the router serves the fleet queue by (slack,
        fairness debt), not arrival: a late interactive request is
        routed before the earlier batch flood."""
        engine = _QosFakeEngine("a")
        fleet = Fleet(
            _Factory([engine]),
            _quiet_config(qos=QosConfig()),
            start=False,
        )
        for i in range(3):
            fleet.submit(np.asarray([i + 1], np.int32), priority="batch")
        fleet.submit(np.asarray([9], np.int32), priority="interactive")
        fleet.start()
        deadline = time.time() + 10
        while len(engine.priorities) < 4 and time.time() < deadline:
            time.sleep(0.01)
        fleet.close()
        assert engine.priorities[0] == "interactive", engine.priorities

    def test_class_backlog_aggregates_replica_backlogs(self):
        """fleet.health() class_backlog = fleet queue + every replica's
        own (QoS engines report theirs; fakes report none)."""
        engine = _QosFakeEngine("a")
        fleet = Fleet(
            _Factory([engine]),
            _quiet_config(qos=QosConfig()),
            start=False,
        )
        fleet.submit(np.asarray([1], np.int32), priority="batch")
        fleet.submit(np.asarray([2], np.int32), priority="batch")
        fleet.submit(np.asarray([3], np.int32), priority="interactive")
        health = fleet.health()
        assert health["class_backlog"] == {
            "interactive": 1, "standard": 0, "batch": 2,
        }
        fleet.close(drain=False)


class TestQosAutoscaler:
    def test_class_backlog_triggers_scale_up(self):
        """A sustained interactive backlog scales up even when the
        TOTAL depth sits below the total threshold."""
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=3,
            scale_up_queue_depth=100.0,  # total signal can't fire
            window=2, cooldown=0,
            class_scale_up_depth={"interactive": 2.0},
        ))
        backlog = {"interactive": 3, "batch": 0}
        assert scaler.observe(
            queue_depth=3, ready_replicas=1, class_backlog=backlog
        ) == "hold"  # window not full yet
        assert scaler.observe(
            queue_depth=3, ready_replicas=1, class_backlog=backlog
        ) == "up"

    def test_one_interactive_burst_does_not_scale(self):
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=3,
            scale_up_queue_depth=100.0, window=2, cooldown=0,
            class_scale_up_depth={"interactive": 2.0},
        ))
        scaler.observe(queue_depth=5, ready_replicas=1,
                       class_backlog={"interactive": 5})
        assert scaler.observe(
            queue_depth=0, ready_replicas=1,
            class_backlog={"interactive": 0},
        ) == "hold"

    def test_class_depth_validation(self):
        with pytest.raises(ValueError, match="class_scale_up_depth"):
            AutoscaleConfig(class_scale_up_depth={"interactive": 0.0})

    def test_no_class_signal_is_byte_identical(self):
        """Without class thresholds the decision path is the pre-QoS
        one whatever class_backlog says."""
        scaler = QueueDepthAutoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=2, scale_up_queue_depth=2.0,
            window=2, cooldown=0,
        ))
        scaler.observe(queue_depth=4, ready_replicas=1,
                       class_backlog={"interactive": 4})
        assert scaler.observe(
            queue_depth=4, ready_replicas=1,
            class_backlog={"interactive": 4},
        ) == "up"


class _HealthReplica:
    """Minimal replica-shaped object for pure router tests."""

    def __init__(self, rid, health):
        self.id = rid
        self._health = dict(health)

    def health(self):
        return dict(self._health)

    def routable(self, health=None):
        return True


def _replica_with_backlog(rid, *, active, backlog):
    depth = sum(backlog.values())
    return _HealthReplica(rid, {
        "ready": True, "queue_depth": depth, "active_slots": active,
        "num_slots": 4, "class_backlog": backlog,
    })


class TestQosRouter:
    WEIGHTS = {"interactive": 8.0, "standard": 4.0, "batch": 1.0}

    def test_batch_backlog_discounted_for_interactive_requests(self):
        """An interactive arrival prefers the replica whose backlog is
        batch-class (its QoS engine will admit past it) over one with
        a smaller but interactive backlog."""
        batchy = _replica_with_backlog(
            0, active=0, backlog={"interactive": 0, "batch": 8},
        )
        interactivey = _replica_with_backlog(
            1, active=0, backlog={"interactive": 3, "batch": 0},
        )
        router = LeastLoadedRouter(class_weights=self.WEIGHTS)
        # batchy load for interactive = 8 * (1/8) = 1 < 3.
        best, _ = router.pick([batchy, interactivey],
                              priority="interactive")
        assert best.id == 0
        # Plain load (no priority): batchy 8 > interactivey 3.
        best, _ = router.pick([batchy, interactivey])
        assert best.id == 1

    def test_same_or_higher_class_counts_in_full(self):
        a = _replica_with_backlog(
            0, active=0, backlog={"interactive": 4, "batch": 0},
        )
        b = _replica_with_backlog(
            1, active=0, backlog={"interactive": 0, "batch": 5},
        )
        router = LeastLoadedRouter(class_weights=self.WEIGHTS)
        # For a BATCH request nothing is discounted (everything queued
        # is same-or-higher class): a=4 < b=5.
        best, _ = router.pick([a, b], priority="batch")
        assert best.id == 0

    def test_unclassed_queue_depth_counts_in_full(self):
        """A replica whose own QoS is off reports zero class backlog;
        its raw queue depth must still count."""
        plain = _HealthReplica(0, {
            "ready": True, "queue_depth": 6, "active_slots": 0,
            "num_slots": 4,
            "class_backlog": {"interactive": 0, "batch": 0},
        })
        empty = _replica_with_backlog(
            1, active=1, backlog={"interactive": 0, "batch": 0},
        )
        router = LeastLoadedRouter(class_weights=self.WEIGHTS)
        best, _ = router.pick([plain, empty], priority="interactive")
        assert best.id == 1

    def test_class_weight_validation(self):
        with pytest.raises(ValueError, match="class_weights"):
            LeastLoadedRouter(class_weights={"interactive": 0.0})


class TestQosReport:
    def _event(self, name, dur_s, **args):
        return {"name": name, "ph": "X", "ts": 0, "dur": dur_s * 1e6,
                "args": args}

    def test_qos_summary_per_class_percentiles(self):
        events = [
            self._event("serve/request", 1.0, priority="interactive",
                        ttft_s=0.1),
            self._event("serve/request", 2.0, priority="interactive",
                        ttft_s=0.2),
            self._event("serve/request", 3.0, priority="interactive",
                        ttft_s=0.9),
            self._event("serve/request", 8.0, priority="batch",
                        ttft_s=4.0),
        ]
        report = TraceReport(events)
        summary = report.qos_summary()
        classes = summary["classes"]
        assert classes["interactive"]["requests"] == 3
        assert classes["interactive"]["ttft_p50_s"] == pytest.approx(0.2)
        assert classes["interactive"]["ttft_p99_s"] == pytest.approx(0.9)
        assert classes["batch"]["latency_p99_s"] == pytest.approx(8.0)
        rendered = report.render()
        assert "QoS classes" in rendered
        assert "interactive: 3 request(s)" in rendered

    def test_no_qos_spans_no_section(self):
        report = TraceReport([
            self._event("serve/chunk", 0.1, tokens=4, occupancy=0.5),
        ])
        assert report.qos_summary() is None
        assert "QoS classes" not in report.render()

    def test_empty_timeline_does_not_crash(self):
        assert TraceReport([]).qos_summary() is None
