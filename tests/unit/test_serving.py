"""Serving-engine tests: scheduling must be observationally invisible.

The load-bearing contract (ISSUE 4 + ISSUE 6 acceptance): for a
mixed-length request set — including staggered arrivals and mixed
per-request decode budgets — engine outputs are token-for-token
identical (greedy) to per-request ``generation.generate`` calls.
Bucket padding, batch padding rows, co-batching with strangers, slot
reuse over stale cache, and mid-chunk expiry must never leak into a
request's tokens.  Around that: the scheduler's slot
lifecycle (insert-into-freed-slot, per-slot ``max_new_tokens`` expiry,
drain of a partially full grid, one-chunk-compile retrace guard),
admission control
(block/reject + typed errors), graceful drain on shutdown, AOT warmup
through the compile-cache registry, and the same thread-hygiene
guarantee as test_pipeline_engine — a closed engine owns zero live
threads.
"""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import generation, transformer
from cloud_tpu.serving import (
    EngineClosedError,
    QueueFullError,
    ServeConfig,
    ServingEngine,
    SERVE_SCHEDULER_THREAD_NAME,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Every thread the engine may own while live (scheduler + the
#: compile-ahead warmup worker); the leak guard asserts none survive
#: close() — same discipline as test_pipeline_engine's prefetch guard.
ENGINE_THREAD_PREFIXES = ("cloud-tpu-serve", "cloud-tpu-compile-ahead")


def _engine_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith(ENGINE_THREAD_PREFIXES)
    ]


@pytest.fixture(scope="module")
def model():
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _eos_after_first(greedy):
    """(eos, num_generated) for an eos that retires a greedy run early but
    not at once: the first token that differs from ``greedy[0]``."""
    stop = next(i for i, t in enumerate(greedy) if t != greedy[0])
    assert 0 < stop < len(greedy) - 1, greedy
    return int(greedy[stop]), stop + 1


def _direct(params, config, prompt, max_new_tokens,
            sample=generation.SampleConfig(temperature=0.0)):
    return generation.generate(
        params, jnp.asarray(prompt[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=max_new_tokens, sample=sample,
    )


class TestParity:
    @pytest.mark.slow
    def test_mixed_lengths_match_unbatched_generate(self, model):
        """The acceptance criterion: 6 ragged prompts spanning two
        buckets, batched by the engine, each identical to its own
        unbatched greedy run.

        Slow tier (the PR 8 wall-clock move): the churn parity tests of
        ``TestContinuous`` hold the same contract per commit."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8, 16), num_slots=4,
        )
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(1, 255, n).astype(np.int32)
            for n in (3, 8, 12, 5, 16, 2)
        ]
        engine = ServingEngine(params, config, serve, start=False)
        futures = [engine.submit(p) for p in prompts]
        engine.start()  # all queued up front: the grid fills at once
        results = [f.result(timeout=120) for f in futures]
        engine.close()

        for prompt, result in zip(prompts, results):
            want = _direct(params, config, prompt, 5)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
            assert result.num_generated == int(want["num_generated"][0])
        stats = engine.stats()
        assert stats["completed"] == len(prompts)
        # Batching actually happened: the chunks carried more than one
        # request's tokens at a time.
        assert stats["mean_slot_occupancy"] > 1.0 / serve.num_slots

    def test_per_request_max_new_tokens_trims(self, model):
        """A request below the engine-wide decode length gets exactly a
        shorter direct run's tokens (greedy is prefix-consistent)."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,), num_slots=1,
        )
        prompt = np.asarray([5, 9, 17, 2], np.int32)
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(prompt, max_new_tokens=3).result(
                timeout=120
            )
        want = _direct(params, config, prompt, 3)
        assert result.tokens.shape == (3,)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert result.num_generated == int(want["num_generated"][0])

    def test_eos_parity_through_engine(self, model):
        """eos handling (emit, then pad) survives the batched path."""
        config, params = model
        prompt = np.asarray([7, 3, 11, 2], np.int32)
        greedy = np.asarray(_direct(params, config, prompt, 6)["tokens"])[0]
        eos, n_until_eos = _eos_after_first(greedy)
        sample = generation.SampleConfig(temperature=0.0, eos_id=eos,
                                         pad_id=0)
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,), num_slots=2,
            sample=sample,
        )
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(prompt).result(timeout=120)
        want = _direct(params, config, prompt, 6, sample=sample)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert result.num_generated == int(
            want["num_generated"][0]
        ) == n_until_eos

    def test_sampled_decode_deterministic_per_seed(self, model):
        """Non-greedy serving: the engine owns the rng chain, so the same
        seed + the same deterministic admission order reproduces."""
        config, params = model
        rng = np.random.default_rng(1)
        prompts = [
            rng.integers(1, 255, n).astype(np.int32) for n in (3, 5, 7, 4)
        ]

        def run():
            serve = ServeConfig(
                max_new_tokens=4, prompt_buckets=(8,), num_slots=4,
                seed=7,
                sample=generation.SampleConfig(temperature=0.9, top_k=20),
            )
            engine = ServingEngine(params, config, serve, start=False)
            futures = [engine.submit(p) for p in prompts]
            engine.start()  # 4 queued = the whole grid, in FIFO order
            results = [f.result(timeout=120) for f in futures]
            engine.close()
            return results

        first, second = run(), run()
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a.tokens, b.tokens)


class TestAdmission:
    def test_reject_policy_raises_typed_error(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=8,
            max_queue=2, admission="reject",
        )
        engine = ServingEngine(params, config, serve, start=False)
        prompt = np.asarray([1, 2], np.int32)
        first, second = engine.submit(prompt), engine.submit(prompt)
        with pytest.raises(QueueFullError):
            engine.submit(prompt)
        assert engine.stats()["rejected"] == 1
        engine.close()  # never started: owed requests fail, typed
        for f in (first, second):
            with pytest.raises(EngineClosedError):
                f.result(timeout=5)

    def test_submit_validation(self, model):
        config, params = model
        serve = ServeConfig(max_new_tokens=2, prompt_buckets=(8,),
                            num_slots=1)
        engine = ServingEngine(params, config, serve, start=False)
        with pytest.raises(ValueError, match="1-D"):
            engine.submit(np.zeros((2, 2), np.int32))
        with pytest.raises(ValueError, match="outside"):
            engine.submit(np.zeros((9,), np.int32))  # > largest bucket
        with pytest.raises(ValueError, match="outside"):
            engine.submit(np.zeros((0,), np.int32))
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.submit(np.asarray([1], np.int32), max_new_tokens=3)
        engine.close()

    def test_config_validation(self):
        with pytest.raises(ValueError, match="increasing"):
            ServeConfig(prompt_buckets=(16, 8))
        with pytest.raises(ValueError, match="admission"):
            ServeConfig(admission="drop")
        with pytest.raises(ValueError, match="max_new_tokens"):
            ServeConfig(max_new_tokens=0)

    @pytest.mark.parametrize("name,value", [
        ("scheduler", "batch"),
        ("batch_buckets", (1, 2)),
        ("flush_deadline_s", 0.01),
    ], ids=["scheduler", "batch_buckets", "flush_deadline_s"])
    def test_retired_options_are_refused(self, name, value):
        """The batch scheduler went with its three options: asking for
        one is a TypeError at construction, not a silently inert knob."""
        with pytest.raises(TypeError, match=name):
            ServeConfig(**{name: value})

    def test_num_slots_default(self, model):
        config, params = model
        assert ServeConfig().num_slots == 8
        engine = ServingEngine(
            params, config,
            ServeConfig(max_new_tokens=2, prompt_buckets=(8,)),
            start=False,
        )
        try:
            assert engine.health()["num_slots"] == 8
            assert engine.health()["free_slots"] == 8
            assert all(
                shape[1] == 8 for shape in engine.placement()["kv_shapes"])
        finally:
            engine.close()

    def test_submit_after_close_raises(self, model):
        config, params = model
        serve = ServeConfig(max_new_tokens=2, prompt_buckets=(8,),
                            num_slots=1)
        engine = ServingEngine(params, config, serve, start=False)
        engine.close()
        with pytest.raises(EngineClosedError):
            engine.submit(np.asarray([1], np.int32))


class TestShutdown:
    def test_close_drains_admitted_requests(self, model):
        """Admitted-but-unbatched requests (deadline far away, batch not
        full) are served — not dropped — by a draining close."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=8,
        )
        engine = ServingEngine(params, config, serve)
        futures = [
            engine.submit(np.asarray([1, 2, i], np.int32))
            for i in range(1, 4)
        ]
        engine.close()  # drain=True default
        for f in futures:
            assert f.result(timeout=5) is not None
        assert engine.stats()["completed"] == 3

    def test_no_threads_leak_after_close(self, model):
        """The acceptance criterion's hygiene half: scheduler + warmup
        worker both joined by close()."""
        config, params = model
        assert not _engine_threads()
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=1,
            warmup=True,
        )
        with ServingEngine(params, config, serve) as engine:
            assert any(
                t.name == SERVE_SCHEDULER_THREAD_NAME
                for t in threading.enumerate()
            )
            engine.submit(np.asarray([4, 2], np.int32)).result(timeout=120)
        assert not _engine_threads()

    def test_close_is_idempotent(self, model):
        config, params = model
        serve = ServeConfig(max_new_tokens=2, prompt_buckets=(8,),
                            num_slots=1)
        engine = ServingEngine(params, config, serve)
        engine.close()
        engine.close()


class TestHealth:
    """The health() load-signal contract (ISSUE 8): the fleet router
    reads ``queue_depth``/``active_slots``/``num_slots`` off every
    routing decision, so the keys are pinned here alongside the
    pre-existing readiness keys, which must stay stable."""

    #: Keys the PR 6 consumers (check_chaos, external supervisors)
    #: already depend on.
    STABLE_KEYS = (
        "healthy", "ready", "live", "reason", "closed", "waiting",
        "orphaned_dispatches", "last_dispatch_age_s",
    )

    def _assert_load_signal(self, health, serve):
        for key in self.STABLE_KEYS:
            assert key in health, key
        assert health["queue_depth"] == health["waiting"]
        assert isinstance(health["active_slots"], int)
        assert health["active_slots"] >= 0
        assert health["num_slots"] == serve.num_slots
        # ISSUE 10: the prefix-cache load signal is part of the schema
        # (zeros when the cache is off), so the fleet router reads one
        # stable shape.
        for key in ("prefix_cache_blocks", "prefix_hit_tokens",
                    "evictions"):
            assert health[key] == 0, key
        # ISSUE 15: the host-DRAM tier keys and the cost-model router's
        # cached-prefix summary are schema too — zeros / empty whenever
        # the tier (or the whole prefix cache) is off.
        for key in ("prefix_dram_blocks", "prefix_dram_hits",
                    "prefix_dram_hit_tokens", "prefix_dram_demotions",
                    "prefix_dram_evictions",
                    "prefix_dram_swapin_failures"):
            assert health[key] == 0, key
        assert health["cached_prefixes"] == {}
        # ISSUE 12: the speculative-decoding keys are schema too —
        # zeros whenever draft=None.
        assert health["spec_acceptance_rate"] == 0.0
        assert health["spec_k"] == 0
        # ISSUE 14: the QoS per-class backlog is schema — all-zeros
        # whenever qos=None (the FIFO path never classes its queue,
        # even when requests carry tags).
        assert health["class_backlog"] == {
            "interactive": 0, "standard": 0, "batch": 0,
        }
        # ISSUE 17: the decode-kernel selection is schema — the default
        # is (and must stay) the XLA path.
        assert health["decode_kernel"] == "xla"
        # ISSUE 19: the disaggregated-serving keys are schema —
        # role "both" and zero handoff counters whenever
        # no role is assigned and no handoff submits arrive (pinned
        # byte-identical to the colocated engine).
        assert health["role"] == "both"
        for key in ("handoff_exports", "handoff_export_blocks",
                    "handoff_imports", "handoff_import_blocks"):
            assert health[key] == 0, key

    def _assert_qos_stats_zero(self, stats):
        """ISSUE 14: the QoS stats keys are schema — zeros whenever
        qos=None."""
        assert stats["brownout_shed"] == 0
        zeros = {"interactive": 0, "standard": 0, "batch": 0}
        assert stats["class_completed"] == zeros
        assert stats["class_shed"] == zeros
        assert stats["class_backlog"] == zeros
        # ISSUE 26: the KV accounting is schema: in use never exceeds
        # reserved.
        assert 0 <= stats["kv_row_steps_in_use"] <= (
            stats["kv_row_steps_reserved"])
        assert 0 <= stats["kv_bytes_in_use"] <= stats["kv_bytes_reserved"]
        # ISSUE 17: block-table prefix attaches are schema too — zero
        # whenever decode_kernel="xla" (hits copy, never attach).
        assert stats["prefix_attaches"] == 0
        # ISSUE 19: the disagg stats keys mirror health — "both"/zeros
        # on every engine that never serves a handoff leg.
        assert stats["role"] == "both"
        for key in ("handoff_exports", "handoff_export_blocks",
                    "handoff_imports", "handoff_import_blocks"):
            assert stats[key] == 0, key

    def test_continuous_health_carries_load_signal(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=1,
        )
        with ServingEngine(params, config, serve) as engine:
            health = engine.health()
            self._assert_load_signal(health, serve)
            assert health["queue_depth"] == 0
            assert health["active_slots"] == 0
            assert health["free_slots"] == serve.num_slots
            engine.submit(np.asarray([1, 2], np.int32)).result(timeout=120)
            self._assert_load_signal(engine.health(), serve)
            self._assert_qos_stats_zero(engine.stats())


class TestDecodeKernel:
    """ISSUE 17: the paged decode-attention kernel on the serving path.

    ``decode_kernel="pallas"`` routes decode / chunked-prefill / verify
    attention through the block-table paged kernel (interpreted on this
    CPU rig — the same kernel body Mosaic compiles on TPU), and the
    contract is the usual one: token-identical to per-request
    ``generate()``, with prefix hits attaching pool blocks read-in-place
    instead of dispatching ``copy_prefix_program``.  The default
    ``"xla"`` config must stay byte-identical to pre-PR behavior."""

    def _parity(self, model, serve, prompts, budgets=None):
        config, params = model
        budgets = budgets or [serve.max_new_tokens] * len(prompts)
        engine = ServingEngine(params, config, serve)
        try:
            futures = [
                engine.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)
            ]
            results = [f.result(timeout=240) for f in futures]
            for prompt, budget, result in zip(prompts, budgets, results):
                want = _direct(params, config, prompt, budget)
                np.testing.assert_array_equal(
                    result.tokens, np.asarray(want["tokens"])[0]
                )
                assert result.num_generated == int(
                    want["num_generated"][0]
                )
            return engine, engine.stats()
        finally:
            engine.close()

    def test_pallas_cold_insert_parity(self, model):
        from cloud_tpu.ops import paged_attention

        before = paged_attention.KERNEL_TRACE_COUNT
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, decode_kernel="pallas",
        )
        prompts = [np.asarray([5, 3, 1], np.int32),
                   np.asarray([9, 2, 7, 4, 6], np.int32)]
        engine, _ = self._parity(model, serve, prompts)
        assert engine.health()["decode_kernel"] == "pallas"
        # The kernel path (not the jnp reference) is what traced.
        assert paged_attention.KERNEL_TRACE_COUNT > before

    def test_pallas_kv_quant_parity(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, kv_quant=True, decode_kernel="pallas",
        )
        prompts = [np.asarray([5, 3, 1], np.int32),
                   np.asarray([9, 2, 7, 4, 6], np.int32)]
        with ServingEngine(params, config, serve) as engine:
            futures = [engine.submit(p) for p in prompts]
            results = [f.result(timeout=240) for f in futures]
        for prompt, result in zip(prompts, results):
            # The oracle is QUANTIZED generate: kv_quant rounding is the
            # engine's pre-existing contract; the kernel must match it
            # bit for bit, not the f32 path.
            direct = generation.generate(
                params, jnp.asarray(prompt[None, :]),
                jnp.asarray([len(prompt)], np.int32), config,
                max_new_tokens=3,
                sample=generation.SampleConfig(temperature=0.0),
                kv_quant=True,
            )
            np.testing.assert_array_equal(
                result.tokens, np.asarray(direct["tokens"])[0]
            )

    def test_pallas_speculation_parity(self, model):
        from cloud_tpu.serving import DraftConfig

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            draft=DraftConfig(config=config, params=params, spec_k=2),
            decode_kernel="pallas",
        )
        prompts = [np.asarray([5, 3, 1], np.int32),
                   np.asarray([9, 2, 7, 4, 6], np.int32)]
        engine, stats = self._parity(model, serve, prompts)
        assert stats["spec_chunks"] > 0  # the verify path actually ran

    def test_pallas_prefix_hit_attaches_without_copy(self, model):
        """The tentpole's acceptance bar: a prefix hit under the kernel
        path attaches pool blocks through the block table — parity
        holds, the attach stat advances, and the copy program is NEVER
        compiled (warmup included)."""
        from cloud_tpu.monitoring import tracing

        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(16,), num_slots=2,
            chunk_tokens=2, prefix_cache_blocks=8, prefix_block_tokens=4,
            prefill_chunk_tokens=4, warmup=False,
            decode_kernel="pallas",
        )
        head = np.asarray([7, 1, 4, 2, 9, 3, 5, 8], np.int32)
        prompts = [np.concatenate([head, [11]]).astype(np.int32),
                   np.concatenate([head, [13, 12]]).astype(np.int32)]
        config, params = model
        engine = ServingEngine(params, config, serve)
        try:
            with tracing.collecting() as collector:
                # Sequential: the second request must hit the first's
                # saved blocks.
                for prompt in prompts:
                    result = engine.submit(prompt).result(timeout=240)
                    want = _direct(params, config, prompt, 3)
                    np.testing.assert_array_equal(
                        result.tokens, np.asarray(want["tokens"])[0]
                    )
            stats = engine.stats()
        finally:
            engine.close()
        assert stats["prefix_hits"] >= 1
        assert stats["prefix_attaches"] >= 1
        assert engine._copy_traces == 0
        agg = collector.aggregates()
        assert agg.get("serve/prefix_attach", {}).get("count", 0) >= 1
        assert not any(
            e["name"] == "serve/prefix_copy" for e in collector.events()
        )

    def test_xla_default_is_inert(self, model):
        """Byte-identity pin for the default config: no block table, no
        attach stat movement, prefix hits still COPY (the pre-PR path),
        and no ``serve/prefix_attach`` span ever emitted."""
        from cloud_tpu.monitoring import tracing

        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(16,), num_slots=2,
            chunk_tokens=2, prefix_cache_blocks=8, prefix_block_tokens=4,
            warmup=False,
        )
        assert serve.decode_kernel == "xla"
        head = np.asarray([7, 1, 4, 2, 9, 3, 5, 8], np.int32)
        prompts = [np.concatenate([head, [11]]).astype(np.int32),
                   np.concatenate([head, [13, 12]]).astype(np.int32)]
        config, params = model
        engine = ServingEngine(params, config, serve)
        try:
            with tracing.collecting() as collector:
                for prompt in prompts:
                    result = engine.submit(prompt).result(timeout=240)
                    want = _direct(params, config, prompt, 3)
                    np.testing.assert_array_equal(
                        result.tokens, np.asarray(want["tokens"])[0]
                    )
            stats = engine.stats()
        finally:
            engine.close()
        assert engine._block_table is None
        assert stats["prefix_hits"] >= 1
        assert stats["prefix_attaches"] == 0
        assert engine._copy_traces >= 1  # hits still copy, as pre-PR
        assert not any(
            e["name"] == "serve/prefix_attach"
            for e in collector.events()
        )

    def test_decode_kernel_validation(self):
        with pytest.raises(ValueError, match="decode_kernel"):
            ServeConfig(decode_kernel="bogus")


class TestObservability:
    def test_serve_spans_and_metrics_recorded(self, model):
        from cloud_tpu.monitoring import metrics, tracing

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=2,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                engine.submit(
                    np.asarray([1, 2, 3], np.int32)
                ).result(timeout=120)
        agg = collector.aggregates()
        for name in ("serve/queue_wait", "serve/prefill", "serve/chunk"):
            assert agg.get(name, {}).get("count", 0) >= 1, name
        snap = metrics.snapshot()
        assert snap["counters"].get("serve/requests", 0) >= 1
        assert snap["counters"].get("serve/chunks", 0) >= 1
        assert "serve/slot_occupancy" in snap["gauges"]
        assert "serve/latency_seconds" in snap["distributions"]

    def test_traced_request_emits_terminal_span_on_fifo(self, model):
        """ISSUE 16: a request submitted WITH a trace context gets the
        terminal ``serve/request`` span (trace_id + ttft_s, no phantom
        QoS priority) even on the FIFO path, its result carries the id,
        and every lifecycle span it touched stamps the same id."""
        from cloud_tpu.monitoring import tracing

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=2,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                ctx = tracing.new_trace_context()
                result = engine.submit(
                    np.asarray([1, 2, 3], np.int32), trace=ctx
                ).result(timeout=120)
        assert result.trace_id == ctx.trace_id
        events = collector.events()
        terminals = [e for e in events if e["name"] == "serve/request"]
        assert len(terminals) == 1
        args = terminals[0]["args"]
        assert args["trace_id"] == ctx.trace_id
        assert isinstance(args["ttft_s"], float) and args["ttft_s"] > 0
        assert args["tokens"] == 2
        assert "priority" not in args  # FIFO: no phantom QoS class
        waits = [e for e in events if e["name"] == "serve/queue_wait"]
        assert any(
            (e["args"] or {}).get("trace_id") == ctx.trace_id
            for e in waits
        )

    def test_traced_request_rides_the_chunk_slot_map(self, model):
        """Continuous scheduler: shared decode dispatches serve many
        slots, so the chunk span carries a slot -> trace_id map instead
        of a single id, and the terminal span still stitches."""
        from cloud_tpu.monitoring import tracing

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=1,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                ctx = tracing.new_trace_context()
                result = engine.submit(
                    np.asarray([5, 6], np.int32), trace=ctx
                ).result(timeout=120)
        assert result.trace_id == ctx.trace_id
        events = collector.events()
        chunks = [e for e in events if e["name"] == "serve/chunk"]
        assert any(
            ctx.trace_id in ((e["args"] or {}).get("traces") or {}).values()
            for e in chunks
        )
        terminals = [e for e in events if e["name"] == "serve/request"]
        assert [e["args"]["trace_id"] for e in terminals] == [ctx.trace_id]

    def test_untraced_span_set_is_unchanged(self, model):
        """ISSUE 26's contract, which replaced "the span set stays
        byte-identical when untraced": with the collector on and no
        context passed, ``submit`` mints the request's id and the
        request leaves one terminal span under it; with the collector
        off nothing is minted and nothing recorded."""
        from cloud_tpu.monitoring import tracing

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8,), num_slots=2,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                result = engine.submit(
                    np.asarray([1, 2, 3], np.int32)
                ).result(timeout=120)
        assert result.trace_id is not None
        events = collector.events()
        terminals = [e for e in events if e["name"] == "serve/request"]
        assert [e["args"]["trace_id"] for e in terminals] == [
            result.trace_id]
        assert "priority" not in terminals[0]["args"]
        waits = [e for e in events if e["name"] == "serve/queue_wait"]
        assert [e["args"]["trace_id"] for e in waits] == [result.trace_id]

        assert tracing.active() is None
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(
                np.asarray([1, 2, 3], np.int32)
            ).result(timeout=120)
        assert result.trace_id is None
        assert tracing.timeline_events() == []


class TestContinuous:
    """The ISSUE 6 tentpole: slot-based in-flight decode.  Parity under
    churn, slot lifecycle, drain and the one-chunk-compile retrace
    guard."""

    #: A churn workload: 10 ragged prompts across two buckets with mixed
    #: per-request decode budgets — enough traffic that every slot of a
    #: 4-slot grid is reused at least once.
    CHURN_LENS = (3, 8, 12, 5, 16, 2, 7, 9, 4, 6)
    CHURN_BUDGETS = (5, 2, 4, 1, 5, 3, 5, 2, 4, 5)

    def _churn_prompts(self):
        rng = np.random.default_rng(2)
        return [
            rng.integers(1, 255, n).astype(np.int32) for n in self.CHURN_LENS
        ]

    def _run_churn(self, params, config, serve, stagger=True):
        """Submit the churn workload (staggered mid-stream unless told
        otherwise), resolve everything, close, return (results, engine)."""
        prompts = self._churn_prompts()
        engine = ServingEngine(params, config, serve)
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                engine.submit(prompt, max_new_tokens=self.CHURN_BUDGETS[i])
            )
            if stagger and i in (3, 7):
                time.sleep(0.05)  # arrivals land while earlier slots decode
        results = [f.result(timeout=120) for f in futures]
        engine.close()
        return prompts, results, engine

    @pytest.mark.slow
    def test_churn_parity_with_generate(self, model):
        """The acceptance criterion: staggered arrivals, mixed prompt
        AND output lengths — outputs token-identical to per-request
        generate().

        Slow tier: scripts/check_serving.py's churn phase asserts the
        same parity contract e2e, and the fast tests below keep the
        slot lifecycle pinned per-commit."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8, 16),
            num_slots=4, chunk_tokens=2,
        )
        prompts, results, engine = self._run_churn(params, config, serve)
        for prompt, budget, result in zip(prompts, self.CHURN_BUDGETS,
                                          results):
            want = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
            assert result.num_generated == int(want["num_generated"][0])
        stats = engine.stats()
        assert stats["completed"] == len(prompts)
        assert stats["chunks"] > 0
        assert 0 < stats["mean_slot_occupancy"] <= 1.0

    @pytest.mark.slow
    def test_one_chunk_compile_serves_the_whole_run(self, model):
        """Retrace guard (tests/helpers idiom, counted in the engine):
        the whole churn run — slot reuse, mixed budgets, staggered
        arrivals — retraces the chunk program exactly once, and each
        prompt bucket's insert program once.

        Slow tier (tier-1 wall-clock is at its budget): the identical
        one-chunk-compile + insert-count contract is asserted e2e by
        scripts/check_serving.py's churn phase on every CI pass, and
        the fast chunked-prefill and prefix tests
        (test_serving_prefix.py) pin ``chunk_traces == 1`` per commit."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8, 16),
            num_slots=4, chunk_tokens=2,
        )
        _, _, engine = self._run_churn(params, config, serve)
        assert engine.stats()["inserts"] == len(self.CHURN_LENS)
        assert engine.chunk_traces == 1
        assert engine._insert_traces <= len(serve.prompt_buckets)

    @pytest.mark.slow
    def test_insert_into_freed_slot_reuses_stale_cache_rows(self, model):
        """More requests than slots: every completion frees a slot that
        a LATER, differently-shaped request re-prefills; stale cache
        from the previous occupant must never leak into its tokens.

        Slow tier (PR 8 wall-clock move, continued for the sharded
        serving round): check_serving.py's churn phases push 12
        requests through 4 slots with per-request parity, so
        reuse-over-stale-cache stays pinned end to end every CI run."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8, 16),
            num_slots=2, chunk_tokens=2,
        )
        rng = np.random.default_rng(3)
        # Long prompts first (fill the cache rows deep), short after
        # (reuse the same rows shallow).
        lens = (16, 12, 3, 2, 5)
        prompts = [rng.integers(1, 255, n).astype(np.int32) for n in lens]
        with ServingEngine(params, config, serve) as engine:
            futures = [engine.submit(p) for p in prompts]
            results = [f.result(timeout=120) for f in futures]
            stats = engine.stats()
        for prompt, result in zip(prompts, results):
            want = _direct(params, config, prompt, 4)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        # 5 requests through 2 slots: slots were necessarily reused.
        assert stats["inserts"] == 5 > serve.num_slots

    def test_per_slot_budget_expires_mid_chunk(self, model):
        """A slot whose per-request max_new_tokens runs out mid-chunk
        deactivates there (the active mask), emits nothing further, and
        its neighbor decodes on unaffected."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=4,
        )
        short = np.asarray([5, 9, 17, 2], np.int32)
        long_ = np.asarray([3, 1, 4, 1, 5], np.int32)
        engine = ServingEngine(params, config, serve, start=False)
        # budget 2: tok0 at insert + 1 chunk emission — expires at chunk
        # step 1 of 4, mid-chunk by construction.
        short_future = engine.submit(short, max_new_tokens=2)
        long_future = engine.submit(long_, max_new_tokens=6)
        engine.start()
        short_result = short_future.result(timeout=120)
        long_result = long_future.result(timeout=120)
        engine.close()
        want_short = _direct(params, config, short, 2)
        want_long = _direct(params, config, long_, 6)
        np.testing.assert_array_equal(
            short_result.tokens, np.asarray(want_short["tokens"])[0]
        )
        np.testing.assert_array_equal(
            long_result.tokens, np.asarray(want_long["tokens"])[0]
        )
        assert short_result.num_generated == 2
        assert engine.stats()["expired"] >= 1

    def test_eos_retires_slot_early(self, model):
        """eos parity through the continuous path: the eos is emitted,
        the row pads after it, num_generated counts through the eos —
        and the slot frees early (no expiry counted)."""
        config, params = model
        prompt = np.asarray([7, 3, 11, 2], np.int32)
        greedy = np.asarray(_direct(params, config, prompt, 6)["tokens"])[0]
        eos, n_until_eos = _eos_after_first(greedy)
        sample = generation.SampleConfig(temperature=0.0, eos_id=eos,
                                         pad_id=0)
        serve = ServeConfig(
            max_new_tokens=6, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=3, sample=sample,
        )
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(prompt).result(timeout=120)
            stats = engine.stats()
        want = _direct(params, config, prompt, 6, sample=sample)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert result.num_generated == int(
            want["num_generated"][0]
        ) == n_until_eos
        assert stats["retires"] == 1
        assert stats["expired"] == 0  # eos retired it, not the budget cap

    def test_close_drains_partially_full_grid(self, model):
        """close() on a grid with free slots still serves every admitted
        request to completion before the scheduler exits."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=8, prompt_buckets=(8,), num_slots=4,
            chunk_tokens=2,
        )
        engine = ServingEngine(params, config, serve)
        futures = [
            engine.submit(np.asarray([1, 2, i], np.int32))
            for i in range(1, 3)  # 2 requests in a 4-slot grid
        ]
        engine.close()  # drain=True default
        for f in futures:
            assert f.result(timeout=5).num_generated == 8
        assert engine.stats()["completed"] == 2
        assert not _engine_threads()

    def test_close_without_drain_fails_in_flight(self, model):
        """close(drain=False) resolves in-flight slot requests promptly
        (with EngineClosedError, unless they won the race and finished)
        instead of serving the grid to completion."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=32, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=1,
        )
        engine = ServingEngine(params, config, serve)
        future = engine.submit(np.asarray([1, 2, 3], np.int32))
        engine.close(drain=False)
        assert future.done()
        try:
            result = future.result(timeout=5)
        except EngineClosedError:
            pass  # the expected path: aborted mid-decode
        else:  # raced to completion before close landed: still valid
            assert result.num_generated == 32
        assert not _engine_threads()

    def test_continuous_warmup_precompiles_grid(self, model):
        """warmup=True lands one insert executable per prompt bucket
        plus THE chunk executable in the AOT registry before traffic,
        and the warmed dispatch still matches the oracle with exactly
        one chunk trace."""
        from cloud_tpu.training import compile_cache

        config, params = model
        before = compile_cache.registry_size()
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8, 16), num_slots=2,
            chunk_tokens=2, warmup=True,
        )
        engine = ServingEngine(params, config, serve)
        engine.wait_ready()
        assert engine._warmup_plan.error is None
        # 2 insert programs + 1 chunk program = 3 new entries.
        assert compile_cache.registry_size() >= before + 3
        assert engine._chunk_step.compiled is not None
        for bucket in serve.prompt_buckets:
            assert engine._insert_cells[bucket].compiled is not None

        prompt = np.asarray([9, 4, 1], np.int32)
        result = engine.submit(prompt).result(timeout=120)
        engine.close()
        want = _direct(params, config, prompt, 3)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert engine.chunk_traces == 1

    def test_warmup_plan_is_the_slot_programs(self, model):
        """The warm-up plan of a plain slot engine is one insert program
        a prompt bucket plus THE chunk program, and nothing else: no
        (bucket, batch) cell exists to warm."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(8, 16), num_slots=2,
            chunk_tokens=2, warmup=True,
        )
        engine = ServingEngine(params, config, serve, start=False)
        try:
            engine.wait_ready()
            assert engine._warmup_plan.error is None
            assert sorted(engine._warmup_plan.steps) == [
                "serve/decode_chunk", "serve/insert_L16", "serve/insert_L8",
            ]
            assert all(
                step.compiled is not None
                for step in engine._warmup_plan.steps.values())
        finally:
            engine.close()

    def test_lone_waiting_request_is_shed_at_its_own_deadline(self, model):
        """With the only slot held by a long request, a queued request
        is shed within a pass of ITS deadline — typed, never served —
        not when the slot frees."""
        from cloud_tpu.serving import DeadlineExceededError

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2048, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=1,
        )
        prompt = np.asarray([1, 2, 3], np.int32)
        engine = ServingEngine(params, config, serve)
        try:
            # Warm both programs so the holder's passes are short.
            engine.submit(prompt, max_new_tokens=2).result(timeout=120)
            holder = engine.submit(prompt)
            while engine.health()["active_slots"] < 1:
                time.sleep(0.001)
            queued_at = time.perf_counter()
            queued = engine.submit(prompt, deadline_s=0.2)
            with pytest.raises(DeadlineExceededError):
                queued.result(timeout=120)
            shed_after = time.perf_counter() - queued_at
            # Shed while the holder still decodes: its slot never freed.
            assert not holder.done()
            assert 0.2 <= shed_after < 0.2 + 1.0
        finally:
            engine.close(drain=False)
        stats = engine.stats()
        assert stats["shed"] == 1
        assert stats["completed"] == 1  # the warm-up; the shed one never ran

    def test_continuous_spans_and_metrics(self, model):
        from cloud_tpu.monitoring import metrics, tracing

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                engine.submit(
                    np.asarray([1, 2, 3], np.int32)
                ).result(timeout=120)
        agg = collector.aggregates()
        for name in ("serve/queue_wait", "serve/prefill", "serve/chunk"):
            assert agg.get(name, {}).get("count", 0) >= 1, name
        chunk_events = [
            e for e in collector.events() if e["name"] == "serve/chunk"
        ]
        assert chunk_events
        args = chunk_events[0]["args"]
        assert args["slots"] == serve.num_slots
        assert 0 < args["occupancy"] <= 1.0
        snap = metrics.snapshot()
        assert snap["counters"].get("serve/slot_inserts", 0) >= 1
        assert snap["counters"].get("serve/slot_retires", 0) >= 1
        assert snap["counters"].get("serve/chunks", 0) >= 1
        assert "serve/slot_occupancy" in snap["gauges"]

    def test_continuous_report_breakdown(self, model):
        """The report CLI renders a continuous-batching grid-health line
        from the chunk spans' attributes."""
        from cloud_tpu.monitoring import tracing
        from cloud_tpu.monitoring.report import TraceReport

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2,
        )
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                engine.submit(
                    np.asarray([4, 5, 6], np.int32)
                ).result(timeout=120)
            report = TraceReport(collector.events())
        summary = report.continuous_summary()
        assert summary is not None
        assert summary["chunks"] >= 1
        assert 0 < summary["mean_occupancy"] <= 1.0
        rendered = report.render()
        assert "continuous batching:" in rendered
        assert "serve/chunk" in rendered


class TestShardedServing:
    """Tensor-parallel sharded serving (ISSUE 11): one replica = one
    multi-chip slice.  The whole slot-grid program family runs under a
    ``mesh_shape=(tp, sp)`` mesh — params sharded per the rules table,
    the slot KV cache by attention head, logits resharded only at the
    sampling boundary — and greedy outputs stay token-identical to
    single-chip ``generate()``.  ``mesh_shape`` unset or ``(1, 1)`` IS
    the single-chip path (same objects, no mesh, no new spans)."""

    def test_tp2_churn_parity_health_and_report(self, model):
        """The acceptance workload in one pass: mixed lengths and
        budgets through a TP=2 slice — token parity per request, slice
        shape in health/stats, ONE chunk executable despite the mesh,
        reshard spans at the sampling boundary, and the report's
        grid-health line naming the slice."""
        from cloud_tpu.monitoring import tracing
        from cloud_tpu.monitoring.report import TraceReport

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, mesh_shape=(2, 1),
        )
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(1, 255, int(rng.integers(2, 9))).astype(np.int32)
            for _ in range(4)
        ]
        budgets = [1, 4, 2, 3]
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                health = engine.health()
                futures = [
                    engine.submit(p, max_new_tokens=b)
                    for p, b in zip(prompts, budgets)
                ]
                results = [f.result(timeout=120) for f in futures]
                stats = engine.stats()
                traces = engine.chunk_traces
                placement = engine.placement()
            report = TraceReport(collector.events())
        for prompt, budget, result in zip(prompts, budgets, results):
            direct = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(direct["tokens"])[0]
            )
        assert health["slice_shape"] == (2, 1)
        assert health["slice_chips"] == 2
        assert stats["slice_chips"] == 2
        assert traces == 1, "the mesh must not multiply chunk compiles"
        # Params and slot KV on both chips, each KV leaf split over heads.
        assert len(placement["param_devices"]) == 2
        assert len(placement["kv_devices"]) == 2
        for whole, shard in zip(placement["kv_shapes"],
                                placement["kv_shard_shapes"]):
            assert shard[-2] * 2 == whole[-2] == config.num_heads
        reshards = [
            e for e in collector.events() if e.get("name") == "serve/reshard"
        ]
        assert reshards, "sharded engines span the sampling-boundary pull"
        assert all(
            (e.get("args") or {}).get("chips") == 2 for e in reshards
        )
        summary = report.continuous_summary()
        assert summary["slice"] == "2x1"
        assert summary["slice_chips"] == 2
        assert "slice 2x1 (2 chips)" in report.render()

    def test_tp2_kv_quant_parity(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=2, kv_quant=True, mesh_shape=(2, 1),
        )
        prompt = np.asarray([7, 3, 9, 11, 2], np.int32)
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(prompt).result(timeout=120)
        direct = generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([len(prompt)], np.int32), config,
            max_new_tokens=3,
            sample=generation.SampleConfig(temperature=0.0),
            kv_quant=True,
        )
        np.testing.assert_array_equal(
            result.tokens, np.asarray(direct["tokens"])[0]
        )

    def test_mesh_shape_must_divide_num_heads(self, model):
        config, params = model  # TINY: 4 heads
        with pytest.raises(ValueError, match="num_heads"):
            ServingEngine(
                params, config, ServeConfig(mesh_shape=(3, 1)),
                start=False,
            )

    def test_mesh_shape_needs_enough_devices(self, model):
        config, params = model
        with pytest.raises(ValueError, match="device"):
            ServingEngine(
                params, config, ServeConfig(mesh_shape=(4, 4)),
                start=False,
            )

    def test_mesh_shape_validation(self):
        with pytest.raises(ValueError, match="mesh_shape"):
            ServeConfig(mesh_shape=(0, 1))
        with pytest.raises(ValueError, match="layout"):
            ServeConfig(layout="magic")
        with pytest.raises(ValueError, match="hbm_bytes_per_chip"):
            ServeConfig(hbm_bytes_per_chip=0)

    def test_single_chip_default_is_untouched(self, model):
        """mesh_shape unset / (1, 1): no mesh is built, params are the
        caller's SAME object (no placement), and the slice keys report
        the single chip — the byte-identical compatibility default."""
        config, params = model
        for serve in (ServeConfig(), ServeConfig(mesh_shape=(1, 1))):
            engine = ServingEngine(params, config, serve, start=False)
            try:
                assert engine.mesh is None
                assert engine.params is params
                health = engine.health()
                assert health["slice_shape"] == (1, 1)
                assert health["slice_chips"] == 1
            finally:
                engine.close(drain=False)

    def test_caller_training_mesh_is_not_a_slice(self, model):
        """A caller-provided mesh with no tp/sp extent (a dp training
        mesh reaching the engine via mesh=/the global registry) is NOT
        a serving slice: slice keys read (1, 1)/1, params keep the
        caller's placement (never resharded by the engine), and no
        reshard spans can fire."""
        from cloud_tpu import parallel

        config, params = model
        mesh = parallel.MeshSpec({"dp": 2}).build(jax.devices()[:2])
        engine = ServingEngine(params, config, ServeConfig(),
                               mesh=mesh, start=False)
        try:
            health = engine.health()
            assert health["slice_shape"] == (1, 1)
            assert health["slice_chips"] == 1
            assert engine.params is params
        finally:
            engine.close(drain=False)

    def test_explicit_mesh_conflicts_with_mesh_shape(self, model):
        from cloud_tpu import parallel

        config, params = model
        mesh = parallel.MeshSpec({"tp": 2}).build(jax.devices()[:2])
        with pytest.raises(ValueError, match="not both"):
            ServingEngine(
                params, config, ServeConfig(mesh_shape=(2, 1)),
                mesh=mesh, start=False,
            )

    def test_auto_layout_with_roomy_budget_stays_single_chip(self, model):
        """layout="auto" + a budget one chip already satisfies: the
        planner picks tp=1 (narrowest fitting — spare chips belong to
        more replicas) and the engine takes the single-chip path."""
        config, params = model
        serve = ServeConfig(layout="auto", hbm_bytes_per_chip=1 << 40)
        engine = ServingEngine(params, config, serve, start=False)
        try:
            assert engine.mesh is None
            assert engine.health()["slice_chips"] == 1
        finally:
            engine.close(drain=False)

    @pytest.mark.slow
    def test_auto_layout_uses_whole_slice_with_parity(self, model):
        """Budget-less auto layout on the 8-device rig: TINY's 4 heads
        cap tp at 4; the engine builds the (4, 1) slice and serves
        token-identically."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
            chunk_tokens=2, layout="auto",
        )
        prompt = np.asarray([5, 4, 3, 2], np.int32)
        with ServingEngine(params, config, serve) as engine:
            assert engine.health()["slice_shape"] == (4, 1)
            result = engine.submit(prompt).result(timeout=120)
        direct = _direct(params, config, prompt, 3)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(direct["tokens"])[0]
        )


@pytest.fixture(scope="module")
def spec_model():
    """A 1-layer target (cheap compiles — spec tests build several
    engines) plus a fresh-init draft of the same shape: shared weights
    pin full acceptance, the fresh init pins the all-but-rejected
    path.  Both share the target's vocabulary by construction."""
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
    params = transformer.init(jax.random.PRNGKey(0), config)
    draft_params = transformer.init(jax.random.PRNGKey(7), config)
    return config, params, draft_params


class TestSpeculative:
    """Draft-and-verify speculative decoding (ISSUE 12): greedy outputs
    token-identical to per-request generate() across every serving
    composition axis — cold insert, kv_quant, prefix hits, chunked
    prefill, TP=2 slices — with the dispatch-count win (target verify
    dispatches strictly fewer than tokens emitted) provable on the
    shared-weights draft, and the degenerate knobs (spec_k=1,
    all-rejected proposals) pinned as pure overhead, never corruption."""

    def _direct(self, params, config, prompt, budget, **kw):
        return generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([len(prompt)], np.int32), config,
            max_new_tokens=budget,
            sample=generation.SampleConfig(temperature=0.0), **kw,
        )

    def test_shared_draft_churn_parity_dispatches_and_observability(
            self, spec_model):
        """The acceptance workload in one pass: mixed budgets through a
        shared-weights draft — token parity per request, strictly fewer
        verify dispatches than tokens emitted, full-window acceptance
        visible in the span attrs, serve/draft + serve/verify spans,
        the rolling-acceptance gauge and health keys, the report's
        speculative line, and the one-executable retrace guard (with
        the plain chunk program never dispatched)."""
        from cloud_tpu.monitoring import metrics, tracing
        from cloud_tpu.monitoring.report import TraceReport
        from cloud_tpu.serving import DraftConfig

        config, params, _ = spec_model
        serve = ServeConfig(
            max_new_tokens=7, prompt_buckets=(8,), num_slots=2,
            draft=DraftConfig(config=config, params=params, spec_k=3),
        )
        rng = np.random.default_rng(12)
        prompts = [rng.integers(1, 255, n).astype(np.int32)
                   for n in (3, 6, 5)]
        # Decode budgets (budget - 1 after tok0) in multiples of spec_k:
        # a shared-weights draft then commits FULL windows — acceptance
        # is exactly 1.0 and the per-dispatch accepted == proposed span
        # attribute is deterministic (a mid-window budget cut would
        # shave accepted below proposed without any real mismatch).
        budgets = [7, 7, 4]
        with tracing.collecting() as collector:
            with ServingEngine(params, config, serve) as engine:
                futures = [
                    engine.submit(p, max_new_tokens=b)
                    for p, b in zip(prompts, budgets)
                ]
                results = [f.result(timeout=120) for f in futures]
                stats = engine.stats()
                health = engine.health()
                draft_traces = engine._draft_traces
                verify_traces = engine.verify_traces
                chunk_traces = engine.chunk_traces
            report = TraceReport(collector.events())
        for prompt, budget, result in zip(prompts, budgets, results):
            want = self._direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
            assert result.num_generated == int(want["num_generated"][0])
        # The tentpole's win metric as a hard gate.
        assert stats["spec_chunks"] < stats["spec_emitted"], stats
        assert stats["spec_acceptance_rate"] > 0
        assert health["spec_acceptance_rate"] > 0
        assert health["spec_k"] == 3
        # Shared weights: some dispatch accepted its whole proposal set.
        verify_events = [
            e for e in collector.events() if e["name"] == "serve/verify"
        ]
        assert verify_events
        assert any(
            e["args"].get("proposed", 0) > 0
            and e["args"]["accepted"] == e["args"]["proposed"]
            for e in verify_events
        )
        assert any(
            e["name"] == "serve/draft" for e in collector.events()
        )
        snap = metrics.snapshot()
        assert "serve/spec_accept_rate" in snap["gauges"]
        assert snap["counters"].get("serve/spec_chunks", 0) >= 1
        spec = report.spec_summary()
        assert spec["verify_dispatches"] == stats["spec_chunks"]
        assert spec["acceptance_rate"] > 0
        assert "speculative decoding:" in report.render()
        # Retrace guard: one draft + one verify executable for the
        # whole run; the non-speculative chunk program never traced.
        assert draft_traces == 1 and verify_traces == 1
        assert chunk_traces == 0

    def test_mismatching_draft_and_spec_k1_parity(self, spec_model):
        """A fresh-init draft (acceptance ~0) and the spec_k=1 overhead
        knob: parity holds in both, every verify dispatch commits at
        least one token per active slot, and spec_k=1 commits EXACTLY
        one — the non-speculative schedule with a draft riding along."""
        from cloud_tpu.serving import DraftConfig

        config, params, draft_params = spec_model
        rng = np.random.default_rng(13)
        prompts = [rng.integers(1, 255, 4).astype(np.int32)]
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            draft=DraftConfig(
                config=config, params=draft_params, spec_k=3
            ),
        )
        with ServingEngine(params, config, serve) as engine:
            futures = [engine.submit(p) for p in prompts]
            results = [f.result(timeout=120) for f in futures]
            stats = engine.stats()
        for prompt, result in zip(prompts, results):
            want = self._direct(params, config, prompt, 4)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        assert stats["spec_emitted"] >= stats["spec_chunks"]

        k1 = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=1,
            draft=DraftConfig(
                config=config, params=draft_params, spec_k=1
            ),
        )
        with ServingEngine(params, config, k1) as engine:
            result = engine.submit(prompts[0]).result(timeout=120)
            stats = engine.stats()
        want = self._direct(params, config, prompts[0], 4)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert stats["spec_chunks"] == stats["spec_emitted"]
        assert stats["spec_proposed"] == 0
        assert stats["spec_acceptance_rate"] == 0.0

    def test_spec_kv_quant_parity(self, spec_model):
        from cloud_tpu.serving import DraftConfig

        config, params, _ = spec_model
        prompt = np.asarray([7, 3, 9, 11, 2], np.int32)
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
            kv_quant=True,
            draft=DraftConfig(config=config, params=params, spec_k=2),
        )
        with ServingEngine(params, config, serve) as engine:
            result = engine.submit(prompt).result(timeout=120)
        want = self._direct(params, config, prompt, 3, kv_quant=True)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )

    def test_spec_prefix_cache_and_chunked_prefill_parity(
            self, spec_model):
        """Speculation composes with the PR 9 prefill machinery: the
        second identical prompt hits the prefix cache (target-side),
        its suffix chunk-prefills, the draft re-prefills from the
        prompt — and both requests stay token-identical to generate()."""
        from cloud_tpu.serving import DraftConfig

        config, params, _ = spec_model
        rng = np.random.default_rng(14)
        prompt = rng.integers(1, 255, 7).astype(np.int32)
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=2,
            prefix_cache_blocks=8, prefix_block_tokens=2,
            prefill_chunk_tokens=4,
            draft=DraftConfig(config=config, params=params, spec_k=2),
        )
        with ServingEngine(params, config, serve) as engine:
            first = engine.submit(prompt).result(timeout=120)
            second = engine.submit(prompt).result(timeout=120)
            stats = engine.stats()
        want = np.asarray(self._direct(params, config, prompt, 3)["tokens"])[0]
        np.testing.assert_array_equal(first.tokens, want)
        np.testing.assert_array_equal(second.tokens, want)
        assert stats["prefix_hits"] >= 1
        assert stats["prefill_chunks"] >= 1
        assert stats["draft_prefills"] == 2

    def test_spec_tp2_parity(self, spec_model):
        """Speculation under a TP=2 slice: the target verifies sharded,
        the draft head-shards too (4 heads / tp=2), and greedy outputs
        stay token-identical to single-chip generate()."""
        from cloud_tpu.serving import DraftConfig

        config, params, draft_params = spec_model
        rng = np.random.default_rng(15)
        prompts = [rng.integers(1, 255, n).astype(np.int32)
                   for n in (3, 6)]
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            mesh_shape=(2, 1),
            draft=DraftConfig(
                config=config, params=draft_params, spec_k=3
            ),
        )
        with ServingEngine(params, config, serve) as engine:
            assert engine._draft_sharded
            futures = [
                engine.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, [4, 2])
            ]
            results = [f.result(timeout=120) for f in futures]
            health = engine.health()
            verify_traces = engine.verify_traces
        for prompt, budget, result in zip(prompts, [4, 2], results):
            want = self._direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        assert health["slice_chips"] == 2
        assert verify_traces == 1, "the mesh must not multiply compiles"

    @pytest.mark.slow
    def test_spec_tp2_replicated_draft_parity(self, spec_model):
        """The replicated-draft fallback: a draft whose head count tp
        does NOT divide (3 heads on tp=2) rides the slice replicated —
        params and its slot cache device_put to every chip, programs
        built mesh-free — and parity still holds.  Slow tier: the
        head-sharded TP branch stays pinned fast above; this pins the
        other arm of _init_draft per CI run."""
        from cloud_tpu.serving import DraftConfig

        config, params, _ = spec_model
        dcfg = config.scaled(num_heads=3, head_dim=16, dim=48,
                             mlp_hidden=96)
        dparams = transformer.init(jax.random.PRNGKey(9), dcfg)
        prompt = np.asarray([5, 9, 17, 2], np.int32)
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
            mesh_shape=(2, 1),
            draft=DraftConfig(config=dcfg, params=dparams, spec_k=2),
        )
        with ServingEngine(params, config, serve) as engine:
            assert not engine._draft_sharded
            result = engine.submit(prompt).result(timeout=120)
        want = self._direct(params, config, prompt, 3)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )

    def test_spec_config_validation(self, spec_model):
        from cloud_tpu.serving import DraftConfig

        config, params, draft_params = spec_model
        with pytest.raises(ValueError, match="spec_k"):
            DraftConfig(config=config, params=params, spec_k=0)
        with pytest.raises(ValueError, match="params"):
            DraftConfig(config=config)  # forgotten weights fail HERE
        draft = DraftConfig(config=config, params=draft_params)
        with pytest.raises(ValueError, match="greedy"):
            ServeConfig(
                draft=draft,
                sample=generation.SampleConfig(temperature=0.7),
            )
        with pytest.raises(ValueError, match="repetition_penalty"):
            ServeConfig(
                draft=draft,
                sample=generation.SampleConfig(
                    temperature=0.0, repetition_penalty=1.3
                ),
            )
        bad_cfg = config.scaled(vocab_size=128)
        bad_params = transformer.init(jax.random.PRNGKey(1), bad_cfg)
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(
                params, config,
                ServeConfig(draft=DraftConfig(
                    config=bad_cfg, params=bad_params
                )),
                start=False,
            )


@pytest.mark.slow
def test_check_serving_script():
    """The CI serving harness end to end: N concurrent mixed-length
    requests, parity vs unbatched generate, zero leaked threads."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "check_serving.py")],
        capture_output=True, text=True, timeout=500,
        cwd=REPO_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, (proc.stdout or "") + (proc.stderr or "")
    import json

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
    assert summary["completed"] == summary["requests"]
    assert summary["leaked_threads"] == []
