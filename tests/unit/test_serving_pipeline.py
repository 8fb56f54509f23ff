"""Pipelined continuous batching (ISSUE 20): ``pipeline_depth=2``.

The load-bearing contract: with a second chunk dispatch kept in flight
while the host schedules, greedy outputs are TOKEN-IDENTICAL to
``pipeline_depth=1`` and to per-request ``generation.generate`` — under
slot churn (staggered arrivals, per-request budgets, eos mid-chunk) and
composed with every serving feature that touches the decode hot path:
prefix hits, chunked prefill, speculation, kv_quant, and the paged
decode kernel's block table.  Around that: the one-pass-stale mutation
rule's observable corollaries (a speculatively dispatched chunk for a
just-finished slot emits only masked rows; deferred prefix save-backs
are counted), the dispatch-gap stats surface, the retrace guard (depth
2 adds no recompiles), the ``CLOUD_TPU_PIPELINE=0`` kill switch, the
depth-1 no-new-spans pin, and the close()/drain contract extended to an
in-flight pipelined dispatch — no abandoned device→host copy, no leaked
scheduler thread.
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import generation, transformer
from cloud_tpu.serving import (
    EngineClosedError,
    ServeConfig,
    ServingEngine,
)

#: Same leak-guard family as test_serving: a closed engine owns zero
#: live threads, in-flight pipelined dispatch or not.
ENGINE_THREAD_PREFIXES = ("cloud-tpu-serve", "cloud-tpu-compile-ahead")

#: The churn workload: mixed prompt lengths and mixed decode budgets —
#: slots retire and re-arm mid-run, so a depth-2 ring always holds a
#: chunk dispatched against a slot set that mutates under it.
CHURN_LENS = (3, 8, 12, 5, 7, 2, 6, 4)
CHURN_BUDGETS = (5, 2, 4, 1, 6, 3, 5, 2)


def _engine_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith(ENGINE_THREAD_PREFIXES)
    ]


@pytest.fixture(scope="module")
def model():
    """1-layer TINY: this module builds an engine PAIR (depth 1 + 2)
    per test, so compiles are the budget — parity holds at any depth."""
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _direct(params, config, prompt, max_new_tokens, **kw):
    return generation.generate(
        params, jnp.asarray(prompt[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=max_new_tokens,
        sample=kw.pop("sample", generation.SampleConfig(temperature=0.0)),
        **kw,
    )


def _churn_prompts(lens=CHURN_LENS, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 255, n).astype(np.int32) for n in lens]


def _run(params, config, serve, prompts, budgets, stagger=()):
    """Submit the workload (staggering arrivals mid-decode at the given
    indices), resolve everything, close, return (results, engine)."""
    engine = ServingEngine(params, config, serve)
    futures = []
    for i, prompt in enumerate(prompts):
        futures.append(engine.submit(prompt, max_new_tokens=budgets[i]))
        if i in stagger:
            time.sleep(0.05)  # arrivals land while earlier slots decode
    results = [f.result(timeout=240) for f in futures]
    engine.close()
    return results, engine


def _both_depths(params, config, prompts, budgets, stagger=(), **cfg_kw):
    """The module's core harness: the same workload through a depth-1
    and a depth-2 engine; returns both (results, engine) pairs."""
    base = dict(
        max_new_tokens=6, prompt_buckets=(8, 16), num_slots=4,
        chunk_tokens=2, warmup=False,
    )
    base.update(cfg_kw)
    r1, e1 = _run(params, config, ServeConfig(pipeline_depth=1, **base),
                  prompts, budgets, stagger)
    r2, e2 = _run(params, config, ServeConfig(pipeline_depth=2, **base),
                  prompts, budgets, stagger)
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.num_generated == b.num_generated
    return (r1, e1), (r2, e2)


class TestValidation:
    def test_depth_must_be_1_or_2(self):
        with pytest.raises(ValueError, match="pipeline_depth"):
            ServeConfig(pipeline_depth=3)
        with pytest.raises(ValueError, match="pipeline_depth"):
            ServeConfig(pipeline_depth=0)
        assert ServeConfig(pipeline_depth=2).pipeline_depth == 2


class TestParity:
    def test_churn_parity_and_gap_stats(self, model):
        """The acceptance workload: staggered arrivals, mixed budgets —
        depth 2 token-identical to depth 1 and to per-request
        generate(), with the dispatch-gap surface populated on both
        arms and the retrace guard holding (ONE chunk compile at any
        depth)."""
        config, params = model
        prompts = _churn_prompts()
        (r1, e1), (r2, e2) = _both_depths(
            params, config, prompts, CHURN_BUDGETS, stagger=(3, 6),
        )
        for prompt, budget, result in zip(prompts, CHURN_BUDGETS, r2):
            want = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
            assert result.num_generated == int(want["num_generated"][0])
        # Depth 2 added no recompiles: one chunk executable each.
        assert e1.chunk_traces == 1
        assert e2.chunk_traces == 1
        for engine, depth in ((e1, 1), (e2, 2)):
            stats = engine.stats()
            health = engine.health()
            assert health["pipeline_depth"] == depth
            assert stats["pipeline_depth"] == depth
            # The gap window saw real dispatches on both arms (the
            # probe's per-arm p50/p99 comparison depends on this).
            assert stats["dispatch_gap_ms_p50"] > 0.0
            assert stats["dispatch_gap_ms_p99"] >= (
                stats["dispatch_gap_ms_p50"]
            )
            assert health["dispatch_gap_ms"] > 0.0
            assert stats["completed"] == len(prompts)
        # Depth 2 committed exactly what depth 1 did — occupancy math
        # unchanged by the ring.
        assert (e2.stats()["useful_decode_tokens"]
                == e1.stats()["useful_decode_tokens"])

    def test_eos_mid_chunk_parity(self, model):
        """eos landing mid-chunk retires the slot one drain late at
        depth 2 — the speculatively dispatched chunk for it must emit
        only masked rows, so tokens match depth 1 exactly."""
        config, params = model
        prompt = np.asarray([7, 3, 11, 2], np.int32)
        greedy = np.asarray(
            _direct(params, config, prompt, 6)["tokens"]
        )[0]
        eos = int(greedy[1])
        sample = generation.SampleConfig(
            temperature=0.0, eos_id=eos, pad_id=0
        )
        prompts = [prompt] + _churn_prompts(lens=(5, 9, 4), seed=5)
        budgets = (6, 6, 3, 5)
        (r1, _), (r2, _) = _both_depths(
            params, config, prompts, budgets, sample=sample,
        )
        # The eos request stopped early AND identically on both arms.
        assert r2[0].num_generated == 2
        np.testing.assert_array_equal(r1[0].tokens, r2[0].tokens)

    def test_prefix_hit_and_chunked_prefill_parity(self, model):
        """Prefix cache + chunked prefill under the ring: parity holds,
        the second request still HITS the first's saved blocks, and the
        deferred save-back ordering path demonstrably ran at depth 2
        (and never at depth 1)."""
        config, params = model
        head = np.asarray([7, 1, 4, 2, 9, 3, 5, 8], np.int32)
        seed = np.concatenate([head, [11]]).astype(np.int32)
        hit = np.concatenate([head, [13, 12]]).astype(np.int32)
        filler = _churn_prompts(lens=(6,), seed=9)[0]

        def run(depth):
            serve = ServeConfig(
                max_new_tokens=256, prompt_buckets=(16,),
                num_slots=4, chunk_tokens=2, warmup=False,
                prefix_cache_blocks=8, prefix_block_tokens=4,
                prefill_chunk_tokens=4, pipeline_depth=depth,
            )
            engine = ServingEngine(params, config, serve)
            outs = [
                # Seed the trie: the first shared-head request runs
                # alone, so its save-back is in place before the hit.
                engine.submit(seed, max_new_tokens=4).result(timeout=240)
            ]
            # A long filler keeps decode chunks in flight while the
            # HIT request arrives, so its save-back (and the hit's
            # copy-in) land behind a live ring at depth 2.
            filler_future = engine.submit(filler, max_new_tokens=256)
            time.sleep(0.01)
            outs.append(
                engine.submit(hit, max_new_tokens=4).result(timeout=240)
            )
            outs.append(filler_future.result(timeout=240))
            stats = engine.stats()
            engine.close()
            return outs, stats

        out1, stats1 = run(1)
        out2, stats2 = run(2)
        for a, b in zip(out1, out2):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        for prompt, budget, result in zip(
                (seed, hit, filler), (4, 4, 256), out2):
            want = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        for stats in (stats1, stats2):
            assert stats["prefix_hits"] >= 1
        assert stats1["prefix_deferred_saves"] == 0
        # Depth 2: the hit request's save-back landed while the
        # filler's chunk was in flight — the deferred ordering path
        # demonstrably ran.
        assert stats2["prefix_deferred_saves"] >= 1

    def test_kv_quant_parity(self, model):
        """int8 KV under the ring: the oracle is QUANTIZED generate —
        the pre-existing engine contract, unchanged by pipelining."""
        config, params = model
        prompts = _churn_prompts(lens=(3, 8, 5), seed=3)
        budgets = (4, 3, 5)
        (_, _), (r2, _) = _both_depths(
            params, config, prompts, budgets, kv_quant=True,
        )
        for prompt, budget, result in zip(prompts, budgets, r2):
            want = _direct(params, config, prompt, budget, kv_quant=True)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )

    def test_speculation_parity(self, model):
        """Draft-and-verify through the ring: the verify emissions ride
        the same in-flight records as decode chunks — parity holds and
        the spec path actually ran on both arms."""
        from cloud_tpu.serving import DraftConfig

        config, params = model
        prompts = _churn_prompts(lens=(3, 6, 5), seed=12)
        budgets = (6, 4, 6)
        (_, e1), (r2, e2) = _both_depths(
            params, config, prompts, budgets,
            draft=DraftConfig(config=config, params=params, spec_k=2),
        )
        for prompt, budget, result in zip(prompts, budgets, r2):
            want = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        assert e1.stats()["spec_chunks"] > 0
        assert e2.stats()["spec_chunks"] > 0
        assert e2.verify_traces == 1  # no verify recompiles either

    def test_paged_kernel_parity(self, model):
        """The paged decode-attention block table composes with the
        ring (the in-flight chunk reads pool/slot KV in place; inserts
        for freed slots land behind it via dataflow)."""
        config, params = model
        prompts = _churn_prompts(lens=(3, 5, 8), seed=4)
        budgets = (4, 5, 3)
        (_, _), (r2, e2) = _both_depths(
            params, config, prompts, budgets, decode_kernel="pallas",
        )
        for prompt, budget, result in zip(prompts, budgets, r2):
            want = _direct(params, config, prompt, budget)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        assert e2.health()["decode_kernel"] == "pallas"


class TestLifecycle:
    def test_kill_switch_forces_depth1(self, model, monkeypatch):
        """CLOUD_TPU_PIPELINE=0 downgrades a depth-2 config to the
        synchronous loop at build time (the config object itself is
        untouched — restarts re-read the env)."""
        config, params = model
        monkeypatch.setenv("CLOUD_TPU_PIPELINE", "0")
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, warmup=False, pipeline_depth=2,
        )
        with ServingEngine(params, config, serve) as engine:
            assert engine.health()["pipeline_depth"] == 1
            prompt = np.asarray([5, 3, 1], np.int32)
            result = engine.submit(prompt).result(timeout=240)
        want = _direct(params, config, prompt, 4)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert serve.pipeline_depth == 2  # config untouched

    def test_depth1_emits_no_pipeline_spans(self, model):
        """The byte-identity pin's observable half: a depth-1 run under
        an active collector records NO serve/host_bubble or
        serve/dispatch_gap spans; a depth-2 run records both."""
        from cloud_tpu.monitoring import tracing

        config, params = model
        prompts = _churn_prompts(lens=(3, 6), seed=8)
        budgets = (5, 4)
        names = {}
        for depth in (1, 2):
            serve = ServeConfig(
                max_new_tokens=6, prompt_buckets=(8,),
                num_slots=2, chunk_tokens=2, warmup=False,
                pipeline_depth=depth,
            )
            with tracing.collecting() as collector:
                _run(params, config, serve, prompts, budgets)
            names[depth] = {e["name"] for e in collector.events()}
        assert "serve/host_bubble" not in names[1]
        assert "serve/dispatch_gap" not in names[1]
        assert "serve/host_bubble" in names[2]
        assert "serve/dispatch_gap" in names[2]
        assert "serve/chunk" in names[2]  # drain re-records the chunk span

    def test_graceful_close_drains_inflight_ring(self, model):
        """close(drain=True) with work still decoding: the trailing
        in-flight chunk is drained, every future completes with full
        tokens, and no engine thread survives."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=8, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, warmup=False, pipeline_depth=2,
        )
        prompts = _churn_prompts(lens=(3, 5, 7), seed=6)
        engine = ServingEngine(params, config, serve)
        futures = [engine.submit(p) for p in prompts]
        engine.close()  # drain=True while chunks are still in flight
        for prompt, future in zip(prompts, futures):
            result = future.result(timeout=240)
            want = _direct(params, config, prompt, 8)
            np.testing.assert_array_equal(
                result.tokens, np.asarray(want["tokens"])[0]
            )
        assert not _engine_threads()

    def test_abort_close_with_inflight_dispatch(self, model):
        """close(drain=False) mid-decode at depth 2: the in-flight ring
        is disposed (the pending device→host copy is completed, never
        abandoned), live requests fail typed, and the scheduler thread
        is gone — the extended thread-hygiene contract."""
        config, params = model
        serve = ServeConfig(
            max_new_tokens=64, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, warmup=False, pipeline_depth=2,
        )
        engine = ServingEngine(params, config, serve)
        futures = [
            engine.submit(p)
            for p in _churn_prompts(lens=(3, 5, 7), seed=7)
        ]
        # Let decode actually start so the ring is (very likely)
        # non-empty at the abort; correctness must not depend on it.
        time.sleep(0.2)
        engine.close(drain=False)
        for future in futures:
            with pytest.raises(EngineClosedError):
                future.result(timeout=60)
        assert not _engine_threads()
        assert not engine._inflight  # ring disposed, not abandoned

    def test_scheduler_crash_disposes_ring(self, model):
        """A dispatch fault at depth 2 takes the engine down the usual
        way — queued/live requests fail, the ring is disposed, health
        reports unhealthy, no thread leak."""
        from cloud_tpu.utils import faults

        config, params = model
        serve = ServeConfig(
            max_new_tokens=32, prompt_buckets=(8,), num_slots=2,
            chunk_tokens=2, warmup=False, pipeline_depth=2,
        )
        engine = ServingEngine(params, config, serve)
        plan = [{"site": "serve.chunk", "mode": "raise", "nth": 2}]
        try:
            with faults.inject(plan, propagate=False) as active:
                future = engine.submit(np.asarray([5, 3, 1], np.int32))
                with pytest.raises(faults.FaultInjected):
                    future.result(timeout=240)
                assert active.fired()
            deadline = time.monotonic() + 30
            while _engine_threads() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not _engine_threads()
            assert not engine._inflight
            assert engine.health()["healthy"] is False
        finally:
            engine.close(drain=False)
