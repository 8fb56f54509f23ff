"""Disaggregated prefill/decode fleet tests (ISSUE 19).

The load-bearing contracts:

* **Roles route legs.**  In a fleet with any non-``"both"`` role, a new
  request lands only on a prefill-capable replica; a handoff-carrying
  decode leg lands only on a decode-capable one.  A ``"both"`` replica
  picked in a disaggregated fleet serves colocated — one leg, no
  handoff — and a fleet with roles unset NEVER builds a handoff leg
  (byte-identical pin: plain engines whose ``submit`` lacks the kwargs
  keep working, the schema keys read zero).
* **The handoff pipeline.**  A prefill-ONLY replica serves exactly the
  first token with ``handoff_export=True``; its exported payload is
  stashed into the shared :class:`HostPrefixPool` (bytes deduplicated
  per host by full-chain keys) and the request re-enters the queue as a
  decode leg carrying the rehydrated payload.  A prefill leg that
  exports nothing still flips to a (cold) decode leg.
* **Failure semantics.**  A dead decode leg resets the payload and
  re-prefills at a prefill replica under the ordinary failover budget
  (``handoff_failovers`` counts it); the frozen trace context rides.
* **Token identity.**  A real-engine export/import round trip decodes
  token-identical to colocated ``generate()`` — cold, prefix-hit,
  chunked, speculative, and kv_quant (the fast cold case runs per
  commit; the full matrix and the live disagg fleet are slow-tier,
  with scripts/check_fleet.py's chaos arm asserting the same parity
  under a mid-flood prefill-replica kill).

Satellite pins ride along: the engineless-replica health stub carries
``role`` + zero handoff counters, and the pure-unit helpers
(chain keys, pool LRU/dedup, stash/rehydrate) are pinned directly.
"""

import threading
import time

import numpy as np
import pytest

from cloud_tpu.fleet import (
    Fleet,
    FleetConfig,
    LeastLoadedRouter,
    Replica,
    disagg,
)
from cloud_tpu.serving import ServeConfig, ServeResult, ServingEngine
from tests.unit.test_fleet import (  # the duck-typed fleet rig
    FakeEngine,
    _Factory,
    _fleet_threads,
    _quiet_config,
)

BLOCK_TOKENS = 4


def _payload(num_blocks, block_tokens=BLOCK_TOKENS, base=0):
    """A well-formed export payload: distinct keys, numpy bytes."""
    return {
        "version": 1,
        "block_tokens": block_tokens,
        "covered_tokens": num_blocks * block_tokens,
        "keys": [
            tuple(range(base + i * block_tokens,
                        base + (i + 1) * block_tokens))
            for i in range(num_blocks)
        ],
        "payloads": [
            np.full((3,), base + i, np.float32) for i in range(num_blocks)
        ],
    }


class HandoffFakeEngine(FakeEngine):
    """A FakeEngine whose ``submit`` takes the disagg kwargs.

    A prefill leg (``handoff_export=True``) resolves to a real
    :class:`ServeResult` carrying ``export_payload`` (None models an
    engine that cached nothing); everything else resolves to the usual
    routing dict, with the received ``handoff`` payload recorded so
    tests can assert what the decode leg actually saw.
    """

    def __init__(self, name, *, export_payload=None, **kw):
        super().__init__(name, **kw)
        self.export_payload = export_payload
        self.role_set = None

    def set_role(self, role):
        self.role_set = role

    def submit(self, prompt, *, max_new_tokens=None, deadline_s=None,
               handoff_export=False, handoff=None, **extra):
        from concurrent.futures import Future
        from cloud_tpu.serving import EngineClosedError, QueueFullError

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
                "handoff_export": handoff_export,
                "handoff": handoff,
            })
            future = Future()
            if handoff_export:
                result = ServeResult(
                    tokens=np.asarray([7], np.int32), num_generated=1,
                    bucket_len=8, batch_size=1, latency_seconds=0.001,
                    ttft_seconds=0.001, handoff=self.export_payload,
                )
            else:
                result = {"served_by": self.name, "handoff": handoff}
            if self.auto:
                future.set_result(result)
            else:
                self.pending.append(future)
            return future


def _wait(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


class TestDisaggHelpers:
    def test_role_validation(self):
        for role in disagg.ROLES:
            assert disagg.validate_role(role) == role
        with pytest.raises(ValueError, match="role"):
            disagg.validate_role("gpu")
        assert disagg.serves_prefill("prefill")
        assert disagg.serves_prefill("both")
        assert not disagg.serves_prefill("decode")
        assert disagg.serves_decode("decode")
        assert disagg.serves_decode("both")
        assert not disagg.serves_decode("prefill")

    def test_roles_validation_requires_both_capabilities(self):
        disagg.validate_roles(("prefill", "decode"))
        disagg.validate_roles(("both", "both"))  # colocated stays fine
        with pytest.raises(ValueError, match="decode-capable"):
            disagg.validate_roles(("prefill", "prefill"))
        with pytest.raises(ValueError, match="prefill-capable"):
            disagg.validate_roles(("decode", "decode"))
        with pytest.raises(ValueError, match="role"):
            disagg.validate_roles(("prefill", "tpu"))

    def test_chain_keys_fold_the_full_prefix(self):
        # The SAME block tokens at a different depth must key
        # differently: chain keys fold in everything above them.
        a = disagg.chain_keys([(1, 2), (3, 4)])
        b = disagg.chain_keys([(9, 9), (3, 4)])
        assert len(a) == len(b) == 2
        assert a[1] != b[1]
        # Deterministic per process, and prefix-stable: a longer chain
        # extends, never rewrites, the shared head.
        c = disagg.chain_keys([(1, 2), (3, 4), (5, 6)])
        assert c[:2] == a

    def test_payload_blocks(self):
        assert disagg.payload_blocks(None) == 0
        assert disagg.payload_blocks({}) == 0
        assert disagg.payload_blocks(_payload(3)) == 3

    def test_host_pool_dedup_and_lru_eviction(self):
        pool = disagg.HostPrefixPool(capacity_blocks=2)
        assert pool.put(1, "a") is False
        assert pool.put(1, "a2") is True  # dedup: stored bytes kept
        assert pool.get(1) == "a"
        assert pool.put(2, "b") is False
        pool.get(1)  # bump 1 so 2 is the LRU victim
        assert pool.put(3, "c") is False
        assert len(pool) == 2
        assert pool.get(2) is None  # evicted
        stats = pool.stats()
        assert stats["puts"] == 3
        assert stats["dedup_hits"] == 1
        assert stats["evictions"] == 1
        assert stats["misses"] == 1
        assert stats["blocks"] == 2
        with pytest.raises(ValueError, match="capacity_blocks"):
            disagg.HostPrefixPool(capacity_blocks=0)

    def test_stash_rehydrate_round_trip(self):
        pool = disagg.HostPrefixPool()
        payload = _payload(3)
        slim = disagg.stash(pool, payload)
        assert slim["payloads"] == [None, None, None]
        assert len(slim["chain"]) == 3
        assert len(pool) == 3
        fat = disagg.rehydrate(pool, slim)
        assert fat["keys"] == payload["keys"]
        assert fat["covered_tokens"] == payload["covered_tokens"]
        for got, want in zip(fat["payloads"], payload["payloads"]):
            np.testing.assert_array_equal(got, want)

    def test_rehydrate_truncates_at_first_pool_gap(self):
        # An entry evicted between the legs truncates the import there
        # — the decode replica prefills the rest, never an error.
        pool = disagg.HostPrefixPool(capacity_blocks=1)
        slim = disagg.stash(pool, _payload(3))  # only the last survives
        fat = disagg.rehydrate(pool, slim)
        assert disagg.payload_blocks(fat) == 0  # gap at block 0
        assert fat["covered_tokens"] == 0

    def test_poolless_passthrough(self):
        # No pool (engine-level handoff, or a colocated fleet): bytes
        # ride inline and stash/rehydrate are identity.
        payload = _payload(2)
        assert disagg.stash(None, payload) is payload
        assert disagg.rehydrate(None, payload) is payload
        assert disagg.stash(disagg.HostPrefixPool(), None) is None


class TestRouterRoleFilter:
    def _replicas(self, roles):
        return [
            Replica(i, lambda i=i: FakeEngine(f"e{i}"), role=role)
            for i, role in enumerate(roles)
        ]

    def test_pick_filters_by_leg(self):
        router = LeastLoadedRouter()
        replicas = self._replicas(("prefill", "decode", "both"))
        picked, _ = router.pick(replicas, role="prefill")
        assert picked.id in (0, 2)
        picked, _ = router.pick(replicas, role="decode")
        assert picked.id in (1, 2)
        # decode-only pool for a prefill leg: nothing routable.
        picked, _ = router.pick(replicas[1:2], role="prefill")
        assert picked is None

    def test_role_none_is_the_default_and_filters_nothing(self):
        router = LeastLoadedRouter()
        replicas = self._replicas(("prefill",))
        picked, _ = router.pick(replicas)
        assert picked.id == 0


class TestReplicaRole:
    def test_engineless_stub_carries_role_and_handoff_zeros(self):
        # Satellite: the health stub is schema — an engineless replica
        # still advertises its assigned role next to zero counters.
        replica = Replica(3, lambda: FakeEngine("x"), start=False,
                          role="decode")
        health = replica.health()
        assert health["ready"] is False
        assert health["role"] == "decode"
        for key in ("handoff_exports", "handoff_export_blocks",
                    "handoff_imports", "handoff_import_blocks"):
            assert health[key] == 0, key

    def test_default_role_is_both_and_invalid_rejected(self):
        replica = Replica(0, lambda: FakeEngine("x"), start=False)
        assert replica.role == "both"
        assert replica.health()["role"] == "both"
        with pytest.raises(ValueError, match="role"):
            Replica(1, lambda: FakeEngine("y"), start=False, role="gpu")

    def test_role_stamped_onto_engine_and_fake_health(self):
        # The replica restamps its role onto every engine incarnation
        # (set_role when present) and onto role-less health snaps.
        engine = HandoffFakeEngine("e0")
        replica = Replica(0, lambda: engine, role="prefill")
        assert engine.role_set == "prefill"
        assert replica.accepts_handoff
        assert replica.health()["role"] == "prefill"

    def test_role_aware_factory_receives_the_role_every_build(self):
        # A factory declaring a ``role`` parameter (signature-probed,
        # like the router-pick probes) gets the replica's role on the
        # first build AND on every rebuild — role-tuned engine configs
        # survive restarts.
        seen = []

        def factory(role="both"):
            seen.append(role)
            return FakeEngine(f"e{len(seen)}")

        replica = Replica(0, factory, role="decode")
        assert seen == ["decode"]
        replica.restart()
        assert seen == ["decode", "decode"]

    def test_zero_arg_factory_is_untouched(self):
        # The colocated contract: factories without a ``role``
        # parameter are called exactly as before.
        calls = []

        def factory():
            calls.append(True)
            return FakeEngine("e")

        replica = Replica(0, factory, role="prefill")
        assert calls == [True]
        assert replica.role == "prefill"


class TestFleetDisagg:
    def test_two_leg_handoff_through_the_host_pool(self):
        payload = _payload(2)
        pre = HandoffFakeEngine("pre", export_payload=payload)
        dec = HandoffFakeEngine("dec")
        fleet = Fleet(_Factory([pre, dec]), _quiet_config(
            min_replicas=2, roles=("prefill", "decode"),
        ))
        try:
            result = fleet.submit(
                np.asarray([1, 2, 3], np.int32), max_new_tokens=5,
            ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        # Prefill leg: the prefill-ONLY replica served exactly one
        # token with the export armed.
        assert len(pre.submits) == 1
        assert pre.submits[0]["handoff_export"] is True
        assert pre.submits[0]["max_new_tokens"] == 1
        # Decode leg: full budget, payload rehydrated byte-for-byte
        # from the host pool.
        assert len(dec.submits) == 1
        got = dec.submits[0]["handoff"]
        assert dec.submits[0]["handoff_export"] is False
        assert dec.submits[0]["max_new_tokens"] == 5
        assert got["keys"] == payload["keys"]
        for have, want in zip(got["payloads"], payload["payloads"]):
            np.testing.assert_array_equal(have, want)
        assert result["served_by"] == "dec"
        assert stats["handoffs"] == 1
        assert stats["handoff_failovers"] == 0
        assert stats["completed"] == 1
        assert stats["host_pool"]["puts"] == 2
        assert pre.role_set == "prefill" and dec.role_set == "decode"
        assert not _fleet_threads()

    def test_host_pool_dedups_repeat_prefixes(self):
        # The flash crowd's shared system prompt: a second handoff of
        # the same chain ships references, not bytes.
        payload = _payload(2)
        pre = HandoffFakeEngine("pre", export_payload=payload)
        dec = HandoffFakeEngine("dec")
        fleet = Fleet(_Factory([pre, dec]), _quiet_config(
            min_replicas=2, roles=("prefill", "decode"),
        ))
        try:
            for _ in range(2):
                fleet.submit(
                    np.asarray([1, 2, 3], np.int32), max_new_tokens=5,
                ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert stats["handoffs"] == 2
        assert stats["host_pool"]["puts"] == 2
        assert stats["host_pool"]["dedup_hits"] == 2
        assert stats["host_pool"]["blocks"] == 2

    def test_both_replica_serves_colocated_in_a_disagg_fleet(self):
        # A "both" replica is prefill-capable, so the router may pick
        # it for a new request — but it serves the request in ONE leg,
        # colocated, no handoff (double-serving a request that a
        # colocated engine can finish would only add latency).
        both = HandoffFakeEngine("both")
        dec = HandoffFakeEngine("dec")
        fleet = Fleet(_Factory([both, dec]), _quiet_config(
            min_replicas=2, roles=("both", "decode"),
        ))
        try:
            result = fleet.submit(
                np.asarray([4, 5], np.int32), max_new_tokens=3,
            ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert result["served_by"] == "both"
        assert len(both.submits) == 1
        assert both.submits[0]["handoff_export"] is False
        assert both.submits[0]["handoff"] is None
        assert both.submits[0]["max_new_tokens"] == 3
        assert dec.submits == []
        assert stats["handoffs"] == 0

    def test_empty_export_still_flips_to_a_cold_decode_leg(self):
        # A prefill engine that cached nothing (pool pressure, races)
        # exports None; the fleet still runs the decode leg — cold.
        pre = HandoffFakeEngine("pre", export_payload=None)
        dec = HandoffFakeEngine("dec")
        fleet = Fleet(_Factory([pre, dec]), _quiet_config(
            min_replicas=2, roles=("prefill", "decode"),
        ))
        try:
            result = fleet.submit(
                np.asarray([1], np.int32), max_new_tokens=4,
            ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert result["served_by"] == "dec"
        got = dec.submits[0]["handoff"]
        assert got is not None and got["keys"] == []
        assert stats["handoffs"] == 1

    def test_dead_decode_leg_resets_handoff_and_reprefills(self):
        # ISSUE 19 failure semantics: the seeded blocks died with the
        # decode replica, so the payload is void — the retry is a
        # FRESH prefill at a prefill replica, counted as a
        # handoff_failover, and the caller still gets a result.
        from cloud_tpu.serving import EngineClosedError

        payload = _payload(1)
        pre = HandoffFakeEngine("pre", export_payload=payload)
        dec = HandoffFakeEngine("dec", auto=False)
        fleet = Fleet(_Factory([pre, dec]), _quiet_config(
            min_replicas=2, roles=("prefill", "decode"),
        ))
        try:
            future = fleet.submit(
                np.asarray([1, 2], np.int32), max_new_tokens=5,
            )
            assert _wait(lambda: len(dec.pending) == 1)
            dec.fail_all(EngineClosedError("decode replica died"))
            # The retry re-prefills (leg 1 again) then re-lands decode.
            assert _wait(lambda: len(dec.pending) == 1)
            dec.resolve_all()
            result = future.result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert result["served_by"] == "dec"
        # Two full prefill legs, both exporting.
        assert [s["handoff_export"] for s in pre.submits] == [True, True]
        assert len(dec.submits) == 2
        assert stats["handoffs"] == 2
        assert stats["handoff_failovers"] == 1
        assert stats["failovers"] >= 1
        assert stats["completed"] == 1

    def test_roleless_fleet_builds_no_handoff_legs(self):
        # Byte-identical pin: roles unset means NO leg logic runs, even
        # against engines that would accept the kwargs, and the schema
        # keys read zero.
        engine = HandoffFakeEngine("e0")
        fleet = Fleet(_Factory([engine]), _quiet_config(min_replicas=1))
        try:
            fleet.submit(
                np.asarray([1], np.int32), max_new_tokens=2,
            ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert engine.submits[0]["handoff_export"] is False
        assert engine.submits[0]["handoff"] is None
        assert stats["handoffs"] == 0
        assert stats["handoff_failovers"] == 0
        assert stats["host_pool"] == {
            "puts": 0, "dedup_hits": 0, "gets": 0, "misses": 0,
            "evictions": 0, "blocks": 0,
        }

    def test_plain_engines_keep_working_without_the_kwargs(self):
        # Duck-typed engines predating the disagg kwargs still serve in
        # a roled fleet — colocated, full budget (accepts_handoff is
        # probed per engine build, same idiom as the trace kwarg).
        plain = FakeEngine("plain")
        dec = FakeEngine("dec")
        fleet = Fleet(_Factory([plain, dec]), _quiet_config(
            min_replicas=2, roles=("prefill", "decode"),
        ))
        try:
            result = fleet.submit(
                np.asarray([1, 2], np.int32), max_new_tokens=4,
            ).result(timeout=30)
            stats = fleet.stats()
        finally:
            fleet.close()
        assert result["served_by"] == "plain"
        assert plain.submits[0]["max_new_tokens"] == 4
        assert stats["handoffs"] == 0

    def test_config_validation(self):
        with pytest.raises(ValueError, match="decode-capable"):
            FleetConfig(min_replicas=2, roles=("prefill", "prefill"))
        with pytest.raises(ValueError, match="role"):
            FleetConfig(min_replicas=2, roles=("prefill", "gpu"))
        with pytest.raises(ValueError, match="host_pool_blocks"):
            FleetConfig(min_replicas=1, host_pool_blocks=0)
        # All-"both" roles stay colocated (and validate clean).
        FleetConfig(min_replicas=2, roles=("both", "both"))


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _direct(params, config, prompt, budget):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    out = generation.generate(
        params, jnp.asarray(np.asarray(prompt)[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=budget,
        sample=generation.SampleConfig(temperature=0.0),
    )
    return np.asarray(out["tokens"])[0]


def _serve(**overrides):
    base = dict(
        max_new_tokens=8, prompt_buckets=(8, 32), num_slots=2,
        chunk_tokens=4, prefix_cache_blocks=16,
        prefix_block_tokens=BLOCK_TOKENS,
    )
    base.update(overrides)
    return ServeConfig(**base)


class TestBatchedBlockIO:
    """The batched pool-row gather/scatter programs the handoff seam
    dispatches: one executable moves N blocks, pad rows are inert."""

    def _pool(self):
        import jax.numpy as jnp

        # [L, num_blocks, block_tokens, H, hd] per leaf, like the real
        # prefix pool (values distinct per block so swaps would show).
        rng = np.random.default_rng(5)
        return {
            name: jnp.asarray(
                rng.normal(size=(2, 6, 4, 3, 5)).astype(np.float32)
            )
            for name in ("k", "v")
        }

    def test_upload_writes_rows_and_drops_padding(self):
        from cloud_tpu.models import generation

        pool = self._pool()
        before = {n: np.asarray(l).copy() for n, l in pool.items()}
        rng = np.random.default_rng(6)
        stacked = {
            n: rng.normal(size=(4, 2, 4, 3, 5)).astype(np.float32)
            for n in pool
        }
        # Rows 1, 3, 4 written; index 6 is out of range -> dropped.
        blocks = np.asarray([1, 3, 4, 6], np.int32)
        out = generation.upload_prefix_blocks(pool, stacked, blocks)
        for name in pool:
            got = np.asarray(out[name])
            for i, block in enumerate((1, 3, 4)):
                np.testing.assert_array_equal(
                    got[:, block], stacked[name][i]
                )
            for untouched in (0, 2, 5):
                np.testing.assert_array_equal(
                    got[:, untouched], before[name][:, untouched]
                )

    def test_download_gathers_rows(self):
        from cloud_tpu.models import generation

        pool = self._pool()
        blocks = np.asarray([4, 0, 2], np.int32)
        out = generation.download_prefix_blocks(pool, blocks)
        for name in pool:
            got = np.asarray(out[name])  # [N, L, bt, H, hd]
            assert got.shape[0] == 3
            for i, block in enumerate((4, 0, 2)):
                np.testing.assert_array_equal(
                    got[i], np.asarray(pool[name])[:, block]
                )

    def test_round_trip_matches_single_block_programs(self):
        from cloud_tpu.models import generation

        pool = self._pool()
        singles = [
            {n: np.asarray(l) for n, l in
             generation.download_prefix_block(pool, b).items()}
            for b in (0, 3, 5)
        ]
        batched = generation.download_prefix_blocks(
            pool, np.asarray([0, 3, 5], np.int32)
        )
        for i in range(3):
            for name in pool:
                np.testing.assert_array_equal(
                    np.asarray(batched[name])[i], singles[i][name]
                )


class TestEngineHandoff:
    """The engine-level export/import seam, on real TINY engines."""

    def test_round_trip_is_token_identical(self, model):
        config, params = model
        prefill = ServingEngine(params, config, _serve(), mesh=None)
        decode = ServingEngine(params, config, _serve(), mesh=None)
        try:
            prefill.set_role("prefill")
            decode.set_role("decode")
            prompt = np.asarray(
                [5, 9, 17, 33, 2, 8, 13, 21, 34, 55, 89, 144, 233],
                np.int32,
            )
            r1 = prefill.submit(
                prompt, max_new_tokens=1, handoff_export=True,
            ).result(timeout=120)
            payload = r1.handoff
            assert payload is not None
            # 13 tokens / block_tokens=4 -> 3 full blocks (the partial
            # tail block is never cached, same as the colocated trie).
            assert payload["block_tokens"] == BLOCK_TOKENS
            assert payload["covered_tokens"] == 12
            assert len(payload["keys"]) == 3
            assert all(p is not None for p in payload["payloads"])
            r2 = decode.submit(
                prompt, max_new_tokens=8, handoff=payload,
            ).result(timeout=120)
            np.testing.assert_array_equal(
                r2.tokens, _direct(params, config, prompt, 8)
            )
            # The import seeded the trie, so admission saw an ordinary
            # prefix hit; counters and health both carry the story.
            ds, dh = decode.stats(), decode.health()
            assert ds["prefix_hits"] == 1
            assert ds["handoff_imports"] == 1
            assert ds["handoff_import_blocks"] == 3
            assert dh["role"] == "decode"
            assert dh["handoff_imports"] == 1
            ps = prefill.stats()
            assert ps["handoff_exports"] == 1
            assert ps["handoff_export_blocks"] == 3
            assert ps["role"] == "prefill"
        finally:
            prefill.close()
            decode.close()

    def test_malformed_payloads_import_less_never_fail(self, model):
        config, params = model
        decode = ServingEngine(params, config, _serve(), mesh=None)
        try:
            prompt = np.asarray([5, 9, 17, 33, 2, 8, 13], np.int32)
            want = _direct(params, config, prompt, 6)
            # Wrong block geometry: import skipped wholesale.
            wrong = _payload(2, block_tokens=8)
            r = decode.submit(
                prompt, max_new_tokens=6, handoff=wrong,
            ).result(timeout=120)
            np.testing.assert_array_equal(r.tokens, want)
            assert decode.stats()["handoff_imports"] == 0
            # A hole in the payload truncates the import there.
            holey = _payload(2)
            holey["keys"] = [
                tuple(int(t) for t in prompt[:4]), ("x",) * 4,
            ]
            holey["payloads"][1] = None
            r = decode.submit(
                prompt, max_new_tokens=6, handoff=holey,
            ).result(timeout=120)
            np.testing.assert_array_equal(r.tokens, want)
        finally:
            decode.close()

    def test_submit_and_role_validation(self, model):
        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(8,), num_slots=1,
        )  # no prefix cache
        engine = ServingEngine(params, config, serve, start=False)
        try:
            with pytest.raises(ValueError, match="prefix_cache_blocks"):
                engine.submit(
                    np.asarray([1, 2], np.int32), handoff_export=True,
                )
            with pytest.raises(ValueError, match="prefix_cache_blocks"):
                engine.submit(
                    np.asarray([1, 2], np.int32), handoff=_payload(1),
                )
            with pytest.raises(ValueError, match="prefix_cache_blocks"):
                engine.set_role("prefill")
            with pytest.raises(ValueError, match="role"):
                engine.set_role("gpu")
        finally:
            engine.close(drain=False)

    @pytest.mark.slow
    def test_round_trip_parity_matrix(self, model):
        """The acceptance matrix: export/import round trips are
        token-identical to colocated generate() for chunked prefill,
        kv_quant int8 (scales ride verbatim), speculative decode, and
        a prefix-hit on the decode side."""
        from cloud_tpu.serving import DraftConfig

        config, params = model
        cases = {
            "chunked": (_serve(prefill_chunk_tokens=4), _serve()),
            "kv_quant": (_serve(kv_quant=True), _serve(kv_quant=True)),
            "spec": (_serve(), _serve(draft=DraftConfig(
                config=config, params=params, spec_k=2,
            ))),
        }
        rng = np.random.default_rng(3)
        for tag, (pre_cfg, dec_cfg) in cases.items():
            prefill = ServingEngine(params, config, pre_cfg, mesh=None)
            decode = ServingEngine(params, config, dec_cfg, mesh=None)
            try:
                prefill.set_role("prefill")
                decode.set_role("decode")
                for n in (6, 13, 21):
                    prompt = rng.integers(1, 255, n).astype(np.int32)
                    r1 = prefill.submit(
                        prompt, max_new_tokens=1, handoff_export=True,
                    ).result(timeout=240)
                    r2 = decode.submit(
                        prompt, max_new_tokens=8, handoff=r1.handoff,
                    ).result(timeout=240)
                    np.testing.assert_array_equal(
                        r2.tokens, _direct(params, config, prompt, 8),
                        err_msg=f"{tag} n={n}",
                    )
                # Prefix-hit leg: the SAME prompt again — the decode
                # trie already holds the chain, the import dedups to
                # zero uploads, and parity still holds.
                r1 = prefill.submit(
                    prompt, max_new_tokens=1, handoff_export=True,
                ).result(timeout=240)
                r2 = decode.submit(
                    prompt, max_new_tokens=8, handoff=r1.handoff,
                ).result(timeout=240)
                np.testing.assert_array_equal(
                    r2.tokens, _direct(params, config, prompt, 8),
                    err_msg=f"{tag} repeat",
                )
                assert decode.stats()["prefix_hits"] >= 1, tag
            finally:
                prefill.close()
                decode.close()


class TestRealEngineDisaggFleet:
    @pytest.mark.slow
    def test_disagg_fleet_parity_and_counters(self, model):
        """A live 1-prefill/2-decode fleet: every result token-identical
        to colocated generate(), every request handed off exactly once,
        and the host pool deduplicating the shared prefix."""
        config, params = model

        def factory():
            return ServingEngine(params, config, _serve(), mesh=None)

        rng = np.random.default_rng(11)
        shared = rng.integers(1, 255, 8).astype(np.int32)
        prompts = [
            np.concatenate(
                [shared, rng.integers(1, 255, n).astype(np.int32)]
            )
            for n in (5, 9, 3, 13)
        ]
        fleet = Fleet(factory, FleetConfig(
            min_replicas=3, poll_interval_s=60.0,
            roles=("prefill", "decode", "decode"),
        ))
        try:
            futures = [
                fleet.submit(p, max_new_tokens=6) for p in prompts
            ]
            results = [f.result(timeout=240) for f in futures]
            stats = fleet.stats()
            health = fleet.health()
        finally:
            fleet.close()
        for prompt, result in zip(prompts, results):
            np.testing.assert_array_equal(
                result.tokens, _direct(params, config, prompt, 6)
            )
        assert stats["handoffs"] == len(prompts)
        assert stats["handoff_failovers"] == 0
        assert stats["completed"] == len(prompts)
        # All prefills on replica 0; decode spread over 1 and 2.
        assert stats["routed"][0] == len(prompts)
        # The shared 8-token head is 2 blocks: stashed once, then
        # dedup-hit by every later handoff that covers it.
        assert stats["host_pool"]["dedup_hits"] >= 2
        roles = {
            snap["replica"]: snap["role"]
            for snap in health["replicas"]
        }
        assert roles == {0: "prefill", 1: "decode", 2: "decode"}
        assert not _fleet_threads()
