"""Prefix-cache KV reuse + chunked prefill (ISSUE 10 tentpole), plus
the ISSUE 15 fleet-wide KV economy: the host-DRAM second tier
(demote/promote with ref-count-safe handoff, swap-in-loses-race cold
fallback, byte-identical off path) and the cache-aware routing cost
model (``load - alpha * expected_cached_prefix_tokens`` over the
``cached_prefixes`` summaries ``health()`` exports).

The load-bearing contract: greedy outputs stay token-identical to
per-request ``generation.generate`` whether a prompt's prefix hit is
empty, partial, or (capped at prompt-1) the full prompt — including a
hit evicted between lookup and insert (falls back to cold prefill, no
stale KV) — and with prefill split into bounded chunks a decode chunk
never waits more than ONE prefill-chunk dispatch on a long arrival.
Around that: the radix manager's ref-count / LRU-leaf-eviction
semantics (blocks shared by two in-flight slots survive one retiring),
the retrace guards (prefix programs compile once per bucket, the chunk
prefill once per width, the decode chunk still exactly once), the
router's prefix-affinity tie-break, the report CLI's prefix section
(empty-timeline no-crash pinned, like the fleet section), and the
``health()``/``stats()`` key additions the fleet router reads.
"""

import time

import numpy as np
import pytest

from cloud_tpu.serving.prefix_cache import (
    PrefixCacheManager,
    PrefixHit,
    SKIP_BLOCK,
)


class TestPrefixCacheManager:
    """Host-side radix bookkeeping — no device, no engine."""

    def test_match_walks_whole_blocks_and_caps_at_prompt_minus_one(self):
        m = PrefixCacheManager(num_blocks=8, block_tokens=4)
        tokens = list(range(1, 14))  # 13 tokens -> 3 full blocks
        held, created, evicted = m.insert(
            tokens, PrefixHit(nodes=(), tokens=0)
        )
        assert len(held) == len(created) == 3 and evicted == 0
        assert m.blocks_in_use == 3
        # Full 13-token prompt: cacheable span caps at 12 = 3 blocks.
        hit = m.match(tokens)
        assert hit.tokens == 12 and len(hit.nodes) == 3
        assert m.acquire(hit)  # hits count at ACQUIRE, not match
        m.release(list(hit.nodes))
        # The SAME 12 tokens as the whole prompt: cap leaves 2 blocks.
        hit = m.match(tokens[:12])
        assert hit.tokens == 8 and len(hit.nodes) == 2
        # Diverging third block: partial hit of 2 blocks.
        hit = m.match(tokens[:8] + [99, 98, 97, 96, 95])
        assert hit.tokens == 8
        # Unrelated prompt: miss.
        assert not m.match([50, 51, 52, 53, 54])
        stats = m.stats()
        assert stats["lookups"] == 4 and stats["misses"] == 1
        assert stats["hits"] == 1 and stats["hit_tokens"] == 12

    def test_refcounted_blocks_survive_one_holder_retiring(self):
        """The ISSUE satellite: two in-flight slots share a prefix's
        blocks; one retiring must not free them under the other."""
        m = PrefixCacheManager(num_blocks=2, block_tokens=2)
        tokens = [1, 2, 3, 4, 9]
        held_a, _, _ = m.insert(tokens, PrefixHit(nodes=(), tokens=0))
        hit = m.match(tokens)
        assert m.acquire(hit)  # slot B pins the same 2 blocks
        m.release(held_a)  # slot A retires
        # Pool is full and B still holds both: nothing may evict.
        more, created, evicted = m.insert([7, 8, 9, 10, 11],
                                          PrefixHit(nodes=(), tokens=0))
        assert created == [] and more == [] and evicted == 0
        assert all(node.live for node in hit.nodes)
        m.release(list(hit.nodes))  # B retires: now evictable
        more, created, evicted = m.insert([7, 8, 9, 10, 11],
                                          PrefixHit(nodes=(), tokens=0))
        assert len(created) == 2 and evicted == 2
        assert m.stats()["evictions"] == 2

    def test_lru_evicts_unreferenced_leaf_first(self):
        m = PrefixCacheManager(num_blocks=2, block_tokens=2)
        held, _, _ = m.insert([1, 2, 3, 4, 9],
                              PrefixHit(nodes=(), tokens=0))
        parent, leaf = held
        m.release(held)
        # Pool full, both refs 0.  A new insert must take the LEAF
        # (child) block, never the parent under it.
        _, created, evicted = m.insert([5, 6, 7],
                                       PrefixHit(nodes=(), tokens=0))
        assert len(created) == 1 and evicted == 1
        assert not leaf.live and parent.live

    def test_evicted_between_match_and_acquire_fails_acquire(self):
        m = PrefixCacheManager(num_blocks=4, block_tokens=2)
        tokens = [1, 2, 3, 4, 9]
        held, _, _ = m.insert(tokens, PrefixHit(nodes=(), tokens=0))
        m.release(held)
        hit = m.match(tokens)
        assert hit.tokens == 4
        assert m.evict_prefix(tokens) == 2  # the lookup<->insert window
        assert not m.acquire(hit)  # stale hit: caller goes cold
        assert m.match(tokens).tokens == 0
        # The failed pin reads as a MISS on both surfaces (the engine
        # served it cold), with the failure itself counted too.
        stats = m.stats()
        assert stats["hits"] == 0
        assert stats["acquire_failures"] == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="num_blocks"):
            PrefixCacheManager(num_blocks=0, block_tokens=4)
        with pytest.raises(ValueError, match="block_tokens"):
            PrefixCacheManager(num_blocks=4, block_tokens=0)
        with pytest.raises(ValueError, match="dram_blocks"):
            PrefixCacheManager(num_blocks=4, block_tokens=4,
                               dram_blocks=-1)
        assert SKIP_BLOCK > 2 ** 20  # out of any real pool's range


class TestPrefixTierManager:
    """The host-DRAM second tier's bookkeeping (ISSUE 15): demote on
    HBM eviction, promote on acquire, ref-count-safe handoff across
    tiers, bounded DRAM with its own LRU leaf eviction — all host-only,
    with a trivial fake ``demote_fn`` standing in for the engine's
    device download."""

    @staticmethod
    def _tiered(num_blocks, dram_blocks, block_tokens=2):
        demoted = []
        manager = PrefixCacheManager(
            num_blocks, block_tokens, dram_blocks=dram_blocks,
            demote_fn=lambda block: demoted.append(block) or f"b{block}",
        )
        return manager, demoted

    def test_demote_then_promote_refcount_safety(self):
        m, demoted = self._tiered(2, 4)
        held, _, _ = m.insert([1, 2, 3, 4, 9],
                              PrefixHit(nodes=(), tokens=0))
        m.release(held)
        # A second tenant's insert reclaims both HBM rows: the first
        # prefix DEMOTES instead of vanishing.
        other, _, evicted = m.insert([7, 8, 9, 10, 11],
                                     PrefixHit(nodes=(), tokens=0))
        assert evicted == 2 and len(demoted) == 2
        assert m.stats()["demotions"] == 2
        hit = m.match([1, 2, 3, 4, 9])
        assert hit.tokens == 4  # demoted nodes still match
        # Promote back: allocation demotes the second tenant in turn
        # (its blocks are unreferenced once released).
        m.release(other)
        plan = m.acquire_swapin(hit)
        assert plan is not None and len(plan) == 2
        assert [payload for _, _, payload in plan] == ["b0", "b1"] or all(
            isinstance(p, str) for _, _, p in plan
        )
        assert all(n.tier == "hbm" and n.refs == 1 for n in hit.nodes)
        stats = m.stats()
        assert stats["promotions"] == 2 and stats["dram_hits"] == 1
        assert stats["dram_hit_tokens"] == 4
        # The promoted blocks are PINNED: nothing may reclaim them.
        _, created, _ = m.insert([20, 21, 22, 23, 24],
                                 PrefixHit(nodes=(), tokens=0))
        assert created == []  # pool fully pinned: caches less
        m.release(list(hit.nodes))

    def test_pinned_block_never_demotes(self):
        m, demoted = self._tiered(2, 4)
        m.insert([1, 2, 3, 4, 9], PrefixHit(nodes=(), tokens=0))
        hit = m.match([1, 2, 3, 4, 9])
        assert m.acquire(hit)  # insert's ref + the pin on each block
        # Allocation pressure cannot touch referenced blocks: no
        # demotion, no eviction, the insert just caches less.
        _, created, evicted = m.insert([7, 8, 9, 10, 11],
                                       PrefixHit(nodes=(), tokens=0))
        assert created == [] and evicted == 0 and demoted == []
        assert all(n.tier == "hbm" for n in hit.nodes)
        assert m.stats()["demotions"] == 0

    def test_swapin_loses_race_falls_back_cold(self):
        m, _ = self._tiered(2, 4)
        held, _, _ = m.insert([1, 2, 3, 4, 9],
                              PrefixHit(nodes=(), tokens=0))
        m.release(held)
        other, _, _ = m.insert([7, 8, 9, 10, 11],
                               PrefixHit(nodes=(), tokens=0))
        hit = m.match([1, 2, 3, 4, 9])
        assert hit.tokens == 4
        # ``other`` still pins the whole HBM pool: the promotion cannot
        # allocate rows — the swap-in lost the race.  The acquire must
        # fail WHOLE (no partial pins) and count a miss, exactly like
        # the PR 9 evicted-between-match-and-acquire window.
        assert m.acquire_swapin(hit) is None
        assert all(n.refs == 0 for n in hit.nodes)
        stats = m.stats()
        assert stats["swapin_failures"] == 1
        assert stats["acquire_failures"] == 1
        assert stats["hits"] == 0 and stats["misses"] >= 1

    def test_dram_lru_eviction_is_miss_after_demote_evict(self):
        m, _ = self._tiered(1, 1, block_tokens=2)
        for tokens in ([1, 2, 3], [4, 5, 6], [7, 8, 9]):
            held, _, _ = m.insert(tokens, PrefixHit(nodes=(), tokens=0))
            m.release(held)
        stats = m.stats()
        # [1,2] demoted, then dram-evicted to make room for [4,5],
        # which was demoted by [7,8]'s insert.
        assert stats["demotions"] == 2 and stats["dram_evictions"] == 1
        assert not m.match([1, 2, 3])  # gone through BOTH tiers
        assert m.match([4, 5, 6]).nodes[0].tier == "dram"

    def test_plain_acquire_rejects_demoted_nodes(self):
        """The single-tier pin must never hand out a DRAM node — its
        bytes are not on the device."""
        m, _ = self._tiered(1, 2)
        held, _, _ = m.insert([1, 2, 3], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        m.insert([4, 5, 6], PrefixHit(nodes=(), tokens=0))
        hit = m.match([1, 2, 3])
        assert hit.nodes[0].tier == "dram"
        assert not m.acquire(hit)
        assert m.stats()["acquire_failures"] == 1

    def test_demote_without_fn_vanishes_like_pr9(self):
        m = PrefixCacheManager(1, 2, dram_blocks=4)  # no demote_fn
        held, _, _ = m.insert([1, 2, 3], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        m.insert([4, 5, 6], PrefixHit(nodes=(), tokens=0))
        assert not m.match([1, 2, 3])
        assert m.stats()["demotions"] == 0
        assert m.stats()["evictions"] == 1

    def test_hot_prefixes_summary_matches_request_keys(self):
        from cloud_tpu.serving.prefix_cache import (
            AFFINITY_PREFIX_TOKENS,
            affinity_key,
        )

        m = PrefixCacheManager(16, 4)
        head = list(range(100, 140))  # 40 tokens > the 32-token key
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        summary = m.hot_prefixes()
        # A request sharing the head produces the SAME key the summary
        # carries — the router's lookup path.
        key = affinity_key(head + [7, 8, 9])
        assert summary[key] == 40
        assert key == affinity_key(head[:AFFINITY_PREFIX_TOKENS])
        # The summary is a snapshot: mutating the returned dict does
        # not corrupt the manager's own copy.
        summary[key] = 0
        assert m.hot_prefixes()[key] == 40
        # The steady hot path (hit -> release, re-walk insert with no
        # new blocks) never pays the summary DFS: the trie's node set
        # did not change, so the version gate skips the rebuild.
        version = m._summary_version
        hot = m.match(head + [5])
        assert m.acquire(hot)
        m.release(list(hot.nodes))
        m.insert(head + [5], hot)
        assert m._summary_version == version
        assert m._shape_version == version
        # A cached prefix SHORTER than the key length emits nothing: no
        # request's affinity key can ever hash a d-token path (the
        # cacheable span caps at len-1, so hitters hash >= d+1 tokens)
        # and dead keys must not crowd the bounded summary.
        short_held, _, _ = m.insert([7, 8, 9, 10, 11],
                                    PrefixHit(nodes=(), tokens=0))
        m.release(short_held)
        assert list(m.hot_prefixes()) == [key]
        # Eviction shrinks the advertised depth.
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        m.evict_prefix(head + [1])
        assert m.hot_prefixes() == {}


class _FakeReplica:
    def __init__(self, rid, load, ready=True, cached=None):
        self.id = rid
        self._health = {
            "ready": ready, "queue_depth": load, "active_slots": 0,
            "num_slots": 4, "cached_prefixes": dict(cached or {}),
        }

    def health(self):
        return dict(self._health)

    def routable(self, health=None):
        return (health or self._health)["ready"]


class TestRouterPrefixAffinity:
    def test_tie_breaks_toward_recorded_replica(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(prefix_affinity=True)
        replicas = [_FakeReplica(0, 1), _FakeReplica(1, 1)]
        # No recorded affinity: a tie goes lowest-id.
        picked, _ = router.pick(replicas, affinity_key=123)
        assert picked.id == 0
        # The fleet records where the request actually LANDED (replica
        # 1, say after a failover); later ties for that key follow it.
        router.record_affinity(789, 1)
        picked, _ = router.pick(replicas, affinity_key=789)
        assert picked.id == 1
        # Other keys are unaffected.
        picked, _ = router.pick(replicas, affinity_key=456)
        assert picked.id == 0

    def test_affinity_never_overrides_load(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(prefix_affinity=True)
        busy, idle = _FakeReplica(0, 5), _FakeReplica(1, 0)
        router.record_affinity(1, 0)  # the hot prefix lives on 0...
        picked, _ = router.pick([busy, idle], affinity_key=1)
        assert picked.id == 1  # ...but load wins; no tie, no affinity

    def test_affinity_map_is_lru_bounded(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(prefix_affinity=True,
                                   affinity_capacity=2)
        for key in range(5):
            router.record_affinity(key, 0)
        assert len(router._affinity) == 2
        router.record_affinity(None, 0)  # keyless: ignored, no growth
        assert len(router._affinity) == 2

    def test_default_router_ignores_affinity_and_old_signature_works(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter()
        replicas = [_FakeReplica(0, 2), _FakeReplica(1, 1)]
        picked, health = router.pick(replicas)  # two-arg form unchanged
        assert picked.id == 1 and health["queue_depth"] == 1
        picked, _ = router.pick(replicas, affinity_key=7)
        assert picked.id == 1


class TestRouterCostModel:
    """ISSUE 15 (b): ``score = load - cache_alpha * expected cached
    prefix tokens`` over the live ``cached_prefixes`` summaries —
    a real cost model, not a tie-break."""

    def test_cached_replica_wins_despite_load(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(cache_alpha=0.1)
        busy_cached = _FakeReplica(0, 2, cached={42: 64})
        idle_cold = _FakeReplica(1, 0)
        picked, _ = router.pick([busy_cached, idle_cold], affinity_key=42)
        assert picked.id == 0  # 2 - 6.4 beats 0
        # A key the summary does not carry gets no credit.
        picked, _ = router.pick([busy_cached, idle_cold], affinity_key=9)
        assert picked.id == 1
        # No key at all: plain load.
        picked, _ = router.pick([busy_cached, idle_cold])
        assert picked.id == 1
        # alpha calibrates: too-small credit and load wins again.
        weak = LeastLoadedRouter(cache_alpha=0.01)
        picked, _ = weak.pick([busy_cached, idle_cold], affinity_key=42)
        assert picked.id == 1

    def test_alpha_zero_is_tie_break_only(self):
        """The PR 9 contract survives byte-identical: without
        ``cache_alpha`` the summary is ignored and affinity only picks
        among load-equal candidates."""
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(prefix_affinity=True)
        a = _FakeReplica(0, 1, cached={42: 64})
        b = _FakeReplica(1, 1)
        router.record_affinity(42, 1)
        picked, _ = router.pick([a, b], affinity_key=42)
        assert picked.id == 1  # tie-break follows the map, not the cache
        busy, idle = _FakeReplica(0, 5, cached={42: 64}), _FakeReplica(1, 0)
        picked, _ = router.pick([busy, idle], affinity_key=42)
        assert picked.id == 1  # and load still always wins

    def test_stale_affinity_map_loses_to_live_summary(self):
        """The ISSUE 15 failover satellite: after a replica restart the
        record_affinity map can point at a replica whose cache is gone.
        The cost model reads the LIVE summary, so the replica that
        actually holds the prefix (the failover target) wins — and the
        stale map, being a tie-break only, cannot override it."""
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(prefix_affinity=True, cache_alpha=0.1)
        warm = _FakeReplica(0, 1, cached={42: 48})
        restarted = _FakeReplica(1, 1)  # empty cache after rebuild
        router.record_affinity(42, 1)  # stale: recorded before the kill
        picked, _ = router.pick([warm, restarted], affinity_key=42)
        assert picked.id == 0

    def test_composes_with_class_weights(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        router = LeastLoadedRouter(
            class_weights={"interactive": 8.0, "batch": 1.0},
            cache_alpha=0.1,
        )
        # 8 batch requests discount to 1 for an interactive arrival;
        # the 40-token cache credit then pulls the score below the
        # idle candidate's 0.
        loaded = _FakeReplica(0, 8, cached={42: 40})
        loaded._health["class_backlog"] = {"interactive": 0, "batch": 8}
        idle = _FakeReplica(1, 0)
        idle._health["class_backlog"] = {"interactive": 0, "batch": 0}
        picked, _ = router.pick([loaded, idle], affinity_key=42,
                                priority="interactive")
        assert picked.id == 0
        # Without the cache credit the discounted load (1) still loses.
        tie_only = LeastLoadedRouter(
            class_weights={"interactive": 8.0, "batch": 1.0}
        )
        picked, _ = tie_only.pick([loaded, idle], affinity_key=42,
                                  priority="interactive")
        assert picked.id == 1

    def test_validation(self):
        from cloud_tpu.fleet.router import LeastLoadedRouter

        with pytest.raises(ValueError, match="cache_alpha"):
            LeastLoadedRouter(cache_alpha=-0.5)


class TestSummaryTTL:
    """ISSUE 19 satellite: ``summary_ttl_s`` ages stale entries out of
    the router-facing ``hot_prefixes()`` summary — a replica that lost
    its hot tenant stops advertising cached-prefix credit, while the
    blocks themselves stay servable until LRU pressure takes them."""

    @staticmethod
    def _manager(ttl, now):
        return PrefixCacheManager(
            num_blocks=16, block_tokens=4, summary_ttl_s=ttl,
            clock=lambda: now[0],
        )

    def test_stale_entry_expires_but_blocks_still_serve(self):
        from cloud_tpu.serving.prefix_cache import AFFINITY_PREFIX_TOKENS

        now = [0.0]
        m = self._manager(10.0, now)
        head = list(range(100, 100 + AFFINITY_PREFIX_TOKENS + 8))
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        (key,) = m.hot_prefixes()
        # Within the TTL the advertisement holds…
        now[0] = 9.0
        assert key in m.hot_prefixes()
        # …past it the ADVERTISEMENT drops, the blocks do not: a late
        # request still hits the trie at full depth.
        now[0] = 11.0
        assert m.hot_prefixes() == {}
        hit = m.match(head + [5])
        assert hit.tokens == len(head)
        # The hit refreshes the clock — the entry comes back hot.
        assert m.acquire(hit)
        m.release(list(hit.nodes))
        assert key in m.hot_prefixes()
        now[0] = 22.0
        assert m.hot_prefixes() == {}

    def test_clock_map_prunes_with_the_summary(self):
        from cloud_tpu.serving.prefix_cache import AFFINITY_PREFIX_TOKENS

        now = [0.0]
        m = self._manager(10.0, now)
        head = list(range(100, 100 + AFFINITY_PREFIX_TOKENS))
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        assert len(m._last_hit) == 1
        # Evicting the prefix drops its summary entry AND its TTL
        # clock — the map is bounded by the summary, not by traffic.
        m.evict_prefix(head + [1])
        assert m.hot_prefixes() == {}
        assert m._last_hit == {}

    def test_ttl_off_is_byte_identical(self):
        from cloud_tpu.serving.prefix_cache import AFFINITY_PREFIX_TOKENS

        m = PrefixCacheManager(num_blocks=16, block_tokens=4)
        assert m.summary_ttl_s is None
        head = list(range(100, 100 + AFFINITY_PREFIX_TOKENS))
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        assert len(m.hot_prefixes()) == 1
        assert m._last_hit == {}  # no clock bookkeeping at all

    def test_validation(self):
        with pytest.raises(ValueError, match="summary_ttl_s"):
            PrefixCacheManager(num_blocks=4, block_tokens=4,
                               summary_ttl_s=0.0)

    def test_router_stops_crediting_expired_summary(self):
        """The router-level pin: the cost model reads the LIVE (TTL-
        filtered) summary through ``health()``, so an aged-out prefix
        stops pulling traffic to the busier replica."""
        from cloud_tpu.fleet.router import LeastLoadedRouter
        from cloud_tpu.serving.prefix_cache import (
            AFFINITY_PREFIX_TOKENS,
            affinity_key,
        )

        now = [0.0]
        m = self._manager(10.0, now)
        head = list(range(100, 100 + AFFINITY_PREFIX_TOKENS + 16))
        held, _, _ = m.insert(head + [1], PrefixHit(nodes=(), tokens=0))
        m.release(held)
        key = affinity_key(head)

        cold = _FakeReplica(1, 0)

        class _LiveHealthReplica(_FakeReplica):
            def health(self):
                snap = dict(self._health)
                snap["cached_prefixes"] = m.hot_prefixes()
                return snap

        warm = _LiveHealthReplica(0, 2)
        router = LeastLoadedRouter(cache_alpha=0.1)
        picked, _ = router.pick([warm, cold], affinity_key=key)
        assert picked.id == 0  # 2 - 0.1*tokens beats idle 0
        now[0] = 11.0  # the tenant went quiet; the credit ages out
        picked, _ = router.pick([warm, cold], affinity_key=key)
        assert picked.id == 1


class TestReportPrefixSection:
    def _event(self, name, ts, dur, **args):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur,
                "args": args}

    def test_prefix_summary_and_render(self):
        from cloud_tpu.monitoring.report import TraceReport

        events = [
            self._event("serve/prefix_lookup", 0, 10, hit=True,
                        hit_tokens=32),
            self._event("serve/prefix_lookup", 20, 10, hit=False,
                        hit_tokens=0),
            self._event("serve/prefill_chunk", 40, 5000, tokens=16),
            self._event("serve/prefill_chunk", 6000, 3000, tokens=16),
        ]
        report = TraceReport(events)
        summary = report.prefix_summary()
        assert summary["lookups"] == 2 and summary["hits"] == 1
        assert summary["hit_rate"] == 0.5
        assert summary["hit_tokens"] == 32
        assert summary["prefill_chunks"] == 2
        assert summary["max_decode_stall_seconds"] == pytest.approx(0.005)
        rendered = report.render()
        assert "prefix cache:" in rendered
        assert "chunked prefill:" in rendered
        assert "max decode stall" in rendered

    def test_tier_split_and_swapin_attribution(self):
        """ISSUE 15: lookup spans stamped ``dram=True`` split the hit
        count by tier, and ``serve/prefix_swapin`` spans attribute the
        swap-in stall (max = worst single admission)."""
        from cloud_tpu.monitoring.report import TraceReport

        events = [
            self._event("serve/prefix_lookup", 0, 10, hit=True,
                        hit_tokens=32, dram=False),
            self._event("serve/prefix_lookup", 20, 10, hit=True,
                        hit_tokens=16, dram=True),
            self._event("serve/prefix_lookup", 40, 10, hit=False,
                        hit_tokens=0),
            self._event("serve/prefix_swapin", 25, 4000, blocks=4,
                        tokens=16),
            self._event("serve/prefix_swapin", 60, 2000, blocks=2,
                        tokens=8),
        ]
        report = TraceReport(events)
        summary = report.prefix_summary()
        assert summary["hbm_hits"] == 1 and summary["dram_hits"] == 1
        assert summary["swapins"] == 2
        assert summary["swapin_blocks"] == 6
        assert summary["max_swapin_stall_seconds"] == pytest.approx(
            0.004
        )
        rendered = report.render()
        assert "prefix tiers:" in rendered
        assert "max swap-in stall" in rendered
        # Tier-off timelines (PR 9 span shapes) carry zeros and render
        # WITHOUT the tier line.
        old = TraceReport([
            self._event("serve/prefix_lookup", 0, 10, hit=True,
                        hit_tokens=8),
        ])
        old_summary = old.prefix_summary()
        assert old_summary["dram_hits"] == 0
        assert old_summary["swapins"] == 0
        assert "prefix tiers:" not in old.render()

    def test_empty_timeline_no_crash(self):
        """The ISSUE satellite pin, same contract as the fleet section:
        a timeline without prefix spans renders without the section and
        without crashing."""
        from cloud_tpu.monitoring.report import TraceReport

        report = TraceReport([])
        assert report.prefix_summary() is None
        assert "prefix cache:" not in report.render()
        other = TraceReport([self._event("serve/chunk", 0, 10, tokens=1,
                                         occupancy=0.5)])
        assert other.prefix_summary() is None
        assert "prefix cache:" not in other.render()


class TestDemoteBurst:
    """ISSUE 19 satellite: a demotion burst DEFERS every download into
    one batch and flushes the whole batch under ONE supervised dispatch
    at scope exit — one watchdog thread per burst, pinned — instead of
    paying a fresh dispatch thread per evicted block."""

    class _StubEngine:
        """The slice of ServingEngine the demote paths touch."""

        def __init__(self, timeout):
            import threading

            import numpy as np

            from cloud_tpu.serving import ServeConfig
            from cloud_tpu.serving.engine import ServingEngine

            self.serve_config = ServeConfig(dispatch_timeout_s=timeout)
            # The REAL watchdog and flush, bound to this stub — the
            # burst paths must compose with the genuine supervision
            # contract.
            self._supervised = ServingEngine._supervised.__get__(self)
            self._run_under_watchdog = (
                ServingEngine._run_under_watchdog.__get__(self)
            )
            self._pass_seq = 0
            self._flush_demotes = (
                ServingEngine._flush_demotes.__get__(self)
            )
            self._demote_batch = None
            self._prefix_pool = object()  # opaque to the fake cell
            self._last_dispatch_ts = None
            self._orphan_dispatches = []
            self._unhealthy_reason = None
            self._stats = {"watchdog_timeouts": 0}
            self._stats_lock = threading.Lock()
            self.download_threads = []

            def fake_cell(pool, block):
                self.download_threads.append(
                    threading.current_thread()
                )
                return np.asarray(int(block) * 10)

            self._download_cell = lambda: fake_cell

    def test_burst_defers_then_flushes_as_one_dispatch(self):
        import threading

        from cloud_tpu.serving.engine import (
            ServingEngine,
            _DeferredPayload,
            _resolve_payload,
        )

        engine = self._StubEngine(timeout=5.0)
        placeholders = []
        with ServingEngine._demote_burst(engine):
            for block in range(5):
                payload = ServingEngine._demote_block(engine, block)
                assert isinstance(payload, _DeferredPayload)
                assert not payload.filled
                placeholders.append(payload)
            # Nothing downloads mid-burst — the trie holds placeholders.
            assert engine.download_threads == []
            assert len(engine._demote_batch) == 5
        # Burst exit flushed every download, filled in order…
        assert engine._demote_batch is None
        for block, payload in enumerate(placeholders):
            assert payload.filled
            assert int(_resolve_payload(payload)) == block * 10
        # …on ONE supervised worker thread (the thread-count pin:
        # five demotions, one dispatch thread, never the caller's own).
        assert len(engine.download_threads) == 5
        assert len({t.ident for t in engine.download_threads}) == 1
        assert engine.download_threads[0] is not (
            threading.current_thread()
        )
        assert engine._orphan_dispatches == []

    def test_unfilled_placeholder_read_is_typed(self):
        import numpy as np

        from cloud_tpu.serving.engine import (
            _DeferredPayload,
            _resolve_payload,
        )

        # A placeholder consumed before its burst flushed is a bug in
        # the dispatch ordering — fail loudly, never upload garbage.
        with pytest.raises(RuntimeError, match="burst"):
            _resolve_payload(_DeferredPayload())
        # Plain (already-downloaded) payloads pass through untouched.
        payload = np.arange(3)
        assert _resolve_payload(payload) is payload

    def test_burst_flush_timeout_latches_unhealthy(self):
        import threading

        from cloud_tpu.serving.engine import (
            DispatchTimeoutError,
            ServingEngine,
        )

        engine = self._StubEngine(timeout=0.05)
        release = threading.Event()

        def wedged_cell(pool, block):
            release.wait()

        engine._download_cell = lambda: wedged_cell
        with pytest.raises(DispatchTimeoutError, match="exceeded"):
            with ServingEngine._demote_burst(engine):
                ServingEngine._demote_block(engine, 0)
        # The wedged worker is orphan-tracked and the engine latched
        # unhealthy — same contract as every supervised dispatch.
        assert engine._unhealthy_reason is not None
        assert engine._stats["watchdog_timeouts"] == 1
        assert len(engine._orphan_dispatches) == 1
        release.set()  # unwedge the daemon worker

    def test_burst_batches_inline_without_watchdog(self):
        import threading

        from cloud_tpu.serving.engine import (
            ServingEngine,
            _resolve_payload,
        )

        # dispatch_timeout_s=None still batches (one download window),
        # the flush just runs inline on the caller's thread.
        engine = self._StubEngine(timeout=None)
        with ServingEngine._demote_burst(engine):
            payload = ServingEngine._demote_block(engine, 3)
        assert int(_resolve_payload(payload)) == 30
        assert engine.download_threads == [threading.current_thread()]

    def test_nested_bursts_share_the_outer_batch(self):
        from cloud_tpu.serving.engine import ServingEngine

        engine = self._StubEngine(timeout=5.0)
        with ServingEngine._demote_burst(engine):
            outer = engine._demote_batch
            ServingEngine._demote_block(engine, 0)
            with ServingEngine._demote_burst(engine):
                assert engine._demote_batch is outer
                ServingEngine._demote_block(engine, 1)
            # Inner exit must NOT flush — the outer scope owns it.
            assert engine.download_threads == []
            assert len(engine._demote_batch) == 2
        assert len(engine.download_threads) == 2
        assert len({t.ident for t in engine.download_threads}) == 1

    def test_non_burst_demote_keeps_per_block_dispatch(self):
        import threading

        from cloud_tpu.serving.engine import (
            ServingEngine,
            _DeferredPayload,
        )

        engine = self._StubEngine(timeout=5.0)
        payload = ServingEngine._demote_block(engine, 7)
        # Outside a burst the download is immediate — a real payload,
        # not a placeholder — still under its own watchdog thread.
        assert not isinstance(payload, _DeferredPayload)
        assert int(payload) == 70
        assert len(engine.download_threads) == 1
        assert engine.download_threads[0] is not (
            threading.current_thread()
        )


class TestServeConfigKnobs:
    def test_validation(self):
        from cloud_tpu.serving import ServeConfig

        with pytest.raises(ValueError, match="prefix_cache_blocks"):
            ServeConfig(prefix_cache_blocks=-1)
        with pytest.raises(ValueError, match="prefix_block_tokens"):
            ServeConfig(prefix_cache_blocks=4, prefix_block_tokens=0)
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            ServeConfig(prefill_chunk_tokens=0)
        # ISSUE 15: the DRAM tier needs a non-negative bound AND an HBM
        # pool to demote from.
        with pytest.raises(ValueError, match="prefix_dram_blocks"):
            ServeConfig(prefix_cache_blocks=4, prefix_dram_blocks=-1)
        with pytest.raises(ValueError, match="prefix_dram_blocks"):
            ServeConfig(prefix_dram_blocks=8)
        # ISSUE 19: a disaggregated role needs a prefix pool (the KV
        # handoff is prefix-block traffic), and the summary TTL must be
        # a positive window or None.
        with pytest.raises(ValueError, match="role"):
            ServeConfig(role="router")
        with pytest.raises(ValueError, match="prefix_cache_blocks"):
            ServeConfig(role="prefill")
        with pytest.raises(ValueError, match="prefix_summary_ttl_s"):
            ServeConfig(prefix_summary_ttl_s=0.0)
        assert ServeConfig(
            role="decode", prefix_cache_blocks=4
        ).role == "decode"
        # Compatibility default: every knob off.
        cfg = ServeConfig()
        assert cfg.prefix_cache_blocks == 0
        assert cfg.prefix_dram_blocks == 0
        assert cfg.prefill_chunk_tokens is None
        assert cfg.role == "both"
        assert cfg.prefix_summary_ttl_s is None


# --------------------------------------------------------------------------
# Engine-level contracts (real TINY model on CPU).


@pytest.fixture(scope="module")
def model():
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _direct(params, config, prompt, max_new_tokens):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    return generation.generate(
        params, jnp.asarray(prompt[None, :]),
        jnp.asarray([len(prompt)], np.int32), config,
        max_new_tokens=max_new_tokens,
        sample=generation.SampleConfig(temperature=0.0),
    )


def _assert_parity(params, config, prompts, results, budgets=None):
    for i, (prompt, result) in enumerate(zip(prompts, results)):
        budget = budgets[i] if budgets else len(result.tokens)
        want = _direct(params, config, prompt, budget)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        assert result.num_generated == int(want["num_generated"][0])


class TestPrefixEngine:
    @pytest.mark.slow
    def test_shared_prefix_hits_keep_parity_and_compile_once(self, model):
        """Partial hits, a (capped) full-prompt hit, and cold misses in
        one run: token parity throughout, a real hit rate, references
        held by two in-flight slots (no evictions), and the prefix
        programs compiled once per bucket — not per request.

        Slow tier (tier-1 wall-clock is at its budget): the same
        parity + hit-rate + compile-once contracts run e2e in
        scripts/check_serving.py's shared-prefix phase every CI pass,
        and the fast eviction-fallback test below keeps the hit/miss
        admission path itself in tier-1."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(16,),
            num_slots=2, chunk_tokens=2,
            prefix_cache_blocks=8, prefix_block_tokens=4,
        )
        rng = np.random.default_rng(5)
        head = rng.integers(1, 255, 9).astype(np.int32)
        repeat = np.concatenate(
            [head, rng.integers(1, 255, 3).astype(np.int32)]
        )
        prompts = [
            np.concatenate([head, rng.integers(1, 255, 3).astype(np.int32)]),
            np.concatenate([head, rng.integers(1, 255, 5).astype(np.int32)]),
            repeat,
            rng.integers(1, 255, 14).astype(np.int32),  # unrelated miss
        ]
        with ServingEngine(params, config, serve) as engine:
            futures = [engine.submit(p) for p in prompts]
            # An exact repeat of an already-served prompt: the hit caps
            # at prompt-1 tokens and the tail still prefills.
            futures.append(engine.submit(repeat))
            results = [f.result(timeout=120) for f in futures]
            stats = engine.stats()
            health = engine.health()
        _assert_parity(params, config, prompts + [repeat], results)
        assert stats["prefix_hits"] >= 2
        assert stats["prefix_hit_tokens"] >= 8
        assert stats["prefix_misses"] >= 1
        assert stats["evictions"] == 0
        assert stats["prefix_cache_blocks"] > 0
        for key in ("prefix_cache_blocks", "prefix_hit_tokens",
                    "evictions"):
            assert key in health, key
        # Retrace guards: one copy/save compile per TOUCHED bucket, one
        # suffix-chunk compile per touched bucket, one finalize, and
        # the decode chunk still exactly once.
        n_buckets = len(serve.prompt_buckets)
        assert engine._copy_traces <= n_buckets
        assert engine._save_traces <= n_buckets
        assert engine._prefill_chunk_traces <= n_buckets
        assert engine._finalize_traces == 1
        assert engine.chunk_traces == 1

    @pytest.mark.slow
    def test_hit_parity_and_eviction_between_lookup_and_insert(
            self, model):
        """The per-commit prefix contract in one engine: a real HIT is
        token-identical to cold generate(), and an acquire that fails
        (blocks evicted since the match — the no-stale-KV satellite)
        falls back to a cold prefill with unchanged tokens.

        Slow tier (wall-clock, sharded-serving round): hit parity is
        re-pinned fast by TestShardedPrefix and end to end by
        check_serving.py phase 2; the match-vs-acquire eviction
        semantics stay pinned fast at manager level in
        TestPrefixCacheManager."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=3, prompt_buckets=(16,),
            num_slots=2, chunk_tokens=2,
            prefix_cache_blocks=8, prefix_block_tokens=4,
        )
        rng = np.random.default_rng(6)
        head = rng.integers(1, 255, 9).astype(np.int32)
        first = np.concatenate([head,
                                rng.integers(1, 255, 2).astype(np.int32)])
        second = np.concatenate([head,
                                 rng.integers(1, 255, 4).astype(np.int32)])
        third = np.concatenate([head,
                                rng.integers(1, 255, 3).astype(np.int32)])
        with ServingEngine(params, config, serve) as engine:
            engine.submit(first).result(timeout=120)
            # Simulate the eviction window: every acquire fails once the
            # match succeeded, exactly what a block reused under the
            # lookup looks like to the scheduler.
            real_acquire = engine._prefix.acquire
            engine._prefix.acquire = lambda hit: False
            try:
                result = engine.submit(second).result(timeout=120)
            finally:
                engine._prefix.acquire = real_acquire
            # Acquire restored: this one takes the copy + suffix-chunk
            # HIT path for real.
            hit_result = engine.submit(third).result(timeout=120)
            stats = engine.stats()
        want = _direct(params, config, second, 3)
        np.testing.assert_array_equal(
            result.tokens, np.asarray(want["tokens"])[0]
        )
        want = _direct(params, config, third, 3)
        np.testing.assert_array_equal(
            hit_result.tokens, np.asarray(want["tokens"])[0]
        )
        assert stats["prefix_misses"] >= 1  # the failed acquire counted
        assert stats["prefix_hits"] >= 1
        assert stats["prefix_hit_tokens"] >= 8
        # Retrace guards for the prefix-enabled admission path: the
        # one-shot insert (miss), copy/save (hit), and suffix chunk
        # each compiled at most once for the single bucket.
        assert engine._insert_traces <= 1
        assert engine._copy_traces <= 1
        assert engine._save_traces <= 1
        assert engine._prefill_chunk_traces <= 1

    @pytest.mark.slow
    def test_tiny_pool_evicts_and_post_eviction_miss_keeps_parity(
            self, model):
        """A pool too small for the traffic: LRU leaves evict, later
        requests re-miss on evicted prefixes, and every output stays
        token-identical (extends the PR 5 parity suite per the
        acceptance criteria)."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(16,),
            num_slots=1, chunk_tokens=2,
            prefix_cache_blocks=3, prefix_block_tokens=4,
        )
        rng = np.random.default_rng(7)
        heads = [rng.integers(1, 255, 9).astype(np.int32)
                 for _ in range(3)]
        prompts = [
            np.concatenate([
                heads[i % 3], rng.integers(1, 255, 2).astype(np.int32)
            ])
            for i in range(7)
        ]
        with ServingEngine(params, config, serve) as engine:
            results = [
                engine.submit(p).result(timeout=120) for p in prompts
            ]
            stats = engine.stats()
        _assert_parity(params, config, prompts, results)
        # 3 distinct 2-block prefixes through a 3-block pool with one
        # slot: evictions must have happened, and the run survived them.
        assert stats["evictions"] > 0
        assert stats["completed"] == len(prompts)


class TestChunkedPrefill:
    def test_long_prompt_parity_and_decode_stall_bound(self, model):
        """The acceptance criterion: with chunked prefill on, a long
        arrival mid-decode bounds the decode stall at ONE prefill-chunk
        dispatch — between any two consecutive decode chunks at most
        one serve/prefill_chunk span runs — and outputs stay
        token-identical."""
        from cloud_tpu.monitoring import tracing
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=12, prompt_buckets=(4, 16),
            num_slots=2, chunk_tokens=1,
            prefill_chunk_tokens=4,
        )
        rng = np.random.default_rng(8)
        short = rng.integers(1, 255, 3).astype(np.int32)
        long_ = rng.integers(1, 255, 15).astype(np.int32)
        with tracing.collecting() as collector:
            engine = ServingEngine(params, config, serve, start=False)
            # Both queued before start: the scheduler admits both in one
            # pass, the short prompt's single chunk finalizes first and
            # its 12-token decode runs WHILE the long prompt's 4 prefill
            # chunks advance — deterministic interleave, no sleeps.
            short_future = engine.submit(short, max_new_tokens=12)
            long_future = engine.submit(long_, max_new_tokens=2)
            engine.start()
            results = [short_future.result(timeout=120),
                       long_future.result(timeout=120)]
            stats = engine.stats()
            engine.close()
        _assert_parity(params, config, [short, long_], results,
                       budgets=[12, 2])
        # TTFT rides the result (what the bench prefix probe publishes
        # as serve_ttft_p99_seconds): first token lands at finalize,
        # strictly before the request resolves.
        for result in results:
            assert 0 < result.ttft_seconds <= result.latency_seconds
        assert stats["prefill_chunks"] >= 5  # 1 (short) + 4 (long)
        assert engine._prefill_chunk_traces == 1  # ONE width, one compile
        assert engine.chunk_traces == 1

        # The short slot decodes for 24 chunk_tokens=1 dispatches while
        # the long prompt prefills in 4: every prefill chunk must land
        # between decode chunks, never two in a row (an unchunked
        # prefill would put all 4 back to back — the exact stall this
        # knob removes).
        spans = sorted(
            (e for e in collector.events()
             if e["name"] in ("serve/chunk", "serve/prefill_chunk")),
            key=lambda e: e["ts"],
        )
        decode_seen = 0
        prefill_since_decode = 0
        worst = 0
        for event in spans:
            if event["name"] == "serve/chunk":
                decode_seen += 1
                prefill_since_decode = 0
            elif decode_seen:  # stalls only count between decode chunks
                prefill_since_decode += 1
                worst = max(worst, prefill_since_decode)
        assert decode_seen > 0
        assert worst <= 1, [e["name"] for e in spans]

    @pytest.mark.slow
    def test_prefix_plus_chunked_churn_parity(self, model):
        """Both knobs composed under staggered churn with mixed budgets
        — the full tentpole configuration, same parity oracle as the
        PR 5 suite."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=5, prompt_buckets=(8, 16),
            num_slots=4, chunk_tokens=2,
            prefix_cache_blocks=8, prefix_block_tokens=4,
            prefill_chunk_tokens=4,
        )
        rng = np.random.default_rng(9)
        head = rng.integers(1, 255, 10).astype(np.int32)
        prompts = []
        for i in range(10):
            if i % 3 == 2:
                prompts.append(
                    rng.integers(
                        1, 255, int(rng.integers(2, 16))
                    ).astype(np.int32)
                )
            else:
                prompts.append(np.concatenate([
                    head,
                    rng.integers(
                        1, 255, int(rng.integers(1, 6))
                    ).astype(np.int32),
                ]))
        budgets = [int(rng.integers(1, 6)) for _ in prompts]
        engine = ServingEngine(params, config, serve)
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                engine.submit(prompt, max_new_tokens=budgets[i])
            )
            if i in (3, 7):
                time.sleep(0.05)
        results = [f.result(timeout=120) for f in futures]
        stats = engine.stats()
        engine.close()
        _assert_parity(params, config, prompts, results, budgets)
        assert stats["prefix_hits"] >= 2
        assert stats["prefill_chunks"] > 0
        assert engine.chunk_traces == 1
        assert engine._prefill_chunk_traces == 1


class TestPrefixTierEngine:
    """ISSUE 15 engine-level contracts: the host-DRAM tier's demote ->
    swap-in path keeps greedy outputs token-identical to cold
    ``generate()``, a swap-in that loses the race falls back cold, and
    the off path is inert with a zeroed schema."""

    def test_block_download_upload_roundtrip(self, model):
        """The tier's serialization contract: a downloaded block's host
        payload uploaded into ANY pool row reproduces the source row's
        bytes exactly, for every cache leaf (k/v — and, because the
        leaf loop is generic, the int8+scale leaves of a quantized
        pool ride the same path, pinned end-to-end by the slow
        kv_quant churn test)."""
        import jax.numpy as jnp

        from cloud_tpu.models import generation

        config, _ = model
        pool = generation.init_prefix_pool(config, 4, 4)
        pool = {
            name: leaf + jnp.arange(leaf.size, dtype=leaf.dtype).reshape(
                leaf.shape
            )
            for name, leaf in pool.items()
        }
        payload = generation.download_prefix_block(pool, 2)
        restored = generation.upload_prefix_block(pool, {
            name: np.asarray(leaf) for name, leaf in payload.items()
        }, 0)
        for name, leaf in restored.items():
            np.testing.assert_array_equal(
                np.asarray(leaf[:, 0]), np.asarray(pool[name][:, 2])
            )
            # Other rows untouched.
            np.testing.assert_array_equal(
                np.asarray(leaf[:, 1:]), np.asarray(pool[name][:, 1:])
            )

    def test_dram_off_is_inert_and_schema_zero(self, model):
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=2, prompt_buckets=(16,),
            num_slots=1, chunk_tokens=2,
            prefix_cache_blocks=4, prefix_block_tokens=4,
        )
        engine = ServingEngine(params, config, serve, start=False)
        try:
            # No DRAM pool machinery exists: the manager is single-tier
            # (no demote hook), no mover programs were built, and the
            # schema keys read zero.
            assert engine._prefix.dram_blocks == 0
            assert engine._prefix.demote_fn is None
            assert engine._download_step is None
            assert engine._swapin_step is None
            health = engine.health()
            for key in ("prefix_dram_blocks", "prefix_dram_hits",
                        "prefix_dram_hit_tokens", "prefix_dram_demotions",
                        "prefix_dram_evictions",
                        "prefix_dram_swapin_failures"):
                assert health[key] == 0, key
            assert health["cached_prefixes"] == {}
        finally:
            engine.close(drain=False)

    def test_demote_swapin_hit_parity_and_lost_race_fallback(self, model):
        """The tier states in one engine run: cold fill -> eviction
        pressure demotes the head to DRAM -> a repeat prompt hits via
        swap-in (token-identical) -> a forced lost-race acquire falls
        back to a cold prefill (still token-identical)."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(16,),
            num_slots=1, chunk_tokens=2,
            prefix_cache_blocks=3, prefix_block_tokens=4,
            prefix_dram_blocks=8,
        )
        rng = np.random.default_rng(31)
        head = rng.integers(1, 255, 9).astype(np.int32)
        other = rng.integers(1, 255, 13).astype(np.int32)
        prompts = [
            np.concatenate([head, rng.integers(1, 255, 3).astype(np.int32)]),
            other,  # its 3-block insert demotes the head's 2 blocks
            np.concatenate([head, rng.integers(1, 255, 4).astype(np.int32)]),
            np.concatenate([head, rng.integers(1, 255, 2).astype(np.int32)]),
        ]
        with ServingEngine(params, config, serve) as engine:
            results = [
                engine.submit(p).result(timeout=120) for p in prompts[:3]
            ]
            stats_mid = engine.stats()
            # The lost race: every tiered acquire fails once the match
            # succeeded (exactly what a fully pinned pool looks like
            # to the scheduler) — the engine must serve cold.
            real = engine._prefix.acquire_swapin
            engine._prefix.acquire_swapin = lambda hit: None
            try:
                results.append(
                    engine.submit(prompts[3]).result(timeout=120)
                )
            finally:
                engine._prefix.acquire_swapin = real
            stats = engine.stats()
            health = engine.health()
        _assert_parity(params, config, prompts, results)
        assert stats_mid["prefix_dram_demotions"] >= 2
        assert stats_mid["prefix_dram_hits"] >= 1
        assert stats_mid["prefix_dram_hit_tokens"] >= 8
        assert stats["prefix_misses"] > stats_mid["prefix_misses"]
        # One compile each for the tier's block movers.
        assert engine._download_traces == 1
        assert engine._swapin_traces == 1
        assert engine.chunk_traces == 1
        # The summary the cost-model router reads is live and keyed by
        # the shared head's leading tokens.
        assert isinstance(health["cached_prefixes"], dict)
        assert health["prefix_dram_blocks"] >= 0

    @pytest.mark.slow
    def test_tier_churn_parity_with_kv_quant(self, model):
        """Staggered churn through tiny two-tier pools with kv_quant
        int8: demotions, swap-ins, AND misses-after-demote-evict all
        occur, and every output stays token-identical to cold
        generate() (the ISSUE 15 acceptance matrix's quantized arm —
        the tier moves int8 blocks plus their scale leaves)."""
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(16,),
            num_slots=2, chunk_tokens=2,
            prefix_cache_blocks=3, prefix_block_tokens=4,
            prefix_dram_blocks=3,  # small enough to dram-evict too
            kv_quant=True,
        )
        rng = np.random.default_rng(33)
        heads = [rng.integers(1, 255, 9).astype(np.int32)
                 for _ in range(3)]
        prompts = []
        for i in range(9):
            prompts.append(np.concatenate([
                heads[i % 3],
                rng.integers(1, 255, int(rng.integers(2, 6))).astype(
                    np.int32
                ),
            ]))
        budgets = [int(rng.integers(2, 5)) for _ in prompts]
        engine = ServingEngine(params, config, serve)
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                engine.submit(prompt, max_new_tokens=budgets[i])
            )
            if i in (2, 5):
                time.sleep(0.05)
        results = [f.result(timeout=120) for f in futures]
        stats = engine.stats()
        engine.close()
        _assert_parity(params, config, prompts, results, budgets)
        # Three 2-block heads cycling through a 3-block HBM pool and a
        # 3-block DRAM pool: demotions and dram evictions both happen.
        assert stats["prefix_dram_demotions"] > 0
        assert stats["prefix_dram_evictions"] > 0
        assert stats["completed"] == len(prompts)
        assert engine._swapin_traces <= 1
        assert engine._download_traces <= 1


class TestShardedPrefix:
    """Prefix caching + chunked prefill on a TP=2 slice (ISSUE 11): the
    block pool shards by attention head exactly like the slot grid, so
    pool<->slot copies stay chip-local, and hits/chunked suffixes stay
    token-identical to single-chip generate()."""

    def test_tp2_prefix_hit_and_chunked_prefill_parity(self, model):
        from cloud_tpu.serving import ServeConfig, ServingEngine

        config, params = model
        serve = ServeConfig(
            max_new_tokens=4, prompt_buckets=(16,),
            num_slots=2, chunk_tokens=2,
            prefix_cache_blocks=8, prefix_block_tokens=4,
            prefill_chunk_tokens=4,
            mesh_shape=(2, 1),
        )
        rng = np.random.default_rng(21)
        head = rng.integers(1, 255, 10).astype(np.int32)
        prompts = [
            np.concatenate(
                [head, rng.integers(1, 255, 3).astype(np.int32)]
            )
            for _ in range(3)
        ]
        engine = ServingEngine(params, config, serve)
        try:
            # The pool must be head-sharded over the slice like the
            # grid — a replicated pool would reshard on every hit copy.
            pool_spec = engine._prefix_pool["k"].sharding.spec
            assert "tp" in str(pool_spec)
            grid_spec = engine._grid_cache["k"].sharding.spec
            assert "tp" in str(grid_spec)
            # Serially, so the repeat prompts actually hit the cache.
            results = [
                engine.submit(p).result(timeout=120) for p in prompts
            ]
            stats = engine.stats()
        finally:
            engine.close()
        _assert_parity(params, config, prompts, results)
        assert stats["prefix_hits"] >= 1
        assert stats["prefill_chunks"] > 0
        assert stats["slice_chips"] == 2
        assert engine.chunk_traces == 1
        assert engine._prefill_chunk_traces == 1
