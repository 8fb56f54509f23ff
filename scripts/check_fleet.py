"""End-to-end fleet check on CPU: parity across a replica kill, provable
autoscaling, zero leaked threads.

The fleet contracts (docs/fleet.md) are only real if a deterministic
chaos run proves them — the fleet analogue of ``check_serving.py``'s
parity harness and ``check_chaos.py``'s degradation harness:

1. **churn + replica kill** — staggered mixed-length churn traffic
   through a 2-replica fleet of real TINY engines while a
   ``CLOUD_TPU_FAULT_PLAN`` (exported by ``faults.inject``) hangs one
   mid-run chunk dispatch past ``dispatch_timeout_s``.  The watchdog
   kills that replica's engine; its admitted requests must fail over
   and complete on the surviving replica while the supervisor rebuilds
   the dead one.  Asserted: EVERY future resolves with token-for-token
   greedy parity vs per-request ``generation.generate`` (zero admitted
   requests dropped, failed-over requests serve correct tokens),
   ``failovers >= 1``, ``restarts >= 1``, and after ``Fleet.close()``
   no fleet/engine/compile thread survives.
2. **autoscale** — sustained slow traffic into a ``[1, 3]`` fleet whose
   single replica has one decode slot: the fleet queue backs up, the
   autoscaler must scale up; once the backlog drains and the fleet
   idles, it must drain back down to one replica via graceful drain —
   with every request still served (parity-checked) and zero leaks.
3. **mixed-tenant QoS** — a saturating batch tenant floods the fleet
   while an interactive tenant trickles requests in, with the SAME
   mid-flood replica kill injected into both arms: a FIFO baseline
   (no QoS anywhere) and a QoS arm (priority classes + engine brownout
   + a token-bucket quota on the batch tenant).  Asserted: interactive
   TTFT p99 in the QoS arm beats the FIFO baseline (the whole point of
   the class scheduler), the batch tenant's quota rejects typed
   (``QuotaExceededError``) before queueing, brownout sheds BATCH
   requests only (class-ordered — zero interactive sheds), one
   streamed interactive request's tokens match its final result row,
   every completed request has token-for-token greedy parity, every
   interactive request completes, and zero threads leak.
4. **flash crowd** (ISSUE 15) — N clients sharing ONE long system
   prompt, interleaved 1:1 with unique background traffic that keeps
   each replica's (deliberately small) tiered prefix cache under
   eviction pressure, with the SAME mid-run replica kill in both arms:
   a tie-break-only-affinity arm (the PR 9 router) and a cache-aware
   cost-model arm (``cache_alpha``).  Both arms run under an active
   trace collector (ISSUE 16) and dump the merged per-replica
   timeline.  Asserted: cost-model crowd TTFT p99 strictly below the
   tie-break arm's — compared on the TRACE-DERIVED fleet TTFT from the
   stitched timelines (concentrating the crowd on the replica whose
   cache holds the prefix keeps it resident; load spraying lets
   background churn flush it through both tiers), more prefix hit
   tokens in the cost-model arm, EVERY completed request in both arms
   stitched into a full traced lifecycle (>=1 ``fleet/route`` + a
   terminal ``serve/request`` under one trace id, the failed-over
   requests included, with >=1 failed-over trace per arm), the report
   CLI rendering the TTFT decomposition table, token-for-token parity
   for EVERY request in both arms, and zero leaked threads.
5. **disaggregated serving** (ISSUE 19) — a flash crowd of UNIQUE long
   prompts through two 3-replica arms under the SAME two-fault chaos
   plan (a mid-flood prefill-chunk hang that kills the prefill-owning
   replica, then a decode hang that kills a decode-serving replica): a
   colocated arm (roles unset) vs a 1-prefill/2-decode arm.  Asserted:
   disagg decode TPOT p99 STRICTLY below colocated (prefill compute no
   longer interleaves with decode steps), token parity for every
   completed request in both arms, every measured request handed off,
   >=1 decode-leg death re-prefilling through ``handoff_failovers``
   and completing correctly, the re-handoff deduplicating through the
   host pool, a positive ``handoff`` share in the disagg arm's traced
   TTFT decomposition — and the colocated arm pinned byte-identical
   (zero handoffs, zero host-pool traffic, zero handoff share).

Prints one JSON line per phase plus a summary::

    {"phase": "summary", "ok": true, "failovers": 2, "scale_ups": 1, ...}

Wired as a ``slow``-marked test in tests/unit/test_fleet.py (same
pattern as check_serving.py / check_chaos.py), so CI runs it every time.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

# CPU by default: a correctness harness, not a perf one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Multiple host devices (same idiom as tests/conftest.py, harmless when
# the flag is already set): the disaggregated-serving phase pins each
# replica's engine to its own virtual device so the arms model a fleet
# of per-replica accelerators — without this every engine shares ONE
# serial CPU execution queue and the prefill replica's async chunk
# bursts serialize ahead of other replicas' decode steps, interference
# no deployment topology could ever remove.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FLEET_THREAD_PREFIXES = (
    "cloud-tpu-fleet", "cloud-tpu-serve", "cloud-tpu-compile-ahead",
)


def _fleet_threads():
    return [
        t.name for t in threading.enumerate()
        if t.name.startswith(FLEET_THREAD_PREFIXES)
    ]


def _model():
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    return config, params


def _parity_mismatches(params, config, prompts, budgets, results) -> int:
    import jax.numpy as jnp
    import numpy as np

    from cloud_tpu.models import generation

    mismatches = 0
    for prompt, budget, result in zip(prompts, budgets, results):
        direct = generation.generate(
            params, jnp.asarray(prompt[None, :]),
            jnp.asarray([len(prompt)], np.int32), config,
            max_new_tokens=budget,
            sample=generation.SampleConfig(temperature=0.0),
        )
        want = np.asarray(direct["tokens"])[0]
        if not np.array_equal(result.tokens, want) or (
            result.num_generated != int(direct["num_generated"][0])
        ):
            mismatches += 1
    return mismatches


def check_churn_with_replica_kill(timeout: float) -> dict:
    """Phase 1: mixed-length churn across 2 replicas; one replica's
    chunk dispatch hangs mid-run (watchdog kill); zero requests lost."""
    import numpy as np

    from cloud_tpu.fleet import Fleet, FleetConfig
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils import faults

    config, params = _model()
    serve = ServeConfig(
        max_new_tokens=6, prompt_buckets=(8, 16),
        num_slots=2, chunk_tokens=2, dispatch_timeout_s=1.0, warmup=True,
    )

    def factory():
        return ServingEngine(params, config, serve, mesh=None)

    rng = np.random.default_rng(0)
    n_requests = 16
    lens = rng.integers(2, 17, n_requests)
    budgets = [int(b) for b in rng.integers(2, 7, n_requests)]
    prompts = [
        rng.integers(1, 255, int(n)).astype(np.int32) for n in lens
    ]

    fleet = Fleet(factory, FleetConfig(
        min_replicas=2, poll_interval_s=0.05,
    ))
    fleet.wait_ready(timeout=timeout)
    # One warm pass outside the fault plan: the kill must race decode
    # traffic, not a cold compile.
    fleet.submit(prompts[0], max_new_tokens=budgets[0]).result(
        timeout=timeout
    )

    # The replica kill: the 6th chunk dispatch ACROSS the fleet (site
    # counters are per-process) hangs 3 s — past dispatch_timeout_s=1,
    # so whichever replica dispatches it is watchdogged and dies with
    # requests in flight.  inject() exports CLOUD_TPU_FAULT_PLAN, the
    # same seam a staging rig would set in the environment.
    plan = [{"site": "serve.chunk", "mode": "hang", "hang_s": 3.0,
             "nth": 6}]
    with faults.inject(plan) as active:
        assert os.environ.get(faults.ENV_FAULT_PLAN), "plan must export"
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                fleet.submit(prompt, max_new_tokens=budgets[i])
            )
            if (i + 1) % 4 == 0:
                time.sleep(0.05)  # staggered waves keep slots churning
        results = [f.result(timeout=timeout) for f in futures]
    # The traffic can finish (failed over to the survivor) before the
    # supervisor is done rebuilding the killed replica — its kill-close
    # must first join the injected 3 s hang.  Supervision's contract is
    # eventual: wait for it to converge before asserting on it.
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        stats = fleet.stats()
        health = fleet.health()
        if stats["restarts"] >= 1 and health["ready_replicas"] == 2:
            break
        time.sleep(0.05)
    fleet.close()
    leaked = _fleet_threads()

    mismatches = _parity_mismatches(params, config, prompts, budgets,
                                    results)
    return {
        "phase": "churn_replica_kill",
        "ok": (
            mismatches == 0
            and active.fired() == {"serve.chunk": 1}
            and stats["failovers"] >= 1
            and stats["restarts"] >= 1
            and stats["failed"] == 0
            and stats["completed"] == n_requests + 1  # incl. warm pass
            and health["ready_replicas"] == 2  # supervisor rebuilt it
            and not leaked
        ),
        "mismatches": mismatches,
        "faults_fired": active.fired(),
        "failovers": stats["failovers"],
        "restarts": stats["restarts"],
        "completed": stats["completed"],
        "routed": {str(k): v for k, v in stats["routed"].items()},
        "leaked_threads": leaked,
    }


def check_autoscale(timeout: float) -> dict:
    """Phase 2: sustained queue depth scales the fleet up; idleness
    drains it back down — all requests served with parity."""
    import numpy as np

    from cloud_tpu.fleet import AutoscaleConfig, Fleet, FleetConfig
    from cloud_tpu.serving import ServeConfig, ServingEngine

    from cloud_tpu.fleet import default_route_policy

    config, params = _model()
    # One decode slot, a tiny reject-admission queue, and a real
    # per-request budget: a single replica saturates fast and says so
    # typed, so the backlog stays at the FLEET — where a scaled-up
    # replica can actually absorb it via failover.
    serve = ServeConfig(
        max_new_tokens=8, prompt_buckets=(8,),
        num_slots=1, chunk_tokens=2, warmup=True,
        admission="reject", max_queue=2,
    )

    def factory():
        return ServingEngine(params, config, serve, mesh=None)

    fleet = Fleet(factory, FleetConfig(
        min_replicas=1, max_replicas=3, poll_interval_s=0.05,
        # A generous failover budget: the head request may retry against
        # a saturated fleet for a few hundred ms until capacity frees or
        # the autoscaler adds it.
        route_policy=default_route_policy(
            max_attempts=20, initial_backoff_s=0.02, max_backoff_s=0.2,
        ),
        autoscale=AutoscaleConfig(
            scale_up_queue_depth=2.0, window=2, idle_window=6,
            cooldown=2,
        ),
    ))
    fleet.wait_ready(timeout=timeout)

    rng = np.random.default_rng(1)
    n_requests = 24
    prompts = [
        rng.integers(1, 255, int(rng.integers(2, 9))).astype(np.int32)
        for _ in range(n_requests)
    ]
    budgets = [8] * n_requests
    futures = [
        fleet.submit(p, max_new_tokens=8) for p in prompts
    ]

    # Scale-up must happen while the backlog is live.
    deadline = time.perf_counter() + timeout
    peak = 1
    while time.perf_counter() < deadline:
        peak = max(peak, fleet.num_replicas())
        if peak > 1 and all(f.done() for f in futures):
            break
        time.sleep(0.02)
    results = [f.result(timeout=timeout) for f in futures]

    # ...and the idle fleet must drain back to the floor.
    while fleet.num_replicas() > 1 and time.perf_counter() < deadline:
        time.sleep(0.02)
    settled = fleet.num_replicas()
    stats = fleet.stats()
    fleet.close()
    leaked = _fleet_threads()

    mismatches = _parity_mismatches(params, config, prompts, budgets,
                                    results)
    return {
        "phase": "autoscale",
        "ok": (
            mismatches == 0
            and stats["scale_ups"] >= 1
            and stats["scale_downs"] >= 1
            and peak >= 2
            and settled == 1
            and stats["completed"] == n_requests
            and stats["failed"] == 0
            and not leaked
        ),
        "mismatches": mismatches,
        "peak_replicas": peak,
        "settled_replicas": settled,
        "scale_ups": stats["scale_ups"],
        "scale_downs": stats["scale_downs"],
        "completed": stats["completed"],
        "leaked_threads": leaked,
    }


def _mixed_tenant_traffic(rng):
    """One deterministic mixed-tenant workload (shared by both arms so
    the comparison is like-for-like): a saturating batch flood plus a
    staggered interactive trickle."""
    import numpy as np

    # Sized against the CPU rig so the flood actually SATURATES: the
    # FIFO arm's interactive TTFT must be queue-wait dominated (~2 s,
    # several times the watchdog+failover delay a killed replica can
    # add to either arm) for the comparison to be robust — a p99 over
    # 8 interactive samples is effectively a max, so the FIFO floor
    # must clear the kill-recovery ceiling with margin.
    batch_n, interactive_n = 96, 8
    batch_prompts = [
        rng.integers(1, 255, 6).astype(np.int32) for _ in range(batch_n)
    ]
    interactive_prompts = [
        rng.integers(1, 255, 4).astype(np.int32)
        for _ in range(interactive_n)
    ]
    return batch_prompts, 128, interactive_prompts, 4


def _run_mixed_tenant_arm(params, config, *, qos_on: bool,
                          timeout: float) -> dict:
    """One arm of the mixed-tenant comparison: the SAME traffic and the
    SAME mid-flood replica kill, with or without the QoS stack.  Returns
    interactive TTFTs, per-outcome counts, and the parity verdict."""
    import numpy as np

    from cloud_tpu.fleet import (
        Fleet,
        FleetConfig,
        QosConfig,
        QuotaExceededError,
        BrownoutShedError,
        TenantQuota,
    )
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils import faults

    batch_prompts, batch_budget, interactive_prompts, inter_budget = (
        _mixed_tenant_traffic(np.random.default_rng(7))
    )
    # Engine-level QoS does the slot-admission reordering (the fleet
    # queue drains into engine queues under block admission, so THAT is
    # where interactive must jump the line) and the brownout shedding;
    # fleet-level QoS enforces the batch tenant's quota.  The brownout
    # depth sits above the whole interactive trickle but well below the
    # per-engine batch backlog, so shedding is provably class-ordered.
    engine_qos = QosConfig(brownout_queue_depth=8) if qos_on else None
    # A SHORT watchdog: the kill's cost to any single request is
    # bounded by ~dispatch_timeout_s + failover, which must stay well
    # under the FIFO flood wait for the TTFT gate to be deterministic.
    serve = ServeConfig(
        max_new_tokens=batch_budget, prompt_buckets=(8,),
        num_slots=2, chunk_tokens=2,
        dispatch_timeout_s=0.3, warmup=True, qos=engine_qos,
    )

    def factory():
        return ServingEngine(params, config, serve, mesh=None)

    fleet_qos = None
    if qos_on:
        # Quota sized to admit ~36 of the 96 batch requests (cost =
        # 6-token prompt + 128-token budget = 134 each) with a refill
        # too slow to matter inside the run — well ABOVE the per-engine
        # brownout depth, so both enforcement layers provably bind.
        fleet_qos = QosConfig(
            quotas={"batch-tenant": TenantQuota(
                tokens_per_s=0.1, burst_tokens=134 * 36,
            )},
        )
    fleet = Fleet(factory, FleetConfig(
        min_replicas=2, poll_interval_s=0.05, qos=fleet_qos,
    ))
    fleet.wait_ready(timeout=timeout)
    # Warm pass outside the fault plan (phase-1 discipline: the kill
    # must race decode traffic, not a cold compile).
    fleet.submit(batch_prompts[0][:4], max_new_tokens=2).result(
        timeout=timeout
    )

    quota_rejected = 0
    outcomes = []  # (prompt, budget, future, class) for parity later
    stream_handle = None
    stream_tokens = None
    plan = [{"site": "serve.chunk", "mode": "hang", "hang_s": 1.0,
             "nth": 6}]
    with faults.inject(plan) as active:
        for prompt in batch_prompts:
            try:
                future = fleet.submit(
                    prompt, max_new_tokens=batch_budget,
                    priority="batch" if qos_on else None,
                    tenant="batch-tenant" if qos_on else None,
                )
            except QuotaExceededError:
                quota_rejected += 1
                continue
            outcomes.append((prompt, batch_budget, future, "batch"))
        # The trickle starts immediately, WHILE the flood is queued —
        # that is the window where FIFO buries interactive traffic.
        for i, prompt in enumerate(interactive_prompts):
            if qos_on and i == 0:
                # One streamed request: its per-token view must equal
                # its final row (the streaming identity gate).
                stream_handle = fleet.submit(
                    prompt, max_new_tokens=inter_budget,
                    priority="interactive", tenant="chat-tenant",
                    stream=True,
                )
                outcomes.append((prompt, inter_budget,
                                 stream_handle.future, "interactive"))
            else:
                outcomes.append((prompt, inter_budget, fleet.submit(
                    prompt, max_new_tokens=inter_budget,
                    priority="interactive" if qos_on else None,
                    tenant="chat-tenant" if qos_on else None,
                ), "interactive"))
            time.sleep(0.01)
        if stream_handle is not None:
            stream_tokens = list(stream_handle)  # blocks till complete
        completed = []
        brownout_shed = {"batch": 0, "interactive": 0}
        interactive_ttfts = []
        interactive_failed = 0
        for prompt, budget, future, cls in outcomes:
            try:
                result = future.result(timeout=timeout)
            except BrownoutShedError:
                brownout_shed[cls] += 1
                continue
            except Exception:  # noqa: BLE001 — counted, gated below
                if cls == "interactive":
                    interactive_failed += 1
                continue
            completed.append((prompt, budget, result))
            if cls == "interactive":
                interactive_ttfts.append(result.ttft_seconds)
    stats = fleet.stats()
    fleet.close()
    leaked = _fleet_threads()

    mismatches = _parity_mismatches(
        params, config,
        [c[0] for c in completed], [c[1] for c in completed],
        [c[2] for c in completed],
    )
    stream_ok = True
    if stream_handle is not None:
        result = stream_handle.result(timeout=timeout)
        want = list(result.tokens[:result.num_generated])
        stream_ok = stream_tokens == want
    return {
        "qos_on": qos_on,
        "interactive_ttfts": sorted(interactive_ttfts),
        "interactive_failed": interactive_failed,
        "quota_rejected": quota_rejected,
        "brownout_shed": brownout_shed,
        "completed": len(completed),
        "mismatches": mismatches,
        "stream_ok": stream_ok,
        "faults_fired": active.fired(),
        "fleet_quota_rejected": stats["quota_rejected"],
        "class_shed": stats["class_shed"],
        "restarts": stats["restarts"],
        "leaked_threads": leaked,
    }


def _p99(sorted_values):
    if not sorted_values:
        return 0.0
    idx = min(len(sorted_values) - 1,
              int(0.99 * (len(sorted_values) - 1) + 0.5))
    return sorted_values[idx]


def check_mixed_tenant_qos(timeout: float) -> dict:
    """Phase 3: the QoS arm must beat the FIFO arm on interactive TTFT
    p99 under the SAME saturating batch flood and the SAME mid-flood
    replica kill, while the quota and class-ordered shedding contracts
    hold and every completed request keeps greedy parity."""
    config, params = _model()
    fifo = _run_mixed_tenant_arm(params, config, qos_on=False,
                                 timeout=timeout)
    qos = _run_mixed_tenant_arm(params, config, qos_on=True,
                                timeout=timeout)
    fifo_p99 = _p99(fifo["interactive_ttfts"])
    qos_p99 = _p99(qos["interactive_ttfts"])
    shed = qos["brownout_shed"]
    ok = (
        qos_p99 < fifo_p99
        and fifo["interactive_failed"] == 0
        and qos["interactive_failed"] == 0
        and fifo["mismatches"] == 0
        and qos["mismatches"] == 0
        and qos["quota_rejected"] >= 1
        and qos["fleet_quota_rejected"] == qos["quota_rejected"]
        and shed["batch"] >= 1
        and shed["interactive"] == 0
        and qos["class_shed"].get("interactive", 0) == 0
        and qos["stream_ok"]
        and fifo["faults_fired"] == {"serve.chunk": 1}
        and qos["faults_fired"] == {"serve.chunk": 1}
        and not fifo["leaked_threads"]
        and not qos["leaked_threads"]
    )
    return {
        "phase": "mixed_tenant_qos",
        "ok": ok,
        "fifo_interactive_ttft_p99": round(fifo_p99, 4),
        "qos_interactive_ttft_p99": round(qos_p99, 4),
        "quota_rejected": qos["quota_rejected"],
        "brownout_shed": shed,
        "class_shed": qos["class_shed"],
        "stream_ok": qos["stream_ok"],
        "mismatches": fifo["mismatches"] + qos["mismatches"],
        "interactive_failed": (
            fifo["interactive_failed"] + qos["interactive_failed"]
        ),
        "completed": {"fifo": fifo["completed"], "qos": qos["completed"]},
        "restarts": {"fifo": fifo["restarts"], "qos": qos["restarts"]},
        "faults_fired": {"fifo": fifo["faults_fired"],
                         "qos": qos["faults_fired"]},
        "leaked_threads": fifo["leaked_threads"] + qos["leaked_threads"],
    }


def _flash_crowd_traffic(rng):
    """One deterministic flash-crowd workload (shared by both routing
    arms): a crowd of clients sharing ONE long system prompt (the
    measured flash crowd), plus a second tenant's equally hot long
    system prompt as the eviction pressure.  The two 30-block prefixes
    together exceed one replica's HBM+DRAM tiers, so a replica can
    stay warm for ONE of them but never both: cache-aware routing
    partitions the tenants across the fleet (every request a cheap
    hit), load-spraying interleaves them on both replicas and thrashes
    both prefixes through both tiers on every alternation."""
    import numpy as np

    def tenant(n):
        system_prompt = rng.integers(1, 255, 240).astype(np.int32)
        return [
            (np.concatenate(
                [system_prompt,
                 rng.integers(1, 255, 4).astype(np.int32)]
            ), 3)
            for _ in range(n)
        ]

    return tenant(26), tenant(26)


def _run_flash_crowd_arm(params, config, *, cost_model: bool,
                         timeout: float) -> dict:
    """One arm of the flash-crowd comparison: the SAME crowd+pressure
    traffic and the SAME mid-run replica kill through a 2-replica
    tiered-prefix-cache fleet, routed either by the cache-aware cost
    model (``cache_alpha``) or by the PR 9 tie-break-only affinity.

    The whole arm runs under an active trace collector (ISSUE 16):
    every submission carries a trace context, the arm dumps the merged
    per-replica timeline, and the return row adds the trace gates —
    every completed request stitched a full routed lifecycle (the
    failed-over ones included), at least one failed-over trace
    stitched, and the report CLI rendered the TTFT decomposition table
    — plus the trace-derived crowd TTFT p99 the arms are compared on."""
    import shutil
    import tempfile

    import numpy as np

    from cloud_tpu.fleet import Fleet, FleetConfig, LeastLoadedRouter
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.monitoring.report import TraceReport
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils import faults

    crowd, pressure = _flash_crowd_traffic(np.random.default_rng(11))
    # Cache sizing is the experiment: ONE 30-block system prompt fits
    # the 36-block HBM pool with room to breathe, but the OTHER
    # tenant's 30-block insert evicts most of it and the 12-block DRAM
    # tier cannot hold the demoted remainder — so a replica serving
    # both tenants thrashes (partial swap-in hits, ~24 demotions and a
    # long suffix prefill per alternation) while a replica serving one
    # tenant hits for ~the whole prompt in ONE suffix chunk.
    serve = ServeConfig(
        max_new_tokens=4, prompt_buckets=(256,),
        num_slots=1, chunk_tokens=2,
        prefix_cache_blocks=36, prefix_block_tokens=8,
        prefix_dram_blocks=12,
        prefill_chunk_tokens=16,
        # SHORT watchdog: the kill's worst cost to any single request
        # (~timeout + failover re-run) must stay well under the
        # tie-break arm's thrash-driven TTFT floor, so the p99 gate
        # measures routing, not kill luck (phase-3 discipline).
        dispatch_timeout_s=0.15, warmup=True,
    )

    def factory():
        return ServingEngine(params, config, serve, mesh=None)

    # alpha sized so a whole burst sticks: a 240-token summary entry
    # is worth 240 load units — more than any queue gap a burst can
    # build — while requests with no summary entry anywhere still
    # balance by load.
    router = LeastLoadedRouter(
        prefix_affinity=True,
        cache_alpha=1.0 if cost_model else 0.0,
    )
    tmpdir = tempfile.mkdtemp(prefix="cloud_tpu_check_fleet_")
    timeline_path = os.path.join(tmpdir, "timeline.json")
    crowd_trace_ids = []
    try:
        with tracing.collecting():
            fleet = Fleet(
                factory,
                FleetConfig(min_replicas=2, poll_interval_s=0.05),
                router=router,
            )
            fleet.wait_ready(timeout=timeout)
            # Warm pass outside the fault plan (phase-1 discipline).
            fleet.submit(crowd[0][0][:4], max_new_tokens=2).result(
                timeout=timeout
            )

            # SEED, fully drained before the measurement: crowd prefix
            # onto replica 0 (cold-fleet ties break to the lowest id,
            # then affinity), pressure prefix onto replica 1 (submitted
            # while a crowd request is still in flight on 0, so
            # least-loaded routing lands it on 1).  After this both
            # arms' routers face the same state: summaries {0: crowd
            # prefix, 1: pressure prefix}.
            results = []

            def serve_seed(request):
                prompt, budget = request
                results.append(
                    (prompt, budget,
                     fleet.submit(prompt, max_new_tokens=budget)
                     .result(timeout=timeout))
                )

            serve_seed(crowd[0])
            serve_seed(crowd[1])
            crowd_future = fleet.submit(crowd[2][0],
                                        max_new_tokens=crowd[2][1])
            pressure_future = fleet.submit(pressure[0][0],
                                           max_new_tokens=pressure[0][1])
            results.append((crowd[2][0], crowd[2][1],
                            crowd_future.result(timeout=timeout)))
            results.append((pressure[0][0], pressure[0][1],
                            pressure_future.result(timeout=timeout)))
            serve_seed(pressure[1])

            # The measured traffic: alternating same-tenant BURSTS, all
            # submitted without waiting (open flood).  The cost model
            # keeps each tenant on the replica whose summary advertises
            # its prefix — the two replicas drain their tenants in
            # parallel, every request a one-chunk hit.  The tie-break
            # arm's affinity only fires on load-EQUAL ties, which a
            # burst destroys immediately, so bursts spray by load, the
            # tenants interleave on both replicas, and every
            # alternation pays the thrash.  Mid-flood, a chunk dispatch
            # hangs past the watchdog on whichever replica draws it —
            # requests in flight there fail over, and the router
            # re-learns the surviving cache from the LIVE
            # cached_prefixes summaries.
            plan = [{"site": "serve.chunk", "mode": "hang",
                     "hang_s": 0.3, "nth": 12}]
            rounds = 5
            per_burst = 4
            outcomes = []
            with faults.inject(plan) as active:
                for r in range(rounds):
                    lo, hi = 3 + r * per_burst, 3 + (r + 1) * per_burst
                    for prompt, budget in crowd[lo:hi]:
                        outcomes.append(
                            ("crowd", prompt, budget,
                             fleet.submit(prompt, max_new_tokens=budget))
                        )
                    lo, hi = 2 + r * per_burst, 2 + (r + 1) * per_burst
                    for prompt, budget in pressure[lo:hi]:
                        outcomes.append(
                            ("pressure", prompt, budget,
                             fleet.submit(prompt, max_new_tokens=budget))
                        )
                crowd_ttfts = []
                for kind, prompt, budget, future in outcomes:
                    result = future.result(timeout=timeout)
                    results.append((prompt, budget, result))
                    if kind == "crowd":
                        crowd_ttfts.append(result.ttft_seconds)
                        crowd_trace_ids.append(result.trace_id)
            # Let supervision converge (phase-1 discipline: the
            # kill-close must first join the injected hang) before
            # reading the final state.
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                stats = fleet.stats()
                health = fleet.health()
                if (stats["restarts"] >= 1
                        and health["ready_replicas"] == 2):
                    break
                time.sleep(0.05)
            health = fleet.health()
            stats = fleet.stats()
            hit_tokens = sum(
                int(h.get("prefix_hit_tokens") or 0)
                for h in health["replicas"]
            )
            dram_demotions = sum(
                int(h.get("prefix_dram_demotions") or 0)
                for h in health["replicas"]
            )
            # Merged per-replica timeline BEFORE close (the lanes come
            # from the live replica table) — the artifact the trace
            # gates below read back through the report CLI's machinery.
            fleet.dump_timeline(timeline_path)
            fleet.close()
        leaked = _fleet_threads()

        mismatches = _parity_mismatches(
            params, config,
            [r[0] for r in results], [r[1] for r in results],
            [r[2] for r in results],
        )

        # Trace gates (ISSUE 16): every completed request — the
        # failed-over ones included — must stitch a full lifecycle
        # (>=1 fleet/route and a terminal serve/request) under ONE
        # trace id in the merged timeline, at least one failed-over
        # trace must stitch, and the rendered report must carry the
        # TTFT decomposition table.  The arm comparison itself moves to
        # the trace-derived crowd TTFT p99 (same clock as the raw
        # ServeResult numbers, but reproducible from the artifact).
        report = TraceReport.from_file(timeline_path)
        summary = report.request_summary() or {}

        def stitched(trace_id):
            row = summary.get(trace_id or "")
            return bool(row and row["complete"] and row["routes"] >= 1)

        trace_complete = all(
            stitched(r[2].trace_id) for r in results
        )
        failover_stitched = any(
            stitched(r[2].trace_id)
            and summary[r[2].trace_id]["failovers"] >= 1
            for r in results
        )
        crowd_rows = {
            tid: summary[tid] for tid in crowd_trace_ids
            if tid in summary
        }
        decomposition = report.ttft_decomposition(crowd_rows)
        crowd_ttft_p99_traced = (
            decomposition["ttft_p99_s"] if decomposition else None
        )
        decomposition_rendered = "TTFT decomposition" in report.render()
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return {
        "cost_model": cost_model,
        "crowd_ttfts": sorted(crowd_ttfts),
        "crowd_ttft_p99_traced": crowd_ttft_p99_traced,
        "trace_complete": trace_complete,
        "failover_stitched": failover_stitched,
        "decomposition_rendered": decomposition_rendered,
        "traced_requests": len(summary),
        "completed": len(results),
        "mismatches": mismatches,
        "hit_tokens": hit_tokens,
        "dram_demotions": dram_demotions,
        "failovers": stats["failovers"],
        "restarts": stats["restarts"],
        "faults_fired": active.fired(),
        "leaked_threads": leaked,
    }


def check_flash_crowd(timeout: float) -> dict:
    """Phase 4 (ISSUE 15 + 16): cache-aware cost-model routing must
    beat the tie-break-only affinity on TRACE-DERIVED crowd TTFT p99
    under the SAME shared-system-prompt flash crowd, background
    eviction pressure, and mid-run replica kill — while every request
    keeps greedy parity, every completed request in BOTH arms stitches
    a full traced lifecycle (failed-over ones included), the rendered
    report carries the TTFT decomposition table, and nothing leaks."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    # A deeper TINY than the other phases': prefill compute must
    # dominate the wave's drain time, so the TTFT gap the cache buys
    # dwarfs the (symmetric) watchdog+failover cost of the kill.
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=4)
    params = transformer.init(jax.random.PRNGKey(2), config)
    tiebreak = _run_flash_crowd_arm(params, config, cost_model=False,
                                    timeout=timeout)
    cost = _run_flash_crowd_arm(params, config, cost_model=True,
                                timeout=timeout)
    # The arm comparison reads the TRACE-DERIVED p99 (reproducible from
    # the dumped timeline artifact); the raw ServeResult percentiles
    # stay in the row as the cross-check.
    tiebreak_p99 = tiebreak["crowd_ttft_p99_traced"] or _p99(
        tiebreak["crowd_ttfts"]
    )
    cost_p99 = cost["crowd_ttft_p99_traced"] or _p99(cost["crowd_ttfts"])
    ok = (
        cost_p99 < tiebreak_p99
        and cost["hit_tokens"] > tiebreak["hit_tokens"]
        and tiebreak["mismatches"] == 0
        and cost["mismatches"] == 0
        # The chaos must have HAPPENED: a fault that fired without
        # killing and rebuilding a replica would green-light a
        # kill-free run.
        and tiebreak["restarts"] >= 1
        and cost["restarts"] >= 1
        and tiebreak["faults_fired"] == {"serve.chunk": 1}
        and cost["faults_fired"] == {"serve.chunk": 1}
        # Trace completeness (ISSUE 16) in BOTH chaos arms: every
        # completed request stitched end-to-end, at least one
        # failed-over trace among them, decomposition table rendered,
        # and the traced p99s actually existed (None would silently
        # fall back to the raw compare above).
        and tiebreak["trace_complete"]
        and cost["trace_complete"]
        and tiebreak["failover_stitched"]
        and cost["failover_stitched"]
        and tiebreak["decomposition_rendered"]
        and cost["decomposition_rendered"]
        and tiebreak["crowd_ttft_p99_traced"] is not None
        and cost["crowd_ttft_p99_traced"] is not None
        and not tiebreak["leaked_threads"]
        and not cost["leaked_threads"]
    )
    return {
        "phase": "flash_crowd",
        "ok": ok,
        "tiebreak_crowd_ttft_p99": round(tiebreak_p99, 4),
        "cost_model_crowd_ttft_p99": round(cost_p99, 4),
        "trace_complete": {"tiebreak": tiebreak["trace_complete"],
                           "cost_model": cost["trace_complete"]},
        "failover_stitched": {
            "tiebreak": tiebreak["failover_stitched"],
            "cost_model": cost["failover_stitched"],
        },
        "traced_requests": {"tiebreak": tiebreak["traced_requests"],
                            "cost_model": cost["traced_requests"]},
        "hit_tokens": {"tiebreak": tiebreak["hit_tokens"],
                       "cost_model": cost["hit_tokens"]},
        "dram_demotions": {"tiebreak": tiebreak["dram_demotions"],
                           "cost_model": cost["dram_demotions"]},
        "mismatches": tiebreak["mismatches"] + cost["mismatches"],
        "failovers": {"tiebreak": tiebreak["failovers"],
                      "cost_model": cost["failovers"]},
        "restarts": {"tiebreak": tiebreak["restarts"],
                     "cost_model": cost["restarts"]},
        "faults_fired": {"tiebreak": tiebreak["faults_fired"],
                         "cost_model": cost["faults_fired"]},
        "leaked_threads": (
            tiebreak["leaked_threads"] + cost["leaked_threads"]
        ),
    }


def _decode_tpots(results):
    """Per-request decode time-per-output-token, sorted: the decode-side
    latency a disaggregated pool is supposed to protect.  ``latency -
    ttft`` is the FINAL run's pure decode window (the fleet re-bases
    both on failover, so a re-run never inflates its own TPOT — the
    gate measures steady-state decode interference, not kill luck)."""
    return sorted(
        (r.latency_seconds - r.ttft_seconds)
        / max(r.num_generated - 1, 1)
        for r in results
    )


def _run_disagg_arm(params, config, *, roles, timeout: float) -> dict:
    """One arm of the disaggregated-vs-colocated comparison: the SAME
    long-prompt flash crowd (mostly UNIQUE prompts — a fully shared
    prefix would let the colocated arm cache it and erase the
    interference the split removes; a 6-request shared head rides along
    to exercise the pool-dedup path) and the SAME two-fault chaos plan
    through a 3-replica fleet, either colocated (``roles=None``) or
    1-prefill/2-decode.

    The chaos: a mid-flood prefill-chunk hang kills whichever replica
    owns prefill (in the disagg arm, deterministically the prefill
    replica — decode replicas haven't dispatched yet), and a later
    decode hang kills a decode-serving replica, whose in-flight decode
    legs must reset their handoff and RE-PREFILL elsewhere (the
    ``handoff_failovers`` path).  Both arms run traced and dump the
    merged timeline, so the disagg arm can gate the ``handoff`` share
    in ``ttft_decomposition()`` and the colocated arm can pin it at
    zero."""
    import shutil
    import tempfile

    import numpy as np

    from cloud_tpu.fleet import Fleet, FleetConfig, default_route_policy
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.monitoring.report import TraceReport
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils import faults

    rng = np.random.default_rng(19)
    n_requests = 18
    budget = 32
    # 4064 tokens = 507 full 8-token blocks handed off (the trie caps
    # at len-1) + a 7-token tail the decode replica prefills itself.
    # The length is the point: prefill FLOPs grow quadratically with
    # the prompt while decode grows linearly, so at 4k each prefill is
    # several times one request's whole decode window — the regime
    # prefill/decode disaggregation exists for.  A colocated replica
    # interleaves every admission's ~16 chunk dispatches of that work
    # into its live decode windows; a decode replica admits the same
    # request with one batched block upload.  The first 6 prompts
    # share a 512-token head (the pool-dedup path); the rest are fully
    # unique, so the colocated arm cannot cache its way out of the
    # prefill load.
    head = rng.integers(1, 255, 512).astype(np.int32)
    prompts = [
        np.concatenate([head, rng.integers(1, 255, 3552)]).astype(
            np.int32
        ) if i < 6 else rng.integers(1, 255, 4064).astype(np.int32)
        for i in range(n_requests)
    ]
    serve = ServeConfig(
        max_new_tokens=budget, prompt_buckets=(4096,),
        num_slots=2, chunk_tokens=2,
        # Two pinned 507-block imports (one per slot) plus an incoming
        # admission's worth of headroom.
        prefix_cache_blocks=1536, prefix_block_tokens=8,
        prefill_chunk_tokens=256,
        # Loose enough that only the injected hangs trip it: real
        # chunk dispatches on a loaded 3-engine CPU rig can run
        # hundreds of ms (first-shape compiles, seconds).  The TPOT
        # gate is unaffected — it reads each request's FINAL clean
        # decode window.
        dispatch_timeout_s=3.0, warmup=True,
    )

    # Role-tuned engines (the replica passes its role to factories
    # that declare a ``role`` parameter): a decode replica never runs
    # a prefill leg, so the device memory a colocated replica holds
    # for prefill working state goes into a deeper prefix pool instead
    # — imported prefixes outlive their slot pins, and the 6-request
    # shared head keeps hitting on device rather than re-uploading
    # from the host pool.  In the colocated arm every replica is
    # ``"both"`` and gets the base config, byte-identical to a fleet
    # built from a zero-arg factory.
    decode_serve = dataclasses.replace(serve, prefix_cache_blocks=2048)

    # One virtual host device per engine (round-robin over the forced
    # multi-device CPU platform): committing each replica's params —
    # and therefore every program and cache derived from them — to its
    # own device gives each replica its own execution queue, the way a
    # real fleet gives each replica its own accelerator.  Restarted
    # engines take the next device, so a rebuild never queues behind a
    # survivor.  Both arms pin identically; only the roles differ.
    import itertools

    import jax

    devices = jax.devices()
    next_device = itertools.count()

    def factory(role="both"):
        cfg = decode_serve if role == "decode" else serve
        dev = devices[next(next_device) % len(devices)]
        return ServingEngine(jax.device_put(params, dev), config, cfg,
                             mesh=None)

    tmpdir = tempfile.mkdtemp(prefix="cloud_tpu_check_disagg_")
    timeline_path = os.path.join(tmpdir, "timeline.json")
    try:
        with tracing.collecting():
            fleet = Fleet(factory, FleetConfig(
                min_replicas=3, poll_interval_s=0.05, roles=roles,
                host_pool_blocks=12288,
                # Generous failover budget: while the (only) prefill
                # replica rebuilds, every queued request retries
                # through NoReplicaAvailableError until it returns.
                route_policy=default_route_policy(max_attempts=40),
            ))
            fleet.wait_ready(timeout=timeout)
            results = []
            # Warm pass outside the fault plan, FULL SIZE and
            # CONCURRENT — six unique full-length prompts spread by the
            # least-loaded router across all three replicas, so EVERY
            # engine compiles every shape the flood will dispatch (both
            # chunk widths, batch-1 AND batch-2 decode, and in the
            # disagg arm the whole export/stash/import handoff) before
            # the kills arm.  A single warm request would leave the
            # batch-2 decode executable cold fleet-wide and two of the
            # three engines cold entirely — multi-second compiles
            # landing inside measured decode windows.
            n_warm = 6
            warm_prompts = [
                rng.integers(1, 255, 4064).astype(np.int32)
                for _ in range(n_warm)
            ]
            warm_futures = [
                fleet.submit(w, max_new_tokens=8) for w in warm_prompts
            ]
            for w, future in zip(warm_prompts, warm_futures):
                results.append((w, 8, future.result(timeout=timeout)))
            # The chaos plan: the 6th prefill-chunk dispatch after
            # arming hangs past the watchdog — request 1's chunks are
            # dispatched first, so in the disagg arm this lands on THE
            # prefill replica mid-flood; later, the 60th continuous-
            # decode dispatch hangs, killing a decode-serving replica
            # with handoff-carrying requests in flight.
            plan = [
                {"site": "serve.prefill", "mode": "hang",
                 "hang_s": 8.0, "nth": 6},
                {"site": "serve.chunk", "mode": "hang",
                 "hang_s": 8.0, "nth": 60},
            ]
            with faults.inject(plan) as active:
                futures = [
                    fleet.submit(p, max_new_tokens=budget)
                    for p in prompts
                ]
                for prompt, future in zip(prompts, futures):
                    results.append(
                        (prompt, budget, future.result(timeout=timeout))
                    )
            # Let supervision converge before reading final state: both
            # kill-closes must join their injected hangs and rebuild.
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                stats = fleet.stats()
                health = fleet.health()
                if (stats["restarts"] >= 2
                        and health["ready_replicas"] == 3):
                    break
                time.sleep(0.05)
            health = fleet.health()
            stats = fleet.stats()
            fleet.dump_timeline(timeline_path)
            fleet.close()
        leaked = _fleet_threads()
        mismatches = _parity_mismatches(
            params, config,
            [r[0] for r in results], [r[1] for r in results],
            [r[2] for r in results],
        )
        report = TraceReport.from_file(timeline_path)
        decomposition = report.ttft_decomposition() or {}
        handoff_share_p99 = (
            decomposition.get("shares", {})
            .get("handoff", {}).get("p99")
        )
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    crowd = [r[2] for r in results[n_warm:]]  # the measured flood only
    return {
        "roles": list(roles) if roles else None,
        "decode_tpots": _decode_tpots(crowd),
        "completed": stats["completed"],
        "expected": n_requests + n_warm,
        "mismatches": mismatches,
        "handoffs": stats["handoffs"],
        "handoff_failovers": stats["handoff_failovers"],
        "host_pool": stats["host_pool"],
        "handoff_share_p99": handoff_share_p99,
        "failovers": stats["failovers"],
        "restarts": stats["restarts"],
        "ready_replicas": health["ready_replicas"],
        "replica_roles": {
            str(snap["replica"]): snap["role"]
            for snap in health["replicas"]
        },
        "faults_fired": active.fired(),
        "leaked_threads": leaked,
    }


def check_disagg(timeout: float) -> dict:
    """Phase 5 (ISSUE 19): a 1-prefill/2-decode fleet must hold decode
    TPOT p99 STRICTLY below a colocated 3-replica fleet under the same
    long-prompt flash crowd and the same mid-flood prefill-replica kill
    + decode-replica kill — with token parity for every completed
    request in both arms, >=1 handoff-failover request completing
    correctly, and the colocated arm pinned byte-identical (zero
    handoffs, zero handoff share in the TTFT decomposition)."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.models import transformer

    # A 4064-token chunked prefill is ~16 dispatches of quadratic
    # attention work — several times one request's whole decode window,
    # the interference the prefill/decode split exists to remove.
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(3), config)
    colocated = _run_disagg_arm(params, config, roles=None,
                                timeout=timeout)
    disagg = _run_disagg_arm(
        params, config, roles=("prefill", "decode", "decode"),
        timeout=timeout,
    )
    colocated_p99 = _p99(colocated["decode_tpots"])
    disagg_p99 = _p99(disagg["decode_tpots"])
    ok = (
        # The headline gate: decode-side TPOT p99 strictly better.
        disagg_p99 < colocated_p99
        # Parity + completeness in BOTH arms (failed-over included).
        and colocated["mismatches"] == 0
        and disagg["mismatches"] == 0
        and colocated["completed"] == colocated["expected"]
        and disagg["completed"] == disagg["expected"]
        # The chaos actually happened, in both arms, and both replicas
        # were rebuilt.
        and colocated["faults_fired"] == {
            "serve.prefill": 1, "serve.chunk": 1,
        }
        and disagg["faults_fired"] == {
            "serve.prefill": 1, "serve.chunk": 1,
        }
        and colocated["restarts"] >= 2
        and disagg["restarts"] >= 2
        and colocated["ready_replicas"] == 3
        and disagg["ready_replicas"] == 3
        # Disagg semantics: every measured request handed off, >=1
        # decode-leg death re-prefilled (handoff_failovers) and still
        # completed correctly (parity above covers the whole set), and
        # the re-handoff deduplicated through the host pool.
        and disagg["handoffs"] >= disagg["expected"]
        and disagg["handoff_failovers"] >= 1
        and disagg["host_pool"]["dedup_hits"] >= 1
        and (disagg["handoff_share_p99"] or 0) > 0
        and disagg["replica_roles"] == {
            "0": "prefill", "1": "decode", "2": "decode",
        }
        # Colocated arm pinned byte-identical: no handoff ever built.
        and colocated["handoffs"] == 0
        and colocated["handoff_failovers"] == 0
        and colocated["host_pool"] == {
            "puts": 0, "dedup_hits": 0, "gets": 0, "misses": 0,
            "evictions": 0, "blocks": 0,
        }
        and not colocated["handoff_share_p99"]
        and not colocated["leaked_threads"]
        and not disagg["leaked_threads"]
    )
    return {
        "phase": "disagg",
        "ok": ok,
        "colocated_decode_tpot_p99": round(colocated_p99, 5),
        "disagg_decode_tpot_p99": round(disagg_p99, 5),
        "mismatches": colocated["mismatches"] + disagg["mismatches"],
        "handoffs": {"colocated": colocated["handoffs"],
                     "disagg": disagg["handoffs"]},
        "handoff_failovers": disagg["handoff_failovers"],
        "host_pool_dedup_hits": disagg["host_pool"]["dedup_hits"],
        "handoff_share_p99": disagg["handoff_share_p99"],
        "failovers": {"colocated": colocated["failovers"],
                      "disagg": disagg["failovers"]},
        "restarts": {"colocated": colocated["restarts"],
                     "disagg": disagg["restarts"]},
        "faults_fired": {"colocated": colocated["faults_fired"],
                         "disagg": disagg["faults_fired"]},
        "leaked_threads": (
            colocated["leaked_threads"] + disagg["leaked_threads"]
        ),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--timeout", type=float, default=240.0,
                        help="per-phase wait budget (seconds)")
    args = parser.parse_args(argv)

    start = time.perf_counter()
    phases = [
        check_churn_with_replica_kill(args.timeout),
        check_autoscale(args.timeout),
        check_mixed_tenant_qos(args.timeout),
        check_flash_crowd(args.timeout),
        check_disagg(args.timeout),
    ]
    for phase in phases:
        print(json.dumps(phase), flush=True)
    ok = all(p["ok"] for p in phases)
    print(json.dumps({
        "phase": "summary",
        "ok": ok,
        "failovers": phases[0]["failovers"],
        "restarts": phases[0]["restarts"],
        "scale_ups": phases[1]["scale_ups"],
        "scale_downs": phases[1]["scale_downs"],
        "qos_ttft_win": (
            phases[2]["qos_interactive_ttft_p99"]
            < phases[2]["fifo_interactive_ttft_p99"]
        ),
        "quota_rejected": phases[2]["quota_rejected"],
        "brownout_shed": phases[2]["brownout_shed"],
        "flash_crowd_ttft_win": (
            phases[3]["cost_model_crowd_ttft_p99"]
            < phases[3]["tiebreak_crowd_ttft_p99"]
        ),
        "flash_crowd_hit_tokens": phases[3]["hit_tokens"],
        "flash_crowd_trace_complete": phases[3]["trace_complete"],
        "disagg_tpot_win": (
            phases[4]["disagg_decode_tpot_p99"]
            < phases[4]["colocated_decode_tpot_p99"]
        ),
        "disagg_handoffs": phases[4]["handoffs"]["disagg"],
        "disagg_handoff_failovers": phases[4]["handoff_failovers"],
        "leaked_threads": (
            phases[0]["leaked_threads"] + phases[1]["leaked_threads"]
            + phases[2]["leaked_threads"] + phases[3]["leaked_threads"]
            + phases[4]["leaked_threads"]
        ),
        "wall_seconds": round(time.perf_counter() - start, 3),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
