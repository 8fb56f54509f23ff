"""Benchmark: ResNet-50 train-step throughput per chip (+ context).

Measures the BASELINE.json north-star workload (ResNet50 steps/sec/chip,
CIFAR-10 config) on the attached TPU, in ONE process, and prints one JSON
line per phase as it completes, then ONE summary line:
``{"metric", "value", "unit", "device", ...}``.  Alongside the headline
number the lines carry context:

* ``tflops_per_sec`` / ``mfu`` — achieved model FLOP/s and utilization for
  the CIFAR config (from XLA's compiled cost analysis).
* ``resnet224_*`` — the MFU-honest vision workload (ImageNet-shape
  224x224 b128 bf16 ResNet50) whose utilization the MXU can actually
  demonstrate; the CIFAR number stays the regression canary (BASELINE.md
  "ResNet ceiling").
* ``bert_*`` — the BERT-base fine-tune config (BASELINE config 3) on the
  framework's auto-dispatched attention path, with analytic-FLOPs MFU.
* ``flash_attention_ok`` / ``group_norm_kernel_ok`` — real-hardware
  Pallas gates: kernels compiled on the device and compared against the
  jnp reference, so a Mosaic regression cannot ship undetected.

It fails loudly: no accelerator, an unknown ``device_kind``, a kernel
that does not compile or diverges, or any phase's exception ends the run
with a non-zero exit.  Nothing is caught and re-measured on another path.
One process owns the chip, so nothing here starts a child.

``chip_smoke.py`` is the quick proof that the system runs on the chip;
this file is the older, wider measurement, kept until the benchmark PR
replaces it.
"""

import json
import os
import sys
import time

BATCH_SIZE = 256
WARMUP_STEPS = 3
MEASURE_STEPS = 20

BERT_BATCH = 32
BERT_SEQ = 128
BERT_WARMUP = 3
BERT_MEASURE = 20

R224_BATCH = 128
R224_WARMUP = 3
R224_MEASURE = 10

#: Fused multi-step context (train.make_multi_step): K steps per dispatch
#: on the SAME CIFAR workload as the headline, so fused_steps_per_sec vs
#: the headline isolates the host dispatch overhead the pipelined engine
#: removes.  Small iter count: one window already runs K steps.
FUSED_K = 4
FUSED_WARMUP = 1
FUSED_MEASURE = 5

#: Serving probe (cloud_tpu.serving): concurrent mixed-length requests
#: through the dynamic batcher on the decode phase's SMALL model — the
#: engine's tokens/sec + latency percentiles + occupancy next to the raw
#: decode_tokens_per_sec isolates what batching/scheduling add or cost.
SERVE_REQUESTS = 16
SERVE_PROMPT_BUCKET = 128
SERVE_NEW_TOKENS = 64
SERVE_MAX_BATCH = 8

#: Continuous-batching churn probe: staggered arrivals, mixed prompt AND
#: output lengths through the slot-based scheduler (serve_continuous_*
#: metrics next to the batch-synchronous serve_* ones above).
SERVE_CHURN_REQUESTS = 24
SERVE_CHURN_CHUNK = 8

#: Shared-prefix churn probe: the same continuous engine with the
#: prefix KV cache + chunked prefill on, many requests over a few long
#: system prompts — the workload prefix reuse exists for.  Emits
#: serve_prefix_hit_tokens_per_sec (prefill compute SKIPPED per second;
#: the acceptance bar is beating the cold path's churn tokens/sec) and
#: serve_ttft_p99_seconds (chunked prefill's tail-latency claim).
SERVE_PREFIX_SYSTEM_PROMPTS = 3
SERVE_PREFIX_BLOCKS = 64
SERVE_PREFIX_BLOCK_TOKENS = 16
SERVE_PREFILL_CHUNK = 32

#: Tiered prefix-cache probe (ISSUE 15): a shared-prefix FLASH-CROWD
#: workload — more distinct long system prompts than the HBM pool can
#: hold at once, cycled so the LRU evicts each hot prefix between its
#: uses — run twice through otherwise-identical engines: DRAM tier OFF
#: (an evicted prefix re-prefills cold) vs ON (it demotes to host DRAM
#: and swaps back in).  Emits TTFT p50/p99 for both arms plus the
#: swap-in/demotion counts, so the tier's whole claim (TTFT under HBM
#: pressure) is a per-round before/after number.  On a CPU rig the
#: delta is a trend number — host<->"device" copies are memcpys — but
#: the hit-rate split (tier-on serves from cache what tier-off
#: re-prefills) is exact.
SERVE_TIER_HEADS = 6
SERVE_TIER_HBM_BLOCKS = 18       # holds ~3 of the 6 heads' prefixes
SERVE_TIER_DRAM_BLOCKS = 64      # holds all of them
SERVE_TIER_REQUESTS = 12         # two eviction cycles over the heads
SERVE_TIER_NEW_TOKENS = 8

#: Speculative-decoding probe: the churn workload through a
#: draft-and-verify engine, twice — once with a SHARED-WEIGHTS draft
#: (same architecture and params as the target: acceptance must read
#: ~100%, a self-check that the verify path, not luck, produces the
#: numbers) and once with a genuinely smaller draft (fewer layers,
#: fresh init) next to the identical non-speculative run.  All three
#: runs serve the SAME prompts, so serve_spec_vs_nonspec_speedup is a
#: like-for-like ratio; a token mismatch between the speculative and
#: non-speculative runs zeroes the rate metrics (never publish a rate
#: for wrong tokens).
SERVE_SPEC_REQUESTS = 12
SERVE_SPEC_PROMPT_BUCKET = 64
SERVE_SPEC_NEW_TOKENS = 32
SERVE_SPEC_K = 4
SERVE_SPEC_DRAFT_LAYERS = 3

#: Fleet probe (cloud_tpu.fleet): the same churn workload through TWO
#: engine replicas behind the health-aware router, so what the fleet
#: layer adds (routing overhead) or buys (parallel replicas) is a
#: per-round number next to the single-engine churn metrics.  On the
#: CPU rig these are two CPU replicas; on a single-chip TPU endpoint the
#: replicas share the chip (the router still spreads queueing).
FLEET_REPLICAS = 2

#: Open-loop arrival sweep at the fleet surface (ROADMAP item 5's
#: latency-under-load curves): requests arrive on a fixed wall-clock
#: schedule at each offered QPS — open loop, so queueing delay shows up
#: in TTFT instead of throttling the arrival rate (closed-loop probes
#: can't see saturation).  Each point emits tokens/sec plus TTFT and
#: TPOT p50/p99; the mixed-class run (QoS armed, alternating
#: interactive/batch arrivals) additionally emits per-class TTFT p99 —
#: the curve pair the priority scheduler's whole existence is judged
#: by.  Four points, low to past-saturation, so a round artifact
#: carries an actual curve with the knee INSIDE it instead of a
#: two-point bracket (ISSUE 15 satellite; was (4, 16)).
FLEET_SWEEP_QPS = (2, 4, 8, 16)
FLEET_SWEEP_REQUESTS = 12
FLEET_SWEEP_PROMPT_LEN = 32
FLEET_SWEEP_NEW_TOKENS = 16
#: Few slots per replica ON PURPOSE: the sweep's job is the queueing
#: regime (slot admission order is where QoS lives); a grid wide enough
#: to hold every arrival in flight would measure nothing but decode.
FLEET_SWEEP_SLOTS = 2

#: Disaggregated-vs-colocated probe (ISSUE 19): one long-prompt flash
#: crowd served twice — a 3-replica colocated fleet, then the SAME
#: replica count split 1 prefill / 2 decode with KV block handoff
#: through the host DRAM pool.  Prompts share a head so the prefill
#: replica's exports dedup in the pool.  Per-arm TTFT/TPOT p50/p99 plus
#: handoff counters; tokens must match across arms (the handoff path is
#: bit-exact by construction and this probe re-proves it per round).
DISAGG_REPLICAS = 3
DISAGG_REQUESTS = 12
DISAGG_PROMPT_LEN = 120
DISAGG_PROMPT_BUCKET = 128
DISAGG_SHARED_HEAD = 24
DISAGG_NEW_TOKENS = 16

METRIC = f"resnet50_cifar10_b{BATCH_SIZE}_train_steps_per_sec_per_chip"

#: Per-chip dense bf16 peak in TFLOP/s by ``device_kind``, one entry per
#: chip this code has run on, each with its source.  A device that is not
#: here is an error, not a default: an MFU over a guessed peak is not a
#: measurement.
PEAK_BF16_TFLOPS = {
    # v5e: cloud.google.com/tpu/docs/v5e, "Peak compute per chip (bf16)".
    "TPU v5 lite": 197.0,
}


def _peak_bf16_tflops(device) -> float:
    kind = getattr(device, "device_kind", None)
    if kind not in PEAK_BF16_TFLOPS:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {kind!r} (platform "
            f"{getattr(device, 'platform', None)!r}); add it to "
            "PEAK_BF16_TFLOPS with its source"
        )
    return PEAK_BF16_TFLOPS[kind]


def _compile_step(step, state, batch):
    """AOT-compile the step once; returns (executable, flops).

    The same executable is handed to the timing loop — the step is never
    compiled twice (lower().compile() does not share the jit dispatch
    cache, so timing ``step`` directly would recompile).  ``flops`` comes
    from XLA cost analysis (fwd+bwd of the exact HLO that runs); None when
    the backend can't report it.
    """
    from cloud_tpu.monitoring import tracing

    with tracing.span("bench/compile"):
        compiled = step.lower(state, batch).compile()
    flops = float(compiled.cost_analysis().get("flops", 0.0))
    return compiled, flops if flops > 0 else None


def _add_flops_context(extras, prefix, flops, steps_per_sec, n_chips=1):
    """Achieved TFLOP/s + MFU next to a throughput number.

    ``flops`` is per GLOBAL step; on a multi-chip run divide by ``n_chips``
    so MFU compares per-chip achieved against the per-chip peak (XLA
    cost_analysis already reports the per-device partitioned module, so
    ResNet passes 1; the analytic BERT count is whole-batch).
    """
    peak = extras.get("peak_bf16_tflops")
    if not flops:
        return
    achieved = flops * steps_per_sec / n_chips / 1e12
    extras[f"{prefix}tflops_per_sec"] = round(achieved, 2)
    if peak:
        extras[f"{prefix}mfu"] = round(achieved / peak, 4)


def _throughput(step, state, batch, *, warmup, iters):
    """Chain-then-read timing; single source of truth lives in
    cloud_tpu/utils/benchmarking.py (imported in the child, where
    cloud_tpu is already on the path)."""
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.utils.benchmarking import chain_then_read_throughput

    with tracing.span("bench/measure", warmup=warmup, iters=iters):
        return chain_then_read_throughput(
            step, state, batch, warmup=warmup, iters=iters
        )


def _emit_phase(phase, **payload):
    print(json.dumps({"phase": phase, **payload}), flush=True)


class HeadlineInvalid(RuntimeError):
    """A phase produced a headline number that cannot be real (zero,
    negative, NaN, inf): the run ends instead of publishing it."""


def _measure_resnet_config(extras, prefix, *, imagenet_shape,
                           batch_size, warmup, iters):
    """One ResNet train-step measurement: build state, AOT-compile, time.

    Workload construction lives in
    cloud_tpu/utils/benchmarking.resnet_train_setup.  Returns steps/sec.
    With mesh=None the step executes on ONE device however many the host
    has, so the measured rate already IS per-chip — dividing by
    len(jax.devices()) would under-report N-fold.
    """
    from cloud_tpu.utils.benchmarking import resnet_train_setup

    step, state, batch = resnet_train_setup(
        imagenet_shape=imagenet_shape, batch_size=batch_size
    )
    compiled, flops = _compile_step(step, state, batch)
    steps_per_sec = _throughput(
        compiled, state, batch, warmup=warmup, iters=iters
    )
    _add_flops_context(extras, prefix, flops, steps_per_sec)
    return steps_per_sec


def _measure_resnet(extras):
    """The headline: CIFAR-shape ResNet50 (the regression canary)."""
    import jax

    extras["peak_bf16_tflops"] = _peak_bf16_tflops(jax.devices()[0])
    steps_per_sec = _measure_resnet_config(
        extras, "", imagenet_shape=False,
        batch_size=BATCH_SIZE, warmup=WARMUP_STEPS, iters=MEASURE_STEPS,
    )
    # Fail LOUDLY on a number that cannot be a measurement.
    if not (steps_per_sec > 0.0 and steps_per_sec < float("inf")):
        raise HeadlineInvalid(
            f"resnet measured {steps_per_sec!r} steps/sec — refusing to "
            "publish a non-positive/non-finite headline"
        )
    _emit_phase("resnet", ok=True, value=steps_per_sec, extras=extras)
    return steps_per_sec


def _measure_resnet224(extras):
    """ImageNet-shape ResNet50: the workload whose MFU means something.

    224x224 b128 bf16 activations; per-step FLOPs from XLA cost analysis.
    The Pallas GroupNorm kernel DOES dispatch for the mid-network stages
    here and its custom calls report 0 FLOPs — but normalization is <1%
    of this program's FLOPs (the 224x224 convs dominate and are XLA
    convs, fully counted), so the MFU undercount is within ~1%.  CIFAR
    stays the headline/regression number; this is the utilization claim.
    """
    steps_per_sec = _measure_resnet_config(
        extras, "resnet224_", imagenet_shape=True,
        batch_size=R224_BATCH, warmup=R224_WARMUP, iters=R224_MEASURE,
    )
    extras["resnet224_steps_per_sec"] = round(steps_per_sec, 3)


def _measure_fused(extras):
    """K-step fused-dispatch throughput on the headline workload.

    Context, not the regression number: the headline stays the 1-step
    CIFAR ResNet so the perf trajectory remains comparable across rounds;
    ``fused_steps_per_sec`` next to it shows what the pipelined execution
    engine (multi-step dispatch) buys on this endpoint.
    """
    from cloud_tpu.utils.benchmarking import (
        fused_throughput,
        resnet_train_setup,
    )

    step, state, batch = resnet_train_setup(
        imagenet_shape=False, batch_size=BATCH_SIZE,
        steps_per_dispatch=FUSED_K,
    )
    compiled, _ = _compile_step(step, state, batch)
    steps_per_sec = fused_throughput(
        compiled, state, batch, steps_per_dispatch=FUSED_K,
        warmup=FUSED_WARMUP, iters=FUSED_MEASURE,
    )
    extras["fused_steps_per_sec"] = round(steps_per_sec, 3)
    extras["fused_steps_per_dispatch"] = FUSED_K


def _bert_analytic_flops(cfg, batch_size, seq_len) -> float:
    """Matmul FLOPs of one BERT train step (fwd + 2x bwd).

    Analytic because XLA's cost analysis is wrong for this program: the
    ``lax.scan`` over layers is counted for ONE trip, and Pallas
    custom-calls report zero FLOPs — the XLA number comes out ~12-15x low.
    Per token per layer (fwd): QKV+out projections 8d^2, scores+values
    4*T*d, MLP 16d^2; embeddings/pooler/classifier are negligible.
    """
    d, layers = cfg.dim, cfg.num_layers
    tokens = batch_size * seq_len
    fwd = tokens * layers * (24 * d * d + 4 * seq_len * d)
    return 3.0 * fwd


def _measure_bert(extras):
    import functools

    import jax
    import numpy as np
    import optax

    from cloud_tpu.models import bert
    from cloud_tpu.training import train as train_lib

    cfg = bert.BERT_BASE
    state = train_lib.create_sharded_state(
        jax.random.PRNGKey(0), functools.partial(bert.init, cfg=cfg),
        optax.adamw(2e-5), mesh=None,
    )
    step = train_lib.make_train_step(
        functools.partial(bert.loss_fn, cfg=cfg), optax.adamw(2e-5)
    )
    rng = np.random.default_rng(0)
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, (BERT_BATCH, BERT_SEQ)).astype(np.int32),
        "label": rng.integers(0, 2, BERT_BATCH).astype(np.int64),
    }
    batch = jax.device_put(batch)

    compiled, _ = _compile_step(step, state, batch)
    steps_per_sec = _throughput(
        compiled, state, batch, warmup=BERT_WARMUP, iters=BERT_MEASURE
    )
    extras["bert_steps_per_sec"] = round(steps_per_sec, 3)
    # n_chips=1: with mesh=None this step executes on ONE device no matter
    # how many the endpoint exposes, so whole-batch FLOPs vs one chip's
    # peak is the correct per-chip MFU.
    _add_flops_context(
        extras, "bert_", _bert_analytic_flops(cfg, BERT_BATCH, BERT_SEQ),
        steps_per_sec, n_chips=1,
    )


def _check_flash_attention(extras):
    """Compile the Pallas flash kernels on the real device (fwd + bwd,
    including the (out, lse) ring-attention entry point with its lse
    cotangent) and compare against the jnp reference (CPU interpret-mode
    coverage is tests/unit/test_ops.py)."""
    import jax
    import jax.numpy as jnp

    # NB: ``from cloud_tpu.ops import flash_attention`` yields the *function*
    # (re-exported in ops/__init__), not the module.
    from cloud_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_with_lse,
    )

    b, t, h, d = 2, 512, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (
        jax.random.normal(key, (b, t, h, d), jnp.bfloat16) for key in keys
    )

    def loss(q, k, v, use_pallas):
        # Both entry points in one program: the plain kernel and the
        # (out, lse) variant with a nonzero lse cotangent (ring's merge).
        # (On one chip ``partitioned=True`` is the same direct call.)
        out = flash_attention(q, k, v, causal=True, use_pallas=use_pallas)
        out2, lse = flash_attention_with_lse(
            q, k, v, causal=False, use_pallas=use_pallas
        )
        return (
            jnp.mean(out.astype(jnp.float32) ** 2)
            + jnp.mean(out2.astype(jnp.float32) ** 2)
            + 0.3 * jnp.mean(jnp.sin(lse))
        )

    grad_fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
    val_kernel, grads_kernel = jax.jit(
        lambda q, k, v: grad_fn(q, k, v, True)
    )(q, k, v)
    val_ref, grads_ref = jax.jit(
        lambda q, k, v: grad_fn(q, k, v, False)
    )(q, k, v)

    def close(a, b):
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        denom = jnp.maximum(jnp.max(jnp.abs(b)), 1e-6)
        return float(jnp.max(jnp.abs(a - b)) / denom) < 3e-2

    ok = close(val_kernel, val_ref) and all(
        close(gk, gr) for gk, gr in zip(grads_kernel, grads_ref)
    )
    if not ok:
        raise AssertionError("flash attention kernel diverged from reference")
    extras["flash_attention_ok"] = True


def _check_group_norm(extras):
    """Compile the fused GroupNorm kernel (fwd+bwd) on the device and
    compare against the jnp reference.  Raises on divergence."""
    import jax
    import jax.numpy as jnp

    from cloud_tpu.ops import group_norm

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (4, 8, 8, 128), jnp.bfloat16) * 2.0 + 5.0
    s = jax.random.normal(k2, (128,), jnp.float32) * 0.2 + 1.0
    b = jnp.zeros((128,), jnp.float32)

    k3 = jax.random.split(k2)[0]
    r = jax.random.normal(k3, x.shape, jnp.bfloat16)

    def loss(x, s, b, r, use_pallas):
        y = group_norm(x, s, b, num_groups=32, use_pallas=use_pallas,
                       partitioned=False)
        # The ResNet headline runs the fused-ReLU epilogue AND the
        # fused-residual bottleneck tail; gate both kernel variants.
        y2 = group_norm(x, s, b, num_groups=32, use_pallas=use_pallas,
                        partitioned=False, activation="relu")
        y3 = group_norm(x, s, b, num_groups=32, use_pallas=use_pallas,
                        partitioned=False, activation="relu", residual=r)
        return (
            jnp.sum(y.astype(jnp.float32) ** 2)
            + jnp.sum(y2.astype(jnp.float32) ** 2)
            + jnp.sum(y3.astype(jnp.float32) ** 2)
        )

    got = jax.jit(jax.value_and_grad(lambda *a: loss(*a, True),
                                     argnums=(0, 1, 2, 3)))(x, s, b, r)
    want = jax.jit(jax.value_and_grad(lambda *a: loss(*a, False),
                                      argnums=(0, 1, 2, 3)))(x, s, b, r)

    def close(a, c):
        a = jnp.asarray(a, jnp.float32)
        c = jnp.asarray(c, jnp.float32)
        denom = jnp.maximum(jnp.max(jnp.abs(c)), 1e-6)
        return float(jnp.max(jnp.abs(a - c)) / denom) < 3e-2

    ok = close(got[0], want[0]) and all(
        close(g, w) for g, w in zip(got[1], want[1])
    )
    if not ok:
        raise AssertionError("group_norm kernel diverged from reference")
    extras["group_norm_kernel_ok"] = True


def _measure_decode(extras):
    """Generation decode throughput: CloudLM SMALL (124M, GPT-2 shape),
    KV-cache greedy decode, tokens/sec — the capability's perf number
    (BASELINE.md had none).  Workload + timing live in
    cloud_tpu/utils/benchmarking.py."""
    from cloud_tpu.utils.benchmarking import (
        decode_setup,
        decode_tokens_per_sec,
    )

    b, t_prompt, new = 4, 128, 128
    cfg, params, prompts, lens = decode_setup(
        batch_size=b, prompt_len=t_prompt
    )
    tokens_per_sec = decode_tokens_per_sec(
        params, cfg, prompts, lens, max_new_tokens=new
    )
    extras["decode_tokens_per_sec"] = round(tokens_per_sec, 1)
    extras["decode_config"] = f"SMALL b{b} prompt{t_prompt} new{new}"


def _latency_pct(latencies, q):
    """Nearest-rank percentile over an already-sorted latency list (one
    rule shared by every serving probe)."""
    return latencies[min(len(latencies) - 1,
                         int(q * (len(latencies) - 1) + 0.5))]


def _measure_serving(extras):
    """Serving-engine probe: N concurrent mixed-length requests through
    the dynamic batcher (``cloud_tpu.serving``), AOT-warmed, on the same
    SMALL model as the decode phase.  Emits engine tokens/sec, request
    latency percentiles, and mean batch occupancy — the three numbers
    TPU serving economics hinge on (bucketed batching only pays while
    occupancy stays high and the flush deadline doesn't dominate p99).
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    serve = ServeConfig(
        max_new_tokens=SERVE_NEW_TOKENS,
        prompt_buckets=(SERVE_PROMPT_BUCKET,),
        batch_buckets=(1, SERVE_MAX_BATCH),
        flush_deadline_s=0.05,
        warmup=True,
        # Pinned to the batch-synchronous path: these serve_* metrics
        # are the PR 4 baseline the continuous churn probe is compared
        # against round over round.
        scheduler="batch",
    )
    rng = np.random.default_rng(0)
    lengths = rng.integers(
        SERVE_PROMPT_BUCKET // 4, SERVE_PROMPT_BUCKET + 1, SERVE_REQUESTS
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]
    with ServingEngine(params, cfg, serve, mesh=None) as engine:
        engine.wait_ready()
        # One warm request absorbs any residual first-dispatch cost the
        # AOT warmup didn't cover; the measured window is steady-state,
        # so occupancy is delta-based past the warm batch (same rule as
        # the churn probe).
        engine.submit(prompts[0]).result()
        warm = engine.stats()
        start = time.perf_counter()
        futures = [engine.submit(p) for p in prompts]
        results = [f.result() for f in futures]
        wall = time.perf_counter() - start
        stats = engine.stats()
    latencies = sorted(r.latency_seconds for r in results)
    total_tokens = sum(r.num_generated for r in results)
    rows = stats["real_rows"] - warm["real_rows"]
    slots = stats["slots"] - warm["slots"]
    extras["serve_decode_tokens_per_sec"] = round(total_tokens / wall, 1)
    extras["serve_p50_latency_seconds"] = round(_latency_pct(latencies, 0.5), 4)
    extras["serve_p99_latency_seconds"] = round(_latency_pct(latencies, 0.99), 4)
    extras["serve_mean_batch_occupancy"] = round(
        rows / slots if slots else 0.0, 3
    )
    extras["serve_config"] = (
        f"SMALL bucket{SERVE_PROMPT_BUCKET} new{SERVE_NEW_TOKENS} "
        f"maxbatch{SERVE_MAX_BATCH} n{SERVE_REQUESTS}"
    )


def _measure_serving_churn(extras):
    """Continuous-batching churn probe: staggered arrivals with mixed
    prompt AND output lengths through the slot-based scheduler — the
    workload batch-synchronous dispatch is worst at (short requests ride
    out long neighbors; late arrivals wait for the drain).  Emits
    ``serve_continuous_occupancy`` (useful emitted tokens / dispatched
    token slots, engine stats) plus churn latency percentiles next to
    the PR 4 serving metrics, so the occupancy win — and its latency
    cost, if any — is tracked per round.
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    serve = ServeConfig(
        max_new_tokens=SERVE_NEW_TOKENS,
        prompt_buckets=(SERVE_PROMPT_BUCKET // 2, SERVE_PROMPT_BUCKET),
        batch_buckets=(1, SERVE_MAX_BATCH),
        num_slots=SERVE_MAX_BATCH,
        chunk_tokens=SERVE_CHURN_CHUNK,
        warmup=True,
    )
    rng = np.random.default_rng(1)
    lengths = rng.integers(
        8, SERVE_PROMPT_BUCKET + 1, SERVE_CHURN_REQUESTS
    )
    budgets = rng.integers(
        SERVE_NEW_TOKENS // 4, SERVE_NEW_TOKENS + 1, SERVE_CHURN_REQUESTS
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]
    with ServingEngine(params, cfg, serve, mesh=None) as engine:
        engine.wait_ready()
        engine.submit(prompts[0]).result()  # absorb residual first-dispatch
        # Delta-base AFTER the warm request: a solo 64-token run through
        # an 8-slot grid is ~1/8 occupancy and must not pollute the
        # published steady-state quotient.
        warm = engine.stats()
        start = time.perf_counter()
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                engine.submit(prompt, max_new_tokens=int(budgets[i]))
            )
            if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                time.sleep(0.02)  # staggered waves, not one burst
        results = [f.result() for f in futures]
        wall = time.perf_counter() - start
        stats = engine.stats()
    latencies = sorted(r.latency_seconds for r in results)
    total_tokens = sum(r.num_generated for r in results)
    dispatched = stats["decode_slot_steps"] - warm["decode_slot_steps"]
    useful = (
        stats["useful_decode_tokens"] - warm["useful_decode_tokens"]
    )
    extras["serve_continuous_occupancy"] = round(
        useful / dispatched if dispatched else 0.0, 3
    )
    extras["serve_churn_tokens_per_sec"] = round(total_tokens / wall, 1)
    extras["serve_churn_p50_latency_seconds"] = round(_latency_pct(latencies, 0.5), 4)
    extras["serve_churn_p99_latency_seconds"] = round(_latency_pct(latencies, 0.99), 4)
    extras["serve_churn_config"] = (
        f"SMALL slots{SERVE_MAX_BATCH} chunk{SERVE_CHURN_CHUNK} "
        f"new<= {SERVE_NEW_TOKENS} n{SERVE_CHURN_REQUESTS} staggered"
    )


def _measure_serving_prefix(extras):
    """Shared-prefix churn probe: requests drawn from a few long system
    prompts (plus short unique tails) through the continuous scheduler
    with the prefix KV cache and chunked prefill enabled.  Emits
    ``serve_prefix_hit_tokens_per_sec`` — prefill tokens SKIPPED per
    wall-clock second via KV reuse, the direct measure of what the
    cache buys — and ``serve_ttft_p99_seconds`` beside the cold-path
    churn metrics, so both levers (reuse and bounded prefill stalls)
    are tracked per round.
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    serve = ServeConfig(
        max_new_tokens=SERVE_NEW_TOKENS,
        prompt_buckets=(SERVE_PROMPT_BUCKET // 2, SERVE_PROMPT_BUCKET),
        num_slots=SERVE_MAX_BATCH,
        chunk_tokens=SERVE_CHURN_CHUNK,
        prefix_cache_blocks=SERVE_PREFIX_BLOCKS,
        prefix_block_tokens=SERVE_PREFIX_BLOCK_TOKENS,
        prefill_chunk_tokens=SERVE_PREFILL_CHUNK,
        warmup=True,
    )
    rng = np.random.default_rng(3)
    # Long shared heads: most of each prompt is one of a few system
    # prompts, so steady-state lookups hit nearly the whole prompt.
    head_len = (SERVE_PROMPT_BUCKET * 3) // 4
    heads = [
        rng.integers(1, cfg.vocab_size, head_len).astype(np.int32)
        for _ in range(SERVE_PREFIX_SYSTEM_PROMPTS)
    ]
    prompts = []
    for _ in range(SERVE_CHURN_REQUESTS):
        tail = rng.integers(
            1, cfg.vocab_size, int(rng.integers(1, 9))
        ).astype(np.int32)
        prompts.append(np.concatenate([
            heads[int(rng.integers(len(heads)))], tail
        ]))
    budgets = rng.integers(
        SERVE_NEW_TOKENS // 4, SERVE_NEW_TOKENS + 1, SERVE_CHURN_REQUESTS
    )
    with ServingEngine(params, cfg, serve, mesh=None) as engine:
        engine.wait_ready()
        engine.submit(prompts[0]).result()  # absorb residual first-dispatch
        warm = engine.stats()
        start = time.perf_counter()
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                engine.submit(prompt, max_new_tokens=int(budgets[i]))
            )
            if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                time.sleep(0.02)  # staggered waves, not one burst
        results = [f.result() for f in futures]
        wall = time.perf_counter() - start
        stats = engine.stats()
    ttfts = sorted(r.ttft_seconds for r in results)
    total_tokens = sum(r.num_generated for r in results)
    hit_tokens = stats["prefix_hit_tokens"] - warm["prefix_hit_tokens"]
    lookups = (
        stats["prefix_hits"] + stats["prefix_misses"]
        - warm["prefix_hits"] - warm["prefix_misses"]
    )
    hits = stats["prefix_hits"] - warm["prefix_hits"]
    extras["serve_prefix_hit_tokens_per_sec"] = round(hit_tokens / wall, 1)
    extras["serve_prefix_hit_rate"] = round(
        hits / lookups if lookups else 0.0, 3
    )
    extras["serve_prefix_tokens_per_sec"] = round(total_tokens / wall, 1)
    extras["serve_ttft_p99_seconds"] = round(_latency_pct(ttfts, 0.99), 4)
    extras["serve_ttft_p50_seconds"] = round(_latency_pct(ttfts, 0.5), 4)
    extras["serve_prefix_evictions"] = (
        stats["evictions"] - warm["evictions"]
    )
    extras["serve_prefix_config"] = (
        f"SMALL slots{SERVE_MAX_BATCH} blocks{SERVE_PREFIX_BLOCKS}"
        f"x{SERVE_PREFIX_BLOCK_TOKENS} pchunk{SERVE_PREFILL_CHUNK} "
        f"heads{SERVE_PREFIX_SYSTEM_PROMPTS} n{SERVE_CHURN_REQUESTS}"
    )


def _measure_serving_prefix_tier(extras):
    """Host-DRAM prefix tier before/after probe (constants block above):
    the SAME flash-crowd workload — more hot system prompts than the
    HBM pool holds, cycled so each one's blocks are evicted between
    uses — through a tier-off engine (evictions are losses: the next
    request re-prefills cold) and a tier-on engine (evictions demote
    to host DRAM and swap back in).  Emits TTFT p50/p99 per arm plus
    the swap-in/hit accounting, so the tier's claim — TTFT survival
    under HBM pressure — is a per-round number.
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    rng = np.random.default_rng(11)
    head_len = (SERVE_PROMPT_BUCKET * 3) // 4
    heads = [
        rng.integers(1, cfg.vocab_size, head_len).astype(np.int32)
        for _ in range(SERVE_TIER_HEADS)
    ]
    prompts = []
    for i in range(SERVE_TIER_REQUESTS):
        tail = rng.integers(
            1, cfg.vocab_size, int(rng.integers(1, 9))
        ).astype(np.int32)
        # Cycle the heads: each one's reuse distance exceeds the HBM
        # pool, so the LRU has always evicted it again by its next use.
        prompts.append(np.concatenate([
            heads[i % SERVE_TIER_HEADS], tail
        ]))

    def crowd(dram_blocks):
        serve = ServeConfig(
            max_new_tokens=SERVE_TIER_NEW_TOKENS,
            prompt_buckets=(SERVE_PROMPT_BUCKET,),
            num_slots=2,
            chunk_tokens=SERVE_CHURN_CHUNK,
            prefix_cache_blocks=SERVE_TIER_HBM_BLOCKS,
            prefix_block_tokens=SERVE_PREFIX_BLOCK_TOKENS,
            prefill_chunk_tokens=SERVE_PREFILL_CHUNK,
            prefix_dram_blocks=dram_blocks,
            warmup=True,
        )
        with ServingEngine(params, cfg, serve, mesh=None) as engine:
            engine.wait_ready()
            # Seed every head once (outside the measurement): the crowd
            # then measures REUSE under eviction pressure, not first
            # contact.
            for head in heads:
                engine.submit(
                    np.concatenate([head, head[:1]]), max_new_tokens=2
                ).result()
            warm = engine.stats()
            futures = []
            for i, prompt in enumerate(prompts):
                futures.append(engine.submit(prompt))
                if (i + 1) % 4 == 0:
                    time.sleep(0.02)  # staggered waves, not one burst
            results = [f.result() for f in futures]
            stats = engine.stats()
        ttfts = sorted(r.ttft_seconds for r in results)
        return ttfts, warm, stats

    off_ttfts, off_warm, off_stats = crowd(0)
    on_ttfts, on_warm, on_stats = crowd(SERVE_TIER_DRAM_BLOCKS)
    extras["serve_prefix_tier_off_ttft_p50_seconds"] = round(
        _latency_pct(off_ttfts, 0.5), 4
    )
    extras["serve_prefix_tier_off_ttft_p99_seconds"] = round(
        _latency_pct(off_ttfts, 0.99), 4
    )
    extras["serve_prefix_tier_on_ttft_p50_seconds"] = round(
        _latency_pct(on_ttfts, 0.5), 4
    )
    extras["serve_prefix_tier_on_ttft_p99_seconds"] = round(
        _latency_pct(on_ttfts, 0.99), 4
    )
    extras["serve_prefix_tier_off_hit_tokens"] = (
        off_stats["prefix_hit_tokens"] - off_warm["prefix_hit_tokens"]
    )
    extras["serve_prefix_tier_on_hit_tokens"] = (
        on_stats["prefix_hit_tokens"] - on_warm["prefix_hit_tokens"]
    )
    extras["serve_prefix_tier_swapin_hits"] = (
        on_stats["prefix_dram_hits"] - on_warm["prefix_dram_hits"]
    )
    extras["serve_prefix_tier_demotions"] = (
        on_stats["prefix_dram_demotions"]
        - on_warm["prefix_dram_demotions"]
    )
    extras["serve_prefix_tier_config"] = (
        f"SMALL slots2 hbm{SERVE_TIER_HBM_BLOCKS}"
        f"x{SERVE_PREFIX_BLOCK_TOKENS} dram{SERVE_TIER_DRAM_BLOCKS} "
        f"heads{SERVE_TIER_HEADS}x{head_len} n{SERVE_TIER_REQUESTS} "
        f"pchunk{SERVE_PREFILL_CHUNK}"
    )


def _measure_serving_spec(extras):
    """Speculative-decoding probe (constants block above): the same
    staggered churn through a non-speculative engine, a smaller-draft
    speculative engine, and a shared-weights speculative engine.  Emits
    ``serve_spec_accepted_tokens_per_sec`` (committed tokens per
    wall-clock second with the real draft),
    ``serve_spec_acceptance_rate`` (committed draft tokens / proposed),
    ``serve_spec_vs_nonspec_speedup`` (same prompts, same engine knobs,
    only the draft differs), and
    ``serve_spec_selfcheck_acceptance_rate`` — the shared-weights run,
    which must read ~1.0 (budget truncation at window tails shaves a
    little) or the verify path is broken.  Parity-gated: any token
    mismatch vs the non-speculative run zeroes the rate metrics and
    reports the mismatch count instead of publishing a rate for wrong
    tokens.
    """
    import jax
    import numpy as np

    from cloud_tpu.models import transformer
    from cloud_tpu.serving import DraftConfig, ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_SPEC_PROMPT_BUCKET
    )
    draft_cfg = cfg.scaled(num_layers=SERVE_SPEC_DRAFT_LAYERS)
    draft_params = jax.device_put(
        transformer.init(jax.random.PRNGKey(5), draft_cfg)
    )
    rng = np.random.default_rng(6)
    lengths = rng.integers(
        8, SERVE_SPEC_PROMPT_BUCKET + 1, SERVE_SPEC_REQUESTS
    )
    budgets = rng.integers(
        SERVE_SPEC_NEW_TOKENS // 2, SERVE_SPEC_NEW_TOKENS + 1,
        SERVE_SPEC_REQUESTS,
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]

    def churn(draft):
        serve = ServeConfig(
            max_new_tokens=SERVE_SPEC_NEW_TOKENS,
            prompt_buckets=(SERVE_SPEC_PROMPT_BUCKET,),
            num_slots=SERVE_MAX_BATCH,
            chunk_tokens=SERVE_CHURN_CHUNK,
            draft=draft,
            warmup=True,
        )
        with ServingEngine(params, cfg, serve, mesh=None) as engine:
            engine.wait_ready()
            engine.submit(prompts[0]).result()  # absorb first dispatch
            warm = engine.stats()
            start = time.perf_counter()
            futures = []
            for i, prompt in enumerate(prompts):
                futures.append(
                    engine.submit(prompt, max_new_tokens=int(budgets[i]))
                )
                if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                    time.sleep(0.02)  # staggered waves, not one burst
            results = [f.result() for f in futures]
            wall = time.perf_counter() - start
            stats = engine.stats()
        tokens = sum(r.num_generated for r in results)
        delta = {
            key: stats[key] - warm[key]
            for key in ("spec_accepted", "spec_proposed", "spec_chunks")
        }
        return results, tokens / wall if wall else 0.0, delta

    nonspec_results, nonspec_rate, _ = churn(None)
    spec_results, spec_rate, spec_delta = churn(DraftConfig(
        config=draft_cfg, params=draft_params, spec_k=SERVE_SPEC_K,
    ))
    self_results, _, self_delta = churn(DraftConfig(
        config=cfg, params=params, spec_k=SERVE_SPEC_K,
    ))

    mismatches = sum(
        1 for spec_r, base_r in zip(spec_results, nonspec_results)
        if not np.array_equal(spec_r.tokens, base_r.tokens)
    ) + sum(
        1 for self_r, base_r in zip(self_results, nonspec_results)
        if not np.array_equal(self_r.tokens, base_r.tokens)
    )
    ok = mismatches == 0

    def rate(delta):
        return (
            delta["spec_accepted"] / delta["spec_proposed"]
            if delta["spec_proposed"] else 0.0
        )

    extras["serve_spec_accepted_tokens_per_sec"] = round(
        spec_rate if ok else 0.0, 1
    )
    extras["serve_spec_acceptance_rate"] = round(
        rate(spec_delta) if ok else 0.0, 3
    )
    extras["serve_spec_vs_nonspec_speedup"] = round(
        spec_rate / nonspec_rate if ok and nonspec_rate else 0.0, 3
    )
    extras["serve_spec_selfcheck_acceptance_rate"] = round(
        rate(self_delta) if ok else 0.0, 3
    )
    extras["serve_spec_nonspec_tokens_per_sec"] = round(nonspec_rate, 1)
    extras["serve_spec_parity_mismatches"] = mismatches
    extras["serve_spec_config"] = (
        f"SMALL draft{SERVE_SPEC_DRAFT_LAYERS}L k{SERVE_SPEC_K} "
        f"slots{SERVE_MAX_BATCH} bucket{SERVE_SPEC_PROMPT_BUCKET} "
        f"new<= {SERVE_SPEC_NEW_TOKENS} n{SERVE_SPEC_REQUESTS} staggered"
    )


def _measure_serving_decode_kernel(extras):
    """Paged decode-kernel probe: the churn workload through an
    ``decode_kernel="xla"`` engine (today's copy-based path) and a
    ``decode_kernel="pallas"`` engine.
    Emits ``serve_kernel_tokens_per_sec``,
    ``serve_kernel_vs_xla_speedup``, and per-arm TTFT/TPOT percentiles,
    parity-gated like ``serving_spec``: a token mismatch
    between the arms zeroes the rates rather than publishing a speedup
    for wrong tokens.
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    kernel_mode = "pallas"
    rng = np.random.default_rng(6)
    lengths = rng.integers(
        8, SERVE_PROMPT_BUCKET + 1, SERVE_CHURN_REQUESTS
    )
    budgets = rng.integers(
        SERVE_NEW_TOKENS // 4, SERVE_NEW_TOKENS + 1, SERVE_CHURN_REQUESTS
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]

    def churn(decode_kernel):
        serve = ServeConfig(
            max_new_tokens=SERVE_NEW_TOKENS,
            prompt_buckets=(SERVE_PROMPT_BUCKET // 2, SERVE_PROMPT_BUCKET),
            num_slots=SERVE_MAX_BATCH,
            chunk_tokens=SERVE_CHURN_CHUNK,
            warmup=True,
            decode_kernel=decode_kernel,
        )
        with ServingEngine(params, cfg, serve, mesh=None) as engine:
            engine.wait_ready()
            engine.submit(prompts[0]).result()  # absorb first dispatch
            start = time.perf_counter()
            futures = []
            for i, prompt in enumerate(prompts):
                futures.append(
                    engine.submit(prompt, max_new_tokens=int(budgets[i]))
                )
                if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                    time.sleep(0.02)  # staggered waves, not one burst
            results = [f.result() for f in futures]
            wall = time.perf_counter() - start
        tokens = sum(r.num_generated for r in results)
        return results, tokens / wall if wall else 0.0

    xla_results, xla_rate = churn("xla")
    kernel_results, kernel_rate = churn(kernel_mode)

    mismatches = sum(
        1 for kr, xr in zip(kernel_results, xla_results)
        if not np.array_equal(kr.tokens, xr.tokens)
        or kr.num_generated != xr.num_generated
    )
    ok = mismatches == 0

    for arm, results in (("kernel", kernel_results), ("xla", xla_results)):
        ttfts = sorted(r.ttft_seconds for r in results)
        tpots = sorted(
            (r.latency_seconds - r.ttft_seconds)
            / max(r.num_generated - 1, 1)
            for r in results
        )
        extras[f"serve_{arm}_ttft_p50_seconds"] = round(
            _latency_pct(ttfts, 0.5), 4
        )
        extras[f"serve_{arm}_ttft_p99_seconds"] = round(
            _latency_pct(ttfts, 0.99), 4
        )
        extras[f"serve_{arm}_tpot_p50_seconds"] = round(
            _latency_pct(tpots, 0.5), 5
        )
        extras[f"serve_{arm}_tpot_p99_seconds"] = round(
            _latency_pct(tpots, 0.99), 5
        )
    extras["serve_kernel_tokens_per_sec"] = round(
        kernel_rate if ok else 0.0, 1
    )
    extras["serve_kernel_vs_xla_speedup"] = round(
        kernel_rate / xla_rate if ok and xla_rate else 0.0, 3
    )
    extras["serve_kernel_xla_tokens_per_sec"] = round(xla_rate, 1)
    extras["serve_kernel_parity_mismatches"] = mismatches
    extras["serve_kernel_config"] = (
        f"SMALL decode_kernel={kernel_mode} slots{SERVE_MAX_BATCH} "
        f"chunk{SERVE_CHURN_CHUNK} new<= {SERVE_NEW_TOKENS} "
        f"n{SERVE_CHURN_REQUESTS} staggered"
    )
    if not ok:
        raise RuntimeError(
            f"decode-kernel arm failed parity: {mismatches} mismatched "
            "request(s) vs the xla arm"
        )


def _measure_serving_pipeline(extras):
    """Pipelined-scheduling probe: the churn workload through a
    ``pipeline_depth=1`` engine (today's lockstep dispatch->sync loop)
    and a ``pipeline_depth=2`` engine (second chunk in flight while the
    host drains the first).  Emits ``serve_pipeline_tokens_per_sec``,
    ``serve_pipeline_vs_depth1_speedup``, and per-arm dispatch-gap
    p50/p99 (from ``engine.stats()`` — the host-side gap between
    consecutive chunk dispatches, the latency the pipeline exists to
    hide), parity-gated like ``serving_decode_kernel``: a token
    mismatch between the arms zeroes the rates rather than publishing
    a speedup for wrong tokens.
    """
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    rng = np.random.default_rng(11)
    lengths = rng.integers(
        8, SERVE_PROMPT_BUCKET + 1, SERVE_CHURN_REQUESTS
    )
    budgets = rng.integers(
        SERVE_NEW_TOKENS // 4, SERVE_NEW_TOKENS + 1, SERVE_CHURN_REQUESTS
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]

    def churn(depth):
        serve = ServeConfig(
            max_new_tokens=SERVE_NEW_TOKENS,
            prompt_buckets=(SERVE_PROMPT_BUCKET // 2, SERVE_PROMPT_BUCKET),
            num_slots=SERVE_MAX_BATCH,
            chunk_tokens=SERVE_CHURN_CHUNK,
            warmup=True,
            pipeline_depth=depth,
        )
        with ServingEngine(params, cfg, serve, mesh=None) as engine:
            engine.wait_ready()
            engine.submit(prompts[0]).result()  # absorb first dispatch
            start = time.perf_counter()
            futures = []
            for i, prompt in enumerate(prompts):
                futures.append(
                    engine.submit(prompt, max_new_tokens=int(budgets[i]))
                )
                if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                    time.sleep(0.02)  # staggered waves, not one burst
            results = [f.result() for f in futures]
            wall = time.perf_counter() - start
            stats = engine.stats()
        return results, tokens_rate(results, wall), stats

    def tokens_rate(results, wall):
        tokens = sum(r.num_generated for r in results)
        return tokens / wall if wall else 0.0

    d1_results, d1_rate, d1_stats = churn(1)
    d2_results, d2_rate, d2_stats = churn(2)

    mismatches = sum(
        1 for a, b in zip(d2_results, d1_results)
        if not np.array_equal(a.tokens, b.tokens)
        or a.num_generated != b.num_generated
    )
    ok = mismatches == 0

    for arm, stats in (("depth1", d1_stats), ("depth2", d2_stats)):
        extras[f"serve_pipeline_{arm}_gap_p50_ms"] = round(
            stats.get("dispatch_gap_ms_p50", 0.0), 3
        )
        extras[f"serve_pipeline_{arm}_gap_p99_ms"] = round(
            stats.get("dispatch_gap_ms_p99", 0.0), 3
        )
    extras["serve_pipeline_tokens_per_sec"] = round(
        d2_rate if ok else 0.0, 1
    )
    extras["serve_pipeline_vs_depth1_speedup"] = round(
        d2_rate / d1_rate if ok and d1_rate else 0.0, 3
    )
    extras["serve_pipeline_depth1_tokens_per_sec"] = round(d1_rate, 1)
    extras["serve_pipeline_parity_mismatches"] = mismatches
    extras["serve_pipeline_config"] = (
        f"SMALL pipeline_depth=2 slots{SERVE_MAX_BATCH} "
        f"chunk{SERVE_CHURN_CHUNK} new<= {SERVE_NEW_TOKENS} "
        f"n{SERVE_CHURN_REQUESTS} staggered"
    )
    if not ok:
        raise RuntimeError(
            f"pipelined arm failed parity: {mismatches} mismatched "
            "request(s) vs the depth-1 arm"
        )


def _measure_fleet(extras):
    """Fleet probe: the churn workload (staggered arrivals, mixed prompt
    AND output lengths) through ``cloud_tpu.fleet.Fleet`` fronting
    ``FLEET_REPLICAS`` serving engines.  Emits fleet tokens/sec and
    latency percentiles — measured at the FLEET submit surface, so they
    include routing — plus the failover count (0 in a healthy run; the
    chaos coverage lives in scripts/check_fleet.py).
    """
    from cloud_tpu.fleet import Fleet, FleetConfig
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=SERVE_MAX_BATCH, prompt_len=SERVE_PROMPT_BUCKET
    )
    serve = ServeConfig(
        max_new_tokens=SERVE_NEW_TOKENS,
        prompt_buckets=(SERVE_PROMPT_BUCKET // 2, SERVE_PROMPT_BUCKET),
        batch_buckets=(1, SERVE_MAX_BATCH),
        num_slots=SERVE_MAX_BATCH,
        chunk_tokens=SERVE_CHURN_CHUNK,
        warmup=True,
        admission="reject",  # fleet backstop: full replicas fail over
    )

    def factory():
        return ServingEngine(params, cfg, serve, mesh=None)

    rng = np.random.default_rng(2)
    lengths = rng.integers(
        8, SERVE_PROMPT_BUCKET + 1, SERVE_CHURN_REQUESTS
    )
    budgets = rng.integers(
        SERVE_NEW_TOKENS // 4, SERVE_NEW_TOKENS + 1, SERVE_CHURN_REQUESTS
    )
    prompts = [
        rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in lengths
    ]
    fleet_config = FleetConfig(
        min_replicas=FLEET_REPLICAS, max_replicas=FLEET_REPLICAS,
        poll_interval_s=0.1,
    )
    with Fleet(factory, fleet_config) as fleet:
        fleet.wait_ready()
        fleet.submit(prompts[0]).result()  # absorb residual first-dispatch
        start = time.perf_counter()
        futures = []
        for i, prompt in enumerate(prompts):
            futures.append(
                fleet.submit(prompt, max_new_tokens=int(budgets[i]))
            )
            if (i + 1) % (SERVE_MAX_BATCH // 2) == 0:
                time.sleep(0.02)  # staggered waves, not one burst
        results = [f.result() for f in futures]
        wall = time.perf_counter() - start
        stats = fleet.stats()
    latencies = sorted(r.latency_seconds for r in results)
    total_tokens = sum(r.num_generated for r in results)
    extras["fleet_tokens_per_sec"] = round(total_tokens / wall, 1)
    extras["fleet_p50_latency_seconds"] = round(_latency_pct(latencies, 0.5), 4)
    extras["fleet_p99_latency_seconds"] = round(_latency_pct(latencies, 0.99), 4)
    extras["fleet_failover_count"] = stats["failovers"]
    _emit_ttft_decomposition(extras, "fleet", results)
    extras["fleet_config"] = (
        f"SMALL replicas{FLEET_REPLICAS} slots{SERVE_MAX_BATCH} "
        f"chunk{SERVE_CHURN_CHUNK} new<= {SERVE_NEW_TOKENS} "
        f"n{SERVE_CHURN_REQUESTS} staggered"
    )


def _emit_ttft_decomposition(extras, key, results, *, gate=False):
    """Trace-derived TTFT attribution for a fleet probe's requests.

    The bench child runs with tracing enabled, so every fleet
    submission carried a trace context; stitching THIS probe's trace
    ids (from ``ServeResult.trace_id``) out of the live ring buffer
    yields the queue / route / swap-in / prefill / first-decode shares
    of fleet TTFT at p99 — the distributional view a raw percentile
    hides (a regression that moves time between phases at equal TTFT
    still shows here).  With ``gate=True`` an incomplete lifecycle
    (a traced request missing its ``fleet/route`` or terminal
    ``serve/request`` span) raises, failing the phase: the probe
    promises every request stitches end to end.
    """
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.monitoring.report import TraceReport

    trace_ids = {r.trace_id for r in results if r.trace_id}
    if not trace_ids:
        return
    report = TraceReport(tracing.timeline_events())
    summary = report.request_summary() or {}
    mine = {t: summary[t] for t in trace_ids if t in summary}
    if gate:
        incomplete = sorted(
            t for t in trace_ids
            if not mine.get(t, {}).get("complete")
            or not mine.get(t, {}).get("routes")
        )
        if incomplete:
            raise RuntimeError(
                f"{key}: {len(incomplete)}/{len(trace_ids)} traced "
                "requests did not stitch a complete lifecycle "
                f"(first: {incomplete[0]})"
            )
    decomposition = report.ttft_decomposition(mine)
    if not decomposition:
        return
    for name in TraceReport.TTFT_COMPONENTS:
        extras[f"{key}_ttft_{name}_share_p99"] = round(
            decomposition["shares"][name]["p99"], 4
        )
    extras[f"{key}_ttft_traced_p99_seconds"] = round(
        decomposition["ttft_p99_s"], 4
    )


def _measure_fleet_qps_sweep(extras):
    """Open-loop arrival sweep at the fleet surface: tokens/sec and
    TTFT/TPOT percentiles vs OFFERED load (constants block above).

    Two passes per offered-QPS point over one 2-replica QoS fleet:
    requests alternate interactive/batch classes, arrivals follow the
    wall clock (a late submission does not push later ones — open
    loop), and every request's TTFT is the fleet-surface number (fleet
    queueing + routing + engine queue + prefill).  Emits per-point
    aggregates plus per-class TTFT p99, so a round artifact carries a
    small latency-under-load curve instead of one point.
    """
    from cloud_tpu.fleet import Fleet, FleetConfig
    from cloud_tpu.serving import QosConfig, ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=FLEET_SWEEP_SLOTS, prompt_len=FLEET_SWEEP_PROMPT_LEN
    )
    serve = ServeConfig(
        max_new_tokens=FLEET_SWEEP_NEW_TOKENS,
        prompt_buckets=(FLEET_SWEEP_PROMPT_LEN,),
        batch_buckets=(1, FLEET_SWEEP_SLOTS),
        num_slots=FLEET_SWEEP_SLOTS,
        chunk_tokens=SERVE_CHURN_CHUNK,
        warmup=True,
        qos=QosConfig(),
    )

    def factory():
        return ServingEngine(params, cfg, serve, mesh=None)

    rng = np.random.default_rng(3)
    sweep_results = []
    with Fleet(factory, FleetConfig(
        min_replicas=FLEET_REPLICAS, max_replicas=FLEET_REPLICAS,
        poll_interval_s=0.1, qos=QosConfig(),
    )) as fleet:
        fleet.wait_ready()
        fleet.submit(
            rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
            max_new_tokens=2,
        ).result()  # absorb residual first-dispatch latency
        for qps in FLEET_SWEEP_QPS:
            prompts = [
                rng.integers(
                    1, cfg.vocab_size, FLEET_SWEEP_PROMPT_LEN
                ).astype(np.int32)
                for _ in range(FLEET_SWEEP_REQUESTS)
            ]
            classes = [
                "interactive" if i % 2 == 0 else "batch"
                for i in range(FLEET_SWEEP_REQUESTS)
            ]
            interval = 1.0 / qps
            start = time.perf_counter()
            futures = []
            for i, prompt in enumerate(prompts):
                # Open loop: arrivals track the wall clock, not the
                # fleet's progress.
                target = start + i * interval
                delay = target - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                futures.append(fleet.submit(
                    prompt, max_new_tokens=FLEET_SWEEP_NEW_TOKENS,
                    priority=classes[i],
                ))
            results = [f.result() for f in futures]
            wall = time.perf_counter() - start

            ttfts = sorted(r.ttft_seconds for r in results)
            tpots = sorted(
                (r.latency_seconds - r.ttft_seconds)
                / max(r.num_generated - 1, 1)
                for r in results
            )
            total_tokens = sum(r.num_generated for r in results)
            key = f"fleet_sweep_q{qps}"
            extras[f"{key}_tokens_per_sec"] = round(
                total_tokens / wall, 1
            )
            extras[f"{key}_ttft_p50_seconds"] = round(
                _latency_pct(ttfts, 0.5), 4
            )
            extras[f"{key}_ttft_p99_seconds"] = round(
                _latency_pct(ttfts, 0.99), 4
            )
            extras[f"{key}_tpot_p50_seconds"] = round(
                _latency_pct(tpots, 0.5), 5
            )
            extras[f"{key}_tpot_p99_seconds"] = round(
                _latency_pct(tpots, 0.99), 5
            )
            for name in ("interactive", "batch"):
                class_ttfts = sorted(
                    r.ttft_seconds
                    for r, c in zip(results, classes) if c == name
                )
                extras[f"{key}_{name}_ttft_p99_seconds"] = round(
                    _latency_pct(class_ttfts, 0.99), 4
                )
            sweep_results.extend(results)
    # Trace-completeness gate over the WHOLE sweep: every traced
    # request must stitch a full routed lifecycle, and the shares of
    # the sweep's fleet TTFT ride the artifact next to the raw
    # percentiles above.
    _emit_ttft_decomposition(
        extras, "fleet_sweep", sweep_results, gate=True
    )
    extras["fleet_sweep_config"] = (
        f"SMALL replicas{FLEET_REPLICAS} open-loop "
        f"qps{list(FLEET_SWEEP_QPS)} n{FLEET_SWEEP_REQUESTS}/point "
        f"prompt{FLEET_SWEEP_PROMPT_LEN} new{FLEET_SWEEP_NEW_TOKENS} "
        "classes interactive/batch alternating, QoS armed"
    )


def _measure_fleet_disagg(extras):
    """Disaggregated serving probe: one long-prompt flash crowd through
    a colocated 3-replica fleet, then through the same replica count
    split 1 prefill / 2 decode (``FleetConfig.roles``) with KV block
    handoff riding the shared host-DRAM prefix pool.  Emits per-arm
    TTFT/TPOT p50/p99 and tokens/sec plus the disagg arm's handoff /
    dedup counters, and GATES on cross-arm token identity — the probe
    re-proves the handoff path bit-exact every round, not just in the
    unit suite.  (Chaos coverage — mid-flood replica kills — lives in
    scripts/check_fleet.py phase 5; this probe measures the healthy
    steady state.)
    """
    from cloud_tpu.fleet import Fleet, FleetConfig
    from cloud_tpu.serving import ServeConfig, ServingEngine
    from cloud_tpu.utils.benchmarking import decode_setup

    import numpy as np

    cfg, params, _, _ = decode_setup(
        batch_size=2, prompt_len=DISAGG_PROMPT_BUCKET
    )
    serve = ServeConfig(
        max_new_tokens=DISAGG_NEW_TOKENS,
        prompt_buckets=(DISAGG_PROMPT_BUCKET,),
        batch_buckets=(1, 2),
        num_slots=2,
        chunk_tokens=SERVE_CHURN_CHUNK,
        prefix_cache_blocks=96,
        prefix_block_tokens=8,
        prefill_chunk_tokens=32,
        warmup=True,
    )

    def factory():
        return ServingEngine(params, cfg, serve, mesh=None)

    rng = np.random.default_rng(19)
    head = rng.integers(1, cfg.vocab_size, DISAGG_SHARED_HEAD)
    prompts = [
        np.concatenate([
            head,
            rng.integers(
                1, cfg.vocab_size, DISAGG_PROMPT_LEN - DISAGG_SHARED_HEAD
            ),
        ]).astype(np.int32)
        for _ in range(DISAGG_REQUESTS)
    ]

    reference = None
    for arm, roles in (
        ("colocated", None),
        ("disagg", ("prefill", "decode", "decode")),
    ):
        with Fleet(factory, FleetConfig(
            min_replicas=DISAGG_REPLICAS, max_replicas=DISAGG_REPLICAS,
            poll_interval_s=0.1, roles=roles,
        )) as fleet:
            fleet.wait_ready()
            # Absorb residual first-dispatch latency (and, in the disagg
            # arm, the first prefill->decode leg pair) outside the clock.
            fleet.submit(
                rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=2,
            ).result()
            start = time.perf_counter()
            # Flash crowd: one burst, no staggering — the arm contrast
            # IS how each topology absorbs simultaneous long prefills.
            futures = [
                fleet.submit(p, max_new_tokens=DISAGG_NEW_TOKENS)
                for p in prompts
            ]
            results = [f.result() for f in futures]
            wall = time.perf_counter() - start
            stats = fleet.stats()

        tokens = [tuple(int(t) for t in r.tokens) for r in results]
        if reference is None:
            reference = tokens
        elif tokens != reference:
            diverged = sum(a != b for a, b in zip(tokens, reference))
            raise RuntimeError(
                f"fleet_disagg: {diverged}/{len(tokens)} requests "
                "decoded different tokens in the disagg arm"
            )
        ttfts = sorted(r.ttft_seconds for r in results)
        tpots = sorted(
            (r.latency_seconds - r.ttft_seconds)
            / max(r.num_generated - 1, 1)
            for r in results
        )
        total_tokens = sum(r.num_generated for r in results)
        key = f"fleet_disagg_{arm}"
        extras[f"{key}_tokens_per_sec"] = round(total_tokens / wall, 1)
        extras[f"{key}_ttft_p50_seconds"] = round(
            _latency_pct(ttfts, 0.5), 4
        )
        extras[f"{key}_ttft_p99_seconds"] = round(
            _latency_pct(ttfts, 0.99), 4
        )
        extras[f"{key}_tpot_p50_seconds"] = round(
            _latency_pct(tpots, 0.5), 5
        )
        extras[f"{key}_tpot_p99_seconds"] = round(
            _latency_pct(tpots, 0.99), 5
        )
        extras[f"{key}_handoffs"] = stats["handoffs"]
        extras[f"{key}_handoff_failovers"] = stats["handoff_failovers"]
        if roles is not None:
            extras["fleet_disagg_host_pool_puts"] = (
                stats["host_pool"]["puts"]
            )
            extras["fleet_disagg_host_pool_dedup_hits"] = (
                stats["host_pool"]["dedup_hits"]
            )
    extras["fleet_disagg_config"] = (
        f"SMALL replicas{DISAGG_REPLICAS} colocated vs "
        "prefill1/decode2 flash-crowd "
        f"n{DISAGG_REQUESTS} prompt{DISAGG_PROMPT_LEN} "
        f"head{DISAGG_SHARED_HEAD} new{DISAGG_NEW_TOKENS} "
        "token-identity gated"
    )


def _measure_durability(extras):
    """Durability probe on the CIFAR workload (the headline's state):

    ``checkpoint_save_blocking_seconds`` — the blocking half of the
    async checkpoint save (host gather + handoff + previous-save wait +
    manifest commit), which is exactly what a training step pays at a
    save boundary; and ``resume_restore_seconds`` — the wall-clock of a
    verified walk-back restore into a fresh state, what a preempted
    node pays before its first resumed step.
    """
    import shutil
    import tempfile
    import types

    from cloud_tpu.training.checkpoint import (
        CheckpointManager,
        resume_trainer_state,
    )
    from cloud_tpu.utils.benchmarking import resnet_train_setup

    _, state, _ = resnet_train_setup(
        imagenet_shape=False, batch_size=BATCH_SIZE
    )
    tmp = tempfile.mkdtemp(prefix="cloud_tpu_bench_ckpt_")
    try:
        manager = CheckpointManager(tmp, max_to_keep=2)
        # Save 1 primes the pipeline; save 2 is the steady-state number:
        # it waits out save 1's async tail, commits save 1's manifest
        # (the full-lineage hash), and hands off its own write — the
        # whole stall a training step pays at a save boundary.
        manager.save(1, state)
        start = time.perf_counter()
        manager.save(2, state)
        extras["checkpoint_save_blocking_seconds"] = round(
            time.perf_counter() - start, 4
        )
        manager.wait()  # save 2's async tail + manifest, off the step path
        manager.close()

        holder = types.SimpleNamespace(state=state)
        restore_manager = CheckpointManager(tmp)
        start = time.perf_counter()
        # quarantine=False: a measurement probe must be read-only.
        ok = resume_trainer_state(holder, restore_manager,
                                  only_if_ahead=False, quarantine=False)
        extras["resume_restore_seconds"] = round(
            time.perf_counter() - start, 4
        )
        restore_manager.close()
        if not ok:
            raise RuntimeError("durability probe could not restore the "
                               "checkpoint it just wrote")
        extras["durability_config"] = (
            "resnet50_cifar state, async save + verified walk-back restore"
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _device_stamp():
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def main() -> int:
    """Headline first; every phase prints its own JSON line as it ends and
    the first phase that fails ends the run (the exception propagates)."""
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.training import compile_cache

    device = _device_stamp()
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "error": "bench.py measures a TPU; none found"}),
              flush=True)
        return 1
    # JAX_COMPILATION_CACHE_DIR where it is set, else one fixed path in
    # the checkout (the directory is part of the cache key).
    compile_cache.maybe_enable_persistent_cache(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".jax_cache")
    )
    # Span tracing on for the whole run: compile vs measure wall-clock
    # lands in the output (span_aggregates below).
    tracing.enable()
    _emit_phase("env", ok=True, extras={"device": device})
    extras = {}
    # Phase 1: the headline.  Nothing runs before this.
    value = _measure_resnet(extras)
    # Phase 2: GroupNorm correctness gate for the kernel the headline ran.
    gn_extras = {}
    _check_group_norm(gn_extras)
    _emit_phase("group_norm", ok=True, extras=gn_extras)
    extras.update(gn_extras)

    # Phase 3+: context.  The fused measurement runs first: it reuses the
    # headline's workload (cheapest compile delta).
    for fn, tag in (
        (_measure_fused, "fused"),
        (_check_flash_attention, "flash_attention"),
        (_measure_bert, "bert"),
        (_measure_resnet224, "resnet224"),
        (_measure_decode, "decode"),
        (_measure_serving, "serving"),
        (_measure_serving_churn, "serving_churn"),
        (_measure_serving_prefix, "serving_prefix"),
        (_measure_serving_prefix_tier, "serving_prefix_tier"),
        (_measure_serving_spec, "serving_spec"),
        (_measure_serving_decode_kernel, "serving_decode_kernel"),
        (_measure_serving_pipeline, "serving_pipeline"),
        (_measure_fleet, "fleet"),
        (_measure_fleet_qps_sweep, "fleet_qps_sweep"),
        (_measure_fleet_disagg, "fleet_disagg"),
        (_measure_durability, "durability"),
    ):
        phase_extras = {"peak_bf16_tflops": extras.get("peak_bf16_tflops")}
        fn(phase_extras)
        phase_extras.pop("peak_bf16_tflops", None)
        _emit_phase(tag, ok=True, extras=phase_extras)
        extras.update(phase_extras)

    # Phase-latency aggregates for everything spanned above
    # (bench/compile, bench/measure, plus any framework spans).  Rounded —
    # these are attribution context, not the measurement.
    spans = {
        name: {
            "count": agg["count"],
            "total_s": round(agg["total_seconds"], 3),
            "mean_s": round(agg["mean_seconds"], 4),
            "max_s": round(agg["max_seconds"], 4),
        }
        for name, agg in sorted(tracing.aggregates().items())
    }
    _emit_phase("spans", ok=True, extras={"span_aggregates": spans})
    print(json.dumps({
        "metric": METRIC, "value": round(value, 3),
        "unit": "steps/sec/chip", "device": device, **extras,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
