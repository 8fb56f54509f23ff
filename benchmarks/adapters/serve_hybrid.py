"""``entry: serve_hybrid`` — a hybrid attention + state-space decoder
(Falcon-H1's block) through ``ServingEngine.submit``, as a caller drives
one replica.

``serve_engine.run`` hard-codes ``TransformerConfig``'s seven arguments and
``harness.work``'s counts, so this configuration restates three things and
imports the rest from it: ``model_config`` (grouped K/V heads, the mixer's
sizes, the multipliers), ``_traced_work`` (``harness.work_hybrid``, with
the bytes one chunk program must move) and ``run``, which is
``serve_engine.run`` line for line but for those two names.  A
``benchmark`` PR folds the two adapters into one whose model builder and
work counts the configuration names (ROADMAP, benchmark queue).
"""

import gc
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.adapters.serve_engine import (  # noqa: F401
    GRACE_S, _Request, _answers_wrong, _check_sample, _logit_checks,
    _numeric_delta, _offer, _trace_window)
from benchmarks.harness import context, stats, traffic, work_hybrid, xplane
from cloud_tpu.models import ssm, transformer
from cloud_tpu.serving import ServeConfig, ServingEngine


def model_config(sizes, mix):
    engine = mix["engine"]
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]
    gate, down = sizes["mlp_multipliers"]
    return transformer.TransformerConfig(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        dim=sizes["hidden_size"], num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], mlp_hidden=sizes["intermediate_size"],
        max_seq_len=max(sizes["max_position_embeddings"], rows),
        rope_base=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"], dtype=jnp.bfloat16,
        ssm=ssm.SsmConfig(
            num_heads=sizes["mamba_n_heads"],
            head_dim=sizes["mamba_d_head"], state_dim=sizes["mamba_d_state"],
            num_groups=sizes["mamba_n_groups"],
            conv_width=sizes["mamba_d_conv"],
            chunk_size=sizes["mamba_chunk_size"]),
        multipliers=transformer.Multipliers(
            embedding=float(sizes["embedding_multiplier"]),
            attention_in=float(sizes["attention_in_multiplier"]),
            attention_out=sizes["attention_out_multiplier"],
            key=sizes["key_multiplier"],
            ssm_in=sizes["ssm_in_multiplier"],
            ssm_out=sizes["ssm_out_multiplier"],
            ssm=tuple(sizes["ssm_multipliers"]),
            mlp_gate=gate, mlp_down=down,
            lm_head=sizes["lm_head_multiplier"]))


def _traced_work(sizes, settings, requests, traced, delta):
    """Operations of what was prefilled and decoded inside the traced
    window, from each token's arrival time, and what ONE execution of the
    chunk program must do (``harness.work_hybrid``): its steps' bytes at
    the window's mean of live slots and of K/V rows holding a token (the
    engine's counters, one count a chunk dispatch), its operations those
    of a step of that many slots at the mean context."""
    lo, hi = traced
    out = {"serve_flops": 0, "prompt_ktok": 0.0}
    for r in requests:
        n = len(r.prompt)
        for (index, _), when in zip(r.tokens, r.times):
            if not lo <= when <= hi:
                continue
            if index == 0:
                out["serve_flops"] += work_hybrid.prefill_flops(sizes, n)
                out["prompt_ktok"] += n / 1000.0
            else:
                out["serve_flops"] += work_hybrid.decode_flops(
                    sizes, n + index - 1)
    chunks = delta.get("chunks", 0)
    state_rows = delta.get("state_row_steps_in_use", 0)
    if chunks and state_rows:
        steps = settings["chunk_tokens"]
        live = state_rows / sizes["num_hidden_layers"] / chunks
        rows = delta["kv_row_steps_in_use"] / chunks
        context_len = int(rows / live)
        out["decode_chunk"] = {
            "flops": steps * live * work_hybrid.decode_flops(
                sizes, context_len),
            "bytes": steps * work_hybrid.decode_step_bytes(
                sizes, live, rows)}
    return out


def run(run):
    sizes, mix = run.cell.config, run.cell.traffic
    reference = importlib.import_module(
        f"benchmarks.references.{sizes['reference']}")
    settings = mix["engine"]
    buckets = tuple(settings["prompt_buckets"])
    config = model_config(sizes, mix)
    backlog = mix["arrivals"]["process"] == "backlog"
    # A traced run measures as long as any other (its tails and counters
    # are over the whole window), with the profiler on for a part of it.
    seconds = max(run.seconds, sum(mix["trace_window_s"])) if run.trace \
        else run.seconds
    compiles = context.CompileCounter.get()

    params = jax.block_until_ready(reference.make_params(run.seed, sizes))
    run.say(f"weights made {time.perf_counter() - run.process_start:.1f}s "
            "after the start")
    engine = ServingEngine(
        params, config,
        ServeConfig(**{**settings, "prompt_buckets": buckets}, warmup=True),
        mesh=None)
    engine.wait_ready()
    # Every program this cell's traffic uses runs once before the window:
    # one full-length prompt per bucket, decoded through a chunk or two.
    rng = np.random.default_rng([int(run.seed), 3])
    warm = [engine.submit(
        rng.integers(1, sizes["vocab_size"], bucket, dtype=np.int32),
        max_new_tokens=min(settings["max_new_tokens"],
                           settings["chunk_tokens"] + 2))
        for bucket in buckets]
    for future in warm:
        future.result(timeout=600)
    run.say(f"engine warm {time.perf_counter() - run.process_start:.1f}s "
            "after the start")

    requests = [_Request(spec) for spec in traffic.make_requests(
        mix, seconds, run.seed, sizes["vocab_size"])]
    stop, traced = threading.Event(), []
    watch = context.HostWatch().start()
    before = engine.stats()
    start = time.perf_counter()
    generator = threading.Thread(
        target=_offer, args=(engine, requests, start, stop),
        name="bench-load-generator")
    generator.start()
    tracer = None
    if run.trace:
        tracer = threading.Thread(target=_trace_window,
                                  args=(run, start, traced),
                                  name="bench-tracer")
        tracer.start()
    time.sleep(max(0.0, start + seconds - time.perf_counter()))
    end = time.perf_counter()
    after = engine.stats()
    watch.stop()
    if backlog:
        # Tokens per second is over what the window finished; the rest
        # of the backlog is dropped with the engine.
        stop.set()
        engine.close(drain=False)
    generator.join()
    offered = [r for r in requests if r.submitted is not None]
    failed = 0
    if not backlog:
        for r in offered:
            try:
                r.future.result(
                    timeout=max(0.1, end + GRACE_S - time.perf_counter()))
            except Exception as exc:  # noqa: BLE001 — counted, not hidden
                run.say(f"request failed: {exc!r}")
                failed += 1
        engine.close()
    if tracer is not None:
        tracer.join()
        xplane.stop_trace()
    window_s = end - start
    late = [r.submitted - (start + r.due_s) for r in offered]
    run.say(f"window {window_s:.3f}s; {len(offered)} of {len(requests)} "
            f"requests offered; generator late by p95 "
            f"{stats.percentile(late, 95) * 1e3:.2f} ms, max "
            f"{max(late) * 1e3:.2f} ms; compilations inside the window: "
            f"{compiles.between(start, end)}; persistent cache so far: "
            f"{compiles.cache}; {watch}")

    finished = [r for r in offered if r.done_at() is not None
                and (not backlog or r.done_at() <= end)]
    observed = {}
    if backlog:
        tokens = sum(len(r.prompt) + r.max_new_tokens for r in finished)
        end_to_end = {"serve_tokens_per_s": stats.rate(tokens, window_s)}
        attempted = len(finished)
    else:
        ttft = [(r.times[0] - (start + r.due_s)) * 1e3 for r in finished]
        tpot = [(r.times[-1] - r.times[0]) * 1e3 / (r.max_new_tokens - 1)
                for r in finished if r.max_new_tokens > 1]
        missing = len(offered) - len(finished)
        # A backlog that grows shows as a TTFT that grows through the
        # window: the sweep for the sustained rate reads this line.
        thirds = [[t for t, r in zip(ttft, finished)
                   if k <= 3 * r.due_s / seconds < k + 1]
                  for k in range(3)]
        run.say("ttft p50 by thirds of the window (ms): "
                + ", ".join(f"{stats.percentile(t, 50):.0f}" if t else "-"
                            for t in thirds)
                + f"; unfinished at the close: "
                f"{sum(1 for r in offered if (r.done_at() or end + 1) > end)}"
                f" of {len(offered)}")
        latency = [(r.times[-1] - (start + r.due_s)) * 1e3 for r in finished]
        end_to_end = {
            "latency_p95_ms": stats.tail_with_failures(latency, missing),
            "tpot_p95_ms": stats.tail_with_failures(tpot, missing)}
        observed = {
            "bench/ttft_p50_ms": stats.tail_with_failures(ttft, missing, 50),
            "bench/ttft_p95_ms": stats.tail_with_failures(ttft, missing)}
        run.say("ttft p50 %.1f p95 %.1f mean %.1f; latency p50 %.1f p95 "
                "%.1f; tpot p50 %.2f p95 %.2f (ms)" % (
                    observed["bench/ttft_p50_ms"],
                    observed["bench/ttft_p95_ms"], sum(ttft) / len(ttft),
                    stats.percentile(latency, 50),
                    end_to_end["latency_p95_ms"],
                    stats.percentile(tpot, 50), end_to_end["tpot_p95_ms"]))
        attempted, failed = len(requests), max(failed, missing)

    wrong = _answers_wrong(finished)
    spans = context.program_spans()
    peak = context.memory_peak_bytes()
    # The engine's counters over the window, and what the benchmark
    # itself observed of the requests (``bench/...``).
    delta = {**_numeric_delta(after, before), **observed}
    work_done = (_traced_work(sizes, settings, offered, traced[0], delta)
                 if traced else {})

    run.say(f"memory_stats: {jax.local_devices()[0].memory_stats()}")
    # Free the program's state: the engine's jitted closures hold the
    # engine, and JAX's caches hold them, so drop what it owns by hand.
    vars(engine).clear()
    del engine, params, warm
    for r in requests:
        r.future = None
    gc.collect()
    jax.clear_caches()
    run.say(f"freed: {sum(x.nbytes for x in jax.live_arrays())} bytes of "
            "arrays still live")
    checks = [("answers_wrong", wrong, mix["limits"]["answers_wrong"])]
    control_checks = []
    if run.check:
        # The reference runs once the window has closed, the peak is read
        # and the engine with its weights and cache is freed.
        logit_checks, control_checks = _logit_checks(run, reference,
                                                     finished)
        checks += logit_checks
    return context.Outcome(
        window_start=start, window_s=window_s, end_to_end=end_to_end,
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=peak, spans=spans, stats=delta, work=work_done,
        traced=traced[0] if traced else None, control_checks=control_checks)
