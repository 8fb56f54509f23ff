"""``entry: serve_engine`` — a decoder through ``ServingEngine.submit``, as
a caller drives one replica.

Weights come from the seed in one jitted call (the reference's own
``make_params``), the engine warms this cell's buckets and its chunk
program (set-up), then one thread offers the mix's requests at their due
times for the window.  Each request is timed from when it was DUE, through
``submit(on_token=...)``.
"""

import gc
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, context, stats, traffic, work, xplane
from cloud_tpu.models import transformer
from cloud_tpu.serving import ServeConfig, ServingEngine

#: How long past the window's close an answer is waited for.
GRACE_S = 60.0


def model_config(sizes, mix):
    engine = mix["engine"]
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]
    return transformer.TransformerConfig(
        vocab_size=sizes["vocab_size"], num_layers=sizes["num_hidden_layers"],
        dim=sizes["hidden_size"], num_heads=sizes["num_attention_heads"],
        head_dim=sizes["head_dim"], mlp_hidden=sizes["intermediate_size"],
        max_seq_len=max(sizes["max_position_embeddings"], rows),
        rope_base=sizes.get("rope_base", 10000.0), dtype=jnp.bfloat16)


class _Request:
    """One request of the run and what came back for it."""

    def __init__(self, spec):
        self.prompt = spec["prompt"]
        self.max_new_tokens = spec["max_new_tokens"]
        self.due_s = spec["due_s"]
        self.submitted = None
        self.future = None
        self.tokens, self.times = [], []

    def on_token(self, index, token):
        self.tokens.append((index, int(token)))
        self.times.append(time.perf_counter())

    def done_at(self):
        return self.times[-1] if len(self.times) == self.max_new_tokens \
            else None


def _offer(engine, requests, start, stop):
    """The load generator: submits each request when it is due (a full
    queue blocks it, as admission="block" says), until ``stop`` is set."""
    for request in requests:
        delay = start + request.due_s - time.perf_counter()
        if delay > 0 and stop.wait(delay):
            return
        if stop.is_set():
            return
        request.submitted = time.perf_counter()
        try:
            request.future = engine.submit(
                request.prompt, max_new_tokens=request.max_new_tokens,
                on_token=request.on_token)
        except Exception:  # noqa: BLE001 — closed under a blocked submit
            if not stop.is_set():
                raise
            request.submitted = None
            return


def _trace_window(run, start, holder):
    """The profiler starts a second ahead of the traced window (the first
    ops after a start are not recorded); the caller stops it."""
    after, length = run.cell.traffic["trace_window_s"]
    time.sleep(max(0.0, start + after - 1.0 - time.perf_counter()))
    xplane.start_trace(run.trace_dir)
    time.sleep(max(0.0, start + after - time.perf_counter()))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION):
        begun = time.perf_counter()
        time.sleep(length)
        ended = time.perf_counter()
    holder.append((begun, ended))


def _numeric_delta(after, before):
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and not isinstance(after[k], bool) and k in before}


def _traced_work(sizes, buckets, requests, traced):
    """Operations of what was prefilled and decoded inside the traced
    window, from each token's arrival time (``harness.work``)."""
    lo, hi = traced
    out = {"serve_flops": 0, "prompt_ktok": 0.0,
           "flash_fwd": {"flops": 0, "bytes": 0}}
    layers = sizes["num_hidden_layers"]
    for r in requests:
        n = len(r.prompt)
        for (index, _), when in zip(r.tokens, r.times):
            if not lo <= when <= hi:
                continue
            if index == 0:
                out["serve_flops"] += work.prefill_flops(sizes, n)
                out["prompt_ktok"] += n / 1000.0
                bucket = next(b for b in buckets if b >= n)
                flops, moved = work.flash_forward_call(sizes, bucket)
                out["flash_fwd"]["flops"] += layers * flops
                out["flash_fwd"]["bytes"] += layers * moved
            else:
                out["serve_flops"] += work.decode_flops(sizes, n + index - 1)
    return out


def _check_sample(finished, count, rng):
    """``count`` of the finished requests, drawn from the seed, the
    longest among them."""
    longest = max(finished, key=lambda r: len(r.prompt) + r.max_new_tokens)
    others = [r for r in finished if r is not longest]
    picked = rng.permutation(len(others))[:count - 1]
    return [longest] + [others[i] for i in picked]


def _answers_wrong(finished):
    """Bookkeeping: every answer once, in order, with its own length, and
    the result's row the tokens that were streamed."""
    wrong = 0
    for r in finished:
        streamed = [t for _, t in r.tokens]
        ok = [i for i, _ in r.tokens] == list(range(r.max_new_tokens))
        if ok and r.future is not None and r.future.done() \
                and r.future.exception() is None:
            result = r.future.result()
            ok = (result.num_generated == r.max_new_tokens
                  and list(result.tokens[:r.max_new_tokens]) == streamed)
        wrong += not ok
    return wrong


def _logit_checks(run, reference, finished):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of the finished requests; with
    ``run.control`` also the control's (the token that fp8 puts first)."""
    sizes, mix = run.cell.config, run.cell.traffic
    settings, limit = mix["engine"], mix["limits"]["logit_gap"]
    sample = _check_sample(finished, mix["check_requests"],
                           np.random.default_rng([int(run.seed), 4]))
    width = settings["prompt_buckets"][-1] + settings["max_new_tokens"]
    tokens = np.zeros((len(sample), width), np.int32)
    rows = np.zeros((len(sample), settings["max_new_tokens"]), np.int32)
    chosen, valid = np.zeros_like(rows), np.zeros(rows.shape, bool)
    for i, r in enumerate(sample):
        served = [t for _, t in r.tokens]
        n, m = len(r.prompt), len(served)
        tokens[i, :n + m] = np.concatenate([r.prompt, served])
        rows[i, :m] = n - 1 + np.arange(m)
        chosen[i, :m], valid[i, :m] = served, True
    started = time.perf_counter()
    scores = reference.score(run.seed, sizes, tokens, rows, chosen, "f32")
    run.say(f"reference: {len(sample)} requests, {int(valid.sum())} "
            f"served tokens, in {time.perf_counter() - started:.1f}s")
    checks = [("logit_gap", compare.widest_logit_gap(scores, valid), limit)]
    control_checks = []
    if run.control:
        low = reference.score(run.seed, sizes, tokens, rows, chosen, "fp8")
        again = reference.score(run.seed, sizes, tokens, rows,
                                low["argmax"], "f32")
        control_checks.append(
            ("fp8.logit_gap", compare.widest_logit_gap(again, valid), limit))
    return checks, control_checks


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
    work_done = (_traced_work(sizes, buckets, offered, traced[0])
                 if traced else {})
    # The engine's counters over the window, and what the benchmark
    # itself observed of the requests (``bench/...``).
    delta = {**_numeric_delta(after, before), **observed}

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
