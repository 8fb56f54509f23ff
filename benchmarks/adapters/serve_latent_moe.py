"""``entry: serve_latent_moe`` — a decoder through ``ServingEngine.submit``,
as a caller drives one replica, with the model builder and the work counts
NAMED BY THE CONFIGURATION: ``config["model"]`` picks a builder of
``MODELS`` and ``config["work"]`` a module of ``benchmarks.harness`` with
``prefill_flops(sizes, n, here_share)`` / ``decode_flops(sizes, position,
here_share)`` and, where it has them, ``decode_step_bytes`` and the kernels'
calls.  It is written as the one serving adapter: a ``benchmark`` PR folds
``serve_engine`` and ``serve_hybrid`` into it by giving their
configurations the two names (ROADMAP, benchmark queue); until then
everything they share is imported from ``serve_engine``.  It drives a
BACKLOG (every request due at the window's start) and refuses any other
arrival process: the open-loop readings come with the fold, when a cell
needs them.

``serve_tokens_per_s`` here counts the tokens the engine WORKED THROUGH
inside the window: a request's prompt once its first token is known, and
every generated token known by the close — of the requests still in
flight at the close too, not only of those that finished.  Counting whole
finished requests alone makes the reading move by which of the ~100
requests admitted in the window's last half happen to end before the
close (their prompts differ by thousands of tokens): 1.3% of noise that
is no property of the program (PERF.md section 6, PR 35).

The program's new modules are imported at the top, so that on a commit
that lacks them the cell fails at import, within seconds.
"""

import gc
import importlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.adapters.serve_engine import (
    _Request, _answers_wrong, _check_sample, _numeric_delta, _offer,
    _trace_window)
from benchmarks.harness import context, stats, traffic, xplane
from cloud_tpu.models import mla, moe, transformer
from cloud_tpu.ops import grouped_matmul, latent_attention  # noqa: F401
from cloud_tpu.serving import ServeConfig, ServingEngine


def latent_moe(sizes, mix):
    """Kimi-K2's block (the DeepSeek-V3 block) at the configuration's
    share: latent attention with YaRN, ``first_k_dense_replace`` leading
    dense layers, then dropless expert layers that hold
    ``n_routed_experts`` of ``published.n_routed_experts``."""
    engine = mix["engine"]
    rows = engine["prompt_buckets"][-1] + engine["max_new_tokens"]
    yarn = sizes["rope_scaling"]
    if (sizes["n_group"], sizes["topk_group"]) != (1, 1) \
            or not sizes["norm_topk_prob"] or yarn["type"] != "yarn":
        raise ValueError("the builder knows one group of experts, "
                         "renormalised weights and YaRN")
    return transformer.TransformerConfig(
        vocab_size=sizes["vocab_size"],
        num_layers=sizes["num_hidden_layers"], dim=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        mlp_hidden=sizes["moe_intermediate_size"],
        max_seq_len=max(sizes["max_position_embeddings"], rows),
        rope_base=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["serve_dtype"]),
        latent=mla.LatentConfig(
            q_rank=sizes["q_lora_rank"], kv_rank=sizes["kv_lora_rank"],
            nope_dim=sizes["qk_nope_head_dim"],
            rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
            rope_factor=float(yarn["factor"]),
            rope_original=yarn["original_max_position_embeddings"],
            beta_fast=float(yarn["beta_fast"]),
            beta_slow=float(yarn["beta_slow"]),
            mscale=float(yarn["mscale"]),
            mscale_all_dim=float(yarn["mscale_all_dim"])),
        leading_dense_layers=sizes["first_k_dense_replace"],
        dense_mlp_hidden=sizes["intermediate_size"],
        moe=moe.MoeConfig(
            num_experts=sizes["published"]["n_routed_experts"],
            top_k=sizes["num_experts_per_tok"], dropless=True,
            experts_held=sizes["n_routed_experts"],
            expert_offset=sizes.get("expert_offset", 0),
            score=sizes["scoring_func"],
            routed_scale=sizes["routed_scaling_factor"],
            shared_hidden=(sizes["moe_intermediate_size"]
                           * sizes["n_shared_experts"]),
            selection_bias=sizes["topk_method"] == "noaux_tc"),
        multipliers=transformer.Multipliers(embedding=1.0))


#: ``config["model"]`` -> the builder of its ``TransformerConfig``.
MODELS = {"latent_moe": latent_moe}


def model_config(sizes, mix):
    return MODELS[sizes["model"]](sizes, mix)


def _here_share(delta):
    """The share of the window's (token, choice) assignments that landed
    on an expert held here; 0 for a model that routes nothing."""
    made = delta.get("expert_assignments", 0)
    return delta.get("expert_assignments_here", 0) / made if made else 0.0


def _expert_loads(after, before):
    """The window's tokens a held expert, and the busiest over their
    mean."""
    loads = [a - b for a, b in zip(after.get("expert_loads", ()),
                                   before.get("expert_loads", ()))]
    if not loads or not sum(loads):
        return {}
    return {"expert_load_max_over_mean": max(loads) * len(loads)
            / sum(loads)}


def _traced_work(work, sizes, settings, requests, traced, delta):
    """Operations of what was prefilled and decoded inside the traced
    window, from each token's arrival time, and what ONE execution of the
    chunk program must do, from the engine's counters over the whole
    window (one count a chunk dispatch): its steps' bytes at the mean of
    experts touched and latent rows in use, its operations those of the
    mean count of live slots at the mean context; likewise one chunk's
    calls of the two kernels.  Counters the program lacks: no such
    entry."""
    lo, hi = traced
    share = _here_share(delta)
    out = {"serve_flops": 0, "prompt_ktok": 0.0,
           "flash_fwd": {"flops": 0, "bytes": 0},
           "grouped_matmul_prefill": {"flops": 0, "bytes": 0}}
    layers, expert_layers = (sizes["num_hidden_layers"],
                             work.layer_counts(sizes)[1])
    for r in requests:
        n = len(r.prompt)
        for (index, _), when in zip(r.tokens, r.times):
            if not lo <= when <= hi:
                continue
            if index == 0:
                out["serve_flops"] += work.prefill_flops(sizes, n, share)
                out["prompt_ktok"] += n / 1000.0
                # An insert's kernels, at the prompt's REAL length: the
                # expanded attention a layer, and the experts' grouped
                # products an expert layer (every held expert's matrices
                # once: a prompt's hundreds of assignments here leave
                # none untouched).
                for key, (flops, moved), times in (
                        ("flash_fwd", work.flash_forward_call(sizes, n),
                         layers),
                        ("grouped_matmul_prefill", work.grouped_products(
                            sizes, n * sizes["num_experts_per_tok"] * share,
                            sizes["n_routed_experts"]), expert_layers)):
                    out[key]["flops"] += times * flops
                    out[key]["bytes"] += times * moved
            else:
                out["serve_flops"] += work.decode_flops(
                    sizes, n + index - 1, share)
    chunks = delta.get("chunks", 0)
    decoded = delta.get("useful_decode_tokens", 0)
    expert_steps = delta.get("expert_steps", 0)
    if not (chunks and decoded and expert_steps
            and hasattr(work, "decode_step_bytes")):
        return out
    steps = settings["chunk_tokens"]
    live = decoded / chunks / steps
    rows = delta["kv_row_steps_in_use"] / chunks
    touched = delta["expert_steps_touched"] / chunks / steps
    context_len = int(rows / live)
    out["decode_chunk"] = {
        "flops": steps * live * work.decode_flops(sizes, context_len, share),
        "bytes": steps * work.decode_step_bytes(sizes, touched, rows)}
    flops, moved = work.latent_decode_call(
        sizes, live * sizes["num_attention_heads"], rows)
    out["latent_decode"] = {"flops": steps * layers * flops,
                            "bytes": steps * layers * moved}
    here = live * sizes["num_experts_per_tok"] * expert_layers * share
    flops, moved = work.grouped_products(sizes, here, touched)
    out["grouped_matmul"] = {"flops": steps * flops, "bytes": steps * moved}
    return out


#: The quantile of the served tokens' gaps that is held to a limit.
GAP_QUANTILE = 0.99

#: The margins the control's readings are swept over (``--control``).
MARGIN_SWEEP = (0.0, 0.002, 0.005, 0.01, 0.02, 0.04)


def _gaps(scores):
    """How far the chosen token's logit lies below the reference's best,
    in units of the position's logit standard deviation
    (``harness.compare.widest_logit_gap`` is this reading's maximum)."""
    return (scores["best"] - scores["chosen"]) / scores["std"]


def gap_checks(scores, valid, limits, prefix=""):
    """The two numbers a run's served tokens are held to, each beside its
    limit.

    An expert model's top-k choice is discrete: where a held expert's
    score lies within rounding of the boundary of the chosen, a sound
    bfloat16 program and the float32 reference choose apart, the token's
    residual stream moves by a whole expert's output, and its gap is as
    wide as the control's (the plain reference in bfloat16 reads a maximum
    of 0.66 against float32 over 4,128 tokens, this program 0.66, fp8
    1.57: PERF.md section 6, PR 35).  So the maximum over ALL tokens
    cannot tell a sound run from the control, and two numbers stand in
    for it:

    ``logit_gap_p99``: the 99th percentile over all the ``valid``
    positions, which holds the bulk (flipped tokens are a fraction of a
    percent);

    ``logit_gap_clear_max``: the MAXIMUM over the positions the reference
    marks clear of such a flip — its ``held_margin`` (how far the nearest
    held expert stands from changing sides, least over the layers) is at
    least ``limits["clear_margin"]`` — which bounds the tail: one wrong
    token among them fails the run, the first (insert) token of a request
    as any other.  No clear position at all fails it too."""
    gaps = _gaps(scores)
    clear = valid & (scores["held_margin"] >= limits["clear_margin"])
    return [(prefix + "logit_gap_p99",
             float(np.quantile(gaps[valid], GAP_QUANTILE)),
             limits["logit_gap_p99"]),
            (prefix + "logit_gap_clear_max",
             float(np.max(gaps[clear])) if clear.any() else float("inf"),
             limits["logit_gap_clear_max"])]


def _say_sweep(run, what, scores, valid):
    """For setting ``clear_margin``: the clear positions' count and their
    widest gap at each margin of :data:`MARGIN_SWEEP`."""
    gaps = _gaps(scores)
    cells = []
    for margin in MARGIN_SWEEP:
        clear = valid & (scores["held_margin"] >= margin)
        cells.append(f"{margin:g}: {int(clear.sum())} clear, max "
                     f"{float(np.max(gaps[clear], initial=0.0)):.4f}")
    run.say(f"{what} by clear_margin: " + "; ".join(cells))


def _logit_checks(run, reference, finished):
    """:func:`gap_checks` of the served tokens of a sample of the finished
    requests; with ``run.control`` also the control's (the token that fp8
    puts first) and both readings swept over the margin."""
    sizes, mix = run.cell.config, run.cell.traffic
    settings, limits = mix["engine"], mix["limits"]
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
    dtype = jnp.dtype(sizes["serve_dtype"])
    scores = reference.score(run.seed, sizes, tokens, rows, chosen, "f32",
                             dtype)
    gaps = _gaps(scores)[valid]
    run.say(f"reference: {len(sample)} requests, {int(valid.sum())} "
            f"served tokens, in {time.perf_counter() - started:.1f}s; "
            f"near-tied choices of experts: "
            f"{100 * scores['near_ties']:.3f}% of (token, layer); "
            f"logit gap max {np.max(gaps):.4f}, p99.9 "
            f"{np.quantile(gaps, 0.999):.4f}, p90 "
            f"{np.quantile(gaps, 0.9):.4f}; first tokens "
            f"{np.round(_gaps(scores)[:, 0], 4).tolist()}")
    checks = gap_checks(scores, valid, limits)
    control_checks = []
    if run.control:
        low = reference.score(run.seed, sizes, tokens, rows, chosen, "fp8",
                              dtype)
        again = reference.score(run.seed, sizes, tokens, rows,
                                low["argmax"], "f32", dtype)
        control_checks = gap_checks(again, valid, limits, "fp8.")
        _say_sweep(run, "sound", scores, valid)
        _say_sweep(run, "fp8", again, valid)
    return checks, control_checks


def run(run):
    sizes, mix = run.cell.config, run.cell.traffic
    reference = importlib.import_module(
        f"benchmarks.references.{sizes['reference']}")
    work = importlib.import_module(f"benchmarks.harness.{sizes['work']}")
    settings = mix["engine"]
    buckets = tuple(settings["prompt_buckets"])
    config = model_config(sizes, mix)
    if mix["arrivals"]["process"] != "backlog":
        raise ValueError(
            "entry serve_latent_moe drives a backlog (arrivals.process "
            f"'backlog'), not {mix['arrivals']['process']!r}: the open-loop "
            "readings are serve_engine's until the adapters are folded")
    # A traced run measures as long as any other (its tails and counters
    # are over the whole window), with the profiler on for a part of it.
    seconds = max(run.seconds, sum(mix["trace_window_s"])) if run.trace \
        else run.seconds
    compiles = context.CompileCounter.get()

    params = jax.block_until_ready(reference.make_params(
        run.seed, sizes, jnp.dtype(sizes["serve_dtype"])))
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
    # The rest of the backlog is dropped with the engine.
    stop.set()
    engine.close(drain=False)
    generator.join()
    if tracer is not None:
        tracer.join()
        xplane.stop_trace()
    offered = [r for r in requests if r.submitted is not None]
    window_s = end - start
    late = [r.submitted - (start + r.due_s) for r in offered]
    run.say(f"window {window_s:.3f}s; {len(offered)} of {len(requests)} "
            f"requests offered; generator late by p95 "
            f"{stats.percentile(late, 95) * 1e3:.2f} ms, max "
            f"{max(late) * 1e3:.2f} ms; compilations inside the window: "
            f"{compiles.between(start, end)}; persistent cache so far: "
            f"{compiles.cache}; {watch}")

    finished = [r for r in offered
                if r.done_at() is not None and r.done_at() <= end]
    unfinished = len(requests) - len(finished)
    # The tokens worked through inside the window (module docstring): a
    # prompt counts once its first token is known, a generated token once
    # it is, in flight at the close or not.
    known = [sum(t <= end for t in r.times) for r in offered]
    tokens = sum(len(r.prompt) + n for r, n in zip(offered, known) if n)
    whole = sum(len(r.prompt) + r.max_new_tokens for r in finished)
    end_to_end = {"serve_tokens_per_s": stats.rate(tokens, window_s)}
    # Saturated means the backlog never emptied: at the close more
    # requests were waiting or decoding than the grid has slots.
    run.say(f"{len(finished)} finished, {unfinished} unfinished at the "
            f"close against {settings['num_slots']} slots; "
            f"{sum(1 for n in known if n) - len(finished)} in flight; "
            f"{tokens} tokens worked through, {whole} of them of finished "
            f"requests ({stats.rate(whole, window_s):.1f}/s)")
    limits = mix["limits"]
    checks = [("answers_wrong", _answers_wrong(finished),
               limits["answers_wrong"]),
              ("backlog_emptied", int(unfinished <= settings["num_slots"]),
               limits["backlog_emptied"])]
    spans = context.program_spans()
    peak = context.memory_peak_bytes()
    # The engine's counters over the window.
    delta = {**_numeric_delta(after, before),
             **_expert_loads(after, before)}
    if delta.get("expert_assignments"):
        run.say("experts: %.3f%% of %d assignments landed here; %.1f%% of "
                "the held experts touched a decode step; busiest over "
                "mean %.3f" % (
                    100 * _here_share(delta), delta["expert_assignments"],
                    100 * delta["expert_steps_touched"]
                    / max(delta["expert_steps"], 1),
                    delta.get("expert_load_max_over_mean", 0.0)))
    work_done = (_traced_work(work, sizes, settings, offered, traced[0],
                              delta) if traced else {})

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
    control_checks = []
    if run.check:
        # The reference runs once the window has closed, the peak is read
        # and the engine with its weights and cache is freed.
        logit_checks, control_checks = _logit_checks(run, reference,
                                                     finished)
        checks += logit_checks
    return context.Outcome(
        window_start=start, window_s=window_s, end_to_end=end_to_end,
        attempted=len(finished), failed=0, checks=checks,
        memory_peak_bytes=peak, spans=spans, stats=delta, work=work_done,
        traced=traced[0] if traced else None, control_checks=control_checks)
