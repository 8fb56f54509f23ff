"""ISSUE 26: a scheduler pass, a request and the KV rows are accounted for.

CPU only, a tiny model.  A pass closes (``serve/pass`` holds its leaves and
its own number), a request closes (one ``serve/request`` and one
``serve/ttft`` under the id its other spans carry), and the KV counters
equal a hand count for a schedule that cannot vary.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_tpu.models import transformer
from cloud_tpu.monitoring import tracing
from cloud_tpu.serving import ServeConfig, ServingEngine

LEAVES = ("serve/launch", "serve/readback", "serve/commit")
IN_A_PASS = LEAVES + ("serve/prefill", "serve/chunk")
#: Six ragged prompts with mixed budgets over three slots: every slot is
#: reused, and requests of different lengths decode side by side.
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9], [10, 11, 12, 13, 14, 15, 16],
           [17, 18], [19, 20, 21, 22]]
BUDGETS = [5, 3, 6, 2, 4, 6]
CHUNK = 2


@pytest.fixture(scope="module")
def model():
    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=1)
    return config, transformer.init(jax.random.PRNGKey(0), config)


def _serve(model, prompts=PROMPTS, budgets=BUDGETS, **overrides):
    """Serve ``prompts`` (all queued before the scheduler starts, so the
    schedule is fixed) under a collector; (events, results, stats)."""
    config, params = model
    settings = dict(max_new_tokens=6, prompt_buckets=(4, 8), num_slots=3,
                    chunk_tokens=CHUNK, warmup=False)
    settings.update(overrides)
    with tracing.collecting() as collector:
        engine = ServingEngine(params, config, ServeConfig(**settings),
                               start=False)
        futures = [engine.submit(np.asarray(p, np.int32), max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        engine.start()
        results = [f.result(timeout=300) for f in futures]
        engine.close()
        stats = engine.stats()
    return collector.events(), results, stats


@pytest.fixture(scope="module")
def served(model):
    return _serve(model)


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _ends(event):
    return event["ts"], event["ts"] + event["dur"]


def test_every_leaf_lies_inside_the_pass_whose_number_it_carries(served):
    events = served[0]
    passes = {e["args"]["pass"]: e for e in _named(events, "serve/pass")}
    assert sorted(passes) == list(range(1, len(passes) + 1))
    inside = {n: 0.0 for n in passes}
    for name in IN_A_PASS:
        spans = _named(events, name)
        assert spans, name
        for span in spans:
            lo, hi = _ends(passes[span["args"]["pass"]])
            start, end = _ends(span)
            assert lo <= start and end <= hi, (name, span["args"])
            if name in ("serve/prefill", "serve/chunk"):
                inside[span["args"]["pass"]] += span["dur"]
    # A pass's self time: what is left once its two programs are out.
    for number, span in passes.items():
        assert span["dur"] - inside[number] >= 0.0, number
    assert {e["args"]["what"] for e in _named(events, "serve/launch")} >= {
        "serve/prefill", "serve/chunk", "rng_split"}
    assert {e["args"]["what"] for e in _named(events, "serve/readback")} == {
        "insert_tok0", "chunk_tokens"}


def test_pass_attributes_add_up_to_what_was_submitted(served):
    events, results, stats = served
    passes = [e["args"] for e in _named(events, "serve/pass")]
    assert sum(p["inserts"] for p in passes) == len(PROMPTS)
    assert sum(p["prompt_tokens"] for p in passes) == sum(
        len(p) for p in PROMPTS)
    assert sum(p["bucket_tokens"] for p in passes) == sum(
        r.bucket_len for r in results)
    assert all(1 <= p["active"] <= 3 for p in passes)
    assert {p["kv_rows_reserved"] for p in passes} == {3 * (8 + 6)}
    assert sum(p["kv_rows_in_use"] for p in passes) == stats[
        "kv_row_steps_in_use"]
    commits = [e["args"] for e in _named(events, "serve/commit")]
    # Every token but each request's first (the prefill's) is committed.
    assert sum(c["tokens"] for c in commits) == sum(BUDGETS) - len(BUDGETS)
    assert sum(c["retired"] for c in commits) == len(PROMPTS)


def test_insert_rows_count_the_bucket_against_the_width_that_ran(
        model, monkeypatch):
    """In tiles of 2 a bucket of 8 has the widths 6 and 8 and a bucket of
    4 one width (``generation.prefill_widths``): prompts of 3, 5, 1, 7,
    2 and 4 tokens are dispatched in 4 + 8 + 4 + 8 + 4 + 4 rows of
    buffer and computed in 4 + 6 + 4 + 8 + 4 + 4, and the passes carry
    the same rows as ``computed_tokens``.  The tokens served are those
    of the default tile (one width a bucket)."""
    from cloud_tpu.models import generation

    _, want, whole = _serve(model)
    assert whole["insert_rows_bucket"] == whole["insert_rows_computed"] == 32
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", 2)
    events, results, stats = _serve(model)
    assert [r.bucket_len for r in results] == [4, 8, 4, 8, 4, 4]
    assert stats["insert_rows_bucket"] == 32
    assert stats["insert_rows_computed"] == 30
    passes = [e["args"] for e in _named(events, "serve/pass")]
    assert sum(p["computed_tokens"] for p in passes) == 30
    assert all(p["prompt_tokens"] <= p["computed_tokens"]
               <= p["bucket_tokens"] for p in passes)
    for got, ref in zip(results, want):
        np.testing.assert_array_equal(got.tokens, ref.tokens)


def test_flash_pairs_count_the_tiles_run_against_the_widths_triangle(
        model, monkeypatch):
    """Where the insert's attention is the flash kernel's (here: the
    armed interpreter), every insert adds the compute tiles the kernel
    runs for the prompt and the tiles of its width's whole causal
    triangle.  Buckets of 16 and 32 in tiles of 8 (widths 16; 24 and
    32), tiles of 8 query rows against key blocks of 8: a prompt of L
    tokens runs n (n + 1) / 2 tiles with n = ceil(L / 8), its width of W
    rows holds m (m + 1) / 2 with m = W / 8.  Prompts of 3, 20, 9, 30,
    17 and 12 tokens: 1 + 6 + 3 + 10 + 6 + 3 of 3 + 6 + 3 + 10 + 6 + 3.
    Off the kernel (the default run) both stay zero."""
    import sys

    from cloud_tpu.models import generation

    fa = sys.modules["cloud_tpu.ops.flash_attention"]
    lengths = [3, 20, 9, 30, 17, 12]
    prompts = [list(range(1, n + 1)) for n in lengths]
    budgets = [2, 3, 2, 3, 2, 3]
    monkeypatch.setattr(generation, "PREFILL_TILE_ROWS", 8)
    _, want, plain = _serve(model, prompts, budgets,
                            prompt_buckets=(16, 32))
    assert plain["flash_pairs_run"] == plain["flash_pairs_width"] == 0
    assert plain["insert_rows_computed"] == 16 + 24 + 16 + 32 + 24 + 16

    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
    monkeypatch.setattr(fa, "MAX_BLOCK_Q", 16)
    monkeypatch.setattr(fa, "MAX_BLOCK_K", 8)
    monkeypatch.setattr(fa, "TILE_Q_ROWS", 8)
    monkeypatch.setattr(fa, "LANES", 8)  # the interpreter has no lanes
    traced = fa.KERNEL_TRACE_COUNT
    _, results, stats = _serve(model, prompts, budgets,
                               prompt_buckets=(16, 32))
    assert fa.KERNEL_TRACE_COUNT > traced
    assert stats["flash_pairs_run"] == 1 + 6 + 3 + 10 + 6 + 3
    assert stats["flash_pairs_width"] == 3 + 6 + 3 + 10 + 6 + 3
    assert stats["insert_rows_computed"] == plain["insert_rows_computed"]
    for got, ref in zip(results, want):
        np.testing.assert_array_equal(got.tokens, ref.tokens)


def test_every_request_closes_under_one_id(served):
    events, results, _ = served
    ids = [r.trace_id for r in results]
    assert None not in ids and len(set(ids)) == len(ids)
    for result, budget, prompt in zip(results, BUDGETS, PROMPTS):
        mine = [e for e in events
                if e["args"].get("trace_id") == result.trace_id]
        names = sorted(e["name"] for e in mine)
        assert names == ["serve/prefill", "serve/queue_wait",
                         "serve/request", "serve/ttft"]
        by_name = {e["name"]: e for e in mine}
        assert by_name["serve/ttft"]["dur"] * 1e-6 == pytest.approx(
            result.ttft_seconds, rel=1e-6, abs=1e-9)
        args = by_name["serve/request"]["args"]
        assert args["tokens"] == budget and args["prompt_len"] == len(prompt)
        assert args["bucket"] == result.bucket_len
        assert args["passes"] == math.ceil((budget - 1) / CHUNK)
        assert args["ttft_s"] == pytest.approx(result.ttft_seconds, abs=1e-6)
        assert 0.0 <= args["queue_wait_s"] <= args["ttft_s"]
        assert args["decode_s"] == pytest.approx(
            result.latency_seconds - result.ttft_seconds, abs=1e-5)
        assert "priority" not in args
        # The chunks that decoded for it name it in their slot map.
        riding = [e for e in _named(events, "serve/chunk")
                  if e["args"]["traces"].get(str(args["slot"]))
                  == result.trace_id]
        assert len(riding) == args["passes"]


def test_leaves_mirror_into_a_live_profile_and_the_pass_does_not(
        model, monkeypatch):
    mirrored = []

    class Annotation:
        def __init__(self, name):
            mirrored.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracing.xprof_trace_started()
    try:
        events, _, _ = _serve(model, PROMPTS[:2], BUDGETS[:2])
    finally:
        tracing.xprof_trace_stopped()
    assert set(IN_A_PASS) <= set(mirrored)
    recorded = {e["name"] for e in events}
    for name in ("serve/pass", "serve/request", "serve/ttft",
                 "serve/queue_wait"):
        assert name in recorded and name not in mirrored


def test_kv_row_steps_equal_a_hand_count(model):
    """Two slots, chunks of 2, no eos.  A (3 prompt tokens, 5 to make)
    decodes in chunks 1 and 2 holding 3+1 then 3+3 rows; B (5 and 3) in
    chunk 1 holding 5+1.  Two chunks of 2 slots x (8 + 5) rows."""
    _, _, stats = _serve(model, [[1, 2, 3], [4, 5, 6, 7, 8]], [5, 3],
                         max_new_tokens=5, prompt_buckets=(8,), num_slots=2)
    assert stats["chunks"] == 2
    assert stats["kv_row_steps_in_use"] == (4 + 6) + 6
    assert stats["kv_row_steps_reserved"] == 2 * 2 * 13
    per_row = stats["kv_bytes_reserved"] // (2 * 13)
    assert per_row * 2 * 13 == stats["kv_bytes_reserved"] > 0
    # The last dispatch: A alone, 3 + 3 rows.
    assert stats["kv_bytes_in_use"] == 6 * per_row


@pytest.mark.parametrize("prompts, overrides, chunks, read", [
    # The read is _cache_attention's (off the chip): every row of the
    # grid, at each of the two chunks.
    ([[1, 2, 3], [4, 5, 6, 7, 8]], {}, 2, 2 * 2 * 13),
    # The paged kernel, pages of 4.  Chunk 1: A holds 3+1 rows, a page's
    # edge (4), B 5+1, mid-page (8).  Chunk 2: A 3+3 (8), B has retired:
    # a dead slot costs no rows.
    ([[1, 2, 3], [4, 5, 6, 7, 8]],
     dict(decode_kernel="pallas", prefix_block_tokens=4), 2, 4 + 8 + 8),
    # B's 8 prompt tokens prefill in two chunks of 4.  Chunk 1: A alone,
    # 3+1 (4).  Chunk 2: A 3+3 (8) while B sits mid-prefill with 4 rows
    # filled: in use, read by no decode step.  Chunk 3: B 8+1 (12).
    ([[1, 2, 3], list(range(4, 12))],
     dict(decode_kernel="pallas", prefix_block_tokens=4,
          prefill_chunk_tokens=4, prefix_cache_blocks=4), 3, 4 + 8 + 12),
], ids=["full-rows", "paged", "mid-prefill"])
def test_kv_row_steps_read_counts_what_a_decode_step_fetches(
        model, prompts, overrides, chunks, read):
    """``kv_row_steps_read``: the grid's rows where the decode read takes
    whole rows, the decoding slots' rows rounded up to the kernel's page
    where it goes through the paged kernel.  A (3 prompt tokens, 5 to
    make) and B (3 to make), two slots of 8 + 5 rows, chunks of 2."""
    _, _, stats = _serve(model, prompts, [5, 3], max_new_tokens=5,
                         prompt_buckets=(8,), num_slots=2, **overrides)
    assert stats["chunks"] == chunks
    assert stats["kv_row_steps_read"] == read
    assert read <= stats["kv_row_steps_reserved"]


@pytest.mark.parametrize("kernel, read", [
    # The jnp step reads and rewrites every reserved row: 2 chunks x
    # 2 slots x 2 layers.
    (False, 2 * 2 * 2),
    # The state kernel fetches the decoding slots' rows alone: A and B in
    # chunk 1, A in chunk 2 (B has retired), a row a layer.
    (True, (2 + 1) * 2),
], ids=["jnp-step", "state-kernel"])
def test_state_row_steps_read_counts_what_a_decode_step_fetches(
        monkeypatch, kernel, read):
    """``state_row_steps_read`` on the "full-rows" schedule above, for a
    model with a recurrent state that tiles as ``ops.ssm_state``'s kernel
    needs: every reserved row where the step is jnp's, one a layer for
    each decoding slot where it is the kernel's (here interpreted; on a
    TPU nothing has to switch it on)."""
    from cloud_tpu.models import ssm

    if kernel:
        monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")
    config = transformer.TINY.scaled(
        dtype=jnp.float32, num_layers=2, ssm=ssm.SsmConfig(
            num_heads=2, head_dim=8, state_dim=128, num_groups=1,
            chunk_size=4))
    hybrid = config, transformer.init(jax.random.PRNGKey(0), config)
    _, results, stats = _serve(hybrid, [[1, 2, 3], [4, 5, 6, 7, 8]], [5, 3],
                               max_new_tokens=5, prompt_buckets=(8,),
                               num_slots=2)
    assert [len(r.tokens) for r in results] == [5, 3]
    assert stats["chunks"] == 2
    assert stats["state_row_steps_reserved"] == 2 * 2 * 2
    assert stats["state_row_steps_in_use"] == (2 + 1) * 2
    assert stats["state_row_steps_read"] == read
    # The K/V read is _cache_attention's either way, off the chip.
    assert stats["kv_row_steps_read"] == stats["kv_row_steps_reserved"]


def test_a_referenced_pool_block_counts_once_as_in_use(model):
    """Prefix pool on: its blocks are reserved whole; a block counts as
    in use while a live slot references it.  One request of 8 prompt
    tokens saves the whole blocks of its first 7 (one block of 4) and
    holds it while it decodes."""
    _, _, stats = _serve(model, [list(range(1, 9))], [3],
                         max_new_tokens=3, prompt_buckets=(8,), num_slots=1,
                         prefix_cache_blocks=4, prefix_block_tokens=4)
    assert stats["chunks"] == 1
    assert stats["kv_row_steps_reserved"] == 1 * (8 + 3) + 4 * 4
    assert stats["kv_row_steps_in_use"] == (8 + 1) + 4


def test_the_same_names_at_pipeline_depth_2(model):
    events, results, _ = _serve(model, pipeline_depth=2)
    recorded = {e["name"] for e in events}
    assert set(IN_A_PASS + ("serve/pass", "serve/request", "serve/ttft")) \
        <= recorded
    for name in ("serve/request", "serve/ttft"):
        assert sorted(e["args"]["trace_id"] for e in _named(events, name)) \
            == sorted(r.trace_id for r in results)


def test_collector_off_counts_kv_and_records_nothing(model):
    config, params = model
    assert tracing.active() is None
    serve = ServeConfig(max_new_tokens=3, prompt_buckets=(4,), num_slots=1,
                        chunk_tokens=CHUNK, warmup=False)
    with ServingEngine(params, config, serve) as engine:
        result = engine.submit(np.asarray([1, 2], np.int32)).result(
            timeout=300)
        stats = engine.stats()
    assert result.trace_id is None and tracing.timeline_events() == []
    assert stats["kv_row_steps_in_use"] == 2 + 1
    assert stats["kv_row_steps_reserved"] == 4 + 3


@pytest.mark.parametrize("held, offset", [(None, 0), (2, 4)],
                         ids=["every-expert", "a-share"])
def test_expert_counters_on_the_full_rows_schedule(held, offset):
    """The routing counts of a model with dropless experts on the
    "full-rows" schedule above (A: 3 prompt tokens, 5 to make; B: 5 and 3;
    two slots, chunks of 2): they come back from the device with each
    insert's and each chunk's tokens.  One dense layer, then one expert
    layer, 2 of 8 experts a token: the inserts route 3 + 5 tokens; chunk 1
    decodes A and B for two steps, chunk 2 A alone (B has retired, and an
    idle slot routes nothing): (8 + 4 + 2) x 2 = 28 assignments.  A chunk
    counts 2 steps x 1 expert layer x the held experts, and those of them
    that got a token."""
    from cloud_tpu.models import mla, moe

    config = transformer.TINY.scaled(
        dtype=jnp.float32, num_layers=2, leading_dense_layers=1,
        dense_mlp_hidden=96, mlp_hidden=32,
        latent=mla.LatentConfig(q_rank=24, kv_rank=32, nope_dim=16,
                                rope_dim=8, v_dim=16),
        moe=moe.MoeConfig(num_experts=8, top_k=2, dropless=True,
                          experts_held=held, expert_offset=offset,
                          score="sigmoid", shared_hidden=32,
                          selection_bias=True))
    model = config, transformer.init(jax.random.PRNGKey(0), config)
    events, results, stats = _serve(
        model, [[1, 2, 3], [4, 5, 6, 7, 8]], [5, 3], max_new_tokens=5,
        prompt_buckets=(8,), num_slots=2)
    assert [len(r.tokens) for r in results] == [5, 3]
    assert stats["chunks"] == 2
    assert stats["expert_assignments"] == 28
    held_here = 8 if held is None else held
    assert stats["expert_steps"] == 2 * 2 * 1 * held_here
    assert len(stats["expert_loads"]) == held_here
    assert sum(stats["expert_loads"]) == stats["expert_assignments_here"]
    if held is None:
        assert stats["expert_assignments_here"] == 28
        # Two tokens a step pick at least 2 experts, at most 4; one, 2.
        assert 2 * 2 + 2 * 2 <= stats["expert_steps_touched"] <= 2 * 4 + 2 * 2
        assert stats["expert_load_max_over_mean"] >= 1.0
    else:
        assert stats["expert_assignments_here"] <= 28
        assert stats["expert_steps_touched"] <= stats["expert_steps"]
    # The pass carries what landed here in it; a latent row is one a token.
    passes = _named(events, "serve/pass")
    assert sum(p["args"]["assignments_here"] for p in passes) == \
        stats["expert_assignments_here"]
    assert stats["kv_row_steps_reserved"] == 2 * 2 * (8 + 5)
    assert stats["kv_row_steps_in_use"] == (3 + 1) + (5 + 1) + (3 + 3)
    assert stats["kv_bytes_reserved"] == 2 * 2 * 13 * 128 * 4
