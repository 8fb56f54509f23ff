"""The latent-attention expert cell's own yardstick, on the CPU: the
``--tiny`` rehearsal of ``kimi-k2-ep32-stage.agent-saturated`` (a sound run
is correct; its control, the reference in fp8, a token altered where it is
produced, the first token of each answer alone altered, and two faults of
this architecture's own are not; a fault in under 1% of the real cell's
sample is not either),
``work_latent_moe``'s counts against counts made by hand, and what the
traced window's work reads from the engine's counters.
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (compare, manifest,  # noqa: E402
                                work_latent_moe as work)

CELL = "kimi-k2-ep32-stage.agent-saturated"


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")


def _drive(seed=2 ** 31 + 35, seconds=2.0, control=False):
    from cloud_tpu.monitoring import tracing

    cell = manifest.Cell(CELL, tiny=True)
    with tracing.collecting():
        outcome, metrics, _ = bench_run.drive(
            cell, seed, seconds, 0, control=control,
            process_start=time.perf_counter())
    return outcome, metrics


def test_sound_run_is_correct_and_its_control_is_not():
    from cloud_tpu.ops import grouped_matmul, latent_attention

    traced = (grouped_matmul.KERNEL_TRACE_COUNT,
              latent_attention.KERNEL_TRACE_COUNT)
    outcome, metrics = _drive(control=True)
    assert compare.judge(outcome.checks), outcome.checks
    assert outcome.failed == 0
    assert set(metrics) == {"serve_tokens_per_s", "setup_s"}
    assert {c[0] for c in outcome.checks} == {
        "answers_wrong", "backlog_emptied", "logit_gap_p99",
        "logit_gap_clear_max"}
    assert {c[0] for c in outcome.control_checks} == {
        "fp8.logit_gap_p99", "fp8.logit_gap_clear_max"}
    assert outcome.control_checks
    assert all(not c[1] <= c[2] for c in outcome.control_checks)
    # Both kernels ran (interpreted), and the routing came back.
    assert grouped_matmul.KERNEL_TRACE_COUNT > traced[0]
    assert latent_attention.KERNEL_TRACE_COUNT > traced[1]
    stats = outcome.stats
    assert 0 < stats["expert_assignments_here"] < stats["expert_assignments"]
    assert 0 < stats["expert_steps_touched"] <= stats["expert_steps"]
    assert stats["expert_load_max_over_mean"] >= 1.0


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    def second_best(rng, logits, sample, **kw):
        return jnp.argsort(logits, axis=-1)[..., -2]

    monkeypatch.setattr(generation, "sample_logits", second_best)
    outcome, _ = _drive()
    assert not compare.judge(outcome.checks)


def test_first_token_alone_altered_is_not_correct(monkeypatch):
    """Only what the INSERT program samples (a request's first token: one
    token of each answer) is altered; the decode chunk's tokens are
    sound.  The maximum over the clear positions holds it."""
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    real = generation.sample_logits

    def second_best_of_one(rng, logits, sample, **kw):
        if logits.shape[0] != 1:  # the chunk's: every slot's row
            return real(rng, logits, sample, **kw)
        return jnp.argsort(logits, axis=-1)[..., -2]

    monkeypatch.setattr(generation, "sample_logits", second_best_of_one)
    outcome, _ = _drive()
    values = {name: (value, limit) for name, value, limit in outcome.checks}
    assert values["answers_wrong"][0] == 0
    assert not values["logit_gap_clear_max"][0] <= \
        values["logit_gap_clear_max"][1]
    assert not compare.judge(outcome.checks)


def test_under_one_percent_of_tokens_wrong_is_not_correct():
    """At the REAL cell's sample (6 requests, some 1,400 served tokens)
    and its limits: a fault in the six first tokens alone, 0.4% of the
    sample, passes the 99th percentile and fails the maximum over the
    clear positions; the same gaps on positions within the margin of a
    flipped choice of experts are what a sound run may read, and pass; a
    sample with no clear position is not correct."""
    import numpy as np

    from benchmarks.adapters import serve_latent_moe

    limits = manifest.Cell(CELL).traffic["limits"]
    rng = np.random.default_rng(35)
    valid = np.zeros((6, 512), bool)
    for row, n in zip(valid, (130, 180, 210, 250, 300, 330)):
        row[:n] = True
    scores = {"best": np.full(valid.shape, 3.0), "std": np.ones(valid.shape),
              "chosen": 3.0 - 0.05 * rng.random(valid.shape) ** 8,
              "held_margin": np.full(valid.shape, 10 * limits["clear_margin"])}

    def judged(scores):
        return compare.judge(
            serve_latent_moe.gap_checks(scores, valid, limits))

    assert judged(scores)
    wrong = dict(scores, chosen=scores["chosen"].copy())
    wrong["chosen"][:, 0] = 3.0 - 1.5
    checks = dict((n, (v, l)) for n, v, l in
                  serve_latent_moe.gap_checks(wrong, valid, limits))
    assert checks["logit_gap_p99"][0] <= checks["logit_gap_p99"][1]
    assert checks["logit_gap_clear_max"][0] == 1.5
    assert not judged(wrong)
    near = dict(wrong, held_margin=scores["held_margin"].copy())
    near["held_margin"][:, 0] = 0.1 * limits["clear_margin"]
    assert judged(near)
    assert not judged(dict(scores, held_margin=0 * scores["held_margin"]))


def test_only_a_backlog_is_driven():
    from benchmarks.adapters import serve_latent_moe
    from benchmarks.harness import context

    cell = manifest.Cell(CELL, tiny=True)
    cell.traffic = dict(cell.traffic, arrivals={
        "process": "stratified_exponential", "rate_per_s": 5.0})
    run = context.Run(cell=cell, seed=1, seconds=1.0, trace=False,
                      process_start=time.perf_counter())
    with pytest.raises(ValueError, match="drives a backlog"):
        serve_latent_moe.run(run)


def test_selection_bias_left_out_is_not_correct(monkeypatch):
    """A fault this architecture brings: the experts chosen by the scores
    alone, the selection bias left out."""
    from cloud_tpu.models import moe

    real = moe.route

    def unbiased(params, flat, cfg):
        return real(dict(params, bias=params["bias"] * 0), flat, cfg)

    monkeypatch.setattr(moe, "route", unbiased)
    outcome, _ = _drive()
    assert not compare.judge(outcome.checks)


def test_rotated_key_not_shared_is_not_correct(monkeypatch):
    """The other: the shared key rotated as if every token stood at
    position 0, so a cache row no longer carries its place (the queries
    still carry theirs)."""
    from cloud_tpu.models import mla

    real = mla.rotate

    def unplaced(x, positions, cfg, base):
        # The shared key is the rotation's one input without a head axis.
        return real(x, positions * 0 if x.ndim == 3 else positions, cfg,
                    base)

    monkeypatch.setattr(mla, "rotate", unplaced)
    outcome, _ = _drive()
    assert not compare.judge(outcome.checks)


def test_work_against_hand_counts():
    s = {"hidden_size": 8, "intermediate_size": 20, "q_lora_rank": 6,
         "kv_lora_rank": 4, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
         "v_head_dim": 3, "num_attention_heads": 2,
         "moe_intermediate_size": 5, "n_routed_experts": 3,
         "n_shared_experts": 1, "num_experts_per_tok": 2,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "vocab_size": 10, "published": {"n_routed_experts": 12}}
    att = 8 * 6 + 6 * 2 * 5 + 8 * 6 + 4 * 2 * 6 + 2 * 3 * 8
    expert = 3 * 8 * 5
    assert work.attention_params(s) == att
    assert work.expert_params(s) == expert
    assert work.outside_params(s) == att + expert + 8 * 12
    assert work.dense_layer_params(s) == att + 3 * 8 * 20
    token = (att + 480) + 2 * (att + expert + 96)
    assert work.token_params(s) == token
    small = 3 * (16 + 6 + 4) + 2 * 12 + 8
    assert work.params(s) == token + 2 * 3 * expert + small + 2 * 10 * 8
    # 5 tokens: 15 (query, key) pairs; 5 x 2 x 2 assignments, a quarter
    # of them here.
    assert work.prefill_flops(s, 5, 0.25) == (
        2 * token * 5 + 3 * 2 * 2 * (5 + 3) * 15
        + 2 * expert * 5 + 2 * 8 * 10)
    # One token at position 6: 7 rows, every head against 4 + 2 numbers
    # of a row for the score and 4 for the value.
    assert work.decode_flops(s, 6, 0.25) == (
        2 * token + 3 * 2 * 2 * (2 * 4 + 2) * 7 + 2 * expert * 1
        + 2 * 8 * 10)
    assert work.latent_row_bytes(s) == 12
    assert work.decode_step_bytes(s, 4.5, 40) == (
        2 * (token + small + 80 + 4.5 * expert) + 3 * 12 * 40)
    assert work.latent_decode_call(s, 6, 40) == (
        2 * 2 * 10 * 40, 2 * (40 * 6 + 6 * (6 + 4)))
    assert work.grouped_products(s, 7, 2) == (
        2 * expert * 7, 2 * (2 * expert + 7 * (16 + 15)))
    # The published sizes, as ISSUE 35 reckons them: attention 101.1M,
    # 147.9M outside an expert layer's routed experts, an expert 44.04M,
    # the dense layer 497.5M, 4,849.5M held here; a row 1,152 B.
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-k2-ep32-stage.json")) as f:
        published = json.load(f)
    assert work.attention_params(published) == 101_122_048
    assert work.outside_params(published) == 147_914_752
    assert work.expert_params(published) == 44_040_192
    assert work.dense_layer_params(published) == 497_483_776
    assert work.params(published) == 4_849_591_552
    assert work.latent_row_bytes(published) == 1152


def test_traced_work_counts_one_chunk_program_from_the_engines_counters():
    from benchmarks.adapters import serve_latent_moe

    cell = manifest.Cell(CELL)
    sizes, settings = cell.config, cell.traffic["engine"]
    # 10 chunk dispatches of 8 steps: 60 live slots and 150,000 latent
    # rows each; 8.5 of the 12 experts of each of the 6 layers touched a
    # step; 3% of the assignments here.
    delta = {"chunks": 10, "useful_decode_tokens": 10 * 8 * 60,
             "kv_row_steps_in_use": 10 * 150_000,
             "expert_steps": 10 * 8 * 6 * 12,
             "expert_steps_touched": 10 * 8 * 51,
             "expert_assignments": 1000, "expert_assignments_here": 30}
    got = serve_latent_moe._traced_work(work, sizes, settings, [],
                                        (0.0, 1.0), delta)
    assert got["serve_flops"] == 0
    assert got["decode_chunk"]["bytes"] == 8 * work.decode_step_bytes(
        sizes, 51, 150_000)
    assert got["decode_chunk"]["flops"] == 8 * 60 * work.decode_flops(
        sizes, 2500, 0.03)
    flops, moved = work.latent_decode_call(sizes, 60 * 64, 150_000)
    assert got["latent_decode"] == {"flops": 8 * 7 * flops,
                                    "bytes": 8 * 7 * moved}
    flops, moved = work.grouped_products(sizes, 60 * 8 * 6 * 0.03, 51)
    assert got["grouped_matmul"] == {"flops": 8 * flops, "bytes": 8 * moved}
    # No insert inside the traced window: nothing for its kernels' shares.
    nothing = {"flops": 0, "bytes": 0}
    assert got["flash_fwd"] == got["grouped_matmul_prefill"] == nothing
    # The touched experts are the step's largest read, the rows second.
    step = work.decode_step_bytes(sizes, 51, 150_000)
    assert 2 * 51 * work.expert_params(sizes) > 0.45 * step
    # A program that counts no routing (the parent's): nothing to read.
    for older in ({}, {"chunks": 10, "useful_decode_tokens": 4800,
                       "kv_row_steps_in_use": 60000}):
        assert set(serve_latent_moe._traced_work(
            work, sizes, settings, [], (0.0, 1.0), older)) == {
                "serve_flops", "prompt_ktok", "flash_fwd",
                "grouped_matmul_prefill"}


def test_traced_work_counts_an_insert_at_the_prompts_real_length():
    """One prompt of 3,000 tokens prefilled inside the traced window and
    one outside it: seven flash calls over its causal pairs at head sizes
    192 / 128, and six expert layers' grouped products for the 3% of its
    assignments that landed here, every held expert's matrices once."""
    from benchmarks.adapters import serve_latent_moe
    from benchmarks.adapters.serve_engine import _Request

    cell = manifest.Cell(CELL)
    sizes, settings = cell.config, cell.traffic["engine"]
    requests = []
    for when in (0.5, 2.0):
        r = _Request({"prompt": [1] * 3000, "max_new_tokens": 4,
                      "due_s": 0.0})
        r.tokens, r.times = [(0, 7)], [when]
        requests.append(r)
    got = serve_latent_moe._traced_work(
        work, sizes, settings, requests, (0.0, 1.0),
        {"expert_assignments": 1000, "expert_assignments_here": 30})
    pairs = 3000 * 3001 // 2
    assert got["flash_fwd"] == {
        "flops": 7 * 2 * 64 * (192 + 128) * pairs,
        "bytes": 7 * 2 * 3000 * 64 * 2 * (192 + 128)}
    flops, moved = work.grouped_products(sizes, 3000 * 8 * 0.03, 12)
    assert got["grouped_matmul_prefill"] == {"flops": 6 * flops,
                                             "bytes": 6 * moved}
    assert got["prompt_ktok"] == 3.0
    assert work.flash_forward_call(
        {"num_attention_heads": 2, "qk_nope_head_dim": 3,
         "qk_rope_head_dim": 2, "v_head_dim": 3, "num_hidden_layers": 3},
        5) == (2 * 2 * (5 + 3) * 15, 2 * 5 * 2 * 2 * (5 + 3))
