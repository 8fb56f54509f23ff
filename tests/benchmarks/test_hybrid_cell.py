"""The hybrid cell's own yardstick, on the CPU: the ``--tiny`` rehearsal of
``falcon-h1-34b-stage.chat-open`` (a sound run is correct; its control, the
reference in fp8, and a token altered where it is produced are not),
``work_hybrid``'s counts against counts made by hand, and the reader that
holds a whole program against its roofline, on the small recorded trace.
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
from benchmarks.harness import (compare, context, manifest, peaks,  # noqa: E402
                                work_hybrid)

CELL = "falcon-h1-34b-stage.chat-open"


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")


def _drive(seed=2 ** 31 + 28, seconds=3.0, control=False):
    from cloud_tpu.monitoring import tracing

    cell = manifest.Cell(CELL, tiny=True)
    with tracing.collecting():
        outcome, metrics, _ = bench_run.drive(
            cell, seed, seconds, 0, control=control,
            process_start=time.perf_counter())
    return outcome, metrics


def test_sound_run_is_correct_and_its_control_is_not():
    outcome, metrics = _drive(control=True)
    assert compare.judge(outcome.checks) and outcome.failed == 0
    assert set(metrics) == {"latency_p95_ms", "tpot_p95_ms", "setup_s"}
    assert outcome.control_checks
    assert all(not c[1] <= c[2] for c in outcome.control_checks)
    # The engine counted the state's rows beside the K/V rows.
    assert 0 < outcome.stats["state_row_steps_in_use"] <= \
        outcome.stats["state_row_steps_reserved"]


def test_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    def second_best(rng, logits, sample, **kw):
        return jnp.argsort(logits, axis=-1)[..., -2]

    monkeypatch.setattr(generation, "sample_logits", second_best)
    outcome, _ = _drive()
    assert not compare.judge(outcome.checks)


def test_state_left_to_the_next_request_is_not_correct(monkeypatch):
    """The fault this configuration brings: an insert that writes the
    prompt's K/V rows and leaves the slot's recurrent state as the last
    occupant left it."""
    from cloud_tpu.models import generation

    real = generation._write_prefill

    def rows_only(cache, left, start, config):
        return real(cache, {"k": left["k"], "v": left["v"],
                            "ssm": cache["ssm"][:, :1] * 0 + 1.0,
                            "conv": left["conv"]}, start, config)

    monkeypatch.setattr(generation, "_write_prefill", rows_only)
    outcome, _ = _drive()
    assert not compare.judge(outcome.checks)


def test_work_against_hand_counts():
    s = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 2, "num_hidden_layers": 3,
         "vocab_size": 10, "mamba_d_ssm": 6, "mamba_n_heads": 3,
         "mamba_d_head": 2, "mamba_d_state": 5, "mamba_n_groups": 1,
         "mamba_d_conv": 4, "mamba_chunk_size": 4}
    # conv over x | B | C = 6 + 5 + 5; in-projection z | xBC | dt.
    conv_dim, in_dim = 16, 6 + 16 + 3
    matmul = (2 * 8 * 8 + 2 * 8 * 4) + (8 * in_dim + 6 * 8) + 3 * 8 * 16
    small = 2 * 8 + 5 * conv_dim + 3 * 3 + 6
    assert work_hybrid.layer_matmul_params(s) == matmul
    assert work_hybrid.layer_small_params(s) == small
    assert work_hybrid.params(s) == 3 * (matmul + small) + 2 * 10 * 8 + 8
    recurrence, conv = 5 * 3 * 2 * 5, 2 * 4 * conv_dim
    assert work_hybrid.decode_flops(s, 6) == (
        2 * 3 * matmul + 3 * 4 * 8 * 7 + 3 * (recurrence + conv)
        + 2 * 8 * 10)
    # 5 tokens: 2 chunks of 4; a chunk 3 heads x (2 Q^2 (N + P) + 4 Q P N).
    ssd = 2 * 3 * (2 * 16 * (5 + 2) + 4 * 4 * 2 * 5)
    assert work_hybrid.prefill_flops(s, 5) == (
        2 * 3 * matmul * 5 + 3 * 4 * 8 * 15 + 3 * (ssd + conv * 5)
        + 2 * 8 * 10)
    state = 3 * (4 * 3 * 2 * 5 + 2 * 3 * conv_dim)
    assert work_hybrid.state_bytes_per_slot(s) == state
    assert work_hybrid.kv_bytes_per_row(s) == 2 * 3 * 2 * 2 * 2
    weights = 2 * (work_hybrid.params(s) - 10 * 8)
    assert work_hybrid.decode_step_bytes(s, 2.5, 40) == (
        weights + 2 * state * 2.5 + 48 * 40)
    # The published sizes, as ISSUE 28 reckons them: one block 430.1M, the
    # six-block stage 5,254.6M, a slot's state 25 MB.
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-stage.json")) as f:
        published = json.load(f)
    assert work_hybrid.layer_matmul_params(published) + \
        work_hybrid.layer_small_params(published) == 430_120_032
    assert work_hybrid.params(published) == 5_254_594_112
    assert work_hybrid.state_bytes_per_slot(published) == 25_350_144


def test_module_roofline_on_the_recorded_trace():
    from benchmarks.readers import module_roofline

    with open(os.path.join(os.path.dirname(__file__),
                           "small_trace.json")) as f:
        trace = json.load(f)
    v5e = peaks.peaks_for("TPU v5 lite")
    # ``jit_step`` ran 1.0 / 1.1 + 0.6 / 0.8 times in the window, for 1.6 s.
    steps = 1.0 / 1.1 + 0.6 / 0.8
    outcome = context.Outcome(
        window_start=0.0, window_s=1.0, end_to_end={}, attempted=1, failed=0,
        checks=[], memory_peak_bytes=0, trace=trace,
        work={"step": {"flops": 1.0, "bytes": 0.4 * 819e9 / steps},
              "busy": {"flops": 0.8 * 197e12 / steps, "bytes": 1.0},
              "empty": {"flops": 0, "bytes": 0}})
    read = module_roofline.read
    assert read({"pattern": "^jit_step", "work": "step"}, outcome,
                v5e) == pytest.approx(100 * 0.4 / 1.6)
    # Whichever of the two bounds is the larger.
    assert read({"pattern": "^jit_step", "work": "busy"}, outcome,
                v5e) == pytest.approx(100 * 0.8 / 1.6)
    # Nothing to read: nothing returned, never a 0.
    assert read({"pattern": "^jit_other", "work": "step"}, outcome,
                v5e) is None
    assert read({"pattern": "^jit_step", "work": "absent"}, outcome,
                v5e) is None
    assert read({"pattern": "^jit_step", "work": "empty"}, outcome,
                v5e) is None
    outcome.trace = None
    assert read({"pattern": "^jit_step", "work": "step"}, outcome,
                v5e) is None


def test_traced_work_counts_one_chunk_program_from_the_engines_counters():
    from benchmarks.adapters import serve_hybrid

    cell = manifest.Cell(CELL)
    sizes, settings = cell.config, cell.traffic["engine"]
    layers = sizes["num_hidden_layers"]
    # 10 chunk dispatches that met 20 live slots and 6,000 K/V rows each.
    delta = {"chunks": 10, "state_row_steps_in_use": 10 * 20 * layers,
             "kv_row_steps_in_use": 10 * 6000}
    work = serve_hybrid._traced_work(sizes, settings, [], (0.0, 1.0), delta)
    assert work["decode_chunk"]["bytes"] == 8 * work_hybrid.decode_step_bytes(
        sizes, 20, 6000)
    assert work["decode_chunk"]["flops"] == 8 * 20 * work_hybrid.decode_flops(
        sizes, 300)
    assert work["serve_flops"] == 0
    # An engine that counts no state rows (the parent's): nothing to read.
    for older in ({}, {"chunks": 10, "kv_row_steps_in_use": 60000}):
        assert "decode_chunk" not in serve_hybrid._traced_work(
            sizes, settings, [], (0.0, 1.0), older)
