"""The benchmark's own tests: CPU only, no chip, no child process.

The yardstick (traffic, tail and rate arithmetic, the trace's reduction,
the counts of operations, the manifest) and the two proofs ``correct``
rests on: the control — the reference in the precision below — comes out
as not correct, and so does a run whose timed path is broken underneath.
"""

import json
import math
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import (compare, context, manifest, peaks,  # noqa: E402
                                stats, traffic, work, xplane)

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
#: Laid out as the repo's root: the cell whose files are written and whose
#: program does not run yet at its size (PERF.md section 7).  Its tiny
#: sizes run here, so the train adapter, its reference and its comparison
#: stay proven for the PR that adds the cell.
PROPOSED = os.path.join(ROOT, "tests", "benchmarks", "proposed")
PROPOSED_CELLS = [w["name"] for w in
                  manifest.load_manifest(PROPOSED)["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(autouse=True)
def _interpret_kernels(monkeypatch):
    monkeypatch.setenv("CLOUD_TPU_FLASH_FORCE_INTERPRET", "1")


# -- traffic ---------------------------------------------------------------


CHAT = {"arrivals": {"process": "stratified_exponential", "rate_per_s": 5.0},
        "prompt_tokens": {"dist": "loguniform", "min": 32, "max": 512},
        "output_tokens": {"dist": "loguniform", "min": 16, "max": 128}}


def test_generator_repeats_from_a_seed_and_matches_its_file():
    a = traffic.make_requests(CHAT, 40, 2 ** 31 + 5, 64000)
    b = traffic.make_requests(CHAT, 40, 2 ** 31 + 5, 64000)
    assert len(a) == 200  # rate x seconds
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["due_s"] == y["due_s"] for x, y in zip(a, b))
    dues = [r["due_s"] for r in a]
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 40
    assert 39 < dues[-1]
    lens = [len(r["prompt"]) for r in a]
    outs = [r["max_new_tokens"] for r in a]
    assert min(lens) >= 32 and max(lens) <= 512
    assert min(outs) >= 16 and max(outs) <= 128
    # log-uniform: the median sits at the geometric mean of the ends.
    assert abs(np.median(lens) - math.sqrt(32 * 512)) < 8
    assert all(r["prompt"].min() >= 1 for r in a)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.make_requests(CHAT, 40, 1, 64000)
    b = traffic.make_requests(CHAT, 40, 2, 64000)
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == sorted(
        r["max_new_tokens"] for r in b)

    def gaps(rs):
        return sorted(np.round(np.diff([0] + [r["due_s"] for r in rs]), 9))

    assert gaps(a) == gaps(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def test_blocks_give_every_stretch_of_the_window_the_same_work():
    mix = dict(CHAT, order={"block_requests": 25})
    a = traffic.make_requests(mix, 40, 1, 64000)
    b = traffic.make_requests(mix, 40, 2, 64000)
    free = traffic.make_requests(CHAT, 40, 1, 64000)
    outs = lambda rs: [r["max_new_tokens"] for r in rs]
    # The same set as without blocks, in another order for each seed.
    assert sorted(outs(a)) == sorted(outs(b)) == sorted(outs(free))
    assert outs(a) != outs(b)
    # Each block of 25 was dealt every eighth quantile: its total work and
    # its span lie close to an eighth of the whole.
    for rs in (a, b):
        sums = [sum(outs(rs)[i:i + 25]) for i in range(0, 200, 25)]
        assert max(sums) - min(sums) < 0.02 * np.mean(sums)
        ends = [rs[i + 24]["due_s"] for i in range(0, 200, 25)]
        assert np.allclose(np.diff([0] + ends), 5.0, atol=0.35)
    spread = lambda rs: np.ptp([sum(outs(rs)[i:i + 25])
                                for i in range(0, 200, 25)])
    assert spread(a) < spread(free)


@pytest.mark.parametrize("arrivals, count, distinct", [
    ({"process": "backlog", "requests_per_s": 8.0}, 80, 1),
    ({"process": "stratified_exponential", "rate_per_s": 5.0}, 50, 50),
])
def test_backlog_and_open_loop(arrivals, count, distinct):
    due = traffic.draw_arrivals(arrivals, 10, np.random.default_rng(0))
    assert len(due) == count and len(set(due.tolist())) == distinct
    assert due.max() < 10


def test_a_process_or_distribution_the_generator_lacks_is_an_error():
    # Named for what it is: independent arrivals are not on offer.
    with pytest.raises(ValueError):
        traffic.draw_arrivals({"process": "poisson", "rate_per_s": 5.0}, 10,
                              np.random.default_rng(0))
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "uniform", "min": 1, "max": 9}, 10,
                             np.random.default_rng(0))


# -- tails and rates -------------------------------------------------------


def test_tail_counts_failures_as_the_worst_and_a_stall_moves_it():
    lat = [10.0] * 95 + [20.0] * 5
    assert stats.percentile(lat, 95) == pytest.approx(
        np.percentile(lat, 95))
    assert stats.tail_with_failures(lat, 0) < 20.0
    assert stats.tail_with_failures(lat, 6) == math.inf
    stalled = [10.0] * 90 + [500.0] * 10  # a stall delays a tenth
    assert stats.tail_with_failures(stalled, 0) == 500.0


def test_rate_is_over_the_whole_window():
    steady = stats.rate(100 * 256, 10.0)
    stalled = stats.rate(80 * 256, 10.0)  # two seconds of the ten stalled
    assert stalled == pytest.approx(0.8 * steady)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


# -- the trace's reduction, on a small recorded trace ----------------------


@pytest.fixture(scope="module")
def small_trace():
    with open(os.path.join(os.path.dirname(__file__),
                           "small_trace.json")) as f:
        return json.load(f)


def test_trace_reduction(small_trace):
    # Clipped to [10, 12]: 0.1 + 0.3 + 0.25 + 0.3 (0.05 shared) + 0.4 + 0.1.
    assert xplane.busy_seconds(small_trace) == pytest.approx(1.4)
    assert xplane.window_seconds(small_trace) == pytest.approx(2.0)
    seconds, count = xplane.device_seconds(small_trace, r"^custom-call")
    assert (seconds, count) == (pytest.approx(0.7), 2)
    seconds, count = xplane.device_seconds(small_trace, "^jit_step",
                                           "modules")
    assert seconds == pytest.approx(1.0 + 0.6) and count == 2
    # Executions inside the window: 1.0 of the first's 1.1 s and 0.6 of
    # the second's 0.8 s lie inside, so 1.659 steps ran there, not 2.
    assert xplane.executions(small_trace, "^jit_step") == pytest.approx(
        1.0 / 1.1 + 0.6 / 0.8)
    assert xplane.executions(small_trace, "^jit_other") == 0.0
    # An operation's own time, under its program's name: fusion.1 is 0.1
    # + 0.25 in the window, less the 0.1 of the copy that ran inside it
    # and the 0.05 it shares with the convolution.
    top = dict(xplane.top_ops(small_trace))
    assert top["jit_step/custom-call.7"] == pytest.approx(0.7)
    assert top["jit_step/fusion.1"] == pytest.approx(0.2)
    assert sum(top.values()) == pytest.approx(xplane.busy_seconds(
        small_trace))
    gaps = dict(xplane.idle_by_host_span(small_trace))
    # 11.0-11.4 lies under step/data; 10.4-10.5 and 11.8-11.9 under nothing.
    assert gaps["step/data"] == pytest.approx(0.4)
    assert gaps["(no span)"] == pytest.approx(0.2)


def test_readers_on_the_recorded_trace(small_trace):
    from benchmarks.readers import (span_sum, stats_key, trace_device_time,
                                    trace_idle_share, trace_roofline,
                                    window_mfu)

    v5e = peaks.peaks_for("TPU v5 lite")
    outcome = context.Outcome(
        window_start=100.0, window_s=10.0, end_to_end={}, attempted=1,
        failed=0, checks=[], memory_peak_bytes=0,
        spans=[("compile/lower", 90.0, 2.0, {}),
               ("step/data", 101.0, 0.5, {}), ("step/data", 102.0, 0.1, {}),
               ("step/compute", 101.5, 0.1, {}),
               ("step/compute", 102.5, 0.1, {}),
               ("step/callbacks", 102.6, 0.4, {}),
               ("bench/step_wait", 102.7, 0.3, {})],
        stats={"useful_decode_tokens": 30, "decode_slot_steps": 40},
        work={"k": {"flops": 0.7 * 197e12 / 2, "bytes": 1.0},
              "flops": 197e12 / 2},
        trace=small_trace)
    assert trace_idle_share.read({}, outcome, v5e) == pytest.approx(30.0)
    assert trace_roofline.read({"pattern": "^custom-call", "work": "k"},
                               outcome, v5e) == pytest.approx(50.0)
    # Nothing to read: nothing returned, never a 0.
    assert trace_roofline.read({"pattern": "^nothing", "work": "k"},
                               outcome, v5e) is None
    # Work given for ONE execution of a program: the trace counts them.
    steps = 1.0 / 1.1 + 0.6 / 0.8
    outcome.work["k_step"] = {"flops": 0.7 * 197e12 / 2 / steps,
                              "bytes": 1.0}
    assert trace_roofline.read(
        {"pattern": "^custom-call", "work": "k_step",
         "per_module": "^jit_step"}, outcome, v5e) == pytest.approx(50.0)
    assert trace_roofline.read(
        {"pattern": "^custom-call", "work": "k_step",
         "per_module": "^jit_other"}, outcome, v5e) is None
    assert trace_device_time.read(
        {"pattern": "^jit_step", "line": "modules", "per": "count",
         "scale": 1000.0}, outcome, v5e) == pytest.approx(1600.0 / steps)
    assert window_mfu.read({"work": "flops"}, outcome,
                           v5e) == pytest.approx(25.0)
    assert span_sum.read({"names": ["compile/lower"], "phase": "setup"},
                         outcome, v5e) == pytest.approx(2.0)
    assert span_sum.read(
        {"names": ["step/data"], "phase": "window", "per": "step/compute",
         "scale": 1000.0}, outcome, v5e) == pytest.approx(300.0)
    # The benchmark's own wait for the device is no host work.
    assert span_sum.read(
        {"names": ["step/data", "step/callbacks"],
         "minus": ["bench/step_wait"], "phase": "window",
         "per": "step/compute", "scale": 1000.0},
        outcome, v5e) == pytest.approx(350.0)
    assert stats_key.read({"key": "useful_decode_tokens",
                           "over": "decode_slot_steps", "scale": 100.0},
                          outcome, v5e) == pytest.approx(75.0)
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9")


# -- operations and bytes, against counts made by hand ---------------------


def test_work_against_hand_counts():
    rn50 = {"stage_sizes": [3, 4, 6, 3], "width": 64, "num_classes": 1000}
    # The published 4.1 GMACs of ResNet-50 at 224x224 (v1.5: 4.09).
    assert work.resnet_forward_macs(rn50, 224) == 4_089_184_256
    tiny = {"stage_sizes": [1], "width": 4, "num_classes": 3}
    # 8x8 input: stem 7x7x3x4 on 4x4; pool to 2x2; 1x1 4->4, 3x3 4->4,
    # 1x1 4->16 and the projection 1x1 4->16 on 2x2; dense 16->3.
    by_hand = (16 * 7 * 7 * 3 * 4 + 4 * (4 * 4 + 9 * 4 * 4 + 4 * 16 + 4 * 16)
               + 16 * 3)
    assert work.resnet_forward_macs(tiny, 8) == by_hand
    assert work.resnet_train_flops_per_sample(tiny, 8) == 6 * by_hand
    assert work.resnet_group_norm_elements(tiny, 8) == (
        16 * 4 + 4 * (4 + 4 + 16 + 16))
    b7 = {"hidden_size": 4096, "intermediate_size": 11008,
          "num_attention_heads": 32, "head_dim": 128,
          "num_hidden_layers": 32, "vocab_size": 64000}
    assert work.decoder_params(b7) == 7_000_293_376  # 7.0B, norms apart
    d = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
         "head_dim": 4, "num_hidden_layers": 3, "vocab_size": 10}
    layers = 3 * (4 * 8 * 8 + 3 * 8 * 16)
    assert work.decoder_layer_params(d) == layers
    assert work.prefill_flops(d, 5) == (
        2 * layers * 5 + 3 * 4 * 8 * 15 + 2 * 8 * 10)
    assert work.decode_flops(d, 6) == 2 * layers + 3 * 4 * 8 * 7 + 2 * 8 * 10
    assert work.flash_forward_call(d, 4) == (4 * 8 * 10, 4 * 4 * 8 * 2)


def test_worst_leaf_and_negligible_gradients():
    ref = {"loss": [2.0, 1.9, 1.8], "grad": [1.0, 2.0, 1e-6, 4.0],
           "change": [0.1, 0.2, 0.5, 0.4]}
    same = compare.training_gaps(ref, ref)
    assert all(same[k] == 0.0 for k in (
        "loss_gap", "grad_gap", "change_gap", "grad_gap_median",
        "change_gap_median"))
    got = {"loss": [2.0, 1.9, 1.89], "grad": [1.0, 2.0, 2e-6, 5.0],
           "change": [0.1, 0.2, 0.0, 0.4]}
    gaps = compare.training_gaps(got, ref)
    assert gaps["loss_gap"] == pytest.approx(0.05)
    # A leaf that is all but zero is held against the median leaf.
    assert gaps["grad_gap"] == pytest.approx(0.25)
    assert gaps["worst"]["grad"] == 3 and gaps["grad_gap_median"] < 1e-6
    # Leaf 2 moves by round-off alone in the reference: left out.
    assert gaps["change_gap"] == 0.0 and gaps["change_gap_median"] == 0.0
    # A leaf turned round keeps its norm; only the difference's norm sees
    # it: 2 x |(3, 4)| against |(3, 4)|; the other leaf reads nought.
    ref = {"loss": [1.0], "grad": [np.array([3.0, 4.0]), np.array([1.0])],
           "change": [np.array([3.0, 4.0]), np.array([1.0])]}
    got = {"loss": [1.0], "grad": [np.array([-3.0, -4.0]), np.array([1.0])],
           "change": ref["change"]}
    gaps = compare.training_gaps(got, ref)
    assert gaps["grad_gap"] == 0.0 and gaps["grad_error"] == pytest.approx(2)
    assert gaps["grad_error_median"] == pytest.approx(1.0)
    assert gaps["grad_error_least"] == 0.0
    assert gaps["change_error"] == gaps["change_error_least"] == 0.0
    assert not compare.judge([("x", float("nan"), 1.0)])
    assert compare.judge([("x", 0.5, 1.0)])
    assert not compare.judge([])  # nothing compared: not correct


def test_every_leaf_is_held_to_the_error_its_place_allows():
    rng = np.random.default_rng(0)
    # 9 leaves; the stated precision errs by 30% on the first (far from the
    # loss) down to 1% on the last (the head).
    ref = [rng.standard_normal(64) for _ in range(9)]
    share = np.geomspace(0.3, 0.01, 9)

    def erring(scale, seed):
        noise = np.random.default_rng(seed)
        out = []
        for leaf, e in zip(ref, share):
            n = noise.standard_normal(64)
            out.append(leaf + scale * e * np.linalg.norm(leaf)
                       * n / np.linalg.norm(n))
        return out

    def side(leaves):
        return {"loss": [1.0], "grad": leaves, "change": leaves}

    reference, baseline = side(ref), side(erring(1.0, 1))
    sound = compare.training_gaps(side(erring(1.0, 2)), reference, baseline)
    assert sound["grad_error_over_baseline"] == pytest.approx(1.0)
    assert "grad_error_over_baseline" not in compare.training_gaps(
        side(erring(1.0, 2)), reference)
    # One kernel's gradient a quarter too large, at a leaf near the loss:
    # its error is still under the first leaf's sound one, so the worst
    # leaf's number and the least leaf's do not move and the median leaf's
    # stays inside any limit with room above a sound run; held to its own
    # place the leaf reads ten times what it may.
    faulty = erring(1.0, 2)
    faulty[6] = 1.25 * ref[6]
    got = compare.training_gaps(side(faulty), reference, baseline)
    assert got["grad_error"] <= sound["grad_error"] + 1e-9
    assert got["grad_error_median"] < 2 * sound["grad_error_median"]
    assert got["grad_error_least"] == pytest.approx(
        sound["grad_error_least"])
    assert got["grad_error_over_baseline"] == pytest.approx(
        0.25 / share[6], rel=0.05)
    assert got["worst"]["grad_over_baseline"] == 6
    # A leaf the baseline happens to get right is held to a share of the
    # median leaf's error, not to nothing.
    lucky = side(erring(1.0, 1))
    lucky["grad"][4] = ref[4].copy()
    got = compare.training_gaps(side(erring(1.0, 2)), reference, lucky)
    assert 5.0 < got["grad_error_over_baseline"] < 30.0


# -- the manifest ----------------------------------------------------------


@pytest.mark.parametrize("root", [ROOT, PROPOSED],
                         ids=["BENCHMARK.json", "proposed"])
def test_manifest_is_consistent(root):
    listing = manifest.load_manifest(root)
    assert set(listing) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}
    for key in ("command", "paths", "run_seconds"):
        assert listing[key] == MANIFEST[key]
    e2e = {m["name"]: m for m in listing["end_to_end"]}
    configs = {c["name"]: c for c in listing["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for metric in listing["end_to_end"] + listing["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for m in listing["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for config in configs.values():
        body = json.load(open(os.path.join(root, config["file"])))
        assert config["file"].startswith("benchmarks/configs/")
        assert body["reduced"] == config["reduced"]
        assert body["source"].startswith(config["source"][:30])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "adapters", body["entry"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "references", body["reference"] + ".py"))
    assert {w["config"] for w in listing["workloads"]} == set(configs)
    for workload in listing["workloads"]:
        name = workload["name"]
        cell = manifest.Cell(name, root=root)
        assert NAME.match(name) and cell.chips == 1
        assert name == f"{cell.workload['config']}.{cell.workload['traffic']}"
        assert len(cell.workload["why"]) <= 200
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, name
        for metric in cell.per_layer:
            # Every cell of a layer metric reports the metric it moves.
            assert metric["moves"] in reported, (name, metric["name"])
            spec = cell.layer_metrics[metric["name"]]
            for key in ("unit", "layer", "moves", "better", "source",
                        "workloads"):
                assert spec[key] == metric[key], (metric["name"], key)
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", "readers", spec["reader"] + ".py"))
        assert cell.traffic["why"]
        # A cell of the benchmark has its limits; a proposed one has none
        # until its program runs at its size, and says so.
        if root == ROOT:
            assert set(cell.traffic["limits"])
        else:
            assert cell.traffic["limits"] is None
            assert cell.traffic["limits_unset"]
            assert set(manifest.Cell(name, root=root,
                                     tiny=True).traffic["limits"])
    listed = {m["name"] for m in listing["per_layer"]}
    on_disk = {json.load(open(p))["name"]
               for p in manifest.layer_metric_files(root)}
    assert listed == on_disk
    # What is proposed merges into the benchmark without a clash.
    if root != ROOT:
        for key in ("configs", "workloads", "per_layer"):
            assert not ({m["name"] for m in listing[key]}
                        & {m["name"] for m in MANIFEST[key]})
        for m in listing["end_to_end"]:
            assert m["name"] not in {x["name"] for x in
                                     MANIFEST["end_to_end"]} or m == next(
                x for x in MANIFEST["end_to_end"] if x["name"] == m["name"])


# -- off the chip: no result ------------------------------------------------


def test_no_result_line_off_the_chip(capsys):
    code = bench_run.main(["--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == ""


# -- the control and the planted faults, at a size a test can hold ----------


def _drive(name, seed=2 ** 31 + 11, seconds=1.0, control=False):
    from cloud_tpu.monitoring import tracing

    cell = manifest.Cell(
        name, root=PROPOSED if name in PROPOSED_CELLS else ROOT, tiny=True)
    with tracing.collecting():
        outcome, metrics, _ = bench_run.drive(
            cell, seed, seconds, 0, control=control,
            process_start=__import__("time").perf_counter())
    return outcome, metrics


TRAIN = next(n for n in PROPOSED_CELLS
             if manifest.Cell(n, root=PROPOSED).config["entry"]
             == "train_fit")
SERVE = [n for n in CELLS if manifest.Cell(n).config["entry"]
         == "serve_engine"]


def test_train_sound_run_is_correct_and_its_control_is_not():
    outcome, metrics = _drive(TRAIN, control=True)
    assert compare.judge(outcome.checks), outcome.checks
    assert set(metrics) == {"train_samples_per_s", "setup_s"}
    assert metrics["train_samples_per_s"]["value"] > 0
    for label in ("fp8", "half_batch"):
        failed = [c for c in outcome.control_checks
                  if c[0].startswith(label) and c[2] is not None
                  and not c[1] <= c[2]]
        assert failed, (label, outcome.control_checks)


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    from cloud_tpu.training import train as train_lib

    real = train_lib.make_train_step

    def broken(*args, **kw):
        import jax

        step = real(*args, **kw)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state.replace(step=state.step + 1), metrics

        return jax.jit(unchanged)

    monkeypatch.setattr(train_lib, "make_train_step", broken)
    outcome, _ = _drive(TRAIN)
    assert not compare.judge(outcome.checks)
    assert dict((c[0], c[1]) for c in outcome.checks)[
        "change_gap_median"] == pytest.approx(1.0)


def test_train_half_the_batch_left_out_is_not_correct(monkeypatch):
    from cloud_tpu.models import resnet

    real = resnet.loss_fn

    def half(params, batch, *args, **kw):
        n = batch["label"].shape[0] // 2
        return real(params, {k: v[:n] for k, v in batch.items()}, *args,
                    **kw)

    monkeypatch.setattr(resnet, "loss_fn", half)
    outcome, _ = _drive(TRAIN)
    assert not compare.judge(outcome.checks)


@pytest.mark.parametrize("name", SERVE)
def test_serve_sound_run_is_correct_and_its_control_is_not(name):
    outcome, metrics = _drive(name, seconds=2.0, control=True)
    assert compare.judge(outcome.checks) and outcome.failed == 0
    assert "setup_s" in metrics and len(metrics) >= 2
    assert all(not c[1] <= c[2] for c in outcome.control_checks)


def test_serve_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import jax.numpy as jnp

    from cloud_tpu.models import generation

    def second_best(rng, logits, sample, **kw):
        return jnp.argsort(logits, axis=-1)[..., -2]

    monkeypatch.setattr(generation, "sample_logits", second_best)
    outcome, _ = _drive(SERVE[0], seconds=2.0)
    assert not compare.judge(outcome.checks)


def test_serve_answer_altered_after_it_is_produced_is_not_correct(
        monkeypatch):
    from cloud_tpu.serving import engine as engine_lib

    real = engine_lib.ServingEngine._retire_slot

    def truncated(self, slot, exc=None):
        entry = self._slot_table[slot]
        if entry is not None and len(entry.tokens) > 1:
            entry.tokens[-1] = (entry.tokens[-1] + 1) % self.config.vocab_size
        return real(self, slot, exc)

    monkeypatch.setattr(engine_lib.ServingEngine, "_retire_slot", truncated)
    outcome, _ = _drive(SERVE[0], seconds=2.0)
    checks = {c[0]: c[1] for c in outcome.checks}
    assert checks["answers_wrong"] > 0 and not compare.judge(outcome.checks)
