"""The reader that sums a program's device time by program scope
(``benchmarks/readers/scope_device_time.py`` over
``benchmarks/harness/xplane_scopes.py``) on a trace written by hand, the
loader on a trace file made here, and the files of the metrics that read
through it."""

import copy
import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import context, manifest, xplane_scopes  # noqa: E402
from benchmarks.readers import scope_device_time  # noqa: E402
from cloud_tpu.models import layers  # noqa: E402

CHUNK, INSERT = "^jit_chunk_fn", "^jit_insert_fn"
PSEUDO = (xplane_scopes.CONTROL, xplane_scopes.UNSCOPED)


@pytest.fixture(scope="module")
def scope_trace():
    with open(os.path.join(os.path.dirname(__file__),
                           "scope_trace.json")) as f:
        return json.load(f)


def _table(trace, pattern):
    return xplane_scopes.merged(
        xplane_scopes.scope_tables(trace, layers.SCOPES), pattern)


def _outcome(trace, **work):
    return context.Outcome(
        window_start=100.0, window_s=10.0, end_to_end={}, attempted=1,
        failed=0, checks=[], memory_peak_bytes=0, work=work, trace=trace)


@pytest.fixture
def reading(monkeypatch):
    """``read`` over a given trace, as over the one a run wrote."""
    def arm(trace):
        monkeypatch.setattr(xplane_scopes, "traced", lambda: trace)
        scope_device_time._tables.cache_clear()
        monkeypatch.setattr(scope_device_time, "_REFUSED", set())
    yield arm
    scope_device_time._tables.cache_clear()


def test_own_time_by_scope_adds_up_to_the_programs_time(scope_trace):
    table = _table(scope_trace, CHUNK)
    # The first fusion is cut by the window to 0.1 s; the loop's 0.7 s are
    # the three operations inside it and 0.1 s of its own; the copy has no
    # name at all.
    assert table["seconds"] == pytest.approx({
        "embed": 0.1, "mlp": 0.3, "attn_read": 0.2, "head": 0.1,
        xplane_scopes.CONTROL: 0.1, xplane_scopes.UNSCOPED: 0.1})
    assert table["unscoped_ops"] == pytest.approx({"%copy.9 copy": 0.1})
    # 0.9 of the execution's 1.0 s lies inside the window, and all of it
    # was some operation's own.
    assert table["executions"] == pytest.approx(0.9)
    assert table["module_seconds"] == pytest.approx(0.9)
    assert sum(table["seconds"].values()) == pytest.approx(0.9)


def test_two_programs_of_one_module_name_are_summed(scope_trace):
    table = _table(scope_trace, INSERT)
    # The conditional's own time is less the kernel inside it; of a name
    # with two scopes in it the last counts; jit_other's fusion is no
    # part of it.
    assert table["seconds"] == pytest.approx({
        "attn_proj": 0.15, "attn_read": 0.15, "mlp": 0.2,
        "cache_write": 0.1, xplane_scopes.CONTROL: 0.1})
    assert table["executions"] == pytest.approx(2.0)
    assert sum(table["seconds"].values()) == pytest.approx(
        table["module_seconds"]) == pytest.approx(0.7)


def test_reader_divides_by_executions_or_by_work(scope_trace, reading,
                                                 capsys):
    reading(scope_trace)
    outcome = _outcome(scope_trace, prompt_ktok=3.5)
    decode = {"module": CHUNK, "per": "count", "scale": 125.0}
    assert scope_device_time.read(
        {**decode, "scopes": ["mlp"]}, outcome, None) == pytest.approx(
            125.0 * 0.3 / 0.9)
    assert scope_device_time.read(
        {**decode, "scopes": list(PSEUDO)}, outcome,
        None) == pytest.approx(125.0 * 0.2 / 0.9)
    assert scope_device_time.read(
        {"module": INSERT, "scopes": ["attn_proj", "mlp"],
         "per": "prompt_ktok", "scale": 1000.0}, outcome,
        None) == pytest.approx(1000.0 * 0.35 / 3.5)
    # Work the adapter did not count, a program that did not run, an
    # untraced run: nothing, never a 0.
    assert scope_device_time.read(
        {"module": INSERT, "scopes": ["mlp"], "per": "no_such_work"},
        outcome, None) is None
    assert scope_device_time.read(
        {"module": "^jit_nothing", "scopes": ["mlp"], "per": "count"},
        outcome, None) is None
    assert scope_device_time.read(
        {**decode, "scopes": ["mlp"]}, _outcome(None), None) is None
    printed = capsys.readouterr().out.splitlines()
    # One line a program that took a hundredth of the window, whatever the
    # number of metrics read; two programs of one name share theirs.
    lines = [line for line in printed if line.startswith("scopes jit_")]
    assert [line.split(":")[0] for line in lines] == [
        "scopes jit_chunk_fn", "scopes jit_chunk_fn",
        "scopes jit_insert_fn", "scopes jit_other"]
    assert "mlp=333.3333" in lines[0]
    assert "sum 1000.0000 of module 1000.0000 ms" in lines[0]
    assert lines[1] == ("scopes jit_chunk_fn: unscoped holds "
                        "%copy.9 copy=111.1111")
    assert "2.00 executions" in lines[2]


def test_executables_compiled_before_the_scopes_give_no_value(
        scope_trace, reading, capsys):
    stale = copy.deepcopy(scope_trace)
    for op in stale["devices"][0]["ops"]:
        op[3] = "jit(chunk_fn)/while/body/closed_call/dot_general:"
    reading(stale)
    for scopes in (["mlp"], ["unscoped", "control"]):
        assert scope_device_time.read(
            {"module": CHUNK, "scopes": scopes, "per": "count"},
            _outcome(stale), None) is None
    assert capsys.readouterr().out.count(
        "no value: 89% of the device time is unscoped") == 1


def test_a_program_without_scopes_gives_no_value(scope_trace, reading,
                                                 monkeypatch, capsys):
    """The benchmark's files laid over a parent commit that has no list."""
    reading(scope_trace)
    monkeypatch.delattr(layers, "SCOPES")
    assert scope_device_time.read(
        {"module": CHUNK, "scopes": ["mlp"], "per": "count"},
        _outcome(scope_trace), None) is None
    assert "the program has no scopes" in capsys.readouterr().out


def test_loader_reads_the_framework_name_from_the_events_metadata(tmp_path):
    space = xplane_scopes._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = "bench/window"
    thread = host.lines.add(name="python", timestamp_ns=10_000_000_000)
    thread.events.add(metadata_id=1, offset_ps=0,
                      duration_ps=2_000_000_000_000)
    device = space.planes.add(name="/device:TPU:0")
    for key, name in [(1, "tf_op"), (2, "hlo_category"),
                      (3, "jit(chunk_fn)/while/body/mlp/dot_general:")]:
        device.stat_metadata.add(key=key).value.name = name
    fusion = device.event_metadata.add(key=7).value
    fusion.name = "%fusion.235 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    fusion.stats.add(metadata_id=2, str_value="convolution fusion")
    fusion.stats.add(metadata_id=1, ref_value=3)
    copied = device.event_metadata.add(key=8).value
    copied.name = "%copy.9 = bf16[8]{0} copy(bf16[8]{0} %x)"
    device.event_metadata.add(key=9).value.name = "jit_chunk_fn(11)"
    named = device.event_metadata.add(key=10).value
    named.name = "%fusion.3 = s32[8]{0} fusion(bf16[8]{0} %w), kind=kLoop"
    named.stats.add(metadata_id=1, str_value="jit(chunk_fn)/head/argmax:")
    ops = device.lines.add(name="XLA Ops", timestamp_ns=9_000_000_000)
    ops.events.add(metadata_id=7, offset_ps=1_100_000_000_000,
                   duration_ps=300_000_000_000)
    ops.events.add(metadata_id=8, offset_ps=1_400_000_000_000,
                   duration_ps=100_000_000_000)
    ops.events.add(metadata_id=10, offset_ps=1_500_000_000_000,
                   duration_ps=200_000_000_000)
    modules = device.lines.add(name="XLA Modules",
                               timestamp_ns=9_000_000_000)
    modules.events.add(metadata_id=9, offset_ps=1_100_000_000_000,
                       duration_ps=600_000_000_000)
    space.planes.add(name="/device:TPU:0 SparseCore")  # no device of ours
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(space.SerializeToString())

    trace = xplane_scopes.load(str(path))
    assert trace["window"] == pytest.approx([10.0, 12.0])
    (only,) = trace["devices"]
    assert [op[0].split(" ")[0] for op in only["ops"]] == [
        "%fusion.235", "%copy.9", "%fusion.3"]
    assert [op[3] for op in only["ops"]] == [
        "jit(chunk_fn)/while/body/mlp/dot_general:", "",
        "jit(chunk_fn)/head/argmax:"]
    assert only["ops"][0][1:3] == pytest.approx([10.1, 0.3])
    assert only["modules"] == [["jit_chunk_fn(11)", pytest.approx(10.1),
                                pytest.approx(0.6)]]
    table = _table(trace, CHUNK)
    assert table["seconds"] == pytest.approx(
        {"mlp": 0.3, "head": 0.2, xplane_scopes.UNSCOPED: 0.1})


SCOPE_METRICS = sorted(
    path for path in manifest.layer_metric_files()
    if json.load(open(path))["reader"] == "scope_device_time")


def test_the_scope_metrics_are_the_sixteen():
    assert len(SCOPE_METRICS) == 16


@pytest.mark.parametrize(
    "path", SCOPE_METRICS, ids=[os.path.basename(p) for p in SCOPE_METRICS])
def test_scope_metric_names_a_reader_scopes_and_a_cell_that_exist(path):
    with open(path) as f:
        spec = json.load(f)
    assert os.path.basename(path) == spec["name"] + ".json"
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "readers", spec["reader"] + ".py"))
    args = spec["args"]
    assert args["scopes"] and set(args["scopes"]) <= set(layers.SCOPES) | set(
        PSEUDO)
    assert args["module"] in (CHUNK, INSERT)
    assert (args["per"], args["scale"]) == (
        ("count", 125.0) if args["module"] == CHUNK
        else ("prompt_ktok", 1000.0))
    declared = manifest.load_manifest()
    cells = {w["name"] for w in declared["workloads"]}
    assert spec["workloads"] and set(spec["workloads"]) <= cells
    (entry,) = [m for m in declared["per_layer"]
                if m["name"] == spec["name"]]
    assert entry == {key: spec[key] for key in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")}
    for cell in spec["workloads"]:
        reports = {m["name"] for m in declared["end_to_end"]
                   if cell in m.get("workloads", [cell])}
        assert spec["moves"] in reports
