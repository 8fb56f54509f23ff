"""Unit tests for what remains of bench.py: one process that measures the
attached TPU and fails loudly (no probe child, no retry, no fallback)."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _main_source():
    src = open(os.path.join(REPO, "bench.py")).read()
    return src[src.index("def main"):]


def test_main_measures_fused_phase():
    """Static check: the fused context phase is wired into the phase
    list, after the headline."""
    main = _main_source()
    assert "_measure_fused" in main
    assert main.index("_measure_resnet(extras)") < main.index(
        "_measure_fused"
    )


def test_main_measures_fleet_qps_sweep_phase():
    """Static check: the open-loop fleet arrival sweep (ISSUE 14 —
    latency-under-load curves) is wired into the phase list, after the
    single-point fleet probe whose workload it extends."""
    main = _main_source()
    assert "_measure_fleet_qps_sweep" in main
    assert main.index("_measure_fleet,") < main.index(
        "_measure_fleet_qps_sweep"
    )


def test_main_runs_headline_before_gates():
    """Static order check: ResNet is measured before any gate or context
    phase."""
    main = _main_source()
    assert main.index("_measure_resnet(extras)") < main.index(
        "_check_group_norm"
    )
    assert main.index("_measure_resnet(extras)") < main.index(
        "_check_flash_attention"
    )


def test_no_phase_failure_is_survived():
    """Nothing in bench.py's main catches a phase's exception, starts a
    child, or re-measures on another path."""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "import subprocess" not in src
    assert "except " not in _main_source()


def test_the_chip_this_code_has_run_on_has_a_peak(bench):
    device = types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert bench._peak_bf16_tflops(device) == 197.0
    assert list(bench.PEAK_BF16_TFLOPS) == ["TPU v5 lite"]


@pytest.mark.parametrize(
    "kind", ["cpu", "TPU v9 hyper", None, "TPU v4", "TPU v6 lite"])
def test_unknown_device_kind_raises(bench, kind):
    """An MFU over a guessed (or zero) peak is not a measurement — and a
    chip nothing here has run on is as unknown as one that does not
    exist."""
    device = types.SimpleNamespace(device_kind=kind, platform="cpu")
    with pytest.raises(ValueError, match="no bf16 peak"):
        bench._peak_bf16_tflops(device)


def test_bench_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert proc.returncode != 0
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["ok"] is False and line["device"]["platform"] == "cpu"
    assert "metric" not in line
