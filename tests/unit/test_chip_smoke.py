"""CPU rehearsal of chip_smoke.py: the control flow, never the verdict.

The script proves the chip path, so off a TPU it must fail — at once with
its default (full-size) arguments, and after walking every phase with
``--tiny``.  Each case runs it as the driver would: a fresh process.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, *, devices=1, wrapper=None, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # Placed from outside, as on the chip machine: the rehearsal must not
    # write a cache into the checkout.
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed_cache")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    argv = [sys.executable, SCRIPT, *args]
    if wrapper is not None:
        argv = [sys.executable, "-c", wrapper, SCRIPT, *args]
    proc = subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=str(tmp_path))
    lines = proc.stdout.strip().splitlines()
    return proc, lines


def _last(lines):
    return json.loads(lines[-1])


def test_default_arguments_refuse_to_run_off_tpu(tmp_path):
    proc, lines = _run([], tmp_path, timeout=120)
    assert proc.returncode != 0
    assert len(lines) == 1, lines  # no phase ran at full size on the CPU
    verdict = _last(lines)
    assert verdict["ok"] is False
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_tiny_walks_both_phases_and_still_fails(tmp_path):
    proc, lines = _run(["--tiny"], tmp_path)
    out = "\n".join(lines)
    assert proc.returncode != 0, out
    verdict = _last(lines)
    assert verdict["ok"] is False and "not tpu" in verdict["error"]
    assert "train_phase passed" in out and "serve_phase passed" in out
    assert "multichip" not in out
    assert "tokens vs generate(): identical on 4 of 4 requests" in out
    assert '"pallas" vs generate(): identical on 4 of 4 requests' in out
    assert "group_norm fwd+grad vs _reference" in out
    assert all(ln.startswith("[cpu / cpu / 1] ") for ln in lines[:-1])
    # JAX_COMPILATION_CACHE_DIR is honoured: entries there, none in-repo.
    assert f"compile cache at {tmp_path / 'placed_cache'}" in lines[0]
    assert os.listdir(tmp_path / "placed_cache")


def test_tiny_chips_4_runs_only_the_multichip_phase(tmp_path):
    proc, lines = _run(["--tiny", "--chips", "4"], tmp_path,
                       devices=4)
    out = "\n".join(lines)
    assert proc.returncode != 0, out
    verdict = _last(lines)
    assert verdict["ok"] is False and verdict["device"]["count"] == 4
    assert "multichip_train_phase passed" in out
    assert "multichip_serve_phase passed" in out
    assert " train_phase passed" not in out and " serve_phase " not in out
    assert "every param on {4} devices" in out
    assert "serve tp=4: greedy tokens vs tp=1: identical on 4 of 4" in out
    assert 'tp=4 decode_kernel="pallas": greedy tokens vs tp=1: identical' in out


def test_chips_4_needs_four_devices(tmp_path):
    proc, lines = _run(["--tiny", "--chips", "4"], tmp_path, devices=2,
                       timeout=120)
    assert proc.returncode != 0
    assert _last(lines)["ok"] is False and len(lines) == 1


_FAULT_WRAPPER = """
import runpy, sys
from cloud_tpu.utils import faults
sys.argv = sys.argv[1:]
with faults.inject([{{"site": "{site}", "mode": "raise", "nth": 2}}]):
    runpy.run_path(sys.argv[0], run_name="__main__")
"""


@pytest.mark.parametrize("site,reached", [
    ("train.dispatch", None),
    ("serve.chunk", "train_phase passed"),
])
def test_a_failing_phase_is_not_survived(tmp_path, site, reached):
    """A fault injected into either phase ends the run non-zero with no
    verdict line at all: nothing on the smoke path catches and goes on."""
    proc, lines = _run(["--tiny"], tmp_path,
                       wrapper=_FAULT_WRAPPER.format(site=site))
    out = "\n".join(lines)
    assert proc.returncode != 0, out
    assert '"ok"' not in out
    assert "serve_phase passed" not in out
    if reached:
        assert reached in out


def test_unset_cache_variable_means_the_fixed_in_checkout_path():
    """Static: the one directory the script names is <repo>/.jax_cache —
    no mkdtemp, pid or time in a cache path — and .gitignore lists it."""
    src = open(SCRIPT).read()
    assert 'os.path.join(REPO, ".jax_cache")' in src
    for moving in ("mkdtemp", "getpid", "time.time()"):
        assert moving not in src
    assert "jax_compilation_cache_dir\"," not in src  # never set in code
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


@pytest.fixture(scope="module")
def smoke():
    """chip_smoke imported as a module (importing it runs nothing), as a
    tiny run would configure it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, module.Smoke(module._parse(["--tiny"]))


def test_greedy_check_accepts_greedy_and_refuses_wrong_tokens(smoke):
    """Where a request leaves the reference tokens, the teacher-forced
    check decides, on BOTH sides: tokens that are not greedy fail it,
    whichever side they are on."""
    import jax
    import numpy as np

    module, run = smoke
    z = run.sizes
    params = module.transformer.init(jax.random.PRNGKey(0), z.lm)
    prompts = run._prompts()[:2]
    greedy = run._generate_reference(params, prompts)
    assert "identical on 2 of 2" in run._check_greedy(
        "t", params, prompts, greedy, greedy)
    wrong = [(g + 1) % z.lm.vocab_size for g in greedy]
    with pytest.raises(module.SmokeFailure, match="its tokens are not"):
        run._check_greedy("t", params, prompts, wrong, greedy)
    with pytest.raises(module.SmokeFailure,
                       match="the reference's tokens are not"):
        run._check_greedy("t", params, prompts, greedy, wrong)


@pytest.mark.parametrize("margin,passes", [
    (2.0 ** -7, True),    # inside the tie width, on both sides
    (2.0 ** -5, False),   # the width the chip's widest swap (0.0093) needs
                          # is 2**-6; twice that is a second-best token
], ids=["near-tie", "second-best"])
def test_greedy_check_tie_width(smoke, monkeypatch, margin, passes):
    import numpy as np

    module, run = smoke
    assert module.TIE == 2.0 ** -6
    monkeypatch.setattr(
        run, "_forced_margins",
        lambda params, prompt, tokens: np.full(len(tokens), margin))
    got, want = [np.array([1, 2, 3])], [np.array([1, 5, 3])]
    prompts = [np.array([7, 7])]
    if passes:
        report = run._check_greedy("t", None, prompts, got, want)
        assert "identical on 0 of 1" in report and "at token 1" in report
        assert "both sides greedy" in report
    else:
        with pytest.raises(module.SmokeFailure, match="not greedy"):
            run._check_greedy("t", None, prompts, got, want)
