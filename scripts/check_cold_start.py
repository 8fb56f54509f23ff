"""Cold-vs-warm first-dispatch check for the compile-ahead engine.

Runs a tiny ``Trainer.fit(compile_ahead=True, steps_per_dispatch=2)`` in
two fresh child processes under ``JAX_PLATFORMS=cpu`` sharing one
persistent compile cache directory: the first child compiles from
scratch (cold), the second warm-starts its executables from disk.  Each
child prints one JSON line with its first-dispatch timing breakdown
(``compile/ahead_wait`` + the first dispatch span, plus
``compile/backend_compile`` attribution); the parent prints a final
summary line::

    {"phase": "summary", "cold_first_dispatch_seconds": ...,
     "warm_first_dispatch_seconds": ..., ...}

A compile-ahead regression (compile no longer overlapping, tail
retraces, persistent cache silently off) shows up as the warm number
converging on the cold one.  Wired as a ``slow``-marked test in
``tests/unit/test_compile_cache.py`` so full runs see it.

This is a CPU rig: cold means an EMPTY cache, so unlike ``chip_smoke.py``
(one fixed directory, so that a later run hits) it makes a
fresh temporary directory by design, and hands it to its children as
``JAX_COMPILATION_CACHE_DIR`` — placed from outside, as on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

_CHILD_SOURCE = """
import json, time
import numpy as np
import jax, jax.numpy as jnp
import optax

from cloud_tpu.monitoring import tracing
from cloud_tpu.training import data
from cloud_tpu.training.trainer import Trainer


def loss(params, batch):
    l = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
    return l, {"loss": l}


rng = np.random.default_rng(0)
x = rng.normal(size=(8, 4)).astype(np.float32)
ds = data.ArrayDataset(
    {"x": x, "y": np.ones((8, 2), np.float32)}, batch_size=2
)
trainer = Trainer(
    loss, optax.sgd(0.1),
    init_fn=lambda r: {"w": jnp.zeros((4, 2), jnp.float32)},
)
trainer.init_state(jax.random.PRNGKey(0))
t0 = time.perf_counter()
with tracing.collecting() as col:
    trainer.fit(ds, epochs=1, steps_per_dispatch=2, compile_ahead=True)
fit_seconds = time.perf_counter() - t0
agg = col.aggregates()


def total(name):
    return agg.get(name, {}).get("total_seconds", 0.0)


print(json.dumps({
    "first_dispatch_seconds": round(
        total("compile/ahead_wait") + total("step/first_compile"), 4
    ),
    "backend_compile_seconds": round(total("compile/backend_compile"), 4),
    "fit_seconds": round(fit_seconds, 4),
}))
"""


def _run_child(env: dict, timeout: float) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_SOURCE],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"child rc={proc.returncode}: {(proc.stderr or '')[-500:]}"
        )
    for line in reversed(proc.stdout.splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise RuntimeError(f"child printed no JSON: {proc.stdout[-300:]!r}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--cache-dir", default=None,
        help="persistent cache dir shared by the two children "
        "(default: a fresh temp dir, deleted afterwards)",
    )
    parser.add_argument("--timeout", type=float, default=240.0)
    args = parser.parse_args(argv)

    cache_dir = args.cache_dir
    cleanup = cache_dir is None
    if cache_dir is None:
        cache_dir = tempfile.mkdtemp(prefix="cloud_tpu_cold_start_")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # Empty by design (see the module docstring), never a fixed path.
        JAX_COMPILATION_CACHE_DIR=cache_dir,
    )
    try:
        cold = _run_child(env, args.timeout)
        print(json.dumps({"phase": "cold", **cold}), flush=True)
        warm = _run_child(env, args.timeout)
        print(json.dumps({"phase": "warm", **warm}), flush=True)
        print(json.dumps({
            "phase": "summary",
            "cold_first_dispatch_seconds": cold["first_dispatch_seconds"],
            "warm_first_dispatch_seconds": warm["first_dispatch_seconds"],
            "cold_backend_compile_seconds": cold["backend_compile_seconds"],
            "warm_backend_compile_seconds": warm["backend_compile_seconds"],
            # The whole-fit wall-clock is where the warm start shows on
            # CPU (many small compiles served from disk); per-executable
            # deserialize ~ compile for tiny CPU programs.
            "cold_fit_seconds": cold["fit_seconds"],
            "warm_fit_seconds": warm["fit_seconds"],
            "cache_dir": cache_dir,
        }), flush=True)
    finally:
        if cleanup:
            shutil.rmtree(cache_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
