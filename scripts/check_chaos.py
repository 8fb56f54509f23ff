"""End-to-end chaos check on CPU: inject faults, assert graceful degradation.

The fault-tolerance contracts (docs/robustness.md) are only real if a
deterministic chaos run proves them, so this harness drives the three
headline degradation paths through ``utils.faults`` fault plans and
asserts the system behaves per contract — the robustness analogue of
``check_serving.py``'s parity harness:

1. **submit-retry** — two transient 503s injected at the API seam
   (``api.request``) during job submission; ``deploy.deploy_job`` must
   succeed on the third attempt through the typed retry layer
   (``retry/api_request`` span shows attempts == 3), with zero rollback.
2. **checkpoint-crash** — one ``checkpoint.save`` crash injected
   mid-fit; training must run to completion, its final step AND loss
   equal to a fault-free control run, and a fresh trainer must resume
   from the train-end checkpoint the tolerant callback still wrote.
3. **hung-dispatch** — one serving chunk dispatch hangs (``serve.chunk``
   hang fault) past ``dispatch_timeout_s``; the watchdog must fail the
   live slots with :class:`DispatchTimeoutError` within the budget,
   ``health()`` must report unhealthy, and after ``close()`` no engine
   thread may survive (the finite hang unwinds).

Prints one JSON line per phase plus a summary::

    {"phase": "summary", "ok": true, "submit_attempts": 3, ...}

Wired as a ``slow``-marked test in tests/unit/test_robustness.py (same
pattern as check_serving.py / check_cold_start.py), so CI runs it every
time; the fast per-piece unit tests live in tier-1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# CPU by default: a correctness harness, not a perf one.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ENGINE_THREAD_PREFIXES = ("cloud-tpu-serve", "cloud-tpu-compile-ahead")


def _engine_threads():
    return [
        t.name for t in threading.enumerate()
        if t.name.startswith(ENGINE_THREAD_PREFIXES)
    ]


class _FakeHttp:
    """requests.Session stand-in: every call succeeds with a done LRO /
    READY node, so the only failures are the injected ones."""

    class _Resp:
        status_code = 200
        text = ""
        headers: dict = {}

        def __init__(self, payload):
            self._payload = payload
            self.content = b"{}"

        def json(self):
            return self._payload

    def __init__(self):
        self.calls = 0

    def request(self, method, url, headers=None, params=None, data=None):
        self.calls += 1
        if method == "GET" and "/nodes/" in url:
            return self._Resp({"state": "READY"})
        return self._Resp({"name": "ops/op", "done": True})


def check_submit_retry() -> dict:
    """Phase 1: two injected 503s on the submit path, absorbed by retries."""
    from cloud_tpu.core import deploy, machine_config
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.parallel import planner
    from cloud_tpu.utils import api_client, faults, retries

    tpu = machine_config.COMMON_MACHINE_CONFIGS["TPU"]
    plan = planner.plan_mesh(chief_config=tpu)
    session = api_client.GcpApiSession(
        requests_session=_FakeHttp(),
        retry=retries.RetryPolicy(
            max_attempts=4, initial_backoff_s=0.001, sleep=lambda _s: None,
        ),
    )
    fault_plan = [{"site": "api.request", "mode": "raise",
                   "error": "transient", "times": 2}]
    with tracing.collecting() as collector:
        with faults.inject(fault_plan) as active:
            info = deploy.deploy_job(
                "gcr.io/p/img:1", tpu, 0, plan, session=session,
                project="p", zone="z", sleep=lambda _s: None,
            )
    retry_spans = [
        e for e in collector.events()
        if e["name"] == "retry/api_request"
    ]
    attempts = retry_spans[0]["args"]["attempts"] if retry_spans else 0
    return {
        "phase": "submit_retry",
        "ok": (
            bool(info.get("job_id"))
            and active.fired() == {"api.request": 2}
            and attempts == 3
            and retry_spans[0]["args"]["outcome"] == "ok"
        ),
        "attempts": attempts,
        "faults_fired": active.fired(),
    }


def check_checkpoint_crash(tmp_dir: str) -> dict:
    """Phase 2: a checkpoint-save crash mid-fit; training unharmed."""
    import functools

    import jax
    import numpy as np
    import optax

    from cloud_tpu.models import mnist
    from cloud_tpu.training import data as data_lib
    from cloud_tpu.training.checkpoint import CheckpointCallback
    from cloud_tpu.training.trainer import Trainer
    from cloud_tpu.utils import faults

    cfg = mnist.MnistConfig(hidden_dim=16)

    def build():
        tr = Trainer(
            functools.partial(mnist.loss_fn, config=cfg),
            optax.sgd(0.1),
            init_fn=functools.partial(mnist.init, config=cfg),
        )
        tr.init_state(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        ds = data_lib.ArrayDataset(
            {"image": rng.normal(size=(48, 784)).astype(np.float32),
             "label": rng.integers(0, 10, 48).astype(np.int64)},
            batch_size=8,
        )
        return tr, ds

    # Control: fault-free run (no checkpointing — saving never touches
    # the parameter trajectory, which is exactly what we assert).
    control, ds = build()
    control_hist = control.fit(ds, epochs=1)
    control_loss = control_hist.history["loss"][-1]

    ckpt_dir = os.path.join(tmp_dir, "chaos_ckpt")
    chaos, ds2 = build()
    cb = CheckpointCallback(ckpt_dir, every_n_steps=2)
    fault_plan = [{"site": "checkpoint.save", "mode": "raise", "nth": 1}]
    with faults.inject(fault_plan) as active:
        hist = chaos.fit(ds2, epochs=1, callbacks=[cb])

    from cloud_tpu.training.checkpoint import CheckpointManager

    latest = CheckpointManager(ckpt_dir).latest_step()
    resumed, _ = build()
    resume_cb = CheckpointCallback(ckpt_dir, every_n_steps=100)
    resume_cb.on_train_begin(resumed)  # restore only
    final_match = np.allclose(
        np.asarray(chaos.state.params["hidden"]["kernel"]),
        np.asarray(resumed.state.params["hidden"]["kernel"]),
        atol=1e-6,
    )
    return {
        "phase": "checkpoint_crash",
        "ok": (
            active.fired() == {"checkpoint.save": 1}
            and int(chaos.state.step) == int(control.state.step) == 6
            and abs(hist.history["loss"][-1] - control_loss) < 1e-6
            and latest == 6
            and final_match
        ),
        "faults_fired": active.fired(),
        "final_step": int(chaos.state.step),
        "latest_checkpoint": latest,
        "loss_delta": abs(hist.history["loss"][-1] - control_loss),
    }


def check_hung_dispatch() -> dict:
    """Phase 3: one hung chunk dispatch; watchdog fails it, engine
    reports unhealthy, threads unwind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cloud_tpu.models import transformer
    from cloud_tpu.serving import (
        DispatchTimeoutError, ServeConfig, ServingEngine,
    )
    from cloud_tpu.utils import faults

    config = transformer.TINY.scaled(dtype=jnp.float32, num_layers=2)
    params = transformer.init(jax.random.PRNGKey(0), config)
    serve = ServeConfig(
        max_new_tokens=6, prompt_buckets=(8,), num_slots=2,
        chunk_tokens=2, dispatch_timeout_s=1.0, warmup=True,
    )
    prompt = np.asarray([5, 9, 17, 2], np.int32)
    engine = ServingEngine(params, config, serve, mesh=None)
    # AOT-warm the grid and serve one request OUTSIDE the fault plan so
    # the injected hang races a dispatch, not a compile.
    engine.wait_ready(timeout=300)
    engine.submit(prompt).result(timeout=300)

    fault_plan = [{"site": "serve.chunk", "mode": "hang", "hang_s": 3.0,
                   "nth": 1}]
    timed_out = False
    within_budget = False
    start = time.perf_counter()
    with faults.inject(fault_plan) as active:
        future = engine.submit(prompt)
        try:
            future.result(timeout=30)
        except DispatchTimeoutError:
            timed_out = True
            # The future must fail once the watchdog fires — near
            # dispatch_timeout_s, far before the 3 s hang finishes.
            within_budget = (time.perf_counter() - start) < 2.5
        health = engine.health()
        engine.close()
    leaked = _engine_threads()
    return {
        "phase": "hung_dispatch",
        "ok": (
            timed_out and within_budget
            and active.fired() == {"serve.chunk": 1}
            and health["healthy"] is False
            and "dispatch_timeout" in (health["reason"] or "")
            and not leaked
        ),
        "timed_out": timed_out,
        "within_budget": within_budget,
        "health": {k: health.get(k) for k in ("healthy", "ready", "reason")},
        "leaked_threads": leaked,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--tmp-dir", default="/tmp/cloud_tpu_chaos")
    args = parser.parse_args(argv)
    os.makedirs(args.tmp_dir, exist_ok=True)

    start = time.perf_counter()
    phases = [
        check_submit_retry(),
        check_checkpoint_crash(args.tmp_dir),
        check_hung_dispatch(),
    ]
    for phase in phases:
        print(json.dumps(phase), flush=True)
    ok = all(p["ok"] for p in phases)
    print(json.dumps({
        "phase": "summary",
        "ok": ok,
        "submit_attempts": phases[0]["attempts"],
        "leaked_threads": phases[2]["leaked_threads"],
        "wall_seconds": round(time.perf_counter() - start, 3),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
