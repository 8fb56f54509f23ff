"""Compile-ahead engine: AOT compilation, tail padding, persistent cache.

Covers the ISSUE 3 contracts:

* Tail-batch padding — a ``steps_per_dispatch=K`` run over a dataset
  whose length is NOT a multiple of K pads the tail window, reuses the
  one fused executable (retrace-guard: zero extra compiles, tail
  included), and reproduces the exact K=1 History/metrics.
* Compile-ahead — ``fit(compile_ahead=True)`` compiles on a worker
  thread while prefetch warms: ``compile/backend_compile`` spans finish
  before the first dispatch span starts, executables attach without
  fallback, and the AOT registry serves repeat fits without recompiling.
* Safe persistent cache — the round-trip probe refuses to enable on a
  failing child (stubbed subprocess), refuses blocklisted jaxlibs
  without FORCE, and on a passing probe enables + warm-starts a second
  process (no new cache entries for an already-cached executable).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from helpers.retrace_guard import RetraceGuard

from cloud_tpu.core import deploy
from cloud_tpu.monitoring import tracing
from cloud_tpu.parallel import sharding as sharding_lib
from cloud_tpu.training import compile_cache, data
from cloud_tpu.training import train as train_lib
from cloud_tpu.training.trainer import Trainer

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _linear_problem(n=16, batch_size=2, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    w_true = rng.normal(size=(4, 2)).astype(np.float32)
    return data.ArrayDataset(
        {"x": x, "y": (x @ w_true).astype(np.float32)}, batch_size=batch_size
    )


def _linear_loss(params, batch):
    pred = batch["x"] @ params["w"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"loss": loss}


def _make_trainer(loss_fn=_linear_loss, lr=0.1, opt=None):
    trainer = Trainer(
        loss_fn, opt or optax.sgd(lr),
        init_fn=lambda rng: {"w": jnp.zeros((4, 2), jnp.float32)},
    )
    trainer.init_state(jax.random.PRNGKey(0))
    return trainer


def _spy_plan(monkeypatch, trainer):
    """Capture the CompileAhead plan fit() launches (to assert no silent
    jit fallback happened)."""
    holder = {}
    orig = trainer._launch_compile_ahead

    def spy(*args, **kwargs):
        plan, peeked = orig(*args, **kwargs)
        holder["plan"] = plan
        return plan, peeked

    monkeypatch.setattr(trainer, "_launch_compile_ahead", spy)
    return holder


class TestPadBatch:
    def test_pads_and_masks(self):
        batch = {"x": np.ones((3, 4), np.float32),
                 "y": np.ones((3, 2), np.int32)}
        padded, valid = sharding_lib.pad_batch(batch, 5)
        assert padded["x"].shape == (5, 4)
        assert padded["y"].shape == (5, 2)
        assert padded["y"].dtype == np.int32
        np.testing.assert_array_equal(valid, [1, 1, 1, 0, 0])
        np.testing.assert_array_equal(padded["x"][3:], 0)
        np.testing.assert_array_equal(padded["x"][:3], batch["x"])

    def test_full_batch_is_identity(self):
        batch = {"x": np.ones((4, 2), np.float32)}
        padded, valid = sharding_lib.pad_batch(batch, 4)
        assert padded["x"] is batch["x"]  # no copy when nothing to pad
        np.testing.assert_array_equal(valid, np.ones(4))

    def test_oversize_and_bad_pad_to_raise(self):
        batch = {"x": np.ones((4, 2), np.float32)}
        with pytest.raises(ValueError, match="more than pad_to"):
            sharding_lib.pad_batch(batch, 2)
        with pytest.raises(ValueError, match="pad_to"):
            sharding_lib.pad_batch(batch, 0)

    def test_scalar_and_axis_free_leaves_pass_through(self):
        batch = {"x": np.ones((3, 4), np.float32), "scale": np.float32(2.0)}
        padded, valid = sharding_lib.pad_batch(batch, 5)
        assert padded["x"].shape == (5, 4)
        assert np.shape(padded["scale"]) == ()  # side data untouched
        np.testing.assert_array_equal(valid, [1, 1, 1, 0, 0])

    def test_disagreeing_batch_axes_raise(self):
        batch = {"x": np.ones((5, 4), np.float32),
                 "y": np.ones((6,), np.float32)}
        with pytest.raises(ValueError, match="disagree on axis 0"):
            sharding_lib.pad_batch(batch, 8)
        with pytest.raises(ValueError, match="no leaf has axis"):
            sharding_lib.pad_batch({"s": np.float32(1.0)}, 4)

    def test_shard_batch_pad_to_returns_mask(self):
        batch = {"x": np.ones((3, 4), np.float32)}
        placed, valid = train_lib.shard_batch(batch, None, pad_to=4)
        assert placed["x"].shape == (4, 4)
        np.testing.assert_array_equal(valid, [1, 1, 1, 0])


class TestTailPaddingParity:
    def test_k4_with_tail_matches_exact_k1_run(self):
        """22 rows / batch 2 = 11 batches: K=4 runs 2 full windows + a
        3-step padded tail per epoch.  History and the final params must
        match the exact K=1 run — the padded slot is skipped on device,
        and the valid steps execute the identical step body (params come
        out bitwise-identical on the CPU rig; epoch metric means differ
        only by the window-mean divide/multiply round-trip, ~1 ulp)."""

        def run(k):
            trainer = _make_trainer(opt=optax.adam(0.05))
            history = trainer.fit(
                _linear_problem(n=22), epochs=2, steps_per_dispatch=k
            )
            return history, trainer

        h1, t1 = run(1)
        h4, t4 = run(4)
        assert int(t1.state.step) == int(t4.state.step) == 22
        assert set(h1.history) == set(h4.history)
        for key in h1.history:
            if key == "epoch_seconds":  # wall-clock, not comparable
                continue
            np.testing.assert_allclose(
                h1.history[key], h4.history[key], rtol=1e-6, atol=1e-8,
                err_msg=key,
            )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            t1.state.params, t4.state.params,
        )

    def test_exactly_one_fused_compile_for_whole_epoch(self):
        """Retrace guard (acceptance): with K=4 over a non-multiple-of-4
        dataset, the tail must add ZERO traces beyond what a tail-less
        run compiles — one fused executable serves the whole epoch — and
        a second epoch adds none either."""
        guard_full = RetraceGuard(_linear_loss)
        _make_trainer(loss_fn=guard_full.loss_fn).fit(
            _linear_problem(n=16), epochs=1, steps_per_dispatch=4
        )  # 8 batches: 2 full windows, no tail -> exactly one compile

        guard_tail = RetraceGuard(_linear_loss)
        trainer = _make_trainer(loss_fn=guard_tail.loss_fn)
        trainer.fit(
            _linear_problem(n=22), epochs=1, steps_per_dispatch=4
        )  # 11 batches: 2 full windows + 3-step tail
        assert int(trainer.state.step) == 11
        assert guard_tail.traces == guard_full.traces  # tail: 0 extra
        baseline = guard_tail.snapshot()
        trainer.fit(_linear_problem(n=22), epochs=1, steps_per_dispatch=4)
        guard_tail.assert_no_new_traces(baseline, "second epoch")

    def test_ragged_final_batch_degrades_to_single_steps(self):
        """A drop_remainder=False dataset's short FINAL BATCH cannot
        stack with its window-mates; the window degrades to per-step
        dispatches (valid None) instead of crashing np.stack mid-epoch —
        the pre-padding behavior for this case."""
        rng = np.random.default_rng(0)
        x = rng.normal(size=(10, 4)).astype(np.float32)
        ds = data.ArrayDataset(
            {"x": x, "y": np.ones((10, 2), np.float32)},
            batch_size=4, drop_remainder=False,
        )  # batches of 4, 4, 2 -> one full pair + ragged [4-row, 2-row]
        trainer = _make_trainer()
        history = trainer.fit(ds, epochs=1, steps_per_dispatch=2)
        assert int(trainer.state.step) == 3
        assert len(history.history["loss"]) == 1
        # compile_ahead over a ragged FIRST window degrades, not crashes.
        trainer = _make_trainer()
        trainer.fit(
            data.ArrayDataset(
                {"x": x[:6], "y": np.ones((6, 2), np.float32)},
                batch_size=4, drop_remainder=False,
            ),  # batches of 4, 2 -> the very first window is ragged
            epochs=1, steps_per_dispatch=2, compile_ahead=True,
        )
        assert int(trainer.state.step) == 2

    def test_stochastic_tail_preserves_rng_chain(self):
        """The skipped padded slot must not consume a PRNG split: 3
        padded-fused stochastic steps end with the same rng as 3
        sequential ones."""
        import dataclasses
        import functools

        from cloud_tpu.models import bert
        from cloud_tpu.training import pipeline_io

        cfg = dataclasses.replace(bert.TINY, dropout_rate=0.2)
        tx = optax.adam(1e-3)
        loss = functools.partial(bert.loss_fn, cfg=cfg)
        make_state = lambda: train_lib.create_sharded_state(  # noqa: E731
            jax.random.PRNGKey(0), functools.partial(bert.init, cfg=cfg),
            tx, mesh=None, train_rng=jax.random.PRNGKey(7),
        )
        batches = [
            {
                "tokens": np.full((2, 4), 1 + i, np.int32),
                "label": np.asarray([0, 1], np.int32),
            }
            for i in range(3)
        ]
        single = train_lib.make_train_step(loss, tx, stochastic=True)
        seq = make_state()
        for b in batches:
            seq, _ = single(seq, b)
        multi = train_lib.make_multi_step(
            loss, tx, steps_per_dispatch=4, stochastic=True
        )
        stacked, valid = sharding_lib.pad_batch(
            pipeline_io.stack_batches(batches), 4
        )
        fused, _ = multi(make_state(), stacked, valid)
        np.testing.assert_array_equal(
            np.asarray(seq.rng), np.asarray(fused.rng)
        )


class TestCompileAhead:
    def test_compile_finishes_before_first_dispatch(self, monkeypatch):
        """Acceptance: the step executable's compile/backend_compile span
        overlaps the prefetch-warmup window — it ENDS before the first
        dispatch span STARTS.  The eval compile rides BEHIND the train
        compile on the worker and must not gate dispatch 1; its avals
        come from the validation data's own (differently-sized) batches,
        so it stays attached through evaluate() with no jit fallback."""
        trainer = _make_trainer()
        holder = _spy_plan(monkeypatch, trainer)
        with tracing.collecting() as collector:
            trainer.fit(
                _linear_problem(n=22), epochs=1, steps_per_dispatch=4,
                prefetch=2, compile_ahead=True,
                validation_data=_linear_problem(n=16, batch_size=4),
            )
        events = collector.events()
        compiles = [e for e in events if e["name"] == "compile/backend_compile"]
        assert {e["args"].get("fn") for e in compiles} == {
            "multi_step", "eval_step"
        }
        first_dispatch = [e for e in events if e["name"] == "step/first_compile"]
        assert len(first_dispatch) == 1
        step_compile_end = max(
            e["ts"] + e["dur"] for e in compiles
            if e["args"].get("fn") == "multi_step"
        )
        assert step_compile_end <= first_dispatch[0]["ts"]
        plan = holder["plan"]
        assert plan.error is None
        # The executables stayed attached: every dispatch went through
        # the AOT-compiled path, no silent jit fallback — including eval
        # over batch_size=4 while training ran batch_size=2.
        assert plan.steps["multi_step"].compiled is not None
        assert plan.steps["eval_step"].compiled is not None

    @pytest.mark.parametrize("prefetch", [0, 2])
    def test_k1_compile_ahead_parity(self, monkeypatch, prefetch):
        plain = _make_trainer().fit(_linear_problem(), epochs=2)
        trainer = _make_trainer()
        holder = _spy_plan(monkeypatch, trainer)
        ahead = trainer.fit(
            _linear_problem(), epochs=2, prefetch=prefetch,
            compile_ahead=True,
        )
        assert holder["plan"].steps["train_step"].compiled is not None
        for key in plain.history:
            if key == "epoch_seconds":
                continue
            np.testing.assert_allclose(
                plain.history[key], ahead.history[key], rtol=1e-6,
                err_msg=key,
            )

    def test_batch_spec_compiles_without_peeking(self, monkeypatch):
        trainer = _make_trainer()
        holder = _spy_plan(monkeypatch, trainer)
        spec = {
            "x": np.zeros((2, 4), np.float32),
            "y": np.zeros((2, 2), np.float32),
        }
        history = trainer.fit(
            _linear_problem(n=22), epochs=1, steps_per_dispatch=4,
            compile_ahead=True, batch_spec=spec,
        )
        assert len(history.history["loss"]) == 1
        assert holder["plan"].steps["multi_step"].compiled is not None

    def test_registry_serves_repeat_fits(self, monkeypatch):
        """A second fit over the same shapes finds its executables in the
        AOT registry: zero new backend compiles."""
        trainer = _make_trainer()
        trainer.fit(
            _linear_problem(), epochs=1, steps_per_dispatch=4,
            compile_ahead=True,
        )
        holder = _spy_plan(monkeypatch, trainer)
        with tracing.collecting() as collector:
            trainer.fit(
                _linear_problem(), epochs=1, steps_per_dispatch=4,
                compile_ahead=True,
            )
        assert "compile/backend_compile" not in collector.aggregates()
        assert holder["plan"].steps["multi_step"].compiled is not None

    def test_aot_step_falls_back_on_aval_mismatch(self):
        jitted = jax.jit(lambda a, b: a + b)
        compiled = compile_cache.aot_compile(
            jitted,
            jax.ShapeDtypeStruct((2,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.float32),
            label="add",
        )
        step = compile_cache.AotStep(jitted, "add")
        step.attach(compiled)
        ones2 = jnp.ones((2,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(step(ones2, ones2)), 2.0)
        assert step.compiled is not None
        ones3 = jnp.ones((3,), jnp.float32)  # mismatched avals
        np.testing.assert_array_equal(np.asarray(step(ones3, ones3)), 2.0)
        assert step.compiled is None  # permanently reverted to jit

    def test_get_or_compile_memoizes_by_fn_and_avals(self):
        jitted = jax.jit(lambda x: x * 2)
        aval_a = (jax.ShapeDtypeStruct((4,), jnp.float32),)
        aval_b = (jax.ShapeDtypeStruct((8,), jnp.float32),)
        c1 = compile_cache.get_or_compile(jitted, aval_a, label="x2")
        c2 = compile_cache.get_or_compile(jitted, aval_a, label="x2")
        c3 = compile_cache.get_or_compile(jitted, aval_b, label="x2")
        assert c1 is c2
        assert c3 is not c1

    def test_registry_is_bounded(self, monkeypatch):
        monkeypatch.setattr(compile_cache, "REGISTRY_MAX_ENTRIES", 3)
        jitted = jax.jit(lambda x: x + 1)
        for n in range(2, 8):  # 6 distinct aval keys through a cap of 3
            compile_cache.get_or_compile(
                jitted, (jax.ShapeDtypeStruct((n,), jnp.float32),),
                label="bounded",
            )
        assert compile_cache.registry_size() <= 3

    def test_empty_dataset_degrades_gracefully(self):
        trainer = _make_trainer()

        def empty():
            return iter(())

        history = trainer.fit(empty, epochs=1, compile_ahead=True)
        assert "loss" not in history.history  # no steps ran, no crash


class TestPersistentCache:
    @pytest.fixture(autouse=True)
    def _isolated(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_COMPILE_CACHE, raising=False)
        monkeypatch.delenv(compile_cache.ENV_JAX_CACHE_DIR, raising=False)
        compile_cache._reset_persistent_state_for_tests()
        before = jax.config.jax_compilation_cache_dir
        yield
        compile_cache._reset_persistent_state_for_tests()
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_env_is_a_noop(self):
        assert compile_cache.maybe_enable_persistent_cache() is False
        assert not compile_cache.persistent_cache_enabled()

    @pytest.mark.parametrize("how", ["argument", "CLOUD_TPU_COMPILE_CACHE"])
    def test_jax_variable_unset_uses_the_given_dir(self, tmp_path,
                                                   monkeypatch, how):
        cache_dir = str(tmp_path / "cache")
        if how == "argument":
            ok = compile_cache.maybe_enable_persistent_cache(cache_dir)
        else:
            monkeypatch.setenv(compile_cache.ENV_COMPILE_CACHE, cache_dir)
            ok = compile_cache.maybe_enable_persistent_cache()
        assert ok is True
        assert compile_cache.persistent_cache_enabled()
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert os.path.isdir(cache_dir)

    @pytest.mark.parametrize("how", ["argument", "CLOUD_TPU_COMPILE_CACHE"])
    def test_jax_variable_is_honoured_and_never_overridden(
        self, tmp_path, monkeypatch, how
    ):
        """A cache placed from outside stays where it was placed."""
        placed = str(tmp_path / "placed")
        other = str(tmp_path / "other")
        monkeypatch.setenv(compile_cache.ENV_JAX_CACHE_DIR, placed)
        if how == "argument":
            ok = compile_cache.maybe_enable_persistent_cache(other)
        else:
            monkeypatch.setenv(compile_cache.ENV_COMPILE_CACHE, other)
            ok = compile_cache.maybe_enable_persistent_cache()
        assert ok is True
        assert jax.config.jax_compilation_cache_dir == placed
        assert not os.path.exists(other)
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,))).block_until_ready()
        assert os.listdir(placed)

    def test_enables_and_warm_starts_second_process(self, tmp_path):
        """Acceptance: the cache enabled in-process is found again by a
        second process — compiling the same step there adds no entry."""
        cache_dir = str(tmp_path / "cache")
        child = (
            "import sys\n"
            "from cloud_tpu.training import compile_cache\n"
            "assert compile_cache.maybe_enable_persistent_cache(sys.argv[1])\n"
            "import jax, jax.numpy as jnp\n"
            "def step(state, batch):\n"
            "    def loss(w):\n"
            "        return ((batch['x'] @ w - batch['y']) ** 2).mean()\n"
            "    g = jax.grad(loss)(state['w'])\n"
            "    return {'w': state['w'] - 0.1 * g}\n"
            "jitted = jax.jit(step, donate_argnums=0)\n"
            "batch = {'x': jnp.ones((8, 4)), 'y': jnp.ones((8, 2))}\n"
            "out = jitted({'w': jnp.zeros((4, 2))}, batch)\n"
            "import numpy as np\n"
            "print('WARM_OK', float(np.asarray(out['w']).sum()))\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop(compile_cache.ENV_JAX_CACHE_DIR, None)
        env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")

        def run_child():
            proc = subprocess.run(
                [sys.executable, "-c", child, cache_dir],
                capture_output=True, text=True, timeout=120, env=env,
            )
            assert proc.returncode == 0, proc.stderr[-500:]
            assert "WARM_OK" in proc.stdout
            return {
                f for f in os.listdir(cache_dir)
                if f.startswith("jit_step") and f.endswith("-cache")
            }

        first = run_child()
        assert first  # the step executable was written to disk
        # Warm start: the second process's step compile was served from
        # disk — it wrote NO new step-executable cache entries.
        assert run_child() == first


class TestDeployForwarding:
    def _script(self, **kwargs):
        return deploy.startup_script(
            "gcr.io/p/img", coordinator_address="c:8476", num_processes=2,
            process_id_base=0, **kwargs,
        )

    def test_env_forwarded_into_container(self, monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_COMPILE_CACHE", "/var/cache/xla")
        assert "-e CLOUD_TPU_COMPILE_CACHE=/var/cache/xla" in self._script()

    def test_absent_without_env(self, monkeypatch):
        monkeypatch.delenv("CLOUD_TPU_COMPILE_CACHE", raising=False)
        assert "CLOUD_TPU_COMPILE_CACHE" not in self._script()

    def test_explicit_empty_suppresses_env(self, monkeypatch):
        monkeypatch.setenv("CLOUD_TPU_COMPILE_CACHE", "/var/cache/xla")
        assert "CLOUD_TPU_COMPILE_CACHE" not in self._script(compile_cache="")

    def test_value_is_shell_quoted(self):
        # This is an arbitrary user-env string inside a root startup
        # script: metacharacters must arrive inert.
        script = self._script(compile_cache="/cache dir/$(reboot)")
        assert "'CLOUD_TPU_COMPILE_CACHE=/cache dir/$(reboot)'" in script

    def test_build_job_request_threads_through(self, monkeypatch):
        from cloud_tpu.core import machine_config
        from cloud_tpu.parallel import planner

        monkeypatch.delenv("CLOUD_TPU_COMPILE_CACHE", raising=False)
        config = machine_config.COMMON_MACHINE_CONFIGS["TPU"]
        plan = planner.plan_mesh(config, worker_count=0)
        request = deploy.build_job_request(
            "gcr.io/p/img", config, 0, plan, compile_cache="/tmp/cc",
        )
        script = next(iter(request["nodes"].values()))["metadata"][
            "startup-script"
        ]
        assert "-e CLOUD_TPU_COMPILE_CACHE=/tmp/cc" in script


@pytest.mark.slow
def test_check_cold_start_script():
    """The CI cold-vs-warm harness runs end to end and prints both
    first-dispatch times (regressions in compile-ahead show up here)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "check_cold_start.py")],
        capture_output=True, text=True, timeout=500,
        cwd=REPO_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    summary = None
    for line in proc.stdout.splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        if record.get("phase") == "summary":
            summary = record
    assert summary is not None, proc.stdout[-500:]
    assert summary["cold_first_dispatch_seconds"] > 0
    assert summary["warm_first_dispatch_seconds"] > 0
    # The warm child serves its many small compiles from disk (measured
    # ~5x faster overall); 1.5x slack absorbs scheduler noise without
    # letting a real cold-start regression through.
    assert summary["warm_fit_seconds"] <= summary["cold_fit_seconds"] * 1.5
