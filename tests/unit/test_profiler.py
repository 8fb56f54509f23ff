"""Profiler subsystem tests: trace capture artifacts, the scheduled
ProfilerCallback window, env-gated server start, annotations, and memory
snapshots.

The reference has no profiler (SURVEY.md §5: nearest artifact is a
TensorBoard callback shipped through cloud_fit); these tests define the
TPU-native first-class behavior instead of mirroring reference goldens.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from cloud_tpu.monitoring import profiler
from cloud_tpu.training import trainer as trainer_lib


def _profile_files(logdir):
    return glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*"), recursive=True
    )


class TestTrace:
    def test_trace_context_writes_profile_dir(self, tmp_path):
        logdir = str(tmp_path / "tr")
        with profiler.trace(logdir) as out:
            jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
        assert out == logdir
        assert _profile_files(logdir), "no profile artifacts written"

    def test_start_stop_trace(self, tmp_path):
        logdir = profiler.start_trace(str(tmp_path / "m"))
        jnp.sum(jnp.arange(16)).block_until_ready()
        profiler.stop_trace()
        assert _profile_files(logdir)

    @pytest.mark.parametrize("entry", ["trace", "start_trace"])
    def test_host_tracer_is_cut_to_annotations(self, entry, monkeypatch,
                                               tmp_path):
        """ISSUE 26: an operator's trace starts as the benchmark's does
        (``host_tracer_level=1``, no Python tracer), not with JAX's
        default flood of futex and Python-call events."""
        seen = []
        real = jax.profiler.start_trace

        def start(log_dir, *args, profiler_options=None, **kwargs):
            seen.append(profiler_options)
            return real(log_dir, *args, profiler_options=profiler_options,
                        **kwargs)

        monkeypatch.setattr(jax.profiler, "start_trace", start)
        if entry == "trace":
            with profiler.trace(str(tmp_path / "t")):
                pass
        else:
            profiler.start_trace(str(tmp_path / "t"))
            profiler.stop_trace()
        assert len(seen) == 1
        assert seen[0].host_tracer_level == 1
        assert seen[0].python_tracer_level == 0

    def test_default_logdir_env(self, monkeypatch, tmp_path):
        monkeypatch.setenv(profiler.ENV_PROFILER_LOGDIR, str(tmp_path))
        assert profiler.default_logdir() == str(tmp_path)

    def test_annotations(self):
        with profiler.annotate("span"):
            pass

        @profiler.annotate_function(name="fn_span")
        def f(x):
            return x + 1

        assert int(f(jnp.asarray(1))) == 2

    def test_device_memory_profile(self, tmp_path):
        path = profiler.save_device_memory_profile(
            str(tmp_path / "mem" / "memory.prof")
        )
        assert os.path.exists(path) and os.path.getsize(path) > 0


class TestServerEnvGate:
    def test_disabled_without_env(self, monkeypatch):
        monkeypatch.delenv(profiler.ENV_PROFILER_PORT, raising=False)
        assert profiler.maybe_start_server_from_env() is False


class TestProfilerCallback:
    def _make_trainer(self):
        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            loss = jnp.mean((pred - batch["y"]) ** 2)
            return loss, {"loss": loss}

        def init_fn(rng):
            return {"w": jax.random.normal(rng, (4, 2))}

        return trainer_lib.Trainer(loss_fn, optax.sgd(0.1), init_fn)

    def _data(self):
        rng = np.random.RandomState(0)
        return lambda: iter(
            [{"x": rng.randn(8, 4).astype(np.float32),
              "y": rng.randn(8, 2).astype(np.float32)} for _ in range(6)]
        )

    def test_window_capture(self, tmp_path):
        logdir = str(tmp_path / "cb")
        cb = profiler.ProfilerCallback(logdir, start_step=2, num_steps=3)
        t = self._make_trainer()
        t.init_state(jax.random.PRNGKey(0))
        t.fit(self._data(), epochs=1, callbacks=[cb])
        assert cb._done and not cb._tracing
        assert _profile_files(logdir)

    def test_fit_shorter_than_window_still_closes(self, tmp_path):
        logdir = str(tmp_path / "short")
        cb = profiler.ProfilerCallback(logdir, start_step=2, num_steps=50)
        t = self._make_trainer()
        t.init_state(jax.random.PRNGKey(0))
        t.fit(self._data(), epochs=1, callbacks=[cb])  # 6 steps < window end
        assert cb._done and not cb._tracing
        assert _profile_files(logdir)

    def test_invalid_window_rejected(self):
        with pytest.raises(ValueError):
            profiler.ProfilerCallback(num_steps=0)
