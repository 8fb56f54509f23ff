"""Tracing / profiling subsystem: first-class ``jax.profiler`` capture.

The reference has no profiler of its own — its nearest artifact is a
TensorBoard callback shipped through cloud_fit serialization
(cloud_fit/tests/unit/remote_test.py:72) and README-promised "hosted
TensorBoard" monitoring.  SURVEY.md §5 calls for the TPU-native
equivalent to be first-class: ``jax.profiler`` trace capture viewable in
XProf/Perfetto/TensorBoard, a profiler *server* for on-demand remote
capture from a running pod, op-level trace annotations, and device-memory
snapshots.

Three entry styles, mirroring how the reference exposes monitoring:

* explicit API — ``trace(logdir)`` context manager, ``start_server()``;
* env-gated auto-start — ``maybe_start_server_from_env()`` called by the
  container bootstrap, gated on ``CLOUD_TPU_PROFILER_PORT`` the same way
  the metrics exporter gates on ``CLOUD_TPU_MONITORING_ENABLED``
  (reference: TF_MONITORING_STACKDRIVER_EXPORTER_ENABLED,
  stackdriver_exporter.cc:31-36);
* Trainer callback — ``ProfilerCallback`` captures a window of training
  steps (the "trace steps 10-20 of epoch 0" TensorBoard idiom) with
  per-step ``StepTraceAnnotation`` markers so XProf can cut the trace by
  step.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Optional

import jax

logger = logging.getLogger(__name__)

#: Setting this env var in the job spec turns the profiler server on in
#: every remote host process (deploy.py forwards job env to the
#: bootstrap).  Value = port to listen on.
ENV_PROFILER_PORT = "CLOUD_TPU_PROFILER_PORT"

#: Where ProfilerCallback / trace() write when no logdir is given.
ENV_PROFILER_LOGDIR = "CLOUD_TPU_PROFILER_LOGDIR"

_DEFAULT_LOGDIR = "/tmp/cloud_tpu_profile"

_server = None


def default_logdir() -> str:
    return os.environ.get(ENV_PROFILER_LOGDIR, _DEFAULT_LOGDIR)


def start_server(port: int = 9012):
    """Start the profiler server for on-demand capture.

    A running server lets ``jax.profiler.trace_server`` clients / XProf
    "capture profile" pull a trace from a live pod without restarting the
    job — the TPU-native replacement for the reference's "hosted
    TensorBoard" monitoring promise (README "What happens when you call
    run?").  Idempotent per process.
    """
    global _server
    if _server is None:
        _server = jax.profiler.start_server(port)
        logger.info("profiler server listening on :%d", port)
    return _server


def stop_server() -> None:
    global _server
    if _server is not None:
        jax.profiler.stop_server()
        _server = None


def maybe_start_server_from_env() -> bool:
    """Env-gated auto-start; called by ``core.bootstrap`` on every host."""
    port = os.environ.get(ENV_PROFILER_PORT)
    if not port:
        return False
    try:
        start_server(int(port))
    except Exception:  # pragma: no cover - double-start in odd harnesses
        logger.exception("profiler server failed to start")
        return False
    return True


def trace_options():
    """The host tracer cut to annotations (``host_tracer_level=1``, no
    Python tracer).  JAX's default also records every futex and every
    Python call: millions of events, and a host slowed enough that the
    trace measures the tracer."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    return options


@contextlib.contextmanager
def trace(logdir: Optional[str] = None, *, perfetto_link: bool = False):
    """Capture a trace of the enclosed block to ``logdir``.

    The output is a TensorBoard-ready ``plugins/profile/...`` directory
    (open with XProf or ``tensorboard --logdir``).  ``gs://`` logdirs are
    supported by the underlying writer, so traces can land next to the
    job's checkpoints.
    """
    logdir = start_trace(logdir, perfetto_link=perfetto_link)
    try:
        yield logdir
    finally:
        stop_trace()


def start_trace(logdir: Optional[str] = None, *,
                perfetto_link: bool = False) -> str:
    from cloud_tpu.monitoring import tracing

    logdir = logdir or default_logdir()
    jax.profiler.start_trace(logdir, create_perfetto_link=perfetto_link,
                             profiler_options=trace_options())
    # Host-side tracing spans opened while the trace is live mirror
    # themselves as TraceAnnotations onto the device timeline.
    tracing.xprof_trace_started()
    return logdir


def stop_trace() -> None:
    from cloud_tpu.monitoring import tracing

    jax.profiler.stop_trace()
    tracing.xprof_trace_stopped()


def annotate(name: str, **kwargs):
    """Named span visible on the XProf timeline (TraceAnnotation)."""
    return jax.profiler.TraceAnnotation(name, **kwargs)


def annotate_function(fn=None, *, name: Optional[str] = None):
    """Decorator form of :func:`annotate`."""
    if fn is None:
        import functools

        def deco(f):
            return annotate_function(f, name=name)

        return deco
    return jax.profiler.annotate_function(fn, name=name)


def save_device_memory_profile(path: Optional[str] = None) -> str:
    """Dump a pprof-format device-memory snapshot (HBM attribution).

    Works on CPU and standard TPU-VM runtimes.  PJRT C-API plugins that
    don't implement ``PJRT_Executable_SizeOfGeneratedCodeInBytes`` fatally
    abort inside the runtime when live executables exist (runtime CHECK,
    not a Python exception) — on such backends prefer :func:`trace`, whose
    capture includes a memory-viewer plane.
    """
    path = path or os.path.join(default_logdir(), "memory.prof")
    if "://" not in path:
        # Only local paths need (or tolerate) makedirs; for gs:// the
        # underlying writer owns path creation — a naive makedirs would
        # create a bogus local "gs:/..." directory tree.
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    jax.profiler.save_device_memory_profile(path)
    return path


class ProfilerCallback:
    """Trainer callback: trace steps ``[start_step, start_step+num_steps)``.

    Equivalent UX to Keras TensorBoard(profile_batch=(a, b)) — the
    mechanism the reference ships via cloud_fit's pickled-callback path.
    Captures once per fit() run; each traced step is wrapped in a
    ``StepTraceAnnotation`` so XProf's step-time view segments correctly.
    """

    def __init__(self, logdir: Optional[str] = None, *, start_step: int = 2,
                 num_steps: int = 3):
        if num_steps < 1:
            raise ValueError("num_steps must be >= 1")
        self.logdir = logdir or default_logdir()
        self.start_step = start_step
        self.num_steps = num_steps
        self._tracing = False
        self._done = False
        self._step_span = None

    # Callback protocol (training.trainer.Callback) -------------------
    def on_train_begin(self, trainer) -> None:
        self._done = False

    def on_step_end(self, step: int, logs, trainer) -> None:
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        if self._tracing and step >= self.start_step + self.num_steps - 1:
            # Block on the last traced step's result so device activity is
            # inside the capture window before stop_trace().
            jax.block_until_ready(next(iter(logs.values()), None))
            stop_trace()
            self._tracing = False
            self._done = True
            logger.info("profiler: wrote trace to %s", self.logdir)
        elif (not self._done and not self._tracing
              and step >= self.start_step - 1):
            start_trace(self.logdir)
            self._tracing = True
        if self._tracing:
            self._step_span = jax.profiler.StepTraceAnnotation(
                "train", step_num=step + 1
            )
            self._step_span.__enter__()

    def on_train_end(self, trainer) -> None:
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        if self._tracing:  # fit() ended before the window closed
            stop_trace()
            self._tracing = False
            self._done = True

    def on_epoch_begin(self, epoch: int, trainer) -> None: ...
    def on_epoch_end(self, epoch: int, logs, trainer) -> None: ...
