"""Compile-ahead engine: AOT step compilation and a safe persistent cache.

XLA compilation dominates small-job submit-to-first-step latency (the
north star's second headline metric): the trainer's first dispatch pays
lower + backend-compile synchronously while the device sits idle, and a
fresh process pays it all again.  This module makes that cost an
engineered quantity instead of an accident, three ways:

* **AOT registry** — :func:`get_or_compile` keys
  ``jax.jit(step).lower(abstract_avals).compile()`` artifacts by
  (step-fn identity, abstract input avals, mesh + sharding rules,
  donation signature, steps-per-dispatch), so a second fit over the same
  shapes reuses the executable without touching jit's dispatch path.
  Every compile is spanned as ``compile/lower`` and
  ``compile/backend_compile`` (monitoring.tracing), so the report CLI
  attributes cold-start wall-clock phase by phase.
* **Background compile-ahead** — :func:`start_compile_ahead` compiles
  the fit's step executables on a worker thread *while*
  ``pipeline_io`` prefetch warms, and hands the trainer
  :class:`AotStep` wrappers that dispatch through the ready executable
  (falling back to the plain jitted function on any input mismatch —
  compile-ahead can make a fit faster, never wrong).  The machinery is
  not Trainer-specific: ``cloud_tpu.serving`` warms its whole inference
  grid through the same registry + worker at engine start — one
  slot-insert executable per prompt bucket plus the single chunk-decode
  program.
* **Persistent cache** — :func:`maybe_enable_persistent_cache` turns on
  jax's on-disk compilation cache.  Where ``JAX_COMPILATION_CACHE_DIR``
  is set the cache was placed from outside and lives there — no code
  here points jax at another directory; otherwise the directory is the
  caller's (``chip_smoke.py`` passes the fixed
  ``<repo>/.jax_cache``) or ``CLOUD_TPU_COMPILE_CACHE=<dir>``, which
  ``core.deploy`` forwards into the container.  A fixed path matters:
  the directory is part of the cache key, so one that moves never hits.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np

from cloud_tpu.monitoring import metrics, tracing

logger = logging.getLogger(__name__)

#: jax's own variable: where it is set, the cache is there and nowhere else.
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: Directory for jax's on-disk compilation cache when jax's own variable
#: is not set; unset/"off" disables.
ENV_COMPILE_CACHE = "CLOUD_TPU_COMPILE_CACHE"
#: Override jax's min-compile-time-to-cache threshold (seconds; default 0 —
#: the jobs this launcher targets are small, so cache everything).
ENV_COMPILE_CACHE_MIN_SECS = "CLOUD_TPU_COMPILE_CACHE_MIN_SECS"


# --------------------------------------------------------------------------
# Abstract avals


def _canonical_dtype(dtype):
    import jax

    return jax.dtypes.canonicalize_dtype(np.dtype(dtype))


def abstract_state(state):
    """ShapeDtypeStruct pytree for a live TrainState (shardings preserved,
    so the AOT executable compiles for the exact placement jit would)."""
    import jax

    def aval(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, _canonical_dtype(x.dtype))

    return jax.tree_util.tree_map(aval, state)


def abstract_batch(batch, mesh=None, rules=None, *, stacked: bool = False,
                   batch_axis: str = "batch"):
    """ShapeDtypeStruct pytree for a batch AS THE STEP WILL SEE IT.

    Device-placed leaves keep their shardings verbatim; host leaves get
    the sharding ``train.shard_batch`` would commit them to (dim 0 on the
    data axes; ``stacked=True`` = super-batch layout with a replicated
    leading step axis).  Accepts a concrete batch or a ``batch_spec``
    pytree of anything with ``.shape``/``.dtype``.
    """
    import jax
    from jax.sharding import NamedSharding

    lead = [None, batch_axis] if stacked else [batch_axis]

    def aval(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        shape = tuple(x.shape)
        dtype = _canonical_dtype(x.dtype)
        if mesh is None:
            return jax.ShapeDtypeStruct(shape, dtype)
        spec = rules.spec(*(lead + [None] * (len(shape) - len(lead))))
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map(aval, batch)


def _args_key(args) -> Tuple:
    """Hashable identity of a lowering's abstract inputs."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (
        str(treedef),
        tuple(
            (tuple(leaf.shape), str(leaf.dtype), str(getattr(leaf, "sharding", None)))
            for leaf in leaves
        ),
    )


def context_key(*, mesh=None, rules=None, donation: Tuple[int, ...] = (),
                steps_per_dispatch: int = 1) -> Tuple:
    """The non-aval half of a registry key: mesh layout, sharding rules,
    donation signature, and K (the fused-dispatch width)."""
    mesh_key = None
    if mesh is not None:
        mesh_key = (
            tuple(mesh.shape.items()),
            tuple(int(d.id) for d in mesh.devices.flat),
        )
    rules_key = None
    if rules is not None:
        rules_key = tuple(sorted(rules.rules.items()))
    return (mesh_key, rules_key, tuple(donation), int(steps_per_dispatch))


# --------------------------------------------------------------------------
# AOT registry

_registry: Dict[Tuple, Tuple[Any, Any]] = {}
_registry_lock = threading.Lock()

#: Registry bound: entries hold STRONG refs to the jitted fn (closure,
#: optimizer, mesh) and its compiled executable, so an unbounded registry
#: grows linearly in a long-lived process that keeps building Trainers
#: (a tuner loop).  FIFO-evict past this; jit's own dispatch cache still
#: backs an evicted fit, which just pays one lower+compile again.
REGISTRY_MAX_ENTRIES = 64


def aot_compile(jitted, *args, label: str = "step"):
    """``jitted.lower(*args).compile()`` with cold-start attribution spans.

    ``args`` may be concrete arrays, ShapeDtypeStructs, or a mix; nothing
    executes.  The two phases are spanned separately because they fail —
    and cost — differently: ``compile/lower`` is Python tracing,
    ``compile/backend_compile`` is XLA.
    """
    with tracing.span("compile/lower", fn=label):
        lowered = jitted.lower(*args)
    with tracing.span("compile/backend_compile", fn=label):
        return lowered.compile()


def get_or_compile(jitted, args, *, context: Tuple = (), label: str = "step"):
    """Registry-memoized :func:`aot_compile`.

    The key is (fn identity, context, abstract avals of ``args``); the
    entry holds a strong ref to ``jitted`` so a recycled ``id()`` can
    never alias a dead function's executables.  The registry is bounded
    at :data:`REGISTRY_MAX_ENTRIES` (FIFO eviction — an evicted fit
    falls back to jit's own cache or one recompile);
    :func:`clear_registry` drops everything.
    """
    key = (id(jitted), context, _args_key(args))
    with _registry_lock:
        entry = _registry.get(key)
    if entry is not None and entry[0] is jitted:
        metrics.counter_inc("compile/registry_hit")
        return entry[1]
    metrics.counter_inc("compile/registry_miss")
    compiled = aot_compile(jitted, *args, label=label)
    with _registry_lock:
        while len(_registry) >= REGISTRY_MAX_ENTRIES:
            _registry.pop(next(iter(_registry)))
        _registry[key] = (jitted, compiled)
    return compiled


def clear_registry() -> None:
    with _registry_lock:
        _registry.clear()


def registry_size() -> int:
    with _registry_lock:
        return len(_registry)


class AotStep:
    """Dispatch wrapper: the AOT executable when inputs match, jit otherwise.

    A compiled executable rejects mismatched input avals with a
    ``TypeError`` *before* executing (donated buffers are untouched), so
    the fallback costs nothing on the happy path — no per-dispatch shape
    walk, just one try.  The first mismatch permanently reverts this
    wrapper to the jitted function (shapes are stable within a fit; a
    mismatch means the caller moved on to different shapes, where jit's
    own cache is the right home).
    """

    __slots__ = ("jitted", "label", "_compiled")

    def __init__(self, jitted, label: str = "step"):
        self.jitted = jitted
        self.label = label
        self._compiled = None

    @property
    def compiled(self):
        return self._compiled

    def attach(self, compiled) -> None:
        self._compiled = compiled

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is not None:
            try:
                return compiled(*args)
            except TypeError as exc:
                logger.warning(
                    "compile-ahead executable for %s rejected its inputs "
                    "(%s); falling back to jit dispatch", self.label, exc,
                )
                self._compiled = None
        return self.jitted(*args)


# --------------------------------------------------------------------------
# Background compile-ahead


class CompileAhead:
    """A fit's background-compile plan: AotStep wrappers + the worker.

    ``wait(label)`` blocks until that ONE job has compiled (spanned as
    ``compile/ahead_wait`` — with prefetch warming in parallel this is ~0
    by the time the first window arrives, which is the whole point); jobs
    queued after it — the eval step rides behind the train step — keep
    compiling in the background and never delay the first dispatch.
    ``wait()`` with no label joins the whole worker.  A compile failure
    is recorded in ``error`` and logged, never raised: the wrappers
    simply stay on the jit path.
    """

    def __init__(self, steps: Dict[str, AotStep]):
        self.steps = steps
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._done = {label: threading.Event() for label in steps}

    def _launch(self, jobs) -> None:
        def worker():
            for aot_step, args, ctx in jobs:
                try:
                    if callable(args):
                        # Deferred avals (e.g. the eval job peeking the
                        # validation data's first batch): resolved HERE,
                        # off the main thread, so a slow pipeline never
                        # delays the jobs queued before it — or fit().
                        args = args()
                    if args is None:
                        continue  # thunk found nothing to compile against
                    aot_step.attach(get_or_compile(
                        aot_step.jitted, args, context=ctx,
                        label=aot_step.label,
                    ))
                except BaseException as exc:  # noqa: BLE001 — advisory only
                    self.error = exc
                    logger.warning(
                        "compile-ahead of %s failed (%s); that step will "
                        "compile on first dispatch instead",
                        aot_step.label, exc,
                    )
                finally:
                    self._done[aot_step.label].set()

        self._thread = threading.Thread(
            target=worker, daemon=True, name="cloud-tpu-compile-ahead"
        )
        self._thread.start()

    def wait(self, label: Optional[str] = None,
             timeout: Optional[float] = None) -> None:
        if label is not None:
            event = self._done.get(label)
            if event is None or event.is_set():
                return
            with tracing.span("compile/ahead_wait", fn=label):
                event.wait(timeout)
            return
        thread = self._thread
        if thread is None or not thread.is_alive():
            return
        with tracing.span("compile/ahead_wait"):
            thread.join(timeout)


def start_compile_ahead(jobs) -> CompileAhead:
    """Launch a background compile of ``jobs``.

    ``jobs`` is a list of ``(AotStep, abstract_args, context_key)``
    triples; compilation happens strictly in order on one worker thread
    (XLA compiles hold the CPU — parallel compiles would fight the
    prefetcher for cores without finishing sooner).  ``abstract_args``
    may instead be a zero-arg callable, resolved on the worker right
    before that job compiles (return None to skip the job) — for avals
    that themselves cost a blocking peek, like the eval step's
    validation batch.
    """
    steps = {job[0].label: job[0] for job in jobs}
    plan = CompileAhead(steps)
    plan._launch(jobs)
    return plan


# --------------------------------------------------------------------------
# Persistent cache

_persist_lock = threading.Lock()
_persist_state: Dict[str, Any] = {"checked": False, "enabled": False,
                                  "dir": None, "restore": None}

_OFF = ("", "off", "0", "false")


def maybe_enable_persistent_cache(cache_dir: Optional[str] = None) -> bool:
    """Turn on jax's on-disk compilation cache; returns whether it is on.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` where that is set (a
    cache placed from outside is never moved, whatever ``cache_dir``
    says), else the explicit ``cache_dir``, else
    ``CLOUD_TPU_COMPILE_CACHE``; none of them (or ``off``/``0``) means
    disabled and this is a cheap no-op — safe to call from every
    ``Trainer.fit`` and every ``ServingEngine``.  The decision is made
    once per process; pass a different ``cache_dir`` to re-decide.
    Everything is cached (``CLOUD_TPU_COMPILE_CACHE_MIN_SECS``, default
    0): these jobs are small and first-step latency is the metric.
    """
    placed = os.environ.get(ENV_JAX_CACHE_DIR, "").strip()
    if placed:
        cache_dir = placed
    elif cache_dir is None:
        cache_dir = os.environ.get(ENV_COMPILE_CACHE, "")
    if cache_dir.strip().lower() in _OFF:
        return False
    with _persist_lock:
        if _persist_state["checked"] and _persist_state["dir"] == cache_dir:
            return _persist_state["enabled"]

    import jax

    restore = {
        name: getattr(jax.config, name) for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as exc:
        logger.warning("compile cache dir %s unusable: %s", cache_dir, exc)
        with _persist_lock:
            _persist_state.update(checked=True, enabled=False, dir=cache_dir)
        return False
    if jax.config.jax_compilation_cache_dir != cache_dir:
        # Only ever jax's own variable's value (set after jax was
        # imported) or, with that unset, the caller's directory.
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        _drop_open_cache()
    try:
        min_secs = float(os.environ.get(ENV_COMPILE_CACHE_MIN_SECS, "0"))
    except ValueError:
        min_secs = 0.0
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent compile cache enabled at %s", cache_dir)
    metrics.counter_inc("compile/cache_enabled")
    with _persist_lock:
        if _persist_state["restore"] is None:
            _persist_state["restore"] = restore
        _persist_state.update(checked=True, enabled=True, dir=cache_dir)
    return True


def persistent_cache_enabled() -> bool:
    with _persist_lock:
        return bool(_persist_state["enabled"])


def _reset_persistent_state_for_tests() -> None:
    """Forget the once-per-process decision AND put jax's cache settings
    back to what they were before this module touched them."""
    import jax

    with _persist_lock:
        restore = _persist_state["restore"]
        _persist_state.update(checked=False, enabled=False, dir=None,
                              restore=None)
    for name, value in (restore or {}).items():
        jax.config.update(name, value)
    _drop_open_cache()


def _drop_open_cache() -> None:
    """jax opens its cache once, at the directory set at that moment; a
    directory set later takes effect only after the open one is dropped."""
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
