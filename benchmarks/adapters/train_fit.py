"""``entry: train_fit`` — a ResNet through ``Trainer.fit`` under the mesh
``core.bootstrap`` installs for one device, as a user's script drives it.

One ``Trainer``, one ``fit``: set-up drives the compiled step through its
first steps from the seed (read for ``correct``), goes on through the
warm-up, and the same call runs the window.  A callback stamps step ends
and sets ``stop_training`` when the window is over.
"""

import functools
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.harness import compare, context, traffic, work, xplane
from cloud_tpu import parallel
from cloud_tpu.models import resnet
from cloud_tpu.monitoring import tracing
from cloud_tpu.parallel import planner
from cloud_tpu.training import data, trainer as trainer_lib

CHECK_STEPS = 3
STEP_WAIT_SPAN = "bench/step_wait"


class _TiledRows:
    """The pool's rows, met again and again: ``ArrayDataset`` gathers
    every batch from it by index as it does from an array, and an epoch is
    longer than any window."""

    def __init__(self, rows, repeats):
        self.rows, self.repeats = rows, repeats

    def __len__(self):
        return len(self.rows) * self.repeats

    def __getitem__(self, index):
        return self.rows[np.asarray(index) % len(self.rows)]


def _momentum_trace(opt_state):
    """The params-shaped momentum of ``optax.sgd(..., momentum=...)``:
    after the first step it IS the first gradient."""
    for part in opt_state:
        if hasattr(part, "trace"):
            return part.trace
    raise ValueError("no momentum trace in the optimizer's state")


def _host_leaves(tree):
    """A copy on the host (the next step donates the state)."""
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(tree)]


class _Driver(trainer_lib.Callback):
    """Trainer callback: reads the first steps, opens the window after
    the warm-up, stamps every step of it, and closes it."""

    def __init__(self, run, params0, warm_steps):
        self.run, self.params0, self.warm = run, params0, warm_steps
        self.steps = 0
        self.losses, self.grad, self.change = [], None, None
        self.window_start, self.stamps, self._previous = None, [], None
        self.watch = None
        self.traced, self._annotation, self._profiling = None, None, False
        self.untraced_until = None
        self._change = jax.jit(lambda p, p0: jax.tree_util.tree_map(
            jnp.subtract, p, p0))

    def on_step_end(self, step, logs, trainer):
        self.steps += 1
        n = self.steps
        if n <= CHECK_STEPS:
            self.losses.append(logs["loss"])
            if n == 1:
                self.grad = _host_leaves(
                    _momentum_trace(trainer.state.opt_state))
            if n == CHECK_STEPS:
                self.change = _host_leaves(self._change(
                    trainer.state.params, self.params0))
        if n < self.warm:
            return
        if n == self.warm:
            jax.block_until_ready(trainer.state)
            self.watch = context.HostWatch().start()
            self.window_start = time.perf_counter()
            return
        # One step stays in flight: wait for the one before it, as a
        # script that logs its loss does, so a stamp is a step done.  The
        # wait is the device's time, not the host loop's: it gets a span
        # of its own, which ``fit_host_ms_per_step`` takes off.
        if self._previous is not None:
            with tracing.span(STEP_WAIT_SPAN):
                jax.block_until_ready(self._previous)
        self._previous = logs["loss"]
        now = time.perf_counter()
        self.stamps.append(now)
        since = now - self.window_start
        if self.run.trace:
            self._trace(since, trainer)
        elif since >= self.run.seconds:
            trainer.stop_training = True

    def _trace(self, since, trainer):
        """A traced run's window is the traced one: the profiler starts a
        second ahead of it (the first ops after a start are not recorded),
        and is stopped once ``fit`` has returned (stopping takes most of a
        minute)."""
        after, length = self.run.cell.traffic["trace_window_s"]
        if not self._profiling and since >= after - 1.0:
            self.untraced_until = time.perf_counter()
            xplane.start_trace(self.run.trace_dir)
            self._profiling = True
        elif self._profiling and self._annotation is None and since >= after:
            self._annotation = jax.profiler.TraceAnnotation(
                xplane.WINDOW_ANNOTATION)
            self._annotation.__enter__()
            self.traced = (time.perf_counter(), None)
        elif self.traced is not None and since >= after + length:
            jax.block_until_ready(trainer.state)
            self._annotation.__exit__(None, None, None)
            self.traced = (self.traced[0], time.perf_counter())
            trainer.stop_training = True


def run(run):
    sizes, mix = run.cell.config, run.cell.traffic
    reference = importlib.import_module(
        f"benchmarks.references.{sizes['reference']}")
    config = resnet.ResNetConfig(
        stage_sizes=tuple(sizes["stage_sizes"]), width=sizes["width"],
        num_classes=sizes["num_classes"], num_groups=sizes["num_groups"],
        dtype=jnp.dtype(sizes["compute_dtype"]))
    batch, image = mix["batch_size"], mix["image_size"]
    pool = traffic.make_image_pool(mix, run.seed, sizes["num_classes"])
    dataset = data.ArrayDataset(
        {name: _TiledRows(rows, mix["pool_repeats"])
         for name, rows in pool.items()}, batch)
    run.say(f"pool of {len(pool['label'])} rows made "
            f"{time.perf_counter() - run.process_start:.1f}s after the start")
    key = reference.root_key(run.seed)
    optimizer = mix["optimizer"]
    compiles = context.CompileCounter.get()

    # As bootstrap does: plan a mesh over the local devices, install it.
    mesh = planner.plan_mesh(num_devices=1).build(jax.devices()[:1])
    with parallel.use_mesh(mesh):
        mesh = parallel.get_global_mesh()
        t = trainer_lib.Trainer(
            functools.partial(resnet.loss_fn, config=config, mesh=mesh),
            optax.sgd(optimizer["learning_rate"],
                      momentum=optimizer["momentum"]),
            lambda k: reference.init_params(k, sizes),
            mesh=mesh, logical_axes=resnet.param_logical_axes(config))
        t.init_state(key)
        # The benchmark's own copy of the first parameters (the step
        # donates the state's).
        driver = _Driver(run, jax.tree_util.tree_map(jnp.copy, t.state.params),
                         mix["warm_steps"])
        run.say(f"state made {time.perf_counter() - run.process_start:.1f}s "
                "after the start")
        t.fit(dataset, epochs=1, callbacks=[driver], **mix["fit"])
        jax.block_until_ready(t.state)
    window_end = time.perf_counter()
    driver.watch.stop()
    if run.trace:
        xplane.stop_trace()
        run.say("profiler stopped")
    start = driver.window_start
    window_s = window_end - start
    run.say(f"window {window_s:.3f}s, {len(driver.stamps)} steps of "
            f"{batch}; compilations inside it: "
            f"{compiles.between(start, window_end)}; persistent cache so "
            f"far: {compiles.cache}; {driver.watch}")
    gaps = np.diff([start] + driver.stamps) * 1e3
    run.say("step ends apart (ms): first " + " ".join(
        f"{g:.0f}" for g in gaps[:8]) + "; p50 %.1f p95 %.1f max %.1f" % (
            np.percentile(gaps, 50), np.percentile(gaps, 95), gaps.max()))
    program = {"loss": np.asarray([float(x) for x in driver.losses]),
               "grad": driver.grad, "change": driver.change}
    spans = context.program_spans()
    peak = context.memory_peak_bytes()
    run.say(f"memory_stats: {jax.local_devices()[0].memory_stats()}")
    # With the profiler on the loop runs at half its speed or less (the
    # device waits between steps), so the time a step takes and the whole
    # step's share of the peak are taken over the steps before it starts:
    # from the first of their stamps to the last.
    steps_traced, untraced, untraced_s, observed = 0, [], 0.0, {}
    if driver.traced is not None:
        steps_traced = sum(
            1 for s in driver.stamps
            if driver.traced[0] <= s <= driver.traced[1])
        untraced = [s for s in driver.stamps if s <= driver.untraced_until]
        if len(untraced) < 2:
            raise RuntimeError("the profiler started before two steps of "
                               "the window had ended: trace_window_s is "
                               "too early for this cell")
        untraced_s = untraced[-1] - untraced[0]
        observed["bench/step_ms"] = untraced_s * 1e3 / (len(untraced) - 1)
        run.say(f"before the profiler: {len(untraced) - 1} steps in "
                f"{untraced_s:.3f}s; in the traced "
                f"window of {driver.traced[1] - driver.traced[0]:.3f}s: "
                f"{steps_traced} steps")
    del t, driver.params0, dataset

    # The reference follows the first steps on the same rows, once the
    # program's state is freed and its peak read.
    checks, control_checks = [], []
    if run.check:
        first = [{name: rows[i * batch:(i + 1) * batch]
                  for name, rows in pool.items()} for i in range(CHECK_STEPS)]
        steps = functools.partial(
            reference.first_steps, key, sizes, first,
            rows=mix["reference_rows"],
            learning_rate=optimizer["learning_rate"],
            momentum=optimizer["momentum"])
        started = time.perf_counter()
        expected = steps(precision="f32")
        # A cell whose limits are not set yet compares nothing, and is not
        # correct: its readings are the control run's to print.
        limits = mix["limits"] or {}
        # What the configuration states, bf16, errs leaf by leaf as a sound
        # program may: the numbers that hold every leaf are held to it.
        baseline = (steps(precision="bf16")
                    if run.control or any("baseline" in n for n in limits)
                    else None)
        gaps = compare.training_gaps(program, expected, baseline)
        names = expected["names"]
        run.say(f"reference: {CHECK_STEPS} float32 steps"
                + ("" if baseline is None else " and the bf16 baseline")
                + f" in {time.perf_counter() - started:.1f}s (persistent "
                f"cache: {compiles.cache}); by the worst leaf: " + "; ".join(
                    f"{what} at {names[at]}"
                    for what, at in gaps["worst"].items()))
        checks = [(name, gaps[name], limits[name]) for name in limits]
        if run.control:
            got = {"program": gaps}
            for label, kw in (
                    ("fp8", {"precision": "fp8"}),
                    ("half_batch", {"precision": "bf16", "drop_half": True})):
                got[label] = compare.training_gaps(steps(**kw), expected,
                                                   baseline)
            got["bf16"] = compare.training_gaps(baseline, expected)
            control_checks = [
                (f"{label}.{name}", value, limits.get(name))
                for label, readings in got.items()
                for name, value in readings.items() if name != "worst"]
    return context.Outcome(
        window_start=start, window_s=window_s,
        end_to_end={"train_samples_per_s":
                    len(driver.stamps) * batch / window_s},
        attempted=len(driver.stamps), failed=0, checks=checks,
        memory_peak_bytes=peak, spans=spans, stats=observed,
        work={
            "train_flops": (len(untraced) - 1) * batch
            * work.resnet_train_flops_per_sample(sizes, image),
            "train_flops_seconds": untraced_s,
            # Of ONE step: the trace says how many ran in its window.
            "group_norm_step": {
                "flops": work.group_norm_step_flops(sizes, image, batch),
                "bytes": work.group_norm_step_bytes(sizes, image, batch)},
        },
        traced=driver.traced, control_checks=control_checks)
