"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process.  Finds the cell's files by name (``harness.manifest``), builds
the system under test through the adapter its configuration names, warms
this cell's shapes (set-up), measures for ``--seconds``, then checks what
the timed path produced against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``) and, last,
``checks``: every number compared beside its limit.

Without a TPU, with fewer chips than the cell asks for, or on a device
whose peaks are not in ``harness/peaks.py`` it exits non-zero and prints no
result.  ``--tiny`` is the CPU rehearsal: tiny shapes, Pallas kernels in
interpret mode, the same code — and still a non-zero exit and no result
line.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
REHEARSAL_PEAKS = "TPU v5 lite"


def _parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--tiny", action="store_true",
                        help="CPU rehearsal at tiny sizes (never a result)")
    parser.add_argument("--root", default=ROOT,
                        help="a directory laid out as the repo's root "
                        "(BENCHMARK.json, benchmarks/configs, traffic, "
                        "layer_metrics): a cell that is proposed and not "
                        "yet in the repo's own BENCHMARK.json")
    parser.add_argument("--control", action="store_true",
                        help="also read the control and the planted faults "
                        "(for setting limits; prints them, changes nothing)")
    return parser.parse_args(argv)


def drive(cell, seed, seconds, trace, control=False,
          process_start=None, check=True):
    """Everything of a run but the look for a chip: the adapter's run, the
    trace's reduction, the readers.  Returns (outcome, metrics, breakdown).
    """
    from benchmarks.harness import context, peaks as peaks_lib, xplane

    run = context.Run(
        cell=cell, seed=seed, seconds=seconds, trace=bool(trace),
        process_start=PROCESS_START if process_start is None
        else process_start,
        trace_dir=TRACE_DIR, control=control, check=check)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    adapter = importlib.import_module(
        f"benchmarks.adapters.{cell.config['entry']}")
    outcome = adapter.run(run)
    setup_s = outcome.window_start - run.process_start
    if not trace:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        return outcome, metrics, None
    if outcome.traced is not None:
        outcome.trace = xplane.load(xplane.find_xplane(TRACE_DIR))
    import jax

    # The rehearsal's shares are of the v5e's peaks, and no result; a run
    # on a device that is not in the table is an error.
    peaks = peaks_lib.peaks_for(
        REHEARSAL_PEAKS if cell.tiny else jax.devices()[0].device_kind)
    metrics = {}
    for entry in cell.per_layer:
        spec = cell.layer_metrics[entry["name"]]
        reader = importlib.import_module(
            f"benchmarks.readers.{spec['reader']}")
        value = reader.read(spec["args"], outcome, peaks)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    breakdown = None
    if outcome.trace is not None:
        breakdown = {"device_ops": xplane.top_ops(outcome.trace),
                     "idle_gaps": xplane.idle_by_host_span(outcome.trace)}
    return outcome, metrics, breakdown


def rehearse_on_cpu():
    """The rehearsal walks the kernels through the Pallas interpreter
    (read by the program's ops.dispatch at every call)."""
    os.environ["CLOUD_TPU_FLASH_FORCE_INTERPRET"] = "1"


def switch_on_cache_and_spans():
    """The compile cache at JAX_COMPILATION_CACHE_DIR where it is set, else
    at one fixed path in the checkout (the directory is part of the cache's
    key), and the program's span collector."""
    from cloud_tpu.monitoring import tracing
    from cloud_tpu.training import compile_cache

    compile_cache.maybe_enable_persistent_cache(CACHE_DIR)
    tracing.enable()


def main(argv=None):
    args = _parse(sys.argv[1:] if argv is None else argv)
    from benchmarks.harness import (compare, manifest, peaks as peaks_lib,
                                    xplane)

    cell = manifest.Cell(args.workload, root=args.root, tiny=args.tiny)
    if args.tiny:
        rehearse_on_cpu()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.tiny:
        print(f"no TPU ({device}): the benchmark measures the chip and "
              "runs nowhere else", file=sys.stderr)
        return 2
    if on_chip:
        if device["count"] < cell.chips:
            print(f"{cell.name} needs {cell.chips} chips, JAX finds "
                  f"{device['count']}", file=sys.stderr)
            return 2
        peaks_lib.peaks_for(device["kind"])  # unknown device: an error

    switch_on_cache_and_spans()
    outcome, metrics, breakdown = drive(
        cell, args.seed, args.seconds, args.trace, control=args.control)
    correct = compare.judge(outcome.checks) and outcome.failed == 0
    device["memory_peak_bytes"] = outcome.memory_peak_bytes
    if args.trace and outcome.trace is not None:
        device["busy_s"] = xplane.busy_seconds(outcome.trace)
        device["window_s"] = xplane.window_seconds(outcome.trace)
    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in outcome.checks}
    for name, value, limit in outcome.control_checks:
        print(f"control {name} = {value!r} (limit {limit!r}; None: not "
              "held)", flush=True)
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    if not on_chip:
        print("rehearsal reached its end; not a result: "
              + json.dumps(result), file=sys.stderr)
        return 1
    for name, value, limit in outcome.checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
