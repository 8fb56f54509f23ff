"""Readings for setting limits and rates — run by hand on the chip, never
by the driver.  One process drives a cell over several seeds (set-up is
most of a run, and the compiled programs are shared):

    python benchmarks/calibrate.py --workload <cell> --seeds 12 \
        --first-seed 4100000001 --seconds 8 --control-seeds 3 \
        [--root tests/benchmarks/proposed]

Prints one JSON line a seed: the numbers compared, the control's and the
planted faults' (on the first ``--control-seeds`` seeds), and the
end-to-end metrics.  ``--root`` is a directory laid out as the repo's root:
a cell that is proposed and not yet in ``BENCHMARK.json``, or a copy of a
cell at another rate for the sweep that finds the highest one the system
sustains.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--control-seeds", type=int, default=0)
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--no-check", action="store_true",
                        help="skip the reference (a sweep for a rate)")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    from benchmarks import run as bench_run
    from benchmarks.harness import manifest

    if args.tiny:
        bench_run.rehearse_on_cpu()
    bench_run.switch_on_cache_and_spans()
    cell = manifest.Cell(args.workload, root=args.root, tiny=args.tiny)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        started = time.perf_counter()
        outcome, metrics, _ = bench_run.drive(
            cell, seed, args.seconds, 0, control=i < args.control_seeds,
            process_start=started, check=not args.no_check)
        print("CALIBRATE " + json.dumps({
            "seed": seed,
            "checks": {n: v for n, v, _ in outcome.checks},
            "control": {n: v for n, v, _ in outcome.control_checks},
            "metrics": {n: m["value"] for n, m in metrics.items()},
            "attempted": outcome.attempted, "failed": outcome.failed,
            "memory_peak_bytes": outcome.memory_peak_bytes,
            "wall_s": time.perf_counter() - started}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
