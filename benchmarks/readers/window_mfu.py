"""A whole stretch's share of the chip's peak: the operations the algorithm
needs for the work done in it (``outcome.work[work]``, from
``harness.work``; padding and recomputation not counted) over its length
times the peak.  The stretch is the traced window, or, where the adapter
gives ``outcome.work[work + "_seconds"]``, a stretch of its own."""

from benchmarks.harness import xplane


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    flops = outcome.work.get(args["work"])
    window = outcome.work.get(args["work"] + "_seconds",
                              xplane.window_seconds(outcome.trace))
    if not flops or window <= 0:
        return None
    chips = max(len(outcome.trace["devices"]), 1)
    return 100.0 * flops / (window * chips * peaks["bf16_flops_per_s"])
