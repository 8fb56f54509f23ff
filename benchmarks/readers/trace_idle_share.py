"""The share of the traced window in which no operation ran on the
device."""

from benchmarks.harness import xplane


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    window = xplane.window_seconds(outcome.trace)
    busy = xplane.busy_seconds(outcome.trace)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
