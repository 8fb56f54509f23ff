"""A kernel's share of its roofline: the least time the chip could take
for ``outcome.work[work]`` (its ``flops`` and ``bytes``, from
``harness.work``) over the device time of the events matching
``pattern``.  With ``per_module`` the work is that of ONE execution of the
program whose module events match it, and the trace says how many ran in
the window (``xplane.executions``): the count then has the clock and the
clipping of the device time it is held against."""

from benchmarks.harness import peaks as peaks_lib
from benchmarks.harness import xplane


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    seconds, _ = xplane.device_seconds(outcome.trace, args["pattern"])
    needed = outcome.work.get(args["work"])
    times = (xplane.executions(outcome.trace, args["per_module"])
             if "per_module" in args else 1.0)
    if not seconds or not times or not needed or not needed["flops"]:
        return None
    share, _ = peaks_lib.roofline_share(
        times * needed["flops"], times * needed["bytes"], seconds, peaks)
    return share
