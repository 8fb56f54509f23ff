"""A whole program's share of its roofline: the least time the chip could
take for ``outcome.work[work]`` — the ``flops`` and ``bytes`` ONE execution
of the program must do, from shapes and the engine's counters — over the
device time of the MODULE events matching ``pattern``, with the trace
counting the executions (``xplane.executions``: one that the window cuts
counts for its share inside, as its device time does).  It names no
operation, so it reads the same work whatever later implements the step."""

from benchmarks.harness import peaks as peaks_lib
from benchmarks.harness import xplane


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    seconds, _ = xplane.device_seconds(outcome.trace, args["pattern"],
                                       "modules")
    times = xplane.executions(outcome.trace, args["pattern"])
    needed = outcome.work.get(args["work"])
    if not seconds or not times or not needed or not needed["bytes"]:
        return None
    share, _ = peaks_lib.roofline_share(
        times * needed["flops"], times * needed["bytes"], seconds, peaks)
    return share
