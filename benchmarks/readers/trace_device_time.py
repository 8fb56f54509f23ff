"""Device seconds of the trace's events that match ``pattern`` (on the
``ops`` or the ``modules`` line), per unit of ``per`` — a number of
``outcome.work``, or ``count`` for the events' own number of executions
(one cut by the window counts for its share inside) — times ``scale``."""

from benchmarks.harness import xplane


def read(args, outcome, peaks):
    if outcome.trace is None:
        return None
    line = args.get("line", "ops")
    seconds, _ = xplane.device_seconds(outcome.trace, args["pattern"], line)
    per = (xplane.executions(outcome.trace, args["pattern"], line)
           if args["per"] == "count" else outcome.work.get(args["per"]))
    if not seconds or not per:
        return None
    return args.get("scale", 1.0) * seconds / per
