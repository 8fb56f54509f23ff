"""A counter of the program over the window (``key``), or the quotient of
two (``key`` over ``over``), times ``scale``."""


def read(args, outcome, peaks):
    value = outcome.stats.get(args["key"])
    if value is None:
        return None
    if "over" in args:
        over = outcome.stats.get(args["over"])
        if not over:
            return None
        value = value / over
    return args.get("scale", 1.0) * value
