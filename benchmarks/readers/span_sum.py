"""Over the program's spans of the given ``names`` in one ``phase``
(``setup``: begun before the window; ``window``: begun inside it):
their total (``stat: sum``, less the total of the spans in ``minus``,
divided by the count of the spans named in ``per`` if given) or a
percentile of their lengths (``stat: p95``)."""

from benchmarks.harness import stats


def read(args, outcome, peaks):
    start, end = outcome.window_start, outcome.window_start + outcome.window_s

    def in_phase(span_start):
        if args["phase"] == "setup":
            return span_start < start
        return start <= span_start <= end

    picked = [s for s in outcome.spans if in_phase(s[1])]
    lengths = [s[2] for s in picked if s[0] in args["names"]]
    if not lengths:
        return None
    scale = args.get("scale", 1.0)
    stat = args.get("stat", "sum")
    if stat == "sum":
        per = args.get("per")
        count = sum(1 for s in picked if s[0] == per) if per else 1
        total = sum(lengths) - sum(
            s[2] for s in picked if s[0] in args.get("minus", ()))
        return scale * total / count if count else None
    if stat.startswith("p"):
        return scale * stats.percentile(lengths, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
