"""Tail and rate arithmetic of the end-to-end metrics."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile`` does by default."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    if ordered[lo] == ordered[hi]:  # also: both infinite
        return ordered[lo]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def tail_with_failures(latencies, failed, q=95.0):
    """A tail over ALL requests: each failed or refused request counts as
    the worst (infinitely late), so enough of them move the tail to
    infinity rather than out of the sample."""
    return percentile(list(latencies) + [math.inf] * failed, q)


def rate(amount, window_s):
    """Work over the WHOLE window: a stall inside it lowers the rate."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return amount / window_s
