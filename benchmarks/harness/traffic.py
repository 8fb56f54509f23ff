"""The one general generator of traffic: reads a mix's parameters
(``traffic/<cell>.json``) and makes the requests or the batches of a run
from ``--seed``.

Every seed gets the same SET of sizes and arrival gaps — the quantiles of
the distributions the file names — in another order, so that seeds change
the order of the work and not its amount.  Where the mix gives
``order.block_requests``, the order is shuffled inside blocks of that many
requests, each block dealt an even share of the quantiles: every stretch
of the window then carries the same work, and a seed moves only what
meets what inside a stretch.

What that hides: an open loop made this way is NOT a Poisson process.  Its
gaps have the exponential distribution's shape, but no stretch of a block's
length is busier than another and no seed carries more work than another,
so the swings of load that make a queue's tail under independent arrivals,
and with them admission and bursts, do not show.  The arrival process is
named for what it is: ``stratified_exponential``.
"""

import math

import numpy as np


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def shuffled(values, rng, block=None):
    """``values`` in an order drawn from ``rng``: a plain permutation, or,
    with ``block``, the sorted values dealt out evenly into blocks of about
    ``block`` and permuted inside each."""
    n = len(values)
    if not block or block >= n:
        return rng.permutation(values)
    ordered = np.sort(values)
    blocks = max(1, round(n / block))
    # Dealt back and forth, so that no block gets the low end of every
    # round.
    row, col = np.divmod(np.arange(n), blocks)
    dealt_to = np.where(row % 2 == 0, col, blocks - 1 - col)
    return np.concatenate(
        [rng.permutation(ordered[dealt_to == b]) for b in range(blocks)])


def draw_lengths(spec, n, rng, block=None):
    """``n`` whole lengths: the quantiles of ``spec``'s distribution,
    shuffled."""
    q = _quantiles(n)
    if spec["dist"] != "loguniform":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    values = np.exp(lo + q * (hi - lo))
    lengths = np.clip(np.rint(values), spec["min"],
                      spec["max"]).astype(np.int64)
    return shuffled(lengths, rng, block)


def draw_arrivals(spec, seconds, rng, block=None):
    """Due times (seconds from the window's start) of the requests.

    ``stratified_exponential``: ``rate_per_s * seconds`` requests whose
    gaps are the exponential distribution's quantiles, shuffled (see the
    module's text for what that is not).  ``backlog``:
    ``requests_per_s * seconds`` requests, all due at 0."""
    process = spec["process"]
    if process == "backlog":
        return np.zeros(max(1, round(spec["requests_per_s"] * seconds)))
    if process != "stratified_exponential":
        raise ValueError(f"unknown arrival process {process!r}")
    rate = spec["rate_per_s"]
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-_quantiles(n)) / rate
    due = np.cumsum(shuffled(gaps, rng, block))
    # The quantiles' mean is a hair under 1/rate; stretch so that the last
    # request is due just inside the window whatever n is.
    return due * (seconds * (n - 0.5) / n / due[-1])


def make_requests(mix, seconds, seed, vocab_size):
    """The requests of one run: dicts of ``due_s``, ``prompt`` (token
    ids, none of them 0) and ``max_new_tokens``, in order of due time."""
    rng = np.random.default_rng([int(seed), 1])
    block = mix.get("order", {}).get("block_requests")
    due = draw_arrivals(mix["arrivals"], seconds, rng, block)
    n = len(due)
    prompt_lens = draw_lengths(mix["prompt_tokens"], n, rng, block)
    output_lens = draw_lengths(mix["output_tokens"], n, rng, block)
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(1, vocab_size, int(prompt_lens[i]),
                                    dtype=np.int32),
             "max_new_tokens": int(output_lens[i])}
            for i in range(n)]


def make_image_pool(mix, seed, num_classes):
    """A pool of ``pool_batches`` batches of distinct rows: float32 NHWC
    images and int32 labels, as arrays of all the pool's rows."""
    rng = np.random.default_rng([int(seed), 2])
    rows = mix["pool_batches"] * mix["batch_size"]
    size = mix["image_size"]
    return {
        "image": rng.standard_normal((rows, size, size, 3),
                                     dtype=np.float32),
        "label": rng.integers(0, num_classes, rows).astype(np.int32),
    }
