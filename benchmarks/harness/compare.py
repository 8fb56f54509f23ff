"""The comparisons that decide ``correct``: the program's readings against
the plain reference's, each as one number with a limit of its own."""

import numpy as np

#: A leaf whose first gradient in the reference is under this share of the
#: median leaf's is nought to rounding: its change under the optimizer is
#: round-off alone, and is left out of the change's comparison.
NEGLIGIBLE_GRADIENT = 1e-3


#: The least error a leaf is allowed under the baseline, as a share of the
#: median leaf's error there.
BASELINE_FLOOR = 0.1


def _norms(leaves):
    return np.asarray([np.linalg.norm(np.ravel(np.asarray(x, np.float64)))
                       for x in leaves])


def _by_leaf(gaps, reference, keep=None):
    """``gaps`` (one number a leaf) against the reference's norm of that
    leaf or of the median leaf, whichever is larger: (the worst, which leaf
    it is, the median, the least)."""
    if keep is None:
        keep = np.ones(reference.shape, bool)
    floor = np.median(reference[keep])
    shares = np.where(keep, gaps / np.maximum(reference, floor), 0.0)
    return (float(np.max(shares)), int(np.argmax(shares)),
            float(np.median(shares[keep])), float(np.min(shares[keep])))


def training_gaps(program, reference, baseline=None):
    """All sides are dicts of ``loss`` [steps] and of ``grad`` and
    ``change``: the leaves of the first gradient and of the parameters'
    change over the steps, as arrays.

    ``loss_gap``: the widest relative gap of a step's loss.  Of the gap
    between the program's NORM of a leaf and the reference's:
    ``grad_gap`` / ``change_gap`` by the worst leaf (the change among the
    leaves whose reference gradient is not negligible),
    ``grad_gap_median`` / ``change_gap_median`` by the median leaf.  Of the
    norm of the DIFFERENCE between the program's leaf and the reference's,
    which also sees an error that leaves the norm as it was:
    ``grad_error`` / ``change_error`` and ``..._median`` likewise, and
    ``..._least`` by the leaf that reads least: the backward
    pass multiplies a rounding error layer by layer, so the leaf it has
    multiplied least (the head's) is the cleanest witness of the forward
    pass's precision.  ``worst``: which leaves were the worst.

    With ``baseline`` — the reference computed in the precision the
    configuration states, which errs as a sound program may —
    ``grad_error_over_baseline`` / ``change_error_over_baseline``: the
    program's error on a leaf over the baseline's error on the same leaf,
    by the worst leaf.  It holds EVERY leaf, each to the error its place
    in the network allows: a fault in one kernel's gradients passes the
    median and the least leaf, and not this."""
    loss_p, loss_r = np.asarray(program["loss"]), np.asarray(reference["loss"])
    out = {"loss_gap": float(np.max(np.abs(loss_p - loss_r)
                                    / np.abs(loss_r))), "worst": {}}
    keep = None
    for what in ("grad", "change"):
        norm_p, norm_r = _norms(program[what]), _norms(reference[what])
        if keep is None:
            keep = norm_r >= NEGLIGIBLE_GRADIENT * np.median(norm_r)
        among = None if what == "grad" else keep
        worst, at, median, _ = _by_leaf(np.abs(norm_p - norm_r), norm_r,
                                        among)
        out.update({f"{what}_gap": worst, f"{what}_gap_median": median})
        out["worst"][what] = at
        error = _norms([np.asarray(p, np.float64) - np.asarray(r)
                        for p, r in zip(program[what], reference[what])])
        # A leaf that is nought on both sides would read least whatever
        # the precision: the difference is read among the others only.
        worst, _, median, least = _by_leaf(error, norm_r, keep)
        out.update({f"{what}_error": worst, f"{what}_error_median": median,
                    f"{what}_error_least": least})
        if baseline is not None:
            allowed = _norms([np.asarray(b, np.float64) - np.asarray(r)
                              for b, r in zip(baseline[what],
                                              reference[what])])
            # A leaf on which the baseline errs by next to nothing is held
            # to a share of the median leaf's error.
            allowed = np.maximum(
                allowed, BASELINE_FLOOR * np.median(allowed[keep]))
            ratios = np.where(keep, error / allowed, 0.0)
            out[f"{what}_error_over_baseline"] = float(np.max(ratios))
            out["worst"][f"{what}_over_baseline"] = int(np.argmax(ratios))
    return out


def widest_logit_gap(scores, valid):
    """How far, at the worst of the ``valid`` positions, the chosen token's
    logit lies below the reference's best, in units of that position's
    logit standard deviation.  ``scores`` is a reference's ``score``."""
    gaps = (scores["best"] - scores["chosen"]) / scores["std"]
    return float(np.max(np.where(valid, gaps, 0.0)))


def judge(checks):
    """``checks``: [(name, value, limit)], each to hold ``value <= limit``
    (a NaN fails).  A run that compared nothing is not correct."""
    return bool(checks) and all(value <= limit for _, value, limit in checks)
