"""The comparison that decides ``correct`` for a training cell.

Two sides took the same first steps from the same weights on the same
batches: the timed path (the program's compiled step, through the
window's own call and feed) and the plain reference.  Each side hands in
its loss at every step, the weights after the first step and the weights
after the last, and the first gradient as the optimizer got it.  Under
SGD that follows from the first step's change, since the momentum starts
at nought (``g1 = -(w1 - w0) / lr``; with weight decay it holds ``wd w0``
too, on both sides alike), and :func:`side` derives it where it is given
none.  Under Adam the first step's change is ``-lr sign(g)`` whatever the
gradient, so a side hands in ``grad=``: the first moment after the first
step over ``1 - beta1``, leaf by leaf.

What the change over all the steps (``w_last - w0``) can show under Adam:
that every leaf moved, once and by the rate (a state left unchanged reads
1; a leaf moved double reads 1), and the signs and relative sizes of the
gradients from the second step on.  It cannot show a gradient that is
wrong by a factor: Adam divides it out.  That shows in ``grad_*``.

A leaf's gap is the gap between the two sides' NORMS of that leaf (not
the norm of their difference), against the reference's norm of that leaf
or of the median leaf, whichever is larger, since some gradients are all
but zero.  Numbers, each with a limit of its own in the cell's traffic
file (``null`` there: read and printed, not compared):

- ``grad_gap_median``, ``change_gap_median``: the median leaf's gap, of
  the first gradient and of the change of the weights over all the
  steps.  Leaves whose first gradient in the reference is under a
  thousandth of the median leaf's are left out of the change: they move
  by round-off alone.
- ``grad_gap_quartile``: the lower-quartile leaf's gap of the first
  gradient.  A leaf's norm averages the rounding of its thousands to
  millions of numbers, so most leaves' gaps follow which ReLUs a
  rounding flipped more than how coarse the rounding was; the quietest
  quarter follows the precision most closely (PERF.md).
- ``grad_gap_weights``: the median gap of the first gradient over the
  leaves that are a product's weights (two dimensions or more); they are
  the largest leaves and the least noisy.
- ``grad_gap_zero``: over the leaves whose gradient is nought to rounding
  in the reference (the rule above), the median of the program's norm
  against the median leaf's: the rounding of the backward pass and
  nothing else.  Not there where a net has no such leaf.
- ``grad_gap_worst``, ``change_gap_worst``: the widest leaf's gap, over
  every leaf.
- ``grad_gap_weights_worst``, ``change_gap_weights_worst``: the widest
  gap over the products' weights alone: a wrong update, or none, of a
  single one of them shows here, where a median passes it.
- ``loss_gap_<k>``: ``|loss - reference| / |reference|`` at step k.
"""
import numpy as np

#: leaves with a reference gradient under this share of the median
#: leaf's are left out of ``change_gap``
TINY_GRADIENT = 1e-3


def leaf_norms(leaves):
    return np.array([np.linalg.norm(np.asarray(a, np.float64).ravel())
                     for a in leaves])


def leaf_gaps(prog, ref):
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


#: the numbers :func:`numbers` can yield, beside ``loss_gap_<k>``
NAMES = ("grad_gap_median", "grad_gap_quartile", "change_gap_median",
         "grad_gap_weights", "grad_gap_zero", "grad_gap_worst",
         "change_gap_worst", "grad_gap_weights_worst",
         "change_gap_weights_worst")


def side(losses, w0, w1, w_last, lr, grad=None):
    """One side's readings: losses, per-leaf norms of the first gradient
    (of ``grad``, the leaves of the gradient itself, where the optimizer's
    state holds it; else derived from the first step's change) and of the
    change over all the steps, and which leaves are a product's weights."""
    if grad is None:
        grad = [(np.asarray(a, np.float64) - np.asarray(b, np.float64))
                / lr for a, b in zip(w1, w0)]
    grad = leaf_norms(grad)
    change = leaf_norms([np.asarray(a, np.float64) - np.asarray(b, np.float64)
                         for a, b in zip(w_last, w0)])
    return {"losses": [float(v) for v in losses], "grad": grad,
            "change": change, "weights": [np.ndim(a) >= 2 for a in w0]}


def numbers(prog, ref):
    """``({name: value}, {name: index of the leaf at fault})``."""
    out, where = {}, {}
    for k, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap_{k}"] = abs(a - b) / abs(b)
    zero = ref["grad"] < TINY_GRADIENT * np.median(ref["grad"])
    weights = np.asarray(ref["weights"], bool)
    grad = leaf_gaps(prog["grad"], ref["grad"])
    change = np.where(zero, 0.0, leaf_gaps(prog["change"], ref["change"]))
    out["grad_gap_median"] = np.median(grad)
    out["grad_gap_quartile"] = np.quantile(grad[~zero], 0.25)
    out["change_gap_median"] = np.median(change[~zero])
    out["grad_gap_weights"] = np.median(grad[weights])
    if zero.any():
        out["grad_gap_zero"] = np.median(grad[zero])
    for name, gaps in (
            ("grad_gap_worst", grad), ("change_gap_worst", change),
            ("grad_gap_weights_worst", np.where(weights, grad, 0.0)),
            ("change_gap_weights_worst", np.where(weights, change, 0.0))):
        where[name] = int(np.argmax(gaps))
        out[name] = gaps[where[name]]
    # a number that is not a number fails whatever the limit
    return {k: float(v) if np.isfinite(v) else float("inf")
            for k, v in out.items()}, where


def verdict(values, limits):
    """``(correct, compared, reported)``: every number that has a limit
    lies within it.  ``compared`` is ``{name: {"value", "limit"}}``;
    ``reported`` holds the numbers whose limit is ``null``.  A number
    that the limits do not name is an error, not a pass."""
    missing = [k for k in values if k not in limits]
    if missing:
        raise KeyError(f"no limit for {missing} in this cell's limits")
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in values.items() if limits[k] is not None}
    reported = {k: v for k, v in values.items() if limits[k] is None}
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    return ok, compared, reported
