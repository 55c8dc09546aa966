"""The comparison that decides ``correct`` for a training cell.

Three numbers, each held to a limit of the cell's own
(``bench/limits/<cell>.json``):

- ``loss_gap``: the largest relative gap between the program's loss and
  the reference's over the compared steps;
- ``grad_gap``: the first step's gradient as the optimizer got it,
  worked out from the Adam state after one step (``m / (1 - b1)``), by
  the worst leaf;
- ``delta_gap``: the change of the parameters over the compared steps,
  as the state after them keeps it, by the worst leaf.

A leaf's gap is the gap between the two norms (not the norm of the
difference) over the reference's norm of that leaf or of the median
leaf, whichever is larger.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left
out.  Besides, every compared batch must be made of distinct edges of
the generated graph with negatives in range (``bad_rows``, limit 0),
and drawn uniformly: its positives over the graph's edges and its
negatives over the items (``skewed_batches``, limit 0).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap")


def gap_by_worst_leaf(got: dict, ref: dict, ref_grads: dict) -> float:
    med_g = statistics.median(ref_grads.values())
    med = statistics.median(ref.values())
    return max(abs(got[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in ref if ref_grads[k] >= 1e-3 * med_g)


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers for the program's readings ``prog`` against
    the reference's ``ref`` (both as ``reference.train`` returns them)."""
    losses = [abs(a - b) / max(abs(b), 1e-30)
              for a, b in zip(prog["losses"], ref["losses"], strict=True)]
    finite = all(np.isfinite(prog["losses"]))
    return {
        "loss_gap": max(losses) if finite else float("inf"),
        "grad_gap": gap_by_worst_leaf(prog["grad_norms"], ref["grad_norms"],
                                      ref["grad_norms"]),
        "delta_gap": gap_by_worst_leaf(prog["delta_norms"],
                                       ref["delta_norms"],
                                       ref["grad_norms"]),
    }


def bad_rows(batches, user: np.ndarray, item: np.ndarray, n_items: int,
             batch: int) -> int:
    """Rows of the compared batches that are not what the mix asks for:
    a batch of the wrong size counts its missing rows; a (user, positive)
    pair that is no edge of the graph, an edge drawn twice within the
    compared steps, or a negative out of range counts once each."""
    keys = np.unique(user.astype(np.int64) * n_items + item)
    drawn = []
    bad = 0
    for u, p, n in batches:
        bad += abs(batch - len(u))
        k = np.asarray(u, np.int64) * n_items + np.asarray(p)
        bad += int((~np.isin(k, keys)).sum())
        bad += int(((np.asarray(n) < 0) | (np.asarray(n) >= n_items)).sum())
        drawn.append(k)
    drawn = np.concatenate(drawn)
    return bad + len(drawn) - len(np.unique(drawn))


def _skewed(sample: np.ndarray, size: int, sigmas: float) -> bool:
    """Whether ``sample`` (values in ``[0, size)``) fails a chi-square
    test of uniformity: equal-width bins with some twenty draws each,
    at most 64, and the statistic ``sigmas`` standard deviations above
    its mean (for a uniform draw a chance of about 3e-9 at 64 bins, the
    cells' count, and 2e-6 at the 12 bins of a test-sized batch)."""
    bins = max(2, min(64, len(sample) // 20, size))
    hist = np.bincount(np.asarray(sample, np.int64) * bins // size,
                       minlength=bins)
    want = len(sample) / bins
    chi2 = float(((hist - want) ** 2).sum() / want)
    return chi2 > (bins - 1) + sigmas * math.sqrt(2 * (bins - 1))


def skewed_batches(batches, user: np.ndarray, item: np.ndarray,
                   n_items: int, sigmas: float = 8.0) -> int:
    """Compared batches whose positives are not a uniform draw of the
    graph's edges (by their place in its edge list) or whose negatives
    are not a uniform draw of the items; a biased sampler feeds program
    and reference the same rows, so only this sees it."""
    keys = user.astype(np.int64) * n_items + item
    order = np.argsort(keys, kind="stable")
    bad = 0
    for u, p, n in batches:
        k = np.asarray(u, np.int64) * n_items + np.asarray(p)
        place = order[np.clip(np.searchsorted(keys[order], k), 0,
                              len(keys) - 1)]
        bad += int(_skewed(place, len(keys), sigmas)
                   or _skewed(np.asarray(n), n_items, sigmas))
    return bad


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for numbers under limits."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    ok = all(bool(np.isfinite(v["value"]) and v["value"] <= v["limit"])
             for v in shown.values())
    return ok, shown
