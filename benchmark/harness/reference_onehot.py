"""The benchmark's own checks of a model trained on the one-hot CSR
(``harness/data_onehot.py``), each independent of the program's kernels:

- ``walk_csr``: ``harness/reference.py``'s float64 walk of the dumped
  trees over the LOGICAL columns of a CSR matrix, densified a block of
  rows at a time, so the scores of a set never pass through the bundle
  columns or their decode;
- ``root_split``: the best split of tree 0's root by a float64 scan of
  the root histograms of every logical column, built from the CSR with
  the iteration-0 gradients of binary log-loss from the prior's log-odds
  (``boost_from_average``) and the model's own bin bounds (the reference's
  numerical threshold scan, as ``tools/reference_auc_cat.py`` writes it).
"""
from __future__ import annotations

import numpy as np

from . import reference

BLOCK = 1 << 16                 # rows densified at a time


def walk_csr(trees: list, X) -> np.ndarray:
    """Raw score (float64) of each row of the CSR ``X``."""
    out = np.empty(X.shape[0], np.float64)
    for a in range(0, X.shape[0], BLOCK):
        b = min(a + BLOCK, X.shape[0])
        out[a:b] = reference.walk(trees, X[a:b].toarray())
    return out


def _gain(g, h, l2):
    return g * g / (h + l2)


def root_histograms(X, y: np.ndarray, bounds: dict):
    """{column: [3, bins] float64 sums of gradient, hessian and rows per
    bin} at tree 0's root, for the columns ``bounds`` names (column ->
    the upper bounds of its bins, the last +inf). Implicit zeros go to
    the bin of 0.0. One pass over the stored values: a stored value's
    bin is the first bound at or above it."""
    y = np.asarray(y, np.float64)
    prior = float(y.mean())
    h = prior * (1.0 - prior)
    n = y.size
    cols = np.asarray(X.indices, np.int32)
    vals = X.data
    width = max(ub.size for ub in bounds.values())
    bins = np.zeros(cols.size, np.int32)
    two = np.full(X.shape[1], np.inf)       # the one bound of a 2-bin column
    for j, ub in bounds.items():
        if ub.size == 2:
            two[j] = ub[0]
    bound = two[cols]
    narrow = np.isfinite(bound)
    bins[narrow] = vals[narrow] > bound[narrow]
    del bound
    for j, ub in bounds.items():
        if ub.size != 2:
            at = cols == j
            bins[at] = np.searchsorted(ub, vals[at], side="left")
    key = cols * np.int32(width) + bins
    del bins
    counts = np.bincount(key, minlength=X.shape[1] * width) \
        .reshape(X.shape[1], width).astype(np.float64)
    # g = prior - y: a bin's sum is prior x its values less its positives
    positive = np.repeat(y > 0.5, np.diff(X.indptr))
    sums = prior * counts - np.bincount(
        key[positive], minlength=X.shape[1] * width) \
        .reshape(X.shape[1], width)
    g = prior - y                       # sigmoid(log-odds of the prior) - y
    G = g.sum()
    out = {}
    for j, ub in bounds.items():
        hist = np.zeros((3, ub.size))
        hist[0], hist[2] = sums[j, :ub.size], counts[j, :ub.size]
        zero = int(np.searchsorted(ub, 0.0, side="left"))
        hist[0, zero] += G - hist[0].sum()
        hist[2, zero] += n - hist[2].sum()
        hist[1] = hist[2] * h
        out[j] = hist
    return out


def root_split(hists: dict, params: dict):
    """Every candidate split of the root by gain, best first: (gain, column,
    threshold bin), where the gain is the reference's (left + right - the
    leaf's own, ``lambda_l2``), sides under ``min_data_in_leaf`` or
    ``min_sum_hessian_in_leaf`` skipped; of equal gains the lower column,
    then the higher threshold, first (the reference's reverse scan)."""
    l2 = float(params.get("lambda_l2", 0.0))
    min_c = float(params.get("min_data_in_leaf", 20))
    min_h = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    found = []
    for j, (g, h, c) in hists.items():
        G, H, C = g.sum(), h.sum(), c.sum()
        own = _gain(G, H, l2)
        rg, rh, rc = np.cumsum(g[::-1]), np.cumsum(h[::-1]), np.cumsum(
            c[::-1])
        # bins t.. go right, t = width-1 .. 1; threshold t - 1
        for k in range(g.size - 1):
            t = g.size - 1 - k
            if rc[k] < min_c or rh[k] < min_h:
                continue
            lg, lh, lc = G - rg[k], H - rh[k], C - rc[k]
            if lc < min_c or lh < min_h:
                break
            found.append((_gain(lg, lh, l2) + _gain(rg[k], rh[k], l2) - own,
                          j, t - 1))
    found.sort(key=lambda s: (-s[0], s[1], -s[2]))
    return found


def onehot_share(trees: list, numerical: tuple) -> float:
    """Internal nodes that split a one-hot column over all internal
    nodes."""
    feats = np.concatenate([t["feature"] for t in trees]) if trees \
        else np.zeros(0)
    return float(np.isin(feats, numerical, invert=True).sum()) \
        / max(feats.size, 1)
