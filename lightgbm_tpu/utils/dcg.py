"""DCG/NDCG helpers shared by the lambdarank objective and rank metrics.

TPU-native analog of ref: src/metric/dcg_calculator.cpp (DCGCalculator).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from . import log

K_MAX_POSITION = 10000


def default_label_gain(label_gain: Optional[Sequence[float]]) -> np.ndarray:
    """label_gain[i] = 2^i - 1 (ref: dcg_calculator.cpp:33)."""
    if label_gain:
        return np.asarray(label_gain, dtype=np.float64)
    return np.array([0.0] + [float((1 << i) - 1) for i in range(1, 31)])


def discounts(n: int) -> np.ndarray:
    """discount[i] = 1/log2(2+i) (ref: dcg_calculator.cpp:49)."""
    return 1.0 / np.log2(2.0 + np.arange(n, dtype=np.float64))


def check_label(label: np.ndarray, num_gains: int) -> None:
    # ref: dcg_calculator.cpp CheckLabel — integral labels within gain table
    li = label.astype(np.int64)
    if np.any(np.abs(label - li) > 1e-9) or label.min() < 0:
        log.fatal("NDCG labels must be non-negative integers")
    if li.max() >= num_gains:
        log.fatal("Label %d is larger than the size of label_gain (%d)",
                  int(li.max()), num_gains)


def max_dcg_at_k(k: int, label: np.ndarray,
                 label_gain: np.ndarray) -> float:
    """Ideal DCG@k — greedy from the top label (ref: dcg_calculator.cpp:55
    CalMaxDCGAtK)."""
    n = len(label)
    k = min(k, n)
    sorted_gain = np.sort(label_gain[label.astype(np.int64)])[::-1]
    return float(np.sum(sorted_gain[:k] * discounts(k)))


def top_slots(query_boundaries: np.ndarray, top: int):
    """(at, disc): every query's first ``top`` slots of rows that are
    sorted within their query: ``[num_queries, top]`` row positions
    (clipped into the rows) and each slot's DCG discount, 0 past the
    query's length. Static given the boundaries: only which document
    sits in a slot depends on the sort."""
    qb = np.asarray(query_boundaries, np.int64)
    slot = np.arange(top, dtype=np.int64)[None, :]
    at = np.minimum(qb[:-1, None] + slot, max(int(qb[-1]) - 1, 0))
    disc = np.where(slot < np.diff(qb)[:, None], discounts(top)[None, :],
                    0.0)
    return at, disc


def max_dcg_table(ks: Sequence[int], label: np.ndarray,
                  query_boundaries: np.ndarray,
                  label_gain: np.ndarray) -> np.ndarray:
    """``max_dcg_at_k`` of every query at every k, ``[num_queries, len(ks)]``
    float64, by array operations: the gains are sorted within their query
    once, each query's top max(ks) are laid side by side and summed
    cumulatively along that short axis (the order ``max_dcg_at_k`` sums
    in)."""
    qb = np.asarray(query_boundaries, np.int64)
    sizes = np.diff(qb)
    out = np.zeros((sizes.size, len(ks)), np.float64)
    if sizes.size == 0 or qb[-1] == 0:
        return out
    top = int(min(max(ks), sizes.max()))
    gains = np.asarray(label_gain, np.float64)[
        np.asarray(label[:qb[-1]]).astype(np.int64)]
    qid = np.repeat(np.arange(sizes.size), sizes)
    at, disc = top_slots(qb, top)
    cum = np.cumsum(gains[np.lexsort((-gains, qid))][at] * disc, axis=1)
    for ki, k in enumerate(ks):
        out[:, ki] = cum[:, min(int(k), top) - 1]
    return out


def dcg_at_k(ks: Sequence[int], label: np.ndarray, score: np.ndarray,
             label_gain: np.ndarray) -> List[float]:
    """DCG at each k for one query, docs ranked by score descending
    (ref: dcg_calculator.cpp CalDCG; stable sort matches reference)."""
    order = np.argsort(-score, kind="stable")
    gains = label_gain[label.astype(np.int64)[order]]
    n = len(label)
    disc = discounts(n)
    cum = np.cumsum(gains * disc)
    return [float(cum[min(k, n) - 1]) if n > 0 else 0.0 for k in ks]
