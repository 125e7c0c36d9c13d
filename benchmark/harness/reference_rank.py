"""The benchmark's own NDCG: plain numpy, one query after another, what
the comparison that decides a ranking cell's ``correct`` is computed
with. Ties in the score keep their original row order (a stable sort),
as the reference's ``std::stable_sort`` does; the gain of label l is
2**l - 1 and the discount of position i is 1 / log2(2 + i); a query with
no relevant document counts as 1 at every cutoff.
"""
from __future__ import annotations

import numpy as np


def dcg(gains_in_order: np.ndarray, k: int) -> float:
    top = gains_in_order[:k]
    return float(np.sum(top / np.log2(2.0 + np.arange(top.size))))


def ndcg_at(ks, label: np.ndarray, score: np.ndarray,
            group: np.ndarray) -> list:
    """Mean NDCG@k over the queries, one value per k of ``ks``. ``group``
    holds the queries' sizes in row order."""
    label = np.asarray(label)
    gains = np.exp2(label.astype(np.float64)) - 1.0
    score = np.asarray(score, np.float64)
    ends = np.cumsum(group)
    total = np.zeros(len(ks))
    for a, b in zip(ends - group, ends):
        g = gains[a:b]
        ideal = np.sort(g)[::-1]
        if ideal.size == 0 or ideal[0] <= 0:
            total += 1.0
            continue
        ranked = g[np.argsort(-score[a:b], kind="stable")]
        total += [dcg(ranked, k) / dcg(ideal, k) for k in ks]
    return list(total / len(group))
