"""Seeded learning-to-rank data: query-grouped rows with graded relevance.

Blockwise and threaded as ``data.py`` (features and noise come from
generators keyed by seed, stream and block index, so a prefix of the rows
has the same features and noise whatever the total). The seed draws the
TRAINING set: its features, noise, per-query offsets and its query sizes
(``query_sizes``: a log-normal of the asked mean, sigma ``SIGMA``, clipped
to 1..``longest``, nudged by single documents so that the sizes sum to
the rows exactly, holding one query of length 1 and one of ``longest``
where the rows have room for them). What is NOT drawn from the seed is
the problem itself, as ``data.weights`` is not:

- the margin direction (``weights``): standard normal times 0.93**j, so
  a handful of the features carry most of the signal, as a ranker's few
  strong features (BM25, PageRank, ...) do;
- the VALIDATION set: the held-out queries a model is judged on are made
  from ``HELD_OUT`` whatever the seed, as a public test fold is the same
  for everyone who trains. NDCG over 6,306 queries carries a sampling
  noise of 0.0028 (a boosting iteration moves it by 0.0045); on a fixed
  held-out set that noise is a constant and two models can be told apart
  by one tree.

Relevance 0-4 comes from fixed thresholds on margin + a per-query offset
(some queries have many relevant documents) + noise; the thresholds are
the normal quantiles that give marginals near 0.52 / 0.32 / 0.13 / 0.02 /
0.01. The same seed gives the same bytes.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20
SIGMA = 0.7                    # of the log-normal of query sizes
DECAY = 0.93                   # of the margin's weights along the features
OFFSET, NOISE = 0.5, 0.7       # standard deviations beside the margin's 1
# cumulative marginals 0.52, 0.84, 0.97, 0.99 as standard normal quantiles
_QUANTILES = np.array([0.050154, 0.994458, 1.880794, 2.326348])
_TRAIN, _VALID = 1, 2          # stream ids under the seed
HELD_OUT = 0                   # the seed of every run's validation set
_THREADS = 4


def weights(features: int) -> np.ndarray:
    """The problem: one fixed margin direction per feature count, scaled
    so that the margin of uniform [0, 1) features has unit variance."""
    w = np.random.default_rng([0, 0]).standard_normal(features) \
        * DECAY ** np.arange(features)
    return (w / np.sqrt(np.sum(w * w) / 12.0)).astype(np.float32)


def thresholds() -> np.ndarray:
    total = np.sqrt(1.0 + OFFSET ** 2 + NOISE ** 2)
    return (_QUANTILES * total).astype(np.float32)


def query_sizes(rng: np.random.Generator, rows: int, queries: int,
                longest: int) -> np.ndarray:
    """``queries`` sizes drawn from ``rng`` that sum to ``rows``."""
    if not queries <= rows <= queries * longest:
        raise ValueError(f"{queries} queries of 1..{longest} documents "
                         f"cannot hold {rows} rows")
    mean = rows / queries
    sizes = np.clip(np.rint(rng.lognormal(
        np.log(mean) - SIGMA ** 2 / 2.0, SIGMA, queries)), 1, longest) \
        .astype(np.int64)
    if queries >= 2 and longest + 2 * (queries - 1) <= rows:
        sizes[np.argmax(sizes)] = longest   # both ends of the range are
        sizes[np.argmin(sizes)] = 1         # present where they fit
    free = np.flatnonzero((sizes > 1) & (sizes < longest))
    while True:
        off = rows - int(sizes.sum())
        if off == 0:
            return sizes
        step = 1 if off > 0 else -1
        ok = free[(sizes[free] + step > 1) & (sizes[free] + step < longest)]
        if ok.size == 0:
            raise ValueError(f"no multiset of {queries} sizes in 1.."
                             f"{longest} sums to {rows} from this draw")
        sizes[rng.permutation(ok)[:abs(off)]] += step


def _fill_block(seed: int, stream: int, index: int, w: np.ndarray,
                X: np.ndarray, z: np.ndarray) -> None:
    """Rows of block ``index``: features into X, margin + noise into z."""
    np.random.default_rng([seed, stream, index, 0]).random(
        out=X, dtype=np.float32)
    noise = np.random.default_rng([seed, stream, index, 1]).standard_normal(
        X.shape[0], dtype=np.float32)
    z[:] = X @ w - np.float32(0.5) * w.sum() + np.float32(NOISE) * noise


def _make_split(seed: int, stream: int, rows: int, queries: int,
                longest: int, w: np.ndarray):
    X = np.empty((rows, w.shape[0]), np.float32)
    z = np.empty(rows, np.float32)
    spans = [(i, a, min(a + BLOCK, rows))
             for i, a in enumerate(range(0, rows, BLOCK))]
    with ThreadPoolExecutor(_THREADS) as pool:
        for _ in pool.map(lambda s: _fill_block(seed, stream, s[0], w,
                                                X[s[1]:s[2]], z[s[1]:s[2]]),
                          spans):
            pass
    rng = np.random.default_rng([seed, stream, 2])
    group = query_sizes(rng, rows, queries, longest)
    offset = rng.standard_normal(queries, dtype=np.float32) \
        * np.float32(OFFSET)
    z += np.repeat(offset, group)
    y = np.searchsorted(thresholds(), z, side="right").astype(np.float32)
    return X, y, group


def make_data(seed: int, rows: int, queries: int, valid_rows: int,
              valid_queries: int, features: int, longest: int):
    """(X, y, group, X_valid, y_valid, group_valid): the training set of
    ``seed`` and the held-out set; float32 features and labels, int64
    query sizes that sum to the rows."""
    w = weights(features)
    return (*_make_split(seed, _TRAIN, rows, queries, longest, w),
            *_make_split(HELD_OUT, _VALID, valid_rows, valid_queries,
                         longest, w))
