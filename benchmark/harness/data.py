"""Seeded input data: the benchmark's only generator.

The problem is ``chip_smoke.make_data``'s (uniform float32 features, one
random linear margin, centred, plus noise 0.5, binary label), with two
changes so that it is cheap at tens of millions of rows and so that a
reference computed on fewer rows sees the same problem:

- rows are drawn block by block (``BLOCK`` rows, generators keyed by seed
  and block index), so a prefix of the rows is the same data whatever the
  total, and blocks are generated by a few threads;
- ``numpy.random.Generator`` draws float32 directly (no float64 pass).

The weight vector does NOT depend on the seed: it is the problem, and the
seed draws the sample. (With a weight vector per seed the validation AUC
after 20 iterations ranged from 0.902 to 0.933 over four seeds — each
seed was a problem of its own and no reference AUC could hold.) The same
seed gives the same bytes.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20
NOISE = 0.5
_TRAIN, _VALID = 1, 2          # stream ids under the seed
_THREADS = 4


def weights(features: int) -> np.ndarray:
    """The problem: one fixed margin direction per feature count."""
    return np.random.default_rng([0, 0]).standard_normal(
        features, dtype=np.float32)


def _fill_block(seed: int, stream: int, index: int, w: np.ndarray,
                X: np.ndarray, y: np.ndarray) -> None:
    """Rows of block ``index`` into X, y. Features and noise come from
    generators of their own, so a block cut short is a prefix of the
    whole block and a prefix of the data does not depend on the total."""
    np.random.default_rng([seed, stream, index, 0]).random(
        out=X, dtype=np.float32)
    noise = np.random.default_rng([seed, stream, index, 1]).standard_normal(
        X.shape[0], dtype=np.float32)
    # (X - 0.5) @ w > -noise, without the [n, features] temporary
    y[:] = (X @ w + np.float32(NOISE) * noise) > np.float32(0.5) * w.sum()


def _make_split(seed: int, stream: int, rows: int, w: np.ndarray):
    X = np.empty((rows, w.shape[0]), np.float32)
    y = np.empty(rows, np.float32)
    spans = [(i, a, min(a + BLOCK, rows))
             for i, a in enumerate(range(0, rows, BLOCK))]
    with ThreadPoolExecutor(_THREADS) as pool:
        for _ in pool.map(lambda s: _fill_block(seed, stream, s[0], w,
                                                X[s[1]:s[2]], y[s[1]:s[2]]),
                          spans):
            pass
    return X, y


def make_data(seed: int, rows: int, valid_rows: int, features: int):
    """(X, y, X_valid, y_valid) for ``seed``; float32 throughout."""
    w = weights(features)
    X, y = _make_split(seed, _TRAIN, rows, w)
    Xv, yv = _make_split(seed, _VALID, valid_rows, w)
    return X, y, Xv, yv

