"""Seeded input data for the one-hot cell: ``harness/data_cat.py``'s
airline table, the same rows and labels for the same seed, with each of
its six categorical columns ONE-HOT coded, as the reference's Expo
experiment gives them (``docs/Experiments.rst``: 700 one-hot columns), and
stored the way a user who one-hot encodes hands them over: a
``scipy.sparse.csr_matrix`` of float32.

The 674 logical columns, in order: Month=1..12, DayofMonth=1..31,
DayOfWeek=1..7, DepTime, UniqueCarrier=0..21, Origin=0..299,
Dest=0..299, Distance (``COLUMNS``). Every row stores exactly eight
values: a 1.0 in each categorical block and its two numbers (DepTime and
Distance are never zero), so ``indptr`` is ``8 * arange``.
"""
from __future__ import annotations

import numpy as np

from . import data_cat

STORED = len(data_cat.COLUMNS)          # values stored on every row


def blocks() -> list:
    """Per raw column: (its first logical column, its width, the lowest
    code), in the raw order."""
    out, first = [], 0
    for c in range(len(data_cat.COLUMNS)):
        if c in data_cat.CATEGORICAL:
            lo = 1 if c < 3 else 0          # the calendar counts from 1
            out.append((first, data_cat.CARDINALITY[c], lo))
            first += data_cat.CARDINALITY[c]
        else:
            out.append((first, 1, 0))
            first += 1
    return out


def _names() -> tuple:
    names = []
    for c, (_, width, lo) in enumerate(blocks()):
        col = data_cat.COLUMNS[c]
        names += ([f"{col}={lo + k}" for k in range(width)]
                  if c in data_cat.CATEGORICAL else [col])
    return tuple(names)


COLUMNS = _names()
NUMERICAL = tuple(COLUMNS.index(data_cat.COLUMNS[c])
                  for c in range(len(data_cat.COLUMNS))
                  if c not in data_cat.CATEGORICAL)


def to_csr(X: np.ndarray):
    """[n, 8] raw rows (``data_cat``'s) -> [n, 674] one-hot CSR float32."""
    import scipy.sparse as sp
    n = X.shape[0]
    indices = np.empty((n, STORED), np.int32)
    data = np.ones((n, STORED), np.float32)
    for c, (first, _, lo) in enumerate(blocks()):
        if c in data_cat.CATEGORICAL:
            indices[:, c] = X[:, c].astype(np.int32) + (first - lo)
        else:
            indices[:, c] = first
            data[:, c] = X[:, c]
    indptr = np.arange(0, STORED * n + 1, STORED, dtype=np.int64)
    return sp.csr_matrix((data.reshape(-1), indices.reshape(-1), indptr),
                         shape=(n, len(COLUMNS)))


def make_data(seed: int, rows: int, valid_rows: int):
    """(X CSR, y, X_valid CSR, y_valid) for ``seed``: ``data_cat``'s rows
    and labels, one-hot coded; the validation rows are its held-out set
    whatever the seed."""
    X, y, Xv, yv = data_cat.make_data(seed, rows, valid_rows)
    X = to_csr(X)
    return X, y, to_csr(Xv), yv
