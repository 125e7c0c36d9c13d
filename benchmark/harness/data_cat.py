"""Seeded input data for the categorical cell: airline on-time ("Expo") at
its raw shape, eight columns in the order

  Month, DayofMonth, DayOfWeek, DepTime, UniqueCarrier, Origin, Dest,
  Distance

of which ``CATEGORICAL`` (0, 1, 2, 4, 5, 6) hold small non-negative
integer codes in float32, the way ``lgb.Dataset(..., categorical_feature=
[...])`` takes them, and DepTime (minutes after midnight, 240-1,440) and
Distance (miles, 30-5,000) are continuous. The data set itself
cannot be fetched; what is kept of it is its shape: calendar columns
near uniform, carriers and airports Zipf-like by count with a thin tail
(``_airport_p``: the largest airport 8 % of the flights, the 254 largest
99.9 %, the smallest of 300 about 400 flights in 28M), so that the
reference's vocabulary rule (count-sorted, 99 % of the mass or
``max_bin``, ``min_data_in_bin`` in the 200,000-row sample) sends the
rarest airports to the "other" bin, as it does with the real table.

The label is "delayed", from a margin that is THE PROBLEM and does not
depend on the seed (``effects``): one fixed effect per category of every
categorical column, a smooth term in DepTime (delays build up over the
day), a small one in Distance, and a carrier x origin term, so that a
one-vs-rest split on either column alone does not tell it; plus logistic
noise. The seed draws the training sample; the validation rows are one
held-out set (``HELD_OUT``). Rows are drawn block by block
(``BLOCK`` rows, generators keyed by seed, stream and block), so a prefix
of the rows is the same data whatever the total, and a few threads fill
the blocks. The same seed gives the same bytes.

Tuned once, on 2M rows (PERF.md section 6, PR 33): positives 0.19 of the
rows, the noiseless margin's own AUC 0.79, the reference tool's AUC after
8 iterations 0.76 with three quarters of its nodes categorical; at full
size 0.766 and 85 % (``benchmark/reference/expo255-cat.train-cat-28m.json``).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20
COLUMNS = ("Month", "DayofMonth", "DayOfWeek", "DepTime", "UniqueCarrier",
           "Origin", "Dest", "Distance")
CATEGORICAL = (0, 1, 2, 4, 5, 6)
CARDINALITY = {0: 12, 1: 31, 2: 7, 4: 22, 5: 300, 6: 300}
_TRAIN, _VALID = 1, 2          # stream ids under the seed
# the validation rows are ONE held-out set whatever the seed, as the
# source's test set (its last 1,000,000 rows) is the same for everyone who
# trains: its sampling noise is then a constant and the cell's reference
# band can tell one tree (harness/data_rank.py does the same)
HELD_OUT = 20090101
_THREADS = 4
_KEY = 33                      # the problem's own key, never the seed

# effect sizes (standard deviations of the fixed per-category effects, and
# the weights of the smooth terms), set once
SD = {0: 0.3, 1: 0.08, 2: 0.2, 4: 0.55, 5: 0.65, 6: 0.5}
SD_CARRIER_X_ORIGIN = 0.5
W_DEPTIME, W_DEPTIME_WAVE, W_DISTANCE = 1.8, 0.5, 0.3
NOISE = 1.0                    # scale of the logistic noise
CUT = 2.07                     # margin + noise > CUT is "delayed": 0.19


def _airport_p(n: int) -> np.ndarray:
    k = np.arange(n, dtype=np.float64)
    p = (k + 6.0) ** -1.1 * np.exp(-k / 70.0)
    return p / p.sum()


def _carrier_p(n: int) -> np.ndarray:
    p = (np.arange(n, dtype=np.float64) + 2.0) ** -1.0
    return p / p.sum()


def _calendar_p(n: int, col: int) -> np.ndarray:
    """Near uniform: months and weekdays differ by a few per cent."""
    p = 1.0 + 0.08 * np.random.default_rng([_KEY, 1, col]).standard_normal(n)
    return p / p.sum()


def shares() -> dict:
    """Column -> probability of each of its category codes (fixed)."""
    return {0: _calendar_p(12, 0), 1: _calendar_p(31, 1),
            2: _calendar_p(7, 2), 4: _carrier_p(22),
            5: _airport_p(300), 6: _airport_p(300)}


def effects() -> dict:
    """The problem: column -> fixed effect of each category code, and
    ``"x"`` the [carriers, origins] interaction table. float32."""
    out = {c: (SD[c] * np.random.default_rng([_KEY, 2, c])
               .standard_normal(n)).astype(np.float32)
           for c, n in CARDINALITY.items()}
    out["x"] = (SD_CARRIER_X_ORIGIN * np.random.default_rng([_KEY, 3])
                .standard_normal((CARDINALITY[4], CARDINALITY[5]))
                ).astype(np.float32)
    return out


def margin(X: np.ndarray, eff: dict) -> np.ndarray:
    """The noiseless margin of rows X ([n, 8] float32), float32."""
    # the calendar columns count from 1, as the table's do
    code = {c: X[:, c].astype(np.int32) - (1 if c < 3 else 0)
            for c in CATEGORICAL}
    m = eff["x"][code[4], code[5]]
    for c in CATEGORICAL:
        m = m + eff[c][code[c]]
    day = X[:, 3] * np.float32(1.0 / 1440.0)          # 0..1
    m += np.float32(W_DEPTIME) * (day - np.float32(0.5))
    m += np.float32(W_DEPTIME_WAVE) * np.sin(np.float32(4 * np.pi) * day)
    m += np.float32(W_DISTANCE) * (np.log(X[:, 7]) - np.float32(6.3))
    return m


def _fill_block(seed: int, stream: int, index: int, cdf: dict, eff: dict,
                X: np.ndarray, y: np.ndarray) -> None:
    """Rows of block ``index`` into X, y. Every column and the noise have
    generators of their own, so a block cut short is a prefix of the whole
    block."""
    n = X.shape[0]

    def rng(k):
        return np.random.default_rng([seed, stream, index, k])

    for c in CATEGORICAL:
        u = rng(c).random(n, dtype=np.float32)
        code = np.minimum(np.searchsorted(cdf[c], u, side="right"),
                          CARDINALITY[c] - 1)
        X[:, c] = code + (1 if c < 3 else 0)
    # DepTime: minutes after midnight, 4:00 to 24:00, most in the afternoon
    X[:, 3] = np.float32(240.0) + np.float32(600.0) * (
        rng(3).random(n, dtype=np.float32)
        + rng(9).random(n, dtype=np.float32))
    # Distance: log-normal around 550 miles, 30 to 5,000
    z = rng(7).standard_normal(n, dtype=np.float32)
    X[:, 7] = np.clip(np.exp(np.float32(6.3) + np.float32(0.75) * z),
                      np.float32(30.0), np.float32(5000.0))
    u = rng(8).random(n, dtype=np.float32)
    u = np.clip(u, np.float32(1e-7), np.float32(1.0 - 1e-7))
    noise = np.float32(NOISE) * (np.log(u) - np.log1p(-u))
    y[:] = margin(X, eff) + noise > np.float32(CUT)


def _make_split(seed: int, stream: int, rows: int, cdf: dict, eff: dict):
    X = np.empty((rows, len(COLUMNS)), np.float32)
    y = np.empty(rows, np.float32)
    spans = [(i, a, min(a + BLOCK, rows))
             for i, a in enumerate(range(0, rows, BLOCK))]
    with ThreadPoolExecutor(_THREADS) as pool:
        for _ in pool.map(lambda s: _fill_block(seed, stream, s[0], cdf, eff,
                                                X[s[1]:s[2]], y[s[1]:s[2]]),
                          spans):
            pass
    return X, y


def make_data(seed: int, rows: int, valid_rows: int):
    """(X, y, X_valid, y_valid) for ``seed``; float32 throughout."""
    cdf = {c: np.cumsum(p).astype(np.float32) for c, p in shares().items()}
    eff = effects()
    X, y = _make_split(seed, _TRAIN, rows, cdf, eff)
    Xv, yv = _make_split(HELD_OUT, _VALID, valid_rows, cdf, eff)
    return X, y, Xv, yv
