#!/usr/bin/env python3
"""Where the one-hot cell's reference AUC comes from: the categorical
cell's plain trainer (``tools/reference_auc_cat.py``, loaded from its
file) with every category a column of its own, as one-hot coding makes
it. Run by hand when the cell is defined, on the CPU, never by the
benchmark itself:

  python3 benchmark/tools/reference_auc_onehot.py --seed 0 [--rows N] \\
      [--iterations 9] [--slot-cap 32]

numpy and float64 throughout, over the benchmark's own generated table
(``harness/data_cat.py``, the rows ``harness/data_onehot.py`` codes). It
needs neither jax nor the program, and shares no code with it.

What one-hot coding changes against the categorical tool, and nothing
else: a categorical column's histogram has one bin per category the
first ``SAMPLE`` rows hold (a category they lack is a column the
reference drops as trivial, and its rows read 0 in every column of the
block), and its candidates are one category against the rest: the
one-hot column k's numerical scan over its two bins, category k going
RIGHT (bin 1 above the threshold), the rest left, its gain against the
leaf's, sides under ``min_data_in_leaf`` or ``min_sum_hessian_in_leaf``
skipped. Columns are searched in the logical order (the blocks in the
raw column order, categories ascending), the first of equal gains
winning. The numerical columns, the growth, the leaf values and the loss
are the categorical tool's.

The trees grow in the program's order (``--schedule levels`` of the
categorical tool): a level at a time the 1, 2, 4, ... best leaves, up to
``--slot-cap`` a level (the program's ``max_slot_cap`` at its kernels'
width: 32 at 13 bundle columns of 256 bins, 64 at 12), then three
passes more.

Prints one JSON object: the validation AUC after every iteration by the
benchmark's own rank AUC, each tree's leaves and one-hot nodes, and how
far rounding the validation scores to bfloat16 moves the AUC and the
scores themselves (the largest move over the rows).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import cells, data_cat, reference  # noqa: E402

cat = cells.load_module("tools", "reference_auc_cat")


class OneHotBinner(cat.Binner):
    """The categorical tool's binner, a categorical column's bins being
    its categories in the first ``SAMPLE`` rows, ascending (bin 0: the
    rest)."""

    def __init__(self, X: np.ndarray, categorical, max_bin: int):
        super().__init__(X, categorical, max_bin)
        sample = X[:cat.SAMPLE]
        for f in self.categorical:
            self.vocab[f] = np.r_[-1, np.unique(
                sample[:, f].astype(np.int64))]


def best_onehot(g, h, c, p: dict):
    """Best one-hot column of a categorical column's histogram: category
    t (bin t) right, the rest left. None, or a split dict whose
    ``not_bin`` is t."""
    l2 = p["lambda_l2"]
    G, H, C = g.sum(), h.sum(), c.sum()
    floor = cat.leaf_gain(G, H, l2) + p["min_gain_to_split"]
    best = None
    for t in range(1, g.size):
        if (c[t] < p["min_data_in_leaf"]
                or h[t] < p["min_sum_hessian_in_leaf"]
                or C - c[t] < p["min_data_in_leaf"]
                or H - h[t] < p["min_sum_hessian_in_leaf"]):
            continue
        lg, lh, lc = G - g[t], H - h[t], C - c[t]
        gain = cat.leaf_gain(lg, lh, l2) + cat.leaf_gain(g[t], h[t], l2)
        if gain > floor and (best is None or gain > best["gain"] + floor):
            best = {"gain": gain - floor, "not_bin": t, "l2": l2,
                    "left": (lg, lh, lc), "right": (g[t], h[t], c[t])}
    return best


def best_of_leaf(hist, binner, p: dict):
    """The categorical tool's search with ``best_onehot`` for the
    categorical columns."""
    best = None
    for f in range(hist.shape[0]):
        nb = binner.num_bin(f)
        g, h, c = hist[f, 0, :nb], hist[f, 1, :nb], hist[f, 2, :nb]
        s = (best_onehot if f in binner.categorical
             else cat.best_numerical)(g, h, c, p)
        if s is not None and (best is None or s["gain"] > best["gain"]):
            best = dict(s, feature=f)
    return best


def goes_left(bins: np.ndarray, split: dict, width: int) -> np.ndarray:
    if "not_bin" in split:
        return bins != split["not_bin"]
    return bins <= split["threshold"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="expo-onehot-efb")
    ap.add_argument("--traffic", default="train-onehot-28m")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="train on a prefix of the rows (0 = all)")
    ap.add_argument("--valid-rows", type=int, default=0)
    ap.add_argument("--iterations", type=int, default=0,
                    help="0 = the traffic mix's iterations + 1")
    ap.add_argument("--slot-cap", type=int, default=32,
                    help="the program's most splits a level")
    args = ap.parse_args()
    cfg = cells.load_json(os.path.join(cells.BENCH, "configs",
                                       args.config + ".json"))
    tr = cells.load_json(os.path.join(cells.BENCH, "traffic",
                                      args.traffic + ".json"))
    p = dict(cat.CAT_DEFAULTS, **{k: v for k, v in cfg["params"].items()
                                  if not isinstance(v, str)})
    iters = args.iterations or int(tr["chunk_iterations"]) * (
        int(tr["warmup_chunks"]) + int(tr["measured_chunks"])) + 1
    rows = args.rows or int(tr["rows"])
    # the categorical tool's growth, search and walk, with this search
    cat.best_of_leaf, cat.goes_left = best_of_leaf, goes_left
    caps = cat.level_caps
    cat.level_caps = lambda n: caps(n, slot_cap=args.slot_cap)
    t0 = time.time()
    X, y, Xv, yv = data_cat.make_data(
        args.seed, rows, args.valid_rows or int(tr["valid_rows"]))
    binner = OneHotBinner(X, data_cat.CATEGORICAL, int(p["max_bin"]))
    Xb, Xvb = binner.transform(X), binner.transform(Xv)
    del X, Xv
    width = max(binner.num_bin(f) for f in range(Xb.shape[0]))
    y = y.astype(np.float64)
    prior = float(y.mean())
    raw = np.full(y.size, np.log(prior / (1.0 - prior)))
    raw_v = np.full(yv.size, raw[0])
    out = {"seed": args.seed, "rows": rows, "slot_cap": args.slot_cap,
           "valid_rows": int(yv.size), "positives": prior,
           "columns": sum(binner.num_bin(f) - 1 if f in binner.categorical
                          else 1 for f in range(Xb.shape[0])),
           "auc": [], "auc_bf16_scores": [], "bf16_scores_max_move": [],
           "leaves": [], "onehot_nodes": []}
    print(f"reference: {rows} rows binned in {time.time() - t0:.0f} s; "
          f"{out['columns']} one-hot and numerical columns", file=sys.stderr,
          flush=True)
    for it in range(iters):
        prob = reference.sigmoid(raw)
        nodes, leaf_value, row_leaf = cat.grow(
            Xb, prob - y, prob * (1.0 - prob), binner, p, width, "levels")
        leaf_value *= p["learning_rate"]
        raw += leaf_value[row_leaf]
        raw_v += leaf_value[cat.leaves_of(nodes, Xvb, width)]
        out["auc"].append(round(reference.rank_auc(yv, raw_v), 9))
        out["auc_bf16_scores"].append(
            round(reference.rank_auc(yv, cat.bf16(raw_v)), 9))
        out["bf16_scores_max_move"].append(
            float(np.max(np.abs(cat.bf16(raw_v) - raw_v))))
        out["leaves"].append(int(leaf_value.size))
        out["onehot_nodes"].append(sum("not_bin" in nd[1] for nd in nodes))
        print(f"reference: iteration {it + 1}: auc {out['auc'][-1]}, "
              f"{out['leaves'][-1]} leaves, {out['onehot_nodes'][-1]} "
              f"one-hot nodes, {time.time() - t0:.0f} s", file=sys.stderr,
              flush=True)
    out["onehot_node_share"] = round(
        sum(out["onehot_nodes"]) / max(sum(n - 1 for n in out["leaves"]),
                                       1), 4)
    out["seconds"] = round(time.time() - t0, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
