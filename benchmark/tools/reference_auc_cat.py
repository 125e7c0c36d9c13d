#!/usr/bin/env python3
"""Where the categorical cell's reference AUC comes from: a plain trainer
that shares no code with the program. Run by hand when the cell is
defined, on the CPU, never by the benchmark itself:

  python3 benchmark/tools/reference_auc_cat.py --config expo255-cat \\
      --traffic train-cat-28m --seed 0 [--rows N] [--iterations 9] \\
      [--schedule levels]

numpy and float64 throughout: histograms by ``bincount`` (gradient,
hessian and row count per bin), leaf-wise best-first growth to
``num_leaves``, the reference's numerical threshold scan and its
categorical search, binary log-loss from the prior's log-odds
(``boost_from_average``), over the benchmark's own generated data
(``harness/data_cat.py``). It needs neither jax nor the program.

The equations are LightGBM's (``src/treelearner/feature_histogram.hpp``):

- numerical, no missing values (``FindBestThresholdSequentially``, the
  reverse scan only): the right side gathers bins from the top down, the
  threshold is the bin below; a side under ``min_data_in_leaf`` or
  ``min_sum_hessian_in_leaf`` is skipped (right) or ends the scan (left);
  gain = G_l^2/(H_l + l2) + G_r^2/(H_r + l2), kept where it exceeds
  ``gain_shift + min_gain_to_split`` with ``gain_shift`` the leaf's own
  G^2/(H + l2); a later candidate wins only if strictly greater, so of
  equal gains the HIGHEST threshold stays;
- categorical (``FindBestThresholdCategoricalInner``): bin 0 (missing,
  unseen and rare categories) is never a candidate and goes right. With
  ``num_bin <= max_cat_to_onehot``: one category against the rest, plain
  ``lambda_l2``. Otherwise: the bins with count >= ``cat_smooth``, stably
  sorted by G/(H + ``cat_smooth``); from each end of that order a prefix
  of at most ``min(max_cat_threshold, (used + 1) // 2)`` categories goes
  left; a prefix is a candidate once it has gathered
  ``min_data_per_group`` rows since the last candidate (and the right
  side keeps as many); gains with ``lambda_l2 + cat_l2``, against the
  same ``gain_shift`` (plain ``lambda_l2``); the forward direction is
  tried first and the other replaces it only if strictly greater; leaf
  outputs -G/(H + ``lambda_l2 + cat_l2``);
- leaf output -G/(H + l2) times the learning rate; the tree grows
  best-first, the leaf with the largest gain next (ties: the lowest leaf
  index), until ``num_leaves`` or no leaf has a split.

Departures from the reference, each deliberate:

- bin counts are the real row counts of the histogram, where the
  reference estimates them as round(H x rows / sum H) because its
  histograms carry no count (the program counts too: ``ops/split.py``);
- ``kEpsilon`` (1e-15) is left out of the hessian sums: float64 here;
- vocabularies and the numerical bin bounds come from the first
  ``SAMPLE`` rows (the rows are i.i.d., so a prefix is a uniform sample;
  the reference draws its 200,000 at random): a categorical column keeps
  its categories by falling count until 99 % of the sample is covered and
  ``max_bin`` bins are used, or until one has fewer than
  ``min_data_in_bin`` (3) rows; a numerical column gets ``max_bin``
  equal-count bins of the sample's distinct values, the bound half-way
  between neighbours (the reference's greedy bin finder, which also
  gives heavy single values bins of their own, is not reproduced:
  both columns are continuous);
- no ``feature_fraction``, bagging, monotone or path smoothing: the
  configuration uses none.

``--schedule levels`` grows each tree in the program's order instead (a
level at a time the 1, 2, 4, ... 64 best leaves anywhere in the tree:
``level_caps``): the same data, search and loss, another order of spending
the 255 leaves. At the cell's size the order alone moves the AUC after 8
iterations by 0.0032, three iterations' worth (0.766061 best-first,
0.762813 by levels, six seeds each), so the cell's reference
(``benchmark/reference/expo255-cat.train-cat-28m.json``) is the
``levels`` reading and the file keeps both.

Prints one JSON object: the validation AUC after every iteration by the
benchmark's own rank AUC, each tree's leaves and categorical nodes, and
how far rounding the validation scores to bfloat16 moves the AUC.
"""
from __future__ import annotations

import argparse
import heapq
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import cells, data_cat, reference  # noqa: E402

SAMPLE = 200_000               # bin_construct_sample_cnt
MIN_DATA_IN_BIN = 3
# the reference's defaults of the categorical search (config.h)
CAT_DEFAULTS = {"cat_smooth": 10.0, "cat_l2": 10.0, "max_cat_threshold": 32,
                "max_cat_to_onehot": 4, "min_data_per_group": 100,
                "lambda_l2": 0.0, "min_gain_to_split": 0.0}


# ----------------------------------------------------------------- binning
def categorical_vocabulary(sample: np.ndarray, max_bin: int) -> np.ndarray:
    """Category codes by bin: entry b is the code of bin b, bin 0 (-1) is
    'missing, unseen or rare' (bin.cpp FindBin, categorical branch)."""
    codes, counts = np.unique(sample.astype(np.int64), return_counts=True)
    order = np.argsort(-counts, kind="stable")
    cut = int(round(sample.size * 0.99))
    limit = min(codes.size, max_bin)
    vocab, used = [-1], 0
    for rank, j in enumerate(order):
        if not (used < cut or len(vocab) < limit):
            break
        if counts[j] < MIN_DATA_IN_BIN and rank > 1:
            break
        vocab.append(int(codes[j]))
        used += int(counts[j])
    return np.asarray(vocab, np.int64)


def numerical_bounds(sample: np.ndarray, max_bin: int) -> np.ndarray:
    """Upper bounds of ``max_bin`` equal-count bins (the last is +inf)."""
    distinct = np.unique(sample)
    if distinct.size <= max_bin:
        mid = (distinct[:-1] + distinct[1:]) / 2.0
    else:
        q = np.quantile(sample.astype(np.float64),
                        np.arange(1, max_bin) / max_bin)
        mid = np.unique(q)
    return np.r_[mid, np.inf]


class Binner:
    def __init__(self, X: np.ndarray, categorical, max_bin: int):
        self.categorical = tuple(categorical)
        sample = X[:SAMPLE]
        self.vocab, self.bounds = {}, {}
        for f in range(X.shape[1]):
            if f in self.categorical:
                self.vocab[f] = categorical_vocabulary(sample[:, f], max_bin)
            else:
                self.bounds[f] = numerical_bounds(sample[:, f], max_bin)

    def num_bin(self, f: int) -> int:
        return (self.vocab[f].size if f in self.categorical
                else self.bounds[f].size)

    def transform(self, X: np.ndarray) -> np.ndarray:
        """[features, rows] uint8/uint16 bins."""
        widest = max(self.num_bin(f) for f in range(X.shape[1]))
        out = np.empty((X.shape[1], X.shape[0]),
                       np.uint8 if widest <= 256 else np.uint16)
        for f in range(X.shape[1]):
            col = X[:, f]
            if f in self.categorical:
                v = self.vocab[f]
                # NaN and negative codes are missing: bin 0
                code = np.where(np.isnan(col) | (col < 0), -1, col) \
                    .astype(np.int64)
                lut = np.zeros(int(max(v.max(), code.max())) + 2, np.int64)
                lut[v[1:]] = np.arange(1, v.size)
                out[f] = lut[code]              # -1 reads the spare last 0
            else:
                out[f] = np.searchsorted(self.bounds[f], col, side="left")
        return out


# ---------------------------------------------------------------- the search
def leaf_gain(g, h, l2):
    return g * g / (h + l2)


def best_numerical(g, h, c, p: dict):
    """Best threshold of one numerical feature's histogram (no missing):
    None, or dict(gain, threshold, left sums). Left is bin <= threshold."""
    l2 = p["lambda_l2"]
    G, H, C = g.sum(), h.sum(), c.sum()
    floor = leaf_gain(G, H, l2) + p["min_gain_to_split"]
    best = None
    rg = rh = rc = 0.0
    for t in range(g.size - 1, 0, -1):         # bins t.. go right
        rg += g[t]
        rh += h[t]
        rc += c[t]
        if rc < p["min_data_in_leaf"] or rh < p["min_sum_hessian_in_leaf"]:
            continue
        lg, lh, lc = G - rg, H - rh, C - rc
        if lc < p["min_data_in_leaf"] or lh < p["min_sum_hessian_in_leaf"]:
            break
        gain = leaf_gain(lg, lh, l2) + leaf_gain(rg, rh, l2)
        if gain <= floor:
            continue
        if best is None or gain > best["gain"] + floor:
            best = {"gain": gain - floor, "threshold": t - 1, "l2": l2,
                    "left": (lg, lh, lc), "right": (rg, rh, rc)}
    return best


def best_categorical(g, h, c, p: dict):
    """Best category set of one categorical feature's histogram: None, or
    dict(gain, bins (the bins that go left), left / right sums, l2)."""
    n = g.size
    G, H, C = g.sum(), h.sum(), c.sum()
    floor = leaf_gain(G, H, p["lambda_l2"]) + p["min_gain_to_split"]
    best = None

    def consider(gain, bins, left, l2):
        nonlocal best
        if gain > floor and (best is None or gain > best["gain"] + floor):
            best = {"gain": gain - floor, "bins": bins, "l2": l2,
                    "left": left,
                    "right": (G - left[0], H - left[1], C - left[2])}

    if n <= p["max_cat_to_onehot"]:
        l2 = p["lambda_l2"]
        for t in range(1, n):
            if (c[t] < p["min_data_in_leaf"]
                    or h[t] < p["min_sum_hessian_in_leaf"]
                    or C - c[t] < p["min_data_in_leaf"]
                    or H - h[t] < p["min_sum_hessian_in_leaf"]):
                continue
            consider(leaf_gain(g[t], h[t], l2)
                     + leaf_gain(G - g[t], H - h[t], l2),
                     (t,), (g[t], h[t], c[t]), l2)
        return best

    l2 = p["lambda_l2"] + p["cat_l2"]
    used = [t for t in range(1, n) if c[t] >= p["cat_smooth"]]
    used.sort(key=lambda t: g[t] / (h[t] + p["cat_smooth"]))   # stable
    most = min(p["max_cat_threshold"], (len(used) + 1) // 2)
    for order in (used, used[::-1]):
        lg = lh = lc = group = 0.0
        for i, t in enumerate(order[:most]):
            lg += g[t]
            lh += h[t]
            lc += c[t]
            group += c[t]
            if (lc < p["min_data_in_leaf"]
                    or lh < p["min_sum_hessian_in_leaf"]):
                continue
            rc, rh = C - lc, H - lh
            if (rc < p["min_data_in_leaf"] or rc < p["min_data_per_group"]
                    or rh < p["min_sum_hessian_in_leaf"]):
                break
            if group < p["min_data_per_group"]:
                continue
            group = 0.0
            consider(leaf_gain(lg, lh, l2) + leaf_gain(G - lg, rh, l2),
                     tuple(order[:i + 1]), (lg, lh, lc), l2)
    return best


def best_of_leaf(hist, binner: Binner, p: dict):
    """Best split over the features of one leaf: the first feature wins a
    tie. hist: [features, 3, bins]."""
    best = None
    for f in range(hist.shape[0]):
        nb = binner.num_bin(f)
        g, h, c = hist[f, 0, :nb], hist[f, 1, :nb], hist[f, 2, :nb]
        s = (best_categorical if f in binner.categorical
             else best_numerical)(g, h, c, p)
        if s is not None and (best is None or s["gain"] > best["gain"]):
            best = dict(s, feature=f)
    return best


# ----------------------------------------------------------------- the tree
def histogram(Xb, rows, grad, hess, width: int) -> np.ndarray:
    """[features, 3, width] float64 sums of grad, hess and rows per bin."""
    g = grad if rows is None else grad[rows]
    h = hess if rows is None else hess[rows]
    out = np.empty((Xb.shape[0], 3, width))
    for f in range(Xb.shape[0]):
        b = Xb[f] if rows is None else Xb[f][rows]
        out[f, 0] = np.bincount(b, weights=g, minlength=width)
        out[f, 1] = np.bincount(b, weights=h, minlength=width)
        out[f, 2] = np.bincount(b, minlength=width)
    return out


def goes_left(bins: np.ndarray, split: dict, width: int) -> np.ndarray:
    if "bins" in split:
        member = np.zeros(width, bool)
        member[list(split["bins"])] = True
        return member[bins]
    return bins <= split["threshold"]


def level_caps(num_leaves: int, slot_cap: int = 64, extra: int = 3) -> list:
    """The program's level schedule (``models/frontier2.level_caps`` at
    this width): 1, 2, 4, ... splits a level, at most ``slot_cap``, until
    they could fill the tree, then ``extra`` passes more."""
    caps, total, d = [], 0, 0
    while total < num_leaves - 1:
        caps.append(min(1 << d, slot_cap, num_leaves - 1))
        total += caps[-1]
        d += 1
    return caps + [min(64, slot_cap, num_leaves - 1)] * extra


def grow(Xb, grad, hess, binner: Binner, p: dict, width: int,
         schedule: str = "best_first"):
    """One tree: (nodes, leaf_value, row_leaf). A node is (feature, split,
    left child, right child); children < 0 are ~leaf. ``best_first``: the
    leaf with the largest gain next, as the reference grows. ``levels``: the
    program's order, a level at a time the ``level_caps`` best leaves
    anywhere in the tree: the same search, another order of spending the
    leaf budget, to see what the order alone does to the model."""
    n = Xb.shape[1]
    rows_of = {0: None}                         # None = every row
    hist_of = {0: histogram(Xb, None, grad, hess, width)}
    best_of = {0: best_of_leaf(hist_of[0], binner, p)}
    G, H = grad.sum(), hess.sum()
    value = {0: -G / (H + p["lambda_l2"])}
    parent_slot = {0: None}                     # (node, side) to patch
    nodes = []

    def split(leaf, new):
        s = best_of.pop(leaf)
        rows = rows_of.pop(leaf)
        f = s["feature"]
        left = goes_left(Xb[f] if rows is None else Xb[f][rows], s, width)
        if rows is None:
            rows = np.arange(n, dtype=np.int64 if n > 2**31 - 1 else np.int32)
        node = len(nodes)
        nodes.append([f, s, ~leaf, ~new])
        if parent_slot[leaf] is not None:
            pn, side = parent_slot[leaf]
            nodes[pn][side] = node
        parent_slot[leaf], parent_slot[new] = (node, 2), (node, 3)
        rows_of[leaf], rows_of[new] = rows[left], rows[~left]
        del rows, left
        for child, (sg, sh, _) in ((leaf, s["left"]), (new, s["right"])):
            value[child] = -sg / (sh + s["l2"])
        parent_hist = hist_of.pop(leaf)
        small, big = ((leaf, new) if rows_of[leaf].size <= rows_of[new].size
                      else (new, leaf))
        hist_of[small] = histogram(Xb, rows_of[small], grad, hess, width)
        hist_of[big] = parent_hist - hist_of[small]
        for child in (leaf, new):
            best_of[child] = best_of_leaf(hist_of[child], binner, p)

    n_leaves = 1
    if schedule == "best_first":
        heap = [(-best_of[0]["gain"], 0)] if best_of[0] is not None else []
        while n_leaves < p["num_leaves"] and heap:
            _, leaf = heapq.heappop(heap)
            split(leaf, n_leaves)
            for child in (leaf, n_leaves):
                if best_of[child] is not None:
                    heapq.heappush(heap, (-best_of[child]["gain"], child))
            n_leaves += 1
    else:
        for cap in level_caps(p["num_leaves"]):
            ready = sorted((leaf for leaf, s in best_of.items()
                            if s is not None),
                           key=lambda leaf: (-best_of[leaf]["gain"], leaf))
            for leaf in ready[:min(cap, p["num_leaves"] - n_leaves)]:
                split(leaf, n_leaves)
                n_leaves += 1
    row_leaf = np.zeros(n, np.int32)
    for leaf, rows in rows_of.items():
        if rows is not None:
            row_leaf[rows] = leaf
    leaf_value = np.array([value[i] for i in range(n_leaves)])
    return nodes, leaf_value, row_leaf


def leaves_of(nodes, Xb, width: int) -> np.ndarray:
    """Leaf of every column of Xb in a grown tree."""
    n = Xb.shape[1]
    out = np.zeros(n, np.int32)
    if not nodes:
        return out
    stack = [(0, np.arange(n, dtype=np.int32))]
    while stack:
        node, rows = stack.pop()
        f, s, lc, rc = nodes[node]
        left = goes_left(Xb[f][rows], s, width)
        for child, part in ((lc, rows[left]), (rc, rows[~left])):
            if part.size == 0:
                continue
            if child < 0:
                out[part] = ~child
            else:
                stack.append((child, part))
    return out


def bf16(x: np.ndarray) -> np.ndarray:
    """float64 -> float32 -> bfloat16 (round to nearest even) -> float64."""
    u = x.astype(np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="expo255-cat")
    ap.add_argument("--traffic", default="train-cat-28m")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="train on a prefix of the rows (0 = all)")
    ap.add_argument("--valid-rows", type=int, default=0)
    ap.add_argument("--iterations", type=int, default=0,
                    help="0 = the traffic mix's iterations + 1")
    ap.add_argument("--schedule", choices=("best_first", "levels"),
                    default="best_first",
                    help="the order of growth: the reference's, or the "
                         "program's level schedule (see grow)")
    args = ap.parse_args()
    cfg = cells.load_json(os.path.join(cells.BENCH, "configs",
                                       args.config + ".json"))
    tr = cells.load_json(os.path.join(cells.BENCH, "traffic",
                                      args.traffic + ".json"))
    p = dict(CAT_DEFAULTS, **{k: v for k, v in cfg["params"].items()
                              if not isinstance(v, str)})
    iters = args.iterations or int(tr["chunk_iterations"]) * (
        int(tr["warmup_chunks"]) + int(tr["measured_chunks"])) + 1
    rows = args.rows or int(tr["rows"])
    t0 = time.time()
    X, y, Xv, yv = data_cat.make_data(
        args.seed, rows, args.valid_rows or int(tr["valid_rows"]))
    binner = Binner(X, cfg["categorical_feature"], int(p["max_bin"]))
    Xb, Xvb = binner.transform(X), binner.transform(Xv)
    del X, Xv
    width = max(binner.num_bin(f) for f in range(Xb.shape[0]))
    y = y.astype(np.float64)
    prior = float(y.mean())
    raw = np.full(y.size, np.log(prior / (1.0 - prior)))
    raw_v = np.full(yv.size, raw[0])
    out = {"seed": args.seed, "schedule": args.schedule, "rows": rows,
           "valid_rows": int(yv.size),
           "positives": prior,
           "num_bin": [binner.num_bin(f) for f in range(Xb.shape[0])],
           "auc": [], "auc_bf16_scores": [], "leaves": [], "cat_nodes": [],
           "cat_nodes_by_feature": {}}
    print(f"reference: {rows} rows binned in {time.time() - t0:.0f} s; "
          f"bins {out['num_bin']}", file=sys.stderr, flush=True)
    for it in range(iters):
        prob = reference.sigmoid(raw)
        nodes, leaf_value, row_leaf = grow(
            Xb, prob - y, prob * (1.0 - prob), binner, p, width,
            args.schedule)
        leaf_value *= p["learning_rate"]
        raw += leaf_value[row_leaf]
        raw_v += leaf_value[leaves_of(nodes, Xvb, width)]
        out["auc"].append(round(reference.rank_auc(yv, raw_v), 6))
        out["auc_bf16_scores"].append(
            round(reference.rank_auc(yv, bf16(raw_v)), 6))
        out["leaves"].append(int(leaf_value.size))
        cat = [nd[0] for nd in nodes if "bins" in nd[1]]
        out["cat_nodes"].append(len(cat))
        for f in cat:
            key = data_cat.COLUMNS[f]
            out["cat_nodes_by_feature"][key] = \
                out["cat_nodes_by_feature"].get(key, 0) + 1
        print(f"reference: iteration {it + 1}: auc {out['auc'][-1]}, "
              f"{out['leaves'][-1]} leaves, {len(cat)} categorical nodes, "
              f"{time.time() - t0:.0f} s", file=sys.stderr, flush=True)
    out["cat_node_share"] = round(
        sum(out["cat_nodes"]) / max(sum(n - 1 for n in out["leaves"]), 1), 4)
    out["seconds"] = round(time.time() - t0, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
