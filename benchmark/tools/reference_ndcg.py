#!/usr/bin/env python3
"""Where a ranking cell's reference NDCG@10 comes from. Run by hand when
the cell is defined, on the CPU, never by the benchmark itself:

  python3 benchmark/tools/reference_ndcg.py --config msltr63 \\
      --traffic train-rank-6m8 --seed 0

A LambdaMART that shares no code with the program: the lambdas of the
reference's equations (rank_objective.hpp:139-230: pairs (i, j), i < j in
score order, i under the truncation level, labels differing; |delta NDCG|
of the swap, divided by 0.01 + |delta score| and the query's sum scaled
by log2(1 + S) / S under ``lambdarank_norm``) in float64 numpy, one
truncation position at a time over all rows, in a plain boosting loop over
scikit-learn's histogram ``TreeGrower`` (as ``reference_auc.py --engine
sklearn``), at the configuration's bins, leaves, learning rate,
``min_data_in_leaf`` and ``min_sum_hessian_in_leaf``, from score 0 as
LightGBM starts a ranking job.

Prints one JSON object: the validation NDCG@1,3,5,10 after every
iteration by the benchmark's own NDCG (``harness/reference_rank.py``), and
``bf16_scores_move``: how far each cutoff's NDCG moves when the last
iteration's validation scores are rounded to bfloat16 (the reading the
kind's ``NDCG_VS_OWN`` is set under).

``--fault`` plants one fault in the model, for the readings the cell's band
is set against (``faults`` in ``benchmark/reference/<cell>.json``):
``no_norm`` trains without ``lambdarank_norm``, ``half_rate`` at half the
learning rate, ``truncation_10`` pairs against the top 10 only.
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

from harness import data_rank, reference_rank  # noqa: E402

TRUNCATION, SIGMOID = 30, 1.0       # LightGBM's defaults, lambdarank_norm on


def lambdas(label: np.ndarray, score: np.ndarray, group: np.ndarray,
            norm: bool = True, truncation: int = TRUNCATION):
    """(gradient, hessian) of every row, float64."""
    n = label.size
    start = np.cumsum(group) - group
    qid = np.repeat(np.arange(group.size), group)
    order = np.lexsort((-score, qid))       # stable: ties in row order
    s, lab = score[order], label[order]
    gain = np.exp2(lab) - 1.0
    rank = np.arange(n) - start[qid]
    disc = 1.0 / np.log2(2.0 + rank)
    # inverse of the best DCG at the truncation level, per query
    ideal = gain[np.lexsort((-gain, qid))]
    best = np.bincount(qid, weights=np.where(rank < truncation,
                                             ideal * disc, 0.0))
    inv_best = np.where(best > 0, 1.0 / np.where(best > 0, best, 1.0), 0.0)
    hi = s[start]
    lo = s[start + group - 1]
    by_gap = (hi != lo)[qid] & norm
    lam = np.zeros(n)
    hes = np.zeros(n)
    total = np.zeros(group.size)
    for t in range(min(truncation, int(group.max()) - 1)):
        j = np.flatnonzero(rank > t)        # partners below position t
        i = start[qid[j]] + t
        differ = lab[i] != lab[j]
        i, j = i[differ], j[differ]
        i_high = lab[i] > lab[j]
        delta_s = np.where(i_high, s[i] - s[j], s[j] - s[i])
        delta = np.abs(gain[i] - gain[j]) * np.abs(disc[i] - disc[j]) \
            * inv_best[qid[j]]
        delta = np.where(by_gap[j], delta / (0.01 + np.abs(delta_s)), delta)
        with np.errstate(over="ignore"):
            rho = 1.0 / (1.0 + np.exp(SIGMOID * delta_s))
        push = SIGMOID * delta * rho
        curve = SIGMOID * SIGMOID * delta * rho * (1.0 - rho)
        to_i = np.where(i_high, -push, push)
        lam[j] -= to_i
        hes[j] += curve
        lam += np.bincount(i, weights=to_i, minlength=n)
        hes += np.bincount(i, weights=curve, minlength=n)
        total += np.bincount(qid[j], weights=2.0 * push,
                             minlength=group.size)
    scale = np.where((total > 0) & norm,
                     np.log2(1.0 + total) / np.where(total > 0, total, 1.0),
                     1.0)[qid]
    g = np.empty(n)
    h = np.empty(n)
    g[order] = lam * scale
    h[order] = hes * scale
    return g, h


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even)."""
    bits = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def curve(X, y, group, Xv, yv, group_v, p: dict, iters: int, seed: int,
          fault: str = "none"):
    from sklearn.ensemble._hist_gradient_boosting.binning import _BinMapper
    from sklearn.ensemble._hist_gradient_boosting.grower import TreeGrower
    mapper = _BinMapper(n_bins=int(p["max_bin"]) + 1, random_state=seed)
    Xb, Xvb = mapper.fit_transform(X), mapper.transform(Xv)
    threads = os.cpu_count() or 1
    ks = [int(k) for k in p["eval_at"]]
    label = y.astype(np.float64)
    raw = np.zeros(y.size)
    raw_v = np.zeros(yv.size)
    out = []
    rate = float(p["learning_rate"]) * (0.5 if fault == "half_rate" else 1)
    for _ in range(iters):
        g, h = lambdas(label, raw, group, norm=fault != "no_norm",
                       truncation=10 if fault == "truncation_10"
                       else TRUNCATION)
        grower = TreeGrower(
            Xb, g.astype(np.float32), h.astype(np.float32),
            max_leaf_nodes=int(p["num_leaves"]),
            min_samples_leaf=int(p.get("min_data_in_leaf", 20)),
            min_hessian_to_split=float(p.get("min_sum_hessian_in_leaf",
                                             1e-3)),
            n_bins=mapper.n_bins,
            n_bins_non_missing=mapper.n_bins_non_missing_,
            has_missing_values=False, l2_regularization=0.0,
            shrinkage=rate, n_threads=threads)
        grower.grow()
        tree = grower.make_predictor(mapper.bin_thresholds_)
        raw += tree.predict_binned(Xb, mapper.missing_values_bin_idx_,
                                   threads)
        raw_v += tree.predict_binned(Xvb, mapper.missing_values_bin_idx_,
                                     threads)
        out.append({"leaves": int(grower.n_nodes + 1) // 2,
                    **{f"ndcg@{k}": v for k, v in zip(
                        ks, reference_rank.ndcg_at(ks, yv, raw_v,
                                                   group_v))}})
    exact = reference_rank.ndcg_at(ks, yv, raw_v.astype(np.float32), group_v)
    rounded = reference_rank.ndcg_at(ks, yv, to_bfloat16(raw_v), group_v)
    return out, {f"ndcg@{k}": abs(a - b)
                 for k, a, b in zip(ks, exact, rounded)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iterations", type=int, default=0,
                    help="0 = the cell's own job length")
    ap.add_argument("--fault", default="none",
                    choices=["none", "no_norm", "half_rate",
                             "truncation_10"])
    args = ap.parse_args()
    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "configs", args.config + ".json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(bench, "traffic", args.traffic + ".json")) as fh:
        tr = json.load(fh)
    iters = args.iterations or int(tr["chunk_iterations"]) * (
        int(tr["warmup_chunks"]) + int(tr["measured_chunks"]))
    t0 = time.time()
    data = data_rank.make_data(
        args.seed, int(tr["rows"]), int(tr["queries"]),
        int(tr["valid_rows"]), int(tr["valid_queries"]),
        int(cfg["features"]), int(tr["longest_query"]))
    by_iteration, moved = curve(*data, cfg["params"], iters, args.seed,
                                args.fault)
    out = {"config": args.config, "traffic": args.traffic,
           "seed": args.seed, "rows": int(tr["rows"]), "iterations": iters,
           "fault": args.fault, "by_iteration": by_iteration,
           "bf16_scores_move": moved,
           "seconds": round(time.time() - t0, 1)}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
