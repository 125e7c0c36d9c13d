#!/usr/bin/env python3
"""Where the GOSS cell's ``reference_auc`` comes from. Run by hand when the
cell is defined, on the CPU, never by the benchmark itself:

  JAX_PLATFORMS=cpu python3 benchmark/tools/reference_auc_goss.py \\
      --config higgs63-goss --traffic train-goss-28m --engine sklearn --seed 0

``--engine sklearn`` shares no code with the program: Gradient-based
One-Side Sampling as the paper states it (Ke et al., NeurIPS 2017,
Algorithm 2) in numpy, around the plain log-loss boosting loop over
scikit-learn's histogram ``TreeGrower`` that ``reference_auc.py --engine
sklearn`` uses. Every iteration from ``int(1 / learning_rate)`` on: sort
the rows by |g * h| descending (a stable sort, so ties go to the lower row
index), keep the first ``top_k = int(n * top_rate)``, choose ``other_k =
int(n * other_rate)`` of the others without replacement from a generator
seeded by (bagging_seed, iteration), multiply their gradient and hessian by
``(n - top_k) / other_k``, grow the tree on those rows alone, and add its
prediction to the scores of ALL rows. The iterations before that use all
rows. ``--engine plain`` is the program's plain reference (``lgb.train``
with ``tpu_fast_path=false`` on the CPU test mode) for the sizes a CPU
reaches. ``--rows`` cuts the training rows to a prefix of the same seeded
stream; validation rows are never cut.

``--fault`` plants what the cell's limits have to tell: ``no_multiplier``
(the drawn rows keep weight 1), ``never_sampled`` (every iteration uses all
rows), ``top_only`` (no rows are drawn from the rest), and the precision
below the configuration's float32 where the configuration says float32:
``bf16_scores`` (the training and the validation scores carried in
bfloat16 from iteration to iteration), ``bf16_gradients`` (gradient and
hessian rounded to bfloat16 before the select and the histograms),
``bf16`` (both).

Prints one JSON object: the validation AUC after every iteration by the
benchmark's own rank AUC over the CARRIED scores (what a program computing
that way would trace), ``auc_walk`` (the AUC of the float64 sum of the same
trees' outputs: what the benchmark's own walk of the dumped trees reads),
``carried_vs_walk`` (their distance: the cell's ``traced_vs_own``; a rank
statistic, which rounding noise mostly cancels in), ``scores_p90_abs`` and
``scores_max_abs`` (the 90th percentile and the largest of |carried score -
walk's score| over the validation rows: the cell's ``scores_vs_walk``,
which does tell the precision), the rows each tree was grown on, and the
AUC of the last iteration's scores rounded to bfloat16.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from harness import data, reference  # noqa: E402

FAULTS = ("", "no_multiplier", "never_sampled", "top_only", "bf16_scores",
          "bf16_gradients", "bf16")


def goss_rows(grad, hess, top_k: int, other_k: int, rng,
              fault: str = ""):
    """(rows the tree is grown on, in row order; the multiplier of each):
    Algorithm 2. ``order`` is a stable descending sort of |g * h|."""
    n = grad.size
    order = np.argsort(-np.abs(grad * hess), kind="stable")
    top, rest = order[:top_k], order[top_k:]
    drawn = rng.choice(rest, size=other_k, replace=False) \
        if fault != "top_only" else rest[:0]
    weight = np.zeros(n, np.float32)
    weight[top] = 1.0
    weight[drawn] = 1.0 if fault == "no_multiplier" \
        else (n - top_k) / other_k
    rows = np.flatnonzero(weight)
    return rows, weight[rows]


def _bf16(a):
    """``a`` rounded to bfloat16, in its own dtype."""
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(a.dtype)


def sklearn_curve(X, y, Xv, yv, p: dict, iters: int, seed: int,
                  fault: str = ""):
    """(validation AUC after each iteration, rows each tree grew on, the
    last validation scores as carried, the float64 sum of the trees'
    outputs over the validation rows)."""
    from sklearn.ensemble._hist_gradient_boosting.binning import _BinMapper
    from sklearn.ensemble._hist_gradient_boosting.grower import TreeGrower
    mapper = _BinMapper(n_bins=int(p["max_bin"]) + 1, random_state=seed)
    Xb, Xvb = mapper.fit_transform(X), mapper.transform(Xv)
    threads = os.cpu_count() or 1
    n = y.size
    first = int(1.0 / float(p["learning_rate"]))
    top_k = max(1, int(n * float(p["top_rate"])))
    other_k = max(1, int(n * float(p["other_rate"])))
    prior = float(y.mean())
    raw = np.full(n, np.log(prior / (1.0 - prior)))
    raw_v = np.full(yv.size, raw[0])
    walk_v = raw_v.copy()
    carry = _bf16 if fault in ("bf16", "bf16_scores") else (lambda a: a)
    round_gh = _bf16 if fault in ("bf16", "bf16_gradients") else (lambda a: a)
    raw, raw_v = carry(raw), carry(raw_v)
    curve, grown_on = [], []
    for it in range(iters):
        prob = reference.sigmoid(raw)
        grad = round_gh((prob - y).astype(np.float32))
        hess = round_gh((prob * (1.0 - prob)).astype(np.float32))
        Xt = Xb
        if it >= first and fault != "never_sampled":
            rng = np.random.default_rng([int(p.get("bagging_seed", 3)), it])
            rows, w = goss_rows(grad, hess, top_k, other_k, rng, fault)
            Xt = np.asfortranarray(Xb[rows])
            grad, hess = grad[rows] * w, hess[rows] * w
        grown_on.append(int(grad.size))
        grower = TreeGrower(
            Xt, grad, hess,
            max_leaf_nodes=int(p["num_leaves"]),
            min_samples_leaf=int(p.get("min_data_in_leaf", 20)),
            min_hessian_to_split=float(p.get("min_sum_hessian_in_leaf",
                                             1e-3)),
            n_bins=mapper.n_bins,
            n_bins_non_missing=mapper.n_bins_non_missing_,
            has_missing_values=False, l2_regularization=0.0,
            shrinkage=float(p["learning_rate"]), n_threads=threads)
        grower.grow()
        tree = grower.make_predictor(mapper.bin_thresholds_)
        # the scores of ALL rows, out-of-bag too
        raw = carry(raw + tree.predict_binned(
            Xb, mapper.missing_values_bin_idx_, threads))
        out_v = tree.predict_binned(Xvb, mapper.missing_values_bin_idx_,
                                    threads)
        raw_v = carry(raw_v + out_v)
        walk_v += out_v
        curve.append(reference.rank_auc(yv, raw_v))
    return curve, grown_on, raw_v, walk_v


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--engine", choices=("plain", "sklearn"), required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="training rows (a prefix); 0 = the cell's own")
    ap.add_argument("--iterations", type=int, default=0,
                    help="0 = the cell's own job length")
    ap.add_argument("--fault", choices=FAULTS, default="")
    args = ap.parse_args()
    bench = os.path.dirname(HERE)
    with open(os.path.join(bench, "configs", args.config + ".json")) as fh:
        cfg = json.load(fh)
    with open(os.path.join(bench, "traffic", args.traffic + ".json")) as fh:
        tr = json.load(fh)
    rows = args.rows or int(tr["rows"])
    iters = args.iterations or int(tr["chunk_iterations"]) * (
        int(tr["warmup_chunks"]) + int(tr["measured_chunks"]))
    p = cfg["params"]
    t0 = time.time()
    X, y, Xv, yv = data.make_data(args.seed, rows, int(tr["valid_rows"]),
                                  int(cfg["features"]))
    out = {"engine": args.engine, "config": args.config,
           "traffic": args.traffic, "seed": args.seed, "rows": rows,
           "valid_rows": int(tr["valid_rows"]), "iterations": iters,
           "fault": args.fault}
    if args.engine == "plain":
        import lightgbm_tpu as lgb
        from lightgbm_tpu.utils.platform import compilation_cache_dir
        compilation_cache_dir()
        ds = lgb.Dataset(X, label=y, params={"max_bin": p["max_bin"],
                                             "verbose": -1})
        bst = lgb.train(dict(p, tpu_fast_path=False, verbose=-1), ds,
                        num_boost_round=iters)
        dumped = bst.dump_model()
        raw_v = np.zeros(Xv.shape[0])
        curve = []
        for t in reference.flatten(dumped):
            raw_v += reference.walk([t], Xv)
            curve.append(reference.rank_auc(yv, raw_v))
        out["grown_on"] = [int(t["tree_structure"].get("internal_count", 0))
                           for t in dumped["tree_info"]]
    else:
        curve, out["grown_on"], raw_v, walk_v = sklearn_curve(
            X, y, Xv, yv, p, iters, args.seed, args.fault)
        out["auc_walk"] = reference.rank_auc(yv, walk_v)
        out["carried_vs_walk"] = abs(curve[-1] - out["auc_walk"])
        off = np.abs(raw_v - walk_v)
        out["scores_p90_abs"] = float(np.quantile(off, 0.9))
        out["scores_max_abs"] = float(off.max())
    out["auc_by_iteration"] = curve
    import ml_dtypes
    out["auc_bf16_scores"] = reference.rank_auc(
        yv, raw_v.astype(ml_dtypes.bfloat16).astype(np.float64))
    out["seconds"] = round(time.time() - t0, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
