#!/usr/bin/env python3
"""Where a train cell's ``reference_auc`` comes from. Run by hand when a
cell is defined, on the CPU, never by the benchmark itself:

  JAX_PLATFORMS=cpu python3 benchmark/tools/reference_auc.py \\
      --config higgs63 --traffic train-28m --engine plain --rows 1048576

``--engine plain`` is the program's plain reference: ``lgb.train`` with
``tpu_fast_path=false`` on the CPU test mode (XLA growers, float32, no
kernels, no megastep), on the benchmark's own data. ``--engine sklearn``
is an implementation that shares no code with the program, for row
counts the plain reference cannot reach on a CPU: scikit-learn's
histogram tree grower (``TreeGrower``, the class behind
``HistGradientBoostingClassifier``, used directly because only it takes
a minimum hessian per leaf) in a plain boosting loop of the binary
log-loss, at the configuration's bins, leaves, learning rate,
``min_data_in_leaf`` and ``min_sum_hessian_in_leaf``, from the prior's
log-odds as LightGBM's ``boost_from_average`` starts. ``--rows`` cuts
the training rows to a prefix of the same seeded stream (the generator
is blockwise, so the prefix is the same data); validation rows are never
cut.

Prints one JSON object: the validation AUC after every iteration, by the
benchmark's own rank AUC (and, for ``plain``, over its own numpy walk of
the dumped trees).
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


def sklearn_curve(X, y, Xv, yv, p: dict, iters: int, seed: int) -> list:
    """Validation AUC after each iteration of a plain log-loss boosting
    loop over scikit-learn's histogram tree grower."""
    from sklearn.ensemble._hist_gradient_boosting.binning import _BinMapper
    from sklearn.ensemble._hist_gradient_boosting.grower import TreeGrower
    mapper = _BinMapper(n_bins=int(p["max_bin"]) + 1, random_state=seed)
    Xb, Xvb = mapper.fit_transform(X), mapper.transform(Xv)
    threads = os.cpu_count() or 1
    prior = float(y.mean())
    raw = np.full(y.size, np.log(prior / (1.0 - prior)))
    raw_v = np.full(yv.size, raw[0])
    curve = []
    for _ in range(iters):
        prob = reference.sigmoid(raw)
        grower = TreeGrower(
            Xb, (prob - y).astype(np.float32),
            (prob * (1.0 - prob)).astype(np.float32),
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
        raw += tree.predict_binned(Xb, mapper.missing_values_bin_idx_,
                                   threads)
        raw_v += tree.predict_binned(Xvb, mapper.missing_values_bin_idx_,
                                     threads)
        curve.append(reference.rank_auc(yv, raw_v))
    return curve


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
           "valid_rows": int(tr["valid_rows"]), "iterations": iters}
    if args.engine == "plain":
        import lightgbm_tpu as lgb
        from lightgbm_tpu.utils.platform import compilation_cache_dir
        compilation_cache_dir()
        ds = lgb.Dataset(X, label=y, params={"max_bin": p["max_bin"],
                                             "verbose": -1})
        bst = lgb.train(dict(p, tpu_fast_path=False, verbose=-1), ds,
                        num_boost_round=iters)
        trees = reference.flatten(bst.dump_model())
        raw = np.zeros(Xv.shape[0])
        curve = []
        for t in trees:
            raw += reference.walk([t], Xv)
            curve.append(reference.rank_auc(yv, raw))
    else:
        curve = sklearn_curve(X, y, Xv, yv, p, iters, args.seed)
    out["auc_by_iteration"] = curve
    out["seconds"] = round(time.time() - t0, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
