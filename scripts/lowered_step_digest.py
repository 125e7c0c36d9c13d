"""Digest of the lowered steps of a checkout, for small jobs on the CPU
(binary, categorical and multiclass through the megastep with its traced
evaluation; binary through the megastep without it and through the
per-iteration step): two checkouts that print the same digests lower the
plain GBDT step to the same StableHLO text and grow the same models (PR 35:
the sampled step of ``boosting=goss`` is a second executable, with an
operand and a result more, and must leave the plain one as it was).

  JAX_PLATFORMS=cpu python scripts/lowered_step_digest.py <checkout>

One line per job: name, sha256[:16] of the step's text, its length,
sha256[:16] of the model text."""
import sys, os, hashlib
root = sys.argv[1]
sys.path.insert(0, root)
import numpy as np, jax
import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
assert os.path.dirname(os.path.dirname(lgb.__file__)) == os.path.abspath(root), lgb.__file__
seen = {}
def record(maker):
    make = getattr(GBDT, maker)
    def recording(self, *rest):
        fn = make(self, *rest)
        def call(*args):
            seen["fn"] = fn
            seen["avals"] = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype if not hasattr(x, "dtype") else x.dtype), args)
            return fn(*args)
        return call
    setattr(GBDT, maker, recording)
record("_make_megastep")
record("_make_fast_step")
for name, extra, cats, valid in (("binary", {}, "auto", True), ("cat", {"min_data_per_group": 20}, [2, 3], True),
                                 ("multiclass", {"objective": "multiclass", "num_class": 3}, "auto", True),
                                 ("binary_no_eval", {}, "auto", False), ("binary_fast_step", {"tpu_megastep": False}, "auto", False)):
    rng = np.random.RandomState(0)
    X = rng.rand(3000, 8).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float32)
    if name == "cat":
        for c, n in ((2, 40), (3, 9)): X[:, c] = np.floor(n * X[:, c] ** 2)
    if name == "multiclass": y = np.floor(3 * X[:, 0]).clip(0, 2)
    params = dict({"objective": "binary", "num_leaves": 15, "max_bin": 63, "verbose": -1, "min_data_in_leaf": 5,
              "tpu_engine": "fused", "tpu_megastep": True, "tpu_megastep_iters": 2, "metric": "auc" if name != "multiclass" else "multi_logloss"}, **extra)
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    seen.clear()
    b = lgb.train(params, ds, num_boost_round=2, valid_sets=[lgb.Dataset(X[:500], label=y[:500], reference=ds)] if valid else [],
                  callbacks=[lgb.record_evaluation({})] if valid else [])
    text = seen["fn"].lower(*seen["avals"]).as_text()
    print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text), hashlib.sha256(b.model_to_string().split("parameters:")[0].encode()).hexdigest()[:16])
