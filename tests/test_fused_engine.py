"""End-to-end GBDT training through the fused engine (tpu_engine=fused,
interpret mode on CPU) vs the default XLA engine."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(R=3000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(R, 8).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] > 0).astype(np.float32)
    X[::23, 4] = np.nan
    return X, y


def _auc(y, p):
    from sklearn.metrics import roc_auc_score
    return roc_auc_score(y, p)


def test_fused_engine_trains_binary():
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "tpu_engine": "fused"}
    ds = lgb.Dataset(X, label=y, params={"verbose": -1})
    bst = lgb.train(params, ds, num_boost_round=15)
    auc_fused = _auc(y, bst.predict(X))

    params_ref = dict(params)
    params_ref["tpu_engine"] = "xla"
    ds2 = lgb.Dataset(X, label=y, params={"verbose": -1})
    bst2 = lgb.train(params_ref, ds2, num_boost_round=15)
    auc_ref = _auc(y, bst2.predict(X))

    assert auc_fused > 0.97
    assert auc_fused > auc_ref - 0.01


def test_fused_engine_regression_l2():
    rng = np.random.RandomState(1)
    X = rng.rand(2000, 6).astype(np.float32)
    y = (3 * X[:, 0] - 2 * X[:, 1] + 0.1 * rng.randn(2000)).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1})
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "verbose": -1, "min_data_in_leaf": 5,
                     "tpu_engine": "fused"}, ds, num_boost_round=20)
    pred = bst.predict(X)
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.05, mse


def test_fused_matches_xla_trees_first_iter():
    """First tree of fused vs xla depthwise engines must pick the same root
    split on clean data (same histograms -> same gain scan)."""
    X, y = _data(R=2000, seed=3)
    base = {"objective": "binary", "num_leaves": 7, "verbose": -1,
            "min_data_in_leaf": 5, "grow_policy": "depthwise"}
    models = {}
    for eng in ("fused", "xla"):
        p = dict(base)
        p["tpu_engine"] = eng
        ds = lgb.Dataset(X, label=y, params={"verbose": -1})
        bst = lgb.train(p, ds, num_boost_round=1)
        models[eng] = bst.dump_model()["tree_info"][0]["tree_structure"]

    def root(m):
        return (m["split_feature"], round(m["threshold"], 6))
    assert root(models["fused"]) == root(models["xla"])


def test_fused_engine_goss_and_rf():
    """GOSS sampling and random-forest mode run through the fused engine
    (host-driven sampling feeding the fused grower)."""
    rng = np.random.RandomState(5)
    X = rng.randn(3000, 6).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    from sklearn.metrics import roc_auc_score
    for boosting, extra in (("goss", {}),
                            ("rf", {"bagging_freq": 1,
                                    "bagging_fraction": 0.7})):
        ds = lgb.Dataset(X, label=y, params={"verbose": -1})
        bst = lgb.train(dict({"objective": "binary", "boosting": boosting,
                              "num_leaves": 15, "verbose": -1,
                              "min_data_in_leaf": 5,
                              "tpu_engine": "fused"}, **extra),
                        ds, num_boost_round=8)
        auc = roc_auc_score(y, bst.predict(X))
        assert auc > 0.9, (boosting, auc)


def test_fused_engine_multiclass_and_weights():
    rng = np.random.RandomState(6)
    X = rng.randn(2000, 5).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.3 * rng.randn(2000, 3), axis=1)
    w = np.abs(rng.randn(2000)).astype(np.float32) + 0.1
    ds = lgb.Dataset(X, label=y, weight=w, params={"verbose": -1})
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 5, "tpu_engine": "fused"},
                    ds, num_boost_round=8)
    acc = (np.argmax(bst.predict(X), 1) == y).mean()
    assert acc > 0.85, acc


def test_fused_engine_quantile_renew():
    """Quantile objective's leaf renewal (host path) composes with the
    fused grower's device row_leaf."""
    rng = np.random.RandomState(7)
    X = rng.rand(2000, 4).astype(np.float32)
    y = (2 * X[:, 0] + rng.standard_exponential(2000) * 0.3) \
        .astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1})
    bst = lgb.train({"objective": "quantile", "alpha": 0.8,
                     "num_leaves": 15, "verbose": -1,
                     "min_data_in_leaf": 10, "tpu_engine": "fused"},
                    ds, num_boost_round=20)
    cover = float((y <= bst.predict(X)).mean())
    assert 0.7 < cover < 0.9, cover


def test_reset_parameter_callback_with_fused_engine():
    """Learning-rate schedules via reset_parameter recompile cleanly
    against the fused engine's cached jits."""
    rng = np.random.RandomState(8)
    X = rng.randn(1500, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1})
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                     "min_data_in_leaf": 5, "tpu_engine": "fused"},
                    ds, num_boost_round=6,
                    callbacks=[lgb.reset_parameter(
                        learning_rate=lambda i: 0.2 * (0.9 ** i))])
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, bst.predict(X)) > 0.95


@pytest.mark.parametrize("params,refused", [
    ({"tpu_engine": "frontier"}, "tpu_engine"),
    ({"tpu_histogram_impl": "pallas"}, "tpu_histogram_impl"),
    ({"tpu_engine": "fused", "tpu_histogram_impl": "onehot"}, None),
], ids=["engine_frontier", "hist_impl_pallas", "fused_onehot"])
def test_a_stale_engine_value_is_refused_by_name(params, refused):
    """A value that went with a deleted engine is an error that names
    the accepted ones, not a silent fall-through to the XLA engine."""
    from lightgbm_tpu.config import Config
    if refused is None:
        cfg = Config(params)
        assert (cfg.tpu_engine, cfg.tpu_histogram_impl) == ("fused", "onehot")
        return
    with pytest.raises(lgb.basic.LightGBMError) as err:
        Config(params)
    assert refused in str(err.value) and "accepted values: auto, " \
        in str(err.value)
