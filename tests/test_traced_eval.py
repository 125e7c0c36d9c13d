"""On-device eval inside the megastep (metric/traced.py + the
boosting/gbdt.py drain-replay path).

Two layers of coverage:

1. Metric parity — every traced metric evaluated directly (jit, no
   training) must match its f64 host implementation within float32
   tolerance, across regression / binary / multiclass / ranking shapes
   with weights and NaN-containing features.

2. Driver semantics — `lgb.train` with eval sets + the built-in
   callback set (early_stopping / log_evaluation / record_evaluation)
   stays on the megastep, replays callbacks at drain, and the
   early-stopped model is BIT-IDENTICAL to the synchronous driver's
   (identical params; the sync run is evicted by an extra opaque user
   callback, which is exactly the documented eviction rule).
"""
import json
import types

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import callback as cbm
from lightgbm_tpu.config import Config
from lightgbm_tpu.metric import create_metric
from lightgbm_tpu.metric.traced import build_traced_metric


def _metadata(label, weight=None, query_boundaries=None):
    return types.SimpleNamespace(label=np.asarray(label),
                                 weight=(np.asarray(weight)
                                         if weight is not None else None),
                                 query_boundaries=query_boundaries,
                                 query_row_map=None)


def _host_vs_traced(name, label, score, objective=None, weight=None,
                    query_boundaries=None, params=None, rtol=2e-5,
                    atol=1e-6):
    cfg = Config(dict(params or {}, verbose=-1))
    m = create_metric(name, cfg)
    m.init(_metadata(label, weight, query_boundaries), len(label))
    host = m.eval(np.asarray(score, np.float64), objective)
    tm = build_traced_metric(m, objective)
    assert tm is not None, f"{name} has no traced form"
    assert list(tm.names) == list(m.names)
    import jax
    traced = jax.jit(tm.fn)(np.asarray(score, np.float32), tm.ops)
    traced = [float(v) for v in jax.device_get(traced)]
    np.testing.assert_allclose(traced, host, rtol=rtol, atol=atol)
    return traced


def _binary_objective():
    from lightgbm_tpu.objective import create_objective
    cfg = Config({"objective": "binary", "verbose": -1})
    obj = create_objective(cfg)
    return obj, cfg


RNG = np.random.RandomState(7)
N = 500


# ---------------------------------------------------------------------------
# 1. metric parity: traced vs host, one metric at a time
# ---------------------------------------------------------------------------
def test_regression_metrics_parity():
    label = RNG.randn(N).astype(np.float32) * 3
    weight = RNG.rand(N).astype(np.float32) + 0.1
    score = (label + RNG.randn(N) * 0.5).astype(np.float32)[None, :]
    for name in ("l2", "rmse", "l1", "quantile", "huber", "mape"):
        _host_vs_traced(name, label, score, weight=weight)
        _host_vs_traced(name, label, score)   # unweighted


def test_binary_metrics_parity():
    obj, cfg = _binary_objective()
    label = (RNG.rand(N) > 0.4).astype(np.float32)
    obj.init(_metadata(label), N)
    weight = RNG.rand(N).astype(np.float32) + 0.1
    score = RNG.randn(1, N).astype(np.float32) * 2
    for name in ("binary_logloss", "binary_error", "auc"):
        _host_vs_traced(name, label, score, objective=obj, weight=weight)
        _host_vs_traced(name, label, score, objective=obj)


def test_auc_tie_handling_parity():
    label = (RNG.rand(N) > 0.5).astype(np.float32)
    score = RNG.randint(0, 5, N).astype(np.float32)[None, :]  # heavy ties
    _host_vs_traced("auc", label, score)


def _auc_case(n, ties, weighted, labels="mixed", seed=0):
    rng = np.random.default_rng([n, seed])
    score = {"distinct": lambda: rng.permutation(n) * 0.5 - n / 4,
             "heavy": lambda: rng.integers(0, 5, n),
             "equal": lambda: np.full(n, 0.25),
             # -0.0 and 0.0 are one group, as != says
             "signed_zero": lambda: rng.choice([-0.0, 0.0, 1.0, -1.0], n),
             }[ties]().astype(np.float32)
    if labels == "mixed":
        label = (rng.random(n) < 0.4).astype(np.float32)
        label[:2] = [0.0, 1.0]
    elif labels in ("top", "bottom"):
        # the positives all at one end of the score order
        ranked = np.argsort(-score, kind="stable")
        label = np.zeros(n, np.float32)
        half = ranked[:n // 2] if labels == "top" else ranked[n // 2:]
        label[half] = 1.0
    else:
        label = np.full(n, float(labels == "ones"), np.float32)
    weight = (rng.uniform(0.1, 2.0, n).astype(np.float32)
              if weighted else None)
    return label, score, weight


_AUC_CASES = (
    [(n, t, w, "mixed") for n in (2, 2047, 2049, 100003)
     for t in ("distinct", "heavy", "equal", "signed_zero")
     for w in (False, True)]
    + [(2049, "distinct", w, one) for w in (False, True)
       for one in ("zeros", "ones", "top", "bottom")])


@pytest.mark.parametrize("n,ties,weighted,labels", _AUC_CASES)
def test_auc_jnp_matches_host(n, ties, weighted, labels):
    """The traced AUC (one sort, blocked scans) against the float64 host
    AUC: lengths not a multiple of the scan block, ties of every kind,
    one class (1.0), positives all at one end (1.0 / 0.0)."""
    import jax
    from lightgbm_tpu.metric import _weighted_auc, _weighted_auc_jnp
    label, score, weight = _auc_case(n, ties, weighted, labels)
    host = _weighted_auc(label, score.astype(np.float64), weight)
    traced = float(jax.jit(_weighted_auc_jnp)(label, score, weight))
    assert abs(traced - host) <= 1e-6, (traced, host)
    if labels in ("zeros", "ones", "top"):
        assert host == 1.0
    if labels == "bottom" and ties == "distinct":
        assert host == 0.0


@pytest.mark.parametrize("weighted", [False, True])
def test_auc_jnp_lowers_without_scatter_or_gather(weighted):
    """The traced AUC is a sort, scans and one reduction: a scatter or a
    gather brought back into it (they cost 5-12 ms each at 1.33M rows on
    the chip, PERF.md section 6) fails here."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.metric import _weighted_auc_jnp
    x = jax.ShapeDtypeStruct((100003,), jnp.float32)
    text = jax.jit(_weighted_auc_jnp).lower(
        x, x, x if weighted else None).as_text()
    assert "sort" in text
    assert "scatter" not in text and "gather" not in text


def test_multiclass_metrics_parity():
    from lightgbm_tpu.objective import create_objective
    nc = 4
    cfg = Config({"objective": "multiclass", "num_class": nc,
                  "verbose": -1})
    obj = create_objective(cfg)
    label = RNG.randint(0, nc, N).astype(np.float32)
    obj.init(_metadata(label), N)
    weight = RNG.rand(N).astype(np.float32) + 0.1
    score = RNG.randn(nc, N).astype(np.float32)
    for name in ("multi_logloss", "multi_error"):
        _host_vs_traced(name, label, score, objective=obj, weight=weight,
                        params={"num_class": nc})
    _host_vs_traced("multi_error", label, score, objective=obj,
                    params={"num_class": nc, "multi_error_top_k": 2})


def test_ndcg_parity():
    n_q = 40
    sizes = RNG.randint(1, 30, n_q)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(qb[-1])
    label = RNG.randint(0, 4, n).astype(np.float32)
    # one all-zero-label query exercises the degenerate counts-as-1 path
    label[qb[0]:qb[1]] = 0.0
    score = RNG.randn(1, n).astype(np.float32)
    _host_vs_traced("ndcg", label, score, query_boundaries=qb,
                    params={"eval_at": [1, 3, 5]})


def test_untraceable_metric_rejected():
    cfg = Config({"verbose": -1})
    m = create_metric("gamma", cfg)   # no loss_jnp: host-only
    m.init(_metadata(np.ones(8, np.float32) + 1.0), 8)
    assert build_traced_metric(m, None) is None


# ---------------------------------------------------------------------------
# 2. driver semantics on the megastep
# ---------------------------------------------------------------------------
def _data(n=1200, f=8, seed=3, nan_frac=0.0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    if nan_frac > 0:
        mask = rng.rand(n, f) < nan_frac
        mask[:, :2] &= rng.rand(n, 2) < 0.5   # keep signal columns usable
        X[mask] = np.nan
    return X, y


FUSED = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
         "verbose": -1, "min_data_in_leaf": 5, "tpu_engine": "fused",
         "tpu_megastep": True, "metric": ["binary_logloss", "auc"]}


def _train_pair(params, rounds, callbacks_extra=(), n_valid=2,
                nan_frac=0.0, seed=3):
    """(megastep booster, sync booster, megastep record, sync record):
    identical params both runs; the sync run carries one extra opaque
    callback, which is the documented megastep eviction and keeps the
    serialized parameter block byte-identical."""
    X, y = _data(seed=seed, nan_frac=nan_frac)
    valids = [_data(seed=11 + i, nan_frac=nan_frac) for i in range(n_valid)]

    def run(evict):
        d = lgb.Dataset(X, label=y)
        rec = {}
        cbs = [cbm.record_evaluation(rec)] + list(callbacks_extra)
        if evict:
            cbs.append(lambda env: None)    # opaque user callback
        b = lgb.train(dict(params), d, num_boost_round=rounds,
                      valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)
                                  for Xv, yv in valids],
                      callbacks=cbs)
        return b, rec
    b1, r1 = run(False)
    b2, r2 = run(True)
    return b1, b2, r1, r2


def test_early_stopped_model_bit_identical_to_sync():
    params = dict(FUSED, early_stopping_round=5)
    b1, b2, r1, r2 = _train_pair(params, rounds=40)
    assert b1.best_iteration == b2.best_iteration > 0
    assert b1.num_trees() == b2.num_trees() < 40
    # the acceptance contract: serialized models (full AND
    # best-iteration-sliced) are byte-identical
    assert b1.model_to_string(num_iteration=-1) == \
        b2.model_to_string(num_iteration=-1)
    assert b1.model_to_string() == b2.model_to_string()
    # recorded curves: same length, f32-tolerance equal values
    for ds in r2:
        for m in r2[ds]:
            a, b = np.asarray(r1[ds][m]), np.asarray(r2[ds][m])
            assert len(a) == len(b)
            np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-7)


def test_first_metric_only_multi_eval_set():
    params = dict(FUSED, early_stopping_round=4, first_metric_only=True)
    b1, b2, r1, r2 = _train_pair(params, rounds=40)
    assert b1.best_iteration == b2.best_iteration > 0
    assert b1.model_to_string(num_iteration=-1) == \
        b2.model_to_string(num_iteration=-1)


def test_nan_features_megastep_eval():
    params = dict(FUSED, early_stopping_round=5)
    b1, b2, r1, r2 = _train_pair(params, rounds=30, nan_frac=0.25)
    assert b1.best_iteration == b2.best_iteration
    assert b1.model_to_string(num_iteration=-1) == \
        b2.model_to_string(num_iteration=-1)


def test_multiclass_megastep_eval():
    rng = np.random.RandomState(5)
    n, f, nc = 900, 6, 3
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] * 3).astype(np.int32).clip(0, nc - 1).astype(np.float32)
    Xv = rng.rand(400, f).astype(np.float32)
    yv = (Xv[:, 0] * 3).astype(np.int32).clip(0, nc - 1) \
        .astype(np.float32)
    params = {"objective": "multiclass", "num_class": nc,
              "num_leaves": 7, "verbose": -1, "min_data_in_leaf": 5,
              "tpu_engine": "fused", "tpu_megastep": True,
              "metric": ["multi_logloss", "multi_error"],
              "early_stopping_round": 4}

    def run(evict):
        d = lgb.Dataset(X, label=y)
        rec = {}
        cbs = [cbm.record_evaluation(rec)]
        if evict:
            cbs.append(lambda env: None)
        b = lgb.train(dict(params), d, num_boost_round=12,
                      valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)],
                      callbacks=cbs)
        return b, rec
    b1, r1 = run(False)
    b2, r2 = run(True)
    assert b1.best_iteration == b2.best_iteration
    assert b1.model_to_string(num_iteration=-1) == \
        b2.model_to_string(num_iteration=-1)
    np.testing.assert_allclose(r1["valid_0"]["multi_logloss"],
                               r2["valid_0"]["multi_logloss"],
                               rtol=3e-5, atol=3e-7)


def test_megastep_stays_on_with_builtin_callbacks(tmp_path):
    # the headline eligibility claim: eval sets + early_stopping +
    # log_evaluation + record_evaluation keep the megastep (dispatch
    # budget far under the sync driver's >= 3/iter)
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    Xv, yv = _data(seed=11)
    Xv2, yv2 = _data(seed=12)
    d = lgb.Dataset(X, label=y)
    rec = {}
    b = lgb.train(dict(FUSED, early_stopping_round=25,
                       telemetry_out=str(out)),
                  d, num_boost_round=10,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=d),
                              lgb.Dataset(Xv2, label=yv2, reference=d)],
                  callbacks=[cbm.log_evaluation(1),
                             cbm.record_evaluation(rec)])
    snap = b.telemetry()
    c = snap["counters"]
    assert c["iterations"] == 10
    assert c["train.dispatches"] / c["iterations"] <= 0.2
    assert len(rec["valid_0"]["binary_logloss"]) == 10
    assert b.best_iteration > 0   # "did not meet" still records best
    recs = [json.loads(line) for line in open(out)]
    evs = {r["event"] for r in recs}
    assert "megastep" in evs and "eval_batch" in evs
    # the run COMPLETED (stopping_rounds never hit): the callback's
    # final-iteration raise must not masquerade as a real early stop
    assert "early_stopping" not in evs
    eb = [r for r in recs if r["event"] == "eval_batch"]
    assert all(not r["stopped"] for r in eb)
    assert eb[0]["slots"] == ["valid_0/binary_logloss", "valid_0/auc",
                              "valid_1/binary_logloss", "valid_1/auc"]
    # one auc_form event per AUC of the plan: which form ran
    forms = [r for r in recs if r["event"] == "auc_form"]
    assert [(r["dataset"], r["rows"], r["weighted"]) for r in forms] == \
        [("valid_0", 1200, False), ("valid_1", 1200, False)]
    assert len(eb[0]["last"]) == 4
    # host-recomputed parity for the final iteration's logged values
    host = dict(
        (f"{ds}/{m}", v) for ds, m, v, _ in
        b.eval_valid())
    for slot, v in zip(eb[-1]["slots"], eb[-1]["last"]):
        np.testing.assert_allclose(v, host[slot], rtol=3e-5, atol=3e-7)


def test_chunk_of_one_flows_through_scan():
    # horizon tails force a length-1 megastep when a consumer is armed
    # (every iteration must flow through the scan for its metric row);
    # the drained [B=1, k, ...] entry must unstack its batch axis, not
    # be mistaken for a pipelined [k, ...] entry
    X, y = _data(n=400)
    Xv, yv = _data(n=300, seed=11)

    def run(evict):
        d = lgb.Dataset(X, label=y)
        rec = {}
        cbs = [cbm.record_evaluation(rec)]
        if evict:
            cbs.append(lambda env: None)
        b = lgb.train(dict(FUSED, tpu_megastep_iters=4), d,
                      num_boost_round=5,
                      valid_sets=[lgb.Dataset(Xv, label=yv,
                                              reference=d)],
                      callbacks=cbs)
        return b, rec
    b1, r1 = run(False)
    b2, r2 = run(True)
    assert b1.num_trees() == 5
    assert len(r1["valid_0"]["binary_logloss"]) == 5
    assert b1.model_to_string(num_iteration=-1) == \
        b2.model_to_string(num_iteration=-1)


def test_megastep_evicted_event_names_feature(tmp_path):
    out = tmp_path / "tel.jsonl"
    X, y = _data(n=600)
    Xv, yv = _data(n=400, seed=11)
    d = lgb.Dataset(X, label=y)
    lgb.train(dict(FUSED, telemetry_out=str(out)), d, num_boost_round=2,
              valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)],
              callbacks=[lambda env: None])
    recs = [json.loads(line) for line in open(out)]
    ev = [r for r in recs if r["event"] == "megastep_evicted"]
    assert ev, recs
    assert ev[0]["feature"].startswith("callback:")


def test_megastep_evicted_event_names_feval(tmp_path):
    out = tmp_path / "tel.jsonl"
    X, y = _data(n=600)
    Xv, yv = _data(n=400, seed=11)
    d = lgb.Dataset(X, label=y)
    lgb.train(dict(FUSED, telemetry_out=str(out)), d, num_boost_round=2,
              valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)],
              feval=lambda preds, ds: ("const", 1.0, True))
    recs = [json.loads(line) for line in open(out)]
    ev = [r for r in recs if r["event"] == "megastep_evicted"]
    assert any(r["feature"] == "feval" for r in ev), recs


def test_snapshots_written_at_drain(tmp_path):
    X, y = _data(n=600)
    Xv, yv = _data(n=400, seed=11)
    base = tmp_path / "model.txt"
    d = lgb.Dataset(X, label=y)
    b = lgb.train(dict(FUSED, snapshot_freq=3,
                       output_model=str(base)),
                  d, num_boost_round=7,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)])
    assert b.num_trees() == 7
    for it in (3, 6):
        snap = tmp_path / f"model.txt.snapshot_iter_{it}"
        assert snap.exists(), f"missing snapshot at iteration {it}"
        bs = lgb.Booster(model_file=str(snap))
        assert bs.num_trees() == it


def test_booster_trainable_after_drain_replay_stop():
    # a drain-replayed early stop must leave the kept booster on the
    # normal one-iteration-per-update contract (the sync early-stop
    # path does); the internal stop latch is cleared at disarm
    X, y = _data(n=400)
    Xv, yv = _data(n=300, seed=11)
    d = lgb.Dataset(X, label=y)
    b = lgb.train(dict(FUSED, early_stopping_round=3,
                       min_sum_hessian_in_leaf=0.1), d,
                  num_boost_round=25,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)],
                  keep_training_booster=True)
    n0 = b.num_trees()
    assert b.best_iteration > 0 and n0 < 25
    b.update()
    assert b.num_trees() == n0 + 1


def test_min_delta_evicts(tmp_path):
    out = tmp_path / "tel.jsonl"
    X, y = _data(n=600)
    Xv, yv = _data(n=400, seed=11)
    d = lgb.Dataset(X, label=y)
    b = lgb.train(dict(FUSED, telemetry_out=str(out)), d,
                  num_boost_round=6,
                  valid_sets=[lgb.Dataset(Xv, label=yv, reference=d)],
                  callbacks=[cbm.early_stopping(30, verbose=False,
                                                min_delta=0.01)])
    assert b.num_trees() == 6
    recs = [json.loads(line) for line in open(out)]
    ev = [r for r in recs if r["event"] == "megastep_evicted"]
    assert any("min_delta" in r["feature"] for r in ev), recs
