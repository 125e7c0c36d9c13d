"""Dispatch-amortized training megastep (boosting/gbdt.py
_train_one_megastep) and the telemetry granularity that keeps the fast
path.

The megastep chains up to tpu_megastep_iters boosting iterations inside
ONE jit via lax.scan over the fused tree-growing step; the scan body is
the same trace as the per-iteration fast step, so the two paths must be
bit-identical. Telemetry at the default `batch` granularity must keep
the fast path (the pre-round-6 behavior evicted any telemetry-on run to
the synchronous driver) and count host dispatches.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=1200, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    return X, y


# tpu_megastep is set EXPLICITLY: off-TPU the fused engine runs in
# interpret mode, where the megastep is opt-in (no dispatch latency to
# amortize — see GBDT._megastep_ok)
FUSED = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
         "verbose": -1, "min_data_in_leaf": 5, "tpu_engine": "fused",
         "tpu_megastep": True}


def _trees_equal(b1, b2):
    assert b1.num_trees() == b2.num_trees()
    for t1, t2 in zip(b1.models, b2.models):
        assert t1.num_leaves == t2.num_leaves
        assert np.array_equal(t1.split_feature, t2.split_feature)
        assert np.array_equal(t1.threshold_bin, t2.threshold_bin)
        assert np.array_equal(t1.leaf_value, t2.leaf_value)


def test_megastep_engages_in_engine_train():
    # 10 rounds on the same data shape as the telemetry test below, so
    # both share ONE compiled megastep(10) program (tier-1 budget)
    X, y = _data()
    b = lgb.train(dict(FUSED), lgb.Dataset(X, label=y),
                  num_boost_round=10)
    g = b._gbdt
    assert g._megastep_fns, "lgb.train did not build a megastep"
    assert 10 in g._megastep_fns         # one fused chunk covered the run
    assert b.num_trees() == 10
    assert not g._megastep_armed         # disarmed after the loop


def test_update_contract_unchanged():
    # the bare Booster.update contract stays one iteration per call —
    # megasteps are consumed only by loops that armed them
    X, y = _data(n=600)
    b = lgb.Booster(params=dict(FUSED), train_set=lgb.Dataset(X, label=y))
    for i in range(3):
        b.update()
        assert b._gbdt.iter == i + 1
    assert not b._gbdt._megastep_fns
    assert b.num_trees() == 3


def test_megastep_bit_identical_to_fast_path():
    X, y = _data()
    b1 = lgb.train(dict(FUSED, tpu_megastep=True),
                   lgb.Dataset(X, label=y), num_boost_round=8)
    b2 = lgb.train(dict(FUSED, tpu_megastep=False),
                   lgb.Dataset(X, label=y), num_boost_round=8)
    _trees_equal(b1, b2)
    # live training scores too, not just the serialized model
    np.testing.assert_array_equal(np.asarray(b1._gbdt.scores),
                                  np.asarray(b2._gbdt.scores))


def test_megastep_early_stop_across_boundary():
    # min_sum_hessian tuned so splits dry up mid-run: the stop fires
    # INSIDE a fused chunk, drain must rewind the tail exactly like the
    # per-iteration pipeline
    X, y = _data(n=400)
    params = dict(FUSED, min_sum_hessian_in_leaf=20.0, learning_rate=0.9)
    b1 = lgb.train(dict(params, tpu_megastep=True),
                   lgb.Dataset(X, label=y), num_boost_round=30)
    b2 = lgb.train(dict(params, tpu_megastep=False),
                   lgb.Dataset(X, label=y), num_boost_round=30)
    b2._gbdt.drain_pending()   # the pipeline detects the stop at drain
    assert b1._gbdt._stopped_early and b2._gbdt._stopped_early
    assert 0 < b1.num_trees() < 30
    _trees_equal(b1, b2)


def test_megastep_valid_and_bagging():
    # valid-score updates ride inside the scan; bagging chunks align to
    # the re-bagging boundary so the LCG stream order is untouched
    X, y = _data()
    Xv, yv = _data(seed=11)
    params = dict(FUSED, bagging_fraction=0.6, bagging_freq=4,
                  bagging_seed=7)

    def run(extra):
        d = lgb.Dataset(X, label=y)
        return lgb.train(dict(params, **extra), d, num_boost_round=10,
                         valid_sets=[lgb.Dataset(Xv, label=yv,
                                                 reference=d)])
    b1 = run({"tpu_megastep": True})
    b2 = run({"tpu_megastep": False})
    _trees_equal(b1, b2)
    np.testing.assert_array_equal(np.asarray(b1._gbdt.valid_scores[0]),
                                  np.asarray(b2._gbdt.valid_scores[0]))
    # bagging forced chunking at the 4-iteration window boundary
    assert 4 in b1._gbdt._megastep_fns


def test_telemetry_batch_keeps_fast_path_and_dispatch_budget(tmp_path):
    # ISSUE 5 acceptance: with telemetry_out set and default granularity
    # the fast path stays on and the megastep path pays < 2 host
    # dispatches per boosting iteration (the sync driver pays >= 3)
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    b = lgb.train(dict(FUSED, telemetry_out=str(out)),
                  lgb.Dataset(X, label=y), num_boost_round=10)
    g = b._gbdt
    assert g._fast_path_ok()
    snap = b.telemetry()
    c = snap["counters"]
    assert c["iterations"] == 10
    assert 0 < c["train.dispatches"] / c["iterations"] < 2.0
    assert c.get("train.drains", 0) >= 1

    recs = [json.loads(line) for line in open(out)]
    for r in recs:
        assert isinstance(r["ts"], float) and isinstance(r["rank"], int)
        assert isinstance(r["event"], str) and r["event"]
    batches = [r for r in recs if r["event"] == "megastep"]
    assert batches, recs
    assert sum(r["kept"] for r in batches) == 10
    for r in batches:
        assert r["iterations"] >= r["kept"] > 0
        assert r["fused_iterations"] >= 0
        assert r["sections"]["batch"] >= 0.0
        assert r["engine"] == "fused"
    summaries = [r for r in recs if r["event"] == "summary"]
    assert summaries and summaries[-1]["counters"]["iterations"] == 10


def test_telemetry_iteration_granularity_keeps_fast_path(tmp_path):
    out = tmp_path / "tel_iter.jsonl"
    X, y = _data(n=800)
    b = lgb.train(dict(FUSED, telemetry_out=str(out),
                       telemetry_granularity="iteration"),
                  lgb.Dataset(X, label=y), num_boost_round=5)
    assert b._gbdt._fast_path_ok()
    recs = [json.loads(line) for line in open(out)]
    iters = [r for r in recs if r["event"] == "iteration"]
    assert [r["iter"] for r in iters] == [0, 1, 2, 3, 4]
    for r in iters:
        assert r["sections"]["fast_iteration"] >= 0.0
        assert r["pipelined"] is True
        assert isinstance(r["num_leaves"], list) and r["num_leaves"]


def test_telemetry_section_granularity_forces_sync(tmp_path):
    out = tmp_path / "tel_sec.jsonl"
    X, y = _data(n=800)
    b = lgb.train(dict(FUSED, telemetry_out=str(out),
                       telemetry_granularity="section"),
                  lgb.Dataset(X, label=y), num_boost_round=3)
    assert not b._gbdt._fast_path_ok()
    recs = [json.loads(line) for line in open(out)]
    iters = [r for r in recs if r["event"] == "iteration"]
    assert len(iters) == 3
    for r in iters:
        assert "histogram_split" in r["sections"]
        assert "score_update" in r["sections"]


def test_trace_out_implies_section_granularity(tmp_path):
    # the Chrome-trace exporter needs synced sections; batch granularity
    # must not silently produce an empty timeline
    X, y = _data(n=600)
    b = lgb.train(dict(FUSED, telemetry_out=str(tmp_path / "t.jsonl"),
                       trace_out=str(tmp_path / "trace.json")),
                  lgb.Dataset(X, label=y), num_boost_round=2)
    assert b._gbdt._tel_granularity() == "section"
    assert not b._gbdt._fast_path_ok()
    assert (tmp_path / "trace.json").exists()


@pytest.mark.parametrize("env_set", [False, True])
def test_compilation_cache_dir_applied(tmp_path, monkeypatch, env_set):
    """The config key places the cache unless JAX_COMPILATION_CACHE_DIR
    already did: then JAX reads the variable and the key yields."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    cache = tmp_path / "xla_cache"
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "from_env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        X, y = _data(n=300)
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "compilation_cache_dir": str(cache)},
                  lgb.Dataset(X, label=y), num_boost_round=2)
        assert jax.config.jax_compilation_cache_dir == \
            (before if env_set else str(cache))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_megastep_disabled_for_unarmed_per_iteration_observers():
    # callbacks observe individual iterations -> engine.train must not
    # arm the megastep; training still works on the per-iteration path
    X, y = _data(n=600)
    seen = []
    cb = lambda env: seen.append(env.iteration)   # noqa: E731
    b = lgb.train(dict(FUSED), lgb.Dataset(X, label=y),
                  num_boost_round=4, callbacks=[cb])
    assert seen == [0, 1, 2, 3]
    assert b.num_trees() == 4
    assert not b._gbdt._megastep_fns
