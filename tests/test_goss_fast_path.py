"""GOSS on the fused fast path (PR 35): the traced sampler against the
paper's Algorithm 2 in numpy (``benchmark/tools/reference_auc_goss.py``),
the row compaction against a gather, the tree grown on the compact matrix
against the tree grown on all rows with zero weights, the three drivers
model for model, and who still evicts, by name."""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import fused_level as fl
from lightgbm_tpu.ops import goss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference_tool():
    path = os.path.join(ROOT, "benchmark", "tools", "reference_auc_goss.py")
    spec = importlib.util.spec_from_file_location("reference_auc_goss", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ the sampler
def _sample_with_sorts(abs_gh, keys, top_k, other_k):
    """What ``goss_sample`` computes, said with sorts."""
    n = abs_gh.shape[0]
    order = np.lexsort((np.arange(n), -abs_gh.astype(np.float64)))
    top = np.zeros(n, bool)
    top[order[:top_k]] = True
    k = np.where(top, -1, keys).astype(np.int64)
    order = np.lexsort((np.arange(n), -k))
    other = np.zeros(n, bool)
    other[order[:other_k]] = True
    return top, other


@pytest.mark.parametrize("n,ties", [(1000, False), (5000, True),
                                    (70_001, True)])
def test_the_traced_sample_is_algorithm_2(n, ties):
    """Exactly top_k rows of the largest |g * h| (the numpy tool's stable
    descending sort), exactly other_k of the others, the multiplier."""
    tool = _reference_tool()
    rng = np.random.default_rng(n)
    g = rng.standard_normal(n).astype(np.float32)
    h = rng.random(n).astype(np.float32)
    if ties:
        g[::5], h[::5] = g[2], h[2]          # a fifth of the rows tie
    plan = goss.goss_plan(n, 0.2, 0.1, 0.1, 3)
    top, other = jax.jit(goss.goss_sample, static_argnums=(3, 4))(
        jnp.abs(jnp.asarray(g) * jnp.asarray(h)), 12, 3, plan.top_k,
        plan.other_k)
    top, other = np.asarray(top), np.asarray(other)
    rows, w = tool.goss_rows(g, h, plan.top_k, plan.other_k,
                             np.random.default_rng(0))
    assert plan.top_k == max(1, int(n * 0.2))
    assert top.sum() == plan.top_k and other.sum() == plan.other_k
    assert np.array_equal(np.flatnonzero(top), rows[w == 1.0])
    assert not (top & other).any()
    mult, inbag = goss.sample_weights(jnp.asarray(top), jnp.asarray(other),
                                      plan.multiply)
    assert np.array_equal(np.asarray(inbag) > 0, top | other)
    assert set(np.unique(w)) == {np.float32(1.0),
                                 np.float32((n - plan.top_k) / plan.other_k)}
    assert np.array_equal(np.unique(np.asarray(mult)[other]),
                          [np.float32(plan.multiply)])
    # and the statement of it with sorts, draw included
    keys = np.asarray(goss.draw_keys(n, 3, 12))
    ref_top, ref_other = _sample_with_sorts(np.abs(g * h), keys,
                                            plan.top_k, plan.other_k)
    assert np.array_equal(top, ref_top) and np.array_equal(other, ref_other)


def test_ties_at_the_threshold_go_to_the_lower_row():
    a = jnp.ones((4096,), jnp.float32)
    top, other = goss.goss_sample(a, 0, 3, 819, 409)
    top, other = np.asarray(top), np.asarray(other)
    assert np.array_equal(np.flatnonzero(top), np.arange(819))
    assert other.sum() == 409 and other[:819].sum() == 0


def test_the_stream_is_the_seed_and_the_iteration():
    a = jnp.asarray(np.random.default_rng(1).random(20_000), jnp.float32)
    draw = lambda seed, it: np.asarray(goss.goss_sample(a, it, seed,
                                                        4000, 2000)[1])
    assert np.array_equal(draw(3, 10), draw(3, 10))
    assert not np.array_equal(draw(3, 10), draw(3, 11))
    assert not np.array_equal(draw(3, 10), draw(4, 10))
    # uniform over the rest: no eighth of the rows is favoured
    share = draw(3, 10).reshape(8, -1).sum(axis=1) / 2000
    assert np.all(np.abs(share - 0.125) < 0.03)


def test_the_plan():
    plan = goss.goss_plan(28_000_000, 0.2, 0.1, 0.1, 3)
    assert (plan.top_k, plan.other_k) == (5_600_000, 2_800_000)
    assert plan.capacity == 8_400_896 and plan.capacity % 2048 == 0
    assert plan.first_iter == 10 and plan.multiply == 8.0
    tiny = goss.goss_plan(7, 0.2, 0.1, 0.3, 3)
    assert (tiny.top_k, tiny.other_k, tiny.first_iter) == (1, 1, 3)


# --------------------------------------------------------- the compaction
MASKS = ["empty", "full", "one_tile", "ragged", "random", "straddle",
         "whole_tile", "odd_capacity", "rows_off_tile"]


def _mask(kind, rng, tile):
    """(rows, mask) of one geometry of the move."""
    n = 9000 if kind == "rows_off_tile" else 16000
    m = np.zeros(n, bool)
    if kind == "full":
        m[:] = True
    elif kind == "one_tile":
        m[600:900] = True
    elif kind == "ragged":
        m[1:n - 1:3] = True
    elif kind == "random":
        m = rng.random(n) < 0.3
    elif kind == "straddle":          # runs across every 128-row boundary
        m[(np.arange(n) % 128 >= 100) | (np.arange(n) % 128 < 29)] = True
    elif kind == "whole_tile":        # one tile all in: two output blocks
        m = rng.random(n) < 0.25
        m[tile:2 * tile] = True
    elif kind == "odd_capacity":      # 5,000 kept: capacity 3 x 2,048
        m[rng.choice(n, 5000, replace=False)] = True
    elif kind == "rows_off_tile":     # 9,000 rows: Rp 10,240, no 4,096
        m = rng.random(n) < 0.6
    return n, m


@pytest.mark.parametrize("tile", [2048, 4096])
@pytest.mark.parametrize("mask", MASKS)
def test_the_compaction_is_a_gather(mask, tile):
    rng = np.random.default_rng(7)
    n, m = _mask(mask, rng, tile)
    Fp = 32
    Rp = -(-n // 2048) * 2048
    bins = rng.integers(0, 64, (Fp, Rp)).astype(np.int8)
    gh = jnp.asarray(rng.standard_normal((8, Rp)), jnp.bfloat16)
    K = int(m.sum())
    cap = max(2048, -(-K // 2048) * 2048)
    if mask == "odd_capacity":
        assert cap == 3 * 2048
    cb, cg = goss.compact_rows(jnp.asarray(bins), gh, jnp.asarray(m),
                               capacity=cap, tile_rows=tile, interpret=True)
    idx = np.flatnonzero(m)
    assert cb.shape == (Fp, cap) and cg.shape == (8, cap)
    assert np.array_equal(np.asarray(cb)[:, :K], bins[:, idx])
    assert np.array_equal(np.asarray(cg.astype(jnp.float32))[:, :K],
                          np.asarray(gh.astype(jnp.float32))[:, idx])
    assert not np.asarray(cb)[:, K:].any()
    assert not np.asarray(cg.astype(jnp.float32))[:, K:].any()


def test_the_compaction_at_the_widest_tile():
    """The tile the Higgs cell's rows take (COMPACT_TILE_MAX), over two
    tiles, the second wholly in the bag."""
    rng = np.random.default_rng(9)
    C = goss.COMPACT_TILE_MAX
    bins = rng.integers(0, 64, (32, 2 * C)).astype(np.int8)
    gh = jnp.asarray(rng.standard_normal((8, 2 * C)), jnp.bfloat16)
    m = rng.random(2 * C) < 0.3
    m[C:] = True
    K = int(m.sum())
    cap = -(-K // 2048) * 2048
    cb, cg = goss.compact_rows(jnp.asarray(bins), gh, jnp.asarray(m),
                               capacity=cap, interpret=True)
    idx = np.flatnonzero(m)
    assert np.array_equal(np.asarray(cb)[:, :K], bins[:, idx])
    assert np.array_equal(np.asarray(cg.astype(jnp.float32))[:, :K],
                          np.asarray(gh.astype(jnp.float32))[:, idx])
    assert not np.asarray(cb)[:, K:].any()


def test_the_compaction_tile_fits_the_rows():
    """The tile is a power of two that divides the rows, from the shapes
    alone: the Higgs cell's rows take the widest, rows that are a multiple
    of 2,048 alone take 2,048, and a requested tile is halved to fit."""
    assert goss.compact_tile(32, 1, 8, 28_000_256) == goss.COMPACT_TILE_MAX
    assert goss.compact_tile(32, 1, 8, 10240) == 2048
    assert goss.compact_tile(8, 2, 8, 16384) == goss.COMPACT_TILE_MAX
    assert goss.compact_tile(32, 1, 8, 10240, 4096) == 2048
    for fp, item in ((32, 1), (8, 2), (512, 1), (2000, 2)):
        c = goss.compact_tile(fp, item, 8, 1 << 20)
        assert c & (c - 1) == 0 and 256 <= c <= goss.COMPACT_TILE_MAX


def test_the_compaction_keeps_int16_bins():
    rng = np.random.default_rng(8)
    bins = rng.integers(0, 256, (8, 4096)).astype(np.int16)
    m = rng.random(4000) < 0.4
    cb, _ = goss.compact_rows(jnp.asarray(bins),
                              jnp.zeros((8, 4096), jnp.bfloat16),
                              jnp.asarray(m), capacity=2048, interpret=True)
    assert cb.dtype == jnp.int16
    assert np.array_equal(np.asarray(cb)[:, :m.sum()],
                          bins[:, np.flatnonzero(m)])


# ------------------------------------------- compact grower = weighted one
def _binned(n=6000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return X, y


def test_growing_on_the_compact_matrix_is_growing_with_zero_weights():
    """Same splits, histograms equal up to float32 regrouping, and the
    replayed leaves of ALL rows are the weighted grower's."""
    from lightgbm_tpu.models.frontier2 import (grow_tree_fused,
                                               replay_route_log)
    X, y = _binned()
    b = lgb.Booster({"objective": "binary", "num_leaves": 15, "max_bin": 63,
                     "min_data_in_leaf": 5, "tpu_engine": "fused",
                     "verbose": -1}, lgb.Dataset(X, label=y))
    g = b._gbdt
    n, Rp = g.num_data, g.fused_Rp
    rng = np.random.default_rng(1)
    grad = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hess = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    plan = goss.goss_plan(n, 0.2, 0.1, 0.1, 3)
    top, other = goss.goss_sample(jnp.abs(grad * hess), 10, 3, plan.top_k,
                                  plan.other_k)
    mult, inbag = goss.sample_weights(top, other, plan.multiply)
    pad = lambda v: jnp.pad(v, (0, Rp - n))
    gh_T = fl.pack_gh(pad(grad * mult), pad(hess * mult), pad(inbag),
                      g.fused_nch)
    fm = jnp.ones((g.fused_f_oh,), bool).at[X.shape[1]:].set(False)
    kw = dict(nch=g.fused_nch, interpret=True, route_log=True)
    grow = lambda bins_T, gh, rows: grow_tree_fused(
        bins_T, gh, g.fused_meta, fm, g.params, 15, g.fused_Bp,
        g.fused_f_oh, num_rows=rows, **kw)
    t_w, leaf_w, _ = grow(g.fused_bins_T, gh_T, n)
    cb, cg = goss.compact_rows(g.fused_bins_T, gh_T, inbag > 0,
                               capacity=plan.capacity, interpret=True)
    t_c, _, log = grow(cb, cg, plan.bag_rows)
    assert int(t_c.num_leaves) == int(t_w.num_leaves) == 15
    for name in ("split_feature", "threshold_bin", "left_child",
                 "right_child", "default_left"):
        assert np.array_equal(np.asarray(getattr(t_c, name)),
                              np.asarray(getattr(t_w, name))), name
    assert np.array_equal(np.asarray(t_c.leaf_count),
                          np.asarray(t_w.leaf_count))
    np.testing.assert_allclose(np.asarray(t_c.leaf_value),
                               np.asarray(t_w.leaf_value), rtol=2e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(t_c.internal_weight),
                               np.asarray(t_w.internal_weight), rtol=2e-5)
    leaf_all = replay_route_log(g.fused_bins_T, log, n,
                                num_bins=g.fused_Bp, f_oh=g.fused_f_oh,
                                interpret=True)[0]
    assert np.array_equal(np.asarray(leaf_all)[:n], np.asarray(leaf_w)[:n])
    # the root histogram of the two matrices, channel by channel
    tbl = fl.root_route_tables(g.fused_Bp, g.fused_f_oh * g.fused_Bp,
                               g.fused_Bp, True, 8)[1]
    root = lambda bins_T, gh, rows: fl.level_pass(
        bins_T, jnp.where(jnp.arange(bins_T.shape[1])[None] < rows, 0, -1)
        .astype(jnp.int32), gh, None, tbl, num_slots=8, num_bins=g.fused_Bp,
        f_oh=g.fused_f_oh, nch=g.fused_nch, interpret=True)[0]
    h_w, h_c = root(g.fused_bins_T, gh_T, n), root(cb, cg, plan.bag_rows)
    np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_w), rtol=1e-5,
                               atol=1e-4)


# ------------------------------------------------------ the three drivers
GOSS = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
        "learning_rate": 0.25, "max_bin": 63, "min_data_in_leaf": 5,
        "tpu_engine": "fused", "verbose": -1, "metric": "auc"}


def _train(extra, rounds=8, valid=True, tel=None, data=None, **more):
    X, y = data or _binned()
    p = dict(GOSS, **extra)
    if tel is not None:
        p["telemetry_out"] = str(tel)
    ds = lgb.Dataset(X, label=y)
    sets = [lgb.Dataset(X[:1500] + 0.01, label=y[:1500], reference=ds)] \
        if valid else []
    curve = {}
    b = lgb.train(p, ds, num_boost_round=rounds, valid_sets=sets,
                  callbacks=[lgb.record_evaluation(curve)] if valid else [],
                  **more)
    return b, curve


def _same_models(b1, b2, exact=True):
    assert b1.num_trees() == b2.num_trees()
    for t1, t2 in zip(b1.models, b2.models):
        assert np.array_equal(t1.split_feature, t2.split_feature)
        assert np.array_equal(t1.threshold_bin, t2.threshold_bin)
        assert np.array_equal(t1.internal_count, t2.internal_count)
        if exact:
            assert np.array_equal(t1.leaf_value, t2.leaf_value)
        else:
            np.testing.assert_allclose(t1.leaf_value, t2.leaf_value,
                                       rtol=1e-5, atol=1e-7)


@pytest.fixture(scope="module")
def mega(tmp_path_factory):
    tel = tmp_path_factory.mktemp("goss") / "mega.jsonl"
    b, curve = _train({"tpu_megastep": True, "tpu_megastep_iters": 4},
                      tel=tel)
    events = [json.loads(line) for line in open(tel)]
    return b, curve, events


def test_goss_rides_the_megastep(mega):
    b, curve, events = mega
    assert not [e for e in events
                if e.get("event") in ("megastep_evicted", "degrade")]
    chunks = [e for e in events if e.get("event") == "megastep"]
    # sampling starts at iteration 4: a chunk ends there
    assert [(e["iter"], e["iterations"]) for e in chunks] \
        == [(0, 4), (4, 4)]
    c = b.telemetry()["counters"]
    assert c["train.dispatches"] == 2
    n = b._gbdt.num_data
    plan = goss.goss_plan(n, 0.2, 0.1, 0.25, 3)
    roots = [int(t.internal_count[0]) for t in b.models]
    assert roots == [n] * 4 + [plan.bag_rows] * 4
    assert c["goss.iterations"] == 4
    assert c["goss.top_rows"] == 4 * plan.top_k
    assert c["goss.other_rows"] == 4 * plan.other_k
    assert c["goss.bag_rows"] == 4 * plan.bag_rows
    assert c["level.trees"] == 8
    assert c["level.rows_streamed"] \
        == 4 * b._gbdt.fused_Rp + 4 * plan.capacity
    assert [e["rows_streamed"] // e["trees"] for e in chunks] \
        == [b._gbdt.fused_Rp, plan.capacity]
    (layout,) = [e for e in events if e.get("event") == "goss_layout"]
    assert (layout["n"], layout["top_k"], layout["other_k"],
            layout["capacity"], layout["first_sampled_iteration"]) \
        == (n, plan.top_k, plan.other_k, plan.capacity, 4)
    assert layout["compaction"] == "staircase"
    assert layout["tile_rows"] == goss.compact_tile(
        b._gbdt.fused_bins_T.shape[0], b._gbdt.fused_bins_T.dtype.itemsize,
        8, b._gbdt.fused_Rp)
    assert b._gbdt._fast_path_reason() is None
    assert len(curve["valid_0"]["auc"]) == 8


def test_the_scores_of_all_rows_are_the_model_s(mega):
    b, _, _ = mega
    X, _ = _binned()
    np.testing.assert_allclose(np.asarray(b._gbdt.scores)[0],
                               b.predict(X, raw_score=True), rtol=1e-5,
                               atol=1e-6)


def test_megastep_is_the_per_iteration_step(mega):
    fast, curve = _train({"tpu_megastep": False})
    _same_models(mega[0], fast)
    assert curve == mega[1]


def test_the_synchronous_driver_grows_the_same_model(mega):
    sync, _ = _train({"tpu_fast_path": False})
    assert sync._gbdt._fast_path_reason() == "config:tpu_fast_path=false"
    _same_models(mega[0], sync, exact=False)


@pytest.mark.parametrize("objective", ["poisson", "huber"])
def test_gradients_from_the_host_are_sampled_once(objective):
    """An objective without traced gradients hands the step its gradients
    ready made: the step's own draw is then the ONLY one (a second sample
    drawn from gradients that the first had already zeroed and multiplied
    is another model)."""
    p = {"objective": objective, "metric": "l2", "tpu_megastep": False}
    fast, _ = _train(p, valid=False)
    sync, _ = _train(dict(p, tpu_fast_path=False), valid=False)
    g = fast._gbdt
    assert not g.objective.supports_traced_gradients()
    assert g._fast_path_reason() is None
    _same_models(fast, sync, exact=False)
    plan = goss.goss_plan(g.num_data, 0.2, 0.1, 0.25, 3)
    assert [int(t.internal_count[0]) for t in fast.models] \
        == [g.num_data] * 4 + [plan.bag_rows] * 4
    X, _ = _binned()
    np.testing.assert_allclose(np.asarray(g.scores)[0],
                               fast.predict(X, raw_score=True), rtol=1e-5,
                               atol=1e-6)


def test_the_extended_steps_sample_too():
    """With a gain EMA in the carry (``tpu_gain_screening``) the steps'
    extended forms take the iteration and hand back the counts as the
    plain forms do."""
    p = {"tpu_gain_screening": True, "tpu_screening_warmup": 1}
    mega, _ = _train(dict(p, tpu_megastep=True, tpu_megastep_iters=4))
    fast, _ = _train(dict(p, tpu_megastep=False))
    g = mega._gbdt
    assert g.use_screening and g._fast_path_reason() is None
    _same_models(mega, fast)
    assert [int(t.internal_count[0]) for t in fast.models] \
        == [g.num_data] * 4 + [1800] * 4


def test_bare_update_samples_too():
    X, y = _binned()
    b = lgb.Booster(dict(GOSS), lgb.Dataset(X, label=y))
    for _ in range(6):
        b.update()
    n = b._gbdt.num_data
    b._gbdt.drain_pending()
    assert [int(t.internal_count[0]) for t in b._gbdt.models] \
        == [n] * 4 + [int(n * 0.2) + int(n * 0.1)] * 2


def test_multiclass_goss_on_the_fast_path():
    rng = np.random.default_rng(6)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    y = np.argmax(X[:, :3] + 0.3 * rng.standard_normal((3000, 3)), axis=1)
    p = {"objective": "multiclass", "num_class": 3, "learning_rate": 0.5}
    fast, _ = _train(dict(p, tpu_megastep=True, tpu_megastep_iters=2),
                     rounds=4, valid=False, data=(X, y))
    sync, _ = _train(dict(p, tpu_fast_path=False), rounds=4, valid=False,
                     data=(X, y))
    assert fast._gbdt._fast_path_reason() is None
    _same_models(fast, sync, exact=False)
    assert [int(t.internal_count[0]) for t in fast.models] \
        == [3000] * 6 + [900] * 6


# ------------------------------------------------- who still evicts, named
@pytest.mark.parametrize("extra,reason", [
    ({"tree_learner": "data"}, "boosting:goss+tree_learner=data"),
    ({"tree_learner": "voting"}, "boosting:goss+tree_learner=voting"),
    ({"tpu_quantized_grad": 8}, "boosting:goss+tpu_quantized_grad"),
    ({"tpu_adaptive_bins": True}, "boosting:goss+tpu_adaptive_bins"),
    ({"max_bin": 500}, "boosting:goss+wide_bins"),
    ({"boosting": "dart"}, "boosting:dart"),
    ({"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.7},
     "boosting:rf"),
])
def test_the_pairs_that_still_evict_say_so(extra, reason, tmp_path):
    tel = tmp_path / "t.jsonl"
    b, _ = _train(dict({"tpu_megastep": True, "num_leaves": 7}, **extra),
                  rounds=5, valid=False, tel=tel, data=_binned(n=1500))
    assert b._gbdt._fast_path_reason() == reason
    said = [json.loads(line) for line in open(tel)]
    if reason != "boosting:rf":     # (RF has a train_one_iter of its own)
        assert reason in [e.get("feature") for e in said
                          if e.get("event") == "megastep_evicted"]
    assert b.num_trees() == 5


def test_efb_evicts_goss_by_name():
    rng = np.random.default_rng(2)
    n = 3000
    X = np.zeros((n, 12), np.float32)
    hot = rng.integers(0, 10, n)
    X[np.arange(n), hot] = rng.random(n) + 0.5      # ten exclusive columns
    X[:, 10:] = rng.standard_normal((n, 2))
    y = (hot % 2 + X[:, 10] > 0.5).astype(np.float32)
    b, _ = _train({"tpu_megastep": True}, rounds=6, valid=False,
                  data=(X, y))
    if not b._gbdt.fused_bundle_cols:
        pytest.skip("the dataset was not bundled")
    assert b._gbdt._fast_path_reason() == "boosting:goss+efb"


def test_the_checkpoint_holds_the_stream_not_mt19937():
    X, y = _binned(n=2000)
    b = lgb.Booster(dict(GOSS), lgb.Dataset(X, label=y))
    payload, arrays = b._gbdt._capture_boosting_extra()
    assert payload == {"goss_stream": {"kind": "counter_hash_v1",
                                       "seed": 3}}
    assert arrays == {}
    assert not hasattr(b._gbdt, "bag_rng")
