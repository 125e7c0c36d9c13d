"""Validation rows routed with the training kernels (the `lgbm.valid_apply`
phase of the fast paths): `grow_tree_fused(route_log=True)` hands back the
per-level route tables it routed the training rows with, and
`replay_route_log` runs them over any matrix in the same layout, so a
validation row finds its leaf through `route_pass` + `table_lookup`
instead of the node-by-node gather walk (ops/predict.route_rows_to_leaves).

The two paths must give the same bits, not similar ones; the gather walk
is forced here by patching `GBDT._valid_route_reason`, the one place the
program decides (CPU, interpret-mode kernels)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.models.frontier2 import grow_tree_fused, replay_route_log
from lightgbm_tpu.models.learner import FeatureMeta
from lightgbm_tpu.ops.fused_level import feature_layout, pack_gh
from lightgbm_tpu.ops.layout import packed_feature_layout
from lightgbm_tpu.ops.split import SplitParams

from test_phase_scopes import _full_names, _scoped_ops, phase_of


# ---------------------------------------------------------------- (a)
R, B = 1500, 32
NUM_BIN = np.array([32, 32, 9, 9, 32, 5], np.int32)


def _bins():
    rng = np.random.RandomState(3)
    return np.stack([rng.randint(0, nb, R) for nb in NUM_BIN], 1) \
        .astype(np.int8), rng


def _grow_and_replay(signal: str, bins_form: bool = False):
    """(tree, the grower's own row_leaf, the replayed leaves, the log) of
    one tree grown on ``signal``; every case shares one set of static
    arguments but ``has_cat`` (off for ``bins_form``: no column is
    categorical then and the kernels trace no membership test), so the
    grower compiles twice in this file. Both take the bins form of the
    routing since PR 34 (the log holds no ``W``: a categorical split
    rides the slot table as its bin set)."""
    bins, rng = _bins()
    y = {"numeric": (bins[:, 0] > 12) + 0.5 * (bins[:, 1] > 20)
         + 0.3 * (bins[:, 4] > 7),
         "categorical": 1.0 * np.isin(bins[:, 2], (1, 4, 7))
         + 0.5 * (bins[:, 0] > 12),
         "one_split": 1.0 * (bins[:, 0] > 12)}[signal] + 0.05 * rng.randn(R)
    F = len(NUM_BIN)
    F_oh, Bp = feature_layout(F, B)
    Rp, Fp = 2048, max(F_oh, 8)
    bins_T = np.zeros((Fp, Rp), np.int8)
    bins_T[:F, :R] = bins.T
    pad = lambda v: jnp.asarray(np.pad(v.astype(np.float32), (0, Rp - R)))
    gh_T = pack_gh(pad(-(y - y.mean())), pad(np.ones(R)), pad(np.ones(R)),
                   5)
    nb = np.zeros(F_oh, np.int32)
    nb[:F] = NUM_BIN
    is_cat = np.zeros(F_oh, bool)
    is_cat[2] = not bins_form
    z = jnp.zeros(F_oh, jnp.int32)
    meta = FeatureMeta(jnp.asarray(nb), z, z, z, jnp.asarray(is_cat))
    kw = dict(nch=5, extra_levels=1, interpret=True, has_cat=not bins_form,
              num_rows=R, route_log=True)
    # (the gain floor is what lets a tree run dry before the schedule ends)
    args = (jnp.asarray(bins_T), gh_T, meta, jnp.asarray(np.arange(F_oh) < F),
            SplitParams(min_data_in_leaf=5, min_gain_to_split=2.0,
                        cat_smooth=1.0, min_data_per_group=5),
            4, B, F_oh)
    tree, row_leaf, log = grow_tree_fused(*args, **kw)
    assert log[0] is None
    leaves = replay_route_log(args[0], log, R, num_bins=Bp, f_oh=F_oh,
                              interpret=True, has_cat=not bins_form)
    return jax.device_get(tree), np.asarray(row_leaf), \
        np.asarray(leaves)[0], jax.device_get(log)


@pytest.mark.parametrize("case,signal", [
    ("plain", "numeric"), ("categorical", "categorical"),
    ("stops_early", "one_split"), ("bins_form", "numeric")])
def test_replay_over_training_matrix_is_row_leaf(case, signal):
    tree, row_leaf, leaves, (log_W, log_tbl) = _grow_and_replay(
        signal, bins_form=case == "bins_form")
    nl = int(tree.num_leaves)
    assert np.array_equal(leaves, row_leaf)     # padding rows: -1 in both
    assert set(np.unique(leaves[:R])) == set(range(nl))
    live = (log_tbl[:, :, 0] >= 0).any(axis=1)
    # one active slot per split, the levels in the grower's order
    assert (log_tbl[:, :, 0] >= 0).sum() == nl - 1
    assert live[0] and not live[int(live.sum()):].any()
    cat_splits = tree.cat_flag[:nl - 1].sum()
    if case == "categorical":
        assert cat_splits >= 1 and nl > 2
    elif case == "stops_early":
        # the schedule has 3 levels; the frontier ran dry after the first
        assert nl == 2 and live.sum() == 1 and len(live) == 3
    else:
        assert cat_splits == 0 and nl == 4


def test_replay_in_the_adaptive_packed_layout():
    """The packed layout permutes the matrix's rows into width classes
    and re-indexes the tables' columns; a hand-made two-level log (the
    grower's packed path does not run on this CPU backend) must route
    like the splits it was made from."""
    from lightgbm_tpu.ops.fused_level import (build_route_table,
                                              pack_route_table)
    bins, _ = _bins()
    F = len(NUM_BIN)
    F_oh, Bp = feature_layout(F, B)
    pk = packed_feature_layout(NUM_BIN, B, f_oh=F_oh)
    order = np.asarray(pk.feat_order)
    assert not np.array_equal(order, np.arange(len(order)))
    bins_T = np.zeros((max(F_oh, 8), 2048), np.int8)
    bins_T[:F, :R] = bins[:, order].T
    nb = jnp.asarray(np.pad(NUM_BIN, (0, F_oh - F)))
    z = jnp.zeros(F_oh, jnp.int32)
    # (leaf, feature, threshold, new right leaf) per level
    levels = [[(0, 0, 12, 1)], [(0, 5, 1, 2), (1, 3, 4, 3)]]
    want = np.zeros(R, np.int32)
    Ws, tbls = [], []
    for splits in levels:
        feat = np.full(8, -1, np.int32)
        thr = np.zeros(8, np.int32)
        tbl = np.zeros((8, 128), np.int32)
        tbl[:, 0] = -2
        before = want.copy()
        for k, (leaf, f, t, new) in enumerate(splits):
            feat[k], thr[k] = f, t
            tbl[k, :2] = leaf, new - leaf
            want[(before == leaf) & (bins[:, f] > t)] = new
        W = build_route_table(jnp.asarray(feat), jnp.asarray(thr),
                              jnp.zeros(8, bool), nb, z, z, 8, F_oh, Bp)
        Ws.append(pack_route_table(W, pk))
        tbls.append(jnp.asarray(tbl))
    leaves = replay_route_log(jnp.asarray(bins_T),
                              (jnp.stack(Ws), jnp.stack(tbls)), R,
                              num_bins=Bp, f_oh=F_oh, interpret=True,
                              packed=pk)
    assert np.array_equal(np.asarray(leaves)[0, :R], want)
    assert (np.asarray(leaves)[0, R:] == -1).all()
    assert len(np.unique(want)) == 4


# ---------------------------------------------------------------- (b)
def _multiclass_data():
    rng = np.random.RandomState(11)
    n = 1300
    X = rng.randn(n, 6).astype(np.float32)
    X[rng.rand(n, 6) < 0.06] = np.nan            # missing bins
    z = np.nan_to_num(X)
    y = (z[:, 0] + 0.5 * z[:, 1] > 0.2).astype(np.float32)
    y[[5, 900, 1200]] = 2       # a class too rare to be worth a split
    noisy = np.where(rng.rand(n) < 0.45, rng.randint(0, 2, n), y)
    return X, y, noisy.astype(np.float32)


# the gain floor leaves the rare class's tree without a split in every
# iteration while its siblings grow; on the noisy set the loss rises from
# the second iteration on, so early_stopping(2) latches at the third of a
# chunk of four and the fourth runs frozen
MULTI = {"objective": "multiclass", "num_class": 3, "num_leaves": 4,
         "max_bin": 31, "min_data_in_leaf": 5, "learning_rate": 0.5,
         "verbose": -1, "tpu_engine": "fused", "tpu_megastep": True,
         "tpu_megastep_iters": 4, "tpu_extra_levels": 1,
         "metric": "multi_logloss", "min_gain_to_split": 20.0}


def _train_multi(monkeypatch, gather: bool):
    if gather:
        monkeypatch.setattr(GBDT, "_valid_route_reason",
                            lambda self, vi: "test:forced_gather")
    seen = {}
    make = GBDT._make_megastep

    def recording(self, chunk):
        fn = make(self, chunk)

        def call(*args):
            seen.setdefault("fn", fn)
            seen.setdefault("avals", jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return fn(*args)
        return call
    monkeypatch.setattr(GBDT, "_make_megastep", recording)
    X, y, yv2 = _multiclass_data()
    ds = lgb.Dataset(X[:800], label=y[:800])
    v1 = lgb.Dataset(X[800:1100], label=y[800:1100], reference=ds)
    v2 = lgb.Dataset(X[1000:], label=yv2[1000:], reference=ds)
    rec = {}
    bst = lgb.train(MULTI, ds, num_boost_round=8, valid_sets=[v1, v2],
                    valid_names=["clean", "noisy"],
                    callbacks=[lgb.record_evaluation(rec),
                               lgb.early_stopping(2, verbose=False)])
    g = bst._gbdt
    monkeypatch.undo()
    return {"bst": bst, "rec": rec, "seen": seen,
            "vscores": [np.asarray(v) for v in g.valid_scores],
            "routes": [g._valid_route(vi)[1] for vi in range(2)],
            "model": bst.model_to_string()}


@pytest.fixture(scope="module")
def multi_runs():
    with pytest.MonkeyPatch.context() as mp:
        kernel = _train_multi(mp, gather=False)
    with pytest.MonkeyPatch.context() as mp:
        gather = _train_multi(mp, gather=True)
    return kernel, gather


def test_the_two_runs_took_the_two_paths(multi_runs):
    kernel, gather = multi_runs
    assert kernel["routes"] == [None, None]
    assert gather["routes"] == ["test:forced_gather"] * 2


@pytest.mark.parametrize("vi", [0, 1], ids=["clean", "noisy"])
def test_megastep_valid_scores_bit_identical(multi_runs, vi):
    kernel, gather = multi_runs
    assert kernel["vscores"][vi].shape[0] == 3
    assert np.array_equal(kernel["vscores"][vi], gather["vscores"][vi])


def test_eval_history_stop_and_model_identical(multi_runs):
    kernel, gather = multi_runs
    assert kernel["rec"] == gather["rec"]
    assert kernel["bst"].best_iteration == gather["bst"].best_iteration
    assert kernel["model"] == gather["model"]


def test_the_run_had_a_frozen_tail_and_a_dried_tree(multi_runs):
    """What the comparison above is worth: the scan ran past the stop
    latch (an iteration whose carries were frozen), and a class tree
    grew no split while its siblings did."""
    kernel, _ = multi_runs
    bst = kernel["bst"]
    n_eval = len(kernel["rec"]["noisy"]["multi_logloss"])
    assert 0 < bst.best_iteration < n_eval and n_eval % 4 != 0
    leaves = [t["num_leaves"] for t in bst.dump_model()["tree_info"]]
    assert min(leaves) == 1 and max(leaves) > 1
    assert all(np.isfinite(v).all() and np.abs(v).max() > 0
               for v in kernel["vscores"])


# ---------------------------------------------------------------- (e)
def _valid_apply_gathers(run, row_counts):
    """Names of the `gather` operations of the lowered megastep under
    `lgbm.valid_apply` that touch a validation-row-length array."""
    text = run["seen"]["fn"].lower(*run["seen"]["avals"]) \
        .as_text(debug_info=True)
    ops, calls = _scoped_ops(text)
    return [name for func, name, dims in ops
            if name.split("/")[-1] == "gather" and dims & row_counts
            and any(phase_of(full) == "valid_apply"
                    for full in _full_names(func, name, calls))]


def test_no_row_length_gather_left_under_valid_apply(multi_runs):
    kernel, gather = multi_runs
    rows = {300, 2048}          # both sets' rows, and their padded length
    assert _valid_apply_gathers(kernel, rows) == []
    # the control: the detector sees the walk's gathers where they are
    assert len(_valid_apply_gathers(gather, rows)) >= 2 * 3   # sets x trees


# ---------------------------------------------------------------- (c)
BINARY = {"objective": "binary", "num_leaves": 4, "max_bin": 31,
          "min_data_in_leaf": 5, "verbose": -1, "tpu_engine": "fused",
          "tpu_megastep": True, "tpu_megastep_iters": 2,
          "tpu_extra_levels": 1, "metric": "auc"}


def _binary_data(n=1600):
    rng = np.random.RandomState(5)
    X = rng.randn(n, 6).astype(np.float32)
    X[rng.rand(n, 6) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) ** 2 > 0.5)
    return X, y.astype(np.float32)


def _train_dp(monkeypatch, gather, tmp_path):
    if gather:
        monkeypatch.setattr(GBDT, "_valid_route_reason",
                            lambda self, vi: "test:forced_gather")
    X, y = _binary_data()
    ds = lgb.Dataset(X[:1200], label=y[:1200])
    dv = lgb.Dataset(X[1200:], label=y[1200:], reference=ds)
    out = tmp_path / ("gather.jsonl" if gather else "kernel.jsonl")
    rec = {}
    bst = lgb.train(dict(BINARY, tree_learner="data",
                         telemetry_out=str(out)),
                    ds, num_boost_round=4, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(rec)])
    events = [json.loads(line) for line in open(out)]
    return bst, rec, events


def test_data_parallel_same_bits_and_no_recompile(monkeypatch, tmp_path):
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    bst, rec, events = _train_dp(monkeypatch, False, tmp_path)
    g = bst._gbdt
    assert g.parallel_mode == "data" and g.n_shards >= 4
    passenger, reason = g._valid_route(0)
    assert reason is None and passenger.sharding.is_fully_replicated \
        and len(passenger.sharding.device_set) == g.n_shards
    steps = [e for e in events if e.get("event") == "compile_executable"
             and e["signature"].startswith("megastep")]
    chunks = [e for e in events if e.get("event") == "megastep"]
    assert len(chunks) == 2 and len(steps) == 1
    # nothing compiles between the first chunk's end and the second's
    late = [e for e in events if e.get("event") == "compile"
            and chunks[0]["ts"] < e["ts"] < chunks[1]["ts"]
            and e.get("phase") == "backend_compile"]
    assert late == [], late
    assert [e["path"] for e in events if e.get("event") == "valid_route"] \
        == ["kernel"]
    kernel_scores = np.asarray(g.valid_scores[0])
    bst2, rec2, _ = _train_dp(monkeypatch, True, tmp_path)
    assert bst2._gbdt._valid_route(0) == (None, "test:forced_gather")
    assert np.array_equal(kernel_scores,
                          np.asarray(bst2._gbdt.valid_scores[0]))
    assert rec == rec2
    assert bst.dump_model()["tree_info"] == bst2.dump_model()["tree_info"]


@pytest.mark.slow
def test_per_iteration_fast_path_same_bits(monkeypatch):
    """Bare `Booster.update` (no megastep): the pipelined fast step hands
    its trees' logs to `_update_valid_from_trees` too."""
    X, y = _binary_data()
    params = dict(BINARY, tpu_megastep=False)

    def scores():
        ds = lgb.Dataset(X[:1200], label=y[:1200])
        bst = lgb.Booster(params, ds)
        bst.add_valid(lgb.Dataset(X[1200:], label=y[1200:], reference=ds),
                      "v")
        for _ in range(3):
            bst.update()
        g = bst._gbdt
        return np.asarray(g.valid_scores[0]), g._wants_route_log()
    kernel, logged = scores()
    assert logged
    monkeypatch.setattr(GBDT, "_valid_route_reason",
                        lambda self, vi: "test:forced_gather")
    gather, logged = scores()
    assert not logged
    assert np.array_equal(kernel, gather) and np.abs(kernel).max() > 0


# ---------------------------------------------------------------- (d)
def test_dense_efb_mismatch_takes_the_gather_walk_and_says_so(tmp_path):
    rng = np.random.RandomState(2)
    n = 1500
    # mutually exclusive sparse columns: dense EFB bundles them
    X = np.zeros((n, 12), np.float32)
    X[np.arange(n), rng.randint(0, 12, n)] = rng.rand(n) + 0.1
    y = (X[:, 0] + X[:, 3] + X[:, 7] > 0.3).astype(np.float32)
    out = tmp_path / "efb.jsonl"
    ds = lgb.Dataset(X[:1000], label=y[:1000])
    valid = [lgb.Dataset(X[1000:1250], label=y[1000:1250], reference=ds),
             lgb.Dataset(X[1250:], label=y[1250:], reference=ds)]
    rec = {}
    bst = lgb.train(dict(BINARY, telemetry_out=str(out)), ds,
                    num_boost_round=2, valid_sets=valid,
                    callbacks=[lgb.record_evaluation(rec)])
    g = bst._gbdt
    assert g.use_bundles and g.fused_bundle_cols
    assert g._valid_bundle(0) is None           # logical-bin validation
    want = "layout:train=efb,valid=logical"
    assert [g._valid_route(vi) for vi in range(2)] == [(None, want)] * 2
    events = [json.loads(line) for line in open(out)]
    said = [e for e in events if e.get("event") == "valid_route"]
    assert [(e["valid_set"], e["path"], e["reason"]) for e in said] == \
        [("valid_0", "gather", want), ("valid_1", "gather", want)]
    assert not [e for e in events if e.get("event") == "degrade"
                and "valid" in e.get("reason", "")]
    counters = bst.telemetry()["counters"]
    assert counters.get("valid.route_gather_sets", 0) == 2
    assert counters.get("valid.route_kernel_sets", 0) == 0
    assert len(rec["valid_1"]["auc"]) == 2 and rec["valid_1"]["auc"][-1] > 0.7


def test_counters_on_the_kernel_side(tmp_path):
    """No training needed: a set's path is decided, said and its
    passenger built when it is added."""
    X, y = _binary_data(900)
    ds = lgb.Dataset(X[:600], label=y[:600])
    bst = lgb.Booster(dict(BINARY, telemetry_out=str(tmp_path / "t.jsonl")),
                      ds)
    bst.add_valid(lgb.Dataset(X[600:], label=y[600:], reference=ds), "v")
    g = bst._gbdt
    passenger, reason = g._valid_route(0)
    assert reason is None
    assert passenger.shape == (g.fused_bins_T.shape[0], 2048)
    assert passenger.dtype == g.fused_bins_T.dtype
    # the same builder, the same columns: the passenger of the training
    # rows IS the training matrix
    from lightgbm_tpu.boosting.gbdt import _fused_layout_T
    assert np.array_equal(
        np.asarray(_fused_layout_T(g.bins_dev, passenger.shape[0],
                                   g.fused_Rp, passenger.dtype)),
        np.asarray(g.fused_bins_T))
    counters = bst.telemetry()["counters"]
    assert counters["valid.route_kernel_sets"] == 1
    assert counters.get("valid.route_gather_sets", 0) == 0
    assert counters["events.valid_route"] == 1
