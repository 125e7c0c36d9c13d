"""Sparse input bundled at ingestion, routed in the kernels' bins form.

A job given a scipy CSR matrix is stored as EFB bundle columns
(`TpuDataset.from_sparse`, no conflicts allowed). The fused kernels route
it by the bundle VALUE: the slot table carries the split feature's bundle
column, window and most-frequent bin, and `_left_from_bins` decodes the
value to the feature's bin before the compare (`ops/fused_level.py`).
Here:

(a) the decode against `W @ one_hot` (`build_route_table_bundled`, the
    table form that ran bundled jobs before) and against numpy on the
    logical bins, kernel by kernel, on random bundle layouts with values
    inside and outside the split feature's window, rows default in every
    member, and missing bins;
(b) whole jobs: the bundled fast-path model against the same data given
    DENSE and UNBUNDLED to the plain path (`enable_bundle=false`,
    `tpu_fast_path=false`: the XLA growers in float32), at several sizes
    and layouts, tree for tree;
(c) a job without bundles traces no decode: the kernels of a
    Higgs-shaped and a categorical job hold no operation of the
    `bundle_decode` scope, a bundled job's do.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models.frontier2 import grow_tree_fused
from lightgbm_tpu.models.learner import BundleCfg, FeatureMeta
from lightgbm_tpu.ops.efb import BundleLayout, encode_bundles
from lightgbm_tpu.ops.fused_level import (NCH_PRECISE,
                                          build_route_table_bundled,
                                          feature_layout, level_pass,
                                          pack_gh, root_route_tables,
                                          route_pass, route_table_columns)
from lightgbm_tpu.ops.split import SplitParams

sp = pytest.importorskip("scipy.sparse")


def _np_left(b, thr, dl, nb, mt, db):
    missing = ((mt == 1) & (b == db)) | ((mt == 2) & (b == nb - 1))
    return np.where(missing, dl, b <= thr)


# ---------------------------------------------------------------- (a)
def _bundle_case(case: str, Sp: int, seed: int):
    """A random bundle layout over logical features, conflict-free rows,
    one level of splits: (logical bins [R, F], meta arrays, layout,
    bundle matrix, split arrays)."""
    rng = np.random.RandomState(seed)
    F, R = 14, 1500
    nb = rng.randint(2, 24, F).astype(np.int32)
    mt = rng.randint(0, 3, F).astype(np.int32)          # none, zero, NaN
    db = np.array([rng.randint(0, n) for n in nb], np.int32)
    mfb = np.array([rng.randint(0, n) for n in nb], np.int32)
    if case == "mfb_zero":
        mfb[:] = 0
    order = rng.permutation(F)
    bundles = [sorted(order[a:b].tolist())
               for a, b in ((0, 5), (5, 6), (6, 10), (10, F))]
    layout = BundleLayout(bundles, nb)
    assert max(layout.col_num_bin) <= 256
    # each row is non-default in at most one member of each bundle:
    # "none" rows are default in every member (bundle bin 0)
    bins = np.tile(mfb, (R, 1))
    p_none = 0.7 if case == "default_in_all" else 0.15
    for b in bundles:
        owner = rng.randint(0, len(b), R)
        owner[rng.rand(R) < p_none] = -1
        for k, f in enumerate(b):
            rows = np.nonzero(owner == k)[0]
            other = rng.randint(0, nb[f] - 1, rows.size)
            bins[rows, f] = np.where(other >= mfb[f], other + 1, other)
    if case == "missing":
        # missing bins are stored values too: the zero bin of a
        # zero-missing feature, the last bin of a NaN one
        for f in np.nonzero(mt > 0)[0]:
            miss = db[f] if mt[f] == 1 else nb[f] - 1
            b = next(b for b in bundles if f in b)
            free = np.all(bins[:, b] == mfb[b], axis=1)
            bins[free & (rng.rand(R) < 0.3), f] = miss
    enc = encode_bundles(bins, mfb, layout)
    # every stored value decodes back: the encode dropped nothing
    for f in range(F):
        c, off = layout.col_of_feat[f], layout.offset_of_feat[f]
        v = enc[:, c].astype(np.int64)
        inside = (v >= off) & (v < off + nb[f])
        assert np.array_equal(np.where(inside, v - off, mfb[f]), bins[:, f])
    feat = rng.randint(0, F, Sp).astype(np.int32)
    if case == "missing":
        feat = rng.choice(np.nonzero(mt > 0)[0], Sp).astype(np.int32)
    thr = np.array([rng.randint(0, nb[f]) for f in feat], np.int32)
    dl = rng.randint(0, 2, Sp).astype(bool)
    return bins, (nb, mt, db, mfb), layout, enc, (feat, thr, dl)


BUNDLE_CASES = ["in_and_out_of_window", "default_in_all", "missing",
                "mfb_zero", "inactive_slots", "root"]


@pytest.mark.parametrize("case", BUNDLE_CASES)
@pytest.mark.parametrize("Sp", [8, 32])
def test_the_decode_routes_like_the_table_and_the_logical_bins(Sp, case):
    """The same splits as the table form's W (the decode written into its
    columns) and as the bins form's slot table (the decode in the
    kernel): identical leaves from both kernels, equal to numpy's routing
    of the LOGICAL bins, and the identical histogram."""
    bins, (nb, mt, db, mfb), layout, enc, (feat, thr, dl) = \
        _bundle_case(case, Sp, seed=Sp + len(case))
    R, F = bins.shape
    rng = np.random.RandomState(3)
    C = layout.num_columns
    C_oh, Bc = feature_layout(C, max(layout.col_num_bin))
    Rp = 2048
    bins_T = np.zeros((max(C_oh, 8), Rp), np.int8 if Bc <= 128 else np.int16)
    bins_T[:C, :R] = enc.T
    leaf = rng.randint(0, Sp, R).astype(np.int32)
    if case == "inactive_slots":
        feat[1::2] = -1
    lof = np.where(feat >= 0, np.arange(Sp), -2).astype(np.int32)
    meta = tuple(jnp.asarray(a) for a in (nb, mt, db))
    bundle = tuple(jnp.asarray(a) for a in (layout.col_of_feat,
                                            layout.offset_of_feat, mfb))
    if case == "root":
        leaf[:] = 0
        W, tbl = root_route_tables(Bc, C_oh * Bc, Bc, False, Sp)
        _, tbl_b = root_route_tables(Bc, C_oh * Bc, Bc, True, Sp,
                                     bundled=True)
        want = leaf.copy()
    else:
        tbl = np.zeros((Sp, 128), np.int32)
        tbl[:, 0] = lof
        tbl[:, 1] = np.where(feat >= 0, Sp + np.arange(Sp) - lof, 0)
        tbl[:, 2] = rng.randint(0, 2, Sp)
        tbl = jnp.asarray(tbl)
        split = (jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(dl))
        W = build_route_table_bundled(*split, *meta, bundle[2], bundle[0],
                                      bundle[1], C_oh, Bc)
        tbl_b = route_table_columns(tbl, *split, *meta, bundle=bundle)
        want = leaf.copy()
        for k in np.nonzero(feat >= 0)[0]:
            f = feat[k]
            left = _np_left(bins[:, f], thr[k], dl[k], nb[f], mt[f], db[f])
            want[(leaf == lof[k]) & ~left] += Sp + k - lof[k]
    leaf_T = np.full((1, Rp), -1, np.int32)
    leaf_T[0, :R] = leaf
    want = np.pad(want, (0, Rp - R), constant_values=-1)
    bins_T, leaf_T = jnp.asarray(bins_T), jnp.asarray(leaf_T)
    gh_T = pack_gh(jnp.asarray(rng.randn(Rp).astype(np.float32)),
                   jnp.asarray(rng.rand(Rp).astype(np.float32) + 0.1),
                   jnp.ones((Rp,), jnp.float32), NCH_PRECISE)
    kw = dict(num_slots=Sp, num_bins=Bc, f_oh=C_oh, tile_rows=512,
              interpret=True)
    hist_t, leaf_t = level_pass(bins_T, leaf_T, gh_T, W, tbl, **kw)
    hist_b, leaf_b = level_pass(bins_T, leaf_T, gh_T, None, tbl_b,
                                bundled=True, **kw)
    assert np.array_equal(np.asarray(leaf_t)[0], want)
    assert np.array_equal(np.asarray(leaf_b), np.asarray(leaf_t))
    assert np.array_equal(np.asarray(hist_b), np.asarray(hist_t))
    for w, t, decode in ((W, tbl, False), (None, tbl_b, True)):
        routed = route_pass(bins_T, leaf_T, w, t, bundled=decode, **kw)
        assert np.array_equal(np.asarray(routed), np.asarray(leaf_t))
    # without the decode the bins form reads bundle values as bins: the
    # test can tell
    if case not in ("root", "inactive_slots"):
        moved = (want != np.asarray(leaf_T)[0]).sum()
        assert moved > 50
        undecoded = route_pass(bins_T, leaf_T, None, tbl_b, **kw)
        assert not np.array_equal(np.asarray(undecoded)[0], want)


# ---------------------------------------------------------------- (b)
def _onehot(codes, cards):
    """[n, k] category codes -> the one-hot CSR columns of each column, in
    order, as [n, sum(cards)] data / indices blocks."""
    base = np.concatenate([[0], np.cumsum(cards)[:-1]])
    return codes + base, int(np.sum(cards))


def _csr(blocks, n):
    """CSR from per-row blocks of (column, value) pairs, the same number
    of stored values on every row (a stored zero stays implicit in the
    dense copy), columns ascending."""
    cols = np.concatenate([c for c, _ in blocks], axis=1)
    vals = np.concatenate([v for _, v in blocks], axis=1)
    order = np.argsort(cols, axis=1, kind="stable")
    cols = np.take_along_axis(cols, order, 1)
    vals = np.take_along_axis(vals, order, 1).astype(np.float32)
    k = cols.shape[1]
    width = int(cols.max()) + 1
    return sp.csr_matrix((vals.reshape(-1), cols.reshape(-1),
                          np.arange(0, n * k + 1, k)), shape=(n, width))


def _job(case: str):
    """(CSR, label, extra params, what the layout has to show)."""
    rng = np.random.RandomState(len(case))
    n = {"two_columns": 2500, "exactly_256": 3000, "wide_bundle": 3000}\
        .get(case, 2000)
    cards = {"numeric_beside": [6, 11, 4], "missing": [7, 5],
             "mfb_not_zero": [9], "two_columns": [150, 40],
             "exactly_256": [127], "wide_bundle": [150]}[case]
    codes = np.stack([rng.randint(0, c, n) for c in cards], 1)
    cols, width = _onehot(codes, cards)
    vals = np.ones_like(cols, np.float32)
    blocks = [(cols, vals)]
    # an effect of its own for every category, so that no two one-hot
    # features split a leaf equally well
    margin = sum(rng.randn(c)[codes[:, j]] for j, c in enumerate(cards))
    extra = {}
    if case == "numeric_beside":
        # a dense numeric column (DepTime's place): a singleton column of
        # its own beside the one-hot bundles
        x = rng.randn(n).astype(np.float32)
        blocks.append((np.full((n, 1), width), x[:, None]))
        margin = margin + 0.8 * x
    elif case == "missing":
        # a sparse numeric feature with NaNs stored: its NaN bin is a
        # stored value of its window
        live = rng.rand(n) < 0.3
        x = np.where(rng.rand(n) < 0.3, np.nan, rng.rand(n) + 0.5)
        blocks.append((np.full((n, 1), width),
                       np.where(live, x, 0.0)[:, None]))
        margin = margin + np.where(live & ~np.isnan(x), x - 1.0, 0.0) \
            + 0.6 * (live & np.isnan(x))
    elif case == "mfb_not_zero":
        # a sparse feature of either sign: zero (its most frequent value)
        # bins in the middle, so its most-frequent bin is not bin 0
        live = rng.rand(n) < 0.25
        x = rng.choice([-2.0, -1.0, 1.0, 2.0], n)
        blocks.append((np.full((n, 1), width),
                       np.where(live, x, 0.0)[:, None]))
        margin = margin + 0.5 * np.where(live, x, 0.0)
    elif case == "exactly_256":
        # 126 two-bin features and one of three bins (two values):
        # 1 + 2 * 126 + 3 = 256 bins in ONE bundle column
        vals[codes[:, 0] == 5, 0] = rng.choice([1.0, 2.0],
                                              int((codes[:, 0] == 5).sum()))
        margin = margin + 0.7 * (vals[:, 0] == 2.0)
    elif case == "wide_bundle":
        # 150 two-bin features under a 512-bin cap: one column of 301
        # bins, over the bins form's 256
        extra = {"tpu_max_bundle_bins": 512}
    X = _csr(blocks, n)
    y = (margin + 0.5 * rng.randn(n) > 0.5).astype(np.float32)
    return X, y, extra


CASES = ["numeric_beside", "missing", "mfb_not_zero", "two_columns",
         "exactly_256", "wide_bundle"]
PLAIN = {"enable_bundle": False, "tpu_fast_path": False}
FAST = {"tpu_engine": "fused", "tpu_megastep": True, "tpu_megastep_iters": 2}
# depth-bounded trees: the fused grower spends a binding leaf budget level
# by level and the plain grower best-first, so only a tree that neither
# has to cut is the same tree on both (tests/test_lambdarank.py); a gain
# floor keeps out the splits of a pure leaf, whose gain is zero but for
# rounding, of either sign; a tree of depth 3 needs no passes past the
# schedule's three (tpu_extra_levels)
PARAMS = {"objective": "binary", "num_leaves": 8, "max_depth": 3,
          "min_data_in_leaf": 3, "min_sum_hessian_in_leaf": 1e-3,
          "min_gain_to_split": 0.05, "learning_rate": 0.3,
          "tpu_extra_levels": 0, "verbose": -1}
ITERS = 2


def _splits(node, out):
    if "split_index" in node:
        out.append((node["split_feature"], node["threshold"],
                    node["default_left"]))
        _splits(node["left_child"], out)
        _splits(node["right_child"], out)
    else:
        out.append(("leaf", node["leaf_value"]))
    return out


def _trees(bst):
    return [_splits(t["tree_structure"], [])
            for t in bst.dump_model()["tree_info"]]


@pytest.mark.parametrize("case", CASES)
def test_the_bundled_fast_path_is_the_dense_unbundled_model(case, tmp_path):
    X, y, extra = _job(case)
    tel = str(tmp_path / "t.jsonl")
    fast = lgb.train(dict(PARAMS, telemetry_out=tel, **FAST, **extra),
                     lgb.Dataset(X, label=y), num_boost_round=ITERS)
    g = fast._gbdt
    layout = g.train_data.prebundled
    assert layout is not None and g.use_fused and g.fused_bundle_cols
    events = [json.loads(line) for line in open(tel)]
    kinds = [e.get("event") for e in events]
    assert "degrade" not in kinds and "megastep_evicted" not in kinds
    (form,) = [e for e in events if e.get("event") == "route_form"]
    (said,) = [e for e in events if e.get("event") == "efb_layout"]
    widest = max(layout.col_num_bin)
    assert said == dict(said, features=g.train_data.num_features,
                        columns=layout.num_columns, max_bins=widest,
                        conflict_rows=0, form=form["form"])
    if case == "wide_bundle":
        assert widest > 256
        assert (form["form"], form.get("reason")) == ("table", "bundled")
    else:
        assert widest <= 256
        assert (form["form"], form.get("reason")) == ("bins", None)
    if case == "exactly_256":
        assert widest == 256 and any(len(b) == 127 for b in layout.bundles)
    if case == "two_columns":
        assert sum(len(b) > 1 for b in layout.bundles) >= 2
    if case == "mfb_not_zero":
        assert int(g.train_data.most_freq_bins[-1]) != 0
    if case == "missing":
        assert int(g.train_data.missing_types[-1]) == 2     # NaN
    plain = lgb.train(dict(PARAMS, **PLAIN), lgb.Dataset(X.toarray(), label=y),
                      num_boost_round=ITERS)
    assert not plain._gbdt.use_bundles and not plain._gbdt.use_fused
    fast_t, plain_t = _trees(fast), _trees(plain)
    assert len(fast_t) == len(plain_t) == ITERS
    bounds = iter(_leaf_bounds(plain, X.toarray(), y))
    for a, b in zip(fast_t, plain_t):
        # the same split features and thresholds in every node
        assert [x for x in a if x[0] != "leaf"] \
            == [x for x in b if x[0] != "leaf"]
        assert [x[0] for x in a] == [x[0] for x in b]
        assert sum(x[0] != "leaf" for x in a) >= 3
        # leaf values within what the fast path's histogram can hold of
        # each: it carries a gradient or hessian as two bfloat16 halves
        # (16 bits of mantissa), so a leaf's sums G and H are off by up to
        # 2^-16 of the sums of their magnitudes, and the leaf -lr G / H by
        # lr 2^-16 (sum|g| + |G|) / H, whatever its own size
        la = np.array([x[1] for x in a if x[0] == "leaf"])
        lb = np.array([x[1] for x in b if x[0] == "leaf"])
        np.testing.assert_array_less(np.abs(la - lb), next(bounds))


def _leaf_bounds(bst, X, y):
    """Per tree, per leaf (in dump order): the bound on a leaf value that
    the hi/lo halves of the fast path's histogram leave, from the
    gradients and hessians of the rows the plain model's tree sends to
    the leaf (binary log loss; the first tree's scores are the average's
    log-odds), plus a float32 rounding of the value."""
    lr = PARAMS["learning_rate"]
    leaf_of = bst.predict(X, pred_leaf=True)
    out = []
    for t, info in enumerate(bst.dump_model()["tree_info"]):
        if t == 0:
            score = np.full(len(y), np.log(y.mean() / (1 - y.mean())))
        else:
            score = bst.predict(X, raw_score=True, num_iteration=t)
        p = 1.0 / (1.0 + np.exp(-score))
        g, h = p - y, p * (1.0 - p)
        bound = []
        for x in _leaf_nodes(info["tree_structure"], []):
            on = leaf_of[:, t] == x["leaf_index"]
            G, H = g[on].sum(), h[on].sum()
            bound.append(lr * 2.0 ** -16 * (np.abs(g[on]).sum() + abs(G)) / H
                         + 1e-6 * abs(x["leaf_value"]))
        out.append(np.array(bound))
    return out


def _leaf_nodes(node, out):
    if "split_index" in node:
        _leaf_nodes(node["left_child"], out)
        _leaf_nodes(node["right_child"], out)
    else:
        out.append(node)
    return out


def test_a_validation_set_is_stored_in_the_training_bundles(tmp_path):
    """The validation set of a sparse job is stored in the training set's
    bundle columns, so the kernels' route logs replay over it. A row
    non-default in two members of one bundle cannot be stored there: the
    set keeps its logical bins beside the bundles, its leaves are walked
    over those, and the set's scores are the trees' over its logical
    columns, on the kernels' path (the traced megastep) and on the host
    trees' replay (a validation set added to a trained model)."""
    X, y, _ = _job("numeric_beside")
    ds = lgb.Dataset(X[:1500], label=y[:1500]).construct()
    layout = ds._inner.prebundled
    Xv, yv = X[1500:], y[1500:]
    ok = lgb.Dataset(Xv, label=yv, reference=ds).construct()
    assert ok._inner.prebundled is layout
    assert ok._inner.exact_rows[0].size == 0
    # a row non-default in a bundle's first member and in a later one
    # the trees split on: stored in the bundle, it would read the first
    # member's value alone and route as if the other were default
    split_on = {x[0] for t in _trees(lgb.train(dict(PARAMS, **FAST), ds,
                                               num_boost_round=ITERS))
                for x in t if x[0] != "leaf"}
    used = ds._inner.used_features
    members, second = next((b, used[k]) for b in layout.bundles
                           for k in b[1:] if used[k] in split_on)
    first = used[members[0]]
    clash = Xv.tolil()
    for r in (0, 7):
        for k in members:
            clash[r, ds._inner.used_features[k]] = 0.0
        clash[r, first] = clash[r, second] = 1.0
    clash = clash.tocsr()
    bad = lgb.Dataset(clash, label=yv, reference=ds).construct()
    assert bad._inner.prebundled is layout
    rows, bins = bad._inner.exact_rows
    assert rows.tolist() == [0, 7]
    dense = lgb.Dataset(clash.toarray(), label=yv, reference=ds,
                        params={"enable_bundle": False}).construct()
    tel = str(tmp_path / "t.jsonl")
    bst = lgb.train(dict(PARAMS, metric="auc", telemetry_out=tel, **FAST),
                    ds, num_boost_round=ITERS, valid_sets=[ok, bad])
    said = [e for e in map(json.loads, open(tel))
            if e.get("event") == "valid_route"]
    assert [(e["path"], e.get("exact_rows")) for e in said] \
        == [("kernel", 0), ("kernel", 2)]
    scores = [np.asarray(bst._gbdt.valid_scores[i])[0] for i in (0, 1)]
    np.testing.assert_allclose(scores[0], bst.predict(Xv, raw_score=True),
                               rtol=1e-5, atol=1e-6)
    want = bst.predict(clash, raw_score=True)
    np.testing.assert_allclose(scores[1], want, rtol=1e-5, atol=1e-6)
    # the rows' logical bins are those of the set given dense
    np.testing.assert_array_equal(bins, np.asarray(dense._inner.bins)[rows])
    # what the bundles alone hold of the clashing rows routes elsewhere
    stored = clash.tolil()
    stored[[0, 7], second] = 0.0
    assert not np.allclose(bst.predict(stored.tocsr(), raw_score=True)[
        [0, 7]], want[[0, 7]])
    # the host trees' replay onto a set added after training
    g = bst._gbdt
    g.add_valid_data(bad._inner, "late", [])
    np.testing.assert_allclose(np.asarray(g.valid_scores[-1])[0], want,
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------- (c)
def _kernel_scopes(fn, *args):
    """The name stacks of every operation inside the Pallas kernels of a
    traced function."""
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            here = inside or eqn.primitive.name == "pallas_call"
            if inside:
                found.append(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)
    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return found


def _grower_args(F, B, bundled=False, has_cat=False):
    """grow_tree_fused's operands over 2,048 rows: F logical features of
    B bins; ``bundled``: stored as two bundle columns."""
    rng = np.random.RandomState(0)
    R = 2048
    f_oh, Bp = feature_layout(F, B)
    z = jnp.zeros((f_oh,), jnp.int32)
    meta = FeatureMeta(
        jnp.asarray(np.pad(np.full(F, B), (0, f_oh - F)), jnp.int32), z, z,
        z, jnp.asarray(np.arange(f_oh) < (2 if has_cat else 0)))
    kw = dict(params=SplitParams(min_data_in_leaf=5), num_leaves=15,
              max_bins=Bp, f_oh=f_oh, num_rows=R, interpret=True,
              has_cat=has_cat)
    k_rows = F
    if bundled:
        nb = [B] * F
        layout = BundleLayout([list(range(0, F, 2)), list(range(1, F, 2))],
                              nb)
        Bc = max(layout.col_num_bin)
        C_oh, Bc_p = feature_layout(2, Bc)
        k_rows = C_oh
        b_i = np.arange(Bp)[None, :]
        flat = np.minimum(layout.col_of_feat[:, None] * Bc_p
                          + layout.offset_of_feat[:, None] + b_i,
                          C_oh * Bc_p - 1)
        pad = lambda a, v=0: np.pad(a, [(0, f_oh - F)] + [(0, 0)] *
                                    (a.ndim - 1), constant_values=v)
        kw.update(bundle_cols=C_oh, bundle_col_bins=Bc_p,
                  bundle_cfg=BundleCfg(
                      flat_idx=jnp.asarray(pad(flat), jnp.int32),
                      valid=jnp.asarray(
                          pad(np.broadcast_to(b_i < B, (F, Bp)))),
                      default_bin=jnp.zeros((f_oh,), jnp.int32),
                      col_of_feat=jnp.asarray(pad(layout.col_of_feat, -1)),
                      offset_of_feat=jnp.asarray(
                          pad(layout.offset_of_feat))))
        Bp = Bc_p
    bins_T = jnp.asarray(rng.randint(0, min(Bp, 127), (max(k_rows, 8), R))
                         .astype(np.int8 if Bp <= 128 else np.int16))
    gh_T = pack_gh(jnp.asarray(rng.randn(R).astype(np.float32)),
                   jnp.ones((R,), jnp.float32), jnp.ones((R,), jnp.float32),
                   NCH_PRECISE)
    fm = jnp.asarray(np.arange(f_oh) < F)
    return (bins_T, gh_T, meta, fm), kw


@pytest.mark.parametrize("job,F,B,bundled,has_cat", [
    ("higgs_shaped", 28, 63, False, False),
    ("categorical", 8, 255, False, True),
    ("bundled", 6, 20, True, False),
], ids=lambda v: v if isinstance(v, str) else None)
def test_only_a_bundled_job_traces_the_decode(job, F, B, bundled, has_cat):
    args, kw = _grower_args(F, B, bundled, has_cat)
    scopes = _kernel_scopes(lambda *a: grow_tree_fused(*a, **kw), *args)
    assert len(scopes) > 100            # the kernels were found
    decode = [s for s in scopes if "bundle_decode" in s]
    assert bool(decode) == bundled, (job, len(decode))
