"""Which routing form a fused-engine job takes, and that it says so (PR 28).

`models/frontier2.route_form` picks, from what is static about a job,
between routing by the bin values (`bins`: the slot table carries the
splits, a categorical split as its 256-bit bin set since PR 34; no
`[Sp, FB]` table exists; EFB bundle columns of at most 256 bins too,
decoded by window in the kernel) and `W @ one_hot` (`table`:
bins over 255, bundle columns of over 256 bins). Here: (a) the choice
itself, and the bins form's set-membership test against the table form's
plane, kernel by kernel;
(b) the grower in both forms over the same data with numerical and
categorical splits: the same tree, the same leaves, the same replay;
(c) whole jobs say their form once, with the reason (`route_form` event,
`route.form_*` counters), and how `level_pass` builds its one-hot under it
(`level_build` event, `level.build_*` counters; PR 31); the bare
`Booster.update` job takes the bins form like `lgb.train`'s.
"""
import contextlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models import frontier2
from lightgbm_tpu.models.frontier2 import (grow_tree_fused, replay_route_log,
                                           route_form)
from lightgbm_tpu.models.learner import FeatureMeta
from lightgbm_tpu.ops.fused_level import (TBL_CAT_FLAG, TBL_CAT_WORD0,
                                          _left_from_bins, build_route_table,
                                          feature_layout, level_pass, pack_gh,
                                          route_pass, route_table_columns)
from lightgbm_tpu.ops.split import SplitParams

from test_valid_route import BINARY, _binary_data


# ---------------------------------------------------------------- (a)
@pytest.mark.parametrize("static,want", [
    ((False, 0, 64), ("bins", None)),
    ((True, 0, 64), ("bins", None)),
    ((False, 3, 512), ("table", "bundled")),
    ((False, 0, 512), ("table", "wide_bins")),
    ((False, 0, 256), ("bins", None)),
    ((True, 3, 512), ("table", "bundled")),
    ((True, 0, 256), ("bins", None)),
    ((True, 0, 512), ("table", "wide_bins")),
    ((False, 3, 256), ("bins", None)),
    ((True, 3, 64), ("bins", None)),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else None)
def test_route_form_is_chosen_from_what_is_static(static, want):
    assert route_form(*static) == want


# one level of splits per case: (bins in a column, bin dtype, what the
# categorical slots' sets are). Columns 0-2 are categorical; column 1 has
# a NaN bin (the last), column 2 a zero bin (bin 3), column 3 a NaN bin
SETS = {
    "empty_set": (256, np.int16, lambda rng, nb: []),
    "full_set": (256, np.int16, lambda rng, nb: range(nb)),
    "bin_0": (256, np.int16, lambda rng, nb: [0]),
    "bin_31": (256, np.int16, lambda rng, nb: [31]),
    "bin_32": (256, np.int16, lambda rng, nb: [32]),
    "bin_255": (256, np.int16, lambda rng, nb: [255]),
    "random_64_int8": (64, np.int8, lambda rng, nb: rng.choice(
        nb, rng.randint(1, 33), replace=False)),
    "random_256_int16": (256, np.int16, lambda rng, nb: rng.choice(
        nb, rng.randint(1, 33), replace=False)),
    "missing_bin_in_set": (64, np.int8, lambda rng, nb: [1, 3, nb - 1]),
    "missing_bin_out_of_set": (64, np.int8, lambda rng, nb: [1, 2, nb - 2]),
}
ROWS = 2048


def _one_level(case):
    """(bins_T, leaf_T, gh_T, W, tbl of the table form, tbl of the bins
    form, kernel kwargs) of one 16-slot level: slots 0-9 split the
    categorical columns by the case's sets, 10-12 numerical columns by
    thresholds (every missing type, default_left both ways), 13 is a
    categorical slot on a column with a numerical sibling, 14-15 are
    inactive (15 with a stale flag and a full set, which must read
    "not left"). Every bin of every column occurs."""
    B, dt, members = SETS[case]
    rng = np.random.RandomState(sorted(SETS).index(case))
    F, Sp = 5, 16
    F_oh, Bp = feature_layout(F, B - 1)
    assert Bp == B
    Fp = max(F_oh, 8)
    nb = np.zeros(F_oh, np.int32)
    nb[:F] = [B, B, B // 2, B, 9]
    mt = np.zeros(F_oh, np.int32)
    mt[:F] = [0, 2, 1, 2, 0]
    db = np.zeros(F_oh, np.int32)
    db[2] = 3
    bins_T = np.zeros((Fp, ROWS), dt)
    for f in range(F):
        col = rng.randint(0, nb[f], ROWS)
        col[:nb[f]] = np.arange(nb[f])
        bins_T[f] = rng.permutation(col)
    feat = np.array([0, 1, 2] * 3 + [0, 3, 4, 3, 1, -1, -1], np.int32)
    thr = rng.randint(0, 8, Sp).astype(np.int32)
    dl = rng.randint(0, 2, Sp).astype(bool)
    dl[:3] = True      # a categorical slot never reads default_left
    flag = np.array([True] * 10 + [False] * 3 + [True, False, True])
    mask = np.zeros((Sp, B), bool)
    for k in np.flatnonzero(flag[:14]):
        mask[k, np.asarray(list(members(rng, nb[feat[k]])), int)] = True
    mask[15] = True
    tbl = np.zeros((Sp, 128), np.int32)
    tbl[:, 0] = np.where(feat >= 0, np.arange(Sp), -2)
    tbl[:, 1] = np.where(feat >= 0, Sp, 0)
    tbl[:, 2] = rng.randint(0, 2, Sp)
    args = [jnp.asarray(a) for a in (feat, thr, dl, nb, mt, db)]
    sets = dict(cat_flag=jnp.asarray(flag), cat_mask=jnp.asarray(mask))
    W = build_route_table(*args, Sp, F_oh, B, **sets)
    tbl_b = route_table_columns(jnp.asarray(tbl), *args, **sets)
    leaf_T = jnp.asarray(rng.randint(0, Sp, ROWS).astype(np.int32))[None]
    ones = jnp.ones(ROWS, jnp.float32)
    gh_T = pack_gh(jnp.asarray(rng.randn(ROWS).astype(np.float32)), ones,
                   ones, 5)
    kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, interpret=True,
              tile_rows=1024)
    return jnp.asarray(bins_T), leaf_T, gh_T, W, jnp.asarray(tbl), tbl_b, kw


@pytest.mark.parametrize("case", sorted(SETS))
def test_the_bins_forms_membership_plane_is_the_table_forms(case):
    """``left[k, r]``, every slot against every row: the bit of the
    stored bin in the slot's eight words is ``W @ one_hot > 0.5``."""
    bins_T, _, _, W, _, tbl_b, kw = _one_level(case)
    F_oh, B = kw["f_oh"], kw["num_bins"]
    one_hot = (np.repeat(np.asarray(bins_T[:F_oh]), B, axis=0)
               == np.tile(np.arange(B), F_oh)[:, None])
    want = np.asarray(W, np.float32) @ one_hot.astype(np.float32) > 0.5
    got = np.asarray(_left_from_bins(bins_T, tbl_b, True))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert not got[14:].any() and np.asarray(tbl_b)[15, TBL_CAT_FLAG] == 0 \
        and not np.asarray(tbl_b)[14:, TBL_CAT_WORD0:TBL_CAT_WORD0 + 8].any()
    if case == "missing_bin_out_of_set":
        # slot 1: a categorical split of the column with a NaN bin sends
        # that bin right whatever default_left says
        nan_rows = np.asarray(bins_T[1]) == B - 1
        assert nan_rows.any() and not got[1, nan_rows].any()
    if case == "missing_bin_in_set":
        nan_rows = np.asarray(bins_T[1]) == B - 1
        assert got[1, nan_rows].all()
    # the numerical slots of the level read the threshold rule
    assert 0 < got[10:13].sum() < got[10:13].size


@pytest.mark.parametrize("case", ["random_64_int8", "random_256_int16",
                                  "bin_255", "missing_bin_out_of_set"])
def test_the_kernels_route_a_categorical_level_alike_in_both_forms(case):
    bins_T, leaf_T, gh_T, W, tbl, tbl_b, kw = _one_level(case)
    routed_t = route_pass(bins_T, leaf_T, W, tbl, **kw)
    routed_b = route_pass(bins_T, leaf_T, None, tbl_b, has_cat=True, **kw)
    assert np.array_equal(routed_t, routed_b)
    assert (np.asarray(routed_t) != np.asarray(leaf_T)).mean() > 0.2
    hist_t, new_t = level_pass(bins_T, leaf_T, gh_T, W, tbl, **kw)
    hist_b, new_b = level_pass(bins_T, leaf_T, gh_T, None, tbl_b,
                               has_cat=True, **kw)
    assert np.array_equal(new_t, routed_t) and np.array_equal(new_b, new_t)
    np.testing.assert_allclose(hist_b, hist_t, rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(hist_t)).sum() > 0


# ---------------------------------------------------------------- (b)
@contextlib.contextmanager
def _table_form(monkeypatch):
    """The grower in the TABLE form whatever the job, by patching the one
    place the form is chosen (the grower has no argument for it). The
    jitted grower is cached by its arguments, not by what ``route_form``
    answered while it was traced, so that cache is dropped on both sides."""
    grower = grow_tree_fused.__wrapped__
    grower.clear_cache()
    with monkeypatch.context() as m:
        m.setattr(frontier2, "route_form", lambda *static: ("table", "test"))
        yield
    grower.clear_cache()


def _grow():
    """One tree over numerical columns with every missing type and two
    categorical columns (2 and 3, nine bins) that carry signal."""
    R, B = 1500, 32
    num_bin = np.array([32, 32, 9, 9, 32, 5], np.int32)
    rng = np.random.RandomState(3)
    bins = np.stack([rng.randint(0, nb, R) for nb in num_bin], 1) \
        .astype(np.int8)
    y = ((bins[:, 0] > 12) + 0.5 * (bins[:, 1] > 20) + 0.3 * (bins[:, 4] > 7)
         + 0.8 * np.isin(bins[:, 2], (1, 4, 8))
         + 0.4 * np.isin(bins[:, 3], (0, 5)) + 0.05 * rng.randn(R))
    F = len(num_bin)
    F_oh, Bp = feature_layout(F, B)
    Rp, Fp = 2048, max(F_oh, 8)
    bins_T = np.zeros((Fp, Rp), np.int8)
    bins_T[:F, :R] = bins.T
    pad = lambda v: jnp.asarray(np.pad(v.astype(np.float32), (0, Rp - R)))
    gh_T = pack_gh(pad(-(y - y.mean())), pad(np.ones(R)), pad(np.ones(R)),
                   5)
    padf = lambda a: jnp.asarray(np.pad(np.asarray(a, np.int32),
                                        (0, F_oh - F)))
    meta = FeatureMeta(padf(num_bin), padf([0, 1, 0, 0, 2, 1]),
                       padf([0, 3, 0, 0, 0, 1]), padf(np.zeros(F)),
                       jnp.asarray(np.isin(np.arange(F_oh), (2, 3))))
    tree, row_leaf, log = grow_tree_fused(
        jnp.asarray(bins_T), gh_T, meta, jnp.asarray(np.arange(F_oh) < F),
        SplitParams(min_data_in_leaf=5, min_gain_to_split=0.5,
                    cat_smooth=1.0, min_data_per_group=5), 8, B, F_oh,
        nch=5, extra_levels=1, interpret=True, has_cat=True, num_rows=R,
        route_log=True)
    leaves = replay_route_log(jnp.asarray(bins_T), log, R, num_bins=Bp,
                              f_oh=F_oh, interpret=True, has_cat=True)
    return jax.device_get(tree), np.asarray(row_leaf), \
        np.asarray(leaves)[0], jax.device_get(log)


def test_the_grower_grows_the_same_tree_in_both_forms(monkeypatch):
    tree_b, leaf_b, replay_b, (logW_b, tbl_b) = _grow()
    with _table_form(monkeypatch):
        tree_t, leaf_t, replay_t, (logW_t, tbl_t) = _grow()
    assert int(tree_b.num_leaves) == 8
    for a, b in zip(tree_b, tree_t):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(leaf_b, leaf_t)
    assert np.array_equal(replay_b, leaf_b) \
        and np.array_equal(replay_t, leaf_t)
    # the bins form logs no [levels, Sp, FB] tables; what both forms log
    # of the slots is the same, and only the bins form fills columns 3-15
    assert logW_b is None and logW_t.shape[2] == 6 * 32 + 2 * 32
    assert np.array_equal(tbl_b[:, :, :3], tbl_t[:, :, :3])
    live = tbl_b[:, :, 0] >= 0
    assert not tbl_t[:, :, 3:].any() and live.sum() == 7 \
        and (tbl_b[:, :, 6][live] >= 0).all()
    # real categorical splits and numerical ones are among them, a split
    # on a feature with a missing bin too; a categorical slot's set is the
    # tree's, bit for bit
    cat = tbl_b[:, :, TBL_CAT_FLAG][live] == 1
    n_cat = int(np.asarray(tree_b.cat_flag)[:7].sum())
    assert 0 < cat.sum() == n_cat < 7
    assert (tbl_b[:, :, 4][live][~cat] >= 0).any()
    words = tbl_b[:, :, TBL_CAT_WORD0][live]
    assert (words[cat] != 0).all() and not words[~cat].any()
    sets = sorted(int(np.sum(m * (1 << np.arange(m.size))))
                  for m in np.asarray(tree_b.cat_mask)[:7][
                      np.asarray(tree_b.cat_flag)[:7]])
    assert sorted(words[cat].tolist()) == sets


# ---------------------------------------------------------------- (c)
def _dense_job(out):
    X, y = _binary_data()
    ds = lgb.Dataset(X[:1200], label=y[:1200])
    dv = lgb.Dataset(X[1200:], label=y[1200:], reference=ds)
    return lgb.train(dict(BINARY, telemetry_out=out), ds, num_boost_round=2,
                     valid_sets=[dv])


def _categorical_job(out, **more):
    rng = np.random.RandomState(9)
    X = rng.randn(1500, 5).astype(np.float32)
    X[:, 2] = rng.randint(0, 8, 1500)
    y = (np.isin(X[:, 2], (1, 4, 7)) + 0.5 * (X[:, 0] > 0) > 0.7)
    ds = lgb.Dataset(X[:1100], label=y[:1100].astype(np.float32),
                     categorical_feature=[2])
    bst = lgb.train(dict(BINARY, min_data_per_group=5, cat_smooth=1.0,
                         telemetry_out=out, **more), ds, num_boost_round=2)
    assert bst._gbdt.has_cat and "cat_threshold" in bst.model_to_string()
    return bst


def _dense_efb_job(out):
    rng = np.random.RandomState(2)
    n = 1500
    X = np.zeros((n, 12), np.float32)
    X[np.arange(n), rng.randint(0, 12, n)] = rng.rand(n) + 0.1
    y = (X[:, 0] + X[:, 3] + X[:, 7] > 0.3).astype(np.float32)
    bst = lgb.train(dict(BINARY, telemetry_out=out),
                    lgb.Dataset(X[:1000], label=y[:1000]), num_boost_round=2)
    assert bst._gbdt.use_bundles and bst._gbdt.fused_bundle_cols
    return bst


def _wide_bins_job(out):
    X, y = _binary_data()
    bst = lgb.train(dict(BINARY, max_bin=511, telemetry_out=out),
                    lgb.Dataset(X[:1200], label=y[:1200]), num_boost_round=2)
    assert bst._gbdt.fused_Bp == 512 and not bst._gbdt.fused_bundle_cols
    return bst


def _bare_update_job(out):
    X, y = _binary_data()
    ds = lgb.Dataset(X[:1200], label=y[:1200])
    bst = lgb.Booster(dict(BINARY, tpu_megastep=False, telemetry_out=out),
                      ds)
    for _ in range(2):
        bst.update()
    return bst


@pytest.mark.parametrize("job,form,reason", [
    (_dense_job, "bins", None),
    (_categorical_job, "bins", None),
    (_dense_efb_job, "table", "bundled"),
    (_wide_bins_job, "table", "wide_bins"),
    (_bare_update_job, "bins", None),
], ids=["dense", "categorical", "dense_efb", "wide_bins", "bare_update"])
def test_a_job_says_its_form_once_with_the_reason(tmp_path, job, form, reason):
    out = tmp_path / "t.jsonl"
    bst = job(str(out))
    said = [e for e in map(json.loads, open(out))
            if e.get("event") == "route_form"]
    assert [(e["form"], e.get("reason")) for e in said] == [(form, reason)]
    counters = bst.telemetry()["counters"]
    # a categorical column in the bins form: the kernels test bin sets
    # (PR 34), said once beside the form
    membership = job is _categorical_job
    assert said[0].get("membership") == (True if membership else None)
    assert counters.get("route.cat_membership", 0) == int(membership)
    other = {"bins": "table", "table": "bins"}[form]
    assert counters["route.form_%s" % form] == 1
    assert counters.get("route.form_%s" % other, 0) == 0
    assert counters["events.route_form"] == 1
    # how level_pass builds its one-hot follows from the form (PR 31): in
    # slabs with no [FB, C] scratch where routing reads the bin values, the
    # whole scratch where the routing dot reads all of it first
    built = [e for e in map(json.loads, open(out))
             if e.get("event") == "level_build"]
    build = {"bins": "slab", "table": "scratch"}[form]
    assert [(e["form"], e.get("reason")) for e in built] == [(build, reason)]
    assert built[0].get("slab_rows") == (512 if build == "slab" else None)
    tiles = built[0]["tile_rows"]
    assert "8" in tiles and all(128 <= t <= 2048 and t & (t - 1) == 0
                                for t in tiles.values())
    # which operand of the histogram dot streams (PR 36): the channels in
    # the slab build, the one-hot where the whole scratch is read
    order = {"slab": "channels", "scratch": "onehot"}[build]
    assert built[0]["dot"] == {sp: order for sp in tiles}
    assert counters["level.dot_stream_%s" % order] >= 2
    assert len([k for k in counters if k.startswith("level.dot_")]) == 1
    other = {"slab": "scratch", "scratch": "slab"}[build]
    assert counters["level.build_%s" % build] == 1
    assert counters.get("level.build_%s" % other, 0) == 0
    assert counters["events.level_build"] == 1
    assert bst.num_trees() == 2


@pytest.mark.parametrize("features,passes,slots", [(28, 9, (8, 16, 32, 64)),
                                                   (137, 19, (8, 16))],
                         ids=["higgs_shaped", "ranking_shaped"])
def test_a_job_says_how_many_passes_stream_the_channels(tmp_path, features,
                                                        passes, slots):
    """255 leaves at 63 bins: a full tree's histogram passes (the root,
    then every level up to the one that spends the leaf budget) are nine
    at Higgs's 28 features and nineteen at the ranking cell's 137, where
    the slot cap is 16; all of them stream the channels. Said where the
    step is built, from shapes alone: no step is compiled here."""
    rng = np.random.RandomState(0)
    X = rng.randn(2000, features).astype(np.float32)
    out = tmp_path / "t.jsonl"
    bst = lgb.Booster({"objective": "binary", "num_leaves": 255,
                       "max_bin": 63, "min_data_in_leaf": 1, "verbose": -1,
                       "tpu_engine": "fused", "tpu_megastep": True,
                       "telemetry_out": str(out)},
                      lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float32)))
    bst._gbdt._route_form()
    bst._gbdt._route_form()             # said once
    counters = bst.telemetry()["counters"]
    assert counters["level.dot_stream_channels"] == passes
    assert "level.dot_stream_onehot" not in counters
    (build,) = [e for e in map(json.loads, open(out))
                if e.get("event") == "level_build"]
    assert build["form"] == "slab"
    assert build["dot"] == {str(sp): "channels" for sp in slots}
    assert build["tile_rows"] == {str(sp): 2048 for sp in slots}


def test_a_categorical_job_on_the_mesh_is_the_one_device_job(tmp_path):
    """``tree_learner=data``: the shards route their rows by the bin sets
    of the replicated slot table and psum the same ``[FB, nch*Sp]``
    plane: the trees of the one-device job, categorical nodes and all
    (leaf values to float32 rounding: the plane is summed per shard)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    one = _categorical_job(str(tmp_path / "one.jsonl"))
    out = tmp_path / "mesh.jsonl"
    mesh = _categorical_job(str(out), tree_learner="data")
    g = mesh._gbdt
    assert g.parallel_mode == "data" and g.n_shards >= 4
    events = [json.loads(line) for line in open(out)]
    kinds = [e.get("event") for e in events]
    assert "degrade" not in kinds and "megastep_evicted" not in kinds
    (form,) = [e for e in events if e.get("event") == "route_form"]
    assert (form["form"], form.get("membership")) == ("bins", True)

    def nodes(bst):
        found = []
        for info in bst.dump_model()["tree_info"]:
            stack = [info["tree_structure"]]
            while stack:
                n = stack.pop()
                if "split_index" in n:
                    found.append((n["split_feature"], n["decision_type"],
                                  n["threshold"]))
                    stack += [n["left_child"], n["right_child"]]
                else:
                    found.append(n["leaf_count"])
        return found
    assert nodes(one) == nodes(mesh)
    assert any(n[1] == "==" for n in nodes(one) if isinstance(n, tuple))
    X = np.random.RandomState(1).randn(300, 5).astype(np.float32)
    X[:, 2] = np.arange(300) % 9
    np.testing.assert_allclose(mesh.predict(X, raw_score=True),
                               one.predict(X, raw_score=True),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("more", [
    {"tpu_adaptive_bins": True}, {"tpu_quantized_grad": 8},
    {"tpu_megastep": False}], ids=["adaptive_bins", "quantized", "fast_step"])
def test_a_categorical_job_is_the_table_forms_under(monkeypatch, tmp_path,
                                                    more):
    """What else shares the kernels' routing prologue: the int8 histogram
    kernel and the per-iteration step grow the table form's model, which
    the job took until PR 34. The adaptive layout (the split feature's
    ROW is its place in the packed order, the stored value still the
    logical bin the set is over) grows the padded layout's model: its
    table form does not run on this CPU backend (a bfloat16 dot XLA's CPU
    thunks refuse), the padded job stands in."""
    bins = _categorical_job(str(tmp_path / "bins.jsonl"), **more)
    assert bins.telemetry()["counters"]["route.cat_membership"] == 1
    if "tpu_adaptive_bins" in more:
        assert bins._gbdt.fused_packed is not None
        other = _categorical_job(str(tmp_path / "padded.jsonl"))
        assert other._gbdt.fused_packed is None
    else:
        with _table_form(monkeypatch):
            other = _categorical_job(str(tmp_path / "table.jsonl"), **more)
        assert other.telemetry()["counters"]["route.form_table"] == 1
    trees = lambda bst: bst.model_to_string().split("\nparameters:")[0]
    assert trees(bins) == trees(other)
