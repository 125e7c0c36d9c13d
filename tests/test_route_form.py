"""Which routing form a fused-engine job takes, and that it says so (PR 28).

`models/frontier2.route_form` picks, from what is static about a job,
between routing by the bin values (`bins`: the slot table carries the
splits, no `[Sp, FB]` table exists) and `W @ one_hot` (`table`: categorical
splits, EFB bundle columns, bins over 255). Here: (a) the choice itself;
(b) the grower in both forms over the same numerical data: the same tree,
the same leaves, the same replay;
(c) whole jobs say their form once, with the reason (`route_form` event,
`route.form_*` counters), and how `level_pass` builds its one-hot under it
(`level_build` event, `level.build_*` counters; PR 31); the bare
`Booster.update` job takes the bins form like `lgb.train`'s.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models.frontier2 import (grow_tree_fused, replay_route_log,
                                           route_form)
from lightgbm_tpu.models.learner import FeatureMeta
from lightgbm_tpu.ops.fused_level import feature_layout, pack_gh
from lightgbm_tpu.ops.split import SplitParams

from test_valid_route import BINARY, _binary_data


# ---------------------------------------------------------------- (a)
@pytest.mark.parametrize("static,want", [
    ((False, 0, 64), ("bins", None)),
    ((True, 0, 64), ("table", "categorical")),
    ((False, 3, 512), ("table", "bundled")),
    ((False, 0, 512), ("table", "wide_bins")),
    ((False, 0, 256), ("bins", None)),
    ((True, 3, 512), ("table", "categorical")),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else None)
def test_route_form_is_chosen_from_what_is_static(static, want):
    assert route_form(*static) == want


# ---------------------------------------------------------------- (b)
def _grow(has_cat: bool):
    """One tree over numerical columns with every missing type; with
    `has_cat` the grower takes the table form (no column IS categorical,
    so the split search finds the same splits)."""
    R, B = 1500, 32
    num_bin = np.array([32, 32, 9, 9, 32, 5], np.int32)
    rng = np.random.RandomState(3)
    bins = np.stack([rng.randint(0, nb, R) for nb in num_bin], 1) \
        .astype(np.int8)
    y = ((bins[:, 0] > 12) + 0.5 * (bins[:, 1] > 20) + 0.3 * (bins[:, 4] > 7)
         + 0.4 * (bins[:, 2] == 8) + 0.05 * rng.randn(R))
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
    meta = FeatureMeta(padf(num_bin), padf([0, 1, 2, 0, 2, 1]),
                       padf([0, 3, 0, 0, 0, 1]), padf(np.zeros(F)),
                       jnp.zeros(F_oh, bool))
    tree, row_leaf, log = grow_tree_fused(
        jnp.asarray(bins_T), gh_T, meta, jnp.asarray(np.arange(F_oh) < F),
        SplitParams(min_data_in_leaf=5, min_gain_to_split=0.5), 8, B, F_oh,
        nch=5, extra_levels=1, interpret=True, has_cat=has_cat, num_rows=R,
        route_log=True)
    leaves = replay_route_log(jnp.asarray(bins_T), log, R, num_bins=Bp,
                              f_oh=F_oh, interpret=True)
    return jax.device_get(tree), np.asarray(row_leaf), \
        np.asarray(leaves)[0], jax.device_get(log)


def test_the_grower_grows_the_same_tree_in_both_forms():
    tree_b, leaf_b, replay_b, (logW_b, tbl_b) = _grow(has_cat=False)
    tree_t, leaf_t, replay_t, (logW_t, tbl_t) = _grow(has_cat=True)
    assert int(tree_b.num_leaves) == 8
    for a, b in zip(tree_b, tree_t):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(leaf_b, leaf_t)
    assert np.array_equal(replay_b, leaf_b) \
        and np.array_equal(replay_t, leaf_t)
    # the bins form logs no [levels, Sp, FB] tables; what both forms log
    # of the slots is the same, and only the bins form fills columns 3-6
    assert logW_b is None and logW_t.shape[2] == 6 * 32 + 2 * 32
    assert np.array_equal(tbl_b[:, :, :3], tbl_t[:, :, :3])
    live = tbl_b[:, :, 0] >= 0
    assert not tbl_t[:, :, 3:].any() and live.sum() == 7 \
        and (tbl_b[:, :, 6][live] >= 0).all()
    # a split on a feature with a missing bin is among them
    assert (tbl_b[:, :, 4][live] >= 0).any()


# ---------------------------------------------------------------- (c)
def _dense_job(out):
    X, y = _binary_data()
    ds = lgb.Dataset(X[:1200], label=y[:1200])
    dv = lgb.Dataset(X[1200:], label=y[1200:], reference=ds)
    return lgb.train(dict(BINARY, telemetry_out=out), ds, num_boost_round=2,
                     valid_sets=[dv])


def _categorical_job(out):
    rng = np.random.RandomState(9)
    X = rng.randn(1500, 5).astype(np.float32)
    X[:, 2] = rng.randint(0, 8, 1500)
    y = (np.isin(X[:, 2], (1, 4, 7)) + 0.5 * (X[:, 0] > 0) > 0.7)
    ds = lgb.Dataset(X[:1100], label=y[:1100].astype(np.float32),
                     categorical_feature=[2])
    bst = lgb.train(dict(BINARY, min_data_per_group=5, cat_smooth=1.0,
                         telemetry_out=out), ds, num_boost_round=2)
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
    (_categorical_job, "table", "categorical"),
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
    other = {"slab": "scratch", "scratch": "slab"}[build]
    assert counters["level.build_%s" % build] == 1
    assert counters.get("level.build_%s" % other, 0) == 0
    assert counters["events.level_build"] == 1
    assert bst.num_trees() == 2
