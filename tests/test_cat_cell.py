"""Categorical features used directly, at the column layout of the
benchmark's categorical cell (``expo255-cat``: Month, DayofMonth,
DayOfWeek, DepTime, UniqueCarrier, Origin, Dest, Distance; six
categorical, ``max_bin=255``), small and seeded, on the CPU:

- the categorical split finder of ``ops/split.py`` against the float64
  finder of ``benchmark/tools/reference_auc_cat.py``, which shares no code
  with it, on random histograms;
- one job three ways: the megastep (Pallas kernels in interpret mode), the
  per-iteration step of the fused engine, and the XLA grower on the
  synchronous driver: the same trees;
- the validation replay with categorical levels against
  ``Booster.predict``;
- the benchmark's own walk (``harness/reference_cat``) against
  ``Booster.predict(raw_score=True)`` with unseen categories and NaN;
- the counters, the event and the dump format the cell reads.
"""
import importlib.util
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.split import SplitParams, best_categorical_split_cm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from harness import data_cat, reference_cat  # noqa: E402


def _tool():
    spec = importlib.util.spec_from_file_location(
        "reference_auc_cat", os.path.join(ROOT, "benchmark", "tools",
                                          "reference_auc_cat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TOOL = _tool()

# ------------------------------------------------------------ the finder
# (bins, rows per bin, SplitParams overrides): the sorted-subset search at
# the cell's width, the same with groups that take several categories to
# fill, thin bins that the cat_smooth filter drops, and one against the
# rest. Ties: the histograms are continuous random numbers, so no two
# categories share a gradient-over-hessian ratio and no two prefixes a
# gain; where they would, both finders keep the first (a stable sort, a
# strictly-greater compare, the forward direction first).
FINDER_CASES = {
    "sorted_255": (255, 400.0, {}),
    "sorted_40_groups": (40, 60.0, {"min_data_per_group": 150}),
    "sorted_120_thin_bins": (120, 14.0, {"cat_smooth": 12.0,
                                         "min_data_per_group": 40}),
    "onehot_4": (4, 3000.0, {}),
}


def _histograms(seed, slots, bins, rows_per_bin):
    rng = np.random.RandomState(seed)
    cnt = np.floor(rng.gamma(2.0, rows_per_bin / 2.0, (slots, 1, bins)))
    cnt[..., 0] = 0                      # bin 0: missing, unseen, rare
    hess = cnt * rng.uniform(0.1, 0.25, cnt.shape)
    grad = rng.randn(*cnt.shape) * np.sqrt(hess) * 1.5
    return (grad.astype(np.float32), hess.astype(np.float32),
            cnt.astype(np.float32))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", sorted(FINDER_CASES))
def test_the_categorical_finder_is_the_float64_tools(case, seed):
    bins, rows_per_bin, over = FINDER_CASES[case]
    slots = 6
    p = SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=100.0,
                    **over)
    grad, hess, cnt = _histograms(seed, slots, bins, rows_per_bin)
    got = best_categorical_split_cm(
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(cnt),
        jnp.asarray([bins], jnp.int32), jnp.asarray([True]), p,
        jnp.zeros((slots,), jnp.float32))
    tool_p = dict(TOOL.CAT_DEFAULTS, min_data_in_leaf=0,
                  min_sum_hessian_in_leaf=100.0, **over)
    found = 0
    for s in range(slots):
        want = TOOL.best_categorical(
            grad[s, 0].astype(np.float64), hess[s, 0].astype(np.float64),
            cnt[s, 0].astype(np.float64), tool_p)
        if want is None:
            assert not bool(got.cat_flag[s])
            assert float(got.gain[s]) == -np.inf
            continue
        found += 1
        assert bool(got.cat_flag[s])
        assert set(np.flatnonzero(np.asarray(got.cat_mask[s]))) \
            == set(want["bins"]), (case, seed, s)
        np.testing.assert_allclose(float(got.gain[s]), want["gain"],
                                   rtol=1e-5)
        lg, lh, lc = want["left"]
        np.testing.assert_allclose(
            [float(got.left_sum_grad[s]), float(got.left_sum_hess[s]),
             float(got.left_count[s])], [lg, lh, lc], rtol=1e-5)
        np.testing.assert_allclose(
            [float(got.left_output[s]), float(got.right_output[s])],
            [-lg / (lh + want["l2"]),
             -want["right"][0] / (want["right"][1] + want["l2"])],
            rtol=1e-5)
    assert found >= slots - 1            # the case does find splits


# ------------------------------------------------------- one job, three ways
ROWS, VALID = 3000, 800
BASE = {"objective": "binary", "metric": "auc", "max_bin": 255,
        "num_leaves": 63, "learning_rate": 0.1, "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 12.0, "verbose": -1}
WAYS = {
    "megastep": {"tpu_engine": "fused", "tpu_megastep": True,
                 "tpu_megastep_iters": 2},
    "step": {"tpu_engine": "fused", "tpu_megastep": False},
    "xla": {"tpu_engine": "xla", "tpu_fast_path": False},
}
ITERS = 4


@pytest.fixture(scope="module")
def table():
    return data_cat.make_data(11, ROWS, VALID)


@pytest.fixture(scope="module")
def jobs(table, tmp_path_factory):
    X, y, Xv, yv = table
    out = {}
    for way, extra in WAYS.items():
        tel = str(tmp_path_factory.mktemp(way) / "telemetry.jsonl")
        ds = lgb.Dataset(X, label=y, params={"verbose": -1, "max_bin": 255},
                         categorical_feature=list(data_cat.CATEGORICAL))
        dv = lgb.Dataset(Xv, label=yv, reference=ds)
        curve = {}
        bst = lgb.train(dict(BASE, telemetry_out=tel, **extra), ds,
                        num_boost_round=ITERS, valid_sets=[dv],
                        callbacks=[lgb.record_evaluation(curve),
                                   lgb.early_stopping(100, verbose=False)])
        with open(tel) as fh:
            events = [json.loads(line) for line in fh if line.strip()]
        out[way] = (bst, curve["valid_0"]["auc"], events)
    return out


def _splits_and_leaves(node, leaves):
    """A tree's splits as nested tuples that do not depend on the order its
    leaves were numbered in, (feature, decision, threshold, left, right)
    with None for a leaf; the leaf values are appended to ``leaves`` in the
    same left-to-right order."""
    if "split_index" not in node:
        leaves.append(float(node["leaf_value"]))
        return None
    return (node["split_feature"], node["decision_type"], node["threshold"],
            _splits_and_leaves(node["left_child"], leaves),
            _splits_and_leaves(node["right_child"], leaves))


def _assert_same_tree(a, b):
    assert a["num_leaves"] == b["num_leaves"] < BASE["num_leaves"]
    la, lb = [], []
    sa = _splits_and_leaves(a["tree_structure"], la)
    sb = _splits_and_leaves(b["tree_structure"], lb)
    assert sa == sb
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-9)
    return json.dumps(sa).count('"=="')


def test_megastep_and_per_iteration_step_grow_one_model(jobs):
    """The same splits and bitsets in every tree, leaf values within rtol
    1e-5: the scan body is the per-iteration step's."""
    mine = jobs["megastep"][0].dump_model()["tree_info"]
    theirs = jobs["step"][0].dump_model()["tree_info"]
    assert len(mine) == len(theirs) == ITERS
    cat_nodes = sum(_assert_same_tree(a, b) for a, b in zip(mine, theirs))
    assert cat_nodes >= ITERS            # the model does use the categories


def test_the_fused_and_the_xla_grower_grow_one_first_tree(jobs):
    """From the same gradients the kernels' grower (level schedule, ``W @
    one_hot`` routing, bf16 high and low histogram channels) and the XLA
    grower on the synchronous driver (best-first, float32 scatter-adds)
    grow the same tree, split for split and bitset for bitset: the leaf
    budget (63) never binds at this size (min_sum_hessian_in_leaf does), so
    the order of growth does not show. Later trees start from scores that
    differ in the last float32 digit, on which a prefix of 18 or of 19
    categories can turn; they are held by the AUC curve instead."""
    mine = jobs["megastep"][0].dump_model()["tree_info"]
    theirs = jobs["xla"][0].dump_model()["tree_info"]
    assert len(mine) == len(theirs) == ITERS
    assert _assert_same_tree(mine[0], theirs[0]) >= 1
    np.testing.assert_allclose(jobs["megastep"][1], jobs["xla"][1],
                               atol=2e-3)


def test_the_traced_auc_is_the_host_auc(jobs, table):
    """The metric the scan computes on the device (float32 ranks) against
    the benchmark's rank AUC of ``Booster.predict`` in float64: the
    tolerance is tests/test_traced_eval.py's."""
    _, _, Xv, yv = table
    for way in ("megastep", "step", "xla"):
        bst, curve, _ = jobs[way]
        host = reference_cat.rank_auc(yv, bst.predict(Xv, raw_score=True))
        np.testing.assert_allclose(curve[-1], host, rtol=2e-5, atol=1e-6)
    assert jobs["megastep"][1] == jobs["step"][1]


def test_the_replay_with_categorical_levels_is_predict(jobs, table):
    """The megastep's validation scores come from replaying each tree's
    route log (slot tables whose categorical slots carry their bin
    sets) over the validation bins: they are ``Booster.predict`` on the
    same rows."""
    _, _, Xv, _ = table
    bst, _, events = jobs["megastep"]
    assert [e["path"] for e in events
            if e.get("event") == "valid_route"] == ["kernel"]
    replayed = np.asarray(bst._gbdt.valid_scores[0]).reshape(-1)[:VALID]
    np.testing.assert_allclose(replayed, bst.predict(Xv, raw_score=True),
                               rtol=1e-5, atol=1e-6)


def test_the_job_stays_on_the_megastep_and_says_its_layout(jobs):
    bst, _, events = jobs["megastep"]
    kinds = [e.get("event") for e in events]
    assert "degrade" not in kinds and "megastep_evicted" not in kinds
    assert kinds.count("megastep") == ITERS // 2
    (form,) = [e for e in events if e.get("event") == "route_form"]
    assert (form["form"], form.get("reason"), form["membership"]) \
        == ("bins", None, True)
    (build,) = [e for e in events if e.get("event") == "level_build"]
    assert (build["form"], build["slab_rows"]) == ("slab", 512)
    (layout,) = [e for e in events if e.get("event") == "cat_layout"]
    assert layout["columns"] == list(data_cat.CATEGORICAL)
    mappers = bst._gbdt.train_data.mappers
    assert layout["bins"] == [mappers[c].num_bin for c in layout["columns"]]
    assert max(layout["bins"]) <= 255
    g = bst._gbdt
    assert (g.fused_Bp, g.fused_f_oh) == (256, 8)
    counters = bst.telemetry()["counters"]
    assert counters["route.cat_membership"] == counters["route.form_bins"] \
        == 1 and "route.form_table" not in counters
    trees = reference_cat.flatten(bst.dump_model())
    nodes = sum(t["feature"].size for t in trees)
    assert counters["split.nodes"] == nodes
    assert counters["split.cat_nodes"] == round(
        reference_cat.categorical_share(trees) * nodes) > 0


# ------------------------------------------------ the benchmark's own walk
def _strange_rows(Xv):
    """Validation rows with what training never saw: unseen categories,
    NaN and negative codes in categorical columns."""
    X = Xv.copy()
    rng = np.random.RandomState(5)
    for c, what in ((5, 1234.0), (6, np.nan), (4, 77.0), (0, np.nan),
                    (2, -3.0), (5, np.nan)):
        X[rng.choice(X.shape[0], 60, replace=False), c] = what
    return X


def test_the_benchmarks_walk_is_predict_with_unseen_categories_and_nan(
        jobs, table):
    bst = jobs["megastep"][0]
    X = _strange_rows(table[2])
    trees = reference_cat.flatten(bst.dump_model(num_iteration=-1))
    assert 0.25 <= reference_cat.categorical_share(trees) <= 1.0
    np.testing.assert_allclose(reference_cat.walk(trees, X),
                               bst.predict(X, raw_score=True),
                               rtol=1e-6, atol=1e-9)


def test_the_dump_names_the_categories_of_the_model_files_bitsets(jobs):
    """``dump_model()`` writes a categorical node's threshold as the
    reference does, "a||b||c"; a dump from before it did (the node's index
    into the bitsets, a number) is read through the model text, to the
    same trees."""
    bst = jobs["megastep"][0]
    dump = bst.dump_model(num_iteration=-1)
    new = reference_cat.flatten(dump)
    old_dump = json.loads(json.dumps(dump))
    seen = 0
    for info in old_dump["tree_info"]:
        # bitsets are numbered in node order within a tree
        cat, stack = [], [info["tree_structure"]]
        while stack:
            node = stack.pop()
            if "split_index" in node:
                stack += [node["left_child"], node["right_child"]]
                if node["decision_type"] == "==":
                    cat.append(node)
        for i, n in enumerate(sorted(cat, key=lambda n: n["split_index"])):
            cats = [int(c) for c in n["threshold"].split("||")]
            assert cats == sorted(cats)
            n["threshold"] = float(i)
        seen += len(cat)
    assert seen > 0
    with pytest.raises(ValueError):
        reference_cat.flatten(old_dump)
    old = reference_cat.flatten(old_dump, bst.model_to_string())
    for a, b in zip(new, old):
        assert [None if c is None else c.tolist()
                for c in a["categories"]] \
            == [None if c is None else c.tolist() for c in b["categories"]]
