"""The `lgbm.*` phase scopes of the traced training step
(docs/Observability.md section 3a): every operation of the megastep that
`lgb.train` builds which touches a row-length array carries one, so a
device trace can say whose each fusion is.

The scopes are read from the lowered module's debug locations, i.e. from
the program as JAX wrote it, not from what one backend's compiler made of
it. A location names an operation relative to the function it is in
(``root/broadcast_in_dim`` inside ``@grow_tree_fused``); the call sites
give the rest (``lgbm.grow/jit(grow_tree_fused)``).
"""
import os
import re
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT

# the benchmark's own parser of the scopes (``phase_of``): what the program
# names has to be what the readers of a device trace understand
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from harness.trace_phases import UNSCOPED, phase_of  # noqa: E402

ROWS, VALID_ROWS = 2000, 500
TOP_LEVEL = {"gradients", "gh_pack", "grow", "score_update", "valid_apply"}
EVAL_ONLY = {"freeze", "eval", "early_stop"}
GROW_CHILDREN = {"root", "level", "level/route", "level/hist",
                 "level/split", "level/book"}
# queries of 1 to 300 documents that sum to ROWS and to VALID_ROWS: two
# length buckets (128 and 512 lanes) in training, one in validation
RANK_GROUPS = [1, 300, 99] + [50] * 32
RANK_GROUPS_VALID = [100, 1, 99] + [50] * 6
# carriers of whole carries, not operations on them
STRUCTURAL = {"while", "cond", "closed_call", "shard_map", "body"}


def _lowered_step(monkeypatch, with_eval: bool, learner: str,
                  ranking: bool = False, categorical: bool = False,
                  goss: bool = False) -> str:
    """Debug text of the megastep `lgb.train` built for a small binary
    (or, ``ranking``, lambdarank + NDCG) job, lowered again from the
    shapes it was called with. ``categorical``: columns 2 and 3 hold
    category codes and are given as such. ``goss``: ``boosting=goss``
    sampling from iteration 2 on, four iterations: the LAST step built,
    the sampled one, is the one lowered."""
    seen = {}
    make = GBDT._make_megastep

    def recording(self, chunk, *sample):
        fn = make(self, chunk, *sample)

        def call(*args):
            seen["fn"] = fn
            seen["avals"] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if getattr(x, "committed", False)
                    else None), args)
            return fn(*args)
        return call

    monkeypatch.setattr(GBDT, "_make_megastep", recording)
    rng = np.random.RandomState(0)
    X = rng.rand(ROWS, 8).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float32)
    Xv = rng.rand(VALID_ROWS, 8).astype(np.float32)
    yv = (Xv[:, 0] + Xv[:, 1] > 1).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "verbose": -1, "min_data_in_leaf": 5, "tpu_engine": "fused",
              "tpu_megastep": True, "tpu_megastep_iters": 2,
              "metric": "auc", "tree_learner": learner}
    group = group_v = None
    if ranking:
        params.update(objective="lambdarank", metric="ndcg", eval_at=[1, 10])
        y = np.floor(3 * X[:, 0] + X[:, 1]).astype(np.float32)
        yv = np.floor(3 * Xv[:, 0] + Xv[:, 1]).astype(np.float32)
        group, group_v = RANK_GROUPS, RANK_GROUPS_VALID
    cats = "auto"
    if categorical:
        for c, n in ((2, 40), (3, 9)):
            X[:, c] = np.floor(n * X[:, c] ** 2)
            Xv[:, c] = np.floor(n * Xv[:, c] ** 2)
        y = ((X[:, 2] % 3 == 0) ^ (X[:, 0] > 0.5)).astype(np.float32)
        yv = ((Xv[:, 2] % 3 == 0) ^ (Xv[:, 0] > 0.5)).astype(np.float32)
        params.update(min_data_per_group=20, cat_smooth=5.0)
        cats = [2, 3]
    if goss:
        params.update(boosting="goss", learning_rate=0.5)
    ds = lgb.Dataset(X, label=y, group=group, categorical_feature=cats)
    # with callbacks the scan evaluates the metric itself and carries the
    # early-stop latch; without them it only keeps the validation scores
    lgb.train(params, ds, num_boost_round=4 if goss else 2,
              valid_sets=[lgb.Dataset(Xv, label=yv, group=group_v,
                                      reference=ds)],
              callbacks=[lgb.record_evaluation({})] if with_eval else None)
    return seen["fn"].lower(*seen["avals"]).as_text(debug_info=True), \
        seen["avals"]


_LOC_DEF = re.compile(r"^(#loc\d+) = loc\((.*)\)$", re.M)
_FUNC = re.compile(r"^\s*func\.func \w+ @(\w+)\(")
_CALL = re.compile(r"\bcall @(\w+)\(")
_LOC_USE = re.compile(r"loc\((#loc\d+)\)\s*$")
_DIMS = re.compile(r"tensor<((?:\d+x)+)")


def _scoped_ops(text: str):
    """[(function, op name relative to it, dims it touches)] for every
    one-line operation, and {function: [names of its call sites]}."""
    named = {}
    for key, body in _LOC_DEF.findall(text):
        m = re.match(r'"([^"]*)"', body)
        named[key] = m.group(1) if m else ""
    ops, calls, func = [], {}, None
    for line in text.splitlines():
        m = _FUNC.match(line)
        if m:
            func = m.group(1)
            continue
        use = _LOC_USE.search(line)
        if not use or func is None or "return" in line.split("loc(")[0]:
            continue
        name = named.get(use.group(1), "")
        callee = _CALL.search(line)
        if callee:
            calls.setdefault(callee.group(1), []).append((func, name))
        dims = {int(d) for run in _DIMS.findall(line)
                for d in run.split("x") if d}
        ops.append((func, name, dims))
    return ops, calls


def _full_names(func: str, name: str, calls: dict, depth: int = 0):
    """Every name the operation can have, one per chain of call sites."""
    if func == "main" or depth > 8:
        return [name]
    return [full for caller, site in calls.get(func, [])
            for full in _full_names(caller, f"{site}/{name}", calls,
                                    depth + 1)]


@pytest.mark.parametrize("learner", ["serial", "data"])
@pytest.mark.parametrize("with_eval", [True, False], ids=["eval", "noeval"])
def test_every_row_length_operation_is_scoped(monkeypatch, with_eval,
                                              learner):
    text, avals = _lowered_step(monkeypatch, with_eval, learner)
    bins_T = avals[0]
    shards = len(bins_T.sharding.device_set) if bins_T.sharding else 1
    row_lengths = {ROWS, bins_T.shape[1], bins_T.shape[1] // shards,
                   ROWS // shards, VALID_ROWS}
    ops, calls = _scoped_ops(text)
    phases, unscoped = set(), []
    for func, name, dims in ops:
        for full in _full_names(func, name, calls):
            phase = phase_of(full)
            if phase != UNSCOPED:
                phases.add(phase)
            elif dims & row_lengths \
                    and full.split("/")[-1] not in STRUCTURAL:
                unscoped.append(full)
    assert not unscoped, f"row-length operations outside any lgbm. " \
        f"scope: {sorted(set(unscoped))[:10]}"
    top = {p.split("/")[0] for p in phases}
    assert top == TOP_LEVEL | (EVAL_ONLY if with_eval else set())
    assert GROW_CHILDREN <= {p[len("grow/"):] for p in phases
                             if p.startswith("grow/")}
    if with_eval:
        assert any("lgbm.eval/auc/" in full for func, name, _ in ops
                   for full in _full_names(func, name, calls))


def test_every_row_length_operation_of_the_lambdarank_step_is_scoped(
        monkeypatch):
    """The ranking objective's gradient is no per-row closed form: window
    gathers into length buckets, per-query sorts, pair planes and the way
    back, each under its own child of `lgbm.gradients`; NDCG's planes and
    their top_k under `lgbm.eval/ndcg`."""
    text, avals = _lowered_step(monkeypatch, True, "serial", ranking=True)
    bins_T = avals[0]
    # the rows, and every size the gradient lays them out in: the padded
    # vectors its tiles are cut from and the planes' slot counts
    slots = [128 * 33, 512 * 1]
    tiles = {(-(-ROWS // w) + 1) * w for w in (128, 512)}
    row_lengths = {ROWS, bins_T.shape[1], VALID_ROWS, sum(slots)} | tiles
    ops, calls = _scoped_ops(text)
    names, unscoped = set(), []
    for func, name, dims in ops:
        for full in _full_names(func, name, calls):
            names.add(full)
            if phase_of(full) == UNSCOPED and dims & row_lengths \
                    and full.split("/")[-1] not in STRUCTURAL:
                unscoped.append(full)
    assert not unscoped, f"row-length operations outside any lgbm. " \
        f"scope: {sorted(set(unscoped))[:10]}"
    assert {phase_of(n).split("/")[0] for n in names} - {UNSCOPED} \
        == TOP_LEVEL | EVAL_ONLY
    for stage in ("rank_sort", "rank_pairs", "rank_scatter"):
        assert any(f"lgbm.gradients/{stage}/" in n for n in names), stage
    assert any(n.endswith("lgbm.gradients/rank_sort/sort") for n in names)
    assert any("lgbm.eval/ndcg/" in n and n.endswith("top_k") for n in names)
    assert not any("lgbm.eval/ndcg@" in n for n in names)


def test_every_row_and_histogram_length_operation_of_a_categorical_step_is_scoped(
        monkeypatch):
    """A job with categorical columns routes by ``W @ one_hot`` and searches
    category sets: the search (a sort of every leaf's categories and two
    prefix scans over the bins) has a scope of its own, ``cat``, inside the
    root's and inside ``level/split``, which ``phase_of`` folds into its
    stage; nothing as long as the rows or as large as a histogram plane (8
    features of 64 bins, flat or not) is outside a scope."""
    text, avals = _lowered_step(monkeypatch, True, "serial",
                                categorical=True)
    bins_T = avals[0]
    lengths = {ROWS, bins_T.shape[1], VALID_ROWS, 8 * 64}
    ops, calls = _scoped_ops(text)
    names, unscoped = set(), []
    for func, name, dims in ops:
        for full in _full_names(func, name, calls):
            names.add(full)
            if phase_of(full) == UNSCOPED \
                    and (dims & lengths or {8, 64} <= dims) \
                    and full.split("/")[-1] not in STRUCTURAL:
                unscoped.append(full)
    assert not unscoped, f"row- or histogram-length operations outside " \
        f"any lgbm. scope: {sorted(set(unscoped))[:10]}"
    assert {phase_of(n).split("/")[0] for n in names} - {UNSCOPED} \
        == TOP_LEVEL | EVAL_ONLY
    under_cat = [n for n in names if "/cat/" in n]
    assert {phase_of(n) for n in under_cat} == {"grow/root",
                                                "grow/level/split"}
    assert any(n.endswith("/sort") for n in under_cat)
    assert any("/cat/" in n and "/while/" in n for n in under_cat)
    # the numerical scan of the same call stays outside the scope
    assert any(phase_of(n) == "grow/level/split" and "/cat/" not in n
               for n in names)


def test_every_row_length_operation_of_a_sampled_goss_step_is_scoped(
        monkeypatch):
    """The sampled step of a ``boosting=goss`` job: the counting passes,
    the keys and the second select, the mask's prefix sum and the
    compaction kernel are under ``lgbm.sample`` > ``select`` / ``draw`` /
    ``compact``; the replay of the route log over all rows is under
    ``lgbm.score_update``; nothing as long as the rows or the compact
    matrix is outside a scope."""
    from lightgbm_tpu.ops.goss import goss_plan
    text, avals = _lowered_step(monkeypatch, True, "serial", goss=True)
    bins_T = avals[0]
    plan = goss_plan(ROWS, 0.2, 0.1, 0.5, 3)
    lengths = {ROWS, bins_T.shape[1], VALID_ROWS, plan.capacity,
               plan.bag_rows}
    ops, calls = _scoped_ops(text)
    names, unscoped = set(), []
    for func, name, dims in ops:
        for full in _full_names(func, name, calls):
            names.add(full)
            if phase_of(full) == UNSCOPED and dims & lengths \
                    and full.split("/")[-1] not in STRUCTURAL:
                unscoped.append(full)
    assert not unscoped, f"row-length operations outside any lgbm. " \
        f"scope: {sorted(set(unscoped))[:10]}"
    assert {phase_of(n).split("/")[0] for n in names} - {UNSCOPED} \
        == TOP_LEVEL | EVAL_ONLY | {"sample"}
    for stage in ("select", "draw", "compact"):
        assert any(f"lgbm.sample/{stage}/" in n for n in names), stage
    assert any("lgbm.sample/compact/" in n and "compact_rows" in n
               for n in names)
    assert any("lgbm.score_update/" in n and "route_pass" in n
               for n in names)
