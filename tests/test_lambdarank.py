"""`objective=lambdarank` + `metric=ndcg` on the fast path.

Three layers, each against something that shares no code with the program:

1. the traced gradient (`objective/rank.py gradients_from`: length buckets,
   per-query sorts, pair planes against each query's top
   `lambdarank_truncation_level`) against `tests/ref_lambdarank.py`, a
   per-query float64 port of the reference's loop;
2. the traced NDCG (`metric/traced.py`: top_k along the planes) and the host one
   against the port's plain NDCG, with tied scores present;
3. `lgb.train` on the fused engine (interpret mode) inside the megastep
   against the plain path (`tpu_fast_path=false`: XLA growers, float32, the
   eager `get_gradients`, the host metric), tree for tree.
"""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.metric import create_metric
from lightgbm_tpu.metric.traced import build_traced_metric
from lightgbm_tpu.utils import dcg
from lightgbm_tpu.utils.query_planes import QueryPlanes

import ref_lambdarank as ref
from test_objectives import setup_obj

# float32 against float64: a lambda is a sum of up to 300 pair terms of
# either sign, each rounded to 6e-8 relative, so |error| <= 1e-5 of the
# value plus 1e-7 of cancellation noise (measured: 0.5 of this budget at
# worst over the cases below). bfloat16 anywhere in the pair arithmetic
# (8 bits of mantissa) misses it by three orders
RTOL, ATOL = 1e-5, 1e-7


def _queries(seed, num_queries=200, longest=300):
    """Heavy-tailed query sizes with the edge cases in: a query of one
    document, the longest, and one whose labels are all equal."""
    rng = np.random.RandomState(seed)
    sizes = np.clip(np.round(rng.lognormal(3.0, 1.0, num_queries)), 1,
                    longest).astype(int)
    sizes[:3] = 1, longest, 7
    qb = np.r_[0, np.cumsum(sizes)]
    n = int(qb[-1])
    label = rng.choice(5, n, p=[.52, .32, .13, .02, .01]).astype(np.float32)
    label[qb[2]:qb[3]] = 2.0
    score = rng.randn(n).astype(np.float32)
    score[rng.rand(n) < 0.2] = 0.25           # ties, within queries too
    weight = (rng.rand(n) + 0.5).astype(np.float32)
    return sizes, qb, label, score, weight


@pytest.mark.parametrize("weighted", [False, True], ids=["", "weights"])
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "nonorm"])
@pytest.mark.parametrize("truncation", [30, 5])
def test_gradients_match_the_port(truncation, norm, weighted):
    sizes, qb, label, score, weight = _queries(0)
    w = weight if weighted else None
    obj = setup_obj("lambdarank", label,
                    {"lambdarank_truncation_level": truncation,
                     "lambdarank_norm": norm}, weight=w, group=sizes)
    # the traced form, through jit, on operands: what the megastep runs
    g, h = jax.jit(obj.gradients_from)(jnp.asarray(score[None, :]),
                                       obj.gradient_operands())
    g_ref, h_ref = ref.lambdarank_gradients(
        label, score, qb, w, norm=norm, truncation_level=truncation)
    np.testing.assert_allclose(np.asarray(g)[0], g_ref, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(h)[0], h_ref, rtol=RTOL, atol=ATOL)
    # the eager entry point is the same function
    g2, h2 = obj.get_gradients(jnp.asarray(score[None, :]))
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g))
    # exact from shapes: the pair set the port's loops visit
    m = np.minimum(sizes, truncation)
    assert obj.pairs_per_iter == int(np.sum(m * sizes - m * (m + 1) // 2))


def test_bfloat16_pair_arithmetic_would_fail_the_tolerance():
    sizes, qb, label, score, _ = _queries(0)
    obj = setup_obj("lambdarank", label, group=sizes)
    rounded = jnp.asarray(score[None, :]).astype(jnp.bfloat16) \
        .astype(jnp.float32)
    g, _ = obj.get_gradients(rounded)
    g_ref, _ = ref.lambdarank_gradients(label, score, qb)
    excess = np.abs(np.asarray(g)[0] - g_ref) / (ATOL + RTOL * np.abs(g_ref))
    assert excess.max() > 1e3


def test_no_plane_is_padded_to_the_longest_query():
    """Operands and planes follow the rows and the buckets: one query of
    1,000 documents among 400 short ones costs its own bucket's row, not
    a [queries, 1000] plane."""
    sizes = np.r_[np.full(400, 10), 1000]
    label = (np.arange(sizes.sum()) % 3).astype(np.float32)
    obj = setup_obj("lambdarank", label, group=sizes)
    assert obj.planes.widths == (128, 1024)
    assert obj.planes.queries == (400, 1)
    assert obj.planes.capacity == (512, 32)
    assert obj.planes.padded_rows == 512 * 128 + 32 * 1024
    biggest = max(int(np.prod(x.shape)) for x in
                  jax.tree_util.tree_leaves(obj.gradient_operands()))
    assert biggest <= obj.planes.padded_rows
    text = jax.jit(obj.gradients_from).lower(
        jnp.zeros((1, label.size)), obj.gradient_operands()).as_text()
    assert "401x1000" not in text and "1000x1000" not in text \
        and "401x1024" not in text and "512x1024" not in text


def test_another_draw_of_the_sizes_runs_the_compiled_program():
    """A plane has its bucket's CAPACITY of rows (the query count rounded
    up to a power of two, fillers of no documents), so two datasets whose
    queries of each length differ in number lower to the same shapes: one
    compile serves both, and the fillers change no gradient."""
    texts = []
    for seed, parts in ((3, [(200, 50), (5, 200), (1, 300)]),
                        (4, [(169, 58), (2, 99), (4, 250), (1, 300)])):
        rng = np.random.default_rng(seed)
        sizes = rng.permutation(np.repeat([n for _, n in parts],
                                          [q for q, _ in parts]))
        assert sizes.sum() == 11_300               # the same rows
        qb = np.r_[0, np.cumsum(sizes)]
        label = rng.integers(0, 5, qb[-1]).astype(np.float32)
        score = rng.standard_normal(qb[-1]).astype(np.float32)
        obj = setup_obj("lambdarank", label, group=sizes)
        assert obj.planes.capacity == (256, 32, 32)
        assert obj.planes.queries != obj.planes.capacity
        fn = jax.jit(obj.gradients_from)
        texts.append(fn.lower(jnp.asarray(score[None, :]),
                              obj.gradient_operands()).as_text())
        g, h = fn(jnp.asarray(score[None, :]), obj.gradient_operands())
        g_ref, h_ref = ref.lambdarank_gradients(label, score, qb)
        np.testing.assert_allclose(np.asarray(g)[0], g_ref, rtol=RTOL,
                                   atol=ATOL)
        np.testing.assert_allclose(np.asarray(h)[0], h_ref, rtol=RTOL,
                                   atol=ATOL)
    assert texts[0] == texts[1]


@pytest.mark.parametrize("name", ["lambdarank", "rank_xendcg"])
def test_multiprocess_row_map_lands_on_the_padded_rows(name):
    """The compacted layout of a multi-process job (query_row_map): the
    same lambdas, on the padded global rows, and no traced operands (that
    path keeps the synchronous driver)."""
    sizes, qb, label, score, _ = _queries(1, num_queries=30, longest=40)
    n = label.size
    row_map = np.r_[np.arange(0, n // 2), np.arange(n // 2 + 5, n + 5)]
    padded = np.zeros(n + 5, np.float32)
    padded[row_map] = label
    md = types.SimpleNamespace(label=padded, weight=None,
                               query_boundaries=qb, query_row_map=row_map)
    from lightgbm_tpu.objective import create_objective
    obj = create_objective(Config({"objective": name}))
    obj.init(md, n)
    assert obj.gradient_operands() is None
    assert not obj.supports_traced_gradients()
    s = np.zeros(n + 5, np.float32)
    s[row_map] = score
    g, h = obj.get_gradients(jnp.asarray(s[None, :]))
    g = np.asarray(g)[0]
    assert g.shape == (n + 5,) and np.isfinite(g).all()
    assert (g[n // 2:n // 2 + 5] == 0).all()
    if name == "lambdarank":
        g_ref, _ = ref.lambdarank_gradients(label, score, qb)
        np.testing.assert_allclose(g[row_map], g_ref, rtol=RTOL, atol=ATOL)


# ------------------------------------------------------------------ NDCG
def _ndcg_metric(label, qb, eval_at):
    m = create_metric("ndcg", Config({"eval_at": eval_at, "verbose": -1}))
    m.init(types.SimpleNamespace(label=label, weight=None,
                                 query_boundaries=qb, query_row_map=None),
           len(label))
    return m


@pytest.mark.parametrize("eval_at", [[1, 3, 5, 10], [2, 1000]])
def test_ndcg_traced_and_host_equal_the_plain_one(eval_at):
    sizes, qb, label, score, _ = _queries(2)
    label[qb[5]:qb[6]] = 0.0          # no relevant document: counts as 1
    m = _ndcg_metric(label, qb, eval_at)
    plain = ref.ndcg_at(eval_at, label, score, qb)
    tm = build_traced_metric(m, None)
    assert tm.names == tuple(f"ndcg@{k}" for k in eval_at)
    traced = jax.jit(tm.fn)(jnp.asarray(score[None, :]), tm.ops)
    # float32 gains, discounts and a mean of 200 per-query terms
    np.testing.assert_allclose([float(v) for v in traced], plain, atol=1e-6,
                               rtol=0)
    np.testing.assert_allclose(m.eval(score[None, :], None), plain,
                               atol=1e-12, rtol=0)
    # operands follow the rows (the gains' planes, each bucket's query
    # count rounded up to a power of two) and the queries: no plane per
    # cutoff, nothing padded to the longest query
    planes = QueryPlanes(qb, min_docs=2)
    slots = planes.padded_rows
    assert planes.capacity == tuple(max(32, 1 << (q - 1).bit_length())
                                    for q in planes.queries)
    assert slots < len(sizes) * sizes.max()
    assert max(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(tm.ops)) <= slots


def test_max_dcg_table_is_max_dcg_at_k_of_every_query():
    sizes, qb, label, _, _ = _queries(3, num_queries=60)
    gain = dcg.default_label_gain(None)
    ks = [1, 5, 30, 1000]
    table = dcg.max_dcg_table(ks, label, qb, gain)
    for q in (0, 1, 2, 17, 59):
        for ki, k in enumerate(ks):
            lab = label[qb[q]:qb[q + 1]]
            assert table[q, ki] == pytest.approx(
                dcg.max_dcg_at_k(k, lab, gain), rel=1e-14)
            assert table[q, ki] == pytest.approx(
                ref.cal_max_dcg_at_k(k, lab, ref.default_label_gain()),
                rel=1e-14)


# ------------------------------------------------- lgb.train, end to end
ITERS, CHUNK = 5, 2
# depth-bounded trees: the fused grower spends a binding leaf budget level
# by level, the plain grower best-first, so only a tree that neither has
# to cut is the same tree on both
PARAMS = {"objective": "lambdarank", "metric": "ndcg",
          "eval_at": [1, 3, 5, 10], "num_leaves": 8, "max_depth": 3,
          "max_bin": 63, "min_data_in_leaf": 5, "learning_rate": 0.1,
          "verbose": -1}
MEGASTEP = {"tpu_engine": "fused", "tpu_megastep": True,
            "tpu_megastep_iters": CHUNK}


def _rank_data(seed, num_queries, feats=6):
    rng = np.random.RandomState(seed)
    sizes = np.clip(np.round(rng.lognormal(2.5, 0.9, num_queries)), 1,
                    157).astype(int)
    sizes[:2] = 157, 1
    n = int(sizes.sum())
    X = rng.rand(n, feats).astype(np.float32)
    rel = X[:, 0] * 2 + X[:, 1] + 0.5 * rng.randn(n)
    y = np.digitize(rel, np.quantile(rel, [.52, .84, .97, .99])) \
        .astype(np.float32)
    return X, y, sizes


def _train(extra, tmp_path=None, record=None):
    X, y, g = _rank_data(0, 120)
    Xv, yv, gv = _rank_data(1, 40)
    ds = lgb.Dataset(X, label=y, group=g)
    dv = lgb.Dataset(Xv, label=yv, group=gv, reference=ds)
    params = dict(PARAMS, **extra)
    if tmp_path is not None:
        params["telemetry_out"] = str(tmp_path / "telemetry.jsonl")
    curve = {}
    bst = lgb.train(params, ds, num_boost_round=ITERS, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(curve),
                               lgb.early_stopping(100, verbose=False)])
    return {"bst": bst, "curve": curve["valid_0"], "sizes": g,
            "rows": len(y), "valid_rows": len(yv),
            "vscores": np.asarray(bst._gbdt.valid_scores[0]),
            "valid": (Xv, yv, gv)}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("rank")
    with pytest.MonkeyPatch.context() as mp:
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
        mp.setattr(GBDT, "_make_megastep", recording)
        fast = _train(MEGASTEP, tmp)
        fast["events"] = [json.loads(line) for line in
                          open(tmp / "telemetry.jsonl")]
        fast["lowered"] = seen["fn"].lower(*seen["avals"]).as_text()
        mp.setattr(GBDT, "_valid_route_reason",
                   lambda self, vi: "test:forced_gather")
        walk = _train(MEGASTEP)
    plain = _train({"tpu_fast_path": False})
    return fast, plain, walk


def test_the_job_stays_on_the_megastep(runs):
    fast, _, _ = runs
    kinds = [e.get("event") for e in fast["events"]]
    assert "degrade" not in kinds and "megastep_evicted" not in kinds
    counters = fast["bst"].telemetry()["counters"]
    assert counters["train.dispatches"] == -(-ITERS // CHUNK)
    assert kinds.count("megastep") == -(-ITERS // CHUNK)
    assert fast["bst"].num_trees() == ITERS
    # the layout's counters, exact from shapes, and its event
    sizes = fast["sizes"]
    m = np.minimum(sizes, 30)
    assert counters["rank.queries"] == len(sizes)
    assert counters["rank.max_docs"] == 157
    assert counters["rank.pairs_per_iter"] == int(
        np.sum(m * sizes - m * (m + 1) // 2))
    (layout,) = [e for e in fast["events"] if e.get("event") == "rank_layout"]
    live = int((sizes >= 2).sum()) - 1
    assert layout["buckets"] == [[128, live, 1 << (live - 1).bit_length()],
                                 [256, 1, 32]]
    assert layout["padded_rows"] == sum(w * c
                                        for w, _, c in layout["buckets"])


def _flat(node, out):
    if "split_index" in node:
        out.append((node["split_feature"], node["threshold"]))
        _flat(node["left_child"], out)
        _flat(node["right_child"], out)
    else:
        out.append(("leaf", node["leaf_value"]))
    return out


def test_the_model_is_the_plain_paths_tree_for_tree(runs):
    fast, plain, _ = runs
    a = fast["bst"].dump_model()["tree_info"]
    b = plain["bst"].dump_model()["tree_info"]
    assert len(a) == len(b) == ITERS
    for ta, tb in zip(a, b):
        fa = _flat(ta["tree_structure"], [])
        fb = _flat(tb["tree_structure"], [])
        # the same splits on the same thresholds in the same places
        assert [x for x in fa if x[0] != "leaf"] \
            == [x for x in fb if x[0] != "leaf"]
        assert [x[0] for x in fa] == [x[0] for x in fb]
        # leaf values: float32 sums of float32 lambdas in another order
        # (one-hot matmul against a scatter-add), shrunk by 0.1
        np.testing.assert_allclose([x[1] for x in fa if x[0] == "leaf"],
                                   [x[1] for x in fb if x[0] == "leaf"],
                                   rtol=0, atol=1e-5)


def test_the_traced_ndcg_history_is_the_plain_paths_and_the_ports(runs):
    fast, plain, _ = runs
    for k in PARAMS["eval_at"]:
        # float32 on the device against the host metric's float64
        np.testing.assert_allclose(fast["curve"][f"ndcg@{k}"],
                                   plain["curve"][f"ndcg@{k}"],
                                   rtol=0, atol=1e-6)
    Xv, yv, gv = fast["valid"]
    own = ref.ndcg_at(PARAMS["eval_at"], yv, fast["bst"].predict(Xv),
                      np.r_[0, np.cumsum(gv)])
    last = [fast["curve"][f"ndcg@{k}"][-1] for k in PARAMS["eval_at"]]
    np.testing.assert_allclose(last, own, rtol=0, atol=1e-6)
    assert last[-1] > fast["curve"]["ndcg@10"][0]


def test_validation_scores_by_replay_are_the_walks(runs):
    fast, _, walk = runs
    assert fast["bst"]._gbdt._valid_route(0)[1] is None
    assert walk["bst"]._gbdt._valid_route(0)[1] == "test:forced_gather"
    np.testing.assert_array_equal(fast["vscores"], walk["vscores"])
    trees = lambda run: run["bst"].model_to_string().split("parameters:")[0]
    assert trees(fast) == trees(walk)


def test_no_array_of_the_step_grows_with_the_longest_query(runs):
    """No dimension of max_docs**2 or queries x max_docs, and no
    [queries, max_docs] plane, anywhere in the lowered step."""
    fast, _, _ = runs
    import re
    queries, longest = len(fast["sizes"]), 157
    shapes = {tuple(int(d) for d in run.split("x") if d)
              for run in re.findall(r"tensor<((?:\d+x)+)", fast["lowered"])}
    assert shapes
    for dims in shapes:
        assert longest not in dims, dims
        assert longest * longest not in dims
        assert queries * longest not in dims
        assert not (queries in dims and 256 in dims), dims
