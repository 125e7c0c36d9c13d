"""The ``train_cat`` kind end to end on the CPU at a tiny size, through a
rehearsal manifest of its own (``rehearsal-cat.json``: one cell of the
categorical cell's column layout that is in no ``workloads`` entry of
BENCHMARK.json, added by files alone), and the pieces the kind brings: the
generator's seed, prefix and held-out properties, the benchmark's own walk
with ``==`` nodes on a hand-made tree, the two readers on the hand-made
trace, and the limits of ``correct`` against the reference file."""
import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH
from harness import cells, data_cat, reference_cat
from synthetic_phases import GROW, NAMES, xspace_named
from test_rehearsal import LINE_KEYS, run_cell
from test_trace_phases import read, traced_run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal-cat.json")
CAT_LAYERS = {"data.bin_s", "data.upload_pack_s",
              "driver.dispatches_per_iter", "entry.tail_s",
              "startup.backend_init_s", "startup.cache_hit",
              "startup.step_first_call_s"}


@pytest.mark.parametrize("trace,produced", [
    (0, {"setup_s", "train_s_per_iter"}), (1, CAT_LAYERS)])
def test_the_categorical_cell_runs_on_the_cpu_and_names_it(trace, produced):
    proc = run_cell("rehearsal-cat.train", trace, manifest=MANIFEST,
                    seed=2 ** 31 + 11)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(last)
    assert last["correct"] is True, last["problems"]
    assert last["failed"] == 0 and last["attempted"] >= 3
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}            # no device metric off the chip
    assert set(last["rehearsal"]["produced"]) == produced
    assert set(last["checks"]) == {"own_auc", "traced_auc"}
    assert abs(last["checks"]["own_auc"] - last["checks"]["traced_auc"]) \
        < 1e-5
    # the share of categorical nodes is checked and said on stderr
    # (harness/output.py prints the two AUCs only)
    assert "checks: categorical_node_share 0." in proc.stderr


def test_the_rehearsal_manifest_finds_its_files():
    manifest = cells.load_manifest(MANIFEST)
    assert manifest["rehearsal"] is True
    (cell,) = manifest["workloads"]
    found = cells.find_cell(manifest, cell["name"])
    assert found["traffic"]["kind"] == "train_cat"
    assert hasattr(cells.load_module("kinds", "train_cat"), "run")
    for m in cells.metrics_of(manifest, "per_layer", cell["name"]):
        assert hasattr(cells.load_module("layers", m["name"]), "read")
    # the real cell's mix and configuration have every key the kind reads
    real = cells.load_json(os.path.join(BENCH, "traffic",
                                        "train-cat-28m.json"))
    assert set(real) == set(found["traffic"])
    real_cfg = cells.load_json(os.path.join(BENCH, "configs",
                                            "expo255-cat.json"))
    for key in ("features", "columns", "categorical_feature"):
        assert real_cfg[key] == found["config"][key]
    assert real_cfg["columns"] == list(data_cat.COLUMNS)
    assert real_cfg["categorical_feature"] == list(data_cat.CATEGORICAL)
    # the categorical search runs at the program's defaults, the reference's
    assert not [k for k in real_cfg["params"]
                if k.startswith(("cat_", "max_cat", "min_data_per"))]


# ---------------------------------------------------------------- data
def test_the_same_seed_gives_the_same_bytes_and_another_seed_others():
    a = data_cat.make_data(2 ** 31 + 3, 5000, 900)
    b = data_cat.make_data(2 ** 31 + 3, 5000, 900)
    c = data_cat.make_data(2 ** 31 + 4, 5000, 900)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype == np.float32 and np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    X, y = a[0], a[1]
    assert X.shape == (5000, 8) and set(np.unique(y)) <= {0.0, 1.0}
    for col, n in data_cat.CARDINALITY.items():
        lo = 1 if col < 3 else 0
        assert np.array_equal(X[:, col], np.floor(X[:, col]))
        assert lo <= X[:, col].min() and X[:, col].max() <= n - 1 + lo
    assert 240 <= X[:, 3].min() and X[:, 3].max() <= 1440
    assert 30 <= X[:, 7].min() and X[:, 7].max() <= 5000


def test_the_held_out_set_is_the_same_whatever_the_seed():
    a = data_cat.make_data(1, 3000, 900)
    b = data_cat.make_data(2, 3000, 900)
    for x, y in zip(a[2:], b[2:]):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0][:900], a[2])    # and is not the train set


def test_a_prefix_of_the_rows_is_the_same_data_whatever_the_total():
    few = data_cat.make_data(9, 1200, 10)
    many = data_cat.make_data(9, 5000, 10)
    assert np.array_equal(few[0], many[0][:1200])
    assert np.array_equal(few[1], many[1][:1200])


def test_the_problem_is_the_one_assumed():
    """Positives near 0.19; the airports' tail is thin enough that the
    reference's vocabulary rule keeps at most 254 of the 300 (255 bins with
    the bin of the rest), in a sample of the size the binning takes."""
    X, y, _, _ = data_cat.make_data(5, 400000, 10)
    assert abs(y.mean() - 0.19) < 0.01
    p = data_cat.shares()
    assert sum(len(v) for v in p.values()) + 2 == 674
    for col in (5, 6):
        assert np.cumsum(p[col])[253] > 0.998 and p[col][0] < 0.1
        _, counts = np.unique(X[:200000, col], return_counts=True)
        assert counts.size > 254           # more airports than bins
    # the label depends on the carrier x origin pairing, not on each alone
    eff = data_cat.effects()
    assert eff["x"].shape == (22, 300) and eff["x"].std() > 0.3


# ------------------------------------------------- the walk with == nodes
def _leaf(i, value):
    return {"leaf_index": i, "leaf_value": value}


HAND_MADE = {"tree_info": [{
    "tree_index": 0, "num_leaves": 3,
    "tree_structure": {
        "split_index": 0, "split_feature": 1, "decision_type": "==",
        "threshold": "2||5||40", "default_left": False,
        "missing_type": "NaN",
        "left_child": {
            "split_index": 1, "split_feature": 0, "decision_type": "<=",
            "threshold": 0.5, "default_left": True, "missing_type": "NaN",
            "left_child": _leaf(0, 1.0), "right_child": _leaf(2, 2.0)},
        "right_child": _leaf(1, -1.0)}}]}


def test_a_categorical_node_sends_its_list_left_and_all_else_right():
    trees = reference_cat.flatten(HAND_MADE)
    assert trees[0]["categories"][0].tolist() == [2, 5, 40]
    assert trees[0]["categories"][1] is None
    assert reference_cat.categorical_share(trees) == 0.5
    X = np.array([[0.1, 2], [0.9, 5], [np.nan, 40.7],   # in the list
                  [0.1, 3], [0.1, 41], [0.1, -2], [0.1, np.nan],
                  [0.1, 1e6]], np.float32)
    assert reference_cat.walk(trees, X).tolist() \
        == [1.0, 2.0, 1.0, -1.0, -1.0, -1.0, -1.0, -1.0]


def test_a_dump_without_the_lists_is_read_through_the_model_text():
    old = json.loads(json.dumps(HAND_MADE))
    old["tree_info"][0]["tree_structure"]["threshold"] = 0.0
    with pytest.raises(ValueError):
        reference_cat.flatten(old)
    # categories 2, 5 and 40 in two 32-bit words
    text = ("tree\nversion=v3\n\nTree=0\nnum_leaves=3\nnum_cat=1\n"
            f"cat_boundaries=0 2\ncat_threshold={(1 << 2) | (1 << 5)} "
            f"{1 << 8}\n\nend of trees\n")
    trees = reference_cat.flatten(old, text)
    assert trees[0]["categories"][0].tolist() == [2, 5, 40]


# ---------------------------------------------------------------- readers
SPLIT = GROW + "level/cond/branch_1_fun/split/"
CAT_NAMES = dict(
    NAMES, **{
        "fusion.1": (SPLIT + "cat/jit(best_categorical_split_cm)/sort",
                     "split.py:470"),
        "copy.7": (GROW + "root/cat/jit(best_categorical_split_cm)/while/"
                   "body/add", "split.py:500"),
        "fusion.8": (SPLIT + "jit(best_numerical_split_cm)/cumsum",
                     "split.py:200"),
        "table_lookup.9": (NAMES["table_lookup.9"][0].replace(
            "table_lookup", "route_pass"), "fused_level.py:700")})


@pytest.mark.parametrize("op_name,want", [
    (SPLIT + "cat/jit(best_categorical_split_cm)/sort", True),
    (GROW + "root/cat/while/body/add", True),
    (SPLIT + "jit(best_numerical_split_cm)/cumsum", False),
    (SPLIT + "cat", False),               # a primitive called cat: no scope
    ("jit(step)/lgbm.eval/cat/sort", False),
    ("", False),
])
def test_under_cat(op_name, want):
    reader = cells.load_module("layers", "grower.cat_split_share")
    assert reader.under_cat(op_name) is want


def test_the_two_readers_on_the_hand_made_trace(tmp_path):
    """100 ns of the categorical sort and 50 ns of its scan in 900 busy ns;
    the 100 ns kernel renamed to a ``route_pass`` launch."""
    xs = xspace_named(1, CAT_NAMES).replace("table_lookup.9", "route_pass.9")
    run = traced_run(tmp_path, xs)
    assert read("grower.cat_split_share", run) \
        == pytest.approx(100 * 150 / 900)
    assert read("kernels.route_pass_share", run) \
        == pytest.approx(100 * 100 / 900)


def test_the_readers_say_nothing_where_there_is_nothing_to_read(tmp_path):
    # the parent's program: a grower, and no cat scope
    run = traced_run(tmp_path, xspace_named(1, NAMES))
    assert read("grower.cat_split_share", run) is None
    assert read("kernels.route_pass_share", run) == 0.0
    run.window = None                       # an untraced run, a rehearsal
    assert read("grower.cat_split_share", run) is None
    assert read("kernels.route_pass_share", run) is None


# ------------------------------------------- the limits of ``correct``
CELL = "expo255-cat.train-cat-28m"
REFERENCE = cells.load_json(os.path.join(BENCH, "reference", CELL + ".json"))


def _judge(own_auc, traced_off=0.0, cat_share=0.7, leaves=(255,) * 8):
    kind = cells.load_module("kinds", "train_cat")
    run = types.SimpleNamespace(cell={"name": CELL}, rehearsal=False)
    return kind.model_problems(run, own_auc, own_auc + traced_off, cat_share,
                               list(leaves), 255,
                               cells.load_module("kinds", "train").AUC_VS_OWN)


@pytest.mark.parametrize("origin", ["by_seed", "chip_by_seed"])
def test_every_reading_of_a_sound_model_is_inside_the_band(origin):
    assert REFERENCE[origin]
    for seed, value in REFERENCE[origin].items():
        assert _judge(value) == [], (origin, seed)


@pytest.mark.parametrize("fault", sorted(REFERENCE["faults"]))
def test_a_planted_fault_leaves_the_band(fault):
    (problem,) = _judge(REFERENCE["faults"][fault])
    assert "is not within" in problem


def test_the_other_limits_of_the_model():
    auc = REFERENCE["auc"]
    assert "own walk" in _judge(auc, traced_off=2e-3)[0]
    assert "categorical" in _judge(auc, cat_share=0.2)[0]
    assert "leaves" in _judge(auc, leaves=(255,) * 7 + (254,))[0]
    # scores rounded to bfloat16 move the AUC by more than the limit
    # between the traced AUC and the benchmark's own
    for seed, moved in REFERENCE["bf16_scores_move"].items():
        assert "own walk" in _judge(auc, traced_off=moved)[0], seed
