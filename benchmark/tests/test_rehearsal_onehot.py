"""The ``train_onehot`` kind end to end on the CPU at a tiny size, through a
rehearsal manifest of its own (``rehearsal-onehot.json``: one cell of the
one-hot cell's column layout that is in no ``workloads`` entry of
BENCHMARK.json, added by files alone), and the pieces the kind brings: the
one-hot coding of the categorical cell's table, the walk over the logical
CSR, the float64 root scan, the three new readers on hand-made streams
and traces, and the limits of ``correct``."""
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from conftest import BENCH
from harness import (cells, data_cat, data_onehot, reference,
                     reference_onehot)
from synthetic_phases import NAMES, xspace_named
from test_layers_setup import span
from test_rehearsal import LINE_KEYS, run_cell
from test_trace_phases import read, traced_run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal-onehot.json")
ONEHOT_LAYERS = {"data.bin_s", "data.upload_pack_s", "data.bin_rows_s",
                 "data.efb_bundle_s", "data.sparse_bin_s",
                 "driver.dispatches_per_iter", "entry.tail_s",
                 "startup.backend_init_s", "startup.cache_hit",
                 "startup.step_first_call_s"}


@pytest.mark.parametrize("trace,produced", [
    (0, {"setup_s", "train_s_per_iter"}), (1, ONEHOT_LAYERS)])
def test_the_onehot_cell_runs_on_the_cpu_and_names_it(trace, produced):
    proc = run_cell("rehearsal-onehot.train", trace, manifest=MANIFEST,
                    seed=2 ** 31 + 13)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(last)
    assert last["correct"] is True, last["problems"]
    assert last["failed"] == 0 and last["attempted"] >= 3
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}            # no device metric off the chip
    assert set(last["rehearsal"]["produced"]) == produced
    assert abs(last["checks"]["own_auc"] - last["checks"]["traced_auc"]) \
        < 1e-5
    # the rest of what was checked is said on stderr; the job ran bundled
    # in the bins form, with nothing lost to a conflict
    said = proc.stderr
    assert "checks: onehot_node_share 0." in said
    assert "'problem': None" in said
    assert "'conflict_rows': 0, 'form': 'bins'" in said


def test_a_run_past_its_deadline_ends_with_no_result():
    """The kind's deadline: a run still busy ``DEADLINE_S`` after its
    process started ends with exit code 3 and prints nothing on standard
    output (the job stands in for a program too slow for the contract)."""
    code = ("import sys, time, types; sys.path.insert(0, %r); "
            "from harness import cells; "
            "k = cells.load_module('kinds', 'train_onehot'); "
            "k._run = lambda run: time.sleep(30); "
            "k.run(types.SimpleNamespace("
            "t_start=time.time() - k.DEADLINE_S + 1.0)); print('done')"
            % BENCH)
    t0 = time.time()
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert proc.stdout == ""
    assert "is not done" in proc.stderr
    assert time.time() - t0 < 20


def test_the_rehearsal_manifest_finds_its_files():
    manifest = cells.load_manifest(MANIFEST)
    assert manifest["rehearsal"] is True
    (cell,) = manifest["workloads"]
    found = cells.find_cell(manifest, cell["name"])
    assert found["traffic"]["kind"] == "train_onehot"
    assert hasattr(cells.load_module("kinds", "train_onehot"), "run")
    for m in cells.metrics_of(manifest, "per_layer", cell["name"]):
        assert hasattr(cells.load_module("layers", m["name"]), "read")
    # the real cell's mix and configuration have every key the kind reads
    real = cells.load_json(os.path.join(BENCH, "traffic",
                                        "train-onehot-28m.json"))
    assert set(real) == set(found["traffic"])
    real_cfg = cells.load_json(os.path.join(BENCH, "configs",
                                            "expo-onehot-efb.json"))
    assert real_cfg["features"] == found["config"]["features"] == 674
    assert real_cfg["columns"] == list(data_onehot.COLUMNS)
    assert "categorical_feature" not in real_cfg
    # the categorical cell's parameters, the reference's Expo settings
    cat_cfg = cells.load_json(os.path.join(BENCH, "configs",
                                           "expo255-cat.json"))
    assert real_cfg["params"] == cat_cfg["params"]


# ---------------------------------------------------------------- data
def test_the_csr_is_the_categorical_table_one_hot_coded():
    X, y, Xv, yv = data_onehot.make_data(2 ** 31 + 3, 5000, 900)
    raw, y_raw, raw_v, yv_raw = data_cat.make_data(2 ** 31 + 3, 5000, 900)
    assert np.array_equal(y, y_raw) and np.array_equal(yv, yv_raw)
    assert X.shape == (5000, 674) and X.dtype == np.float32
    assert np.array_equal(X.indptr, 8 * np.arange(5001))
    dense = X.toarray()
    for c, (first, width, lo) in enumerate(data_onehot.blocks()):
        block = dense[:, first:first + width]
        if c in data_cat.CATEGORICAL:
            assert np.array_equal(block.sum(1), np.ones(5000))
            assert np.array_equal(block.argmax(1) + lo, raw[:, c])
        else:
            assert np.array_equal(block[:, 0], raw[:, c])
            assert data_onehot.COLUMNS[first] == data_cat.COLUMNS[c]
    assert data_onehot.NUMERICAL == (50, 673)
    again = data_onehot.make_data(2 ** 31 + 3, 5000, 900)
    for a, b in zip((X, Xv), (again[0], again[2])):
        assert (a != b).nnz == 0


# ------------------------------------------- the walk and the root scan
def test_the_walk_over_row_blocks_is_the_dense_walk(monkeypatch):
    X, _, _, _ = data_onehot.make_data(5, 3000, 10)
    rng = np.random.RandomState(0)
    tree = {"feature": np.array([50, 80, 673], np.int32),
            "threshold": np.array([700.0, 1e-35, 900.0]),
            "default_left": np.zeros(3, bool),
            "missing": np.zeros(3, np.int8),
            "left": np.array([1, ~0, ~2], np.int32),
            "right": np.array([2, ~1, ~3], np.int32),
            "leaf_value": rng.randn(4)}
    monkeypatch.setattr(reference_onehot, "BLOCK", 700)
    np.testing.assert_array_equal(
        reference_onehot.walk_csr([tree], X),
        reference.walk([tree], X.toarray()))


def test_the_root_scan_finds_the_planted_split():
    """Rows of category 2 of a three-wide block are all positive: the
    scan's best split sends that one-hot column right, with the gain
    the reference's equations give."""
    import scipy.sparse as sp
    rng = np.random.RandomState(1)
    code = rng.randint(0, 3, 4000)
    x = rng.rand(4000).astype(np.float32) + 1.0
    X = sp.csr_matrix(np.c_[np.eye(3)[code], x].astype(np.float32))
    y = ((code == 2) | (rng.rand(4000) < 0.1)).astype(np.float32)
    bounds = {j: np.array([1e-35, np.inf]) for j in range(3)}
    bounds[3] = np.r_[np.quantile(x, np.linspace(0.1, 0.9, 9)), np.inf]
    found = reference_onehot.root_split(
        reference_onehot.root_histograms(X, y, bounds),
        {"min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 1.0})
    gain, col, thr = found[0]
    assert (col, thr) == (2, 0)
    y64 = y.astype(np.float64)
    p = y64.mean()
    g, h = p - y64, np.full(4000, p * (1 - p))
    r = code == 2
    want = (g[r].sum() ** 2 / h[r].sum() + g[~r].sum() ** 2 / h[~r].sum()
            - g.sum() ** 2 / h.sum())
    assert gain == pytest.approx(want, rel=1e-12)
    assert all(s[0] <= gain for s in found)


# ---------------------------------------------------------------- readers
def _run_with(events):
    return types.SimpleNamespace(events=events, traffic={"warmup_chunks": 1})


def test_the_two_setup_readers_on_a_hand_made_stream():
    events = [span("bin/sparse/csc", 10, 2.0, "bin/rows"),
              span("bin/rows", 10, 2.0, "bin"),
              span("bin/sparse/sample", 12, 3.0, "bin"),
              span("bin/bundle/find", 15, 1.5, "bin"),
              span("bin/bundle/encode", 16.5, 4.0, "bin/rows"),
              span("bin/rows", 16.5, 4.0, "bin"),
              span("bin", 10, 11.0),
              # the validation set: its column pass and encode
              span("bin/sparse/csc", 22, 0.25, "bin/rows"),
              span("bin/bundle/encode", 22.25, 0.5, "bin/rows"),
              span("bin/rows", 22, 0.75, "bin"),
              span("train", 30, 10.0)]
    run = _run_with(events)
    assert read("data.efb_bundle_s", run) == pytest.approx(6.0)
    assert read("data.sparse_bin_s", run) == pytest.approx(5.25)
    # the row-length work sits under bin/rows, which the existing reader
    # sums
    assert read("data.bin_rows_s", run) == pytest.approx(6.75)
    # the parent's program: no such spans, nothing to read
    bare = _run_with([span("bin", 10, 11.0), span("train", 30, 10.0)])
    assert read("data.efb_bundle_s", bare) is None
    assert read("data.sparse_bin_s", bare) is None


def test_the_bundled_route_share_is_the_route_share_reader(tmp_path):
    xs = xspace_named(1, NAMES).replace("table_lookup.9", "route_pass.9")
    run = traced_run(tmp_path, xs)
    assert read("kernels.route_pass_bundled_share", run) \
        == read("kernels.route_pass_share", run) \
        == pytest.approx(100 * 100 / 900)
    run.window = None
    assert read("kernels.route_pass_bundled_share", run) is None


# ------------------------------------------- the limits of ``correct``
CELL = "expo-onehot-efb.train-onehot-28m"
REFERENCE = cells.load_json(os.path.join(BENCH, "reference", CELL + ".json"))
ROOT_OK = {"problem": None}


def _judge(own_auc, traced_off=0.0, share=0.5, leaves=(255,) * 8,
           walk=1e-6, root=ROOT_OK):
    kind = cells.load_module("kinds", "train_onehot")
    run = types.SimpleNamespace(cell={"name": CELL}, rehearsal=False)
    return kind.model_problems(run, own_auc, own_auc + traced_off, share,
                               list(leaves), 255,
                               cells.load_module("kinds", "train").AUC_VS_OWN,
                               walk, root)


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
    assert "one-hot" in _judge(auc, share=0.2)[0]
    assert "leaves" in _judge(auc, leaves=(255,) * 7 + (254,))[0]
    assert "logical columns" in _judge(auc, walk=2e-4)[0]
    assert "root" in _judge(auc, root={"problem": "x"})[0]
    # validation scores rounded to bfloat16 move by more than the limit
    # against the walk (their AUC hardly moves: the file says by how much)
    for seed, moved in REFERENCE["bf16_scores_move"].items():
        assert "logical columns" in _judge(auc, walk=moved)[0], seed
