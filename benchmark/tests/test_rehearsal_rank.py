"""The ``train_rank`` kind end to end on the CPU at a tiny size, through a
rehearsal manifest of its own (``rehearsal-rank.json``: one ranking cell
that is in no ``workloads`` entry of BENCHMARK.json, added by files
alone), and the two pieces the kind brings: the benchmark's own NDCG on
hand-worked queries and the ranking data's seed and prefix properties."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from harness import cells, data_rank, reference_rank
from test_rehearsal import LINE_KEYS, run_cell

MANIFEST = os.path.join(BENCH, "tests", "rehearsal-rank.json")
RANK_LAYERS = {"data.bin_s", "data.upload_pack_s",
               "driver.dispatches_per_iter", "entry.tail_s",
               "startup.backend_init_s", "startup.cache_hit",
               "startup.step_first_call_s"}


@pytest.mark.parametrize("trace,produced", [
    (0, {"setup_s", "train_s_per_iter"}), (1, RANK_LAYERS)])
def test_the_ranking_cell_runs_on_the_cpu_and_names_it(trace, produced):
    proc = run_cell("rehearsal-rank.train", trace, manifest=MANIFEST,
                    seed=2 ** 31 + 11)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert LINE_KEYS <= set(last)
    assert last["correct"] is True, last["problems"]
    assert last["failed"] == 0 and last["attempted"] >= 3
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {}            # no device metric off the chip
    assert set(last["rehearsal"]["produced"]) == produced
    # harness/output.py prints only AUCs under "checks": a ranking cell
    # has none, its NDCGs (the program's traced and the benchmark's own)
    # decide ``correct`` and are said on stderr
    assert last["checks"] == {}
    assert "ndcg own {1: " in proc.stderr


def test_the_rehearsal_manifest_finds_its_files():
    manifest = cells.load_manifest(MANIFEST)
    assert manifest["rehearsal"] is True
    (cell,) = manifest["workloads"]
    found = cells.find_cell(manifest, cell["name"])
    assert found["traffic"]["kind"] == "train_rank"
    assert hasattr(cells.load_module("kinds", "train_rank"), "run")
    for m in cells.metrics_of(manifest, "per_layer", cell["name"]):
        assert hasattr(cells.load_module("layers", m["name"]), "read")
    # the real cell's mix has every key the kind reads
    real = cells.load_json(os.path.join(BENCH, "traffic",
                                        "train-rank-6m8.json"))
    assert set(real) == set(found["traffic"])


def test_a_program_that_leaves_the_fast_path_is_stopped(tmp_path):
    """The watch on the telemetry stream: the first eviction event ends
    the process with the kind's exit code, and nothing else does."""
    import subprocess
    import sys
    kind = os.path.join(BENCH, "kinds", "train_rank.py")
    script = f"""
import importlib.util, sys, time
sys.path.insert(0, {BENCH!r})
spec = importlib.util.spec_from_file_location("k", {kind!r})
k = importlib.util.module_from_spec(spec); spec.loader.exec_module(k)
path = sys.argv[1]
with k._EvictionWatch(path, poll_s=0.02):
    with open(path, "a") as fh:
        fh.write('{{"event": "megastep", "iterations": 2}}\\n'); fh.flush()
        time.sleep(0.2)
        print("still here", flush=True)
        fh.write('{{"event": "megastep_evicted", "feature": "x"}}\\n')
        fh.flush()
        time.sleep(5)
print("not reached")
"""
    proc = subprocess.run([sys.executable, "-c", script,
                           str(tmp_path / "telemetry.jsonl")],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 3
    assert proc.stdout.strip() == "still here"
    assert "left its fast path" in proc.stderr


# ---------------------------------------------------------------- NDCG
def test_ndcg_on_hand_worked_queries():
    # query 1: labels 2, 0, 1 scored 0.1, 0.9, 0.5: ranked 0, 1, 2
    #   DCG@3 = 0/1 + 1/log2(3) + 3/2, ideal 3 + 1/log2(3) + 0
    # query 2: no relevant document: 1 at every cutoff
    # query 3: one document, relevant: 1
    label = np.array([2, 0, 1, 0, 0, 4], np.float32)
    score = np.array([.1, .9, .5, .3, .2, .0])
    group = np.array([3, 2, 1])
    l3 = 1 / np.log2(3)
    want = {1: (0 / 3 + 1 + 1) / 3,
            3: ((l3 + 1.5) / (3 + l3) + 1 + 1) / 3}
    got = reference_rank.ndcg_at([1, 3, 10], label, score, group)
    assert got[0] == pytest.approx(want[1], abs=1e-15)
    assert got[1] == pytest.approx(want[3], abs=1e-15)
    assert got[2] == pytest.approx(want[3], abs=1e-15)   # k past the end


def test_ndcg_ties_keep_row_order():
    label = np.array([0, 3], np.float32)
    tied = reference_rank.ndcg_at([1], label, np.zeros(2), np.array([2]))
    assert tied == [0.0]                  # the first row stays first
    flipped = reference_rank.ndcg_at([1], label[::-1], np.zeros(2),
                                     np.array([2]))
    assert flipped == [1.0]


# ---------------------------------------------------------------- data
ARGS = dict(rows=5000, queries=100, valid_rows=900, valid_queries=20,
            features=137, longest=300)


def test_the_same_seed_gives_the_same_bytes_and_another_seed_others():
    a = data_rank.make_data(2 ** 31 + 3, **ARGS)
    b = data_rank.make_data(2 ** 31 + 3, **ARGS)
    c = data_rank.make_data(2 ** 31 + 4, **ARGS)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert sorted(a[2]) != sorted(c[2])            # the sizes are drawn
    X, y, group, Xv, yv, group_v = a
    assert X.shape == (5000, 137) and X.dtype == np.float32
    assert group.sum() == 5000 and group_v.sum() == 900
    assert len(group) == 100 and len(group_v) == 20
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_the_held_out_set_is_the_same_whatever_the_seed():
    """Models of different seeds are judged on the same queries, so their
    NDCG differs by the model and not by the draw of the judges."""
    a = data_rank.make_data(1, **ARGS)
    b = data_rank.make_data(2, **ARGS)
    for x, y in zip(a[3:], b[3:]):
        assert np.array_equal(x, y)
    assert not np.array_equal(a[0][:900], a[3])    # and is not the train set


def test_the_sizes_are_drawn_from_the_seed_and_hold_the_range():
    sizes = [data_rank.make_data(seed, **ARGS)[2] for seed in (1, 2)]
    assert sorted(sizes[0]) != sorted(sizes[1])
    for a in sizes:
        assert a.sum() == 5000 and a.min() == 1 and a.max() == 300
    for seed in (0, 2 ** 31 + 7):
        big = data_rank.query_sizes(np.random.default_rng(seed), 6810888,
                                    56757, 1251)
        assert big.sum() == 6810888 and big.min() == 1 \
            and big.max() == 1251
        assert 90 < np.median(big) < 100        # log-normal: median < mean
    with pytest.raises(ValueError):
        data_rank.query_sizes(np.random.default_rng(0), 10, 20, 5)


def test_a_prefix_of_the_rows_has_the_same_features_whatever_the_total():
    few = data_rank.make_data(9, **dict(ARGS, rows=1200, queries=30))
    many = data_rank.make_data(9, **ARGS)
    assert np.array_equal(few[0], many[0][:1200])


def test_relevance_marginals_are_the_ones_assumed():
    _, y, _, _, _, _ = data_rank.make_data(
        5, 200000, 1700, 900, 20, 137, 300)
    share = np.bincount(y.astype(int), minlength=5) / y.size
    assert np.abs(share - [0.52, 0.32, 0.13, 0.02, 0.01]).max() < 0.01


# ------------------------------------------- the limits of ``correct``
CELL = "msltr63.train-rank-6m8"
REFERENCE = cells.load_json(os.path.join(BENCH, "reference", CELL + ".json"))
TRAFFIC = cells.load_json(os.path.join(BENCH, "traffic",
                                      "train-rank-6m8.json"))
KS = (1, 3, 5, 10)


def _judge(own10, traced_off=None):
    """``model_problems`` of the real cell for a model whose own NDCG@10
    is ``own10`` and whose traced NDCG is off by ``traced_off`` (per
    cutoff) from the benchmark's own."""
    import types
    kind = cells.load_module("kinds", "train_rank")
    run = types.SimpleNamespace(cell={"name": CELL}, rehearsal=False,
                                traffic=TRAFFIC)
    own = {k: 0.5 for k in KS}
    own[10] = own10
    traced = {k: own[k] + (traced_off or {}).get(f"ndcg@{k}", 0.0)
              for k in KS}
    return kind.model_problems(run, own, traced)


@pytest.mark.parametrize("origin", ["by_seed", "chip_by_seed"])
def test_every_reading_of_a_sound_model_is_inside_the_band(origin):
    assert REFERENCE[origin]
    for seed, value in REFERENCE[origin].items():
        assert _judge(value) == [], (origin, seed)
    assert _judge(REFERENCE["ndcg@10"], REFERENCE["chip_traced_vs_own"]) \
        == []


@pytest.mark.parametrize("fault", sorted(REFERENCE["faults"]))
def test_a_planted_fault_leaves_the_band(fault):
    """Readings of ``tools/reference_ndcg.py`` with one fault each (a tree
    fewer or more: the curve's neighbours; ``--fault`` for the rest)."""
    (problem,) = _judge(REFERENCE["faults"][fault])
    assert "is not within" in problem


@pytest.mark.parametrize("seed", sorted(REFERENCE["bf16_scores_move"]))
def test_scores_rounded_to_bfloat16_are_refused(seed):
    """The tool's reading, seed by seed, of how far each cutoff's NDCG
    moves when the model's validation scores are rounded to bfloat16,
    against the limits between the program's traced NDCG and the
    benchmark's own: one cutoff at least is over its limit on every seed,
    and the chip's own largest difference is under a hundredth of the
    least."""
    problems = _judge(REFERENCE["ndcg@10"],
                      REFERENCE["bf16_scores_move"][seed])
    assert problems and all("own walk" in p for p in problems)
    kind = cells.load_module("kinds", "train_rank")
    worst = max(REFERENCE["chip_traced_vs_own"].values())
    assert 100 * worst < kind.ndcg_vs_own_limit(10, 6306) < 3e-5
