"""The ranking objective's readers on the hand-made trace of
synthetic_phases.py, with three of its operations renamed to what the
lambdarank gradient's stages are called: 100 ns of ``rank_sort``, 50 ns of
``rank_pairs`` and 200 ns of ``rank_scatter`` in 900 busy ns."""
import pytest

from harness import trace_rank
from synthetic_phases import NAMES, STEP, xspace_named
from test_trace_phases import read, traced_run

GRAD = STEP + "lgbm.gradients/"
RANK_NAMES = dict(
    NAMES, **{
        "fusion.1": (GRAD + "rank_sort/sort", "rank.py:210"),
        "copy.7": (GRAD + "rank_pairs/while/body/mul", "rank.py:250"),
        "fusion.8": (GRAD + "rank_scatter/gather", "rank.py:165")})


def rank_run(tmp_path, names, pairs=25):
    run = traced_run(tmp_path, xspace_named(1, names))
    run.facts.update(chunk_iterations=2, rank_pairs_per_iter=pairs)
    return run


@pytest.mark.parametrize("op_name,stage", [
    (GRAD + "rank_sort/sort", "rank_sort"),
    (GRAD + "rank_pairs/while/body/closed_call/exp", "rank_pairs"),
    (GRAD + "mul", ""),
    (STEP + "lgbm.grow/shard_map/lgbm.score_update/pallas_call", None),
    (STEP + "lgbm.eval/ndcg/sort", None),
    ("", None),
])
def test_stage_of(op_name, stage):
    assert trace_rank.stage_of(op_name) == stage


@pytest.mark.parametrize("name,want", [
    ("objective.gradients_share", 100 * 350 / 900),
    ("objective.rank_sort_share", 100 * 100 / 900),
    # 350 ns in a chunk of 2 iterations, 25 pairs an iteration
    ("objective.rank_ns_per_pair", 350 / 2 / 25),
])
def test_reader(tmp_path, name, want):
    assert read(name, rank_run(tmp_path, RANK_NAMES)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["objective.gradients_share",
                                  "objective.rank_sort_share",
                                  "objective.rank_ns_per_pair"])
def test_reader_says_nothing_without_the_stages(tmp_path, name):
    # the parent's program: it has gradients, and no rank_ stage
    run = rank_run(tmp_path, dict(NAMES, **{
        "fusion.1": (GRAD + "mul", "binary.py:78")}))
    assert read(name, run) is None
    run.window = None                       # an untraced run, a rehearsal
    assert read(name, run) is None


def test_no_counter_no_ns_per_pair(tmp_path):
    run = rank_run(tmp_path, RANK_NAMES, pairs=None)
    assert read("objective.rank_ns_per_pair", run) is None
    assert read("objective.gradients_share", run) is not None
