"""The ``train_goss`` kind end to end on the CPU at a tiny size, through a
rehearsal manifest of its own (``rehearsal-goss.json``: one cell that is in
no ``workloads`` entry of BENCHMARK.json, added by files alone), and the
pieces the kind brings: the five readers on the hand-made trace and on a
run's facts, the bytes of a compaction, the numpy Algorithm 2 of the
reference tool, and the limits of ``correct`` against the reference file."""
import importlib.util
import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH
from harness import cells, work, work_goss
from synthetic_phases import NAMES, STEP, xspace_named
from test_rehearsal import LINE_KEYS, run_cell
from test_trace_phases import read, traced_run

MANIFEST = os.path.join(BENCH, "tests", "rehearsal-goss.json")
CELL = "higgs63-goss.train-goss-28m"
GOSS_LAYERS = {"data.bin_s", "data.upload_pack_s",
               "driver.dispatches_per_iter", "entry.tail_s",
               "startup.backend_init_s", "startup.cache_hit",
               "startup.step_first_call_s", "grower.streamed_rows_share"}


@pytest.mark.parametrize("trace,produced", [
    (0, {"setup_s", "train_s_per_iter"}), (1, GOSS_LAYERS)])
def test_the_goss_cell_runs_on_the_cpu_and_names_it(trace, produced):
    proc = run_cell("rehearsal-goss.train", trace, manifest=MANIFEST,
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
    # two iterations on all 6,000 rows, eight on the sample's 1,800
    assert "root counts [6000, 6000, 1800, 1800, 1800, 1800, 1800, 1800, " \
        "1800, 1800]" in proc.stderr
    assert "goss.bag_rows 14400" in proc.stderr
    # the validation scores the steps carried are the walk's, to float32
    p90 = float(proc.stderr.split("carried scores vs the walk's p90 ")[1]
                .split()[0])
    assert p90 < 5e-6
    if trace:
        # an exact count: every measured tree streamed the capacity (the
        # sample's 1,800 rows rounded up to one 2,048-row tile)
        assert last["rehearsal"]["counts"]["grower.streamed_rows_share"] \
            == pytest.approx(100 * 2048 / 6000)
        assert last["rehearsal"]["counts"][
            "driver.dispatches_per_iter"] == 0.5


def test_the_rehearsal_manifest_finds_its_files():
    manifest = cells.load_manifest(MANIFEST)
    assert manifest["rehearsal"] is True
    (cell,) = manifest["workloads"]
    found = cells.find_cell(manifest, cell["name"])
    assert found["traffic"]["kind"] == "train_goss"
    assert hasattr(cells.load_module("kinds", "train_goss"), "run")
    for m in cells.metrics_of(manifest, "per_layer", cell["name"]):
        assert hasattr(cells.load_module("layers", m["name"]), "read")
    real = cells.find_cell(cells.load_manifest(), CELL)
    assert set(real["traffic"]) == set(found["traffic"])
    # the real cell is higgs63 with three keys added, and its chunks end
    # where sampling starts, inside the warm-up
    higgs = cells.load_json(os.path.join(BENCH, "configs", "higgs63.json"))
    params = real["config"]["params"]
    assert {k: v for k, v in params.items()
            if k not in ("boosting", "top_rate", "other_rate")} \
        == higgs["params"]
    assert (params["boosting"], params["top_rate"], params["other_rate"]) \
        == ("goss", 0.2, 0.1)
    kind = cells.load_module("kinds", "train_goss")
    tr = real["traffic"]
    first, top_k, other_k = kind.sample_rows(tr["rows"], params)
    assert (first, top_k, other_k) == (10, 5_600_000, 2_800_000)
    assert first % tr["chunk_iterations"] == 0
    assert first < tr["warmup_chunks"] * tr["chunk_iterations"]
    assert real["config"]["guarantees"] and real["config"]["architecture"] \
        is None


# ---------------------------------------------------------------- readers
SAMPLE = STEP + "lgbm.sample/"
GOSS_NAMES = dict(
    NAMES, **{
        "fusion.1": (SAMPLE + "select/while/body/reduce_sum", "goss.py:90"),
        "fusion.8": (SAMPLE + "draw/select/while/body/reduce_sum",
                     "goss.py:131"),
        "fusion.6": (SAMPLE + "compact/jit(compact_rows)/cumsum",
                     "goss.py:77"),
        "table_lookup.9": (SAMPLE + "compact/jit(compact_rows)/pallas_call",
                           "goss.py:214")})


@pytest.mark.parametrize("op_name,stages,want", [
    (SAMPLE + "select/reduce_sum", ("select", "draw"), True),
    (SAMPLE + "draw/select/reduce_sum", ("select", "draw"), True),
    (SAMPLE + "compact/jit(compact_rows)/cumsum", ("select", "draw"), False),
    (SAMPLE + "compact/jit(compact_rows)/cumsum", ("compact",), True),
    (SAMPLE + "compact", ("compact",), False),     # a primitive: no scope
    (STEP + "lgbm.eval/select/sort", ("select",), False),
    (SAMPLE + "select/lgbm.grow/add", ("select",), False),
    ("", ("select",), False),
])
def test_under_a_stage_of_the_sampler(op_name, stages, want):
    reader = cells.load_module("layers", "sampler.select_share")
    assert reader.under(op_name, stages) is want


def _goss_run(tmp_path):
    xs = xspace_named(1, GOSS_NAMES).replace("table_lookup.9",
                                             "compact_rows.9")
    run = traced_run(tmp_path, xs)
    run.facts.update(rows=8_400_000, rows_total=28_000_000, features=28,
                     window_trees=[15, 16, 17, 18, 19],
                     streamed=[(5 * 8_400_896, 5)] * 3)
    return run


def test_the_readers_on_the_hand_made_trace(tmp_path):
    """100 ns of select and 200 of draw, 100 ns of the compaction's prefix
    sum and 100 of its kernel, in 900 busy ns."""
    run = _goss_run(tmp_path)
    assert read("sampler.select_share", run) \
        == pytest.approx(100 * 300 / 900)
    assert read("sampler.compact_share", run) \
        == pytest.approx(100 * 200 / 900)
    assert read("grower.streamed_rows_share", run) \
        == pytest.approx(100 * 8_400_896 / 28_000_000)
    least = 5 * work_goss.compact_bytes(28_000_000, 8_400_000, 28) \
        / work.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert read("kernels.compact_roofline", run) \
        == pytest.approx(100 * least / 100e-9)


def test_the_readers_say_nothing_where_there_is_nothing_to_read(tmp_path):
    # the parent's program: no sampler scope, no kernel, no count
    run = traced_run(tmp_path, xspace_named(1, NAMES))
    run.facts.update(rows=8_400_000, rows_total=28_000_000, features=28,
                     window_trees=[15], streamed=[])
    for name in ("sampler.select_share", "sampler.compact_share",
                 "sampler.replay_share", "grower.streamed_rows_share",
                 "kernels.compact_roofline"):
        assert read(name, run) is None, name
    run.window = None                       # an untraced run, a rehearsal
    run.facts["streamed"] = [(20_000, 2)]
    assert read("sampler.select_share", run) is None
    assert read("sampler.replay_share", run) is None
    assert read("kernels.compact_roofline", run) is None
    assert read("grower.streamed_rows_share", run) \
        == pytest.approx(100 * 10_000 / 28_000_000)


@pytest.mark.parametrize("scope,want", [
    ("lgbm.score_update/jit(replay_route_log)/", 100 * 400 / 900),
    # the validation rows' replay is not the training rows'
    ("lgbm.valid_apply/jit(replay_route_log)/", None)])
def test_the_replay_reader_on_the_hand_made_trace(tmp_path, scope, want):
    """300 ns of ``route_pass`` and 100 of the lookup kernel under
    ``lgbm.score_update``, in 900 busy ns; where no ``route_pass`` ran
    under that scope the lookup alone is no replay."""
    names = dict(NAMES, **{"level_pass.2": (
        STEP + scope + "jit(route_pass)/pallas_call", "fused_level.py:700")})
    xs = xspace_named(1, names).replace("level_pass.2", "route_pass.2")
    got = read("sampler.replay_share", traced_run(tmp_path, xs))
    assert got == (want if want is None else pytest.approx(want))


def test_the_bytes_of_a_compaction():
    assert work_goss.padded_features(28) == 32
    # every row: 4 B of destination + 32 B of bins + 16 B of channels;
    # every kept column: 48 B written
    assert work_goss.compact_bytes(1000, 300, 28) == 1000 * 52 + 300 * 48
    assert work_goss.compact_bytes(1000, 300, 8, bin_bytes=2) \
        == 1000 * 36 + 300 * 32


# ------------------------------------------- the reference's Algorithm 2
def _tool():
    path = os.path.join(BENCH, "tools", "reference_auc_goss.py")
    spec = importlib.util.spec_from_file_location("reference_auc_goss", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_reference_samples_as_the_paper_says():
    tool = _tool()
    rng = np.random.default_rng(0)
    g = rng.standard_normal(1000).astype(np.float32)
    h = np.ones(1000, np.float32)
    g[[5, 900]] = 9.0                       # a tie at the top: both kept
    rows, w = tool.goss_rows(g, h, 200, 100, np.random.default_rng([3, 10]))
    assert rows.size == 300 and np.all(np.diff(rows) > 0)
    top = rows[w == 1.0]
    assert top.size == 200 and {5, 900} <= set(top.tolist())
    assert np.abs(g[top]).min() >= np.sort(np.abs(g))[-200]
    assert np.all(w[w != 1.0] == np.float32(800 / 100))
    again, _ = tool.goss_rows(g, h, 200, 100, np.random.default_rng([3, 10]))
    other, _ = tool.goss_rows(g, h, 200, 100, np.random.default_rng([3, 11]))
    assert np.array_equal(rows, again) and not np.array_equal(rows, other)
    plain, w1 = tool.goss_rows(g, h, 200, 100, np.random.default_rng(0),
                               "no_multiplier")
    assert plain.size == 300 and np.all(w1 == 1.0)
    only, _ = tool.goss_rows(g, h, 200, 100, np.random.default_rng(0),
                             "top_only")
    assert np.array_equal(only, np.sort(top))


# ------------------------------------------- the limits of ``correct``
REFERENCE = cells.load_json(os.path.join(BENCH, "reference", CELL + ".json"))
GOOD = dict(leaves=[255] * 30, roots=[28_000_000] * 10 + [8_400_000] * 20,
            bag_rows=8_400_000 * 20)


def _judge(own_auc, traced_off=0.0, score_p90=0.0, **changed):
    kind = cells.load_module("kinds", "train_goss")
    run = types.SimpleNamespace(cell={"name": CELL}, rehearsal=False)
    f = dict(GOOD, **changed)
    return kind.model_problems(
        run, own_auc, own_auc + traced_off, f["leaves"], 255, f["roots"],
        28_000_000, 10, 8_400_000, f["bag_rows"], 30,
        cells.load_module("kinds", "train").AUC_VS_OWN, score_p90)


@pytest.mark.parametrize("origin", ["by_seed", "chip_by_seed"])
def test_every_reading_of_a_sound_model_is_inside_the_band(origin):
    assert REFERENCE[origin]
    for seed, value in REFERENCE[origin].items():
        assert _judge(value) == [], (origin, seed)


@pytest.mark.parametrize("fault", sorted(REFERENCE["faults_outside"]))
def test_a_planted_fault_leaves_the_band(fault):
    (problem,) = _judge(REFERENCE["faults_outside"][fault])
    assert "is not within" in problem


def test_the_carried_scores_tell_the_precision():
    """Every chip reading of the carried scores against the walk passes;
    every reading of the reference with its scores carried in bfloat16 is
    refused, by this limit and no other."""
    limit = REFERENCE["scores_vs_walk"]
    sound, control = REFERENCE["chip_scores_p90"], REFERENCE["bf16_scores_p90"]
    assert len(sound) >= 4 and len(control) >= 6
    assert max(sound.values()) * 10 < limit < min(control.values()) / 10
    for seed, p90 in sound.items():
        assert _judge(REFERENCE["chip_by_seed"][seed],
                      traced_off=REFERENCE["chip_traced_vs_own"][seed],
                      score_p90=p90) == [], seed
    for seed, p90 in control.items():
        (problem,) = _judge(REFERENCE["bf16_auc_walk"][seed],
                            traced_off=REFERENCE["bf16_carried_vs_walk"][seed],
                            score_p90=p90)
        assert "scores the program carried" in problem, seed
    # a program that hands no scores reads infinity
    assert "carried" in _judge(REFERENCE["auc"], score_p90=float("inf"))[0]


def test_the_other_limits_of_the_model():
    auc = REFERENCE["auc"]
    assert "own walk" in _judge(auc, traced_off=2e-3)[0]
    assert "leaves" in _judge(auc, leaves=[255] * 29 + [254])[0]
    # a job that never sampled, and one that sampled from the first tree
    assert "root counts" in _judge(auc, roots=[28_000_000] * 30)[0]
    assert "root counts" in _judge(auc, roots=[8_400_000] * 30)[0]
    assert _judge(auc, roots=[27_999_000] * 10 + [8_400_800] * 20) == []
    assert "goss.bag_rows" in _judge(auc, bag_rows=None)[0]
    assert "goss.bag_rows" in _judge(auc, bag_rows=8_400_000 * 19)[0]
