"""The phase readers: ``harness/trace_phases.py`` and the five per-layer
metrics built on it, on a hand-made trace whose every number is known
(synthetic_phases.py) and on one steady chunk recorded on the chip.

The recorded chunk (``data/v5e_phases_chunk.textproto.gz``) is step 0 of
issue 25: ``lgb.train`` of the ``higgs63`` parameters on 2,000,000 x 28
rows with 95,238 validation rows, chunks of 2 iterations, one TPU v5e,
this repo at PR 25 with the ``lgbm.*`` scopes in. It is cut to the second
chunk and keeps what the readers read and nothing else: each device
operation's instruction name, opcode and custom-call target, its
metadata's ``tf_op`` and ``source`` stats, the runs of the step, and the
program thread's ``GBDT::*`` sections and step annotation.
"""
import json
import os
import types

import pytest
from jax.profiler import ProfileData

from conftest import BENCH
from harness import cells, context, trace_phases as tp, trace_reduce as tr
from synthetic_phases import NAMES, xspace_named
from test_rehearsal import MANIFEST, run_cell

RECORDED = os.path.join(BENCH, "tests", "data",
                        "v5e_phases_chunk.textproto.gz")
NEW = ("driver.phase_coverage", "grower.glue_share", "driver.update_share",
       "driver.eval_share", "driver.drain_host_ms")


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/while/body/closed_call/lgbm.gradients/mul", "gradients"),
    ("jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)/"
     "root/jit(level_pass)/pallas_call", "grow/root"),
    ("jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)/"
     "level/cond/branch_1_fun/cond/branch_0_fun/route/jit(_where)/select_n",
     "grow/level/route"),
    ("jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)/"
     "level/jit(argsort)/sort", "grow/level"),
    # what XLA makes for the cond itself: all a skipped level costs
    ("jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)/"
     "level/cond/branch_1_fun/cond", "grow/level/skip"),
    # the primitive is never a scope: jnp.split under `book` stays `book`
    ("a/lgbm.grow/jit(f)/level/cond/branch_1_fun/book/split",
     "grow/level/book"),
    ("a/lgbm.grow/jit(f)/level/split", "grow/level"),
    ("jit(step)/while/body/closed_call/lgbm.grow/jit(grow_tree_fused)",
     "grow"),
    # the innermost lgbm. scope: the lookup inside the grower's shard_map
    ("a/lgbm.grow/shard_map/lgbm.score_update/jit(table_lookup)/pallas_call",
     "score_update"),
    ("a/lgbm.eval/auc/jit(argsort)/sort", "eval/auc"),
    ("a/lgbm.eval/concatenate", "eval"),
    ("a/lgbm.early_stop/jit(_where)/select_n", "early_stop"),
    ("jit(step)/while/body/dynamic_update_slice", tp.UNSCOPED),
    ("", tp.UNSCOPED),
])
def test_phase_of(op_name, phase):
    assert tp.phase_of(op_name) == phase


def traced_run(tmp_path, text_proto, devices=1):
    """A run whose scratch holds ``text_proto`` where the profiler would
    have left it, with the steady window of the step's two runs."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    path = where / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text_proto))
    reduced = tr.reduce(str(path))
    window = cells.load_module("kinds", "train").steady_window(reduced)
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    return context.Run(
        cell={"name": "x", "chips": devices}, config={}, traffic={}, seed=0,
        seconds=10.0, trace=True, rehearsal=False, t_start=0.0,
        devices=[chip] * devices, compile_log=None, scratch=str(tmp_path),
        window=window)


def read(name, run):
    return cells.load_module("layers", name).read(run)


def test_phases_partition_busy_time(tmp_path):
    run = traced_run(tmp_path, xspace_named())
    (dev, by), = tp.of_run(run)
    assert (run.window.t0, run.window.t1) == (1000, 2200)
    assert by == {("grow/level/hist", True): 300,     # the level kernel
                  ("grow/level/hist", False): 100,    # glue
                  ("grow/level/skip", False): 50,     # the cond's own copy
                  ("score_update", True): 100,        # the lookup kernel
                  ("valid_apply", False): 200,
                  ("eval/auc", False): 100,
                  (tp.UNSCOPED, False): 50}           # a compiler-made sort
    # the parent `while` is no leaf: nothing is counted twice
    assert sum(by.values()) == run.window.busy_ns(dev) == 900
    ops = tp.busy_by_op(run.window, dev)
    assert "while.3" not in ops and ops["sort.2"] == (50, 1)


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("name,want", [
    ("driver.phase_coverage", 100 * 850 / 900),
    ("grower.glue_share", 100 * 150 / 900),
    ("driver.update_share", 100 * 100 / 900),
    ("driver.eval_share", 100 * 300 / 900),
    # HostTree 30 + 30, DeviceTree 80, Replay 10 in the gap [2000, 2200);
    # Fetch waits for the device and DrainPending is their parent
    ("driver.drain_host_ms", 150 / 1e6),
])
def test_reader_on_the_hand_made_trace(tmp_path, name, want, devices):
    run = traced_run(tmp_path, xspace_named(devices), devices)
    assert read(name, run) == pytest.approx(want)


def test_the_shares_and_the_kernels_add_up(tmp_path):
    """PERF.md section 3's identity: the XLA parts of the phases and the
    Pallas share are all of busy time."""
    run = traced_run(tmp_path, xspace_named())
    pallas = read("kernels.pallas_share", run)
    kernels_in_update = 100 * 100 / 900       # the lookup kernel
    assert (pallas + read("grower.glue_share", run)
            + read("driver.update_share", run) - kernels_in_update
            + read("driver.eval_share", run)
            + 100 - read("driver.phase_coverage", run)
            ) == pytest.approx(100.0)


def test_a_program_without_scopes_or_sections_reads_nothing(tmp_path):
    """The parent of the PR that brought them: its trace has the stats,
    but no ``lgbm.`` in them and no section on the host plane."""
    plain = {k: ("jit(step)/while/body/closed_call/add", src)
             for k, (_, src) in NAMES.items()}
    text = xspace_named(names=plain).replace("GBDT::", "other::")
    run = traced_run(tmp_path, text)
    assert tp.of_run(run) is None
    assert [read(name, run) for name in NEW] == [None] * 5
    run.window = None                       # an untraced run, a rehearsal
    assert [read(name, run) for name in NEW] == [None] * 5


def test_the_recorded_chip_chunk(tmp_path):
    reduced = tr.reduce(RECORDED)
    named = tp.names(RECORDED)
    (dev,) = reduced.devices
    window = cells.load_module("kinds", "train").steady_window(reduced)
    by = tp.busy_by_phase(window, dev, named[dev.name])
    busy = window.busy_ns(dev)
    assert sum(by.values()) == busy == 397_052_940
    share = {key: 100.0 * ns / busy for key, ns in by.items()}
    assert share[("grow/level/hist", True)] == pytest.approx(59.69, abs=.01)
    assert share[("valid_apply", False)] == pytest.approx(26.52, abs=.01)
    assert share[(tp.UNSCOPED, False)] == pytest.approx(0.09, abs=.01)
    assert 100.0 * sum(ns for (ph, _), ns in by.items()
                       if ph != tp.UNSCOPED) / busy > 99.9
    # the five row-length fusions of PERF.md section 5, named at last:
    # the validation walk's gathers, 13 tree levels an iteration
    ops = tp.busy_by_op(window, dev)
    five = [f"fusion.{n}" for n in (3289, 3290, 3291, 3292, 3294)]
    for name in five:
        known = named[dev.name][name]
        assert tp.phase_of(known.op_name) == "valid_apply"
        assert known.op_name.endswith("gather")
        assert "lightgbm_tpu/ops/predict.py:" in known.source
        assert ops[name][1] == 26                     # 2 iterations x 13
    assert sum(ops[n][0] for n in five) / busy == pytest.approx(.2610,
                                                                abs=1e-4)
    # the chunk boundary after the window: 10.04 ms on the device, 5.63
    # of them the drain's own host work (two trees)
    mods = dev.modules
    a, b = tr.step_runs(dev)[-2:]
    work = tp.host_section_ns(reduced, mods.end[a], mods.start[b],
                              tp.is_drain_work)
    assert set(work) == {"GBDT::Drain::HostTree", "GBDT::Drain::DeviceTree",
                         "GBDT::Drain::Replay"}
    assert sum(work.values()) == pytest.approx(5.63e6, rel=1e-2)


def test_a_rehearsal_prints_none_of_them(tmp_path):
    """Off the chip there is no device plane: the new metrics are absent
    from the line, not zero (and nothing raises)."""
    with open(MANIFEST) as fh:
        manifest = json.load(fh)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        manifest["per_layer"] += [m for m in json.load(fh)["per_layer"]
                                  if m["name"] in NEW]
    path = tmp_path / "rehearsal.json"
    path.write_text(json.dumps(manifest))
    proc = run_cell("rehearsal.train", 1, manifest=str(path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, last["problems"]
    assert last["metrics"] == {}
    assert not set(NEW) & set(last["rehearsal"]["produced"])
    assert "driver.dispatches_per_iter" in last["rehearsal"]["produced"]
