"""Section timer subsystem (utils/timer.py — analog of the reference's
TIMETAG Timer, ref: include/LightGBM/utils/common.h:978)."""
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.timer import Timer, global_timer


def test_timer_disabled_is_noop():
    t = Timer(enabled=False)
    t.start("x")
    t.stop("x")
    assert t.stats() == {}


def test_timer_accumulates_sections():
    t = Timer(enabled=True)
    with t.section("a"):
        sum(range(1000))
    with t.section("a"):
        pass
    with t.section("b"):
        pass
    s = t.stats()
    assert set(s) == {"a", "b"}
    assert s["a"].total >= 0.0 and s["a"].count == 2
    assert s["b"].count == 1
    t.reset()
    assert t.stats() == {}


def test_timer_reset_clears_open_starts():
    """A section started before reset() must not pollute the next run
    (reset() bumps the generation that invalidates per-thread start
    stacks)."""
    t = Timer(enabled=True)
    t.start("stale")
    t.reset()
    t.stop("stale")     # stale start discarded: no accumulation
    assert t.stats() == {}
    # and a fresh start/stop after the reset still records normally
    t.start("fresh")
    t.stop("fresh")
    assert set(t.stats()) == {"fresh"}


def test_timer_add_and_print_sorted_by_cost():
    t = Timer(enabled=True)
    t.add("cheap", 0.25)
    t.add("costly", 2.0)
    t.add("mid", 1.0)
    lines = []
    level = log.get_log_level()
    log.set_log_level(log.LogLevel.INFO)
    log.register_logger(lines.append)
    try:
        t.print()
    finally:
        log.register_logger(None)
        log.set_log_level(level)
    order = [name for line in lines
             for name in ("costly", "mid", "cheap") if name in line]
    assert order == ["costly", "mid", "cheap"]


def test_training_sections_recorded():
    rng = np.random.RandomState(0)
    X = rng.rand(500, 5).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    global_timer.enable()
    global_timer.reset()
    try:
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbose": -1}, lgb.Dataset(X, label=y),
                        num_boost_round=3)
        bst.predict(X)
        s = global_timer.stats()
        assert "DatasetLoader::Construct" in s
        assert ("GBDT::TrainOneIter" in s
                or "GBDT::TrainOneIterFast" in s)
        assert "Predictor::Predict" in s
        if "GBDT::TrainOneIter" in s:
            # the synchronous driver also feeds the per-phase sections
            # (the pipelined fast path on TPU intentionally does not —
            # its phases overlap and cannot be attributed honestly)
            assert "GBDT::histogram_split" in s
            assert s["GBDT::histogram_split"].count >= 3
    finally:
        global_timer.disable()
        global_timer.reset()


def _host_events(trace_dir, wanted):
    """{thread line: [(name, start ns, end ns)]} of the events named in
    ``wanted`` on the host plane of the newest trace under trace_dir."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in wanted:
                    out.setdefault(i, []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_section_is_a_trace_annotation_even_when_disabled(tmp_path):
    """A section lies on the profiler's clock whether or not the timer
    accumulates (TIMETAG off is the default), and sections nest."""
    import jax
    t = Timer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.section("Test::Outer"):
            with t.section("Test::Inner"):
                sum(range(1000))
    finally:
        jax.profiler.stop_trace()
    assert t.stats() == {}
    lines = _host_events(tmp_path, {"Test::Outer", "Test::Inner"})
    assert len(lines) == 1, "both sections on the one thread that ran them"
    (events,) = lines.values()
    spans = {name: (a, b) for name, a, b in events}
    assert set(spans) == {"Test::Outer", "Test::Inner"}
    assert spans["Test::Outer"][0] <= spans["Test::Inner"][0]
    assert spans["Test::Inner"][1] <= spans["Test::Outer"][1]


def test_drain_sections_nest_in_profile_window(tmp_path):
    """`profile_dir` on the megastep: the trace of one chunk holds
    GBDT::DrainPending with the GBDT::Drain::* children inside it, on
    the thread that trains; and the window asked for in the middle of a
    chunk snaps outward to the chunk's boundaries instead of evicting
    the megastep."""
    rng = np.random.RandomState(0)
    X = rng.rand(600, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbose": -1,
         "min_data_in_leaf": 5, "tpu_engine": "fused", "tpu_megastep": True,
         "tpu_megastep_iters": 4, "metric": "auc",
         "profile_dir": str(tmp_path / "prof"),
         "profile_start_iteration": 5, "profile_num_iterations": 2,
         "telemetry_out": str(tmp_path / "t.jsonl")},
        ds, num_boost_round=12,
        valid_sets=[lgb.Dataset(X, label=y, reference=ds)],
        callbacks=[lgb.record_evaluation({})])
    import json
    events = [json.loads(ln) for ln in open(tmp_path / "t.jsonl")]
    kinds = [e.get("event") for e in events]
    assert "megastep_evicted" not in kinds and "degrade" not in kinds
    assert bst.telemetry()["counters"]["train.dispatches"] == 3
    start = next(e for e in events if e.get("event") == "profiler_trace_start")
    stop = next(e for e in events if e.get("event") == "profiler_trace_stop")
    # iterations 5..6 asked for; chunks are [4, 8): that is what was traced
    assert (start["iter"], stop["iter"]) == (4, 8)
    assert (stop["first_iteration"], stop["iterations"]) == (4, 4)

    children = {"GBDT::Drain::Fetch", "GBDT::Drain::HostTree",
                "GBDT::Drain::DeviceTree", "GBDT::Drain::Replay"}
    lines = _host_events(tmp_path / "prof",
                         children | {"GBDT::DrainPending",
                                     "GBDT::TrainMegastep"})
    assert len(lines) == 1, "one thread trains and drains"
    (events,) = lines.values()
    drains = [(a, b) for name, a, b in events
              if name == "GBDT::DrainPending"]
    assert len(drains) == 1 and any(
        name == "GBDT::TrainMegastep" for name, _, _ in events)
    a, b = drains[0]
    inside = {name for name, s, e in events
              if name in children and a <= s and e <= b}
    assert inside == children
    assert all(a <= s and e <= b for name, s, e in events
               if name in children)
