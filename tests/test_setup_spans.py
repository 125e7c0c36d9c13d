"""The spans inside a job's set-up and end (obs/registry.Span, written as
``setup_span`` events; docs/Observability.md section 3b): the tree a tiny
``lgb.train`` yields, its clock and nesting, what a job without telemetry
does not do, and the jax.monitoring listener that opens a step's first
call."""
import glob
import json

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import Telemetry, jaxmon
from lightgbm_tpu.obs.registry import Span, open_spans

SLACK = 5e-3    # a span's end is its start plus a perf_counter duration

# every name of a fused megastep job with one validation set, and how
# often it closes (``bin`` and its children once a constructed set; the
# validation set takes its reference's mappers)
ONCE = ["train", "train/booster_init", "init/config_objective", "init/meta",
        "init/kernels_import", "init/upload", "init/pack", "init/state",
        "init/profile", "train/valid_sets", "valid/metrics", "valid/upload",
        "valid/pack", "train/callbacks_plan", "first_call/build",
        "first_call", "first_call/trace", "first_call/lower",
        "first_call/load", "cost/analyze", "finish", "finish/score_profile",
        "finish/cost_flush", "finish/summary", "finish/report",
        "finish/trace_export", "finish/flush", "finish/callbacks",
        "bin/sample", "bin/mappers"]
TWICE = ["bin", "bin/rows", "bin/finalize", "finish/drain"]


def _data(n, seed):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6).astype(np.float32)
    return X, (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float32)


def _fused_job(path, syncs):
    """A megastep job of two chunks on the fused engine with a validation
    set; ``syncs`` counts its ``jax.block_until_ready`` calls."""
    X, y = _data(1500, 0)
    Xv, yv = _data(400, 1)
    ds = lgb.Dataset(X, label=y, params={"verbose": -1, "max_bin": 15})
    dv = lgb.Dataset(Xv, label=yv, reference=ds)
    ds.construct()      # as the benchmark does: binned before lgb.train
    dv.construct()
    params = {"objective": "binary", "metric": "auc", "num_leaves": 4,
              "max_bin": 15, "verbose": -1, "tpu_engine": "fused",
              "tpu_megastep": True, "tpu_megastep_iters": 2}
    if path:
        params["telemetry_out"] = str(path)
    real = jax.block_until_ready

    def counted(x):
        syncs.append(1)
        return real(x)

    jax.block_until_ready = counted
    try:
        lgb.train(params, ds, num_boost_round=4, valid_sets=[dv],
                  callbacks=[lgb.record_evaluation({})])
    finally:
        jax.block_until_ready = real


def _spans(path):
    with open(path) as fh:
        events = [json.loads(line) for line in fh]
    return [e for e in events if e["event"] == "setup_span"], events


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    out = tmp_path_factory.mktemp("spans")
    on, off = [], []
    _fused_job(out / "on.jsonl", on)
    _fused_job(None, off)
    spans, events = _spans(out / "on.jsonl")
    return {"spans": spans, "events": events, "syncs_on": len(on),
            "syncs_off": len(off), "dir": out}


def test_every_span_of_the_table_once_per_job_or_set(job):
    names = [s["name"] for s in job["spans"]]
    assert {n: names.count(n) for n in ONCE} == {n: 1 for n in ONCE}
    assert {n: names.count(n) for n in TWICE} == {n: 2 for n in TWICE}
    assert set(names) == set(ONCE) | set(TWICE)
    for s in job["spans"]:
        assert s["dur_s"] >= 0 and isinstance(s["t0"], float)
        assert s["ts"] >= s["t0"] + s["dur_s"] - SLACK   # written at close
    by = {s["name"]: s for s in job["spans"]}
    assert by["init/upload"]["bytes"] == 1500 * 6
    assert by["bin/mappers"]["features"] == 6
    assert by["first_call"]["signature"].startswith("megastep[chunk=2")
    assert by["first_call/trace"]["fun_name"] == "step"
    assert by["first_call/load"]["cache"] in ("hit", "miss")
    # the step's thousands of inner jits are folded into its own phases
    assert by["first_call/trace"]["inner_jits"] > 10
    assert len(by["first_call/trace"]["inner"]) <= 8


def test_children_lie_inside_their_parents_and_leaves_do_not_overlap(job):
    spans = job["spans"]

    def inside(child, parent):
        return parent["t0"] - SLACK <= child["t0"] and \
            child["t0"] + child["dur_s"] \
            <= parent["t0"] + parent["dur_s"] + SLACK

    tops = [s for s in spans if s["parent"] is None]
    assert sorted(s["name"] for s in tops) == ["bin", "bin", "train"]
    for s in spans:
        if s["parent"] is not None:
            assert any(p["name"] == s["parent"] and inside(s, p)
                       for p in spans if p is not s), s
    leaves = sorted((s for s in spans if not any(
        c["parent"] == s["name"] and inside(c, s) for c in spans
        if c is not s)), key=lambda s: s["t0"])
    for a, b in zip(leaves, leaves[1:]):
        assert a["t0"] + a["dur_s"] <= b["t0"] + SLACK, (a, b)
    # the first call has the bounds of compile_executable's compile_ms
    (built,) = [e for e in job["events"]
                if e["event"] == "compile_executable"]
    (first,) = [s for s in spans if s["name"] == "first_call"]
    assert first["dur_s"] == pytest.approx(built["compile_ms"] / 1e3,
                                           abs=0.05)
    phases = sum(s["dur_s"] for s in spans
                 if s["parent"] == "first_call")
    assert 0 < phases <= first["dur_s"] + SLACK


def test_one_job_a_train_call_and_the_clock_of_the_stream(job, tmp_path):
    jobs = {s["job"] for s in job["spans"]}
    assert len(jobs) == 1
    # a second lgb.train in the process (the XLA engine: no first call)
    X, y = _data(500, 2)
    lgb.train({"objective": "binary", "num_leaves": 4, "max_bin": 15,
               "verbose": -1, "telemetry_out": str(tmp_path / "b.jsonl")},
              lgb.Dataset(X, label=y), num_boost_round=2)
    spans, events = _spans(tmp_path / "b.jsonl")
    (other,) = {s["job"] for s in spans}
    assert other not in jobs
    names = {s["name"] for s in spans}
    assert {"bin", "bin/rows", "train", "train/booster_init", "init/upload",
            "finish", "finish/summary"} <= names
    assert not any(n.startswith("first_call") for n in names)
    # t0 is on the clock of the events' ts: the train span starts before
    # the first event and ends after the summary
    (train,) = [s for s in spans if s["name"] == "train"]
    summary = [e for e in events if e["event"] == "summary"][0]
    assert train["t0"] <= events[0]["ts"]
    assert train["t0"] + train["dur_s"] >= summary["ts"] - SLACK


def test_telemetry_off_writes_nothing_and_syncs_nothing_more(job):
    assert glob.glob(str(job["dir"] / "*")) == [str(job["dir"] / "on.jsonl")]
    # the four device spans of this job (init/upload, init/pack,
    # valid/upload, valid/pack) block only while the registry is on
    assert job["syncs_on"] - job["syncs_off"] == 4


def test_spans_on_the_profilers_host_plane(tmp_path):
    """A profiler session around the whole call has the spans as
    TraceAnnotations, on the clock of the device's operations (jax's own
    phases inside ``first_call`` are its spans, not annotations)."""
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        _fused_job(None, [])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof/plugins/profile/*/*.xplane.pb"))
    names = {e.name for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert {"bin/rows", "train", "init/upload", "init/pack", "init/meta",
            "first_call/build", "first_call", "finish",
            "finish/drain"} <= names


# ------------------------------------------------------------ the primitive
def _registry(tmp_path):
    tel = Telemetry()
    tel.enable(str(tmp_path / "t.jsonl"))
    return tel


def _written(tel):
    return [e for e in tel.snapshot()["events"]
            if e["event"] == "setup_span"]


def test_span_parent_attributes_and_failure(tmp_path):
    tel = _registry(tmp_path)
    with tel.timed("outer", rows=3) as outer:
        with tel.timed("outer/inner") as inner:
            inner.set(bytes=7)
        with pytest.raises(ValueError):
            with tel.timed("outer/broken"):
                with tel.timed("outer/broken/deeper"):
                    raise ValueError("x")
        assert open_spans() == [outer]      # the failed ones are gone
    assert open_spans() == []
    got = {s["name"]: s for s in _written(tel)}
    assert set(got) == {"outer", "outer/inner"}     # a failed span: none
    assert got["outer/inner"]["parent"] == "outer"
    assert got["outer/inner"]["bytes"] == 7 and got["outer"]["rows"] == 3
    assert got["outer"]["parent"] is None
    assert got["outer"]["job"] == tel.run_id == got["outer/inner"]["job"]
    tel.close()


def test_spans_wait_for_a_registry(tmp_path):
    """Without a registry a span closes into the list of the span around
    it; a registry that is off keeps it until it is enabled."""
    held = []
    with Span(None, "train", hold=held) as train:
        with Span(None, "early"):
            pass
        assert [s["name"] for s in held] == ["early"]
        tel = Telemetry()           # off: as before record_telemetry's
        train.bind(tel)             # first call
        with tel.timed("later") as later:
            later.sync([np.zeros(2)])       # off: nothing to block on
            assert later._sync is None
    assert _written(tel) == [] and len(tel._held_spans) == 3
    tel.enable(str(tmp_path / "late.jsonl"))
    assert [(s["name"], s["parent"]) for s in _written(tel)] == [
        ("early", "train"), ("later", "train"), ("train", None)]
    assert tel._held_spans == []
    tel.close()


# ------------------------------------------------- jax's phases as children
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def test_jax_time_spans_become_children_of_an_open_first_call(tmp_path):
    from jax import monitoring
    tel = _registry(tmp_path)       # (enable() installs the listeners)
    monitoring.record_event_time_span(TRACE, 1.0, 2.0, fun_name="before")
    with tel.timed("first_call", adopt=True, signature="s") as first:
        t0 = first.t0
        # an inner jit's trace ends first, inside the step's own
        monitoring.record_event_time_span(TRACE, t0 + .2, t0 + .3,
                                          fun_name="level_pass")
        monitoring.record_event_time_span(TRACE, t0 + .4, t0 + .5,
                                          fun_name="level_pass")
        monitoring.record_event_time_span(TRACE, t0 + .15, t0 + .55,
                                          fun_name="grow")
        monitoring.record_event_time_span(TRACE, t0 + .1, t0 + .6,
                                          fun_name="step")
        monitoring.record_event_time_span(LOWER, t0 + .6, t0 + .7,
                                          fun_name="jit(step)")
        # a cache hit: jax records two durations inside the backend phase
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 90.5)
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 1.25)
        monitoring.record_event_time_span(BACKEND, t0 + .7, t0 + .9,
                                          fun_name="jit(step)")
        monitoring.record_event_time_span(BACKEND, t0 + .9, t0 + .95,
                                          fun_name="jit(other)")
    monitoring.record_event_time_span(TRACE, 3.0, 4.0, fun_name="after")
    got = [s for s in _written(tel) if s["name"] != "first_call"]
    assert [(s["name"], s["parent"], s["fun_name"]) for s in got] == [
        ("first_call/trace", "first_call", "step"),
        ("first_call/lower", "first_call", "jit(step)"),
        ("first_call/load", "first_call", "jit(step)"),
        ("first_call/load", "first_call", "jit(other)")]
    trace, _, hit, miss = got
    assert trace["t0"] == pytest.approx(t0 + .1)
    assert trace["dur_s"] == pytest.approx(.5)
    # the two inner jits lie in ``grow``, which lies in the step's trace
    assert trace["inner_jits"] == 3
    assert trace["inner"] == [["grow", 1, pytest.approx(.4), [
        ["level_pass", 2, pytest.approx(.2)]]]]
    assert (hit["cache"], hit["compile_time_saved_s"],
            hit["cache_retrieval_s"]) == ("hit", 90.5, 1.25)
    assert miss["cache"] == "miss" and "cache_retrieval_s" not in miss
    # the counters the exporter's recompile rate reads stay
    counters = tel.snapshot()["counters"]
    assert counters["events.setup_span"] == 5
    monitoring.record_event_duration_secs(BACKEND, 2.0, fun_name="f")
    after = tel.snapshot()["counters"]
    assert after["compile.events"] == counters.get("compile.events", 0) + 1
    assert after["compile.seconds"] == pytest.approx(
        counters.get("compile.seconds", 0) + 2.0)
    jaxmon.detach(tel)
    tel.close()


def test_compile_track_has_the_bounds_jax_measured(tmp_path):
    from jax import monitoring
    tel = Telemetry()
    tel.enable(trace=True)
    monitoring.record_event_time_span(LOWER, 10.0, 12.5, fun_name="f")
    (span,) = [s for s in tel.drain_spans() if s["track"] == "compile"]
    assert (span["name"], span["ts"], span["dur"]) == (
        "compile:jaxpr_to_mlir_module_duration", 10.0, 2.5)
    assert span["args"] == {"fun_name": "f"}
    tel.close()
