"""Observability tier 2: trace export, health auditing, numerical
guards, crash flight recorder (ISSUE 4).

Covers the Chrome-trace exporter (valid JSON, per-rank tracks, span
nesting), the cross-rank health auditor (unit-level divergence /
straggler detection plus a forced divergence on the two-process
driver), NaN/Inf guard anomaly events, the crash dump, the JsonlSink
re-open lifecycle, and scripts/bench_compare.py.
"""
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import Telemetry, chrome_trace_events
from lightgbm_tpu.obs.health import HealthAuditor, model_state_hash

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=500, f=6, seed=9):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    return X, y


def _load_trace(path):
    """A trace_out file must be a loadable Chrome-trace JSON object with
    a traceEvents list (the contract chrome://tracing / ui.perfetto.dev
    relies on)."""
    with open(path) as fh:
        doc = json.load(fh)
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    return doc["traceEvents"]


# ---------------------------------------------------------------- trace
def test_chrome_trace_events_unit():
    """Exporter pure function: rank -> pid, track -> named tid, X spans
    in microseconds, zero-duration records as instants."""
    spans = [
        [{"name": "iteration", "ts": 10.0, "dur": 0.5, "rank": 0,
          "track": "train", "iter": 0},
         {"name": "histogram_split", "ts": 10.1, "dur": 0.2, "rank": 0,
          "track": "train", "iter": 0},
         {"name": "psum_data", "ts": 10.2, "dur": 0.0, "rank": 0,
          "track": "collectives", "args": {"bytes": 64}}],
        [{"name": "iteration", "ts": 10.0, "dur": 0.6, "rank": 1,
          "track": "train", "iter": 0}],
    ]
    events = chrome_trace_events(spans)
    meta = [e for e in events if e["ph"] == "M"]
    names = {(e["pid"], e["name"], json.dumps(e["args"])) for e in meta}
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "rank 0" and e["pid"] == 0
               for e in meta)
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "rank 1" and e["pid"] == 1
               for e in meta), names
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    it0 = next(e for e in xs if e["pid"] == 0 and e["name"] == "iteration")
    assert it0["ts"] == pytest.approx(10.0 * 1e6)
    assert it0["dur"] == pytest.approx(0.5 * 1e6)
    assert it0["args"]["iter"] == 0
    # the zero-duration collective renders as an instant, on its own tid
    inst = next(e for e in events if e["ph"] == "i")
    assert inst["name"] == "psum_data" and inst["args"]["bytes"] == 64
    assert inst["tid"] != it0["tid"]


def test_trace_out_writes_loadable_timeline(tmp_path):
    trace = tmp_path / "run.trace.json"
    X, y = _data()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "trace_out": str(trace)},
              lgb.Dataset(X, label=y), num_boost_round=4)
    events = _load_trace(trace)
    xs = [e for e in events if e["ph"] == "X"]
    iters = [e for e in xs if e["name"] == "iteration"]
    assert [e["args"]["iter"] for e in iters] == [0, 1, 2, 3]
    # driver sections nest inside their iteration span: same pid/tid,
    # start at/after the iteration start, end at/before its end (1ms
    # slack: section edges use perf_counter durations on a time.time
    # base)
    slack = 1e3  # µs
    for sec_name in ("histogram_split", "score_update", "boosting"):
        secs = [e for e in xs if e["name"] == sec_name]
        assert secs, f"no {sec_name} spans in trace"
        for s in secs:
            it = next(e for e in iters
                      if e["args"]["iter"] == s["args"]["iter"])
            assert s["pid"] == it["pid"] and s["tid"] == it["tid"]
            assert s["ts"] >= it["ts"] - slack
            assert s["ts"] + s["dur"] <= it["ts"] + it["dur"] + slack
    # iteration 0 compiles: the compile track carries the phases jax timed
    compiles = [e for e in xs if str(e["name"]).startswith("compile:")]
    assert compiles, "no compile spans on the compile track"
    assert {e["cat"] for e in compiles} == {"compile"}


def test_trace_without_telemetry_out_needs_no_jsonl(tmp_path):
    """trace_out alone enables the registry sink-less — no JSONL file
    appears, the trace still does."""
    trace = tmp_path / "t.json"
    X, y = _data(n=300)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbose": -1, "trace_out": str(trace)},
                    lgb.Dataset(X, label=y), num_boost_round=2)
    assert trace.exists()
    assert bst.telemetry()["enabled"]
    assert not list(tmp_path.glob("*.jsonl"))


# --------------------------------------------------------------- health
def test_model_state_hash_detects_model_change_and_fault(monkeypatch):
    X, y = _data(n=400)
    b1 = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1},
                   lgb.Dataset(X, label=y), num_boost_round=2)
    models = b1._gbdt.models
    assert model_state_hash(models) == model_state_hash(models)
    b2 = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                    "learning_rate": 0.31},
                   lgb.Dataset(X, label=y), num_boost_round=2)
    assert model_state_hash(models) != model_state_hash(b2._gbdt.models)
    # fault injection salts exactly the matching rank's digest
    clean = model_state_hash(models, rank=1)
    monkeypatch.setenv("LIGHTGBM_TPU_HEALTH_FAULT_RANK", "1")
    assert model_state_hash(models, rank=1) != clean
    assert model_state_hash(models, rank=0) == model_state_hash(models)


def test_health_auditor_unit_divergence_and_straggler(monkeypatch):
    """Unit-level audit round against a faked 3-rank gather: a diverging
    hash yields rank_divergence, a 4x slow section yields a straggler
    event naming the slowest rank."""
    import lightgbm_tpu.obs.registry as registry

    tel = Telemetry()
    tel.enable()
    tel._rank = 0

    def fake_gather(local):
        others = [dict(local, rank=1),
                  dict(local, rank=2,
                       hash="deadbeef" * 8,
                       sections={"histogram_split": 0.4,
                                 "score_update": 0.01})]
        return [local] + others

    monkeypatch.setattr(registry, "allgather_json", fake_gather)
    aud = HealthAuditor(tel, period=2, skew_threshold=2.0)
    assert not aud.due(0) and aud.due(1)
    ok = aud.check(1, [], sections={"histogram_split": 0.1,
                                    "score_update": 0.01})
    assert ok is False
    snap = tel.snapshot()
    assert snap["counters"]["health.checks"] == 1
    assert snap["counters"]["health.rank_divergence"] == 1
    assert snap["counters"]["health.straggler"] >= 1
    events = {e["event"]: e for e in snap["events"]}
    assert events["health_check"]["ok"] is False
    assert set(events["rank_divergence"]["hashes"]) == {"0", "1", "2"}
    strag = [e for e in snap["events"] if e["event"] == "straggler"]
    assert any(e["section"] == "histogram_split"
               and e["slowest_rank"] == 2 and e["skew"] >= 2.0
               for e in strag), strag
    assert snap["gauges"]["health.skew.histogram_split"] >= 2.0


def test_health_check_period_single_process(tmp_path):
    """End-to-end single process: checks fire on the configured period
    and agree (one rank can't diverge from itself)."""
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                     "telemetry_out": str(out), "health_check_period": 2},
                    lgb.Dataset(X, label=y), num_boost_round=6)
    with open(out) as fh:
        recs = [json.loads(line) for line in fh]
    checks = [r for r in recs if r["event"] == "health_check"]
    assert [c["iter"] for c in checks] == [1, 3, 5]
    assert all(c["ok"] for c in checks)
    assert not any(r["event"] in ("rank_divergence", "straggler")
                   for r in recs)
    assert bst.telemetry()["counters"]["health.checks"] == 3


# ------------------------------------------------------ numerical guards
def test_nan_gradient_guard_emits_anomaly(tmp_path):
    """A custom objective injecting NaN gradients at iteration 1 must
    produce a structured anomaly event (and training must survive)."""
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    calls = {"n": 0}

    def bad_fobj(preds, ds):
        grad = preds - ds.get_label()
        hess = np.ones_like(grad)
        if calls["n"] == 1:
            grad = grad.copy()
            grad[:7] = np.nan
        calls["n"] += 1
        return grad, hess

    result = {}
    lgb.train({"objective": "none", "num_leaves": 7, "verbose": -1,
               "telemetry_out": str(out)},
              lgb.Dataset(X, label=y), num_boost_round=3, fobj=bad_fobj,
              callbacks=[lgb.record_telemetry(result)])
    with open(out) as fh:
        recs = [json.loads(line) for line in fh]
    anomalies = [r for r in recs if r["event"] == "anomaly"
                 and r["kind"] == "nonfinite_grad_hess"]
    assert anomalies and anomalies[0]["iter"] == 1
    assert anomalies[0]["grad"] == 7 and anomalies[0]["hess"] == 0
    # record_telemetry surfaces the findings as a first-class list
    assert any(a["kind"] == "nonfinite_grad_hess"
               for a in result["anomalies"])


def test_split_gain_stats_in_iteration_records(tmp_path):
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
               "telemetry_out": str(out)},
              lgb.Dataset(X, label=y), num_boost_round=3)
    with open(out) as fh:
        iters = [json.loads(l) for l in fh
                 if '"iteration"' in l]
    iters = [r for r in iters if r["event"] == "iteration"]
    assert iters
    for r in iters:
        sg = r["split_gain"]
        assert sg["count"] > 0
        assert sg["min"] <= sg["mean"] <= sg["max"]


# --------------------------------------------------- crash flight recorder
def test_crash_flight_recorder(tmp_path):
    """An exception unwinding out of the train loop dumps
    <telemetry_out>.crash.json (ring buffer + section stack + config)
    before re-raising."""
    out = tmp_path / "tel.jsonl"
    X, y = _data()

    def exploding_fobj(preds, ds):
        if exploding_fobj.calls == 2:
            raise RuntimeError("injected-mid-train-failure")
        exploding_fobj.calls += 1
        grad = preds - ds.get_label()
        return grad, np.ones_like(grad)

    exploding_fobj.calls = 0
    with pytest.raises(RuntimeError, match="injected-mid-train-failure"):
        lgb.train({"objective": "none", "num_leaves": 7, "verbose": -1,
                   "telemetry_out": str(out)},
                  lgb.Dataset(X, label=y), num_boost_round=5,
                  fobj=exploding_fobj)
    crash = tmp_path / "tel.jsonl.crash.json"
    assert crash.exists(), "flight recorder wrote no crash dump"
    with open(crash) as fh:
        payload = json.load(fh)
    assert payload["rank"] == 0 and payload["iteration"] == 2
    exc = payload["exception"]
    assert exc["type"] == "RuntimeError"
    assert "injected-mid-train-failure" in exc["message"]
    assert any("exploding_fobj" in ln for ln in exc["traceback"])
    # the custom objective runs BEFORE the driver's sections, so the
    # stack is empty here (test_crash_dump_records_active_section covers
    # the in-section case)
    assert payload["telemetry"]["section_stack"] == []
    assert payload["config"]["telemetry_out"] == str(out)
    assert payload["config"]["num_iterations"] == 5
    # the ring buffer preserved the pre-crash iteration records
    events = payload["telemetry"]["events"]
    assert sum(1 for e in events if e["event"] == "iteration") == 2
    # and the JSONL stream was flushed, so both views agree
    with open(out) as fh:
        recs = [json.loads(line) for line in fh]
    assert sum(1 for r in recs if r["event"] == "iteration") == 2


def test_crash_dump_records_active_section(tmp_path, monkeypatch):
    """An exception INSIDE a driver section leaves that section on the
    dumped stack — the flight recorder's 'where training was'."""
    import lightgbm_tpu.boosting.gbdt as gbdt_mod

    out = tmp_path / "tel.jsonl"
    X, y = _data()
    orig = gbdt_mod.GBDT._to_host_tree

    def boom(self, tree, shrinkage):
        if self.iter == 1:
            raise RuntimeError("injected-materialize-failure")
        return orig(self, tree, shrinkage)

    monkeypatch.setattr(gbdt_mod.GBDT, "_to_host_tree", boom)
    with pytest.raises(RuntimeError, match="injected-materialize"):
        lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                   "telemetry_out": str(out)},
                  lgb.Dataset(X, label=y), num_boost_round=3)
    with open(tmp_path / "tel.jsonl.crash.json") as fh:
        payload = json.load(fh)
    assert payload["iteration"] == 1
    assert payload["telemetry"]["section_stack"] == ["tree_materialize"]


def test_no_crash_dump_without_telemetry(tmp_path):
    X, y = _data(n=300)

    def bad_fobj(preds, ds):
        raise ValueError("boom")

    with pytest.raises(ValueError):
        lgb.train({"objective": "none", "num_leaves": 7, "verbose": -1},
                  lgb.Dataset(X, label=y), num_boost_round=2,
                  fobj=bad_fobj)
    assert not list(tmp_path.glob("*.crash.json*"))


# ------------------------------------------------------- sink lifecycle
def test_jsonl_sink_reopen_appends(tmp_path):
    from lightgbm_tpu.obs.events import JsonlSink

    path = str(tmp_path / "s.jsonl")
    s1 = JsonlSink(path)
    s1.write({"event": "first"})
    s1.close()
    # a later sink on the SAME path in this process appends — the
    # established stream is never clobbered (ISSUE 4 satellite)
    s2 = JsonlSink(path)
    s2.write({"event": "second"})
    s2.close()
    with open(path) as fh:
        events = [json.loads(l)["event"] for l in fh]
    assert events == ["first", "second"]


def test_enable_reenable_same_path_is_noop(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel = Telemetry()
    assert tel.enable(sink_path=path) is True
    sink = tel._sink
    # re-enable with the same path: same sink object, nothing re-attached
    assert tel.enable(sink_path=path) is False
    assert tel._sink is sink
    # a different path is a genuine re-target: old sink closed, new one on
    other = str(tmp_path / "u.jsonl")
    assert tel.enable(sink_path=other) is True
    assert tel.sink_path == other
    tel.event("after_retarget")
    tel.disable()
    with open(other) as fh:
        assert [json.loads(l)["event"] for l in fh] == ["after_retarget"]


def test_reset_parameter_reenable_preserves_stream(tmp_path):
    """The end-to-end lifecycle bug from the satellite: train, then
    reset_parameter(telemetry_out=<same path>) and keep training — the
    earlier records must survive."""
    out = tmp_path / "tel.jsonl"
    X, y = _data()
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbose": -1, "telemetry_out": str(out)},
                      train_set=lgb.Dataset(X, label=y))
    bst.update()
    bst.reset_parameter({"telemetry_out": str(out), "verbose": -1})
    bst.update()
    with open(out) as fh:
        recs = [json.loads(line) for line in fh]
    iters = [r["iter"] for r in recs if r["event"] == "iteration"]
    assert iters == [0, 1], f"re-enable clobbered the stream: {iters}"


# -------------------------------------------------------- bench compare
def _bench_compare(tmp_path, records, *extra):
    traj = tmp_path / "traj.jsonl"
    with open(traj, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "bench_compare.py"),
         "--trajectory", str(traj), *extra],
        capture_output=True, text=True)
    return r, json.loads(r.stdout.strip().splitlines()[-1])


def test_bench_compare_flags_regression(tmp_path):
    prev = {"run_id": "a", "metric": "m", "value": 1.0,
            "phase_timings": {"GBDT::histogram_split":
                              {"total": 1.0, "count": 10},
                              "tiny": {"total": 1e-4, "count": 10}}}
    cur = {"run_id": "b", "metric": "m", "value": 1.30,
           "phase_timings": {"GBDT::histogram_split":
                             {"total": 2.0, "count": 10},
                             "tiny": {"total": 1e-2, "count": 10}}}
    r, rep = _bench_compare(tmp_path, [prev, cur], "--fail-on-regress")
    assert r.returncode == 1, r.stderr
    assert rep["status"] == "ok"
    names = {e["name"] for e in rep["regressions"]}
    assert names == {"m", "GBDT::histogram_split"}  # headline + phase
    assert rep["headline"]["ratio"] == pytest.approx(1.3)
    # sub-threshold / sub-min-seconds phases are not flagged
    assert "tiny" not in {e["name"] for e in rep["phases"]}


def test_bench_compare_ok_and_insufficient(tmp_path):
    rec = {"run_id": "a", "metric": "m", "value": 1.0,
           "phase_timings": {"p": {"total": 1.0, "count": 10}}}
    r, rep = _bench_compare(tmp_path, [rec], "--fail-on-regress")
    assert r.returncode == 0 and rep["status"] == "insufficient_history"
    faster = dict(rec, run_id="b", value=0.9)
    r, rep = _bench_compare(tmp_path, [rec, faster], "--fail-on-regress")
    assert r.returncode == 0 and rep["regressions"] == []


# ------------------------------------------------- two-process driver
_MP_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=sys.argv[1],
        num_processes=int(sys.argv[2]), process_id=int(sys.argv[3]))
    import numpy as np
    import lightgbm_tpu as lgb

    path, tel_path, trace_path = sys.argv[4], sys.argv[5], sys.argv[6]
    ds = lgb.Dataset(path, params={"label_column": 0, "verbose": -1,
                                   "max_bin": 63})
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "learning_rate": 0.2, "tree_learner": "data",
                     "verbose": -1, "telemetry_out": tel_path,
                     "trace_out": trace_path,
                     "health_check_period": 2},
                    ds, num_boost_round=4)
""")


def test_multiproc_trace_and_forced_divergence(tmp_path):
    """Acceptance run: two-process driver with trace_out +
    health_check_period, rank 1's model hash salted via the fault env —
    rank 0's merged trace carries both ranks' tracks and every rank
    records the rank_divergence."""
    rng = np.random.RandomState(11)
    n, F = 2000, 6
    X = rng.rand(n, F)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float64)
    train = tmp_path / "train.csv"
    np.savetxt(train, np.column_stack([y, X]), delimiter=",", fmt="%.6f")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(_MP_WORKER)
    tel_path = tmp_path / "tel.jsonl"
    trace_path = tmp_path / "run.trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO_ROOT,
               LIGHTGBM_TPU_HEALTH_FAULT_RANK="1")
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, str(script), coord, "2", str(i), str(train),
         str(tel_path), str(trace_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for i in range(2)]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err.decode()[-3000:]

    # every rank holds the divergence evidence in its own stream
    for rank, path in enumerate([tel_path,
                                 tmp_path / "tel.jsonl.rank1"]):
        with open(path) as fh:
            recs = [json.loads(line) for line in fh]
        checks = [r for r in recs if r["event"] == "health_check"]
        assert [c["iter"] for c in checks] == [1, 3]
        assert all(c["ok"] is False and c["ranks"] == 2 for c in checks)
        divs = [r for r in recs if r["event"] == "rank_divergence"]
        assert divs, f"rank {rank} recorded no divergence"
        hashes = divs[0]["hashes"]
        assert set(hashes) == {"0", "1"} and hashes["0"] != hashes["1"]

    # rank 0 merged both ranks' spans into one timeline
    events = _load_trace(trace_path)
    assert not trace_path.with_name(trace_path.name + ".rank1").exists()
    proc_names = {e["args"]["name"] for e in events
                  if e.get("name") == "process_name"}
    assert proc_names == {"rank 0", "rank 1"}
    xs = [e for e in events if e["ph"] == "X"]
    for pid in (0, 1):
        names = {e["name"] for e in xs if e["pid"] == pid}
        assert "iteration" in names and "histogram_split" in names
        assert "health_check" in names
    # the REAL host-plane collectives of the multiproc layout show up as
    # timed spans on the collectives track
    assert any(e["cat"] == "collectives" and e["name"] == "host_allgather"
               for e in xs)
