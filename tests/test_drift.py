"""Drift & lineage plane (lightgbm_tpu/obs/drift.py + its hooks).

Covers the four layers of the drift ISSUE and its acceptance contract:

- divergence math on every degenerate shape the monitors meet (empty
  reference bins, single-bin features, all-missing columns, empty
  windows) plus the coarsening step that keeps PSI off sampling noise;
- training DataProfile + provenance capture, embedded in the model
  artifact and resilience checkpoints, byte-stable through round trips;
- the serving DriftMonitor A/B acceptance: a distribution-B feed
  against an A-trained model raises EXACTLY one hysteresis-gated
  ``drift_alert`` while an A-fed control raises none — with the 1.0
  dispatches/request and zero-recompile serving contracts
  counter-asserted in BOTH runs, and a profile-less artifact degrading
  to one ``drift_unavailable`` event, never an exception;
- ingest mapper-drift events, the lineage chain (training run_id ->
  checkpoint -> rollover) and the run-report/diff surfacing.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import drift as drift_mod
from lightgbm_tpu.obs.drift import (DriftMonitor, build_profile,
                                    canonical_json, coarsen,
                                    js_divergence, profile_digest, psi)
from lightgbm_tpu.serve import PredictionService

F = 5


def _data(n=800, f=F, seed=0, shift=0.0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    if shift:
        X = np.clip(X + shift, 0.0, 1.0).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    return X, y


def _train(X, y, rounds=6, **extra):
    params = {"objective": "binary", "num_leaves": 15,
              "learning_rate": 0.2, "verbose": -1, "min_data_in_leaf": 5,
              "max_bin": 63, "metric": "None"}
    params.update(extra)
    return lgb.train(params, lgb.Dataset(
        X, label=y, params={"max_bin": 63, "verbose": -1}),
        num_boost_round=rounds)


@pytest.fixture(scope="module")
def bst():
    X, y = _data()
    return _train(X, y)


# ----------------------------------------------------------- psi / js
def test_psi_js_identical_distributions_near_zero():
    c = np.array([10, 20, 30, 40])
    assert psi(c, 10 * c) == pytest.approx(0.0, abs=1e-9)
    assert js_divergence(c, 10 * c) == pytest.approx(0.0, abs=1e-9)


def test_psi_empty_reference_bins_finite_via_smoothing():
    # reference mass entirely absent from bins the current window
    # fills: the epsilon smoothing keeps every log term finite
    v = psi([0, 0, 0, 0], [5, 5, 5, 5])
    assert np.isfinite(v)
    v2 = psi([100, 0, 0, 0], [0, 0, 0, 100])
    assert np.isfinite(v2) and v2 > 1.0


def test_psi_single_bin_feature_is_zero():
    assert psi([7], [3]) == pytest.approx(0.0, abs=1e-12)


def test_psi_empty_vectors_and_length_mismatch():
    assert psi([], []) == 0.0
    assert js_divergence([], []) == 0.0
    # shorter side is padded with empty bins, not truncated
    long = psi([10, 10, 10, 10], [10, 10])
    assert np.isfinite(long) and long > 0.0
    assert np.isfinite(psi([], [1, 2, 3]))


def test_js_symmetric_and_bounded():
    a, b = [100, 0, 0], [0, 0, 100]
    assert js_divergence(a, b) == pytest.approx(js_divergence(b, a))
    assert 0.0 <= js_divergence(a, b) <= np.log(2) + 1e-9


def test_coarsen_groups_and_preserves_mass():
    c = np.arange(64, dtype=np.float64)
    g = coarsen(c, 8)
    assert g.size == 8 and g.sum() == pytest.approx(c.sum())
    # short vectors pass through untouched
    np.testing.assert_array_equal(coarsen([1, 2, 3], 8), [1.0, 2.0, 3.0])


# ------------------------------------------------- profile + artifact
def test_profile_captured_and_byte_stable(bst):
    p = bst.data_profile
    assert p is not None and p["schema"] == drift_mod.PROFILE_SCHEMA
    assert p["rows"] == 800 and len(p["features"]) >= 1
    assert p["mappers_digest"]
    assert "score" in p          # finalize attached the margin sketch
    # canonical dump of a parsed dump is byte-identical
    s = canonical_json(p)
    assert canonical_json(json.loads(s)) == s
    prov = bst.provenance
    assert prov["schema"] == drift_mod.PROVENANCE_SCHEMA
    assert prov["run_id"] and prov["params_digest"]
    assert prov["profile_digest"] == profile_digest(p)


def test_profile_roundtrip_model_string(bst):
    s = bst.model_to_string()
    assert "\ndata_profile:\n" in s and "\nprovenance:\n" in s
    b2 = lgb.Booster(model_str=s)
    assert canonical_json(b2.data_profile) == canonical_json(
        bst.data_profile)
    assert canonical_json(b2.provenance) == canonical_json(bst.provenance)
    # and the re-serialized artifact carries the identical blocks
    assert canonical_json(lgb.Booster(
        model_str=b2.model_to_string()).data_profile) \
        == canonical_json(bst.data_profile)


def test_profile_roundtrip_checkpoint(tmp_path):
    from lightgbm_tpu.resilience.state import booster_from_checkpoint
    X, y = _data(seed=3)
    a = _train(X, y, rounds=6, checkpoint_dir=str(tmp_path / "ck"),
               checkpoint_period=3)
    b = booster_from_checkpoint(str(tmp_path / "ck"))
    assert canonical_json(b.data_profile) == canonical_json(
        a.data_profile)
    assert b.provenance["run_id"] == a.provenance["run_id"]


def test_checkpoint_payload_holds_a_copy_of_the_profile(bst):
    # the writer thread serialises the payload while the finalize epilogue
    # may still add "score" to the live profile
    from lightgbm_tpu.resilience.state import capture
    payload, _ = capture(bst._gbdt)
    assert payload["data_profile"] == bst.data_profile
    assert payload["data_profile"] is not bst._gbdt.data_profile
    assert payload["provenance"] is not bst._gbdt.provenance


def test_resume_chains_parent_checkpoint(tmp_path):
    X, y = _data(seed=4)
    ck = str(tmp_path / "ck")
    a = _train(X, y, rounds=4, checkpoint_dir=ck, checkpoint_period=2)
    assert a.provenance["parent_checkpoint"] == ""
    b = _train(X, y, rounds=8, checkpoint_dir=ck, checkpoint_period=2,
               resume=ck)
    assert b.provenance["parent_checkpoint"] != ""


def test_all_missing_column_profile_and_monitor():
    rng = np.random.RandomState(5)
    X = rng.rand(400, 4).astype(np.float32)
    X[:, 2] = np.nan                      # all-missing column
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = _train(X, y, rounds=3, use_missing=True)
    prof = bst.data_profile
    assert prof is not None
    # the monitor stays finite when fed the same all-missing shape
    mon = DriftMonitor(prof, eval_rows=1)
    mon.accumulate_raw(np.asarray(X[:64], np.float64))
    mon.accumulate_scores(np.zeros(64))
    res = mon.evaluate(force=True)
    assert res is not None
    assert all(np.isfinite(v) for v in res["psi"].values())


# ----------------------------------------------------- serving monitor
def _serve_counters(bst, feed_shift, requests=20, rows=40):
    svc = PredictionService({"m": bst}, max_batch_rows=256,
                            max_delay_ms=0.5, min_bucket_rows=16,
                            batch_events=False, drift_eval_rows=128,
                            drift_hysteresis=2)
    svc.warmup()
    rng = np.random.RandomState(17)
    s0 = svc.stats()
    for _ in range(requests):
        Xq = rng.rand(rows, F).astype(np.float32)
        if feed_shift:
            Xq = np.clip(Xq + 0.35, 0.0, 1.0).astype(np.float32)
        svc.predict("m", Xq, timeout=60)
    s1 = svc.stats()
    rep = svc.run_report()
    stats = svc.stats()
    svc.close()
    snap = svc.tel.snapshot()
    return {"dispatches": s1["dispatches"] - s0["dispatches"],
            "compiles": s1["compiles"] - s0["compiles"],
            "requests": requests, "snap": snap, "report": rep,
            "stats": stats}


def test_serve_drift_ab_acceptance(bst):
    """The ISSUE acceptance: distribution-B feed vs the A-trained model
    raises exactly one hysteresis-gated alert with nonzero per-feature
    PSI; the A-fed control raises none — dispatches/request == 1.0 and
    zero compiles in BOTH runs."""
    ctrl = _serve_counters(bst, feed_shift=False)
    drifted = _serve_counters(bst, feed_shift=True)
    for r in (ctrl, drifted):
        assert r["dispatches"] == r["requests"]     # exactly 1.0/request
        assert r["compiles"] == 0                   # zero recompiles
    cc = ctrl["snap"]["counters"]
    dc = drifted["snap"]["counters"]
    assert cc.get("drift.alerts", 0) == 0
    assert dc.get("drift.alerts", 0) == 1
    assert dc.get("drift.evaluations", 0) >= 2      # hysteresis had data
    alert = [e for e in drifted["snap"]["events"]
             if e.get("event") == "drift_alert"]
    assert len(alert) == 1
    assert alert[0]["model_id"] == "m"
    assert alert[0]["worst_psi"] > 0.2
    assert alert[0]["worst_feature"] >= 0
    # per-feature gauges exported under drift.psi.f<i>
    gauges = drifted["snap"]["gauges"]
    assert any(k.startswith("drift.psi.f") and v > 0.2
               for k, v in gauges.items())
    assert gauges.get("drift.psi_max", 0) > 0.2
    # the service stats surface the drift block
    assert drifted["stats"]["drift"]["alerts"] == 1
    assert ctrl["stats"]["drift"]["alerts"] == 0


def test_serve_drift_report_sections(bst):
    drifted = _serve_counters(bst, feed_shift=True)
    rep = drifted["report"]
    assert rep["drift"]["alert_count"] == 1
    assert any(a.get("event") == "drift_alert"
               for a in rep["drift"]["alerts"])
    lin = rep["lineage"]["m"]
    assert lin["provenance"]["run_id"] == bst.provenance["run_id"]
    assert lin["model_age_s"] is not None and lin["model_age_s"] >= 0


def test_run_diff_flags_new_drift_alert(bst):
    from lightgbm_tpu.obs.report import compare_reports
    ctrl = _serve_counters(bst, feed_shift=False)
    drifted = _serve_counters(bst, feed_shift=True)
    rep = compare_reports(ctrl["report"], drifted["report"],
                          threshold=9.0)
    names = [e["name"] for e in rep["regressions"]]
    assert any(n.startswith("drift_alert:") for n in names), names
    # same-report diff is clean of drift regressions
    rep2 = compare_reports(drifted["report"], drifted["report"],
                           threshold=9.0)
    assert not any(str(e["name"]).startswith("drift_alert:")
                   for e in rep2["regressions"])


def test_profileless_model_degrades_structurally(bst):
    """A model file without an embedded profile serves with one
    drift_unavailable event — never an exception (satellite f)."""
    s = bst.model_to_string()
    stripped = s.split("\ndata_profile:")[0] + "\n"
    b = lgb.Booster(model_str=stripped)
    assert b.data_profile is None
    svc = PredictionService({"m": b}, max_batch_rows=128,
                            max_delay_ms=0.5, batch_events=False)
    svc.warmup()
    rng = np.random.RandomState(2)
    out = svc.predict("m", rng.rand(16, F).astype(np.float32),
                      timeout=60)
    assert out.shape[0] == 16
    svc.close()
    snap = svc.tel.snapshot()
    unavailable = [e for e in snap["events"]
                   if e.get("event") == "drift_unavailable"]
    assert len(unavailable) == 1
    assert unavailable[0]["reason"] == "no_embedded_profile"
    assert snap["counters"].get("drift.alerts", 0) == 0


def test_rollover_chains_lineage(bst):
    X, y = _data(seed=9)
    cand = _train(X, y, rounds=3)
    svc = PredictionService({"m": bst}, max_batch_rows=128,
                            max_delay_ms=0.5, batch_events=False)
    svc.warmup()
    rep = svc.rollover("m", cand)
    assert rep["promoted"]
    snap = svc.tel.snapshot()
    svc.close()
    ev = [e for e in snap["events"] if e.get("event") == "serve_rollover"]
    assert len(ev) == 1
    assert ev[0]["old_run_id"] == bst.provenance["run_id"]
    assert ev[0]["new_run_id"] == cand.provenance["run_id"]
    assert ev[0]["new_profile_digest"] == \
        cand.provenance["profile_digest"][:16]
    # the promoted model's age gauge restarted
    assert snap["gauges"].get("serve.model_age_s.m", 1e9) < 60.0


def test_drift_monitor_hysteresis_latches_once():
    prof = {"schema": drift_mod.PROFILE_SCHEMA, "rows": 100,
            "features": [{"index": 0, "num_bin": 4,
                          "counts": [100, 0, 0, 0],
                          "missing_rate": 0.0, "categorical": False}]}
    mon = DriftMonitor(prof, psi_threshold=0.2, eval_rows=1,
                       hysteresis=2)
    shifted = np.full((8, 1), 3, np.int64)
    mon.accumulate(shifted)
    assert mon.evaluate(force=True)["alert"] is False   # 1st over: armed
    mon.accumulate(shifted)
    assert mon.evaluate(force=True)["alert"] is True    # 2nd over: fires
    mon.accumulate(shifted)
    assert mon.evaluate(force=True)["alert"] is False   # latched
    assert mon.alerts == 1


# --------------------------------------------------------- ingest drift
def test_ingest_mapper_drift_event(tmp_path):
    from lightgbm_tpu.ingest.prefetch import publish_ingest_stats
    from lightgbm_tpu.obs.registry import Telemetry
    rng = np.random.RandomState(0)
    Xa = rng.rand(400, 4).astype(np.float32)
    ya = (Xa[:, 0] > 0.5).astype(np.float32)
    pa = str(tmp_path / "a.csv")
    with open(pa, "w") as fh:
        for i in range(len(ya)):
            fh.write(",".join([f"{ya[i]:g}"]
                              + [repr(float(v)) for v in Xa[i]]) + "\n")
    dsp = {"max_bin": 63, "verbose": -1, "two_round": True,
           "ingest_chunk_rows": 97}
    ds_a = lgb.Dataset(pa, params=dict(dsp))
    ds_a.construct()
    # the training file diffs clean against its own mappers
    md_a = ds_a._inner.ingest_stats["mapper_drift"]
    assert md_a["flagged_chunks"] == 0
    # a validation file from a SHIFTED distribution, binned against the
    # frozen reference mappers, must flag
    Xb = (Xa + 2.0).astype(np.float32)
    pb = str(tmp_path / "b.csv")
    with open(pb, "w") as fh:
        for i in range(len(ya)):
            fh.write(",".join([f"{ya[i]:g}"]
                              + [repr(float(v)) for v in Xb[i]]) + "\n")
    ds_b = lgb.Dataset(pb, params=dict(dsp), reference=ds_a)
    ds_b.construct()
    md_b = ds_b._inner.ingest_stats["mapper_drift"]
    assert md_b["flagged_chunks"] > 0
    assert md_b["out_of_range"] > 0
    assert md_b["worst_feature"] >= 0
    # publishing the stats lands the structured event + counters
    tel = Telemetry(enabled=True)
    publish_ingest_stats(tel, ds_b._inner.ingest_stats)
    snap = tel.snapshot()
    assert snap["counters"]["ingest.drift_chunks"] == \
        md_b["flagged_chunks"]
    assert snap["counters"]["ingest.out_of_range_values"] == \
        md_b["out_of_range"]
    ev = [e for e in snap["events"] if e.get("event") == "mapper_drift"]
    assert len(ev) == 1 and ev[0]["threshold"] == md_b["threshold"]


def test_chunk_mapper_drift_rates():
    from lightgbm_tpu.obs.drift import chunk_mapper_drift
    rng = np.random.RandomState(1)
    # float32 throughout: the mappers froze on the float32 view, and a
    # float64 value past the rounded max would read as (tiny) drift
    X = rng.rand(300, 3).astype(np.float32).astype(np.float64)
    y = (X[:, 0] > 0.5).astype(np.float32)
    bst = _train(X.astype(np.float32), y, rounds=2)
    ds = bst.train_set._inner
    clean = chunk_mapper_drift(ds.mappers, ds.used_features, X)
    assert clean["out_of_range"] == 0 and clean["new_categories"] == 0
    drifted = chunk_mapper_drift(ds.mappers, ds.used_features, X + 5.0)
    assert drifted["out_of_range_rate"] > 0.5


# --------------------------------------------- training-side lineage
def test_training_run_report_carries_lineage(tmp_path, bst):
    X, y = _data(seed=6)
    rep_path = str(tmp_path / "rep.json")
    b = _train(X, y, rounds=3, run_report_out=rep_path,
               telemetry_out=str(tmp_path / "tel.jsonl"))
    rep = json.load(open(rep_path))
    lin = rep["lineage"]["training"]
    assert lin["run_id"] == b.provenance["run_id"]
    assert lin["profile_digest"] == profile_digest(b.data_profile)
    assert "drift" in rep        # section present even with no alerts
    assert rep["drift"]["alert_count"] == 0


# ------------------------------------------------- export / obs_tail
def test_metrics_renders_empty_dist_without_quantiles():
    from lightgbm_tpu.obs.export import render_openmetrics
    from lightgbm_tpu.obs.registry import Telemetry
    # empty-ring summary: count/sum only, no NaN quantiles
    summ = Telemetry._dist_summary([], (0, 0.0))
    assert summ == {"count": 0, "sum": 0.0}
    snap = {"counters": {}, "gauges": {}, "timings": {},
            "dists": {"serve.latency_ms": {"count": 0, "sum": 0.0}}}
    body = render_openmetrics(snap)
    assert "quantile" not in body
    assert "nan" not in body.lower()
    # a populated ring still renders its quantile series
    snap2 = {"counters": {}, "gauges": {}, "timings": {},
             "dists": {"serve.latency_ms": Telemetry._dist_summary(
                 [1.0, 2.0, 3.0])}}
    assert 'quantile="0.5"' in render_openmetrics(snap2)


def test_obs_tail_summary_drift_line(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    from obs_tail import summarize
    records = [
        {"ts": 1.0, "event": "drift", "model_id": "m", "psi_max": 0.41,
         "score_psi": 0.1, "rows": 256, "model_age_s": 12.5},
        {"ts": 2.0, "event": "drift_alert", "model_id": "m",
         "psi_max": 0.41, "worst_feature": 2, "worst_psi": 0.41},
    ]
    out = summarize(records)
    line = next(l for l in out.splitlines() if l.startswith("drift:"))
    assert "psi_max=0.41" in line
    assert "alerts=1" in line
    assert "model_age_s=12.5" in line
    # drift_alert records land in the findings tail too
    assert "findings (1):" in out
