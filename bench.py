"""Benchmark: Higgs-shaped GBDT training throughput on one TPU chip.

Mirrors the reference's headline benchmark configuration
(ref: docs/GPU-Performance.rst:108-123 — Higgs, max_bin=63, num_leaves=255,
lr=0.1; docs/Experiments.rst:113 — CPU LightGBM trains Higgs 10.5M×28 in
130.094 s / 500 iterations = 0.2602 s/iter on 2×E5-2690v4).

Default (chip) mode drives the fused route+histogram Pallas engine at the
REAL 10.5M-row scale (BENCH_ROWS scales down for smoke runs) and prints
ONE JSON line:
  {"metric": "higgs_sec_per_iter_10.5M_rows", "value": ..., "unit": "s",
   "vs_baseline": baseline/ours (>1 means faster than reference CPU),
   "platform": ..., "device_kind": ..., "device_count": ...}
It runs the fused engine on a TPU or exits non-zero: no engine fallback,
no CPU fallback, and every failure path (dead backend, SIGTERM, internal
deadline, exception) ends with a non-zero exit code.

``--micro`` / ``--serve --micro``: deterministic CPU-backend modes (small
synthetic data, fused engine in interpret mode, dispatch/drain/compile
counters from telemetry) — exact counts CI gates on, never device times;
see run_micro() / run_serve().
"""
from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

import numpy as np

# The bench record must be indestructible (round 3 lost an
# already-measured perf number because the JSON printed only after a
# ~1-hour quality leg that the driver's budget killed).  The
# current best-known result lives here; it is printed+flushed the moment
# each leg lands, and re-emitted by the SIGTERM handler / watchdog if a
# later leg dies, so the LAST stdout line is always parseable JSON.
_RESULT = {"metric": "higgs_sec_per_iter_10.5M_rows", "value": None,
           "unit": "s", "vs_baseline": None}

# Failure trail: which phases completed + the timer/telemetry snapshot
# collected so far, attached to the record as "tail" whenever a leg dies
# (a dead run must say where the time went, not just that it went).
_TAIL = {"phases": []}
_T0 = time.time()

# Persistent bench trajectory: every run appends its (latest) record to
# BENCH_TRAJECTORY.jsonl so scripts/bench_compare.py can diff consecutive
# runs and flag regressions — the bench history must outlive any single
# round's stdout (ISSUE 4 satellite).
_RUN_ID = f"{time.strftime('%Y%m%dT%H%M%S')}_{os.getpid()}"
_TRAJECTORY_PATH = os.environ.get(
    "BENCH_TRAJECTORY",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "BENCH_TRAJECTORY.jsonl"))


def _append_trajectory():
    """Mirror the current record into the trajectory file, each line
    carrying the timer's phase totals so bench_compare.py can diff
    per-phase, not just the headline number. A run that emits twice
    appends twice — the reader (bench_compare.load_trajectory) keeps
    each run_id's last line, so a plain O(1) append suffices and
    concurrent runs cannot erase each other's records the way a
    read-modify-replace would. Must never kill a run."""
    rec = dict(_RESULT)
    rec["run_id"] = _RUN_ID
    rec["ts"] = round(time.time(), 3)
    try:
        from lightgbm_tpu.utils.timer import global_timer
        rec["phase_timings"] = {
            name: {"total": round(st.total, 4), "count": st.count}
            for name, st in global_timer.stats().items()}
    except Exception:
        pass
    try:
        with open(_TRAJECTORY_PATH, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    except Exception as e:
        print(f"bench trajectory append failed: {e}", file=sys.stderr)


def _phase(name: str):
    _TAIL["phases"].append({"phase": name, "t": round(time.time() - _T0, 3)})
    print(f"bench phase: {name}", file=sys.stderr)


def _attach_tail():
    try:
        from lightgbm_tpu.utils.timer import global_timer
        _TAIL["timer"] = {name: {"total": round(st.total, 4),
                                 "count": st.count}
                          for name, st in global_timer.stats().items()}
    except Exception as e:  # the tail must never kill the record
        _TAIL["timer_error"] = f"{type(e).__name__}: {e}"[:200]
    _RESULT["tail"] = _TAIL


def _emit():
    print(json.dumps(_RESULT), flush=True)
    _append_trajectory()


def _die_with_record(reason: str):
    """Emit the record with the failure reason, then exit NON-ZERO (hard
    exit: this runs from the signal handler and the watchdog thread,
    possibly while the main thread is stuck in a device call)."""
    _RESULT.setdefault("error", reason)
    _attach_tail()
    _emit()
    os._exit(1)


def _install_guards():
    # SIGTERM: what `timeout` (the driver) sends first
    signal.signal(signal.SIGTERM,
                  lambda s, f: _die_with_record("sigterm"))
    # watchdog thread: fires even when the main thread is stuck inside a
    # blocking device call (signal handlers can't run there)
    deadline = float(os.environ.get("BENCH_DEADLINE_SECS", "3000"))

    def _watch():
        time.sleep(deadline)
        _die_with_record(f"internal_deadline_{deadline:.0f}s")

    threading.Thread(target=_watch, daemon=True).start()


def _free_port() -> int:
    # the launcher's probe (SO_REUSEADDR narrows the rebind race);
    # imported lazily — by the time a bench leg needs a port,
    # lightgbm_tpu is imported anyway
    from lightgbm_tpu.parallel.launcher import _free_port as probe
    return probe()


def _require_tpu() -> None:
    """Chip modes measure the TPU or exit non-zero; the record names the
    device whatever happens next."""
    import jax
    devices = jax.devices()
    _RESULT.update(platform=devices[0].platform,
                   device_kind=devices[0].device_kind,
                   device_count=len(devices))
    print(f"bench device: {devices[0].platform} {devices[0].device_kind} "
          f"x {len(devices)}", file=sys.stderr)
    if devices[0].platform != "tpu":
        _die_with_record(f"no_tpu:found_{devices[0].platform}")


def _make_data(n_rows: int, n_feat: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n_rows, n_feat).astype(np.float32)
    w = rng.randn(n_feat).astype(np.float32)
    # centred margin: with raw X in [0, 1] the class balance rides on
    # sum(w) — the micro shape (4000 x 10, seed 0) came out with ONE
    # positive row and its training dried up after two iterations
    y = ((X - 0.5) @ w + 0.5 * rng.randn(n_rows) > 0).astype(np.float32)
    return X, y


def _run(X, y, n_iters: int):
    import jax
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 1e-3, "verbose": -1,
              "metric": "None", "tpu_engine": "fused"}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    booster = lgb.Booster(params=params, train_set=ds)
    g = booster._gbdt
    if not (g.use_fused and not g.fused_interpret):
        raise RuntimeError(
            f"bench: the compiled fused engine did not engage "
            f"(use_fused={g.use_fused}, interpret={g.fused_interpret})")

    def settle():
        # the driver pipelines iterations asynchronously; timing is only
        # honest if the host model list AND the device queue are settled
        if hasattr(g, "drain_pending"):
            g.drain_pending()
        jax.block_until_ready(g.scores)

    booster.update()  # warmup: compile + first tree (the one fast step;
    # later iterations compile nothing)
    settle()
    t0 = time.perf_counter()
    for _ in range(n_iters):
        booster.update()
    settle()
    return (time.perf_counter() - t0) / n_iters


def _quality_leg(iters: int = 500) -> dict:
    """Differential AUC vs the rebuilt reference CPU package on identical
    data + params (the bf16 hi/lo histogram precision claim needs a
    quality number at scale, not a 0.005-tolerance fixture).
    Ref contract being matched: docs/GPU-Performance.rst:136 — the fp32-
    histogram GPU build holds AUC to ~5e-4 of the CPU build on Higgs.
    Our AUC is pushed into _RESULT and emitted BEFORE the (up to 1 h)
    reference-CPU subprocess so a deadline mid-reference-run cannot
    destroy the measured TPU number."""
    import lightgbm_tpu as lgb
    from sklearn.metrics import roc_auc_score

    n_train = int(os.environ.get("BENCH_QUALITY_ROWS", 1_000_000))
    n_test = max(100_000, n_train // 5)
    rng = np.random.RandomState(7)
    n_feat = 28
    X = rng.rand(n_train + n_test, n_feat).astype(np.float32)
    w = rng.randn(n_feat).astype(np.float32)
    # interactions make the trees matter; noise keeps AUC off the ceiling
    margin = X @ w + 0.9 * X[:, 0] * X[:, 1] - 0.9 * X[:, 2] * X[:, 3]
    y = (margin + 0.8 * rng.randn(len(X)) > np.median(margin)) \
        .astype(np.float32)
    Xtr, ytr = X[:n_train], y[:n_train]
    Xte, yte = X[n_train:], y[n_train:]
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
              "learning_rate": 0.1, "num_iterations": iters,
              "verbose": -1, "metric": "None"}

    ds = lgb.Dataset(Xtr, label=ytr, params={"max_bin": 63, "verbose": -1})
    # the quality claim is about the fused engine's bf16 hi/lo
    # histograms: that engine or an exception, never a stand-in
    bst = lgb.train(dict(params, tpu_engine="fused"), ds)
    auc = float(roc_auc_score(yte, bst.predict(Xte)))
    out = {"auc": round(auc, 6),
           "auc_bayes": round(float(roc_auc_score(yte, margin[n_train:])),
                              6)}
    _RESULT.update(out)
    _emit()   # the measured TPU AUC is now on stdout, whatever happens
              # to the reference-CPU leg below

    # the reference package is built out-of-tree by
    # scripts/build_reference.sh; absent -> report our AUC alone
    if os.path.isdir("/tmp/refpkg"):
        import subprocess
        code = (
            "import sys, json, numpy as np\n"
            "sys.path.insert(0, '/tmp/refpkg')\n"
            "import lightgbm as rl\n"
            "from sklearn.metrics import roc_auc_score\n"
            f"d = np.load('/tmp/bench_quality.npz')\n"
            f"ds = rl.Dataset(d['Xtr'], label=d['ytr'],\n"
            f"                params={{'max_bin': 63, 'verbose': -1}})\n"
            f"b = rl.train({params!r}, ds)\n"
            "auc = roc_auc_score(d['yte'], b.predict(d['Xte']))\n"
            "print(json.dumps({'auc_ref': round(float(auc), 6)}))\n")
        np.savez("/tmp/bench_quality.npz", Xtr=Xtr, ytr=ytr, Xte=Xte,
                 yte=yte)
        try:
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True,
                               timeout=3600)
            ref = json.loads(r.stdout.strip().splitlines()[-1])
            out.update(ref)
            out["auc_delta"] = round(out["auc"] - ref["auc_ref"], 6)
        except Exception as e:
            print(f"quality leg: reference run failed: {e}",
                  file=sys.stderr)
    return out


def run_micro() -> None:
    """Deterministic CPU-backend micro benchmark (``--micro``).

    A small synthetic dataset on the CPU backend through the REAL
    product path (lgb.train -> megastep/pipelined fast path, fused
    engine in interpret mode), with the dispatch-per-iteration and
    drain counters pulled from telemetry so bench_compare.py can flag a
    fast-path eviction (dispatch-count regression) exactly. Its
    ``*_sec_per_iter`` fields are CPU-interpreter times and say nothing
    about the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"   # before any jax import
    _RESULT.update(metric="micro_cpu_sec_per_iter", unit="s")
    _install_guards()
    from lightgbm_tpu.utils.timer import global_timer
    global_timer.enable()
    _phase("micro_start")
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.utils.platform import compilation_cache_dir
    compilation_cache_dir()
    import lightgbm_tpu as lgb

    n_rows = int(os.environ.get("BENCH_MICRO_ROWS", 4000))
    n_iters = int(os.environ.get("BENCH_MICRO_ITERS", 8))
    n_feat = 10
    _RESULT["bench_config"] = {"mode": "micro", "rows": n_rows,
                               "iters": n_iters,
                               "eval_iters": int(os.environ.get(
                                   "BENCH_MICRO_EVAL_ITERS", 16))}
    _RESULT["platform"] = "cpu"
    X, y = _make_data(n_rows, n_feat)

    tel_path = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                            f"bench_micro_tel_{os.getpid()}.jsonl")
    # run reports land at stable paths so CI can run scripts/run_diff.py
    # over the job's two reports after the bench exits
    report_dir = os.environ.get("BENCH_REPORT_DIR",
                                os.environ.get("TMPDIR", "/tmp"))
    report_base = os.path.join(report_dir, "bench_micro_run_report.json")
    report_obs = os.path.join(report_dir,
                              "bench_micro_run_report_obs.json")
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 15,
              "learning_rate": 0.2, "min_data_in_leaf": 5, "verbose": -1,
              "metric": "None", "tpu_engine": "fused",
              # explicit: interpret-mode megastep is opt-in (the micro
              # mode exists precisely to measure its dispatch counters)
              "tpu_megastep": True, "telemetry_out": tel_path}
    t0 = time.perf_counter()
    bst = lgb.train(dict(params, run_report_out=report_base), lgb.Dataset(
        X, label=y, params={"max_bin": 63, "verbose": -1}),
        num_boost_round=n_iters)
    wall = time.perf_counter() - t0
    _phase("micro_train_ok")
    snap = bst.telemetry()
    c = snap.get("counters", {})
    # the KEPT iteration count is the denominator everywhere: a run that
    # dries up early (no-more-splits) must not understate sec/iter
    iters = max(1, int(c.get("iterations", n_iters)))
    _RESULT["value"] = round(wall / iters, 5)
    _RESULT["iterations_kept"] = iters
    _RESULT["engine"] = "fused"
    _RESULT["counters"] = {k: v for k, v in sorted(c.items())
                           if k.startswith(("train.", "iterations",
                                            "events."))}
    _RESULT["dispatches_per_iter"] = round(
        float(c.get("train.dispatches", 0)) / iters, 4)
    _RESULT["drains"] = int(c.get("train.drains", 0))
    _RESULT["fast_path"] = bool(bst._gbdt._fast_path_ok())
    # attach the consolidated run report (trimmed to its comparable
    # core — the full artifact stays on disk for run_diff) so the
    # trajectory history carries attribution, not just headlines
    try:
        rep = json.load(open(report_base))
        _RESULT["run_report"] = {
            "path": report_base, "schema": rep.get("schema"),
            "run_id": rep.get("run_id"),
            "derived": rep.get("derived"),
            "cost": {k: rep.get("cost", {}).get(k)
                     for k in ("flops_per_iter", "hlo_bytes_per_iter",
                               "achieved_fraction")},
            "reasons": rep.get("reasons")}
        _RESULT["run_report_ok"] = bool(
            str(rep.get("schema", "")).startswith(
                "lightgbm_tpu.run_report/"))
    except Exception as e:
        print(f"run report attach failed: {e}", file=sys.stderr)
        _RESULT["run_report_ok"] = False
    _emit()   # the bare-training counters are on stdout now

    # ---- eval leg: the dominant production config — train() with two
    # valid sets + early_stopping + log_evaluation + record_evaluation —
    # must stay on the megastep (on-device eval + drain-replay
    # callbacks, metric/traced.py). `eval_dispatches_per_iter` is the
    # deterministic gate: a regression back to the per-iteration sync
    # driver moves it from ~1/chunk to >= 3.
    from lightgbm_tpu import callback as lgb_cb
    tel_eval = tel_path + ".eval"
    n_eval_iters = int(os.environ.get("BENCH_MICRO_EVAL_ITERS", 16))
    Xv1, yv1 = _make_data(max(512, n_rows // 4), n_feat, seed=1)
    Xv2, yv2 = _make_data(max(512, n_rows // 4), n_feat, seed=2)
    rec = {}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst2 = lgb.train(
        dict(params, telemetry_out=tel_eval,
             metric=["binary_logloss", "auc"], early_stopping_round=25),
        ds, num_boost_round=n_eval_iters,
        valid_sets=[lgb.Dataset(Xv1, label=yv1, reference=ds),
                    lgb.Dataset(Xv2, label=yv2, reference=ds)],
        callbacks=[lgb_cb.log_evaluation(100),
                   lgb_cb.record_evaluation(rec)])
    eval_wall = time.perf_counter() - t0
    _phase("micro_eval_train_ok")
    c2 = bst2.telemetry().get("counters", {})
    eval_iters = max(1, int(c2.get("iterations", n_eval_iters)))
    _RESULT["eval_sec_per_iter"] = round(eval_wall / eval_iters, 5)
    _RESULT["eval_dispatches_per_iter"] = round(
        float(c2.get("train.dispatches", 0)) / eval_iters, 4)
    _RESULT["eval_iterations_kept"] = eval_iters
    _RESULT["eval_curve_points"] = len(
        rec.get("valid_0", {}).get("binary_logloss", []))
    # the bare-leg `counters`/`fast_path`/`drains` fields above describe
    # the FIRST training; the eval leg's counters get their own
    # namespaced copy so the merged record stays unambiguous
    _RESULT["eval_counters"] = {k: v for k, v in sorted(c2.items())
                                if k.startswith(("train.", "iterations",
                                                 "events."))}
    _emit()   # the eval-leg counters are on stdout now

    # ---- checkpoint leg: the bare training again with async resilience
    # checkpoints armed. Checkpoints capture at drain boundaries on a
    # background thread, so they must be dispatch-neutral:
    # ckpt_dispatches_per_iter == dispatches_per_iter EXACTLY is the
    # deterministic gate (bench_compare + the perf-smoke absolute
    # assertion) — any regression that makes checkpointing evict the
    # fast path or add device round trips moves the counter.
    import shutil
    import tempfile
    ckpt_root = tempfile.mkdtemp(prefix="bench_micro_ckpt_")
    tel_ckpt = tel_path + ".ckpt"
    ds3 = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst3 = lgb.train(dict(params, telemetry_out=tel_ckpt,
                          checkpoint_dir=ckpt_root, checkpoint_period=4),
                     ds3, num_boost_round=n_iters)
    ckpt_wall = time.perf_counter() - t0
    _phase("micro_ckpt_train_ok")
    c3 = bst3.telemetry().get("counters", {})
    ckpt_iters = max(1, int(c3.get("iterations", n_iters)))
    _RESULT["ckpt_sec_per_iter"] = round(ckpt_wall / ckpt_iters, 5)
    _RESULT["ckpt_dispatches_per_iter"] = round(
        float(c3.get("train.dispatches", 0)) / ckpt_iters, 4)
    _RESULT["checkpoints_written"] = int(c3.get("ckpt.written", 0))
    shutil.rmtree(ckpt_root, ignore_errors=True)

    # ---- observability leg: the bare training again with the LIVE
    # OpenMetrics exporter serving scrapes. The observability plane may
    # not touch the fast path: obs_dispatches_per_iter must equal
    # dispatches_per_iter EXACTLY (bench_compare deterministic counter +
    # the perf-smoke absolute assertion), and a mid-process scrape of
    # the endpoint must return parseable OpenMetrics whose dispatch
    # counter agrees with the registry snapshot.
    obs_port = _free_port()
    tel_obs = tel_path + ".obs"
    ds4 = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst4 = lgb.train(dict(params, telemetry_out=tel_obs,
                          metrics_port=obs_port,
                          run_report_out=report_obs),
                     ds4, num_boost_round=n_iters)
    obs_wall = time.perf_counter() - t0
    _phase("micro_obs_train_ok")
    c4 = bst4.telemetry().get("counters", {})
    obs_iters = max(1, int(c4.get("iterations", n_iters)))
    _RESULT["obs_sec_per_iter"] = round(obs_wall / obs_iters, 5)
    _RESULT["obs_dispatches_per_iter"] = round(
        float(c4.get("train.dispatches", 0)) / obs_iters, 4)
    # the exporter outlives finalize by design — the endpoint must
    # answer while the process holds the booster. Scrape its ACTUAL
    # url: a TCP race on the probed port degrades the exporter to an
    # ephemeral bind (its own resilience contract), not a CI failure.
    mx = getattr(bst4._gbdt, "_metrics", None)
    try:
        from lightgbm_tpu.obs.export import scrape
        _, body = scrape(mx.url, timeout=10)
        line = next(l for l in body.splitlines()
                    if l.startswith("lgbm_train_dispatches_total"))
        _RESULT["exporter_scrape_ok"] = (
            float(line.rsplit(" ", 1)[1])
            == float(c4.get("train.dispatches", 0)))
    except Exception as e:
        print(f"exporter scrape failed: {e}", file=sys.stderr)
        _RESULT["exporter_scrape_ok"] = False
    # armed-but-untriggered /profile endpoint: arming after the last
    # drain boundary leaves the request pending forever — the window
    # never opens, the counters never move (the CI assertion that
    # obs_dispatches_per_iter == dispatches_per_iter above is measured
    # with this armed endpoint live), and a second POST refuses with
    # 409 (the overlap contract)
    try:
        from lightgbm_tpu.obs.export import post
        base_url = mx.url.rsplit("/metrics", 1)[0]
        code1, body1 = post(f"{base_url}/profile?iters=2")
        code2, body2 = post(f"{base_url}/profile?iters=2")
        _RESULT["profile_armed_untriggered_ok"] = (
            code1 == 200 and bool(body1.get("armed"))
            and code2 == 409 and not body2.get("armed", True))
        c4b = bst4.telemetry().get("counters", {})
        # arming must not have moved a single dispatch
        _RESULT["profile_armed_untriggered_ok"] &= (
            c4b.get("train.dispatches") == c4.get("train.dispatches"))
    except Exception as e:
        print(f"profile arm check failed: {e}", file=sys.stderr)
        _RESULT["profile_armed_untriggered_ok"] = False
    finally:
        if mx is not None:
            mx.stop()
    _emit()   # the obs-leg counters are on stdout now

    # ---- control-plane leg: POST /profile?iters=2 against a LIVE
    # megastep training job (the ISSUE 15 acceptance run). Two chunks
    # of n_iters iterations each, a watcher thread arming the endpoint
    # as soon as it answers: the on-demand jax.profiler window opens at
    # a drain boundary / iteration edge and closes at the next drain
    # boundary — so the leg must measure ctl_dispatches_per_iter ==
    # dispatches_per_iter EXACTLY (2 dispatches / 2*n_iters iterations
    # == 1/n_iters == the base leg; profiling is dispatch-neutral),
    # with exactly one closed profile_window and a non-empty trace dir.
    import threading as _threading
    ctl_port = _free_port()
    tel_ctl = tel_path + ".ctl"
    ctl_prof_dir = tempfile.mkdtemp(prefix="bench_micro_ctlprof_")
    # roofline leg rides the control-plane leg: the window close parses
    # the trace (obs/kernelstats.py) and appends measured samples to
    # this perf database (obs/perfdb.py); a second profiled run below
    # appends to the SAME file to prove cross-run accumulation
    ctl_perfdb = tel_path + ".perfdb"
    if os.path.exists(ctl_perfdb):
        os.unlink(ctl_perfdb)
    n_ctl_iters = 2 * n_iters
    ctl_stop = _threading.Event()
    ctl_armed = {}

    def _arm_profile():
        from lightgbm_tpu.obs.export import post as _post
        from lightgbm_tpu.obs.export import scrape as _scrape
        url = (f"http://127.0.0.1:{ctl_port}/profile?iters=2"
               f"&dir={ctl_prof_dir}")
        # wait until the first chunk has DISPATCHED before arming, so
        # the window's open lands at the chunk's drain boundary — the
        # drain-boundary semantics the acceptance criterion names
        # (arming earlier is equally dispatch-neutral, just opens at
        # the iteration-0 edge instead). Poll /snapshot, NOT /metrics:
        # the metrics body is TTL-cached ~1 s, and a stale read here
        # could slip the arm past the first drain boundary on a fast
        # runner (the window must close at a drain, not at finalize)
        while not ctl_stop.is_set():
            try:
                _, body = _scrape(
                    f"http://127.0.0.1:{ctl_port}/snapshot", timeout=2)
                if json.loads(body).get("counters", {}).get(
                        "train.dispatches", 0) >= 1:
                    break
            except Exception:
                pass
            time.sleep(0.02)
        while not ctl_stop.is_set():
            try:
                code, body = _post(url, timeout=2)
                ctl_armed["code"], ctl_armed["body"] = code, body
                if code == 200:
                    return
            except Exception:
                pass
            time.sleep(0.02)

    ctl_thread = _threading.Thread(target=_arm_profile, daemon=True)
    ctl_thread.start()
    ds6 = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst6 = lgb.train(dict(params, telemetry_out=tel_ctl,
                          metrics_port=ctl_port,
                          tpu_megastep_iters=n_iters,
                          perf_db=ctl_perfdb),
                     ds6, num_boost_round=n_ctl_iters)
    ctl_wall = time.perf_counter() - t0
    ctl_stop.set()
    ctl_thread.join(timeout=5)
    _phase("micro_ctl_train_ok")
    snap6 = bst6._gbdt.telemetry.snapshot()
    c6 = snap6.get("counters", {})
    ctl_iters = max(1, int(c6.get("iterations", n_ctl_iters)))
    _RESULT["ctl_sec_per_iter"] = round(ctl_wall / ctl_iters, 5)
    _RESULT["ctl_dispatches_per_iter"] = round(
        float(c6.get("train.dispatches", 0)) / ctl_iters, 4)
    windows = [e for e in snap6.get("events", [])
               if e.get("event") == "profile_window"]
    _RESULT["ctl_profile_windows"] = sum(
        1 for e in windows if e.get("state") == "closed")
    _RESULT["ctl_profile_states"] = [e.get("state") for e in windows]
    ctl_files = [os.path.join(r, f)
                 for r, _, fs in os.walk(ctl_prof_dir) for f in fs]
    _RESULT["ctl_profile_trace_ok"] = bool(ctl_files)
    # ---- roofline leg (rides the control-plane leg): the window close
    # above already parsed the trace via obs/kernelstats.py and joined
    # it to the cost ledger. Deterministic gates: join coverage must be
    # EXACTLY 1.0 (every measured megastep dispatch joined its analytic
    # cost signature) and the dispatch counter measured WITH the parse
    # active must equal the base leg's (the parser is host-side work at
    # a window close the driver already owns — dispatch-neutral).
    g6 = snap6.get("gauges", {})
    _RESULT["roofline_join_coverage"] = float(
        g6.get("roofline.join_coverage", -1.0))
    _RESULT["roofline_joined_executables"] = int(
        g6.get("roofline.joined_executables", 0))
    _RESULT["roofline_dispatches_per_iter"] = round(
        float(c6.get("train.dispatches", 0)) / ctl_iters, 4)
    _RESULT["roofline_trace_bytes_ok"] = bool(
        g6.get("profile.trace_bytes", 0) > 0
        and g6.get("profile.trace_files", 0) > 0)
    mx6 = getattr(bst6._gbdt, "_metrics", None)
    if mx6 is not None:
        mx6.stop()
    shutil.rmtree(ctl_prof_dir, ignore_errors=True)
    # second profiled run, same shape, appending to the SAME perf
    # database — this one through the profile_dir config window (the
    # other capture flavor; it closes at finalize) — then assert the
    # shape key accumulated one sample per run. perfdb_samples == 2 is
    # the deterministic cross-run-accumulation gate.
    ctl2_prof_dir = tempfile.mkdtemp(prefix="bench_micro_ctlprof2_")
    ds7 = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    bst7 = lgb.train(dict(params, telemetry_out=tel_path + ".ctl2",
                          tpu_megastep_iters=n_iters,
                          profile_dir=ctl2_prof_dir,
                          perf_db=ctl_perfdb),
                     ds7, num_boost_round=n_ctl_iters)
    _phase("micro_ctl2_train_ok")
    from lightgbm_tpu.obs import perfdb as _perfdb
    _db = _perfdb.PerfDB(ctl_perfdb).load()
    _summ = _perfdb.summarize(_db["rows"])
    _RESULT["perfdb_rows"] = len(_db["rows"])
    _RESULT["perfdb_keys"] = len(_summ)
    # samples accumulated for the most-sampled shape key (the megastep
    # executable both runs measured): exactly one per profiled run
    _RESULT["perfdb_samples"] = max(
        (e["samples"] for e in _summ), default=0)
    mx7 = getattr(bst7._gbdt, "_metrics", None)
    if mx7 is not None:
        mx7.stop()
    shutil.rmtree(ctl2_prof_dir, ignore_errors=True)
    _emit()   # the control-plane + roofline counters are on stdout now

    # ---- histogram-plane leg: quantized gradients + gain screening +
    # adaptive per-feature bins (ROADMAP item 4). Two trainings on a
    # MIXED-CARDINALITY dataset (half the features carry 8 distinct
    # values — the shape adaptive bins exist for): an f32 full-plane
    # baseline and the three-cut configuration. Deterministic gates:
    # `hist_dispatches_per_iter` == dispatches_per_iter EXACTLY (the
    # cuts ride the megastep, never evict it), `hist_bytes_per_iter`
    # (the driver's analytic byte model of what the histogram kernels
    # read/build/keep per iteration — layout arithmetic, zero noise)
    # must show >= 2x reduction vs `hist_bytes_per_iter_f32`, plus
    # `hist_quant_bits` and `screening_active_features`.
    n_hf = 12
    rng_h = np.random.RandomState(5)
    Xh = rng_h.rand(n_rows, n_hf).astype(np.float32)
    Xh[:, n_hf // 2:] = np.floor(Xh[:, n_hf // 2:] * 8.0) / 8.0
    yh = (Xh @ rng_h.randn(n_hf).astype(np.float32) > 0) \
        .astype(np.float32)
    tel_hb = tel_path + ".histbase"
    dsh = lgb.Dataset(Xh, label=yh, params={"max_bin": 63, "verbose": -1})
    bsth0 = lgb.train(dict(params, telemetry_out=tel_hb), dsh,
                      num_boost_round=n_iters)
    gh0 = bsth0.telemetry().get("gauges", {})
    _RESULT["hist_bytes_per_iter_f32"] = float(
        gh0.get("hist.bytes_per_iter", 0.0))
    tel_hc = tel_path + ".histcut"
    cut_params = dict(params, telemetry_out=tel_hc,
                      tpu_quantized_grad=16, tpu_gain_screening=True,
                      tpu_screening_warmup=2,
                      tpu_screening_explore_period=4,
                      tpu_adaptive_bins=True)
    dsh2 = lgb.Dataset(Xh, label=yh, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bsth = lgb.train(cut_params, dsh2, num_boost_round=n_iters)
    hist_wall = time.perf_counter() - t0
    _phase("micro_hist_train_ok")
    snap_h = bsth.telemetry()
    ch = snap_h.get("counters", {})
    gh = snap_h.get("gauges", {})
    hist_iters = max(1, int(ch.get("iterations", n_iters)))
    _RESULT["hist_sec_per_iter"] = round(hist_wall / hist_iters, 5)
    _RESULT["hist_dispatches_per_iter"] = round(
        float(ch.get("train.dispatches", 0)) / hist_iters, 4)
    _RESULT["hist_bytes_per_iter"] = float(
        gh.get("hist.bytes_per_iter", 0.0))
    _RESULT["hist_quant_bits"] = float(gh.get("hist.quant_bits", 0.0))
    _RESULT["screening_active_features"] = float(
        gh.get("screening.active_features", 0.0))
    _RESULT["hist_bytes_ratio"] = round(
        _RESULT["hist_bytes_per_iter_f32"]
        / max(1.0, _RESULT["hist_bytes_per_iter"]), 4)
    _emit()   # the histogram-plane counters are on stdout now

    # ---- ingest leg: chunked streaming ingest + binary dataset cache
    # (lightgbm_tpu/ingest/). Deterministic gates: `ingest_chunks`
    # (two streaming passes x ceil(rows/chunk)),
    # `ingest_max_live_chunks` <= 2 (the bounded-host-RSS invariant),
    # `ingest_model_mismatch` == 0 (streamed/cached model byte-equal to
    # the monolithic text load), and `ingest_dispatches_per_iter` ==
    # dispatches_per_iter EXACTLY (ingest is a data-loading plane — it
    # must not touch the training fast path). Timing-informational:
    # `prefetch_host_wait_ms` and `cache_hit_startup_ratio` (cold text
    # parse+bin construct time / cache-hit mmap construct time).
    ingest_dir = tempfile.mkdtemp(prefix="bench_micro_ingest_")
    csv_path = os.path.join(ingest_dir, "train.csv")
    with open(csv_path, "w") as fh:
        for i in range(n_rows):
            fh.write(",".join([f"{y[i]:g}"]
                              + [repr(float(v)) for v in X[i]]) + "\n")
    chunk = max(1, n_rows // 4)
    mono_ds_params = {"max_bin": 63, "verbose": -1}
    stream_ds_params = dict(mono_ds_params, two_round=True,
                            ingest_chunk_rows=chunk, save_binary=True)
    plain_params = {k: v for k, v in params.items()
                    if k != "telemetry_out"}
    t0 = time.perf_counter()
    ds_text = lgb.Dataset(csv_path, params=dict(mono_ds_params))
    ds_text.construct()
    text_construct_s = time.perf_counter() - t0
    m_text = lgb.train(dict(plain_params), ds_text,
                       num_boost_round=n_iters)

    tel_ing = tel_path + ".ingest"
    t0 = time.perf_counter()
    # pre-construct like the monolithic leg above so the sidecar cache
    # fingerprint is computed from the DATASET params alone (a booster
    # param merged pre-construction would change the digest and turn
    # the cache-hit leg below into a rebuild)
    ds_stream = lgb.Dataset(csv_path, params=dict(stream_ds_params))
    ds_stream.construct()
    bst5 = lgb.train(dict(params, telemetry_out=tel_ing), ds_stream,
                     num_boost_round=n_iters)
    ing_wall = time.perf_counter() - t0
    _phase("micro_ingest_train_ok")
    snap5 = bst5.telemetry()
    c5 = snap5.get("counters", {})
    g5 = snap5.get("gauges", {})
    ing_iters = max(1, int(c5.get("iterations", n_iters)))
    _RESULT["ingest_sec_per_iter"] = round(ing_wall / ing_iters, 5)
    _RESULT["ingest_dispatches_per_iter"] = round(
        float(c5.get("train.dispatches", 0)) / ing_iters, 4)
    _RESULT["ingest_chunks"] = int(c5.get("ingest.chunks", 0))
    _RESULT["ingest_rows"] = int(c5.get("ingest.rows", 0))
    _RESULT["ingest_max_live_chunks"] = int(
        g5.get("ingest.max_live_chunks", 0))
    _RESULT["prefetch_chunks"] = int(c5.get("prefetch.chunks", 0))
    _RESULT["prefetch_host_wait_ms"] = round(
        float(c5.get("prefetch.host_wait_ms", 0.0)), 3)

    # cache-hit startup: the streamed run above wrote the sidecar
    # cache; this construct must mmap it (no parsing, no binning)
    t0 = time.perf_counter()
    ds_hit = lgb.Dataset(csv_path, params=dict(stream_ds_params))
    ds_hit.construct()
    cache_construct_s = time.perf_counter() - t0
    stats_hit = ds_hit._inner.ingest_stats or {}
    _RESULT["ingest_cache_hit"] = int(stats_hit.get("cache_hit", 0))
    _RESULT["cache_hit_startup_ratio"] = round(
        text_construct_s / max(cache_construct_s, 1e-9), 3)
    m_hit = lgb.train(dict(plain_params), ds_hit,
                      num_boost_round=n_iters)
    _RESULT["ingest_model_mismatch"] = float(
        m_text.model_to_string(num_iteration=-1)
        != m_hit.model_to_string(num_iteration=-1))
    shutil.rmtree(ingest_dir, ignore_errors=True)
    _emit()   # the ingest-leg counters are on stdout now

    # ---- drift leg: the drift & lineage plane (obs/drift.py). The
    # training-side profile capture is pure host numpy at dataset
    # finalize, so `drift_dispatches_per_iter` must EQUAL
    # dispatches_per_iter EXACTLY, with the profile + provenance
    # blocks embedded in the artifact. The serving-side DriftMonitor
    # accumulates on the already-encoded batch host-side, so the
    # closed loop keeps `serve_drift_dispatches_per_request` at
    # exactly 1.0 with zero compiles — while a deterministically
    # shifted feed (np.clip(x + 0.35, 0, 1) vs the rand(0,1) training
    # distribution) raises EXACTLY one hysteresis-gated drift_alert at
    # a reproducible PSI, and the in-distribution control raises none.
    from lightgbm_tpu.serve import PredictionService as _DriftSvc
    tel_drift = tel_path + ".drift"
    ds_dr = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst_dr = lgb.train(dict(params, telemetry_out=tel_drift,
                            drift_profile=True), ds_dr,
                       num_boost_round=n_iters)
    drift_wall = time.perf_counter() - t0
    _phase("micro_drift_train_ok")
    c7 = bst_dr.telemetry().get("counters", {})
    dr_iters = max(1, int(c7.get("iterations", n_iters)))
    _RESULT["drift_sec_per_iter"] = round(drift_wall / dr_iters, 5)
    _RESULT["drift_dispatches_per_iter"] = round(
        float(c7.get("train.dispatches", 0)) / dr_iters, 4)
    model_dr = bst_dr.model_to_string()
    _RESULT["drift_profile_embedded"] = float(
        "\ndata_profile:\n" in model_dr and "\nprovenance:\n" in model_dr)

    def _drift_serve(shift):
        svc = _DriftSvc({"m": bst_dr}, max_batch_rows=256,
                        max_delay_ms=0.5, min_bucket_rows=16,
                        batch_events=False, drift_eval_rows=128,
                        drift_hysteresis=2)
        svc.warmup()
        rng_d = np.random.RandomState(17)
        s0 = svc.stats()
        for _ in range(20):
            Xq = rng_d.rand(40, n_feat).astype(np.float32)
            if shift:
                Xq = np.clip(Xq + 0.35, 0.0, 1.0).astype(np.float32)
            svc.predict("m", Xq, timeout=60)
        s1 = svc.stats()
        # close() joins the batcher worker, and post-batch drift_flush
        # records run synchronously on it — snapshotting after close
        # makes the final evaluation (and so psi_max) deterministic
        svc.close()
        snap_d = svc.tel.snapshot()
        return {
            "dpr": round((s1["dispatches"] - s0["dispatches"]) / 20.0, 6),
            "cp1k": round((s1["compiles"] - s0["compiles"]) * 50.0, 6),
            "alerts": int(snap_d.get("counters", {})
                          .get("drift.alerts", 0)),
            "psi_max": round(float(snap_d.get("gauges", {})
                                   .get("drift.psi_max", 0.0)), 4)}

    ctrl = _drift_serve(shift=False)
    drifted = _drift_serve(shift=True)
    _phase("micro_drift_serve_ok")
    _RESULT["serve_drift_dispatches_per_request"] = drifted["dpr"]
    _RESULT["serve_drift_compiles_per_1k"] = drifted["cp1k"]
    _RESULT["drift_alerts"] = drifted["alerts"]
    _RESULT["drift_psi_max"] = drifted["psi_max"]
    _RESULT["drift_alerts_control"] = ctrl["alerts"]
    _RESULT["drift_psi_max_control"] = ctrl["psi_max"]
    _emit()   # the drift-plane counters are on stdout now

    # ---- slo leg: the SLO plane (obs/slo.py) armed with the BUILT-IN
    # objective catalog on a clean training run. The engine evaluates
    # host-side telemetry snapshots on its daemon ticker plus the drain
    # boundaries the driver already owns, so arming it is
    # dispatch-neutral: slo_dispatches_per_iter must EQUAL
    # dispatches_per_iter EXACTLY (bench_compare deterministic counter
    # + the perf-smoke absolute assertion). The finalize force-tick
    # makes slo_ticks >= 1 deterministic, and a healthy run must
    # produce ZERO alerts — slo_alerts is the false-positive gate.
    tel_slo = tel_path + ".slo"
    ds_slo = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    t0 = time.perf_counter()
    bst_slo = lgb.train(dict(params, telemetry_out=tel_slo,
                             slo_enabled=True),
                        ds_slo, num_boost_round=n_iters)
    slo_wall = time.perf_counter() - t0
    _phase("micro_slo_train_ok")
    c8 = bst_slo.telemetry().get("counters", {})
    slo_iters = max(1, int(c8.get("iterations", n_iters)))
    _RESULT["slo_sec_per_iter"] = round(slo_wall / slo_iters, 5)
    _RESULT["slo_dispatches_per_iter"] = round(
        float(c8.get("train.dispatches", 0)) / slo_iters, 4)
    _RESULT["slo_ticks"] = int(c8.get("slo.ticks", 0))
    _RESULT["slo_alerts"] = int(c8.get("slo.alerts_fired", 0))
    _emit()   # the slo-plane counters are on stdout now

    # ---- multiproc leg: 2 REAL processes x 2 virtual CPU devices over
    # one gloo mesh, tree_learner=data on the fused engine with the
    # megastep armed — the pod-scale fast path. The deterministic gate
    # is the ABSOLUTE parity contract `mp_dispatches_per_iter ==
    # dispatches_per_iter` (0.125 at defaults): the multi-chip megastep
    # keeps the in-trace collectives inside the scan, so a multi-process
    # run pays EXACTLY the single-device dispatch schedule; a regression
    # back to the per-iteration sync driver (the pre-round-12 eviction)
    # moves it to >= 3. `mp_ranks_agree` (1.0 = both ranks emitted the
    # byte-identical model) guards SPMD consistency vacuity.
    _RESULT["mp_dispatches_per_iter"] = None
    _RESULT["mp_ranks_agree"] = None
    try:
        mp_rows = int(os.environ.get("BENCH_MICRO_MP_ROWS", n_rows))
        reports = _micro_multiproc_leg(
            X[:mp_rows], y[:mp_rows], n_iters,
            dict({k: v for k, v in params.items()
                  if k != "telemetry_out"}, tree_learner="data"))
        mp_iters = max(1, int(reports[0]["iterations"]))
        _RESULT["mp_dispatches_per_iter"] = round(
            float(reports[0]["dispatches"]) / mp_iters, 4)
        _RESULT["mp_ranks_agree"] = float(
            reports[0]["model"] == reports[1]["model"])
        _RESULT["mp_fast_path"] = bool(reports[0]["fast_path"])
        _RESULT["mp_iterations_kept"] = mp_iters
    except Exception as e:
        print(f"multiproc leg failed: {e}", file=sys.stderr)
    for p in (tel_path, tel_eval, tel_ckpt, tel_obs, tel_ctl, tel_ing,
              tel_hb, tel_hc, tel_drift, tel_slo):
        try:
            os.remove(p)
        except OSError:
            pass
    _emit()


_MP_WORKER = '''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from lightgbm_tpu.utils.platform import compilation_cache_dir
compilation_cache_dir()
jax.distributed.initialize(
    coordinator_address=sys.argv[1],
    num_processes=int(sys.argv[2]), process_id=int(sys.argv[3]))
import lightgbm_tpu as lgb

train_path, out_path = sys.argv[4], sys.argv[5]
params = json.loads(sys.argv[6])
rounds = int(sys.argv[7])
ds = lgb.Dataset(train_path, params={"label_column": 0, "verbose": -1,
                                     "max_bin": 63})
bst = lgb.train(dict(params, num_iterations=rounds), ds)
g = bst._gbdt
c = bst.telemetry().get("counters", {})
with open(out_path, "w") as fh:
    json.dump({"rank": jax.process_index(),
               "dispatches": float(c.get("train.dispatches", 0)),
               "iterations": int(c.get("iterations", rounds)),
               "fast_path": bool(g._fast_path_ok()),
               "model": bst.model_to_string()}, fh)
'''


def _micro_multiproc_leg(X, y, n_iters, params):
    """Run the 2-process joint training and return both rank reports.
    The worker subprocesses carry the REAL product path end to end
    (loader rank-sharding -> MultiProcLayout -> shard_map growers in the
    megastep scan); the parent only compares their reports."""
    import socket
    import subprocess
    import tempfile
    mp_dir = tempfile.mkdtemp(prefix="bench_micro_mp_")
    train_csv = os.path.join(mp_dir, "train.csv")
    with open(train_csv, "w") as fh:
        for i in range(len(y)):
            fh.write(",".join([f"{y[i]:g}"]
                              + [repr(float(v)) for v in X[i]]) + "\n")
    worker_py = os.path.join(mp_dir, "worker.py")
    with open(worker_py, "w") as fh:
        fh.write(_MP_WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    wp = dict(params)
    outs = [os.path.join(mp_dir, f"rank{i}.json") for i in range(2)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # ONLY the repo on the path (same rule as the multiproc tests): the
    # package must be importable from the workers' cwd-less interpreter
    env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
    env.pop("XLA_FLAGS", None)
    procs = []
    for i in range(2):
        wp_i = dict(wp, telemetry_out=os.path.join(
            mp_dir, f"tel_rank{i}.jsonl"))
        procs.append(subprocess.Popen(
            [sys.executable, worker_py, coord, "2", str(i), train_csv,
             outs[i], json.dumps(wp_i), str(n_iters)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    try:
        for i, p in enumerate(procs):
            out, err = p.communicate(
                timeout=int(os.environ.get("BENCH_MICRO_MP_TIMEOUT",
                                           1200)))
            if p.returncode != 0:
                raise RuntimeError(
                    f"mp worker rank {i} exited {p.returncode}: "
                    + err.decode(errors="replace")[-2000:])
        reports = [json.load(open(o)) for o in outs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        import shutil as _sh
        _sh.rmtree(mp_dir, ignore_errors=True)
    # the model strings embed per-rank telemetry_out paths; normalize so
    # rank agreement compares the MODEL, not the config echo
    for i, r in enumerate(reports):
        r["model"] = r["model"].replace(f"tel_rank{i}.jsonl",
                                        "tel_rank.jsonl")
    return reports


def run_serve() -> None:
    """Prediction-serving bench (``--serve``; add ``--micro`` for the
    deterministic CPU mode CI gates on).

    Two legs against a live ``lightgbm_tpu.serve.PredictionService``:

    - **closed loop** — sequential mixed-size requests, one at a time:
      per-request latency (p50 is the headline) plus the two
      DETERMINISTIC counters the regression gate keys on:
      ``dispatches_per_request`` (bucketing keeps it at exactly 1.0 —
      a chunking/bucketing regression moves it) and
      ``compiles_per_1k_requests`` (0 after warmup — a bucket-shape
      leak recompiling per request size moves it to ~1000/len(sizes));
    - **open loop** — all requests submitted concurrently so the
      micro-batcher coalesces: throughput + observed batching ratio
      (timing-dependent, recorded informationally, never gated).
    """
    micro = "--micro" in sys.argv[1:]
    if micro:
        os.environ["JAX_PLATFORMS"] = "cpu"   # before any jax import
    _RESULT.update(metric="serve_micro_p50_ms" if micro
                   else "serve_p50_ms", unit="ms", vs_baseline=None)
    _install_guards()
    _phase("serve_start")
    import jax
    if micro:
        jax.config.update("jax_platforms", "cpu")
    else:
        _require_tpu()
    from lightgbm_tpu.utils.platform import compilation_cache_dir
    compilation_cache_dir()
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serve import PredictionService

    n_models = int(os.environ.get("SERVE_MODELS", 2))
    n_requests = int(os.environ.get("SERVE_REQUESTS", 200))
    train_rows = int(os.environ.get("SERVE_TRAIN_ROWS",
                                    2000 if micro else 200_000))
    n_feat = 12
    max_batch = int(os.environ.get("SERVE_MAX_BATCH_ROWS", 1024))
    _RESULT["bench_config"] = {"mode": "serve_micro" if micro else "serve",
                               "models": n_models, "requests": n_requests,
                               "train_rows": train_rows,
                               "max_batch_rows": max_batch}
    if micro:
        _RESULT["platform"] = "cpu"

    models = {}
    for m in range(n_models):
        X, y = _make_data(train_rows, n_feat)
        rngm = np.random.RandomState(100 + m)
        y = (X @ rngm.randn(n_feat) > 0).astype(np.float32)
        models[f"m{m}"] = lgb.train(
            {"objective": "binary", "num_leaves": 31, "verbose": -1,
             "metric": "None", "max_bin": 63},
            lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1}),
            num_boost_round=int(os.environ.get("SERVE_TREES", 20)))
    _phase("serve_models_trained")

    # live exporter ON for the whole bench: the deterministic counters
    # below (dispatches_per_request == 1.0, compiles_per_1k == 0) are
    # measured WITH the observability plane active, so the CI absolute
    # gate doubles as the exporter-on/off equality check — the off
    # values are the contract itself
    serve_metrics_port = _free_port()
    # single lane: the closed/open-loop legs are the trajectory's
    # longest-lived comparable series — they keep measuring the ONE
    # bounded queue regardless of how many host devices the runner
    # forces; the fleet leg below measures the multi-device plane
    svc = PredictionService(models, max_batch_rows=max_batch,
                            max_delay_ms=1.0, min_bucket_rows=16,
                            batch_events=False, serve_devices=1,
                            metrics_port=serve_metrics_port)
    svc.warmup()
    _phase("serve_warmup_ok")

    # ---- closed loop: deterministic request stream, one in flight ----
    rng = np.random.RandomState(7)
    sizes = rng.randint(1, max_batch + 1, size=n_requests)
    mids = [f"m{i % n_models}" for i in range(n_requests)]
    reqs = [rng.rand(int(s), n_feat).astype(np.float32) for s in sizes]
    s0 = svc.stats()
    lat = []
    t0 = time.perf_counter()
    for mid, Xq in zip(mids, reqs):
        r0 = time.perf_counter()
        svc.predict(mid, Xq)
        lat.append((time.perf_counter() - r0) * 1000.0)
    closed_wall = time.perf_counter() - t0
    s1 = svc.stats()
    lat.sort()

    def q(p):
        return lat[min(len(lat) - 1, int(p * (len(lat) - 1) + 0.5))]

    _RESULT["value"] = round(q(0.50), 4)
    _RESULT["p95_ms"] = round(q(0.95), 4)
    _RESULT["p99_ms"] = round(q(0.99), 4)
    d_disp = s1["dispatches"] - s0["dispatches"]
    d_comp = s1["compiles"] - s0["compiles"]
    _RESULT["dispatches_per_request"] = round(d_disp / n_requests, 6)
    _RESULT["compiles_per_1k_requests"] = round(
        d_comp * 1000.0 / n_requests, 6)
    _RESULT["closed_loop_rows_per_s"] = round(
        float(sizes.sum()) / closed_wall, 1)
    # mid-run scrape: the service is still live (open loop follows) —
    # the exporter must answer NOW with the requests already counted
    # (the serve-smoke CI job asserts exporter_requests_total > 0)
    try:
        from lightgbm_tpu.obs.export import scrape
        _, body = scrape(svc.metrics_url, timeout=10)
        line = next(l for l in body.splitlines()
                    if l.startswith("lgbm_serve_requests_total"))
        _RESULT["exporter_requests_total"] = int(
            float(line.rsplit(" ", 1)[1]))
    except Exception as e:
        print(f"serve exporter scrape failed: {e}", file=sys.stderr)
        _RESULT["exporter_requests_total"] = 0
    _phase("serve_closed_ok")
    _emit()   # the deterministic gate numbers are on stdout now

    # ---- open loop: concurrent submits exercise the micro-batcher ----
    t0 = time.perf_counter()
    futs = [svc.submit(mid, Xq) for mid, Xq in zip(mids, reqs)]
    for f in futs:
        f.result(timeout=600)
    open_wall = time.perf_counter() - t0
    s2 = svc.stats()
    _RESULT["open_loop_rows_per_s"] = round(
        float(sizes.sum()) / open_wall, 1)
    ob = s2["batches"] - s1["batches"]
    _RESULT["open_loop_batches"] = ob
    _RESULT["open_loop_requests_per_batch"] = round(
        n_requests / max(1, ob), 3)
    _RESULT["serve_stats"] = {
        k: s2[k] for k in ("requests", "batches", "dispatches", "compiles",
                           "evictions", "degradations")}
    _RESULT["latency_ms"] = s2.get("latency_ms")
    _phase("serve_open_ok")
    svc.close()

    # ---- overload leg: open-loop offered load >> capacity ----------
    # The ROADMAP-mandated acceptance numbers: with a bounded queue, a
    # deadline and a dispatch gate that makes capacity << offered load
    # DETERMINISTIC on any runner, the service must shed/reject the
    # excess with structured errors, keep the queue at its bound and
    # leave ZERO futures unresolved.  shed_ratio is exact by
    # construction (the queue fills to its request bound, the gate
    # outlasts every queued deadline, so exactly the bound sheds);
    # reject_ratio varies only by the first batch's coalesce count.
    import threading as _threading

    from lightgbm_tpu.serve import (ServeDeadlineExceeded, ServeError,
                                    ServeRejected)
    q_bound = int(os.environ.get("SERVE_OVERLOAD_QUEUE", 24))
    n_offered = int(os.environ.get("SERVE_OVERLOAD_REQUESTS", 240))
    svc3 = PredictionService({"m0": models["m0"]}, max_batch_rows=64,
                             max_delay_ms=0.5, min_bucket_rows=16,
                             batch_events=False, serve_devices=1,
                             max_queue_requests=q_bound,
                             default_deadline_ms=250.0)
    svc3.warmup()
    real_dispatch = svc3.batcher._dispatch
    gate = _threading.Event()

    def gated(mid, Xg):
        gate.wait(5.0)
        return real_dispatch(mid, Xg)
    svc3.batcher._dispatch = gated
    rng_o = np.random.RandomState(11)
    reqs_o = [rng_o.rand(8, n_feat).astype(np.float32)
              for _ in range(n_offered)]
    done_lat = {}
    futs_o, rejected = [], 0
    for Xq in reqs_o:
        try:
            fut = svc3.submit("m0", Xq)
            t_sub = time.perf_counter()
            # keyed by the future itself: rejections interleave with
            # admissions, so positional indices would mispair latencies
            fut.add_done_callback(
                lambda f, t=t_sub:
                done_lat.__setitem__(id(f), time.perf_counter() - t))
            futs_o.append(fut)
        except ServeRejected:
            rejected += 1
    time.sleep(0.6)   # queued deadlines (250 ms) all expire
    gate.set()
    served = shed = unresolved = 0
    for fut in futs_o:
        try:
            fut.result(timeout=60)
            served += 1
        except ServeDeadlineExceeded:
            shed += 1
        except ServeError:
            shed += 1        # structured either way; bucket with shed
        except Exception:
            unresolved += 1
    snap3 = svc3.tel.snapshot()
    peak = int(snap3.get("gauges", {}).get("serve.queue_peak_requests",
                                           0))
    lat_ok = sorted(1000.0 * done_lat[id(f)] for f in futs_o
                    if f.exception() is None and id(f) in done_lat)
    _RESULT["shed_ratio"] = round(shed / n_offered, 6)
    _RESULT["reject_ratio"] = round(rejected / n_offered, 6)
    _RESULT["overload_p99_ms"] = round(
        lat_ok[min(len(lat_ok) - 1,
                   int(0.99 * (len(lat_ok) - 1) + 0.5))], 3) \
        if lat_ok else None
    _RESULT["overload_unresolved"] = unresolved
    _RESULT["overload_queue_overflow"] = max(0, peak - q_bound)
    _RESULT["overload_served"] = served
    svc3.close(drain_timeout_s=10)
    _phase("serve_overload_ok")

    # ---- rollover-under-load leg -----------------------------------
    # Continuous closed-loop traffic across a rollover(): the swap is
    # one dict assignment under the residency lock, so the dropped-
    # request count is deterministically ZERO (gated in serve-chaos CI
    # and by bench_compare).
    svc4 = PredictionService({"m": models["m0"]}, max_batch_rows=256,
                             max_delay_ms=0.5, min_bucket_rows=16,
                             batch_events=False)
    svc4.warmup()
    stop_t = _threading.Event()
    roll_failures, roll_served = [], [0]

    def _traffic(seed):
        rt = np.random.RandomState(seed)
        while not stop_t.is_set():
            try:
                svc4.predict("m", rt.rand(4, n_feat).astype(np.float32),
                             timeout=60)
                roll_served[0] += 1
            except Exception as e:   # any failure IS the regression
                roll_failures.append(repr(e))
    traffic_threads = [_threading.Thread(target=_traffic, args=(21 + i,),
                                         daemon=True) for i in range(2)]
    for th in traffic_threads:
        th.start()
    time.sleep(0.2)
    # the candidate must be a DIFFERENT model state so the hash-changed
    # gate is meaningful even under SERVE_MODELS=1 (a one-tree-trimmed
    # copy — no retraining cost)
    if n_models > 1:
        roll_to = models["m1"]
    else:
        m0 = models["m0"]
        roll_to = lgb.Booster(model_str=m0.model_to_string(
            num_iteration=max(1, m0.num_trees() - 1)))
    roll_rep = svc4.rollover("m", roll_to)
    time.sleep(0.2)
    stop_t.set()
    for th in traffic_threads:
        th.join(timeout=30)
    svc4.close(drain_timeout_s=30)
    _RESULT["rollover_dropped_requests"] = len(roll_failures)
    _RESULT["rollover_requests_served"] = roll_served[0]
    _RESULT["rollover_hash_changed"] = float(
        roll_rep["promoted"]
        and roll_rep["old_hash"] != roll_rep["new_hash"])
    _phase("serve_rollover_ok")

    # ---- fleet leg: replicated multi-device serving ----------------
    # Three sub-legs against one serve_devices=all service (the
    # serve-fleet CI job forces 4 host devices via XLA_FLAGS; on a
    # 1-device runner everything below degenerates to the single-lane
    # plane and the scaling ratio sits at ~1.0):
    #
    # 1. closed loop, REAL dispatches -> the per-device deterministic
    #    contract: every device that took traffic measured exactly 1.0
    #    dispatches/request and 0 steady-state compiles, and the
    #    round-robin tie-break routed EVERY device
    #    (fleet_unrouted_devices == 0);
    # 2. open loop with a fixed per-batch dispatch floor on BOTH a
    #    1-lane service and the fleet -> rows/s scaling that is
    #    deterministic on any runner speed (the floor dominates, so the
    #    ratio measures lane overlap, not CPU contention);
    # 3. predict_bulk -> row-sharded scoring over the mesh must be
    #    numerically identical (f32 tolerance) to the single-device
    #    dispatch path, with its throughput recorded.
    fleet_n = len(jax.local_devices())
    _RESULT["fleet_devices"] = fleet_n
    svcF = PredictionService({"m0": models["m0"]},
                             max_batch_rows=max_batch,
                             max_delay_ms=1.0, min_bucket_rows=16,
                             batch_events=False, serve_devices=0)
    svcF.warmup()
    _phase("serve_fleet_warmup_ok")

    n_fleet = int(os.environ.get("SERVE_FLEET_REQUESTS", 32)) * fleet_n
    rng_f = np.random.RandomState(17)
    sizes_f = rng_f.randint(1, 257, size=n_fleet)
    reqs_f = [rng_f.rand(int(s), n_feat).astype(np.float32)
              for s in sizes_f]
    for Xq in reqs_f:
        svcF.predict("m0", Xq)
    sF = svcF.stats()
    per_f = sF.get("fleet", {}).get("per_device")
    if per_f is None:      # 1-device runner: no fleet section
        per_f = [{"device": 0, "requests": sF["requests"],
                  "dispatches_per_request":
                      sF["dispatches_per_request"],
                  "compiles_per_1k_requests":
                      sF["compiles_per_1k_requests"], "spills": 0}]
    routed = sum(1 for e in per_f if e.get("requests", 0) > 0)
    _RESULT["routed_devices"] = routed
    _RESULT["fleet_unrouted_devices"] = fleet_n - routed
    dprs = [e["dispatches_per_request"] for e in per_f
            if "dispatches_per_request" in e]
    c1ks = [e["compiles_per_1k_requests"] for e in per_f
            if "compiles_per_1k_requests" in e]
    _RESULT["fleet_dispatches_per_request_worst"] = \
        max(dprs, key=lambda v: abs(v - 1.0)) if dprs else None
    _RESULT["fleet_compiles_per_1k_worst"] = \
        max(c1ks) if c1ks else None
    _RESULT["fleet_spills"] = int(
        sF.get("fleet", {}).get("spills", 0))
    _phase("serve_fleet_closed_ok")

    # open-loop scaling: identical request stream, identical per-batch
    # floor; requests sized to max_batch_rows so one request == one
    # batch on both topologies (coalescing differences would otherwise
    # let the 1-lane backlog batch more rows per floor payment)
    # the floor must DOMINATE the real per-batch dispatch (~2-7 ms for
    # 16 rows on a loaded CPU): real dispatches serialize on a small
    # runner's cores, so a thin floor would measure CPU contention
    # instead of lane overlap and under-report the scaling (measured:
    # a 25 ms floor reads ~2.7-3.3x and a 50 ms floor still dips to
    # ~2.95x on a busy 1-core box; at 100 ms the predicted 4-lane
    # scaling (100+r)/(25+r) stays >= 3.1x out to r = 10 ms of real
    # serialized dispatch, which keeps the gate margin even under
    # heavy co-tenancy)
    floor_s = float(os.environ.get("SERVE_FLEET_FLOOR_MS", 100.0)) / 1000.0
    # tiny requests: the real dispatch must stay a sliver of the floor
    # even when a 1-core runner serializes every lane's device work
    scale_rows = 16
    n_scale = int(os.environ.get("SERVE_FLEET_SCALE_REQUESTS", 40)) \
        * max(1, fleet_n)
    rng_s = np.random.RandomState(23)
    reqs_s = [rng_s.rand(scale_rows, n_feat).astype(np.float32)
              for _ in range(n_scale)]

    def _floored_open_loop(svc_x):
        real_x = svc_x.batcher._dispatch

        def floored(*a):
            time.sleep(floor_s)
            return real_x(*a)
        svc_x.batcher._dispatch = floored
        t0x = time.perf_counter()
        fs = [svc_x.submit("m0", Xq) for Xq in reqs_s]
        for f in fs:
            f.result(timeout=600)
        wall = time.perf_counter() - t0x
        svc_x.batcher._dispatch = real_x
        return n_scale * scale_rows / wall

    svcS = PredictionService({"m0": models["m0"]},
                             max_batch_rows=scale_rows,
                             max_delay_ms=1.0, min_bucket_rows=16,
                             batch_events=False, serve_devices=1)
    svcS.warmup()
    rate_1dev = _floored_open_loop(svcS)
    svcS.close()
    svcF.batcher.max_batch_rows = scale_rows
    rate_fleet = _floored_open_loop(svcF)
    svcF.batcher.max_batch_rows = max_batch
    _RESULT["fleet_rows_per_s_1dev"] = round(rate_1dev, 1)
    _RESULT["fleet_rows_per_s"] = round(rate_fleet, 1)
    _RESULT["fleet_scaling_x"] = round(rate_fleet / rate_1dev, 3)
    _phase("serve_fleet_scaling_ok")

    # bulk identity + throughput: warm call compiles the sharded
    # executable, the timed call measures steady-state rows/s
    Xb = np.random.RandomState(29).rand(
        int(os.environ.get("SERVE_BULK_ROWS", 20_000)),
        n_feat).astype(np.float32)
    svcF.predict_bulk("m0", Xb[:256])
    t0b = time.perf_counter()
    out_bulk = svcF.predict_bulk("m0", Xb)
    bulk_wall = time.perf_counter() - t0b
    out_single = svcF.predict("m0", Xb)
    bulk_diff = float(np.max(np.abs(out_bulk - out_single)))
    bulk_ok = bool(np.allclose(out_bulk, out_single,
                               rtol=1e-5, atol=1e-6))
    _RESULT["bulk_rows_per_s"] = round(Xb.shape[0] / bulk_wall, 1)
    _RESULT["bulk_max_abs_diff"] = bulk_diff
    _RESULT["bulk_identity_ok"] = float(bulk_ok)
    _RESULT["bulk_identity_mismatch"] = float(not bulk_ok)
    svcF.close()
    _phase("serve_fleet_ok")
    _emit()

    # ---- slo forced-alert leg: deterministic alert lifecycle ---------
    # A serve_slow_dispatch fault injects ONE ~400 ms dispatch into an
    # slo-armed service whose latency objective is overridden down to
    # 50 ms with hysteresis 2 (the rest of the built-in catalog stays
    # armed, so any OTHER objective firing here is a false positive).
    # tick_period 0 disables the ticker — every evaluation below is an
    # explicit forced step, which makes the lifecycle exact on any
    # runner: two breaching evaluations fire the alert and capture the
    # incident artifact, ~300 fast requests push the slow sample past
    # the p99 index, two clean evaluations resolve it. Exactly one
    # firing->resolved cycle, a schema-valid incident and
    # slo_false_positives == 0 are gated absolutely by the
    # serve-alert-smoke CI job and bench_compare's deterministic set.
    import tempfile
    slo_dir = tempfile.mkdtemp(prefix="bench_serve_slo_")
    slo_cfg = os.path.join(slo_dir, "slo.json")
    slo_tel = os.path.join(slo_dir, "tel.jsonl")
    with open(slo_cfg, "w") as fh:
        json.dump({"objectives": [
            {"id": "serve.latency_p99", "target": 50.0,
             "hysteresis": 2, "resolve_hysteresis": 2}]}, fh)
    svc5 = PredictionService({"m0": models["m0"]}, max_batch_rows=64,
                             max_delay_ms=0.5, min_bucket_rows=16,
                             batch_events=False, serve_devices=1,
                             slo_config=slo_cfg, slo_tick_period_s=0.0,
                             metrics_port=_free_port(),
                             telemetry_out=slo_tel)
    svc5.warmup()
    s5_warm = svc5.stats()              # baseline: warmup dispatches
    # arm the fault only AFTER warmup so the slow dispatch lands on the
    # measured request (the hook re-reads the env per batch); restore
    # the previous value either way
    prev_faults = os.environ.get("LIGHTGBM_TPU_FAULTS")
    os.environ["LIGHTGBM_TPU_FAULTS"] = "serve_slow_dispatch@1:ms=400"
    Xs = np.random.RandomState(31).rand(4, n_feat).astype(np.float32)
    try:
        svc5.predict("m0", Xs)          # ~400 ms: the breaching sample
    finally:
        if prev_faults is None:
            os.environ.pop("LIGHTGBM_TPU_FAULTS", None)
        else:
            os.environ["LIGHTGBM_TPU_FAULTS"] = prev_faults
    eng = svc5.slo
    eng.step(force=True)
    eng.step(force=True)                # hysteresis 2 -> firing
    for _ in range(300):                # refill the latency ring fast
        svc5.predict("m0", Xs)
    eng.step(force=True)
    eng.step(force=True)                # resolve_hysteresis 2 -> clear
    # live /alerts endpoint + build-info series while the svc is up
    try:
        from lightgbm_tpu.obs.export import scrape as _scr5
        base5 = svc5.metrics_url.rsplit("/metrics", 1)[0]
        _, abody = _scr5(f"{base5}/alerts", timeout=10)
        _RESULT["slo_alerts_endpoint_ok"] = float(
            int(json.loads(abody).get("fired", 0)) >= 1)
        _, mbody = _scr5(svc5.metrics_url, timeout=10)
        _RESULT["slo_build_info_ok"] = float(any(
            l.startswith("lgbm_build_info{") and l.rstrip().endswith(" 1")
            for l in mbody.splitlines()))
    except Exception as e:
        print(f"slo endpoint scrape failed: {e}", file=sys.stderr)
        _RESULT["slo_alerts_endpoint_ok"] = 0.0
        _RESULT["slo_build_info_ok"] = 0.0
    pay = eng.alerts_payload()
    s5 = svc5.stats()
    svc5.close()
    hist = pay.get("history", [])
    fired5 = [h for h in hist if h.get("state") == "firing"]
    _RESULT["slo_alert_fired"] = len(fired5)
    _RESULT["slo_alert_resolved"] = len(
        [h for h in hist if h.get("state") == "resolved"])
    _RESULT["slo_false_positives"] = len(
        [h for h in fired5
         if h.get("objective") != "serve.latency_p99"])
    inc_ok = 0.0
    try:
        with open(pay["incidents"][0]) as fh:
            inc = json.load(fh)
        inc_ok = float(
            inc.get("schema") == "lightgbm_tpu.incident/1"
            and inc.get("alert", {}).get("objective")
            == "serve.latency_p99"
            and isinstance(inc.get("telemetry"), dict)
            and isinstance(inc.get("context"), dict))
    except Exception as e:
        print(f"slo incident check failed: {e}", file=sys.stderr)
    _RESULT["slo_incident_valid"] = inc_ok
    # inverted forms for bench_compare's zero-to-nonzero gate (the
    # ratio gate only flags increases, so "must stay 1" contracts are
    # expressed as "must stay 0" failures)
    _RESULT["slo_incident_invalid"] = 1.0 - inc_ok
    _RESULT["slo_alert_missed"] = float(
        _RESULT["slo_alert_fired"] != 1)
    _RESULT["slo_alert_unresolved"] = float(
        _RESULT["slo_alert_resolved"] != _RESULT["slo_alert_fired"])
    _RESULT["slo_dispatches_per_request"] = round(
        (s5["dispatches"] - s5_warm["dispatches"])
        / max(1, s5["requests"] - s5_warm["requests"]), 6)
    import shutil as _sh5
    _sh5.rmtree(slo_dir, ignore_errors=True)
    _phase("serve_slo_alert_ok")
    _emit()


def main() -> None:
    if "--serve" in sys.argv[1:]:
        run_serve()
        return
    if "--micro" in sys.argv[1:]:
        run_micro()
        return
    _install_guards()
    # the TIMETAG timer collects section times for the failure tail (its
    # sections carry no sync points, so the pipelined hot loop stays hot)
    from lightgbm_tpu.utils.timer import global_timer
    global_timer.enable()
    _phase("start")

    # the parent is the ONE process that touches JAX: a chip belongs to
    # one process at a time
    _require_tpu()
    from lightgbm_tpu.utils.platform import compilation_cache_dir
    compilation_cache_dir()

    n_rows = int(os.environ.get("BENCH_ROWS", 10_500_000))
    n_feat = 28
    n_iters = int(os.environ.get("BENCH_ITERS", 10))
    # the trajectory record carries the run shape so bench_compare.py
    # only diffs like-for-like (a 20k-row smoke next to a full run would
    # otherwise flag order-of-magnitude fake regressions)
    _RESULT["bench_config"] = {"rows": n_rows, "iters": n_iters}
    baseline_sec_per_iter = 130.094 / 500  # ref: docs/Experiments.rst:113

    X, y = _make_data(n_rows, n_feat)

    # the fused engine or a non-zero exit (the handler under __main__):
    # a bench that silently changes engine measures a different system
    sec_per_iter = _run(X, y, n_iters)
    _phase("perf_fused_ok")

    scaled = sec_per_iter * (10_500_000 / n_rows)
    _RESULT["value"] = round(scaled, 4)
    _RESULT["vs_baseline"] = round(baseline_sec_per_iter / scaled, 3)
    _RESULT["engine"] = "fused"
    _emit()   # the perf record is now on stdout, whatever happens next

    # quality leg: differential AUC vs the rebuilt reference CPU package
    # (skippable with BENCH_QUALITY=0)
    if os.environ.get("BENCH_QUALITY", "1") != "0":
        q_iters = int(os.environ.get("BENCH_QUALITY_ITERS", 500))
        _RESULT["quality_iters"] = q_iters
        _RESULT.update(_quality_leg(iters=q_iters))
        _phase("quality_ok")
        _emit()   # merged record; last stdout line wins


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:   # the record, then a NON-ZERO exit
        import traceback
        traceback.print_exc()
        _die_with_record(f"{type(exc).__name__}: {str(exc)[:300]}")
