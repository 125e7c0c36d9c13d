"""Traffic kind ``train_goss``: ONE ``lgb.train`` call with
``boosting=goss``, the way a user makes it: a validation set,
``metric=auc``, ``record_evaluation`` and ``early_stopping(100)``, on the
``train`` kind's data (``harness/data.py``: the Higgs cell's generator and
binned matrix).

The generator, the clock, the window, the depth of a tree and the walk of
the dumped trees are the ``train`` kind's (``kinds/train.py``), the
fail-fast follower of the telemetry stream is the ``train_rank`` kind's
(``_EvictionWatch``: a program that evicts GOSS to its synchronous driver
ends within seconds of its first ``megastep_evicted`` event, exit code 3,
no result); both are loaded from their files, not copied. What differs:

- the job has two kinds of iteration: the first ``int(1 / learning_rate)``
  use all rows, the rest grow on the sample. The mix's chunks are aligned
  to that boundary and the warm-up holds at least one chunk of each, so
  ``setup_s`` carries both bodies' first calls and nothing may compile
  after the LAST warm-up chunk (a job with two bodies may compile twice
  inside the warm-up);
- ``correct`` also needs every tree full (every seed then does the same
  work), the root of every tree holding the rows it should (all of them
  before the boundary, ``top_k + other_k`` after it, within 0.1 %, read
  from ``dump_model()`` by the benchmark itself: the AUC band alone cannot
  tell a job that never sampled), the program's own count of the sampled
  rows (``goss.bag_rows``), the own AUC inside the band of the cell's
  reference file (``tools/reference_auc_goss.py``), and the validation
  SCORES as the program's steps carried them (``carried_scores``) within
  the file's ``scores_vs_walk`` of the benchmark's walk, row by row at the
  90th percentile. The traced AUC against the own keeps the train kind's
  limit: an AUC is a rank statistic in which rounding noise mostly cancels
  (scores carried in bfloat16 move it by 5e-7 to 8e-6, a float32 run on
  the chip by up to 7e-7: no limit lies between), while the scores
  themselves tell the two precisions apart by four orders of magnitude;
- ``facts["rows"]`` is what the algorithm has to stream in the measured
  window, the sample's rows, and ``rows_total`` the training rows:
  ``kernels.level_pass_roofline`` reads ``facts["rows"]``, so a run that
  streams all rows reads a low share and a compacted run an honest one.

A traffic file of this kind has: ``rows``, ``valid_rows``,
``chunk_iterations``, ``warmup_chunks``, ``measured_chunks``.
"""
from __future__ import annotations

import contextlib
import os
import statistics
import sys
import time

import numpy as np

from harness import (cells, data, monitor, reference, trace_capture,
                     trace_reduce)

ROOT_COUNT_TOLERANCE = 1e-3     # of the rows a tree's root should hold


def _train():
    return cells.load_module("kinds", "train")


def steady_window(reduced):
    """The train kind's window (one whole run of the step and the gap
    after it); ``tools/phase_table.py`` asks a cell's kind for it."""
    return _train().steady_window(reduced)


def _say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def sample_rows(rows: int, params: dict) -> tuple:
    """(first sampled iteration, top_k, other_k): the configuration's
    ``guarantees``, in the benchmark's own arithmetic."""
    return (int(1.0 / float(params["learning_rate"])),
            max(1, int(rows * float(params["top_rate"]))),
            max(1, int(rows * float(params["other_rate"]))))


def carried_scores(bst):
    """The raw validation scores as the program's steps carried them (what
    its traced metric is computed from), float64 [valid_rows], through the
    public ``Booster.eval_valid(feval=...)``: a custom metric is handed
    them. None from a program that hands nothing."""
    got = []

    def grab(scores, dataset):
        got.append(np.array(scores, np.float64).reshape(-1))
        return "carried", 0.0, False
    bst.eval_valid(feval=grab)
    return got[0] if got else None


def run(run) -> dict:
    import jax.profiler as jp
    import lightgbm_tpu as lgb

    train = _train()
    watch = cells.load_module("kinds", "train_rank")._EvictionWatch

    cfg, tr = run.config, run.traffic
    chunk = int(tr["chunk_iterations"])
    warm, measured = int(tr["warmup_chunks"]), int(tr["measured_chunks"])
    iters = chunk * (warm + measured)
    params = dict(cfg["params"])
    rows = int(tr["rows"])
    first, top_k, other_k = sample_rows(rows, params)
    if first % chunk or not 0 < first < warm * chunk:
        raise ValueError(
            f"sampling starts at iteration {first}: the mix's chunks of "
            f"{chunk} must end there, inside its {warm} warm-up chunks")

    with run.phase("generate"):
        X, y, Xv, yv = data.make_data(run.seed, rows, int(tr["valid_rows"]),
                                      int(cfg["features"]))
    with run.phase("bin"):
        # the keys of the configuration that shape the binned set
        ds = lgb.Dataset(X, label=y, params={
            "verbose": -1, **{k: params[k] for k in
                              ("max_bin", "min_data_in_leaf") if k in params}})
        dv = lgb.Dataset(Xv, label=yv, reference=ds)
        ds.construct()
        dv.construct()
    del X, y

    tel_path = os.path.join(run.scratch, "telemetry.jsonl")
    params.update(telemetry_out=tel_path, tpu_megastep_iters=chunk,
                  verbose=-1)
    curve = {}
    callbacks = [lgb.record_evaluation(curve),
                 lgb.early_stopping(100, verbose=False)]
    tracing = (trace_capture.ChunkTrace(
        tel_path, os.path.join(run.scratch, "trace"), start_after=warm,
        stop_after=warm + 3, devices=run.devices)
        if run.trace else contextlib.nullcontext())
    with tracing, watch(tel_path):
        t_train0 = time.time()
        with jp.TraceAnnotation("bench:lgb.train"):
            bst = lgb.train(params, ds, num_boost_round=iters,
                            valid_sets=[dv], callbacks=callbacks)
        t_train1 = time.time()

    run.events = events = monitor.read_events(tel_path)
    mega = monitor.of_kind(events, "megastep")
    built = monitor.of_kind(events, "compile_executable")
    counters = bst.telemetry().get("counters", {})
    problems = []

    def require(ok, what):
        if not ok:
            problems.append(what)

    require(built, "no compile_executable event: the first dispatch is "
            "not marked")
    bad = [e for e in events
           if e.get("event") in ("degrade", "megastep_evicted")]
    require(not bad, f"the program left its fast path: {bad[:3]}")
    require(len(mega) == warm + measured,
            f"{len(mega)} megastep chunks, want {warm + measured}")
    require(counters.get("train.dispatches") == len(mega),
            f"train.dispatches={counters.get('train.dispatches')} for "
            f"{len(mega)} chunks")
    require(bst.num_trees() == iters,
            f"{bst.num_trees()} trees of {iters} requested")
    auc_curve = curve.get("valid_0", {}).get("auc", [])
    require(len(auc_curve) == iters,
            f"{len(auc_curve)} evaluations of {iters} iterations")
    if len(mega) < warm + 1 or not built:
        return {"metrics": {}, "attempted": measured,
                "failed": measured, "problems": problems}

    t_setup_end = mega[warm - 1]["ts"]
    t_close = t_setup_end + run.seconds
    late = run.compile_log.compiled_between(t_setup_end, mega[-1]["ts"])
    require(not late, f"compiled after the last warm-up chunk: {late}")
    samples = [(b["ts"] - a["ts"]) / b["iterations"]
               for a, b in zip(mega[warm - 1:], mega[warm:])
               if b["ts"] <= t_close]
    not_run = warm + measured - len(mega)
    require(samples, "no chunk ended inside the measured window")

    with run.phase("check"):
        dumped = bst.dump_model(num_iteration=-1)
        trees = reference.flatten(dumped)
        walked = reference.walk(trees, Xv)
        own_auc = reference.rank_auc(yv, walked)
        carried = carried_scores(bst)
        off = (np.abs(carried - walked) if carried is not None
               and carried.shape == walked.shape else np.full(1, np.inf))
        score_p90 = float(np.quantile(off, 0.9))
    traced_auc = float(auc_curve[-1]) if auc_curve else float("nan")
    leaves = [int(t["leaf_value"].size) for t in trees]
    roots = [int(t["tree_structure"].get("internal_count", -1))
             for t in dumped["tree_info"]]
    bag_rows = counters.get("goss.bag_rows")
    problems += model_problems(
        run, own_auc, traced_auc, leaves, int(params["num_leaves"]), roots,
        rows, first, top_k + other_k, bag_rows, iters, train.AUC_VS_OWN,
        score_p90)

    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1e3
    run.facts.update(
        rows=top_k + other_k, rows_total=rows,
        valid_rows=int(tr["valid_rows"]),
        features=int(cfg["features"]), max_bin=int(params["max_bin"]),
        iterations=iters, first_sampled_iteration=first,
        chunk_iterations=chunk, chips=int(run.cell["chips"]),
        dispatches=counters.get("train.dispatches"),
        tree_leaves=leaves,
        tree_levels=[train._levels(t) for t in trees],
        tree_root_counts=roots, bag_rows=bag_rows,
        own_auc=own_auc, traced_auc=traced_auc,
        score_p90_abs=score_p90, score_max_abs=float(off.max()),
        # per measured chunk, what the program says its level passes
        # streamed (the megastep event; a program without it: nothing)
        streamed=[(m["rows_streamed"], m["trees"]) for m in mega[warm:]
                  if "rows_streamed" in m and m.get("trees")],
        t_train0=t_train0, t_train1=t_train1, t_dispatch0=t_dispatch0,
        t_last_chunk=mega[-1]["ts"],
        step_first_call_s=built[0]["compile_ms"] / 1e3,
        step_first_calls_s=[b["compile_ms"] / 1e3 for b in built],
        megastep_cache_hit=run.compile_log.cache_traffic(
            t_dispatch0, mega[0]["ts"])["hits"] > 0)
    # harness/output.py prints own_auc and traced_auc under "checks"; the
    # rest of what was checked is said here
    _say(f"checks: root counts {roots}, trees' leaves {leaves}, levels "
         f"{run.facts['tree_levels']}, goss.bag_rows {bag_rows}, own_auc "
         f"{own_auc}, traced_auc {traced_auc}, carried scores vs the walk's "
         f"p90 {score_p90:.3e} max {off.max():.3e} rows over 1e-3 "
         f"{int((off > 1e-3).sum())}, first calls of the steps "
         f"{run.facts['step_first_calls_s']} s")
    if run.trace:
        run.facts["window_in_use_bytes"] = tracing.in_use_peak
        reduced = trace_reduce.reduce_dir(
            os.path.join(run.scratch, "trace"), run.rehearsal)
        if reduced is not None:
            run.window = steady_window(reduced)
            # the trees grown in the window: the program's step annotation
            # nearest its start carries the chunk's first iteration
            _, first_tree = min(reduced.steps,
                                key=lambda st: abs(st[0] - run.window.t0))
            run.facts["window_trees"] = list(range(first_tree,
                                                   first_tree + chunk))
    metrics = {"setup_s": t_setup_end - run.t_start}
    if samples:
        metrics["train_s_per_iter"] = statistics.median(samples)
    return {"metrics": metrics, "attempted": len(samples) + not_run,
            "failed": not_run, "problems": problems}


def model_problems(run, own_auc: float, traced_auc: float, leaves: list,
                   num_leaves: int, roots: list, rows: int, first: int,
                   bag: int, bag_rows, iters: int,
                   auc_vs_own: float, score_p90: float) -> list:
    """The model itself, by the benchmark's own reading of it: the
    program's traced AUC has to be the AUC of its trees; every tree has to
    be full; the root of tree i has to hold all ``rows`` for i < ``first``
    and the sample's ``bag`` rows from there on; the program has to have
    counted ``bag`` rows in each sampled iteration; the own AUC has to be
    inside the band of the cell's reference
    (``benchmark/reference/<cell>.json``); and the validation scores the
    program carried have to be the walk's, at the 90th percentile of the
    rows' distances ``score_p90``, within that file's ``scores_vs_walk``."""
    ref_path = os.path.join(cells.BENCH, "reference",
                            run.cell["name"] + ".json")
    ref = cells.load_json(ref_path) if os.path.exists(ref_path) else {}
    problems = []
    if not abs(traced_auc - own_auc) <= auc_vs_own:
        problems.append(f"the program's AUC {traced_auc} vs the benchmark's "
                        f"own walk of its trees {own_auc}: over "
                        f"{auc_vs_own:.1e}")
    # (between the largest a float32 run reads on the chip and the least
    # that scores carried in bfloat16 read; the file has both, by seed)
    if ref and not score_p90 <= ref["scores_vs_walk"]:
        problems.append(f"the validation scores the program carried are "
                        f"{score_p90:.3e} from the benchmark's walk of its "
                        f"trees (90th percentile of the rows): over "
                        f"{ref['scores_vs_walk']:.1e}")
    if not run.rehearsal and any(n != num_leaves for n in leaves):
        problems.append(f"trees of {leaves} leaves, want {num_leaves} each: "
                        "the seed decides how much work the run does")
    want = [rows if i < first else bag for i in range(len(roots))]
    off = [(i, got, w) for i, (got, w) in enumerate(zip(roots, want))
           if not abs(got - w) <= ROOT_COUNT_TOLERANCE * w]
    if off:
        problems.append(f"root counts (tree, got, want) {off[:4]}: trees "
                        f"before iteration {first} grow on all {rows} rows, "
                        f"the others on the sample's {bag}")
    if bag_rows != bag * (iters - first):
        problems.append(f"goss.bag_rows={bag_rows}, want {bag} x "
                        f"{iters - first} sampled iterations")
    if ref:
        if not abs(own_auc - ref["auc"]) <= ref["band"]:
            problems.append(
                f"AUC {own_auc} is not within {ref['band']} of the cell's "
                f"reference {ref['auc']} ({ref_path})")
    elif not run.rehearsal:
        problems.append(f"{ref_path} is missing: a train cell needs its "
                        "reference AUC (benchmark/tools/"
                        "reference_auc_goss.py)")
    return problems
