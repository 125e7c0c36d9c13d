"""Traffic kind ``train``: ONE ``lgb.train`` call, the way a user makes
it — a validation set, ``metric=auc``, ``record_evaluation`` and
``early_stopping(100)`` (the built-in callback set, which stays on the
megastep) — on data generated from the seed.

The job is fixed work: ``warmup_chunks + measured_chunks`` megastep
chunks of ``chunk_iterations`` boosting iterations each, so two commits
time the same trees at the same ages. The program's telemetry stream
marks the end of every chunk (a ``megastep`` event, written when its
drain completes). Set-up ends with the last warm-up chunk; a sample is
the interval between two consecutive chunk ends divided by the
iterations of the chunk, and counts if it ends inside ``--seconds``.

A traffic file of this kind has: ``rows``, ``valid_rows``,
``chunk_iterations``, ``warmup_chunks``, ``measured_chunks``.
"""
from __future__ import annotations

import contextlib
import os
import statistics
import time

from harness import (cells, data, monitor, reference, trace_capture,
                     trace_reduce)

AUC_VS_OWN = 1e-3        # program's traced AUC vs the benchmark's own
# the benchmark's own AUC vs the cell's reference (issue 22's band): four
# to five times the reference's scatter over seeds (3.7e-4 at 10.5M rows,
# 4.8e-4 at 28M). One iteration fewer, or one more, leaves it (0.0023 to
# 0.0041 an iteration at the 16th); training on a tenth of the rows
# (-0.0015) does not, so early in a job it is no check of the row count
# (PERF.md section 6)
AUC_VS_REFERENCE = 2e-3


def run(run) -> dict:
    import jax.profiler as jp
    import lightgbm_tpu as lgb

    cfg, tr = run.config, run.traffic
    chunk = int(tr["chunk_iterations"])
    warm, measured = int(tr["warmup_chunks"]), int(tr["measured_chunks"])
    iters = chunk * (warm + measured)
    params = dict(cfg["params"])

    with run.phase("generate"):
        X, y, Xv, yv = data.make_data(run.seed, int(tr["rows"]),
                                      int(tr["valid_rows"]),
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
    # traced: from the end of the warm-up to the end of the third measured
    # chunk; the profiler starts inside the first, so the second and third
    # are whole
    tracing = (trace_capture.ChunkTrace(
        tel_path, os.path.join(run.scratch, "trace"), start_after=warm,
        stop_after=warm + 3, devices=run.devices)
        if run.trace else contextlib.nullcontext())
    with tracing:
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
    late = run.compile_log.compiled_between(mega[0]["ts"], mega[-1]["ts"])
    require(not late, f"compiled after the first chunk: {late}")
    samples = [(b["ts"] - a["ts"]) / b["iterations"]
               for a, b in zip(mega[warm - 1:], mega[warm:])
               if b["ts"] <= t_close]
    not_run = warm + measured - len(mega)
    require(samples, "no chunk ended inside the measured window")

    with run.phase("check"):
        trees = reference.flatten(bst.dump_model(num_iteration=-1))
        own_auc = reference.rank_auc(yv, reference.walk(trees, Xv))
    traced_auc = float(auc_curve[-1]) if auc_curve else float("nan")
    problems += model_problems(run, own_auc, traced_auc)

    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1e3
    run.facts.update(
        rows=int(tr["rows"]), valid_rows=int(tr["valid_rows"]),
        features=int(cfg["features"]), max_bin=int(params["max_bin"]),
        iterations=iters,
        chunk_iterations=chunk, chips=int(run.cell["chips"]),
        dispatches=counters.get("train.dispatches"),
        tree_leaves=[int(t["leaf_value"].size) for t in trees],
        tree_levels=[_levels(t) for t in trees],
        own_auc=own_auc, traced_auc=traced_auc,
        t_train0=t_train0, t_train1=t_train1, t_dispatch0=t_dispatch0,
        t_last_chunk=mega[-1]["ts"],
        step_first_call_s=built[0]["compile_ms"] / 1e3,
        megastep_cache_hit=run.compile_log.cache_traffic(
            t_dispatch0, mega[0]["ts"])["hits"] > 0)
    if run.trace:
        run.facts["window_in_use_bytes"] = tracing.in_use_peak
        reduced = trace_reduce.reduce_dir(
            os.path.join(run.scratch, "trace"), run.rehearsal)
        if reduced is not None:
            run.window = steady_window(reduced)
            # the trees grown in the window: the program's step annotation
            # nearest its start carries the chunk's first iteration
            _, first = min(reduced.steps,
                           key=lambda st: abs(st[0] - run.window.t0))
            run.facts["window_trees"] = list(range(first, first + chunk))
    metrics = {"setup_s": t_setup_end - run.t_start}
    if samples:
        metrics["train_s_per_iter"] = statistics.median(samples)
    return {"metrics": metrics, "attempted": len(samples) + not_run,
            "failed": not_run, "problems": problems}


def model_problems(run, own_auc: float, traced_auc: float) -> list:
    """The model itself, by the benchmark's own scorer: the program's
    traced AUC has to be the AUC of its trees, and that AUC has to be the
    cell's reference (``benchmark/reference/<cell>.json``)."""
    problems = []
    if not abs(traced_auc - own_auc) <= AUC_VS_OWN:
        problems.append(f"the program's AUC {traced_auc} vs the benchmark's "
                        f"own walk of its trees {own_auc}")
    ref_path = os.path.join(cells.BENCH, "reference",
                            run.cell["name"] + ".json")
    if os.path.exists(ref_path):
        ref_auc = cells.load_json(ref_path)["auc"]
        if not abs(own_auc - ref_auc) <= AUC_VS_REFERENCE:
            problems.append(
                f"AUC {own_auc} is not within {AUC_VS_REFERENCE} of the "
                f"cell's reference {ref_auc} ({ref_path})")
    elif not run.rehearsal:
        problems.append(f"{ref_path} is missing: a train cell needs its "
                        "reference AUC (benchmark/tools/reference_auc.py)")
    return problems


def _levels(tree: dict) -> int:
    """Depth of a flattened tree: the level passes it needs."""
    if tree["feature"].size == 0:
        return 0
    deepest, stack = 0, [(0, 1)]
    while stack:
        node, depth = stack.pop()
        deepest = max(deepest, depth)
        stack += [(int(c), depth + 1)
                  for c in (tree["left"][node], tree["right"][node])
                  if c >= 0]
    return deepest


def steady_window(reduced):
    """From the start of one whole run of the training step on the device
    to the start of the next: one chunk and the idle gap after it."""
    mods = reduced.devices[0].modules
    step = trace_reduce.step_runs(reduced.devices[0])
    if step.size < 2:
        raise RuntimeError(
            f"the trace holds {step.size} whole run(s) of the training "
            "step; two are needed to bound a steady chunk")
    return reduced.window(mods.start[step[-2]], mods.start[step[-1]])
