"""Traffic kind ``train_cat``: ONE ``lgb.train`` call on a table with
categorical columns given as such, the way a user makes it:
``lgb.Dataset(X, label, categorical_feature=[...])``, a validation set,
``metric=auc``, ``record_evaluation`` and ``early_stopping(100)`` (the
built-in callback set, which stays on the megastep), on data generated
from the seed (``harness/data_cat.py``).

The clock, the window and the depth of a tree are the ``train`` kind's
(``kinds/train.py``: ``steady_window``, ``_levels``, ``AUC_VS_OWN``), the
fail-fast follower of the telemetry stream is the ``train_rank`` kind's
(``_EvictionWatch``: the first ``megastep_evicted`` or ``degrade`` event
ends the run within seconds, exit code 3, no result); both are loaded from
their files, not copied. What differs: the generator, the categorical
columns on the ``Dataset``, the benchmark's own walk
(``harness/reference_cat.py``: ``==`` nodes), the reference's band (read
from the reference file, as the ranking cell's is), and one more check:
the share of categorical internal nodes.

A traffic file of this kind has: ``rows``, ``valid_rows``,
``chunk_iterations``, ``warmup_chunks``, ``measured_chunks``; the
configuration has ``categorical_feature`` beside ``params``.
"""
from __future__ import annotations

import contextlib
import os
import statistics
import sys
import time

from harness import (cells, data_cat, monitor, reference_cat, trace_capture,
                     trace_reduce)

# at least this share of the internal nodes has to be categorical: the
# cell measures the categorical search and the table form of the routing,
# and a model that stopped using the categorical columns measures neither
MIN_CATEGORICAL_SHARE = 0.25


def _train():
    return cells.load_module("kinds", "train")


def steady_window(reduced):
    """The train kind's window (one whole run of the step and the gap
    after it); ``tools/phase_table.py`` asks a cell's kind for it."""
    return _train().steady_window(reduced)


def _say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


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
    categorical = [int(c) for c in cfg["categorical_feature"]]

    with run.phase("generate"):
        X, y, Xv, yv = data_cat.make_data(run.seed, int(tr["rows"]),
                                          int(tr["valid_rows"]))
    with run.phase("bin"):
        # the keys of the configuration that shape the binned set
        ds = lgb.Dataset(X, label=y, categorical_feature=categorical,
                         params={"verbose": -1, **{
                             k: params[k] for k in
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
    late = run.compile_log.compiled_between(mega[0]["ts"], mega[-1]["ts"])
    require(not late, f"compiled after the first chunk: {late}")
    samples = [(b["ts"] - a["ts"]) / b["iterations"]
               for a, b in zip(mega[warm - 1:], mega[warm:])
               if b["ts"] <= t_close]
    not_run = warm + measured - len(mega)
    require(samples, "no chunk ended inside the measured window")

    with run.phase("check"):
        trees = reference_cat.flatten(bst.dump_model(num_iteration=-1),
                                      bst.model_to_string())
        own_auc = reference_cat.rank_auc(yv, reference_cat.walk(trees, Xv))
    traced_auc = float(auc_curve[-1]) if auc_curve else float("nan")
    cat_share = reference_cat.categorical_share(trees)
    leaves = [int(t["leaf_value"].size) for t in trees]
    problems += model_problems(run, own_auc, traced_auc, cat_share, leaves,
                               int(params["num_leaves"]),
                               train.AUC_VS_OWN)

    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1e3
    run.facts.update(
        rows=int(tr["rows"]), valid_rows=int(tr["valid_rows"]),
        features=int(cfg["features"]), max_bin=int(params["max_bin"]),
        iterations=iters,
        chunk_iterations=chunk, chips=int(run.cell["chips"]),
        dispatches=counters.get("train.dispatches"),
        tree_leaves=leaves,
        tree_levels=[train._levels(t) for t in trees],
        own_auc=own_auc, traced_auc=traced_auc,
        categorical_node_share=cat_share,
        t_train0=t_train0, t_train1=t_train1, t_dispatch0=t_dispatch0,
        t_last_chunk=mega[-1]["ts"],
        step_first_call_s=built[0]["compile_ms"] / 1e3,
        megastep_cache_hit=run.compile_log.cache_traffic(
            t_dispatch0, mega[0]["ts"])["hits"] > 0)
    # harness/output.py prints own_auc and traced_auc under "checks"; the
    # rest of what was checked is said here
    _say(f"checks: categorical_node_share {cat_share:.4f}, trees' leaves "
         f"{leaves}, levels {run.facts['tree_levels']}, own_auc {own_auc}, "
         f"traced_auc {traced_auc}")
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


def model_problems(run, own_auc: float, traced_auc: float, cat_share: float,
                   leaves: list, num_leaves: int, auc_vs_own: float) -> list:
    """The model itself, by the benchmark's own scorer: the program's
    traced AUC has to be the AUC of its trees, that AUC has to be inside
    the band of the cell's reference (``benchmark/reference/<cell>.json``,
    made by ``benchmark/tools/reference_auc_cat.py``), every tree has to be
    full (every seed then does the same work) and the categorical columns
    have to carry their share of the splits."""
    ref_path = os.path.join(cells.BENCH, "reference",
                            run.cell["name"] + ".json")
    ref = cells.load_json(ref_path) if os.path.exists(ref_path) else {}
    # the cell's own limit between the program's traced AUC (float32 scores
    # on the device) and the benchmark's own, where its reference file sets
    # one: between what the chip reads and what scores rounded to bfloat16
    # would (the file has both); never looser than the train kind's
    limit = min(auc_vs_own, ref.get("traced_vs_own", auc_vs_own))
    problems = []
    if not abs(traced_auc - own_auc) <= limit:
        problems.append(f"the program's AUC {traced_auc} vs the benchmark's "
                        f"own walk of its trees {own_auc}: over {limit:.1e}")
    if not run.rehearsal and any(n != num_leaves for n in leaves):
        problems.append(f"trees of {leaves} leaves, want {num_leaves} each: "
                        "the seed decides how much work the run does")
    if not cat_share >= MIN_CATEGORICAL_SHARE:
        problems.append(f"{cat_share:.4f} of the internal nodes are "
                        f"categorical, want {MIN_CATEGORICAL_SHARE}")
    if ref:
        if not abs(own_auc - ref["auc"]) <= ref["band"]:
            problems.append(
                f"AUC {own_auc} is not within {ref['band']} of the cell's "
                f"reference {ref['auc']} ({ref_path})")
    elif not run.rehearsal:
        problems.append(f"{ref_path} is missing: a train cell needs its "
                        "reference AUC (benchmark/tools/"
                        "reference_auc_cat.py)")
    return problems
