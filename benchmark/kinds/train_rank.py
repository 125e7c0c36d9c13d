"""Traffic kind ``train_rank``: ONE ``lgb.train`` call of a learning-to-rank
job, the way a user makes it: query groups on the training and the
validation set, ``metric=ndcg``, ``record_evaluation`` and
``early_stopping(100)`` (the built-in callback set, which stays on the
megastep), on data generated from the seed (``harness/data_rank.py``).

The clock is the ``train`` kind's: the job is ``warmup_chunks +
measured_chunks`` megastep chunks of ``chunk_iterations`` iterations, the
program's telemetry stream marks the end of every chunk, set-up ends with
the last warm-up chunk and a sample is the interval between two chunk ends
divided by the chunk's iterations.

The cell measures the megastep: a thread follows the stream, and the first
``megastep_evicted`` or ``degrade`` event ends the run within seconds with
a non-zero exit code and no result (the job would otherwise go on, off the
path that is measured, for minutes).

A traffic file of this kind has: ``rows``, ``queries``, ``valid_rows``,
``valid_queries``, ``longest_query``, ``chunk_iterations``,
``warmup_chunks``, ``measured_chunks``.
"""
from __future__ import annotations

import contextlib
import json
import math
import os
import statistics
import sys
import threading
import time

from harness import (cells, data_rank, monitor, reference, reference_rank,
                     trace_capture, trace_reduce)

# the program's traced NDCG (float32 scores on the device) vs the benchmark's
# own (float64, over its own walk of the dumped trees): they rank the same
# scores, so they differ by the float32 rounding of a mean (the chip read at
# most 1.9e-7 at any cutoff) unless two documents a rounding apart swap
# places. The limit at each cutoff is HALF the worst such swap (a query's
# only relevant document falls from first to second place, or out of the top
# k): 7.9e-5, 4.0e-5, 3.1e-5, 2.9e-5 at @1, 3, 5, 10 over 6,306 queries. A
# reference model's validation scores hold 0.004 pairs a rounding (1e-7)
# apart in first and second place, so a sound run meets even one such swap
# once in hundreds; scores rounded to bfloat16 are over a limit, twice at
# least, on every seed of the reference tool (PERF.md section 6)
SWAPS = 0.5


def ndcg_vs_own_limit(k: int, queries: int) -> float:
    worst = max(1.0 - 1.0 / math.log2(3.0), 1.0 / math.log2(k + 1.0))
    return SWAPS * worst / queries


LEFT_THE_FAST_PATH = 3          # exit code of a run that was evicted


def steady_window(reduced):
    """The train kind's window (one whole run of the step and the gap
    after it); ``tools/phase_table.py`` asks a cell's kind for it."""
    return cells.load_module("kinds", "train").steady_window(reduced)


def _say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


class _EvictionWatch:
    """Follows the telemetry stream while ``lgb.train`` blocks; the first
    event that says the job left the fast path ends the process."""

    def __init__(self, path: str, poll_s: float = 0.2):
        self.path, self.poll_s = path, poll_s
        self._quit = threading.Event()
        self._thread = threading.Thread(target=self._follow, daemon=True,
                                        name="bench-eviction-watch")

    def _follow(self) -> None:
        offset = 0
        while not self._quit.wait(self.poll_s):
            try:
                with open(self.path) as fh:
                    fh.seek(offset)
                    fresh = fh.read()
            except FileNotFoundError:
                continue
            whole = fresh[:fresh.rfind("\n") + 1]
            offset += len(whole.encode())
            for line in whole.splitlines():
                if '"megastep_evicted"' in line or '"degrade"' in line:
                    event = json.loads(line)
                    if event.get("event") in ("megastep_evicted",
                                              "degrade"):
                        _say(f"the program left its fast path: {event}. "
                             "This cell measures the megastep; nothing "
                             "was measured.")
                        sys.stderr.flush()
                        os._exit(LEFT_THE_FAST_PATH)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._quit.set()
        self._thread.join(timeout=10)


def run(run) -> dict:
    import jax.profiler as jp
    import lightgbm_tpu as lgb

    levels_of = cells.load_module("kinds", "train")._levels

    cfg, tr = run.config, run.traffic
    chunk = int(tr["chunk_iterations"])
    warm, measured = int(tr["warmup_chunks"]), int(tr["measured_chunks"])
    iters = chunk * (warm + measured)
    params = dict(cfg["params"])

    with run.phase("generate"):
        X, y, group, Xv, yv, group_v = data_rank.make_data(
            run.seed, int(tr["rows"]), int(tr["queries"]),
            int(tr["valid_rows"]), int(tr["valid_queries"]),
            int(cfg["features"]), int(tr["longest_query"]))
    with run.phase("bin"):
        # the keys of the configuration that shape the binned set
        ds = lgb.Dataset(X, label=y, group=group, params={
            "verbose": -1, **{k: params[k] for k in
                              ("max_bin", "min_data_in_leaf") if k in params}})
        dv = lgb.Dataset(Xv, label=yv, group=group_v, reference=ds)
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
    with tracing, _EvictionWatch(tel_path):
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
    ks = [int(k) for k in params["eval_at"]]
    curves = curve.get("valid_0", {})
    for k in ks:
        require(len(curves.get(f"ndcg@{k}", [])) == iters,
                f"{len(curves.get(f'ndcg@{k}', []))} evaluations of "
                f"ndcg@{k} for {iters} iterations")
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
        own = dict(zip(ks, map(float, reference_rank.ndcg_at(
            ks, yv, reference.walk(trees, Xv), group_v))))
    traced = {k: float(curves[f"ndcg@{k}"][-1])
              for k in ks if curves.get(f"ndcg@{k}")}
    problems += model_problems(run, own, traced)

    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1e3
    run.facts.update(
        rows=int(tr["rows"]), valid_rows=int(tr["valid_rows"]),
        queries=int(tr["queries"]), valid_queries=int(tr["valid_queries"]),
        features=int(cfg["features"]), max_bin=int(params["max_bin"]),
        iterations=iters,
        chunk_iterations=chunk, chips=int(run.cell["chips"]),
        dispatches=counters.get("train.dispatches"),
        rank_pairs_per_iter=counters.get("rank.pairs_per_iter"),
        tree_leaves=[int(t["leaf_value"].size) for t in trees],
        tree_levels=[levels_of(t) for t in trees],
        own_ndcg=own, traced_ndcg=traced,
        t_train0=t_train0, t_train1=t_train1, t_dispatch0=t_dispatch0,
        t_last_chunk=mega[-1]["ts"],
        step_first_call_s=built[0]["compile_ms"] / 1e3,
        megastep_cache_hit=run.compile_log.cache_traffic(
            t_dispatch0, mega[0]["ts"])["hits"] > 0)
    _say(f"trees' leaves {run.facts['tree_leaves']}, levels "
         f"{run.facts['tree_levels']}; ndcg own {own}, traced {traced}")
    if run.trace:
        run.facts["window_in_use_bytes"] = tracing.in_use_peak
        reduced = trace_reduce.reduce_dir(
            os.path.join(run.scratch, "trace"), run.rehearsal)
        if reduced is not None:
            run.window = steady_window(reduced)
            _, first = min(reduced.steps,
                           key=lambda st: abs(st[0] - run.window.t0))
            run.facts["window_trees"] = list(range(first, first + chunk))
    metrics = {"setup_s": t_setup_end - run.t_start}
    if samples:
        metrics["train_s_per_iter"] = statistics.median(samples)
    return {"metrics": metrics, "attempted": len(samples) + not_run,
            "failed": not_run, "problems": problems}


def model_problems(run, own: dict, traced: dict) -> list:
    """The model itself, by the benchmark's own scorer: the program's
    traced NDCG has to be the NDCG of its trees at every cutoff, and the
    NDCG@10 has to be inside the band of the cell's reference
    (``benchmark/reference/<cell>.json``, made by
    ``benchmark/tools/reference_ndcg.py``)."""
    problems = []
    if 10 not in own or 10 not in traced:
        return ["eval_at does not hold 10: nothing to judge"]
    for k in own:
        limit = ndcg_vs_own_limit(k, int(run.traffic["valid_queries"]))
        if not abs(traced.get(k, float("nan")) - own[k]) <= limit:
            problems.append(f"the program's ndcg@{k} {traced.get(k)} vs the "
                            f"benchmark's own walk of its trees {own[k]}: "
                            f"over {limit:.2e}")
    ref_path = os.path.join(cells.BENCH, "reference",
                            run.cell["name"] + ".json")
    if os.path.exists(ref_path):
        ref = cells.load_json(ref_path)
        if not abs(own[10] - ref["ndcg@10"]) <= ref["band"]:
            problems.append(
                f"ndcg@10 {own[10]} is not within {ref['band']} of the "
                f"cell's reference {ref['ndcg@10']} ({ref_path})")
    elif not run.rehearsal:
        problems.append(f"{ref_path} is missing: a ranking cell needs its "
                        "reference (benchmark/tools/reference_ndcg.py)")
    return problems
