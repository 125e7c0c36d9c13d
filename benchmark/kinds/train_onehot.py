"""Traffic kind ``train_onehot``: ONE ``lgb.train`` call on a one-hot
coded SPARSE table, the way a user who one-hot encodes makes it:
``lgb.Dataset(csr, label)`` with a ``scipy.sparse.csr_matrix``, a
validation set ``lgb.Dataset(csr_valid, label, reference=train)``,
``metric=auc``, ``record_evaluation`` and ``early_stopping(100)`` (the
built-in callback set, which stays on the megastep), on data generated
from the seed (``harness/data_onehot.py``: the categorical cell's table,
one-hot coded). The program bundles the exclusive columns at ingestion
(EFB); the cell measures the bundled job.

The clock, the window and the depth of a tree are the ``train`` kind's
(``kinds/train.py``), the fail-fast follower of the telemetry stream the
``train_rank`` kind's (``_EvictionWatch``: the first ``megastep_evicted``
or ``degrade`` event ends the run within seconds, exit code 3, no
result); both are loaded from their files, not copied. What differs: the
generator and the CSR on the ``Dataset``, the benchmark's own walk over
the LOGICAL columns in row blocks (``harness/reference_onehot.py``), the
reference's band (``benchmark/reference/<cell>.json``, made by
``tools/reference_auc_onehot.py``), and the checks of ``correct``:

- every tree has ``num_leaves`` leaves;
- at least ``MIN_ONEHOT_SHARE`` of the internal nodes split a one-hot
  column;
- the program's validation scores (its device scores, routed by its
  kernels over its stored validation set) are the benchmark's walk of the
  dumped trees over the logical CSR within ``SCORES_VS_WALK``;
- the traced AUC and the benchmark's own inside the reference's limits;
- tree 0's root split is the one a float64 scan of the root histograms
  of every logical column chooses (``reference_onehot.root_split``): the
  same column and threshold unless another candidate is within
  ``ROOT_GAIN_RTOL`` of the best gain, and the model's gain within
  ``ROOT_GAIN_RTOL`` of the scan's (the device histogram is float32).

Besides the public entry points the kind reads three things of the
program: the validation scores (``Booster._gbdt.valid_scores``), the bin
bounds of the training set's mappers (``Dataset._inner.mappers``) and,
for the kernels' width, the bundle layout (the ``efb_layout`` event, or
the training set's own where the program says none).

A run that is not done ``DEADLINE_S`` after its process started (a
program whose bundled job is too slow for the contract's 360 s, or
hangs) ends then with exit code 3 and no result, rather than being
killed.

A traffic file of this kind has: ``rows``, ``valid_rows``,
``chunk_iterations``, ``warmup_chunks``, ``measured_chunks``.
"""
from __future__ import annotations

import contextlib
import os
import statistics
import sys
import threading
import time

import numpy as np

from harness import (cells, data_onehot, monitor, reference,
                     reference_onehot, trace_capture, trace_reduce)

# the share of internal nodes that split a one-hot column: the cell
# measures the bundled columns' decode, and a model that split only
# DepTime and Distance would measure none of it
MIN_ONEHOT_SHARE = 0.25
# the program's float32 scores against the benchmark's float64 walk: 8
# trees of leaves under 1 leave a rounding of 1e-6 at most; one row routed
# to another leaf moves its score by a leaf's difference (1e-3 and more)
SCORES_VS_WALK = 1e-4
# the root histograms are float32 sums on the device (its bf16 high and
# low halves: 16 bits of a gradient's mantissa) and float64 here
ROOT_GAIN_RTOL = 1e-4
# seconds after the process started by which a run has its result: the
# contract gives a run 360
DEADLINE_S = 345.0
OUT_OF_TIME = 3                 # exit code of a run past the deadline


def steady_window(reduced):
    """The train kind's window (one whole run of the step and the gap
    after it); ``tools/phase_table.py`` asks a cell's kind for it."""
    return cells.load_module("kinds", "train").steady_window(reduced)


def _say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def _out_of_time() -> None:
    _say(f"the run is not done {DEADLINE_S:.0f} s after it started; "
         "nothing was measured.")
    sys.stderr.flush()
    os._exit(OUT_OF_TIME)


def run(run) -> dict:
    deadline = threading.Timer(
        max(DEADLINE_S - (time.time() - run.t_start), 0.0), _out_of_time)
    deadline.daemon = True
    deadline.start()
    try:
        return _run(run)
    finally:
        deadline.cancel()


def _run(run) -> dict:
    import jax.profiler as jp
    import lightgbm_tpu as lgb

    train = cells.load_module("kinds", "train")
    watch = cells.load_module("kinds", "train_rank")._EvictionWatch

    cfg, tr = run.config, run.traffic
    chunk = int(tr["chunk_iterations"])
    warm, measured = int(tr["warmup_chunks"]), int(tr["measured_chunks"])
    iters = chunk * (warm + measured)
    params = dict(cfg["params"])

    with run.phase("generate"):
        X, y, Xv, yv = data_onehot.make_data(run.seed, int(tr["rows"]),
                                             int(tr["valid_rows"]))
    with run.phase("bin"):
        # the keys of the configuration that shape the binned set
        ds = lgb.Dataset(X, label=y, params={"verbose": -1, **{
            k: params[k] for k in ("max_bin", "min_data_in_leaf")
            if k in params}})
        dv = lgb.Dataset(Xv, label=yv, reference=ds)
        ds.construct()
        dv.construct()

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

    layout = layout_of(events, ds)
    with run.phase("check"):
        dump = bst.dump_model(num_iteration=-1)
        trees = reference.flatten(dump)
        walked = reference_onehot.walk_csr(trees, Xv)
        own_auc = reference.rank_auc(yv, walked)
        scores = np.asarray(bst._gbdt.valid_scores[0],
                            np.float64).reshape(-1)[:walked.size]
        scores_vs_walk = float(np.max(np.abs(scores - walked)))
        root = root_check(X, y, ds, dump, params)
    del X, y
    traced_auc = float(auc_curve[-1]) if auc_curve else float("nan")
    share = reference_onehot.onehot_share(trees, data_onehot.NUMERICAL)
    leaves = [int(t["leaf_value"].size) for t in trees]
    problems += model_problems(run, own_auc, traced_auc, share, leaves,
                               int(params["num_leaves"]), train.AUC_VS_OWN,
                               scores_vs_walk, root)

    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1e3
    run.facts.update(
        rows=int(tr["rows"]), valid_rows=int(tr["valid_rows"]),
        # the kernels stream the BUNDLE columns, 256 bins each: the level
        # kernel's work is counted over them (harness/work.py)
        features=layout["columns"], max_bin=int(params["max_bin"]),
        logical_features=int(cfg["features"]), efb_layout=layout,
        iterations=iters,
        chunk_iterations=chunk, chips=int(run.cell["chips"]),
        dispatches=counters.get("train.dispatches"),
        tree_leaves=leaves,
        tree_levels=[train._levels(t) for t in trees],
        own_auc=own_auc, traced_auc=traced_auc,
        onehot_node_share=share, scores_vs_walk=scores_vs_walk,
        # the validation rows the program keeps in logical bins beside
        # its bundles (its ``valid_route`` event; None where it says none)
        valid_exact_rows=next((e.get("exact_rows") for e in events
                               if e.get("event") == "valid_route"), None),
        root_split=root,
        t_train0=t_train0, t_train1=t_train1, t_dispatch0=t_dispatch0,
        t_last_chunk=mega[-1]["ts"],
        step_first_call_s=built[0]["compile_ms"] / 1e3,
        megastep_cache_hit=run.compile_log.cache_traffic(
            t_dispatch0, mega[0]["ts"])["hits"] > 0)
    # harness/output.py prints own_auc and traced_auc under "checks"; the
    # rest of what was checked is said here
    _say(f"checks: onehot_node_share {share:.4f}, scores_vs_walk "
         f"{scores_vs_walk:.3e} (validation rows in logical bins "
         f"{run.facts['valid_exact_rows']}), root_split {root}, "
         f"efb_layout {layout}, "
         f"trees' leaves {leaves}, levels {run.facts['tree_levels']}, "
         f"own_auc {own_auc}, traced_auc {traced_auc}")
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


def layout_of(events: list, ds) -> dict:
    """The program's ``efb_layout`` event (columns, their widest bins,
    conflict rows, the routing form), or what its training set says of
    the layout where the program has no such event."""
    said = monitor.of_kind(events, "efb_layout")
    if said:
        return {k: said[-1][k] for k in ("features", "columns", "max_bins",
                                         "conflict_rows", "form")}
    pb = ds._inner.prebundled
    return {"features": int(ds._inner.num_features),
            "columns": int(pb.num_columns),
            "max_bins": int(max(pb.col_num_bin))}


def root_check(X, y, ds, dump: dict, params: dict) -> dict:
    """Tree 0's root against the float64 scan of the root histograms of
    every logical column (``reference_onehot.root_split``) under the
    model's own bin bounds. Returns what was compared and, under
    ``problem``, why it does not hold (None when it does)."""
    bounds = {j: np.asarray(m.bin_upper_bound, np.float64)
              for j, m in enumerate(ds._inner.mappers)
              if not m.is_trivial}
    found = reference_onehot.root_split(
        reference_onehot.root_histograms(X, y, bounds), params)
    node = dump["tree_info"][0]["tree_structure"]
    out = {"feature": int(node["split_feature"]),
           "threshold": float(node["threshold"]),
           "gain": float(node["split_gain"]), "problem": None}
    if not found:
        out["problem"] = "the scan finds no split of the root"
        return out
    best = found[0][0]
    out.update(scan_feature=int(found[0][1]),
               scan_threshold=float(bounds[found[0][1]][found[0][2]]),
               scan_gain=float(best))
    mine = [s for s in found if s[1] == out["feature"]
            and bounds[s[1]][s[2]] == out["threshold"]]
    if not mine:
        out["problem"] = "the model's root split is no candidate of the scan"
    elif mine[0][0] < best * (1.0 - ROOT_GAIN_RTOL):
        out["problem"] = (f"the model's root split has gain {mine[0][0]} in "
                          f"the scan, the best {best}: not a near tie")
    elif not abs(out["gain"] - mine[0][0]) <= ROOT_GAIN_RTOL * mine[0][0]:
        out["problem"] = (f"the model's root gain {out['gain']} against the "
                          f"scan's {mine[0][0]}")
    return out


def model_problems(run, own_auc: float, traced_auc: float, share: float,
                   leaves: list, num_leaves: int, auc_vs_own: float,
                   scores_vs_walk: float, root: dict) -> list:
    """The model itself, by the benchmark's own checks (module docstring):
    the reference's band and the traced-vs-own limit come from the cell's
    reference file where it has them, as the categorical kind reads
    them."""
    ref_path = os.path.join(cells.BENCH, "reference",
                            run.cell["name"] + ".json")
    ref = cells.load_json(ref_path) if os.path.exists(ref_path) else {}
    limit = min(auc_vs_own, ref.get("traced_vs_own", auc_vs_own))
    problems = []
    if not abs(traced_auc - own_auc) <= limit:
        problems.append(f"the program's AUC {traced_auc} vs the benchmark's "
                        f"own walk of its trees {own_auc}: over {limit:.1e}")
    if not scores_vs_walk <= SCORES_VS_WALK:
        problems.append(f"the program's validation scores are up to "
                        f"{scores_vs_walk} from the benchmark's walk over "
                        f"the logical columns: over {SCORES_VS_WALK}")
    if not run.rehearsal and any(n != num_leaves for n in leaves):
        problems.append(f"trees of {leaves} leaves, want {num_leaves} each: "
                        "the seed decides how much work the run does")
    if not share >= MIN_ONEHOT_SHARE:
        problems.append(f"{share:.4f} of the internal nodes split a one-hot "
                        f"column, want {MIN_ONEHOT_SHARE}")
    if root.get("problem"):
        problems.append(f"tree 0's root: {root['problem']} ({root})")
    if ref:
        if not abs(own_auc - ref["auc"]) <= ref["band"]:
            problems.append(
                f"AUC {own_auc} is not within {ref['band']} of the cell's "
                f"reference {ref['auc']} ({ref_path})")
    elif not run.rehearsal:
        problems.append(f"{ref_path} is missing: a train cell needs its "
                        "reference AUC (benchmark/tools/"
                        "reference_auc_onehot.py)")
    return problems
