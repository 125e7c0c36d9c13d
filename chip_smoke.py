#!/usr/bin/env python3
"""The quickest proof that lightgbm_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the one configuration the repo has been timed at on
hardware — 10.5M rows x 28 features, max_bin=63, num_leaves=255,
learning_rate=0.1, binary objective, plus a 500K-row validation set with
metric=auc (BASELINE.md GPU-benchmark row; data generated from a seed):

  kernels  the three Pallas kernels (level_pass, route_pass,
           table_lookup) COMPILED at the Higgs layout and compared with
           the numpy oracle of tests/test_fused_level.py;
  train    lgb.train, 64 iterations = two megastep dispatches of 32,
           every key but telemetry_out at its default (tpu_engine=auto
           must resolve to the compiled fused engine by itself);
  predict  Booster.predict on the validation rows (device predictor)
           against the host tree walk;
  serve    a PredictionService over the same booster: warmup, three
           requests of different sizes, flat compile count.

Any failed check or exception ends the process non-zero. It never sets
JAX_PLATFORMS and exits non-zero, printing no result, unless JAX finds a
TPU. On success stdout carries two JSON lines: first the summary (device,
versions, wall time per phase, AUC, dispatches/iter, compile and cache
counts, memory; also written to <out>/result.json; its timings are smoke
timings, not benchmark numbers), and LAST the verdict the driver parses,
exactly {"ok": true, "device": {"platform", "kind", "count"}}.

  python3 chip_smoke.py                    # the contract run, one chip
  python3 chip_smoke.py --data-parallel    # same configuration with
        tree_learner=data over every local chip, one process (needs > 1);
        compare its result.json / model.txt with the one-chip run's
  --rows / --valid-rows cut the data for a builder's debugging runs; the
  JSON says full_width=false then.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ROWS, VALID_ROWS, FEATURES, ITERS = 10_500_000, 500_000, 28, 64
MEGASTEP_CHUNK = 32      # min(tpu_megastep_iters, _FAST_SYNC_EVERY)
PARAMS = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
          "learning_rate": 0.1, "metric": "auc", "verbose": -1}
# Validation AUC after 64 iterations must clear this. Fixed from a CPU
# reference run of the same generator and parameters (XLA engine,
# JAX_PLATFORMS=cpu) at 100K train / 50K validation rows, which reached
# 0.96203; the floor sits 0.007 under it, and more rows only raise it.
AUC_FLOOR = 0.955


def require(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: FAILED: {what}")


def verdict(device: dict) -> str:
    """The line the driver parses: these keys and no others."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


@contextlib.contextmanager
def timed(wall: dict, phase: str):
    t0 = time.time()
    yield
    wall[phase] = round(time.time() - t0, 3)


class CompileLog:
    """Backend compiles and persistent-cache traffic, wall-stamped, from
    jax.monitoring — how the smoke proves the second chunk reused the
    first one's executable and whether the first chunk's executable
    came out of the cache. ("writes" is JAX's cache_misses event: it
    fires when an entry is written, not on every lookup that misses.)"""

    _CACHE = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "writes"}

    def __init__(self):
        from jax import monitoring
        self.backend = []           # (time.time(), seconds, fun_name)
        self.cache = []             # (time.time(), hits|writes)
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend.append((time.time(), float(seconds),
                                 str(kw.get("fun_name", "?"))))

    def _event(self, event, **_):
        if event in self._CACHE:
            self.cache.append((time.time(), self._CACHE[event]))

    def compiled_between(self, t0: float, t1: float) -> list:
        return [name for ts, _, name in self.backend if t0 < ts <= t1]

    def cache_traffic(self, t0: float = 0.0, t1: float = float("inf")):
        out = {"hits": 0, "writes": 0}
        for ts, key in self.cache:
            if t0 < ts <= t1:
                out[key] += 1
        return out


# ---------------------------------------------------------------- kernels
def kernel_case(Sp: int, rows: int = 4096, seed: int = 0) -> dict:
    """One level's worth of kernel inputs at the Higgs layout (28
    features x 64 padded bins, FB=1792, nch=5): numpy originals for the
    oracle plus the device operands level_pass/route_pass take. About
    three quarters of the Sp slots are live, with every missing type,
    both default directions and both smaller sides among them."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused_level import (build_route_table,
                                              feature_layout,
                                              route_table_columns)
    F = FEATURES
    F_oh, Bp = feature_layout(F, PARAMS["max_bin"])
    require((F_oh, Bp) == (28, 64), f"Higgs layout moved: {(F_oh, Bp)}")
    rng = np.random.RandomState(seed)
    nb = np.full(F, PARAMS["max_bin"], np.int32)
    nb[3], nb[10] = 7, 40
    mt = rng.randint(0, 3, F).astype(np.int32)
    db = np.where(mt == 1, rng.randint(0, 5, F), 0).astype(np.int32)
    bins = np.stack([rng.randint(0, nb[f], rows) for f in range(F)],
                    axis=1).astype(np.int8)
    grad = rng.randn(rows).astype(np.float32)
    hess = (np.abs(rng.randn(rows)) + 0.1).astype(np.float32)
    w = np.ones(rows, np.float32)
    live = max(1, Sp * 3 // 4)
    leaf = rng.randint(0, live, rows).astype(np.int32)
    slots = []
    for k in range(live):
        f = int(rng.randint(F))
        slots.append((k, f, int(rng.randint(0, nb[f] - 1)),
                      bool(rng.randint(2)), live, int(rng.randint(2))))
    slots += [(-2, 0, 0, False, 0, 0)] * (Sp - live)

    bins_T = np.zeros((max(F_oh, 8), rows), np.int8)
    bins_T[:F] = bins.T
    split = (jnp.asarray([s[1] if s[0] >= 0 else -1 for s in slots],
                         jnp.int32),
             jnp.asarray([s[2] for s in slots], jnp.int32),
             jnp.asarray([s[3] for s in slots]), jnp.asarray(nb),
             jnp.asarray(mt), jnp.asarray(db))
    W = build_route_table(*split, Sp, F_oh, Bp)
    tbl = np.zeros((Sp, 128), np.int32)
    for k, (lf, _, _, _, delta, small_left) in enumerate(slots):
        tbl[k, :3] = lf, delta, small_left
    tbl = jnp.asarray(tbl)
    # the same splits in the kernels' two routing forms: (W, tbl) by
    # table, (None, tbl with the split columns) by the bin values
    return dict(F=F, F_oh=F_oh, Bp=Bp, Sp=Sp, rows=rows, bins=bins,
                leaf=leaf, grad=grad, hess=hess, w=w, slots=slots,
                meta=(nb, mt, db), bins_T=jnp.asarray(bins_T),
                leaf_T=jnp.asarray(leaf[None, :]), W=W, tbl=tbl,
                forms={"table": (W, tbl),
                       "bins": (None, route_table_columns(tbl, *split))})


def _planes(hist, c, Sp):
    from lightgbm_tpu.ops.fused_level import NCH_PRECISE, hist_planes
    g, h, n = hist_planes(hist, NCH_PRECISE, Sp, c["F_oh"], c["Bp"])
    return np.stack([np.asarray(g), np.asarray(h), np.asarray(n)],
                    axis=-1)[:, :c["F"]]


def check_kernels(interpret: bool = False) -> None:
    """Each of the three kernels, compiled, against numpy at the
    tolerances tests/test_fused_level.py uses. A kernel that compiles
    and computes something else is the failure interpret mode cannot
    show."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused_level import (NCH_PRECISE, level_pass,
                                              pack_gh, route_pass,
                                              table_lookup)
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from test_fused_level import _oracle

    for Sp in (8, 128):
        c = kernel_case(Sp)
        want, want_leaf = _oracle(c["bins"], c["leaf"], c["grad"],
                                  c["hess"], c["w"], c["slots"], c["meta"],
                                  c["F"], c["Bp"])
        gh_T = pack_gh(jnp.asarray(c["grad"]), jnp.asarray(c["hess"]),
                       jnp.asarray(c["w"]), NCH_PRECISE)
        kw = dict(num_slots=Sp, num_bins=c["Bp"], f_oh=c["F_oh"],
                  interpret=interpret)
        for form, (W, tbl) in c["forms"].items():
            what = f"Sp={Sp} {form} form"
            hist, new_leaf = level_pass(c["bins_T"], c["leaf_T"], gh_T, W,
                                        tbl, nch=NCH_PRECISE, **kw)
            np.testing.assert_allclose(_planes(hist, c, Sp), want,
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"level_pass {what}")
            np.testing.assert_array_equal(
                np.asarray(new_leaf)[0], want_leaf,
                err_msg=f"level_pass leaf {what}")
            routed = route_pass(c["bins_T"], c["leaf_T"], W, tbl, **kw)
            np.testing.assert_array_equal(np.asarray(routed)[0], want_leaf,
                                          err_msg=f"route_pass {what}")
        say(f"kernels: level_pass + route_pass Sp={Sp} match numpy in "
            "both routing forms")

    rng = np.random.RandomState(1)
    L = PARAMS["num_leaves"]
    rows = c["rows"]
    lv = (0.1 * rng.randn(L)).astype(np.float32)
    idx = rng.randint(-1, L, size=rows).astype(np.int32)
    out = table_lookup(jnp.asarray(idx[None, :]), jnp.asarray(lv),
                       interpret=interpret)
    np.testing.assert_allclose(
        np.asarray(out)[0], np.where(idx >= 0, lv[np.clip(idx, 0, L - 1)],
                                     0.0), rtol=1e-6,
        err_msg="table_lookup")
    say("kernels: table_lookup matches numpy")


# ------------------------------------------------------------------- data
def make_data(rows: int, valid_rows: int, seed: int = 0):
    """bench.py _make_data's generator (uniform features, one random
    linear margin, centred, plus noise) with the weight vector drawn
    FIRST, so every row count is the same problem (the AUC floor comes
    from a reduced-row run), and train and validation drawn as one block
    so they share it."""
    rng = np.random.RandomState(seed)
    w = rng.randn(FEATURES).astype(np.float32)
    n = rows + valid_rows
    X = rng.rand(n, FEATURES).astype(np.float32)
    y = ((X - 0.5) @ w + 0.5 * rng.randn(n) > 0).astype(np.float32)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def read_events(path: str):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


# ------------------------------------------------------------------- main
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--valid-rows", type=int, default=VALID_ROWS)
    ap.add_argument("--data-parallel", action="store_true",
                    help="tree_learner=data over every local chip")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args()
    t_start = time.time()
    wall = {}

    # ---- device: the first JAX call; nothing here chooses a platform
    import jax
    import jaxlib
    devices = jax.devices()
    wall["backend_init"] = round(time.time() - t_start, 3)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                "numpy": np.__version__}
    say(f"device {device} versions {versions}")
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"'{device['platform']}' ({device['kind']} x "
                 f"{device['count']}, jax_platforms="
                 f"{jax.config.jax_platforms!r}). Nothing was run.")
    if args.data_parallel:
        require(len(devices) > 1, "--data-parallel needs several chips")

    import lightgbm_tpu as lgb
    from lightgbm_tpu.metric import _weighted_auc
    from lightgbm_tpu.native import loader
    from lightgbm_tpu.utils.platform import compilation_cache_dir
    cache_dir = compilation_cache_dir()
    compiles = CompileLog()
    os.makedirs(args.out, exist_ok=True)
    tel_path = os.path.join(args.out, "telemetry.jsonl")
    if os.path.exists(tel_path):
        os.remove(tel_path)

    with timed(wall, "kernels"):
        check_kernels()
    with timed(wall, "generate"):
        X, y, Xv, yv = make_data(args.rows, args.valid_rows)
    with timed(wall, "bin"):
        ds = lgb.Dataset(X, label=y, params={"max_bin": PARAMS["max_bin"],
                                             "verbose": -1})
        dv = lgb.Dataset(Xv, label=yv, reference=ds)
        ds.construct()
        dv.construct()

    # ---- train: one lgb.train call; the telemetry stream is how the
    # smoke sees inside it
    params = dict(PARAMS, telemetry_out=tel_path)
    if args.data_parallel:
        params["tree_learner"] = "data"
    curve = {}
    t_train = time.time()
    bst = lgb.train(params, ds, num_boost_round=ITERS, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(curve)])
    t_trained = time.time()
    g = bst._gbdt
    snap = bst.telemetry()
    counters = snap.get("counters", {})
    events = read_events(tel_path)
    mega = [e for e in events if e.get("event") == "megastep"]
    built = [e for e in events if e.get("event") == "compile_executable"]
    require(g.on_tpu and g.use_fused and not g.fused_interpret,
            f"engine is not the compiled fused one: on_tpu={g.on_tpu} "
            f"use_fused={g.use_fused} interpret={g.fused_interpret}")
    require(counters.get("train.dispatches") == ITERS // MEGASTEP_CHUNK,
            f"train.dispatches={counters.get('train.dispatches')} for "
            f"{ITERS} iterations, want {ITERS // MEGASTEP_CHUNK}")
    bad = [e for e in events
           if e.get("event") in ("degrade", "megastep_evicted")]
    require(not bad, f"fallback events in the telemetry stream: {bad}")
    require(bst.num_trees() == ITERS, f"{bst.num_trees()} trees grown")
    leaves0 = int(g.models[0].num_leaves)
    require(leaves0 > 128, f"first tree has {leaves0} leaves")
    require(len(mega) == 2 and len(built) == 1,
            f"{len(mega)} megastep records, {len(built)} executables")
    t_dispatch0 = built[0]["ts"] - built[0]["compile_ms"] / 1000.0
    wall["upload"] = round(t_dispatch0 - t_train, 3)
    wall["first_chunk"] = round(mega[0]["ts"] - t_dispatch0, 3)
    wall["second_chunk"] = round(mega[1]["ts"] - mega[0]["ts"], 3)
    late = compiles.compiled_between(mega[0]["ts"], mega[1]["ts"])
    require(not late, f"compiled during the second chunk: {late}")
    smoke_s_per_iter = mega[1]["sections"]["batch"] / mega[1]["iterations"]
    mem = [d.memory_stats() or {} for d in devices]
    say(f"trained: {wall} s; per device bytes_in_use "
        f"{[m.get('bytes_in_use') for m in mem]} peak "
        f"{[m.get('peak_bytes_in_use') for m in mem]}")

    if args.data_parallel:
        require(g.parallel_mode == "data" and g.n_shards == len(devices),
                f"parallel_mode={g.parallel_mode} n_shards={g.n_shards}")
        shards = g.fused_bins_T.addressable_shards
        require(len(shards) == len(devices)
                and len({s.data.shape for s in shards}) == 1,
                f"fused_bins_T shards: {[s.data.shape for s in shards]}")
        # Device 0 still holds every single-device per-row operand
        # (label_val, label_weight, bag_weight: 3 x 4 B x rows) and the
        # validation bins, so it is NOT within 20% of its peers yet
        # (ROADMAP A6); the peers hold only sharded and replicated
        # state and must agree. The JSON lists every device.
        peers = [m["bytes_in_use"] for m in mem[1:]]
        require(max(peers) <= 1.2 * min(peers)
                and mem[0]["bytes_in_use"] >= max(peers),
                f"per-device bytes_in_use: "
                f"{[m['bytes_in_use'] for m in mem]}")

    auc_curve = curve["valid_0"]["auc"]
    auc = float(auc_curve[-1])
    require(len(auc_curve) == ITERS and np.isfinite(auc)
            and auc > AUC_FLOOR,
            f"validation AUC {auc} (floor {AUC_FLOOR}), "
            f"{len(auc_curve)} points")

    # ---- predict: device predictor on the validation rows vs host walk
    with timed(wall, "predict"):
        pred = bst.predict(Xv)
    require(getattr(bst, "_device_predictor", None) is not None
            and bst._device_predictor.ok,
            "Booster.predict did not take the device predictor")
    host = bst.predict(Xv[:10_000])      # below pred_device_min_work
    np.testing.assert_allclose(pred[:10_000], host, rtol=1e-5,
                               err_msg="device predict vs host walk")
    host_auc = float(_weighted_auc(yv, pred, None))
    require(abs(host_auc - auc) < 1e-3,
            f"traced AUC {auc} vs host AUC of predictions {host_auc}")

    # ---- serve: warm up, answer three requests, compile count flat
    from lightgbm_tpu.serve import PredictionService
    with timed(wall, "serve"):
        svc = PredictionService({"higgs": bst})
        try:
            svc.warmup()
            compiled = svc.stats()["compiles"]
            for n in (7, 1000, 8192):
                ans = svc.predict("higgs", Xv[:n])
                np.testing.assert_allclose(
                    ans, bst.predict(Xv[:n]), rtol=1e-5,
                    err_msg=f"served {n} rows")
            require(svc.stats()["compiles"] == compiled,
                    f"serving compiled after warmup: {compiled} -> "
                    f"{svc.stats()['compiles']}")
        finally:
            svc.close()

    require(loader._LIB is None and not loader._TRIED,
            "the smoke path loaded or built a native library")

    model = bst.model_to_string(num_iteration=-1)
    with open(os.path.join(args.out, "model.txt"), "w") as fh:
        fh.write(model)
    result = {
        "ok": True, "device": device, "versions": versions,
        "config": {"rows": args.rows, "valid_rows": args.valid_rows,
                   "features": FEATURES, "iterations": ITERS, **PARAMS,
                   "tree_learner": g.parallel_mode},
        "full_width": (args.rows, args.valid_rows) == (ROWS, VALID_ROWS),
        "wall_s": dict(wall, train_total=round(t_trained - t_train, 3),
                       total=round(time.time() - t_start, 3)),
        "smoke_s_per_iter_second_chunk_not_a_benchmark":
            round(smoke_s_per_iter, 4),
        "auc": round(auc, 6), "auc_floor": AUC_FLOOR,
        "first_tree_leaves": leaves0,
        "dispatches_per_iter": counters["train.dispatches"] / ITERS,
        "backend_compiles": len(compiles.backend),
        "megastep_compile_ms": built[0]["compile_ms"],
        "cache": dict(compiles.cache_traffic(), dir=cache_dir,
                      first_chunk_hit=compiles.cache_traffic(
                          t_dispatch0, mega[0]["ts"])["hits"] > 0),
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
    }
    result["claim"] = None
    with open(os.path.join(args.out, "result.json"), "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result), flush=True)
    print(verdict(device), flush=True)     # the last line of stdout


if __name__ == "__main__":
    main()
