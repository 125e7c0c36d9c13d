"""Table form against bins form of the routing, on the attached chip.

Times, at one cell's shape, ``route_pass`` and ``level_pass`` with the
same random numerical splits in both forms (ops/fused_level.py: table =
``W @ one_hot`` over K = FB, bins = the split feature's bin value picked
with a K = Fp dot), checks that both give the same leaves and the same
histogram, and sweeps the bins-form route kernel's tile. What the
difference of the two ``level_pass`` timings is: the routing dot's cost
inside a pass; the table-form ``route_pass`` minus that: the one-hot
build.

Run: ROWS=28000000 FEATURES=28 SLOTS=8,64 python scripts/ablate_route_form.py
     ROWS=6810888 FEATURES=137 SLOTS=8,16 VALID_ROWS=753611 ...
(INTERPRET=1 rehearses the script on the CPU at a tiny ROWS.)
One JSON line per timing on stdout, all of them in
chiprun_out/ablate_route_form/<FEATURES>.jsonl.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import fused_level as fl


def _splits(rng, Sp, F, max_bin, F_oh):
    """Random numerical splits on every slot but the last quarter, with
    every missing type present."""
    feat = rng.randint(0, F, Sp).astype(np.int32)
    feat[Sp - Sp // 4:] = -1
    thr = rng.randint(0, max_bin - 1, Sp).astype(np.int32)
    dl = rng.randint(0, 2, Sp).astype(bool)
    nb = np.zeros(F_oh, np.int32)
    nb[:F] = max_bin
    mt = np.zeros(F_oh, np.int32)
    mt[:F] = rng.randint(0, 3, F)
    db = np.zeros(F_oh, np.int32)
    lof = np.where(feat >= 0, np.arange(Sp), -2).astype(np.int32)
    tbl = np.zeros((Sp, 128), np.int32)
    tbl[:, 0] = lof
    tbl[:, 1] = np.where(feat >= 0, Sp, 0)
    tbl[:, 2] = rng.randint(0, 2, Sp)
    args = [jnp.asarray(a) for a in (feat, thr, dl, nb, mt, db)]
    return jnp.asarray(tbl), args


def _time(fn, reps):
    out = jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        last = fn()
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / reps * 1e3, out


def main():
    R = int(os.environ.get("ROWS", 28_000_000))
    Rv = int(os.environ.get("VALID_ROWS", 0))
    F = int(os.environ.get("FEATURES", 28))
    max_bin = int(os.environ.get("MAX_BIN", 63))
    slots = [int(s) for s in os.environ.get("SLOTS", "8,64").split(",")]
    tiles = [int(t) for t in
             os.environ.get("TILES", "0,1024,2048,4096,8192").split(",")]
    reps = int(os.environ.get("REPS", 5))
    F_oh, B = fl.feature_layout(F, max_bin)
    Fp = max(F_oh, 8)
    Rp = -(-R // 2048) * 2048
    rng = np.random.RandomState(0)
    bins_np = np.zeros((Fp, Rp), np.int8)
    bins_np[:F] = rng.randint(0, max_bin, size=(F, Rp), dtype=np.int8)
    bins_T = jnp.asarray(bins_np)
    del bins_np
    g = jnp.asarray(rng.randn(Rp).astype(np.float32))
    ones = jnp.ones((Rp,), jnp.float32)
    gh_T = fl.pack_gh(g, ones, ones, fl.NCH_PRECISE)
    dev = jax.devices()[0]
    out_dir = os.path.join("chiprun_out", "ablate_route_form")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"{F}.jsonl"), "w")

    def say(**rec):
        rec.update(rows=R, features=F, fb=F_oh * B, device=dev.device_kind)
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for Sp in slots:
        tbl, sp_args = _splits(rng, Sp, F, max_bin, F_oh)
        leaf_T = jnp.asarray(
            np.where(np.arange(Rp) < R, rng.randint(0, Sp, Rp), -1)
            .astype(np.int32))[None, :]
        W = fl.build_route_table(*sp_args, Sp, F_oh, B)
        tbl_b = fl.route_table_columns(tbl, *sp_args)
        kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh,
                  interpret=bool(int(os.environ.get("INTERPRET", "0"))))

        ms_t, leaf_t = _time(
            lambda: fl.route_pass(bins_T, leaf_T, W, tbl, **kw), reps)
        say(kernel="route_pass", form="table", slots=Sp, ms=ms_t,
            tile=fl.default_tile_rows(Sp, F_oh * B, fl.NCH_FAST))
        for tile in tiles:
            ms_b, leaf_b = _time(
                lambda: fl.route_pass(bins_T, leaf_T, None, tbl_b,
                                      tile_rows=tile, **kw), reps)
            say(kernel="route_pass", form="bins", slots=Sp, ms=ms_b,
                tile=tile or fl.route_tile_rows(Sp, Fp),
                default_tile=tile == 0,
                same_leaves=bool(jnp.array_equal(leaf_t, leaf_b)))
        if Rv:
            Rvp = -(-Rv // 2048) * 2048
            bins_v, leaf_v = bins_T[:, :Rvp], leaf_T[:, :Rvp]
            for form, w, t in (("table", W, tbl), ("bins", None, tbl_b)):
                ms_v, _ = _time(
                    lambda: fl.route_pass(bins_v, leaf_v, w, t, **kw), reps)
                say(kernel="route_pass", form=form, slots=Sp, ms=ms_v,
                    valid_rows=Rv)
        ms_lt, (hist_t, nl_t) = _time(
            lambda: fl.level_pass(bins_T, leaf_T, gh_T, W, tbl, **kw), reps)
        say(kernel="level_pass", form="table", slots=Sp, ms=ms_lt,
            tile=fl.default_tile_rows(Sp, F_oh * B, fl.NCH_PRECISE))
        ms_lb, (hist_b, nl_b) = _time(
            lambda: fl.level_pass(bins_T, leaf_T, gh_T, None, tbl_b, **kw),
            reps)
        say(kernel="level_pass", form="bins", slots=Sp, ms=ms_lb,
            same_leaves=bool(jnp.array_equal(nl_t, nl_b)
                             and jnp.array_equal(nl_t, leaf_t)),
            same_hist=bool(jnp.array_equal(hist_t, hist_b)),
            routing_dot_ms=ms_lt - ms_lb, build_ms=ms_t - (ms_lt - ms_lb))


if __name__ == "__main__":
    main()
