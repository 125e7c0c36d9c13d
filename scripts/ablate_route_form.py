"""Table form against bins form of the routing, on the attached chip.

Times, at one cell's shape, ``route_pass`` and ``level_pass`` with the
same random numerical splits in both forms (ops/fused_level.py: table =
``W @ one_hot`` over K = FB, bins = the split feature's bin value picked
with a K = Fp dot), checks that both give the same leaves and the same
histogram, and sweeps the bins-form route kernel's tile. What the
difference of the two ``level_pass`` timings is: the routing dot's cost
inside a pass; the table-form ``route_pass`` minus that: the one-hot
build.

The categorical case (PR 34; CAT_COLUMNS > 0): the first CAT_COLUMNS
columns are split by random bin SETS of 1-32 members, the others by
thresholds. The table form is the one a categorical job ran until PR 34
(``build_route_table(cat_flag, cat_mask)``); the bins form carries each
slot's set as 256 bits of the slot table and tests membership in the
kernel's routing prologue (``has_cat``). What the test costs: the same
bins-form launch with every slot read as numerical (``has_cat`` off, so
the membership code is not traced), printed as ``prologue_ms`` and its
share of the launch.

Run: ROWS=28000000 FEATURES=28 SLOTS=8,64 python scripts/ablate_route_form.py
     ROWS=6810888 FEATURES=137 SLOTS=8,16 VALID_ROWS=753611 ...
     ROWS=28000000 FEATURES=8 MAX_BIN=255 CAT_COLUMNS=6 SLOTS=8,16,32,64 \
         TILES=0 VALID_ROWS=1000000 ...
(INTERPRET=1 rehearses the script on the CPU at a tiny ROWS.)
One JSON line per timing on stdout, all of them in
chiprun_out/ablate_route_form/<FEATURES>[-cat].jsonl.
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


def _bin_sets(rng, feature, cat_columns, max_bin, B):
    """cat_flag [Sp] / cat_mask [Sp, B] of the slots whose split column
    is one of the first ``cat_columns``: a random set of 1-32 of the
    column's ``max_bin`` bins goes left."""
    feature = np.asarray(feature)
    flag = (feature >= 0) & (feature < cat_columns)
    mask = np.zeros((len(feature), B), bool)
    for k in np.flatnonzero(flag):
        mask[k, rng.choice(max_bin, rng.randint(1, 33), replace=False)] = True
    return dict(cat_flag=jnp.asarray(flag), cat_mask=jnp.asarray(mask))


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
    cat_columns = int(os.environ.get("CAT_COLUMNS", 0))
    slots = [int(s) for s in os.environ.get("SLOTS", "8,64").split(",")]
    tiles = [int(t) for t in
             os.environ.get("TILES", "0,1024,2048,4096,8192").split(",")]
    reps = int(os.environ.get("REPS", 5))
    F_oh, B = fl.feature_layout(F, max_bin)
    Fp = max(F_oh, 8)
    Rp = -(-R // 2048) * 2048
    rng = np.random.RandomState(0)
    bins_dt = np.int8 if B <= 128 else np.int16
    bins_np = np.zeros((Fp, Rp), bins_dt)
    bins_np[:F] = rng.randint(0, max_bin, size=(F, Rp), dtype=bins_dt)
    bins_T = jnp.asarray(bins_np)
    del bins_np
    g = jnp.asarray(rng.randn(Rp).astype(np.float32))
    ones = jnp.ones((Rp,), jnp.float32)
    gh_T = fl.pack_gh(g, ones, ones, fl.NCH_PRECISE)
    dev = jax.devices()[0]
    out_dir = os.path.join("chiprun_out", "ablate_route_form")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(
        out_dir, f"{F}{'-cat' if cat_columns else ''}.jsonl"), "w")

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
        sets = _bin_sets(rng, sp_args[0], cat_columns, max_bin, B) \
            if cat_columns else {}
        W = fl.build_route_table(*sp_args, Sp, F_oh, B, **sets)
        tbl_b = fl.route_table_columns(tbl, *sp_args, **sets)
        kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh,
                  interpret=bool(int(os.environ.get("INTERPRET", "0"))))
        # the bins form of a categorical job traces the membership test
        kw_b = dict(kw, has_cat=True) if sets else kw
        # the same launch with every slot read as numerical: what is left
        # of it without the test
        tbl_n = fl.route_table_columns(tbl, *sp_args)

        def prologue(ms, fn):
            if not sets:
                return {}
            ms_n, _ = _time(fn, reps)
            return dict(membership=True, numerical_ms=ms_n,
                        prologue_ms=ms - ms_n,
                        prologue_share=(ms - ms_n) / ms)

        ms_t, leaf_t = _time(
            lambda: fl.route_pass(bins_T, leaf_T, W, tbl, **kw), reps)
        say(kernel="route_pass", form="table", slots=Sp, ms=ms_t,
            tile=fl.default_tile_rows(Sp, F_oh * B, fl.NCH_FAST))
        for tile in tiles:
            ms_b, leaf_b = _time(
                lambda: fl.route_pass(bins_T, leaf_T, None, tbl_b,
                                      tile_rows=tile, **kw_b), reps)
            say(kernel="route_pass", form="bins", slots=Sp, ms=ms_b,
                tile=tile or fl.route_tile_rows(Sp, Fp, bool(sets)),
                default_tile=tile == 0,
                same_leaves=bool(jnp.array_equal(leaf_t, leaf_b)),
                **prologue(ms_b, lambda: fl.route_pass(
                    bins_T, leaf_T, None, tbl_n, tile_rows=tile, **kw)))
        if Rv:
            Rvp = -(-Rv // 2048) * 2048
            bins_v, leaf_v = bins_T[:, :Rvp], leaf_T[:, :Rvp]
            for form, w, t, k in (("table", W, tbl, kw),
                                  ("bins", None, tbl_b, kw_b)):
                ms_v, _ = _time(
                    lambda: fl.route_pass(bins_v, leaf_v, w, t, **k), reps)
                say(kernel="route_pass", form=form, slots=Sp, ms=ms_v,
                    valid_rows=Rv)
        ms_lt, (hist_t, nl_t) = _time(
            lambda: fl.level_pass(bins_T, leaf_T, gh_T, W, tbl, **kw), reps)
        say(kernel="level_pass", form="table", slots=Sp, ms=ms_lt,
            tile=fl.default_tile_rows(Sp, F_oh * B, fl.NCH_PRECISE))
        ms_lb, (hist_b, nl_b) = _time(
            lambda: fl.level_pass(bins_T, leaf_T, gh_T, None, tbl_b, **kw_b),
            reps)
        # the two forms run different row tiles since PR 31, which regroups
        # the float32 partial sums: the difference is said as a share of
        # the largest sum
        say(kernel="level_pass", form="bins", slots=Sp, ms=ms_lb,
            tile=fl.level_build(True, Sp, F_oh * B, fl.NCH_PRECISE, Fp,
                                has_cat=bool(sets))["tile_rows"],
            same_leaves=bool(jnp.array_equal(nl_t, nl_b)
                             and jnp.array_equal(nl_t, leaf_t)),
            same_hist=bool(jnp.array_equal(hist_t, hist_b)),
            hist_diff=float(jnp.max(jnp.abs(hist_t - hist_b))
                            / jnp.max(jnp.abs(hist_t))),
            routing_dot_ms=ms_lt - ms_lb, build_ms=ms_t - (ms_lt - ms_lb),
            **prologue(ms_lb, lambda: fl.level_pass(
                bins_T, leaf_T, gh_T, None, tbl_n, **kw)))


if __name__ == "__main__":
    main()
