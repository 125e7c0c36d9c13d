"""Step 0 of GOSS on the fast path, on the attached chip: the parts of a
sampled iteration timed alone, at the Higgs cell's shape.

  select      exactly top_k rows of the largest |g * h| (ops/goss.select_top:
              31 counting passes + the tie rule), and a full ``jnp.sort`` of
              the same vector beside it (the thing it avoids)
  draw        the counter-hash keys + the second select
  cumsum      the prefix sum of the in-bag mask, flat (``jnp.cumsum``) and in
              blocks (ops/scan.blocked_scan)
  compact     the Pallas window compaction in two forms, at each tile of
              TILES (1,024 to 8,192 rows), each checked against a gather of
              a slice: ``staircase``, the program's (ops/goss.compact_rows:
              a [256, 128] 0/1 block per 128-row chunk, one MXU dot into the
              window at a 128-aligned lane offset), and ``lane_shift``
              (``compact_lane_shift`` below: every column shifted left by
              its count of out-of-bag rows before it, in log2 C stages of
              ``pltpu.roll`` and a select, LSB first, no MXU), with 32 int8
              bin rows and with 8 int16 ones; option B, XLA only:
              ``nonzero(size=K)`` for the index list, a row gather of a
              resident row-major copy + a transpose for the bins, element
              gathers for the channels
  level_pass  at ROWS and at the capacity K, 8 and 64 slots
  route_pass  over ROWS at 8 and 64 slots (the replay over all rows), and
              ``table_lookup`` over ROWS

One JSON line per timing on stdout and in chiprun_out/step0_goss/timings.jsonl
(ms per launch: the mean of REPS launches after one warm-up,
``block_until_ready`` at both ends). INTERPRET=1 ROWS=20000 rehearses on the
CPU.

Run: python scripts/step0_goss.py        (ROWS=28000000 FEATURES=28 MAX_BIN=63)
"""
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import fused_level as fl
from lightgbm_tpu.ops import goss
from lightgbm_tpu.ops.scan import blocked_scan

from ablate_route_form import _splits, _time


def main():
    R = int(os.environ.get("ROWS", 28_000_000))
    F = int(os.environ.get("FEATURES", 28))
    max_bin = int(os.environ.get("MAX_BIN", 63))
    reps = int(os.environ.get("REPS", 5))
    interp = bool(int(os.environ.get("INTERPRET", "0")))
    only = set(filter(None, os.environ.get("ONLY", "").split(",")))
    tiles = [int(t) for t in
             os.environ.get("TILES", "1024,2048,4096,8192").split(",")]
    forms = os.environ.get("FORMS", "staircase,lane_shift").split(",")
    plan = goss.goss_plan(R, 0.2, 0.1, 0.1, 3)
    K = plan.capacity
    F_oh, B = fl.feature_layout(F, max_bin)
    Fp = max(F_oh, 8)
    Rp = -(-R // 2048) * 2048
    rng = np.random.RandomState(0)
    bins_np = np.zeros((Fp, Rp), np.int8)
    bins_np[:F] = rng.randint(0, max_bin, size=(F, Rp), dtype=np.int8)
    bins_T = jnp.asarray(bins_np)
    g = jnp.asarray(rng.randn(Rp).astype(np.float32))
    h = jnp.asarray(rng.rand(Rp).astype(np.float32))
    dev = jax.devices()[0]
    out_dir = os.path.join("chiprun_out", "step0_goss")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "timings.jsonl"), "w")

    def say(**rec):
        rec.update(rows=R, capacity=K, device=dev.device_kind)
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    def want(name):
        return not only or name in only

    abs_gh = jnp.abs(g[:R] * h[:R])
    bits = goss.magnitude_bits(abs_gh)
    select = jax.jit(lambda v: goss.select_top(v, plan.top_k))
    top = select(bits)
    if want("select"):
        ms, top = _time(lambda: select(bits), reps)
        say(stage="select", ms=ms, taken=int(jnp.sum(top)))
        kth = jax.jit(_kth_by_sort, static_argnums=1)
        ms, _ = _time(lambda: kth(abs_gh, R - plan.top_k),
                      max(1, reps // 2))
        say(stage="select.full_sort", ms=ms)
    draw = jax.jit(lambda t, it: goss.select_top(
        jnp.where(t, -1, goss.draw_keys(R, 3, it)), plan.other_k))
    other = draw(top, 12)
    if want("draw"):
        ms, other = _time(lambda: draw(top, 12), reps)
        say(stage="draw", ms=ms, taken=int(jnp.sum(other)),
            overlap=int(jnp.sum(other & top)))
    inbag = top | other
    if want("cumsum"):
        m = inbag.astype(jnp.int32)
        ms, a = _time(lambda: jax.jit(jnp.cumsum)(m), reps)
        say(stage="cumsum.flat", ms=ms)
        ms, b = _time(lambda: jax.jit(blocked_scan)(m), reps)
        say(stage="cumsum.blocked", ms=ms, equal=bool(jnp.all(a == b)))
    mult, w = goss.sample_weights(top, other, plan.multiply)
    pad = Rp - R
    gh_T = fl.pack_gh(jnp.pad(g[:R] * mult, (0, pad)),
                      jnp.pad(h[:R] * mult, (0, pad)), jnp.pad(w, (0, pad)),
                      fl.NCH_PRECISE)
    bins_c = gh_c = None
    if want("compact"):
        idx_head = np.flatnonzero(np.asarray(inbag[:200_000]))
        gh_head = np.asarray(gh_T[:, :200_000].astype(jnp.float32))
        wide_np = rng.randint(0, 256, size=(8, Rp)).astype(np.int16)
        for bins_name, b_np in (("int8x32", bins_np), ("int16x8", wide_np)):
            b_dev = jnp.asarray(b_np)
            for form in forms:
                for C in tiles:
                    fn = (functools.partial(goss.compact_rows, tile_rows=C)
                          if form == "staircase" else
                          functools.partial(compact_lane_shift,
                                            tile_rows=C))
                    tag = dict(stage="compact.A", form=form, bins=bins_name,
                               tile=C)
                    try:
                        ms, (cb, cg) = _time(lambda: fn(
                            b_dev, gh_T, inbag, capacity=K,
                            interpret=interp), reps)
                    except Exception as e:   # what the chip refuses, said
                        say(**tag, error=str(e)[:300])
                        continue
                    ok = bool(np.array_equal(
                        np.asarray(cb[:, :len(idx_head)]),
                        b_np[:, idx_head])) and bool(np.array_equal(
                            np.asarray(cg[:, :len(idx_head)]
                                       .astype(jnp.float32)),
                            gh_head[:, idx_head]))
                    tail = int(jnp.sum(jnp.abs(cb[:, plan.bag_rows:]
                                               .astype(jnp.int32))))
                    say(**tag, ms=ms, exact=ok, tail_sum=tail,
                        min_bytes=Rp * (4 + b_np.shape[0] * b_np.itemsize
                                        + 16)
                        + K * (b_np.shape[0] * b_np.itemsize + 16))
                    if bins_name == "int8x32":
                        bins_c, gh_c = cb, cg
                    del cb, cg
            del b_dev
        tables = jax.jit(lambda m: goss.compact_tables(m, Rp, K, tiles[-1]))
        ms, _ = _time(lambda: tables(inbag), reps)
        say(stage="compact.A.tables", ms=ms)
        # option B: XLA only
        bins_rm = jnp.asarray(np.ascontiguousarray(bins_np.T))   # [Rp, Fp]
        nz = jax.jit(lambda m: jnp.nonzero(m, size=plan.bag_rows)[0])
        ms, idx = _time(lambda: nz(inbag), max(1, reps // 2))
        say(stage="compact.B.nonzero", ms=ms)
        gat = jax.jit(lambda rm, i: jnp.take(rm, i, axis=0).T)
        ms, _ = _time(lambda: gat(bins_rm, idx), max(1, reps // 2))
        say(stage="compact.B.row_gather_T", ms=ms)
        el = jax.jit(lambda x, i: jnp.take(x, i, axis=1))
        ms, _ = _time(lambda: el(gh_T[:5], idx), max(1, reps // 2))
        say(stage="compact.B.channel_gather", ms=ms)
        del bins_rm
    del bins_np
    if bins_c is None:
        bins_c, gh_c = bins_T[:, :K], gh_T[:, :K]
    for Sp in (8, 64):
        tbl, sp_args = _splits(rng, Sp, F, max_bin, F_oh)
        tbl_b = fl.route_table_columns(tbl, *sp_args)
        kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, interpret=interp)
        for name, bt, gt, rows in (("all_rows", bins_T, gh_T, R),
                                   ("capacity", bins_c, gh_c,
                                    plan.bag_rows)):
            n_cols = bt.shape[1]
            leaf = jnp.asarray(
                np.where(np.arange(n_cols) < rows,
                         rng.randint(0, Sp, n_cols), -1)
                .astype(np.int32))[None, :]
            if want("level_pass"):
                ms, _ = _time(lambda: fl.level_pass(bt, leaf, gt, None,
                                                    tbl_b, **kw), reps)
                say(stage="level_pass", slots=Sp, over=name, cols=n_cols,
                    ms=ms)
            if want("route_pass") and name == "all_rows":
                ms, _ = _time(lambda: fl.route_pass(bt, leaf, None, tbl_b,
                                                    **kw), reps)
                say(stage="route_pass", slots=Sp, over=name, cols=n_cols,
                    ms=ms)
    if want("route_pass"):
        leaf = jnp.asarray(rng.randint(0, 255, Rp).astype(np.int32))[None, :]
        vals = jnp.asarray(rng.randn(255).astype(np.float32))
        ms, _ = _time(lambda: fl.table_lookup(leaf, vals, interpret=interp),
                      reps)
        say(stage="table_lookup", cols=Rp, ms=ms)
    sink.close()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


def _kth_by_sort(a, pos):
    return jnp.sort(a)[pos]


# ------------------------------------------- the lane-shift compaction form
def _lane_shift_kernel(blk_ref, a_ref, dest_ref, bins_ref, gh_ref, obins_ref,
                       ogh_ref, acc_ref, *, C: int, T: int, steps: int,
                       nb: int):
    """The window scheme of ops/goss.compact_rows with another move: the
    tile's bins and channels bitcast to int32 rows, every in-bag column
    shifted left to its place by log2 stages of a roll and a select (a
    column whose shift has bit k set moves 2^k lanes, LSB first: the
    shifts do not fall along the tile, so no two columns meet), and the
    tile added into the window at the 128-aligned lane ``a``."""
    t = pl.program_id(0)
    b = blk_ref[t]
    W = C + 128

    @pl.when(t == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((t > 0) & (b != blk_ref[jnp.maximum(t - 1, 0)]))
    def _():
        acc_ref[:, :C] = acc_ref[:, C:]
        acc_ref[:, C:] = jnp.zeros((acc_ref.shape[0], C), acc_ref.dtype)

    @pl.when(t < T)
    def _():
        a = pl.multiple_of(a_ref[jnp.minimum(t, T - 1)], 128)
        d = dest_ref[:]                                        # [1, C]
        inb = d >= 0
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) + 128
        sft = jnp.where(inb, pos - (d - b * C - a), 0)
        x = jnp.concatenate([pltpu.bitcast(bins_ref[:], jnp.int32),
                             pltpu.bitcast(gh_ref[:], jnp.int32)], axis=0)
        x = jnp.where(inb, x, 0)
        R = x.shape[0]
        x = jnp.concatenate([jnp.zeros((R, 128), jnp.int32), x], axis=1)
        sft = jnp.concatenate([jnp.zeros((1, 128), jnp.int32), sft], axis=1)
        for k in range((C + 127).bit_length()):
            xin = pltpu.roll(x, W - (1 << k), 1)
            sin = pltpu.roll(sft, W - (1 << k), 1)
            come = ((sin >> k) & 1) == 1
            go = ((sft >> k) & 1) == 1
            x = jnp.where(come, xin, jnp.where(go, 0, x))
            sft = jnp.where(come, sin, jnp.where(go, 0, sft))
        acc_ref[:, pl.ds(a, W)] += x

    @pl.when((t == steps - 1) | (blk_ref[jnp.minimum(t + 1, steps - 1)] != b))
    def _():
        obins_ref[:] = pltpu.bitcast(acc_ref[:nb, :C], obins_ref.dtype)
        ogh_ref[:] = pltpu.bitcast(acc_ref[nb:, :C], ogh_ref.dtype)


@functools.partial(jax.jit, static_argnames=("capacity", "tile_rows",
                                             "interpret"))
def compact_lane_shift(bins_T, gh_T, inbag, *, capacity, tile_rows,
                       interpret=False):
    """``ops/goss.compact_rows``' contract, by ``_lane_shift_kernel``."""
    Fp, Rp = bins_T.shape
    G = gh_T.shape[0]
    C = tile_rows
    T = Rp // C
    blk, dest, _ = goss.compact_tables(inbag, Rp, capacity, C)
    steps = blk.shape[0]
    m = jnp.pad(inbag.astype(jnp.int32), (0, Rp - inbag.shape[0]))
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(jnp.sum(m.reshape(-1, C),
                                                 axis=1))[:-1]])
    a = (before - blk[:T] * C) // 128 * 128
    nb = Fp * bins_T.dtype.itemsize // 4
    ng = G * 2 // 4
    row_in = lambda rows: pl.BlockSpec(
        (rows, C), lambda t, blk, a: (0, jnp.minimum(t, T - 1)))
    row_out = lambda rows: pl.BlockSpec((rows, C),
                                        lambda t, blk, a: (0, blk[t]))
    return pl.pallas_call(
        functools.partial(_lane_shift_kernel, C=C, T=T, steps=steps, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps,),
            in_specs=[row_in(1), row_in(Fp), row_in(G)],
            out_specs=[row_out(Fp), row_out(G)],
            scratch_shapes=[pltpu.VMEM((nb + ng, 2 * C), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((Fp, capacity), bins_T.dtype),
                   jax.ShapeDtypeStruct((G, capacity), gh_T.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blk, a, dest, bins_T, gh_T)


if __name__ == "__main__":
    main()
