"""Step 0 of PR 35 (GOSS on the fast path), on the attached chip: the parts
of a sampled iteration timed alone, at the Higgs cell's shape.

  select      exactly top_k rows of the largest |g * h| (ops/goss.select_top:
              31 counting passes + the tie rule), and a full ``jnp.sort`` of
              the same vector beside it (the thing it avoids)
  draw        the counter-hash keys + the second select
  cumsum      the prefix sum of the in-bag mask, flat (``jnp.cumsum``) and in
              blocks (ops/scan.blocked_scan)
  compact     option A, the Pallas window compaction (ops/goss.compact_rows),
              at tiles of 256 / 512 / 1,024 rows, checked against a gather of
              a slice; option B, XLA only: ``nonzero(size=K)`` for the index
              list, a row gather of a resident row-major copy + a transpose
              for the bins, element gathers for the channels
  level_pass  at ROWS and at the capacity K, 8 and 64 slots
  route_pass  over ROWS at 8 and 64 slots (the replay over all rows), and
              ``table_lookup`` over ROWS

One JSON line per timing on stdout and in chiprun_out/step0_goss/timings.jsonl
(ms per launch: the mean of REPS launches after one warm-up,
``block_until_ready`` at both ends). INTERPRET=1 ROWS=20000 rehearses on the
CPU.

Run: python scripts/step0_goss.py        (ROWS=28000000 FEATURES=28 MAX_BIN=63)
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
    tiles = [int(t) for t in os.environ.get("TILES", "256,512,1024").split(",")]
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
        for C in tiles:
            fn = lambda: goss.compact_rows(bins_T, gh_T, inbag, capacity=K,
                                           tile_rows=C, interpret=interp)
            try:
                ms, (bins_c, gh_c) = _time(fn, reps)
            except Exception as e:       # what the chip refuses, said
                say(stage="compact.A", tile=C, error=str(e)[:300])
                continue
            ok = bool(np.array_equal(
                np.asarray(bins_c[:, :len(idx_head)]),
                bins_np[:, idx_head])) and bool(np.array_equal(
                    np.asarray(gh_c[:, :len(idx_head)].astype(jnp.float32)),
                    np.asarray(gh_T[:, :200_000].astype(jnp.float32))
                    [:, idx_head]))
            tail = int(jnp.sum(jnp.abs(bins_c[:, plan.bag_rows:]
                                       .astype(jnp.int32))))
            say(stage="compact.A", tile=C, ms=ms, exact=ok, tail_sum=tail,
                min_bytes=Rp * (4 + Fp + 16) + K * (Fp + 16))
        tables = jax.jit(lambda m: goss.compact_tables(m, Rp, K, 512))
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


if __name__ == "__main__":
    main()
