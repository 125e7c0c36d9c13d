"""Tile-width x quant-bits ablation of the fused histogram level pass.

The histogram-plane cuts land with their CPU-side contracts proven
(byte-identity, accuracy A/Bs, dispatch parity) but their on-chip speed
unmeasured.  This harness is the ready-to-run measurement: it times
``ops/fused_level.level_pass`` over a tile-width x quant-bits grid
(f32/bf16x2 baseline vs int16 vs int8 channels, padded vs adaptive
layout) and appends one tagged record per combination to
BENCH_TRAJECTORY.jsonl, so the ablation series lands in the same history
``scripts/bench_compare.py`` reads.

Run (on the chip):   ROWS=10500000 python scripts/ablate_hist.py
CPU smoke:           ROWS=4096 INTERPRET=1 REPS=1 python scripts/ablate_hist.py
Knobs: TILES=0,512,1024,2048  BITS=0,16,8  SP=64  MIXED=1 (half the
features at 8 distinct values — the adaptive-layout shape).

PERF_DB=<path> additionally appends each measured combination to the
shape-keyed performance database (obs/perfdb.py) — the same store the
profile-window close hook and ``bench.py`` write, so the ablation grid
lands in the history ``scripts/perfdb_query.py`` and
``scripts/run_diff.py --perf-db`` read.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

if os.environ.get("INTERPRET"):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.ops import fused_level as fl  # noqa: E402
from lightgbm_tpu.ops.layout import (hist_plane_bytes,  # noqa: E402
                                     packed_feature_layout)
from lightgbm_tpu.ops.quantize import QNCH  # noqa: E402

_TRAJECTORY = os.environ.get(
    "BENCH_TRAJECTORY",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_TRAJECTORY.jsonl"))
_RUN_ID = f"{time.strftime('%Y%m%dT%H%M%S')}_{os.getpid()}_ablate_hist"


def _append(rec):
    rec = dict(rec, metric="ablate_hist", run_id=_RUN_ID,
               ts=round(time.time(), 3))
    try:
        with open(_TRAJECTORY, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    except Exception as e:  # the ablation must never lose a timing
        print(f"trajectory append failed: {e}", file=sys.stderr)
    print(json.dumps(rec), flush=True)


def main():
    R = int(os.environ.get("ROWS", 10_500_000))
    reps = int(os.environ.get("REPS", 5))
    Sp = int(os.environ.get("SP", 64))
    interpret = bool(os.environ.get("INTERPRET"))
    mixed = os.environ.get("MIXED", "1") != "0"
    n_feat = int(os.environ.get("FEATURES", 28))
    max_bin = int(os.environ.get("MAX_BIN", 63))
    tiles = [int(t) for t in os.environ.get("TILES",
                                            "0,512,1024,2048").split(",")]
    bits_list = [int(b) for b in os.environ.get("BITS", "0,16,8").split(",")]

    F_oh, Bp = fl.feature_layout(n_feat, max_bin)
    Rp = ((R + 2047) // 2048) * 2048
    rng = np.random.RandomState(0)
    num_bin = np.full(n_feat, max_bin, np.int32)
    if mixed:
        num_bin[n_feat // 2:] = 9        # 8 distinct values + missing bin
    bins_np = np.stack([rng.randint(0, nb, Rp) for nb in num_bin]) \
        .astype(np.int8)
    Fp = max(F_oh, 8)
    bins_full = np.zeros((Fp, Rp), np.int8)
    bins_full[:n_feat] = bins_np
    leaf_T = jnp.zeros((1, Rp), jnp.int32)
    g = rng.randn(Rp).astype(np.float32)
    h = np.abs(rng.randn(Rp)).astype(np.float32)
    ones = np.ones(Rp, np.float32)

    layouts = [("padded", None)]
    pk = packed_feature_layout(num_bin, max_bin, f_oh=F_oh)
    if pk.fb < F_oh * Bp:
        layouts.append(("packed", pk))

    tbl = (jnp.zeros((Sp, 128), jnp.int32)
           .at[:, 0].set(-2).at[0, 0].set(0).at[0, 2].set(1))
    print(f"rows={R} (padded {Rp}) F_oh={F_oh} Bp={Bp} Sp={Sp} "
          f"packed_fb={pk.fb}", file=sys.stderr)

    for lname, packed in layouts:
        if packed is not None:
            order = np.asarray(packed.feat_order)
            bt = np.zeros((Fp, Rp), np.int8)
            bt[:n_feat] = bins_np[order]
            bins_T = jnp.asarray(bt)
            fb = packed.fb
        else:
            bins_T = jnp.asarray(bins_full)
            fb = F_oh * Bp
        for bits in bits_list:
            if bits:
                gh_T, scales = fl.pack_gh_quant(
                    jnp.asarray(g), jnp.asarray(h), jnp.asarray(ones),
                    bits, np.uint32(1))
                nch = QNCH[bits]
            else:
                gh_T = fl.pack_gh(jnp.asarray(g), jnp.asarray(h),
                                  jnp.asarray(ones), 5)
                nch = 5
            w0 = packed.widths[0] if packed is not None else Bp
            W = jnp.zeros((Sp, fb), jnp.bfloat16).at[0, :w0].set(1)
            for tile in tiles:
                def one(lt):
                    return fl.level_pass(
                        bins_T, lt, gh_T, W, tbl, num_slots=Sp,
                        num_bins=Bp, f_oh=F_oh, nch=nch,
                        tile_rows=tile, interpret=interpret,
                        quant_bits=bits, packed=packed)
                try:
                    hst, nl = one(leaf_T)
                    float(jnp.sum(hst))            # compile + settle
                    t0 = time.perf_counter()
                    lt = leaf_T
                    for _ in range(reps):
                        hst, lt = one(lt)
                    float(jnp.sum(hst))
                    sec = (time.perf_counter() - t0) / reps
                except Exception as e:
                    _append({"layout": lname, "bits": bits, "tile": tile,
                             "error": f"{type(e).__name__}: {e}"[:200]})
                    continue
                eff_tile = tile or fl.default_tile_rows(
                    Sp, F_oh * Bp, nch, wide_bins=Bp > 256)
                _append({
                    "layout": lname, "bits": bits, "tile": tile,
                    "value": round(sec, 6), "unit": "s/pass",
                    "rows": R, "sp": Sp, "fb": fb, "nch": nch,
                    "interpret": interpret,
                    "bytes_per_level": hist_plane_bytes(
                        fb, nch, Sp, Rp, min(eff_tile, Rp), bits),
                    "rows_per_s": round(R / sec, 1),
                })
                if os.environ.get("PERF_DB"):
                    # one measured sample per combination in the
                    # shape-keyed perf database (obs/perfdb.py):
                    # level_pass timing keyed exactly like the
                    # training executables, tile width in the
                    # signature so the grid stays queryable
                    from lightgbm_tpu.obs import perfdb
                    key = perfdb.make_key(
                        f"level_pass[sp={Sp},tile={tile}]",
                        "hist_level",
                        f"r{Rp}.f{n_feat}.b{max_bin}",
                        jax.default_backend(), quant_bits=bits,
                        packed_layout=packed is not None)
                    perfdb.PerfDB(os.environ["PERF_DB"]).append([
                        perfdb.sample(
                            key, dispatches=reps,
                            device_time_us_per_dispatch=sec * 1e6,
                            achieved_bytes_per_s=hist_plane_bytes(
                                fb, nch, Sp, Rp,
                                min(eff_tile, Rp), bits) / sec,
                            source="ablate_hist", run_id=_RUN_ID)])


if __name__ == "__main__":
    main()
