"""Which operand of ``level_pass``'s histogram dot the MXU latches, on the
attached chip (PR 36's step 0; on ablate_slab_build.py's harness).

The bins form multiplies each slab of the one-hot with the masked
channels ``ghs [nch*Sp, C]``. ``onehot`` is the order the kernel had
until PR 36, ``dot_general(oh [512, C], ghs)``: the one-hot's rows are
STREAMED through latched [128, 128] tiles of ``ghs``, so the channel axis
pads to whole 128-column N-tiles (160 columns pay for 256, 320 for 384).
``channels`` is the other order, ``dot_general(ghs, oh)`` into a
transposed accumulator ``[nch*Sp, FB]``: the one-hot's tiles are latched
and the nch*Sp channel rows streamed, which pads to 8 sublanes. By the
pass model the issue started from, the first costs ceil(nch*Sp / 128)
units of 65.2 ms (28M rows, FB 1,792) and the second max(1, nch*Sp /
128); what this script read on a v5e (PERF.md section 6, PR 36) is 0.82 /
0.90 / 1.29 / 2.62 units at 8 / 16 / 32 / 64 slots for the second, the
same bits, so ``level_build`` wires it at every slot count.

This times one launch at each of the cells' shapes with the same random
splits in both orders, over three more axes:

  <order>.<spelling>.s<slab>   order ``onehot`` / ``channels``; spelling
      ``dg`` (``dot_general`` contracting the row axis of both operands)
      or ``T`` (``a @ b.T``); slab = one-hot rows built and multiplied at
      once (the ``channels`` dot's N)
  TILES                        the row tile (the dot's K)

and ``level_pass`` of this checkout as wired (``level_build`` chooses),
and of a PARENT checkout where one is given. Each line says ms per launch
(median of REPS, ``block_until_ready`` at both ends), whether the
histogram is bit-equal to the reference launch's (the parent's
``level_pass``, else ``onehot.dg.s512`` at 2,048 rows: the kernel as it
was), whether the leaves are, and the largest difference from a float64
sum of the same bf16 channel values relative to the largest sum.

Run (the cells' four shapes, one process, 5 minutes of one v5e chip):
    PARENT=.chip_scratch/parent python scripts/ablate_dot_order.py
SHAPES=higgs,cat,goss,rank,cap128 chooses; SLOTS=32,64 overrides a shape's
slot counts; VARIANTS=channels.dg.s512 keeps some; INTERPRET=1 ROWS=4096
rehearses on the CPU; COMPILE_ONLY=1 compiles every variant for a
described v5e and prints the compiled scoped-VMEM bytes (no chip, no
timing). One JSON line per timing on stdout, all of them in
chiprun_out/ablate_dot_order/<shape>.jsonl.
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

from ablate_route_form import _bin_sets, _splits
from ablate_slab_build import (_compile_only, _float64_sum, _parent_module,
                               _time)

NCH = fl.NCH_PRECISE
# name -> rows, features, max_bin, categorical columns, slot counts: the
# five cells' level passes (Higgs's and the four-chip cell's a quarter of
# it; the categorical cell's; the GOSS cell's compact matrix; the ranking
# cell's, capped at 16 slots: the control), and ``cap128``, no cell's: the
# widest pass there is (FB 1,024 leaves 128 slots: 640 columns, a whole
# number of N-tiles, where the one-hot streamed pads nothing)
SHAPES = {
    "higgs": (28_000_000, 28, 63, 0, (8, 16, 32, 64)),
    "cat": (28_000_000, 8, 255, 6, (32, 64)),
    "goss": (8_400_896, 28, 63, 0, (64,)),
    "rank": (6_810_888, 137, 63, 0, (16,)),
    "cap128": (28_000_000, 16, 63, 0, (128,)),
}
DEFAULT_VARIANTS = ("onehot.dg.s512,channels.dg.s512,channels.T.s512,"
                    "channels.dg.s1024,onehot.T.s512")


def _order_kernel(bins_ref, leaf_ref, gh_ref, tbl_ref, hist_ref, newleaf_ref,
                  *, B, F_oh, order, spelling, slab, has_cat):
    """_level_kernel's bins form with the dot's order, spelling and slab
    as arguments."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    left_i = fl._left_from_bins(bins_ref, tbl_ref, has_cat)
    newleaf_ref[:], ghs = fl._small_child_channels(
        leaf_ref[:], left_i, tbl_ref, gh_ref, NCH, False)
    if spelling == "dg":
        dot = lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        dot = lambda a, b: jnp.dot(a, b.T,
                                   preferred_element_type=jnp.float32)
    binsv = bins_ref[:].astype(jnp.float32)
    k = max(1, slab // B)
    for f0 in range(0, F_oh, k):
        f1 = min(f0 + k, F_oh)
        oh = fl._onehot_slab(binsv[f0:f1], B, False)
        if order == "channels":
            hist_ref[:, f0 * B:f1 * B] += dot(ghs, oh)
        else:
            hist_ref[f0 * B:f1 * B] += dot(oh, ghs)


def _variant(name, Sp, F_oh, B, C, has_cat, interpret):
    """jitted (bins_T, leaf_T, gh_T, tbl) -> ([FB, nch*Sp] hist, leaves)
    of one variant at row tile C."""
    order, spelling, slab = name.split(".")
    FB = F_oh * B
    acc = (NCH * Sp, FB) if order == "channels" else (FB, NCH * Sp)
    kernel = functools.partial(_order_kernel, B=B, F_oh=F_oh, order=order,
                               spelling=spelling, slab=int(slab[1:]),
                               has_cat=has_cat)

    def run(bins_T, leaf_T, gh_T, tbl):
        Fp, R = bins_T.shape
        row = lambda rows: pl.BlockSpec((rows, C), lambda t: (0, t))
        hist, leaf = pl.pallas_call(
            kernel, grid=(R // C,),
            in_specs=[row(Fp), row(1), row(8),
                      pl.BlockSpec((Sp, 128), lambda t: (0, 0))],
            out_specs=[pl.BlockSpec(acc, lambda t: (0, 0)), row(1)],
            out_shape=[jax.ShapeDtypeStruct(acc, jnp.float32),
                       jax.ShapeDtypeStruct((1, R), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret)(bins_T, leaf_T, gh_T, tbl)
        return (hist.T if order == "channels" else hist), leaf
    return jax.jit(run)


def main():
    env = os.environ.get
    names = env("SHAPES", "higgs,cat,goss,rank").split(",")
    variants = env("VARIANTS", DEFAULT_VARIANTS).split(",")
    tiles = [int(t) for t in env("TILES", "2048,1024").split(",")]
    reps = int(env("REPS", 5))
    interpret = bool(int(env("INTERPRET", "0")))
    compile_only = bool(int(env("COMPILE_ONLY", "0")))
    parent = _parent_module(env("PARENT", ""))
    out_dir = os.path.join("chiprun_out", "ablate_dot_order")
    os.makedirs(out_dir, exist_ok=True)
    device = jax.devices()[0].device_kind
    for shape in names:
        R, F, max_bin, cat_columns, slots = SHAPES[shape]
        R = int(env("ROWS", R))
        if env("SLOTS"):
            slots = [int(s) for s in env("SLOTS").split(",")]
        F_oh, B = fl.feature_layout(F, max_bin)
        FB, Fp = F_oh * B, max(F_oh, 8)
        Rp = -(-R // 2048) * 2048
        if compile_only:
            Rp = min(Rp, 65_536)
        rng = np.random.RandomState(0)
        bins_dt = np.int8 if B <= 128 else np.int16
        bins_np = np.zeros((Fp, Rp), bins_dt)
        bins_np[:F] = rng.randint(0, max_bin, size=(F, Rp), dtype=bins_dt)
        bins_T = jnp.asarray(bins_np)
        g = jnp.asarray(rng.randn(Rp).astype(np.float32))
        ones = jnp.ones((Rp,), jnp.float32)
        gh_T = fl.pack_gh(g, ones, ones, NCH)
        g_np = (np.asarray(gh_T[0].astype(jnp.float32), np.float64)
                + np.asarray(gh_T[1].astype(jnp.float32), np.float64))
        sink = open(os.path.join(out_dir, f"{shape}.jsonl"), "a")

        def say(**rec):
            rec.update(shape=shape, rows=R, features=F, fb=FB, device=device)
            line = json.dumps(rec)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()

        for Sp in slots:
            tbl, sp_args = _splits(rng, Sp, F, max_bin, F_oh)
            sets = _bin_sets(rng, sp_args[0], cat_columns, max_bin, B) \
                if cat_columns else {}
            has_cat = bool(sets)
            tbl_b = fl.route_table_columns(tbl, *sp_args, **sets)
            leaf_np = np.where(np.arange(Rp) < R, rng.randint(0, Sp, Rp),
                               -1).astype(np.int32)
            ops = (bins_T, jnp.asarray(leaf_np)[None, :], gh_T, tbl_b)
            kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, has_cat=has_cat,
                      interpret=interpret)
            ref64 = None if compile_only else _float64_sum(
                bins_np, leaf_np, g_np, tbl_b, Sp, F_oh, B, sets)
            ref = {}

            def measure(name, tile, fn, **more):
                rec = dict(kernel=name, slots=Sp, columns=NCH * Sp,
                           tile=tile, **more)
                if compile_only:
                    say(**rec, **_compile_only(fn, ops))
                    return
                try:
                    ms, (hist, leaf) = _time(lambda: fn(*ops), reps)
                except Exception as e:
                    say(**rec, error=str(e).splitlines()[-1][:300])
                    return
                hist = np.asarray(hist)
                ref.setdefault("hist", hist)
                ref.setdefault("leaf", np.asarray(leaf))
                g_sum = (hist[:, :Sp].astype(np.float64)
                         + hist[:, Sp:2 * Sp].astype(np.float64))
                say(**rec, ms=ms,
                    hist_bit_equal=bool(np.array_equal(hist, ref["hist"])),
                    same_leaves=bool(np.array_equal(np.asarray(leaf),
                                                    ref["leaf"])),
                    max_rel_diff_f64=float(np.abs(g_sum - ref64).max()
                                           / np.abs(ref64).max()))

            build = fl.level_build(True, Sp, FB, NCH, Fp, has_cat=has_cat)
            if parent is not None:
                p_tile = parent.level_build(True, Sp, FB, NCH, Fp,
                                            has_cat=has_cat)["tile_rows"]
                measure("parent.level_pass", p_tile,
                        lambda *a: parent.level_pass(a[0], a[1], a[2], None,
                                                     a[3], **kw))
            for C in tiles:
                if Rp % C:
                    continue
                for name in variants:
                    if C != tiles[0] and name.split(".")[1:] != ["dg", "s512"]:
                        continue        # the tile axis: the wired spelling
                    measure(name, C, _variant(name, Sp, F_oh, B, C, has_cat,
                                              interpret))
            measure("level_pass", build["tile_rows"],
                    lambda *a: fl.level_pass(a[0], a[1], a[2], None, a[3],
                                             **kw), wired_dot=build["dot"])
        del bins_T, gh_T, ops


if __name__ == "__main__":
    main()
