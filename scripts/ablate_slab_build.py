"""Whole-scratch one-hot against slab-wise builds of ``level_pass``, on the
attached chip (PR 31's step 0; replaces ablate_build.py, whose kernels had
the table form's shape).

In the bins form the histogram dot is the one-hot's only reader, so the
one-hot need not exist whole. This times, at one cell's shape and with the
same random numerical splits, ``level_pass`` of this checkout, of a PARENT
checkout (the whole ``[FB, C]`` scratch; also at larger tiles under a
raised ``vmem_limit_bytes``, to tell the tile's share of a gain from the
overlap's), and slab-wise kernels over two axes:

  fb<rows>.<loop>  FB-row slabs: <rows> one-hot rows (rows/B features) x
                   the whole row tile, multiplied at once into the slab's
                   own rows of the accumulator. <loop>: ``unroll`` (static
                   Python loop), ``pipe`` (the same, slab i + 1 built in
                   the source before slab i is multiplied), ``fori1`` /
                   ``fori2`` (lax.fori_loop over aligned slabs of the
                   converted bins, 1 / 2 slabs an iteration, and a static
                   tail);
                   ``.f32`` builds the slab in float32 and narrows once.
  col<cols>        column slabs: all FB rows x <cols> of the tile's rows,
                   partial products added into the accumulator per slab.

Each line says ms per launch (median of REPS, ``block_until_ready`` at
both ends), whether the histogram is bit-equal to the reference launch's
(the parent's ``level_pass`` at its own tile, else this checkout's), and
the largest difference from a float64 sum of the same bf16 channel values
relative to the largest sum.

Run: ROWS=28000000 FEATURES=28 SLOTS=8,64 TILES=512,1024,2048 \
       PARENT=.chip_scratch/parent python scripts/ablate_slab_build.py
     ROWS=6810888 FEATURES=137 SLOTS=8,16 TILES=128,256,512,1024,2048 ...
VARIANTS=fb512.fori1,col256 keeps some; INTERPRET=1 rehearses on the CPU
at a tiny ROWS; COMPILE_ONLY=1 compiles every variant for a described v5e
(no chip, no timing: what the compiler refuses, how long it takes and
the scoped VMEM the kernel asks for).
One JSON line per timing on stdout, all of them in
chiprun_out/ablate_slab_build/<FEATURES>.jsonl.
"""
import functools
import importlib
import json
import os
import statistics
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import fused_level as fl

from ablate_route_form import _splits

NCH = fl.NCH_PRECISE
DEFAULT_VARIANTS = ("fb512.unroll,fb512.pipe,fb512.fori1,fb512.fori2,"
                    "fb256.fori1,fb1024.fori1,fb1024.unroll,fb2048.fori1,"
                    "fb512.unroll.f32,fb512.fori1.f32,col128,col256,col512")


def _parent_module(path):
    """ops/fused_level.py of another checkout, beside this checkout's: its
    ``ops`` directory loaded as a package of its own (the kernels' module
    imports only its siblings layout.py and quantize.py)."""
    if not path:
        return None
    pkg = types.ModuleType("parent_ops")
    pkg.__path__ = [os.path.join(os.path.abspath(path), "lightgbm_tpu", "ops")]
    sys.modules["parent_ops"] = pkg
    return importlib.import_module("parent_ops.fused_level")


def _onehot_f32(rows, w):
    k, C = rows.shape
    iota_b = (jax.lax.broadcasted_iota(jnp.int32, (k * w, C), 0) % w) \
        .astype(jnp.float32)
    big = jnp.repeat(rows.astype(jnp.float32), w, axis=0)
    return jnp.maximum(1.0 - jnp.abs(big - iota_b), 0.0).astype(jnp.bfloat16)


def _slab_kernel(bins_ref, leaf_ref, gh_ref, tbl_ref, hist_ref, newleaf_ref,
                 *scratch, B, F_oh, axis, slab, loop, f32):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    Fp, C = bins_ref.shape
    left_i = fl._left_from_bins(bins_ref, tbl_ref)
    newleaf_ref[:], ghs = fl._small_child_channels(
        leaf_ref[:], left_i, tbl_ref, gh_ref, NCH, False)
    onehot = ((lambda rows: _onehot_f32(rows, B)) if f32
              else (lambda rows: fl._onehot_slab(rows, B, False)))
    dot = lambda oh, rhs: jax.lax.dot_general(
        oh, rhs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if axis == "col":
        for c0 in range(0, C, slab):
            hist_ref[:] += dot(onehot(bins_ref[:F_oh, c0:c0 + slab]),
                               ghs[:, c0:c0 + slab])
        return
    k = slab // B                       # features of one slab
    if loop in ("unroll", "pipe"):
        binsv = bins_ref[:].astype(jnp.float32)
        cuts = [(f0, min(k, F_oh - f0)) for f0 in range(0, F_oh, k)]
        if loop == "unroll":
            for f0, kk in cuts:
                hist_ref[f0 * B:(f0 + kk) * B] += dot(
                    onehot(binsv[f0:f0 + kk]), ghs)
            return
        nxt = onehot(binsv[:cuts[0][1]])
        for i, (f0, kk) in enumerate(cuts):
            oh = nxt
            if i + 1 < len(cuts):
                g0, gk = cuts[i + 1]
                nxt = onehot(binsv[g0:g0 + gk])
            hist_ref[f0 * B:(f0 + kk) * B] += dot(oh, ghs)
        return
    binsf_ref, ghs_ref = scratch
    binsf_ref[:Fp] = bins_ref[:].astype(jnp.float32)
    ghs_ref[:] = ghs

    per = int(loop[4:])                 # slabs of one iteration
    n_iter = F_oh // (k * per)

    def body(s, carry):
        f0s = [pl.multiple_of((s * per + j) * k, k) for j in range(per)]
        ohs = [onehot(binsf_ref[pl.ds(f0, k), :]) for f0 in f0s]
        for f0, oh in zip(f0s, ohs):
            r0 = pl.multiple_of(f0 * B, k * B)
            hist_ref[pl.ds(r0, k * B), :] += dot(oh, ghs_ref[:])
        return carry
    jax.lax.fori_loop(0, n_iter, body, 0)
    for f0 in range(n_iter * per * k, F_oh, k):         # the static tail
        f1 = min(f0 + k, F_oh)
        hist_ref[f0 * B:f1 * B] += dot(onehot(binsf_ref[f0:f1]), ghs_ref[:])


def _call(kernel, scratch, Sp, FB, C, vmem_limit, interpret):
    """The pallas_call of level_pass's bins form around ``kernel``."""
    def run(bins_T, leaf_T, gh_T, tbl):
        Fp, R = bins_T.shape
        row = lambda rows: pl.BlockSpec((rows, C), lambda t: (0, t))
        params = dict(dimension_semantics=("arbitrary",))
        if vmem_limit:
            params["vmem_limit_bytes"] = vmem_limit
        return pl.pallas_call(
            kernel, grid=(R // C,),
            in_specs=[row(Fp), row(1), row(8),
                      pl.BlockSpec((Sp, 128), lambda t: (0, 0))],
            out_specs=[pl.BlockSpec((FB, NCH * Sp), lambda t: (0, 0)),
                       row(1)],
            out_shape=[jax.ShapeDtypeStruct((FB, NCH * Sp), jnp.float32),
                       jax.ShapeDtypeStruct((1, R), jnp.int32)],
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(**params),
            interpret=interpret)(bins_T, leaf_T, gh_T, tbl)
    return jax.jit(run)


def _variant(name, Sp, F_oh, B, Fp, C, vmem_limit, interpret):
    parts = name.split(".")
    f32 = parts[-1] == "f32"
    if parts[0].startswith("col"):
        axis, slab, loop = "col", int(parts[0][3:]), ""
        if slab >= C or slab * 4 < C:
            return None
    else:
        axis, slab, loop = "fb", int(parts[0][2:]), parts[1]
        if slab < B or slab >= F_oh * B:
            return None
    scratch = []
    if loop.startswith("fori"):
        scratch = [pltpu.VMEM((fl._round_up(Fp, 8), C), jnp.float32),
                   pltpu.VMEM((NCH * Sp, C), jnp.bfloat16)]
    kernel = functools.partial(_slab_kernel, B=B, F_oh=F_oh, axis=axis,
                               slab=slab, loop=loop, f32=f32)
    return _call(kernel, scratch, Sp, F_oh * B, C, vmem_limit, interpret)


def _parent_at(parent, Sp, F_oh, B, C, vmem_limit, interpret):
    """The PARENT's kernel (whole [FB, C] scratch) at tile C."""
    kernel = functools.partial(parent._level_kernel, B=B, F_oh=F_oh, Sp=Sp,
                               nch=NCH, has_w=False)
    return _call(kernel, [pltpu.VMEM((F_oh * B, C), jnp.bfloat16)], Sp,
                 F_oh * B, C, vmem_limit, interpret)


def _float64_sum(bins_np, leaf_np, g_np, tbl_b, Sp, F, B, sets=None):
    """[F*B, Sp] float64 sums of the g channel (hi + lo, the values the
    kernels multiply) over each slot's smaller child; ``sets``: the
    categorical slots' flag and bin sets (ablate_route_form._bin_sets)."""
    t = np.asarray(tbl_b)
    on = leaf_np >= 0
    k = np.where(on, leaf_np, 0)
    active = on & (t[k, fl.TBL_LEAF] == k)
    v = bins_np[np.maximum(t[k, fl.TBL_FEATURE_ROW], 0),
                np.arange(leaf_np.size)].astype(np.int32)
    left = np.where(v == t[k, fl.TBL_MISSING_BIN],
                    t[k, fl.TBL_DEFAULT_LEFT] > 0, v <= t[k, fl.TBL_THRESHOLD])
    if sets:
        flag = np.asarray(sets["cat_flag"])
        left = np.where(flag[k], np.asarray(sets["cat_mask"])[k, v], left)
    left &= t[k, fl.TBL_FEATURE_ROW] >= 0
    keep = np.nonzero(active & (left == (t[k, fl.TBL_SMALL_LEFT] > 0)))[0]
    key0 = k[keep] * B
    w = g_np[keep]
    out = np.zeros((F * B, Sp))
    for f in range(F):
        out[f * B:(f + 1) * B] = np.bincount(
            key0 + bins_np[f, keep], weights=w,
            minlength=Sp * B).reshape(Sp, B).T
    return out


def _time(fn, reps):
    out = jax.block_until_ready(fn())
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ms.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ms), out


def _compile_only(fn, shapes):
    """Compile for a described v5e: what the compiler refuses, how long
    it takes, and the scoped VMEM the kernel it accepts asks for."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.utils.platform import scoped_vmem_bytes
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
            for a in shapes]
    t0 = time.perf_counter()
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    except Exception as e:  # what the chip's compiler would refuse
        return dict(compiled=False, error=str(e).splitlines()[-1][:300])
    return dict(compiled=True, compile_s=time.perf_counter() - t0,
                scoped_vmem_bytes=scoped_vmem_bytes(compiled))


def main():
    env = os.environ.get
    R = int(env("ROWS", 28_000_000))
    F = int(env("FEATURES", 28))
    max_bin = int(env("MAX_BIN", 63))
    slots = [int(s) for s in env("SLOTS", "8,64").split(",")]
    tiles = [int(t) for t in env("TILES", "512,1024,2048").split(",")]
    variants = env("VARIANTS", DEFAULT_VARIANTS).split(",")
    reps = int(env("REPS", 5))
    interpret = bool(int(env("INTERPRET", "0")))
    compile_only = bool(int(env("COMPILE_ONLY", "0")))
    vmem_limit = int(env("VMEM_LIMIT", 100 * 1024 * 1024))  # parent.vmem's
    slab_vmem = int(env("SLAB_VMEM_LIMIT", 0))       # 0: the default 16 MB
    parent = _parent_module(env("PARENT", ""))
    F_oh, B = fl.feature_layout(F, max_bin)
    FB = F_oh * B
    Fp = max(F_oh, 8)
    Rp = -(-R // 2048) * 2048
    if compile_only:
        Rp = min(Rp, 65_536)
    rng = np.random.RandomState(0)
    bins_np = np.zeros((Fp, Rp), np.int8)
    bins_np[:F] = rng.randint(0, max_bin, size=(F, Rp), dtype=np.int8)
    bins_T = jnp.asarray(bins_np)
    g = jnp.asarray(rng.randn(Rp).astype(np.float32))
    ones = jnp.ones((Rp,), jnp.float32)
    gh_T = fl.pack_gh(g, ones, ones, NCH)
    g_np = (np.asarray(gh_T[0].astype(jnp.float32), np.float64)
            + np.asarray(gh_T[1].astype(jnp.float32), np.float64))
    out_dir = os.path.join("chiprun_out", "ablate_slab_build")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, f"{F}.jsonl"), "a")
    device = jax.devices()[0].device_kind

    def say(**rec):
        rec.update(rows=R, features=F, fb=FB, device=device)
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    for Sp in slots:
        tbl, sp_args = _splits(rng, Sp, F, max_bin, F_oh)
        tbl_b = fl.route_table_columns(tbl, *sp_args)
        leaf_np = np.where(np.arange(Rp) < R, rng.randint(0, Sp, Rp),
                           -1).astype(np.int32)
        leaf_T = jnp.asarray(leaf_np)[None, :]
        ops = (bins_T, leaf_T, gh_T, tbl_b)
        kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, interpret=interpret)
        ref64 = None if compile_only else _float64_sum(
            bins_np, leaf_np, g_np, tbl_b, Sp, F_oh, B)
        ref = {}

        def measure(name, tile, fn):
            if fn is None:
                return
            if compile_only:
                say(kernel=name, slots=Sp, tile=tile,
                    **_compile_only(fn, ops))
                return
            try:
                ms, (hist, leaf) = _time(lambda: fn(*ops), reps)
            except Exception as e:
                say(kernel=name, slots=Sp, tile=tile,
                    error=str(e).splitlines()[-1][:300])
                return
            hist = np.asarray(hist)
            ref.setdefault("hist", hist)
            ref.setdefault("leaf", np.asarray(leaf))
            g_sum = (hist[:, :Sp].astype(np.float64)
                     + hist[:, Sp:2 * Sp].astype(np.float64))
            say(kernel=name, slots=Sp, tile=tile, ms=ms,
                hist_bit_equal=bool(np.array_equal(hist, ref["hist"])),
                same_leaves=bool(np.array_equal(np.asarray(leaf),
                                                ref["leaf"])),
                max_rel_diff_f64=float(np.abs(g_sum - ref64).max()
                                       / np.abs(ref64).max()))

        own_tile = fl.level_build(True, Sp, FB, NCH, Fp)["tile_rows"]
        if parent is not None:
            p_tile = parent.default_tile_rows(Sp, FB, NCH)
            measure("parent.level_pass", p_tile, lambda *a: parent.level_pass(
                a[0], a[1], a[2], None, a[3], **kw))
        measure("level_pass", own_tile, lambda *a: fl.level_pass(
            a[0], a[1], a[2], None, a[3], **kw))
        for C in tiles:
            if Rp % C:
                continue
            if parent is not None:
                measure("parent.vmem", C, _parent_at(
                    parent, Sp, F_oh, B, C, vmem_limit, interpret))
            for name in variants:
                measure(name, C, _variant(name, Sp, F_oh, B, Fp, C,
                                          slab_vmem, interpret))


if __name__ == "__main__":
    main()
