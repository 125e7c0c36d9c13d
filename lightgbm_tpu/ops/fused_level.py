"""Fused per-level Pallas kernel: route + histogram in ONE pass over rows.

This is the hot path of the fused engine. It replaces the reference's
hottest loops (ref: src/io/dense_bin.hpp
ConstructHistogram, src/treelearner/serial_tree_learner.cpp:355-453,
ocl/histogram256.cl) with a single streaming kernel per tree level.

Design (timings: TPU v5e, PERF.md section 6, step 0 of PR 28 and PR 31):

- Layout is TRANSPOSED: rows ride the 128-wide lane dimension,
  features/bins/slots ride sublanes. The bin one-hot build then uses only
  native sublane broadcasts (no per-feature lane broadcast / int8 sublane
  extraction, which cost 2-3x in a row-major kernel).
- The one-hot ``oh[f*B+b, r] = (bins[f, r] == b)`` feeds the histogram
  dot against the masked channels ``ghs [nch*S, C]``, whose order
  ``level_build`` chooses (timings: PR 36's step 0, PERF.md section 6):
    * ``channels``, the bf16 passes of the bins form whose nch*S columns
      are not a whole number of 128 (every pass of at most 64 slots):
      ``hist^T += ghs @ oh^T -> [nch*S, FB]``. The MXU LATCHES the one-hot's [128, 128]
      tiles and streams the nch*S channel rows through each, into a
      TRANSPOSED accumulator whose lanes are the (feature, bin) pairs
      (``level_pass`` hands back [FB, nch*S] all the same). The streamed
      axis pads to 8 sublanes, so a pass pays for the columns it has: a
      latched tile costs about 105-115 cycles while it streams at most
      ~100 rows and the rows it streams above that. In units of the
      other order's N-tile (65.2 ms over 28M rows x FB 1,792): 0.82 /
      0.90 / 1.29 / 2.62 at 8 / 16 / 32 / 64 slots of five channels.
    * ``onehot``, the table form and the int8 paths (neither timed the
      other way) and a pass of 128 slots (640 columns pad nothing: 206.3
      ms against 211.8): ``hist += oh @ ghs^T -> [FB, nch*S]``. The MXU streams
      the FB one-hot rows through one latched [128, 128] tile of ``ghs``
      per 128 data rows and N-tile, so the channel axis pads to whole
      N-tiles of 128 columns: ceil(nch*S / 128) units, 1 / 1 / 2 / 3 at
      the same slot counts (160 columns pay for 256, 320 for 384). Until
      PR 36 every pass ran this order: 74.1 / 75.2 / 142.2 / 210.3 ms
      where ``channels`` takes 62.6 / 67.5 / 93.2 / 180.0, bit for bit
      the same histogram (both sum a tile's products in the same 128-deep
      chunks).
  The one-hot is built (``_onehot_slab``) per row tile of ``level_pass``,
  in one of two ways that follow from the routing form below:
    * in SLABS, the bins form: ``ghs`` is known before any one-hot
      element is, and each FB-row block of the one-hot has one reader,
      its own rows of the accumulator. So SLAB_ROWS = 512 one-hot rows (8
      features of 64 bins) are built and multiplied at once into
      ``hist[slab rows]``, slab after slab in one basic block: there is
      no [FB, C] scratch, the row tile does not shrink with FB (2,048
      rows at every width a cell runs) and the VPU build of one slab
      runs under the MXU's pass over its neighbours. In the ``onehot``
      order, 28M x 28 x 64 bins: 74.0 ms at 8 slots, 210.1 at 64 (the
      whole-scratch build: 96.3 / 247.6 at 1,024-row tiles); 6.81M x 137
      x 64 at 16 slots: 81.8 ms (71.9 in the ``channels`` order) against
      183.6 with the scratch, which left 128-row tiles: 53,216 grid
      steps, each rewriting the 2.8 MB accumulator. A ``lax.fori_loop``
      over the slabs is 4-10 % slower
      (the loop's back-edge is a barrier to that overlap) and column
      slabs (all FB rows x 512 of the tile's rows) 1-6 %;
    * WHOLE, into an [FB, C] VMEM scratch (``_write_onehot``), the table
      form: ``D = W @ oh`` must be complete before ``ghs`` exists, so the
      one-hot is read twice and the tile is what the scratch leaves.
- ROUTING (which rows of slot k's leaf go left) has two forms, chosen
  once per grower from what is static about the job
  (models/frontier2.route_form):
    * BINS form, every dense job of at most 256 bins a column: a split
      reads ONE stored value, so slot k's feature row is picked out of
      the [Fp, C] bin tile with a K = Fp dot (``sel[Sp, Fp] @ bins``,
      exact: one non-zero term, values <= 255) and compared with the
      threshold and missing bin the slot table carries
      (``route_table_columns``, ``_left_from_bins``). A categorical
      split is MEMBERSHIP of that value in a bin set: the slot table
      carries the set as 256 bits (eight int32 words) beside the slot's
      categorical flag, and the kernel takes bit ``v & 31`` of word
      ``v >> 5`` (eight compare-and-selects, one per-lane shift; VPU
      work on [Sp, C] int32 planes before ``ghs`` exists, so nothing of
      it hides under the MXU). That code is traced only when the job has
      a categorical column (the grower's static ``has_cat``): every other
      job's kernels lower as they did before it existed. ``route_pass``
      in this form builds no one-hot and has no FB-sized scratch: 5.3 ms
      over 28M rows x 28 features at 64 slots, 2.4 ms over 6.8M x 137.
      A job stored as EFB bundle columns (the grower's static
      ``bundled``) reads a bundle value, which DECODES to the split
      feature's bin by its window (ops/efb.py): the slot table carries
      the feature's bundle column as its row, its window's first value
      and width and its most-frequent bin, and the kernel turns the
      picked value ``v`` into ``v - offset`` inside the window and the
      most-frequent bin outside it (the rows that are default in the
      feature, and the rows another member of the bundle wrote first)
      before the same compares. Three more [Sp, C] VPU planes, traced
      only for a bundled job, under the ``bundle_decode`` scope.
    * TABLE form: ``D = W @ oh -> [S, C]`` with W [S, FB] encoding the
      level's left-going bins per slot (``build_route_table*``). It
      contracts over K = FB to learn one bit per row and slot, latching
      FB/128 x C/128 one-hot tiles and streaming only S <= 128 rows
      through each: 58-70 ms of every ``level_pass`` and, with the
      one-hot build it needs (28-60 ms), all 103 / 131 ms of a
      ``route_pass`` at the two widths above. Kept where a stored value
      can pass 255 (bins over 255, EFB bundle columns of over 256
      bins), with or without categorical columns
      (``build_route_table*``'s ``cat_mask``).
      ``level_pass`` / ``route_pass`` take the form from their ``W``
      argument (None = bins form).
- All gh channels are packed into ONE dot operand of nch*S rows.
- Channels (``nch=5``, default): g_hi, g_lo, h_hi, h_lo, w — grad/hess are
  split into two bfloat16 halves (hi + exact residual) so the accumulated
  histogram carries ~fp32 input precision, matching the reference GPU
  precision contract (ref: docs/GPU-Performance.rst:130-160) instead of
  raw-bf16 rounding. ``nch=3`` (g, h, w single-bf16) is the fast
  mode.
- The grid is sequential on a TPU core, so the [FB, nch*S] (or
  transposed) output block accumulates across row tiles race-free; the
  updated row->leaf vector is emitted per-tile alongside.
- The ROOT pass needs no special kernel: slot 0 holds leaf 0, sends every
  row "left" (``root_route_tables``) and is its own smaller child, so it
  collects the full-data histogram.

The smaller child of each split is histogrammed (caller puts the smaller
side in the slot tables); the sibling is reconstructed outside by
subtraction (ref: serial_tree_learner.cpp:423-425).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import PackedLayout, feature_layout  # noqa: F401  (shared
# single-source layout contract — re-exported for existing callers)
from . import quantize

NCH_PRECISE = 5   # g_hi, g_lo, h_hi, h_lo, w
NCH_FAST = 3      # g, h, w

# columns of the per-level slot table ``tbl`` [Sp, 128] int32. 0-2 are read
# by both routing forms; 3-6 carry the split itself in the bins form
# (route_table_columns) and stay zero in the table form; 7-15 carry a
# categorical split's left-going bin SET in the bins form of a job with a
# categorical column: the slot's categorical flag, then bins 0-255 as the
# bits of eight int32 words (bin b is bit b & 31 of word b >> 5); 16-18
# carry the split feature's bundle window in the bins form of a job stored
# as EFB bundle columns: its first value, its width (the feature's bins)
# and the feature's most-frequent bin.
TBL_LEAF, TBL_RIGHT_DELTA, TBL_SMALL_LEFT = 0, 1, 2
TBL_THRESHOLD, TBL_MISSING_BIN, TBL_DEFAULT_LEFT, TBL_FEATURE_ROW = 3, 4, 5, 6
TBL_CAT_FLAG, TBL_CAT_WORD0, CAT_WORDS = 7, 8, 8
TBL_WINDOW_OFFSET, TBL_WINDOW_WIDTH, TBL_WINDOW_MFB = 16, 17, 18


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


VMEM_BUDGET = 15 * 1024 * 1024  # scoped-vmem stack limit is 16 MB; leave
# headroom for W/ghs/D values and the pipeline's operand double buffers

SLAB_ROWS = 512   # one-hot rows of one slab of the bins form's build

# what the bins form's set-membership test (_left_from_bins, jobs with a
# categorical column) keeps per row and slot: the integer bin value, the
# chosen word of the set and the shifted bit, int32 each
CAT_PLANE_BYTES = 12
# what the bins form's window decode (_left_from_bins, jobs stored as EFB
# bundle columns) keeps per row and slot: the value less the window's
# offset, the in-window 0/1 and the decoded bin, four bytes each
DECODE_PLANE_BYTES = 12


def _plane_bytes(has_cat: bool, bundled: bool) -> int:
    """Bytes a row and slot of the bins form's routing planes take."""
    return 16 + CAT_PLANE_BYTES * has_cat + DECODE_PLANE_BYTES * bundled


def slab_row_bytes(Sp: int, nch: int, bins_rows: int,
                   has_cat: bool = False, bundled: bool = False) -> int:
    """Scoped-VMEM bytes the bins form's ``level_pass`` is charged per row
    of its tile (default_tile_rows says what for)."""
    return (SLAB_ROWS * 6 + bins_rows * 6
            + Sp * (2 * nch + _plane_bytes(has_cat, bundled)))


def default_tile_rows(Sp: int, FB: int, nch: int,
                      wide_bins: bool = False, bins_rows: int = 0,
                      has_cat: bool = False, bundled: bool = False) -> int:
    """Row-tile width of ``level_pass``: a power of two from 128 to 2,048
    (``_init_fused`` aligns the rows to 2,048 a shard), from the PADDED
    layout's shapes and the form alone, so an adaptive-bins job takes its
    padded twin's tile.

    BINS form (``bins_rows`` = the bin tile's Fp > 0; PR 31): there is no
    [FB, C] scratch. What the kernel keeps on the scoped-VMEM stack is
    charged per row of the tile: one slab of the one-hot with its two
    build intermediates (SLAB_ROWS x 6 B), the [nch*Sp, C] ``ghs``
    (2 B), the [Sp, C] int32 routing planes (16 B a slot, as
    route_tile_rows charges them) and the converted bin tile (4 B + the
    routing dot's bf16 copy); a job with a categorical column
    (``has_cat``) is charged the membership test's [Sp, C] int32 planes
    too (CAT_PLANE_BYTES a slot), a job stored as bundle columns
    (``bundled``) the window decode's (DECODE_PLANE_BYTES). That is
    2,048 rows up to Fp ~700 at 16 slots or 128 slots at Fp 28. The charge is CONSERVATIVE, and the
    same in both orders of the histogram dot (the same operands, the
    same bytes): compiled for a described v5e at Higgs's width and
    2,048-row tiles the kernel needs 1.13 / 1.61 / 2.39 / 4.31 MB at 8 /
    16 / 32 / 64 slots with the channels streamed (1.22 / 1.48 / 3.37 /
    5.05 with the one-hot streamed; the compiler fuses the build's
    intermediates), and the [FB, nch*Sp] accumulator is the
    pipeline's output window, not part of that stack (Epsilon's 20.5 MB
    accumulator compiles under the 16 MB limit). On a v5e (PR 31's step
    0) a pass over 6.81M x 137 x 64 bins at 16 slots took 105.1 / 92.3 /
    86.4 / 83.8 / 81.8 ms at 128 / 256 / 512 / 1,024 / 2,048 rows, over
    28M x 28 x 64 at 8 slots 88.7 / 80.8 / 74.0 at 512 / 1,024 / 2,048.

    TABLE form: the [FB, C] bf16 one-hot scratch (2 B/elem), the
    [FB, C] repeated-bins intermediate, the [FB, C] iota plane (both
    2 B/elem bf16 for B <= 256, else 4 B/elem f32, see _onehot_slab)
    and the [FB, nch*Sp] f32 accumulator are charged together. Round 2's
    formula ignored the build intermediate entirely and a 255-bin config
    exceeded the 16 MB stack limit on the chip. The iota term is charged
    CONSERVATIVELY: Mosaic may fold the broadcasted_iota into the
    subtract, but an overflow is a hard compile failure. On a v5e
    (PR 21) every level of the Higgs layout (FB=1792, nch=5, Sp 8..128
    -> tiles 1024..512) compiled and ran at these tiles under the
    default 16 MB scoped-VMEM limit. Shallow levels (small Sp -> small
    accumulator) get larger tiles."""
    if bins_rows:
        c = VMEM_BUDGET // slab_row_bytes(Sp, nch, bins_rows, has_cat,
                                          bundled)
    else:
        acc = FB * nch * Sp * 4
        avail = max(VMEM_BUDGET - acc, 2 * 1024 * 1024)
        per_elem = 4 if wide_bins else 2       # big + iota_b dtype width
        c = avail // ((2 + 2 * per_elem) * FB)
    c = 1 << max(7, (int(c)).bit_length() - 1)      # floor to pow2, >= 128
    return int(min(2048, c))


def level_build(bins_form: bool, Sp: int, FB: int, nch: int, Fp: int,
                wide_bins: bool = False, has_cat: bool = False,
                quant: bool = False, bundled: bool = False) -> dict:
    """How ``level_pass`` builds its one-hot at these shapes, its row
    tile, and which operand of the histogram dot the MXU streams. THE
    place all three are chosen, from static shapes alone: the kernel
    asks here, and so does the driver for its ``level_build`` event.
    ``slab`` (the bins form): SLAB_ROWS one-hot rows at a time, each
    multiplied at once into its own part of the accumulator; ``scratch``
    (the table form, whose routing dot reads the whole one-hot first):
    all [FB, C] of it. ``dot``: ``onehot`` streams the one-hot's rows
    through latched tiles of ``ghs``; ``channels`` latches the one-hot's
    tiles and streams the nch*Sp channel rows: the bf16 slab build's
    wherever the channel columns would pad an N-tile of 128, which is
    every slot count a cell runs (faster at each, and the same bits:
    module docstring). A whole number of N-tiles pads nothing (128 slots
    x 5 channels = 640 columns: 206.3 ms streamed the old way, 211.8 the
    new), the table form's dot reads the whole scratch and the int8 paths
    have another MXU tile (neither timed): all three keep ``onehot``."""
    if bins_form:
        channels = not quant and (nch * Sp) % 128 != 0
        return {"form": "slab", "slab_rows": SLAB_ROWS,
                "tile_rows": default_tile_rows(Sp, FB, nch, bins_rows=Fp,
                                               has_cat=has_cat,
                                               bundled=bundled),
                "dot": "channels" if channels else "onehot"}
    return {"form": "scratch", "dot": "onehot",
            "tile_rows": default_tile_rows(Sp, FB, nch, wide_bins=wide_bins)}


def _fit_tile(C: int, R: int) -> int:
    """Largest pow2 tile <= C dividing the padded row count."""
    while C > 128 and R % C:
        C //= 2
    return C


def _onehot_slab(rows, w: int, quant: bool):
    """[k, C] bin values of k features of one width ``w`` -> their
    [k*w, C] block of the one-hot, ``oh[f*w+b, r] = (rows[f, r] == b)``:
    THE one spelling of the one-hot, for a slab of it (_level_kernel's
    bins form) and for the whole of it (_write_onehot). Built
    ARITHMETICALLY, relu(1 - |bins - b|), in bf16: integers <= 256 are
    exact in bf16, so the result is bit-identical to a compare while the
    repeated-bins intermediate stays 2 B/elem (Mosaic on this target
    compiles only i32 compares, which forced a 4 B/elem intermediate in
    the round-2/3 build). Bin counts > 256 (wide EFB bundle columns) use
    an f32 intermediate instead. ``quant`` (int8 histograms): a plain
    i32 compare cast to int8; the intermediate cost returns, but the
    one-hot and the MXU dots halve to 1 B/elem on the native s8 path."""
    k, C = rows.shape
    span = k * w
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (span, C), 0) % w
    if quant:
        big = jnp.repeat(rows.astype(jnp.int32), w, axis=0)
        return (big == iota_b).astype(jnp.int8)
    dt = jnp.bfloat16 if w <= 256 else jnp.float32
    big = jnp.repeat(rows.astype(dt), w, axis=0)
    return jnp.maximum(1.0 - jnp.abs(big - iota_b.astype(dt)), 0.0) \
        .astype(jnp.bfloat16)


def _write_onehot(bins_ref, oh_ref, F_oh: int, B: int,
                  packed: PackedLayout = None, fm_ref=None) -> None:
    """The WHOLE one-hot of a row tile, written to the [FB, C] VMEM
    scratch: what the table form needs, because ``D = W @ oh`` must be
    complete before the histogram dot's right-hand side exists.

    - ``packed`` (adaptive per-feature bins): the bin matrix rows are
      pre-permuted into width classes, so each class region builds at
      ITS width instead of the global pow2 B; class padding regions are
      zeroed;
    - ``fm_ref`` ([FB, 128], col 0 live): gain-screened features'
      slabs are zeroed after the build so they contribute nothing to
      either dot.
    """
    quant = oh_ref.dtype == jnp.int8
    C = bins_ref.shape[1]
    if packed is None:
        oh_ref[:] = _onehot_slab(bins_ref[:F_oh], B, quant)
    else:
        for ci, (w, cnt) in enumerate(packed.classes):
            r0 = int(packed.row_offsets[ci])
            o0 = int(packed.class_flat_offsets[ci])
            span = cnt * w
            oh_ref[o0:o0 + span] = _onehot_slab(bins_ref[r0:r0 + cnt], w,
                                                quant)
            pad = _round_up(span, 128) - span
            if pad:
                oh_ref[o0 + span:o0 + span + pad] = jnp.zeros(
                    (pad, C), oh_ref.dtype)
    if fm_ref is not None:
        oh_ref[:] = oh_ref[:] * fm_ref[:, 0:1]


def max_slot_cap(FB: int, nch: int, budget: int = 4 * 1024 * 1024) -> int:
    """Largest per-level slot count whose [FB, nch*Sp] f32 accumulator fits
    in ``budget`` bytes of VMEM (wide-bin datasets get narrower levels and
    more of them)."""
    cap = budget // (FB * nch * 4)
    cap = 1 << max(3, int(cap).bit_length() - 1)
    return int(min(128, cap))


def pack_gh(grad: jax.Array, hess: jax.Array, weight: jax.Array,
            nch: int) -> jax.Array:
    """[8, R] bfloat16 channel block for the kernel.

    nch=5: g_hi, g_lo, h_hi, h_lo, w  (hi/lo bf16 split => fp32-grade sums)
    nch=3: g, h, w
    Rows beyond nch are zero padding (the sublane block is 8 tall anyway).

    The high half is the value rounded to bfloat16 (to nearest, ties to
    even) by integer arithmetic on its bits, so exact in bfloat16, and
    the low half the exact float32 rest rounded to bfloat16. Not
    ``x - f32(bf16(x))``: XLA on the TPU may keep a bfloat16 intermediate
    at float32 (excess precision), and then reads that rest as 0 and every
    low half as 0 (measured on a v5e: the histogram's sums were
    bfloat16's, a root split's gain 0.3-0.4 % off the float64 one; with
    the bits rounded by hand, 5e-7). Finite inputs only, as the kernel
    asks.
    """
    R = grad.shape[-1]
    z = jnp.zeros((R,), jnp.bfloat16)
    if nch == NCH_PRECISE:
        def split(x):
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
            u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) \
                & jnp.uint32(0xFFFF0000)
            hi = jax.lax.bitcast_convert_type(u, jnp.float32)
            return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)
        g_hi, g_lo = split(grad)
        h_hi, h_lo = split(hess)
        rows = [g_hi, g_lo, h_hi, h_lo, weight.astype(jnp.bfloat16), z, z, z]
    else:
        rows = [grad.astype(jnp.bfloat16), hess.astype(jnp.bfloat16),
                weight.astype(jnp.bfloat16), z, z, z, z, z]
    return jnp.stack(rows, axis=0)


def pack_gh_quant(grad: jax.Array, hess: jax.Array, weight: jax.Array,
                  bits: int, seed) -> Tuple[jax.Array, jax.Array]:
    """Quantized sibling of :func:`pack_gh` (``tpu_quantized_grad``):
    stochastic-rounded fixed-point grad/hess under a per-iteration
    global scale from a traced max-abs reduction (ops/quantize.py).

    Returns ([8, R] int8 channel block, [2] f32 scales).  bits=8 packs
    (g, h, w); bits=16 packs the int8 hi/lo split (g_hi, g_lo, h_hi,
    h_lo, w) so the MXU's native s8 x s8 -> s32 path accumulates the
    full 16-bit grid exactly.  ``weight`` must be a 0/1 in-bag mask
    (the fast paths' contract); zero-weight rows encode exactly zero.
    """
    R = grad.shape[-1]
    scales = quantize.quant_scales(grad, hess, bits)
    qg, qh = quantize.quantize_gh(grad, hess, scales, bits, seed)
    rows = quantize.encode_channels(qg, qh, weight, bits)
    z = jnp.zeros((R,), jnp.int8)
    rows = rows + [z] * (8 - len(rows))
    return jnp.stack(rows, axis=0), scales


def pack_route_table(W: jax.Array, packed: PackedLayout) -> jax.Array:
    """Padded-layout route table [Sp, F_oh*Bp] -> packed layout
    [Sp, packed.fb] (class-padding columns zero)."""
    idx = jnp.asarray(packed.packed_to_padded, jnp.int32)
    valid = jnp.asarray(packed.packed_valid)
    Wp = jnp.take(W, idx, axis=1)
    return jnp.where(valid[None, :], Wp, 0).astype(W.dtype)


def unpack_packed_flat(hist: jax.Array, packed: PackedLayout) -> jax.Array:
    """[packed.fb, X] kernel accumulator -> [F_oh*Bp, X] padded flat
    layout (exact gather — the accumulated per-(feature, bin) sums are
    the padded layout's, just re-indexed, so the decode is
    bit-identical to the padded kernel's output)."""
    idx = jnp.asarray(packed.padded_to_packed, jnp.int32)
    valid = jnp.asarray(packed.padded_valid)
    out = jnp.take(hist, idx, axis=0)
    return jnp.where(valid[:, None], out, 0)


def expand_feature_mask(fm: jax.Array, F_oh: int, B: int,
                        packed: PackedLayout = None) -> jax.Array:
    """Per-feature bool mask [F_oh] -> per-flat-position bool [FB] in
    the kernel layout (class/feature padding positions False)."""
    if packed is None:
        return jnp.repeat(fm, B, total_repeat_length=F_oh * B)
    f_of = jnp.asarray(packed.feat_of_packed, jnp.int32)
    valid = jnp.asarray(packed.packed_valid)
    return jnp.take(fm, f_of) & valid


def hist_planes(hist: jax.Array, nch: int, Sp: int, F_oh: int, B: int,
                packed: PackedLayout = None, quant_bits: int = 0,
                scales: jax.Array = None):
    """[FB, nch*Sp] kernel output -> (grad, hess, cnt) planes [Sp, F_oh, B]
    in float32 (hi/lo recombined when nch=5).

    ``packed`` re-indexes an adaptive-layout accumulator back onto the
    padded logical layout first (exact); ``quant_bits`` decodes int32
    integer sums through the ONE f32 rescale boundary (ops/quantize.py)
    — everything above (split search, pools, subtraction) stays f32 and
    unchanged."""
    if packed is not None:
        hist = unpack_packed_flat(hist, packed)

    def plane(c):
        return hist[:, c * Sp:(c + 1) * Sp]
    if quant_bits:
        g, h, c = quantize.decode_sums(
            [plane(i) for i in range(quantize.QNCH[quant_bits])],
            scales, quant_bits)
    elif nch == NCH_PRECISE:
        g = plane(0) + plane(1)
        h = plane(2) + plane(3)
        c = plane(4)
    else:
        g, h, c = plane(0), plane(1), plane(2)
    to = lambda x: x.T.reshape(Sp, F_oh, B)
    return to(g), to(h), to(c)


def build_route_table(feature: jax.Array, threshold: jax.Array,
                      default_left: jax.Array, num_bin: jax.Array,
                      missing_type: jax.Array, default_bin: jax.Array,
                      Sp: int, F_oh: int, B: int,
                      cat_flag: jax.Array = None,
                      cat_mask: jax.Array = None) -> jax.Array:
    """W [Sp, F_oh*B] bfloat16: W[k, f*B+b] = 1 iff a row with bin b of
    feature f goes LEFT under slot k's split. Missing-bin routing follows
    default_left (ref: src/io/dense_bin.hpp Split: zero/NaN bins ride the
    default direction). feature=-1 rows are all-zero (inactive slot).

    Args are per-slot [Sp] (feature/threshold/default_left, and optionally
    cat_flag [Sp] + cat_mask [Sp, B] for categorical splits where "left"
    membership is an explicit bin set) and per-feature [F] metadata.
    """
    F = num_bin.shape[0]
    f_iota = jnp.arange(F_oh, dtype=jnp.int32)[None, :, None]      # [1,Foh,1]
    b_iota = jnp.arange(B, dtype=jnp.int32)[None, None, :]         # [1,1,B]
    nb = jnp.zeros((F_oh,), jnp.int32).at[:F].set(num_bin)
    mt = jnp.zeros((F_oh,), jnp.int32).at[:F].set(missing_type)
    db = jnp.zeros((F_oh,), jnp.int32).at[:F].set(default_bin)
    nb = nb[None, :, None]
    mt = mt[None, :, None]
    db = db[None, :, None]

    feat = feature[:, None, None]                                  # [Sp,1,1]
    thr = threshold[:, None, None]
    dl = default_left[:, None, None]

    is_missing = (((mt == 1) & (b_iota == db))
                  | ((mt == 2) & (b_iota == nb - 1)))
    numeric_left = jnp.where(is_missing, dl, b_iota <= thr)
    if cat_flag is not None:
        cat_left = cat_mask[:, None, :]                            # [Sp,1,B]
        go_left = jnp.where(cat_flag[:, None, None], cat_left, numeric_left)
    else:
        go_left = numeric_left
    w = (f_iota == feat) & go_left & (feat >= 0)
    return w.reshape(Sp, F_oh * B).astype(jnp.bfloat16)


def route_table_columns(tbl: jax.Array, feature: jax.Array,
                        threshold: jax.Array, default_left: jax.Array,
                        num_bin: jax.Array, missing_type: jax.Array,
                        default_bin: jax.Array,
                        packed: PackedLayout = None,
                        cat_flag: jax.Array = None,
                        cat_mask: jax.Array = None,
                        bundle=None) -> jax.Array:
    """The BINS form of a level's splits: ``tbl`` with columns 3-6 filled
    per slot (threshold bin; the bin that rides default_left, -1 for
    none — build_route_table's ``is_missing``; default_left; the split
    feature's ROW of the kernel's bin matrix, its position in
    ``packed.feat_order`` under the adaptive layout). An inactive slot
    (feature -1) gets threshold -1, no missing bin and row -1: it reads
    "not left" for every row, as its all-zero W row does. The kernels
    decide ``left = where(bin == missing, default_left, bin <= threshold)``
    from these (_left_from_bins): the same 0/1 plane as ``W @ one_hot``.

    With ``cat_flag`` [Sp] and ``cat_mask`` [Sp, B <= 256] (a job with a
    categorical column) columns 7-15 are filled too: the slot's
    categorical flag and its left-going bin SET, bin b as bit ``b & 31``
    of word ``b >> 5``. A categorical slot is decided by membership of
    the stored bin in that set alone (unseen, rare, negative and NaN
    categories sit in bins outside it and go right), a numerical slot of
    the same level as above; an inactive slot's flag and words are zero.

    With ``bundle`` = (column, window offset, most-frequent bin), [F]
    each (a job stored as EFB bundle columns, ops/efb.py), a slot's row
    is its feature's bundle COLUMN and columns 16-18 carry the feature's
    window (offset, width = its bins) and most-frequent bin, which the
    kernels decode the column's value by before any compare; an inactive
    slot's window is empty.
    Args as build_route_table ([Sp] per slot, [F] per feature)."""
    on = feature >= 0
    f = jnp.maximum(feature, 0)
    mt = missing_type[f]
    miss = jnp.where(mt == 1, default_bin[f],
                     jnp.where(mt == 2, num_bin[f] - 1, -1))
    if bundle is not None:
        row = bundle[0][f]
    else:
        row = f if packed is None else jnp.asarray(packed.row_of_feat)[f]
    cols = jnp.stack([threshold, miss, default_left.astype(jnp.int32), row],
                     axis=1)
    cols = jnp.where(on[:, None], cols, jnp.array([-1, -1, 0, -1]))
    tbl = tbl.at[:, TBL_THRESHOLD:TBL_FEATURE_ROW + 1].set(
        cols.astype(jnp.int32))
    if bundle is not None:
        window = jnp.stack([bundle[1][f], num_bin[f], bundle[2][f]], axis=1)
        tbl = tbl.at[:, TBL_WINDOW_OFFSET:TBL_WINDOW_MFB + 1].set(
            jnp.where(on[:, None], window, 0).astype(jnp.int32))
    if cat_flag is None:
        return tbl
    Sp, B = cat_mask.shape
    assert B <= 32 * CAT_WORDS, f"bin set of {B} bins"
    is_cat = cat_flag & on
    bits = jnp.pad(cat_mask & is_cat[:, None],
                   ((0, 0), (0, 32 * CAT_WORDS - B))) \
        .reshape(Sp, CAT_WORDS, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=2,
                    dtype=jnp.uint32)
    return tbl.at[:, TBL_CAT_FLAG].set(is_cat.astype(jnp.int32)) \
        .at[:, TBL_CAT_WORD0:TBL_CAT_WORD0 + CAT_WORDS].set(
            jax.lax.bitcast_convert_type(words, jnp.int32))


def root_route_tables(num_bins: int, kern_fb: int, first_width: int,
                      bins_form: bool, Sp: int = 8, bundled: bool = False):
    """(W, tbl) of the ROOT pass: slot 0 holds leaf 0, is its own
    "smaller child" and sends every row left, so it collects the
    full-data histogram; the other slots are inactive. Table form: W[0]
    is 1 over the FIRST kernel column's ``first_width`` bins (each row's
    one-hot holds exactly one of them). Bins form (W None): whatever bin
    the first kernel row holds is <= num_bins - 1, and no bin is the
    missing one (the root sends missing rows left too); ``bundled``: the
    window of slot 0 is the whole column, so its decode is the value."""
    tbl = jnp.zeros((Sp, 128), jnp.int32) \
        .at[:, TBL_LEAF].set(jnp.where(jnp.arange(Sp) == 0, 0, -2)) \
        .at[0, TBL_SMALL_LEFT].set(1)
    if not bins_form:
        W = jnp.zeros((Sp, kern_fb), jnp.bfloat16).at[0, :first_width].set(1)
        return W, tbl
    cols = jnp.array([[num_bins - 1, -1, 0, 0]] + [[-1, -1, 0, -1]] * (Sp - 1),
                     jnp.int32)
    tbl = tbl.at[:, TBL_THRESHOLD:TBL_FEATURE_ROW + 1].set(cols)
    if bundled:
        tbl = tbl.at[0, TBL_WINDOW_WIDTH].set(num_bins)
    return None, tbl


def build_route_table_bundled(feature: jax.Array, threshold: jax.Array,
                              default_left: jax.Array, num_bin: jax.Array,
                              missing_type: jax.Array,
                              default_bin: jax.Array,
                              most_freq_bin: jax.Array,
                              col_of_feat: jax.Array,
                              offset_of_feat: jax.Array,
                              C_cols: int, Bp: int,
                              cat_flag: jax.Array = None,
                              cat_mask: jax.Array = None) -> jax.Array:
    """W [Sp, C_cols*Bp] for LOGICAL splits over EFB bundle columns.

    A bundle-bin bb of column c decodes to logical feature f's bin as
    ``bb - offset_f`` when bb lies in f's window, and to f's
    most-frequent bin otherwise (rows default in every bundled feature
    share bundle bin 0 — ops/efb.py encoding). Only the owning column
    carries the decision; all other columns stay zero so the routing dot
    D = W @ one_hot still reads each row's verdict from exactly one
    lane. Missing-bin semantics follow the numerical rule on the DECODED
    bin (ref: src/io/dense_bin.hpp Split); categorical splits test the
    DECODED bin's membership in ``cat_mask`` [Sp, B_logical]."""
    F = num_bin.shape[0]
    Sp = feature.shape[0]
    c_iota = jnp.arange(C_cols, dtype=jnp.int32)[None, :, None]
    b_iota = jnp.arange(Bp, dtype=jnp.int32)[None, None, :]

    feat_safe = jnp.maximum(feature, 0)
    nb = num_bin[feat_safe][:, None, None]
    mt = missing_type[feat_safe][:, None, None]
    db = default_bin[feat_safe][:, None, None]
    mfb = most_freq_bin[feat_safe][:, None, None]
    col = col_of_feat[feat_safe][:, None, None]
    off = offset_of_feat[feat_safe][:, None, None]
    thr = threshold[:, None, None]
    dl = default_left[:, None, None]

    in_window = (b_iota >= off) & (b_iota < off + nb)
    logical_bin = jnp.where(in_window, b_iota - off, mfb)
    is_missing = (((mt == 1) & (logical_bin == db))
                  | ((mt == 2) & (logical_bin == nb - 1)))
    go_left = jnp.where(is_missing, dl, logical_bin <= thr)
    if cat_flag is not None:
        B = cat_mask.shape[1]
        lb = jnp.clip(logical_bin, 0, B - 1)
        cat_left = cat_mask[jnp.arange(Sp)[:, None, None], lb]
        go_left = jnp.where(cat_flag[:, None, None], cat_left, go_left)
    w = (c_iota == col) & go_left & (feature[:, None, None] >= 0)
    return w.reshape(Sp, C_cols * Bp).astype(jnp.bfloat16)


def bundle_plane_views(plane: jax.Array, flat_idx: jax.Array,
                       valid: jax.Array, default_bin: jax.Array
                       ) -> jax.Array:
    """Bundle histogram -> logical per-feature view with the FixHistogram
    residual on each feature's most-frequent bin (ref:
    src/io/dataset.cpp:1265). The single shared implementation for both
    the fused engine and models/learner.bundle_views.

    plane: [Sp, C_cols, Bp] or [Sp, C_cols, Bp, ch]. Returns the same
    rank with (C_cols, Bp) -> (F, B). Slot totals come from column 0 —
    every row lands in some bin of every column. Padding features (no
    valid bins) stay all-zero."""
    squeeze = plane.ndim == 3
    if squeeze:
        plane = plane[..., None]
    Sp, C, Bp, ch = plane.shape
    F, B = flat_idx.shape
    flat = plane.reshape(Sp, C * Bp, ch)
    view = jnp.take(flat, flat_idx.reshape(-1), axis=1) \
        .reshape(Sp, F, B, ch)
    view = jnp.where(valid[None, :, :, None], view, 0.0)
    totals = jnp.sum(plane[:, 0, :, :], axis=1)                 # [Sp, ch]
    residual = totals[:, None, :] - jnp.sum(view, axis=2)       # [Sp, F, ch]
    residual = residual * jnp.any(valid, axis=1)[None, :, None]
    out = view.at[jnp.arange(Sp)[:, None], jnp.arange(F)[None, :],
                  default_bin[None, :]].add(residual)
    return out[..., 0] if squeeze else out


def _left_from_bins(bins_ref, tbl_ref, has_cat: bool = False,
                    bundled: bool = False):
    """left_i [Sp, C] int32 0/1 from the bin VALUES (bins form): slot
    k's feature row is picked with a K = Fp dot, ``v[k, r] = sum_f
    sel[k, f] * bins[f, r]`` (one non-zero term, bin values <= 255 are
    exact in bf16, so v is exact in f32), then compared with the slot's
    threshold and missing bin from ``tbl``. An inactive slot (feature
    row -1: sel row all zero, threshold -1) reads 0. Mask algebra stays
    in i32 (the i1 relayout bug noted in _level_kernel).

    ``has_cat`` (static: the job has a categorical column): a slot whose
    categorical flag is set is decided by MEMBERSHIP of v in the slot's
    bin set instead, bit ``v & 31`` of word ``v >> 5`` of the eight words
    the slot table carries (route_table_columns): the word by eight
    compare-and-selects, the bit by a per-lane shift. Traced only then:
    the kernels of a job without a categorical column lower as before.

    ``bundled`` (static: the job is stored as EFB bundle columns, the
    feature row is the split feature's bundle column): v is decoded to
    the feature's bin first, ``v - offset`` inside the slot's window
    [offset, offset + width) and its most-frequent bin outside it
    (ops/efb.py's encoding: the rows default in the feature, and those a
    member before it in the bundle wrote), exact in f32, under the
    ``bundle_decode`` scope; the compares, and the membership test,
    read the decoded bin. Traced only then, like ``has_cat``."""
    Fp = bins_ref.shape[0]
    Sp = tbl_ref.shape[0]
    sel = (jax.lax.broadcasted_iota(jnp.int32, (Sp, Fp), 1)
           == tbl_ref[:, TBL_FEATURE_ROW:TBL_FEATURE_ROW + 1]) \
        .astype(jnp.bfloat16)                                  # [Sp, Fp]
    v = jax.lax.dot_general(sel, bins_ref[:].astype(jnp.bfloat16),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Sp, C]
    if bundled:
        with jax.named_scope("bundle_decode"):
            def col(c):
                return tbl_ref[:, c:c + 1].astype(jnp.float32)   # [Sp, 1]
            d = v - col(TBL_WINDOW_OFFSET)
            inside = ((d >= 0.0).astype(jnp.int32)
                      * (d < col(TBL_WINDOW_WIDTH)).astype(jnp.int32))
            mfb = col(TBL_WINDOW_MFB)
            v = mfb + inside.astype(jnp.float32) * (d - mfb)
    thr = tbl_ref[:, TBL_THRESHOLD:TBL_THRESHOLD + 1].astype(jnp.float32)
    miss = tbl_ref[:, TBL_MISSING_BIN:TBL_MISSING_BIN + 1] \
        .astype(jnp.float32)
    dl = tbl_ref[:, TBL_DEFAULT_LEFT:TBL_DEFAULT_LEFT + 1]     # [Sp, 1]
    le = (v <= thr).astype(jnp.int32)
    is_miss = (v == miss).astype(jnp.int32)
    left = le + is_miss * (dl - le)        # where(is_miss, dl, v <= thr)
    if not has_cat:
        return left
    vi = v.astype(jnp.int32)                                   # [Sp, C]
    which = jax.lax.shift_right_logical(vi, 5)
    word = jnp.zeros_like(vi)
    for j in range(CAT_WORDS):
        col = TBL_CAT_WORD0 + j
        word = jnp.where(which == j, tbl_ref[:, col:col + 1], word)
    member = jax.lax.shift_right_logical(word, vi & 31) & 1
    is_cat = tbl_ref[:, TBL_CAT_FLAG:TBL_CAT_FLAG + 1]         # [Sp, 1]
    return left + is_cat * (member - left)  # where(is_cat, member, left)


def _route_rows(leafb, left_i, tbl_ref):
    """(new leaf [1, C], P_i [Sp, C]): rows of slot k's leaf that do not
    go left move to leaf + right_delta[k]."""
    Sp, C = left_i.shape
    leaf_of_slot = tbl_ref[:, TBL_LEAF:TBL_LEAF + 1]           # [Sp, 1]
    right_delta = tbl_ref[:, TBL_RIGHT_DELTA:TBL_RIGHT_DELTA + 1]
    P_i = (jnp.broadcast_to(leafb, (Sp, C))
           == leaf_of_slot).astype(jnp.int32)                  # [Sp, C] 0/1
    go_right = P_i * (1 - left_i)                              # [Sp, C] 0/1
    delta = jnp.sum(go_right * jnp.broadcast_to(right_delta, (Sp, C)),
                    axis=0, keepdims=True)                     # [1, C] i32
    return leafb + delta, P_i


def _small_child_channels(leafb, left_i, tbl_ref, gh_ref, nch: int,
                          quant: bool):
    """(new leaf [1, C], ghs [nch*Sp, C]): the row->leaf update, and the
    histogram dot's right-hand side: every gh channel masked to the rows
    of slot k's SMALLER child, all channels packed into one operand."""
    Sp, C = left_i.shape
    # ---- slot membership + row->leaf update: right-child rows move to
    # their new leaf id
    new_leaf, P_i = _route_rows(leafb, left_i, tbl_ref)
    small_left_i = (tbl_ref[:, TBL_SMALL_LEFT:TBL_SMALL_LEFT + 1]
                    > 0).astype(jnp.int32)                     # [Sp, 1] 0/1
    same_i = 1 - jnp.bitwise_xor(left_i, small_left_i)         # left==small
    in_small = P_i * same_i                                    # [Sp, C] 0/1
    if not quant:
        in_small = in_small.astype(jnp.bfloat16)

    # ---- mask*g instead of a select (i1 selects also hit the relayout
    # bug noted in _level_kernel); requires FINITE grad/hess: a NaN/Inf
    # row would leak 0*NaN into other slots' bins, but non-finite
    # gradients wreck training under any formulation. Quantized mode:
    # int8 channels (integer sums are EXACT and associative,
    # ops/quantize.py, rescaled outside). The mask product runs in i32
    # and narrows afterwards: Mosaic on v5e refuses an i8 x i8 vector
    # multiply ("failed to legalize operation 'arith.muli' ...
    # vector<8x128x4xi8>").
    chans = []
    for ch in range(nch):
        g = gh_ref[ch:ch + 1, :]                               # [1, C]
        if quant:
            chans.append((in_small * jnp.broadcast_to(
                g.astype(jnp.int32), (Sp, C))).astype(jnp.int8))
        else:
            chans.append(in_small * jnp.broadcast_to(g, (Sp, C)))
    return new_leaf, jnp.concatenate(chans, axis=0)            # [nch*Sp, C]


def _slab_cuts(F_oh: int, B: int, packed: PackedLayout = None):
    """The bins form's slabs, static: (first row of the bin matrix,
    features, their width, first row of the accumulator) of each. A slab
    is SLAB_ROWS one-hot rows (8 features of 64 bins) or what is left of
    its width class; class padding rows of the ``packed`` layout belong
    to no slab and stay zero."""
    classes = [(B, F_oh, 0, 0)] if packed is None else [
        (w, cnt, int(packed.row_offsets[ci]),
         int(packed.class_flat_offsets[ci]))
        for ci, (w, cnt) in enumerate(packed.classes)]
    cuts = []
    for w, cnt, r0, o0 in classes:
        k = max(1, SLAB_ROWS // w)
        cuts += [(r0 + f, min(k, cnt - f), w, o0 + f * w)
                 for f in range(0, cnt, k)]
    return cuts


def _level_kernel(*refs, B: int, F_oh: int, Sp: int, nch: int,
                  quant: bool = False, packed: PackedLayout = None,
                  has_fm: bool = False, has_w: bool = True,
                  has_cat: bool = False, dot: str = "onehot",
                  bundled: bool = False):
    """``level_pass``'s body. Table form (``has_w``): the whole one-hot
    goes to the [FB, C] scratch ``oh_ref`` first, because routing reads
    all of it (``D = W @ oh``) before the histogram dot's right-hand side
    exists. Bins form: routing reads the bin values, so ``ghs`` is known
    before any one-hot element is, each slab of the one-hot has exactly
    one reader (its own rows of the accumulator) and there is no scratch:
    slabs are built and multiplied one after the other in ONE basic
    block, which lets the scheduler put the VPU build of a slab under
    the MXU's pass over its neighbours. ``dot`` (level_build's, bins
    form only): ``channels`` multiplies the other way round,
    ``ghs @ slab^T``, into a TRANSPOSED accumulator [nch*Sp, FB] whose
    lanes o0..o0 + k*w are the slab's."""
    refs = list(refs)
    bins_ref, leaf_ref, gh_ref = refs[:3]
    w_ref = refs[3] if has_w else None
    tbl_ref = refs[3 + has_w]
    fm_ref = refs[4 + has_w] if has_fm else None
    hist_ref, newleaf_ref = refs[4 + has_w + has_fm:][:2]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    leafb = leaf_ref[:]                                        # [1, C] i32
    acc_dt = jnp.int32 if quant else jnp.float32
    # the histogram dot over a tile's rows, a @ b^T: all channels packed
    # into one operand; the MXU streams a's rows through latched tiles of b
    hist_dot = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=acc_dt)

    if not has_w:
        left_i = _left_from_bins(bins_ref, tbl_ref, has_cat,
                                 bundled)                      # [Sp, C] 0/1
        newleaf_ref[:], ghs = _small_child_channels(
            leafb, left_i, tbl_ref, gh_ref, nch, quant)
        # the bin tile converted ONCE to 4-byte rows (8 to a register):
        # a slab's rows then start on whole sublanes, which int8's 32 to
        # a register would not
        binsv = bins_ref[:].astype(jnp.int32 if quant else jnp.float32)
        for r0, k, w, o0 in _slab_cuts(F_oh, B, packed):
            oh = _onehot_slab(binsv[r0:r0 + k], w, quant)
            if fm_ref is not None:
                oh = oh * fm_ref[o0:o0 + k * w, 0:1]
            if dot == "channels":
                hist_ref[:, o0:o0 + k * w] += hist_dot(ghs, oh)
            else:
                hist_ref[o0:o0 + k * w] += hist_dot(oh, ghs)
        return

    oh_ref = refs[-1]
    _write_onehot(bins_ref, oh_ref, F_oh, B, packed=packed, fm_ref=fm_ref)
    # ---- routing: left_i[k, r] = 1 iff row r goes left under slot k's
    # split, D = W @ one_hot; quantized mode routes on the same int8
    # one-hot through the MXU's native s8 x s8 -> s32 path (W is
    # 0/1-valued either way).
    oh = oh_ref[:]
    if quant:
        D = jax.lax.dot_general(w_ref[:], oh, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        left_i = (D > 0).astype(jnp.int32)                     # [Sp, C] 0/1
    else:
        D = jax.lax.dot_general(w_ref[:], oh, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # Mask algebra stays in i32/bf16 throughout: broadcast i1 vectors
        # hit a Mosaic relayout bug on this toolchain ("Invalid relayout
        # ... 8x1024xi1" when an [Sp,1] bool meets an [Sp,C] bool), and
        # int select lowers to the same VPU ops anyway.
        left_i = (D > 0.5).astype(jnp.int32)                   # [Sp, C] 0/1
    newleaf_ref[:], ghs = _small_child_channels(leafb, left_i, tbl_ref,
                                                gh_ref, nch, quant)
    hist_ref[:] += hist_dot(oh, ghs)


def _kernel_fb(f_oh: int, num_bins: int, packed: PackedLayout) -> int:
    return packed.fb if packed is not None else f_oh * num_bins


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "num_bins", "f_oh", "nch", "tile_rows",
                     "interpret", "quant_bits", "packed", "has_cat",
                     "bundled"))
def level_pass(bins_T: jax.Array, leaf_T: jax.Array, gh_T: jax.Array,
               W: jax.Array, tbl: jax.Array, fmask: jax.Array = None,
               *, num_slots: int, num_bins: int, f_oh: int,
               nch: int = NCH_PRECISE, tile_rows: int = 0,
               interpret: bool = False, quant_bits: int = 0,
               packed: PackedLayout = None, has_cat: bool = False,
               bundled: bool = False):
    """One fused route+histogram pass over all rows.

    Args:
      bins_T: [Fp, R] int8 binned matrix, transposed (Fp >= f_oh; padded
        feature rows all-zero). R must be a multiple of the tile size
        (pad rows carry leaf_T = -1 so they contribute nothing). With
        ``packed`` the rows are pre-permuted into width-class order
        (packed.feat_order).
      leaf_T: [1, R] int32 row->leaf ids (-1 = inactive/padding row).
      gh_T: [8, R] bfloat16 channel block from pack_gh(), or the int8
        block from pack_gh_quant() when ``quant_bits`` is set.
      W: [Sp, FB] bfloat16 route table (build_route_table, packed via
        pack_route_table under ``packed``), or None: the bins form, whose
        splits ride ``tbl`` (route_table_columns) and whose one-hot is
        built in slabs with no scratch (level_build).
      tbl: [Sp, 128] int32; col 0 leaf_of_slot (-1 = inactive slot),
        col 1 right_delta (new_leaf_id - leaf_id), col 2 small_is_left
        (any value > 0 means left). grad/hess/weight must be FINITE: the
        kernel masks channels by multiplication (Mosaic i1-select
        workaround), so a NaN/Inf row would bleed into other slots.
      fmask: optional [FB, 128] (col 0 live) gain-screening mask — the
        masked slabs of the one-hot are zeroed so screened-out features
        contribute to neither dot.
      quant_bits: 0 (f32 path, unchanged), 8 or 16 — integer MXU/VPU
        accumulation into an int32 [FB, nch*Sp] accumulator; the caller
        rescales via hist_planes(quant_bits=..., scales=...).
      packed: adaptive per-feature bin layout (ops/layout.py). The row
        TILE is still derived from the PADDED layout's f_oh*num_bins so
        the per-element accumulation order, and hence the f32 sums,
        stay bit-identical to the padded kernel's (the adaptive-bin A/B
        contract); the win is the smaller accumulator (and scratch, in
        the table form); the bins form builds each width class in slabs
        of its own.
      has_cat: the job has a categorical column (the grower's static):
        in the bins form ``tbl`` then carries each slot's categorical
        flag and bin set (route_table_columns) and the kernel tests
        membership; the table form reads ``W`` and ignores it.
      bundled: ``bins_T`` holds EFB bundle columns (the grower's
        static): in the bins form ``tbl`` then carries each slot's
        window (route_table_columns) and the kernel decodes the picked
        value by it; the table form's ``W`` is written over the bundle
        bins already and ignores it.

    Returns:
      hist: [FB, nch*Sp] float32 (int32 under quant_bits) smaller-child
        histograms, FB = packed.fb or f_oh*num_bins.
      new_leaf: [1, R] int32 updated assignment.
    """
    Fp, R = bins_T.shape
    B = num_bins
    FB = _kernel_fb(f_oh, B, packed)
    FB_tiles = f_oh * B       # padded formula: keeps tiling A/B-stable
    Sp = tbl.shape[0]
    quant = quant_bits > 0
    bundled = bundled and W is None
    build = level_build(W is None, Sp, FB_tiles, nch, Fp, wide_bins=B > 256,
                        has_cat=has_cat, quant=quant, bundled=bundled)
    C = _fit_tile(tile_rows or build["tile_rows"], R)
    assert R % C == 0, f"rows {R} not padded to tile {C}"
    T = R // C
    # the accumulator of a pass that streams the channels is transposed
    channels = build["dot"] == "channels"
    acc_shape = (nch * Sp, FB) if channels else (FB, nch * Sp)
    oh_dt = jnp.int8 if quant else jnp.bfloat16
    acc_dt = jnp.int32 if quant else jnp.float32

    kernel = functools.partial(_level_kernel, B=B, F_oh=f_oh, Sp=Sp,
                               nch=nch, quant=quant, packed=packed,
                               has_fm=fmask is not None,
                               has_w=W is not None,
                               has_cat=has_cat and W is None,
                               dot=build["dot"], bundled=bundled)
    in_specs = [
        pl.BlockSpec((Fp, C), lambda t: (0, t)),
        pl.BlockSpec((1, C), lambda t: (0, t)),
        pl.BlockSpec((8, C), lambda t: (0, t)),
    ]
    operands = [bins_T, leaf_T, gh_T]
    if W is not None:
        in_specs.append(pl.BlockSpec((Sp, FB), lambda t: (0, 0)))
        operands.append(W.astype(jnp.int8) if quant else W)
    in_specs.append(pl.BlockSpec((Sp, 128), lambda t: (0, 0)))
    operands.append(tbl)
    if fmask is not None:
        in_specs.append(pl.BlockSpec((FB, 128), lambda t: (0, 0)))
        operands.append(fmask.astype(oh_dt))
    hist, new_leaf = pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(acc_shape, lambda t: (0, 0)),
            pl.BlockSpec((1, C), lambda t: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(acc_shape, acc_dt),
            jax.ShapeDtypeStruct((1, R), jnp.int32),
        ],
        scratch_shapes=[] if W is None else [pltpu.VMEM((FB, C), oh_dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    return (hist.T if channels else hist), new_leaf


def _route_kernel(bins_ref, leaf_ref, w_ref, tbl_ref, newleaf_ref,
                  oh_ref, *, B: int, F_oh: int, Sp: int,
                  packed: PackedLayout = None):
    """Routing-only sibling of _level_kernel, TABLE form: updates
    row->leaf without accumulating histograms. Used for passes whose
    histograms can never be consumed (the leaf budget is exhausted, or no
    further pass follows). Routing keeps the bf16 formulation under
    quantization (no precision at stake); only the ``packed`` layout
    matters here (the bin rows are permuted)."""
    _write_onehot(bins_ref, oh_ref, F_oh, B, packed=packed)
    D = jax.lax.dot_general(w_ref[:], oh_ref[:], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    left_i = (D > 0.5).astype(jnp.int32)
    newleaf_ref[:], _ = _route_rows(leaf_ref[:], left_i, tbl_ref)


def _route_bins_kernel(bins_ref, leaf_ref, tbl_ref, newleaf_ref, *,
                       has_cat: bool = False, bundled: bool = False):
    """_route_kernel in the BINS form: no one-hot, no [FB, C] scratch.
    Per tile: [Fp, C] int8 -> bf16, one K = Fp dot, a handful of [Sp, C]
    VPU ops (the set-membership test among them under ``has_cat``, the
    window decode under ``bundled``)."""
    left_i = _left_from_bins(bins_ref, tbl_ref, has_cat, bundled)
    newleaf_ref[:], _ = _route_rows(leaf_ref[:], left_i, tbl_ref)


def route_tile_rows(Sp: int, Fp: int, has_cat: bool = False,
                    bundled: bool = False) -> int:
    """Row-tile width of the bins-form route kernel. Nothing in it is
    FB-sized: what the compiler puts on the scoped-VMEM stack is the bf16
    copy of the [Fp, C] bin tile (2 B an element: 31.4 MB refused at Fp
    2,000 x 8,192 rows, 16.3 MB at 512 x 16,384, compiled for a described
    v5e), beside the int8 tile's double buffer; the [Sp, C] planes are
    charged 16 B a row and slot, CAT_PLANE_BYTES more for the
    membership test of a job with a categorical column (``has_cat``) and
    DECODE_PLANE_BYTES more for the window decode of a bundled job. A
    power of two from 512 to 8,192: a grid
    step costs ~0.35 us, and on a v5e (PR 28) the 28M-row Higgs pass took
    7.8 / 6.0 / 5.3 ms at 2,048 / 4,096 / 8,192 rows and 64 slots."""
    c = VMEM_BUDGET // (4 * Fp + _plane_bytes(has_cat, bundled) * Sp)
    c = 1 << (int(c).bit_length() - 1)
    return int(max(512, min(8192, c)))


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "num_bins", "f_oh", "tile_rows",
                     "interpret", "packed", "has_cat", "bundled"))
def route_pass(bins_T: jax.Array, leaf_T: jax.Array, W: jax.Array,
               tbl: jax.Array, *, num_slots: int, num_bins: int,
               f_oh: int, tile_rows: int = 0,
               interpret: bool = False,
               packed: PackedLayout = None,
               has_cat: bool = False, bundled: bool = False) -> jax.Array:
    """Row->leaf update only (same W/tbl/has_cat/bundled contract as
    level_pass; ``W`` None = bins form, whose tile is sized from Fp and
    Sp, not from FB)."""
    Fp, R = bins_T.shape
    B = num_bins
    Sp = tbl.shape[0]
    row_spec = lambda rows, C: pl.BlockSpec((rows, C), lambda t: (0, t))
    tbl_spec = pl.BlockSpec((Sp, 128), lambda t: (0, 0))
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    if W is None:
        C = _fit_tile(tile_rows or route_tile_rows(Sp, Fp, has_cat,
                                                   bundled), R)
        assert R % C == 0, f"rows {R} not padded to tile {C}"
        return pl.pallas_call(
            functools.partial(_route_bins_kernel, has_cat=has_cat,
                              bundled=bundled),
            grid=(R // C,),
            in_specs=[row_spec(Fp, C), row_spec(1, C), tbl_spec],
            out_specs=row_spec(1, C),
            out_shape=jax.ShapeDtypeStruct((1, R), jnp.int32),
            compiler_params=params,
            interpret=interpret,
        )(bins_T, leaf_T, tbl)
    FB = _kernel_fb(f_oh, B, packed)
    C = _fit_tile(tile_rows or default_tile_rows(Sp, f_oh * B, NCH_FAST,
                                                 wide_bins=B > 256), R)
    assert R % C == 0, f"rows {R} not padded to tile {C}"
    kernel = functools.partial(_route_kernel, B=B, F_oh=f_oh, Sp=Sp,
                               packed=packed)
    return pl.pallas_call(
        kernel,
        grid=(R // C,),
        in_specs=[row_spec(Fp, C), row_spec(1, C),
                  pl.BlockSpec((Sp, FB), lambda t: (0, 0)), tbl_spec],
        out_specs=row_spec(1, C),
        out_shape=jax.ShapeDtypeStruct((1, R), jnp.int32),
        scratch_shapes=[pltpu.VMEM((FB, C), jnp.bfloat16)],
        compiler_params=params,
        interpret=interpret,
    )(bins_T, leaf_T, W, tbl)


def _lookup_kernel(idx_ref, tbl_ref, out_ref, *, Lp: int):
    C = idx_ref.shape[1]
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (Lp, C), 0)
    P = jnp.broadcast_to(idx_ref[:], (Lp, C)) == iota_l
    vals = jnp.broadcast_to(tbl_ref[:, 0:1], (Lp, C))
    out_ref[:] = jnp.sum(jnp.where(P, vals, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def table_lookup(idx_T: jax.Array, table: jax.Array, *,
                 tile_rows: int = 2048, interpret: bool = False) -> jax.Array:
    """out[0, r] = table[idx_T[0, r]] for a SMALL table, without the
    ~30 ns/row random-gather penalty of XLA's [R]-from-[L] gather on TPU:
    one streaming pass with a sublane one-hot reduction.

    idx values outside [0, len(table)) return 0. Used for per-row leaf-value
    score updates (ref: src/boosting/score_updater.hpp:88 AddScore).
    """
    (_, R) = idx_T.shape
    L = table.shape[0]
    Lp = _round_up(max(L, 8), 8)
    C = min(tile_rows, _round_up(R, 128))
    Rp = _round_up(R, C)
    if Rp != R:
        idx_T = jnp.pad(idx_T, ((0, 0), (0, Rp - R)), constant_values=-1)
    tblp = jnp.zeros((Lp, 128), table.dtype).at[:L, 0].set(table)
    kernel = functools.partial(_lookup_kernel, Lp=Lp)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // C,),
        in_specs=[
            pl.BlockSpec((1, C), lambda t: (0, t)),
            pl.BlockSpec((Lp, 128), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, Rp), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx_T, tblp)
    return out[:, :R]
