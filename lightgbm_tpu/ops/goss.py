"""Gradient-based One-Side Sampling, traced: the sample and the compact
matrix a tree is grown on (ref: src/boosting/goss.hpp; Ke et al., NeurIPS
2017, Algorithm 2).

Three row-length stages, each its own scope under ``lgbm.sample`` where the
driver calls them (boosting/gbdt.py):

- SELECT (``select_top``): exactly ``k`` rows of the largest value, ties at
  the threshold broken by the lower row index. The k-th largest is found
  without a sort, by bisection on the bit pattern of the non-negative
  values (a non-negative float32 and its int32 bit pattern order the same
  way): 31 counting passes over the vector, one per bit.
- DRAW (``draw_keys`` + ``select_top`` again): exactly ``other_k`` of the
  remaining rows, uniformly without replacement: every row gets a 31-bit key
  from a counter-based hash of (seed, iteration, row) and the rows of the
  ``other_k`` largest keys are taken. The stream has no state: the same
  seed and iteration give the same sample, on any driver and after a
  resume.
- COMPACT (``compact_rows``): the in-bag COLUMNS of the transposed bin
  matrix ``[Fp, Rp]`` and of the packed channels ``[nch8, Rp]`` moved, in
  row order, to the front of ``[Fp, Kp]`` / ``[nch8, Kp]``. A Pallas stream
  compaction over tiles of C rows (``compact_tile``: 16,384 at the Higgs
  cell's shapes), each moved 128 rows at a time. The destinations are
  monotone, so a chunk's in-bag rows land in two consecutive 128-lane
  blocks of the output, from the lane its first destination names (XLA
  prefix sums of the mask give every row's destination and every chunk's
  first): a [128, 128] 0/1 rotation built from the destinations modulo
  128 and one MXU dot move the chunk's [Fp + G, 128] columns, and two
  masked stores split them between the two blocks of a 2C-wide window at
  a 128-aligned lane offset. The work per row does not grow with the
  tile. The window's first half is the output block the grid step names
  through scalar prefetch, the second half the spill into the next block.
  Bin values (<= 255) and bfloat16 channels are exact in bfloat16, every
  output element has one non-zero term: the copy is exact.

On a v5e (``scripts/step0_goss.py``; 28,000,000 rows -> 8,400,896 columns
of 32 int8 bin rows + 8 bfloat16 channel rows): select 5.2 ms (a full
``jnp.sort`` of the vector: 88.8), draw 1.7 (the keys are computed inside
the counting passes, which then read one byte a row), the mask's prefix
sum 2.3 in blocks (6.5 flat). The compaction, the launch and its tables,
in ms at tiles of 1,024 / 2,048 / 4,096 / 8,192 / 16,384 rows, int8 x 32
bin rows, then int16 x 8:

- this form: 15.77 / 11.06 / 8.51 / 7.10 / 6.51 and 13.84 / 9.58 / 7.32 /
  6.10 / 5.55 (of it the tables 2.38; the HBM's bound is 2.1);
- the same with the chunk loop unrolled 16 deep (8 deep) and read-add-write
  stores, at 2,048-8,192: 12.21 / 10.42 / 9.78 (13.67 / 12.34 / 11.69);
- a [256, 128] block per chunk into both output blocks at once, 8 deep:
  18.16 / 14.98 / 14.50 / 13.75 and 16.47 / 13.20 / 13.51 / 12.80 at
  1,024-8,192;
- every column shifted left by its count of out-of-bag rows before it in
  the tile, log2 C stages of a roll and a select, LSB first, no MXU:
  37.47 / 23.02 / 19.14 / 18.77 and 25.70 / 17.08 / 15.71 / 15.79 (these
  two forms with tables of 4.3);
- a [2C, C] permutation one-hot per tile, the form before: 35.79 at 512
  rows (42.5 / 48.0 at 256 / 1,024).

The same move in XLA (``nonzero`` 318 ms, a row gather of a row-major copy
and a transpose 134, element gathers of the channels 78) is 80 x slower.

Departures from goss.hpp, which samples per thread block with a
sequential acceptance probability (a varying count): here the counts are
exact and the draw is one pass of keys; the program's earlier host version
departed the same way.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .scan import blocked_scan

ROW_ALIGN = 2048          # the fused grower's widest row tile
LANES = 128               # rows of one chunk of the compaction's move
COMPACT_TILE_MAX = 16384  # rows of the compaction's widest tile
COMPACT_VMEM = 12 * 1024 * 1024   # what its window and buffers may hold


def compact_tile(Fp: int, itemsize: int, G: int, Rp: int,
                 most: int = COMPACT_TILE_MAX) -> int:
    """Row tile (and output block) of ``compact_rows``, from the shapes
    alone: the widest power of two up to ``most`` whose window and
    buffers fit COMPACT_VMEM (per row: the [Fp + G, 2C] float32 window,
    the double-buffered bins, channels and int32 destinations, the
    latter padded to 8 sublanes, and the two output blocks), halved
    until it divides the ``Rp`` rows. No tile is under 256 rows."""
    per_row = 8 * (Fp + G) + 4 * (Fp * itemsize + 2 * G) + 64
    c = 1 << ((COMPACT_VMEM // per_row).bit_length() - 1)
    c = max(256, min(most, c))
    while Rp % c:
        c //= 2
    return c


class GossPlan(NamedTuple):
    """What is static about a job's sample (hashable: it keys jits)."""
    n: int              # training rows
    top_k: int          # rows kept for their |g * h|
    other_k: int        # rows drawn from the rest
    capacity: int       # top_k + other_k rounded up to the row tile
    multiply: float     # (n - top_k) / other_k, on the drawn rows
    seed: int           # bagging_seed
    first_iter: int     # int(1 / learning_rate): iterations below use all
    # why the job cannot grow on the compact matrix (it then takes the
    # synchronous driver and the sample as a weight vector); None: it can
    evict_reason: Optional[str] = None

    @property
    def bag_rows(self) -> int:
        return self.top_k + self.other_k


def goss_plan(n: int, top_rate: float, other_rate: float,
              learning_rate: float, seed: int,
              evict_reason: Optional[str] = None) -> GossPlan:
    top_k = max(1, int(n * top_rate))
    other_k = min(max(1, int(n * other_rate)), n - top_k)
    cap = -(-(top_k + other_k) // ROW_ALIGN) * ROW_ALIGN
    return GossPlan(n, top_k, other_k, cap,
                    (n - top_k) / max(other_k, 1), int(seed),
                    int(1.0 / learning_rate), evict_reason)


def kth_largest(v: jax.Array, k) -> jax.Array:
    """The k-th largest of the non-negative int32 vector ``v`` (entries
    below zero never count): the largest t with count(v >= t) >= k, built
    bit by bit from bit 30 down."""
    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        cnt = jnp.sum((v >= cand).astype(jnp.int32))
        return jnp.where(cnt >= k, cand, t)
    return jax.lax.fori_loop(0, 31, bit, jnp.int32(0))


def select_top(v: jax.Array, k) -> jax.Array:
    """Mask of exactly ``k`` entries of the largest ``v`` (non-negative
    int32; entries below zero are never taken while k others exist), ties
    at the threshold by the lower index."""
    thr = kth_largest(v, k)
    gt = v > thr
    eq = v == thr
    need = k - jnp.sum(gt.astype(jnp.int32))
    # (all of the ties are taken in the usual case, a threshold no other
    # row shares: no prefix sum then)
    ties = jax.lax.cond(
        jnp.sum(eq.astype(jnp.int32)) == need,
        lambda: eq,
        lambda: eq & (blocked_scan(eq.astype(jnp.int32)) <= need))
    return gt | ties


def magnitude_bits(x: jax.Array) -> jax.Array:
    """Non-negative float32 -> int32 that orders the same way."""
    return jax.lax.bitcast_convert_type(jnp.abs(x).astype(jnp.float32),
                                        jnp.int32)


def draw_keys(n: int, seed, it) -> jax.Array:
    """[n] int32 keys in [0, 2^31), a counter-based hash of (seed,
    iteration, row): no state, nothing carried."""
    u = jnp.uint32
    salt = (jnp.asarray(seed).astype(u) * u(0x85EBCA77)
            + jnp.asarray(it).astype(u) * u(0xC2B2AE3D) + u(0x27D4EB2F))
    x = jax.lax.iota(u, n) * u(0x9E3779B1) + salt
    x = x ^ (x >> 16)
    x = x * u(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * u(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> 1).astype(jnp.int32)


def goss_sample(abs_gh: jax.Array, it, seed, top_k: int,
                other_k: int) -> Tuple[jax.Array, jax.Array]:
    """(top, other) masks over the rows of ``abs_gh`` (|g * h| summed over
    the classes): exactly ``top_k`` of the largest, exactly ``other_k`` of
    the rest by the (seed, it) stream."""
    n = abs_gh.shape[0]
    with jax.named_scope("select"):
        top = select_top(magnitude_bits(abs_gh), top_k)
    with jax.named_scope("draw"):
        keys = jnp.where(top, -1, draw_keys(n, seed, it))
        other = select_top(keys, other_k)
    return top, other


def sample_weights(top: jax.Array, other: jax.Array,
                   multiply: float) -> Tuple[jax.Array, jax.Array]:
    """(multiplier on gradient and hessian, in-bag 0/1) per row, float32."""
    inbag = top | other
    mult = jnp.where(other, jnp.float32(multiply),
                     top.astype(jnp.float32))
    return mult, inbag.astype(jnp.float32)


# ------------------------------------------------------------- compaction
def _compact_kernel(blk_ref, dest_ref, first_ref, bins_ref, gh_ref, obins_ref,
                    ogh_ref, acc_ref, *, C: int, T: int, Fp: int,
                    steps: int):
    t = pl.program_id(0)
    b = blk_ref[t]

    @pl.when(t == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # the window moved on by one block: its spill half is the new block
    @pl.when((t > 0) & (b != blk_ref[jnp.maximum(t - 1, 0)]))
    def _():
        acc_ref[:, :C] = acc_ref[:, C:2 * C]
        acc_ref[:, C:] = jnp.zeros((acc_ref.shape[0], C + LANES),
                                   acc_ref.dtype)

    @pl.when(t < T)      # (the steps past the rows only flush the window)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        for j in range(C // LANES):    # (unrolled: the dots overlap)
            # the chunk's in-bag rows land in the two 128-lane blocks of
            # the window from ``off`` on, from lane ``v % 128`` of the
            # first: a [128, 128] rotation moves them, a lane mask splits
            cols = slice(j * LANES, (j + 1) * LANES)
            v = first_ref[0, j]
            off = pl.multiple_of(v & -LANES, LANES)
            w = dest_ref[:, cols] - (b * C + off)                # [1, 128]
            rot = jnp.where(w >= 0, w & (LANES - 1), -1)
            perm = (rows == jnp.broadcast_to(rot, rows.shape)) \
                .astype(jnp.bfloat16)                           # [128, 128]
            x = jnp.concatenate([bins_ref[:, cols].astype(jnp.bfloat16),
                                 gh_ref[:, cols]], axis=0)      # [Fp+G, 128]
            y = jax.lax.dot_general(x, perm, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            # (masked stores, no read: a lane the mask takes is this
            # chunk's column or a later chunk's, which that chunk writes)
            low = jnp.broadcast_to(lanes >= (v & (LANES - 1)), y.shape)
            pltpu.store(acc_ref.at[:, pl.ds(off, LANES)], y, mask=low)
            pltpu.store(acc_ref.at[:, pl.ds(off + LANES, LANES)], y,
                        mask=jnp.logical_not(low))

    # the block is written once, at the last step that names it
    @pl.when((t == steps - 1) | (blk_ref[jnp.minimum(t + 1, steps - 1)] != b))
    def _():
        obins_ref[:] = acc_ref[:Fp, :C].astype(obins_ref.dtype)
        ogh_ref[:] = acc_ref[Fp:, :C].astype(ogh_ref.dtype)


def compact_tables(inbag: jax.Array, Rp: int, capacity: int,
                   C: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(blk [T + E], dest [1, Rp], first [T, 1, C / 128]) int32 of
    ``compact_rows``: the output block each grid step's window starts at,
    every row's column in the compact matrix (-1: out of bag), and the
    column each 128-row chunk's first in-bag row takes, counted from its
    tile's window. ``inbag``: [n] bool, n <= Rp. After the tiles,
    ceil(ROW_ALIGN / C) + 1 steps flush the window and zero the blocks
    behind it: as many as a count within ROW_ALIGN of ``capacity`` leaves
    (``compact_rows``' precondition)."""
    m = jnp.pad(inbag.astype(jnp.int32), (0, Rp - inbag.shape[0]))
    dest = jnp.where(m > 0, blocked_scan(m) - 1, -1)[None, :]
    cnt = jnp.sum(m.reshape(-1, LANES), axis=1)
    first = (blocked_scan(cnt) - cnt).reshape(-1, C // LANES)
    nblk = -(-capacity // C)
    blk = jnp.minimum(first[:, 0] // C, nblk - 1)
    flush = jnp.minimum(blk[-1] + 1 + jnp.arange(-(-ROW_ALIGN // C) + 1,
                                                 dtype=jnp.int32), nblk - 1)
    # (a chunk past the bag, under a clamped block, has nothing to move)
    first = jnp.clip(first - blk[:, None] * C, 0, 2 * C - 1)
    return jnp.concatenate([blk, flush]), dest, first[:, None, :]


@functools.partial(jax.jit, static_argnames=("capacity", "tile_rows",
                                             "interpret"))
def compact_rows(bins_T: jax.Array, gh_T: jax.Array, inbag: jax.Array, *,
                 capacity: int, tile_rows: Optional[int] = None,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """In-bag columns of ``bins_T`` [Fp, Rp] (int8 / int16, values <= 256)
    and ``gh_T`` [G, Rp] bfloat16 (G a multiple of 8), in row order, at the
    front of [Fp, capacity] / [G, capacity]; the columns past the in-bag
    count are zero. ``inbag``: [n] bool; ``capacity`` and Rp multiples of
    ROW_ALIGN. PRECONDITION, the caller's to keep (the count is traced, the
    grid is not): the in-bag count is at most ``capacity`` and at least
    ``capacity - ROW_ALIGN``, as an exact count rounded up to the row tile
    is (``goss_plan``). With fewer rows in the bag the output blocks past
    the flush steps are never written and hold whatever was there.
    ``tile_rows``: a power of two, the most rows of a tile (``compact_tile``
    halves it to fit); None: ``compact_tile``'s own."""
    Fp, Rp = bins_T.shape
    G = gh_T.shape[0]
    C = compact_tile(Fp, bins_T.dtype.itemsize, G, Rp,
                     tile_rows or COMPACT_TILE_MAX)
    T = Rp // C
    blk, dest, first = compact_tables(inbag, Rp, capacity, C)
    steps = blk.shape[0]
    row_in = lambda rows: pl.BlockSpec(
        (rows, C), lambda t, blk: (0, jnp.minimum(t, T - 1)))
    row_out = lambda rows: pl.BlockSpec((rows, C), lambda t, blk: (0, blk[t]))
    return pl.pallas_call(
        functools.partial(_compact_kernel, C=C, T=T, Fp=Fp, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[row_in(1),
                      pl.BlockSpec((None, 1, C // LANES),
                                   lambda t, blk: (jnp.minimum(t, T - 1), 0,
                                                   0),
                                   memory_space=pltpu.SMEM),
                      row_in(Fp), row_in(G)],
            out_specs=[row_out(Fp), row_out(G)],
            scratch_shapes=[pltpu.VMEM((Fp + G, 2 * C + LANES),
                                       jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Fp, capacity), bins_T.dtype),
                   jax.ShapeDtypeStruct((G, capacity), gh_T.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blk, dest, first, bins_T, gh_T)
