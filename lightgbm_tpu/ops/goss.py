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
  compaction: per tile of C rows a ``[2C, C]`` permutation one-hot is built
  from each row's destination (an XLA prefix sum of the mask) and one MXU
  dot moves the tile's in-bag columns into a 2C-wide window of the output;
  the window's first half is the output block the grid step names through
  scalar prefetch, the second half the spill into the next block. Bin
  values (<= 255) and bfloat16 channels are exact in bfloat16, every
  output element has one non-zero term: the copy is exact.

On a v5e (PR 35's step 0; 28,000,000 rows -> 8,400,896 columns of 32 int8
bin rows + 8 bfloat16 channel rows): select 5.2 ms (a full ``jnp.sort`` of
the vector: 88.8), draw 1.7 (the keys are computed inside the counting
passes, which then read one byte a row), the mask's prefix sum 2.3 in
blocks (6.5 flat), the compaction 42.5 / 35.8 / 48.0 ms at tiles of 256 /
512 / 1,024 rows (0.35 us a grid step against 2C compares and 2C x 40
multiply-adds a row), 6 % of what the HBM would need for the move; the
same move in XLA (``nonzero`` 318 ms, a row gather of a row-major copy and a
transpose 134, element gathers of the channels 78) is 15 x slower.

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
COMPACT_TILE = 512        # rows of one compaction tile (and output block)


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
def _compact_kernel(blk_ref, dest_ref, bins_ref, gh_ref, obins_ref, ogh_ref,
                    acc_ref, *, C: int, T: int, Fp: int):
    t = pl.program_id(0)
    b = blk_ref[t]

    @pl.when(t == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # the window moved on by one block: its spill half is the new block
    @pl.when((t > 0) & (b != blk_ref[jnp.maximum(t - 1, 0)]))
    def _():
        acc_ref[:, :C] = acc_ref[:, C:]
        acc_ref[:, C:] = jnp.zeros((acc_ref.shape[0], C), acc_ref.dtype)

    @pl.when(t < T)      # (the steps past the rows only flush the window)
    def _():
        local = dest_ref[:] - b * C                            # [1, C]
        perm = (jax.lax.broadcasted_iota(jnp.int32, (2 * C, C), 0)
                == jnp.broadcast_to(local, (2 * C, C))) \
            .astype(jnp.bfloat16)                              # [2C, C]
        x = jnp.concatenate([bins_ref[:].astype(jnp.bfloat16), gh_ref[:]],
                            axis=0)                            # [Fp+G, C]
        acc_ref[:] += jax.lax.dot_general(
            x, perm, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    obins_ref[:] = acc_ref[:Fp, :C].astype(obins_ref.dtype)
    ogh_ref[:] = acc_ref[Fp:, :C].astype(ogh_ref.dtype)


def compact_tables(inbag: jax.Array, Rp: int, capacity: int,
                   C: int) -> Tuple[jax.Array, jax.Array]:
    """(blk [T + E] int32, dest [1, Rp] int32) of ``compact_rows``: the
    output block each grid step's window starts at, and every row's
    column in the compact matrix (-1: out of bag). ``inbag``: [n] bool,
    n <= Rp. After the tiles, ROW_ALIGN // C + 1 steps flush the window
    and zero the blocks behind it: as many as a count within ROW_ALIGN of
    ``capacity`` leaves (``compact_rows``' precondition)."""
    m = jnp.pad(inbag.astype(jnp.int32), (0, Rp - inbag.shape[0]))
    csum = blocked_scan(m)
    dest = jnp.where(m > 0, csum - 1, -1)[None, :]
    ends = csum.reshape(-1, C)[:, -1]
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    blk = before // C
    nblk = capacity // C
    flush = jnp.minimum(blk[-1] + 1 + jnp.arange(ROW_ALIGN // C + 1,
                                                 dtype=jnp.int32), nblk - 1)
    return jnp.concatenate([jnp.minimum(blk, nblk - 1), flush]), dest


@functools.partial(jax.jit, static_argnames=("capacity", "tile_rows",
                                             "interpret"))
def compact_rows(bins_T: jax.Array, gh_T: jax.Array, inbag: jax.Array, *,
                 capacity: int, tile_rows: int = COMPACT_TILE,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """In-bag columns of ``bins_T`` [Fp, Rp] (int8 / int16, values <= 256)
    and ``gh_T`` [G, Rp] bfloat16 (G a multiple of 8), in row order, at the
    front of [Fp, capacity] / [G, capacity]; the columns past the in-bag
    count are zero. ``inbag``: [n] bool; ``capacity`` and Rp multiples of
    ROW_ALIGN. PRECONDITION, the caller's to keep (the count is traced, the
    grid is not): the in-bag count is at most ``capacity`` and at least
    ``capacity - ROW_ALIGN``, as an exact count rounded up to the row tile
    is (``goss_plan``). With fewer rows in the bag the output blocks past
    the flush steps are never written and hold whatever was there."""
    Fp, Rp = bins_T.shape
    G = gh_T.shape[0]
    C = tile_rows
    assert Rp % C == 0 and capacity % C == 0 and ROW_ALIGN % C == 0
    T = Rp // C
    blk, dest = compact_tables(inbag, Rp, capacity, C)
    steps = blk.shape[0]
    row_in = lambda rows: pl.BlockSpec(
        (rows, C), lambda t, blk: (0, jnp.minimum(t, T - 1)))
    row_out = lambda rows: pl.BlockSpec((rows, C), lambda t, blk: (0, blk[t]))
    return pl.pallas_call(
        functools.partial(_compact_kernel, C=C, T=T, Fp=Fp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[row_in(1), row_in(Fp), row_in(G)],
            out_specs=[row_out(Fp), row_out(G)],
            scratch_shapes=[pltpu.VMEM((Fp + G, 2 * C), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((Fp, capacity), bins_T.dtype),
                   jax.ShapeDtypeStruct((G, capacity), gh_T.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(blk, dest, bins_T, gh_T)
