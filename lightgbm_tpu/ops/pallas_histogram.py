"""Pallas TPU histogram kernel — the make-or-break hot loop.

Replaces the reference's hand-tuned histogram kernels (ref:
src/io/dense_bin.hpp ConstructHistogram 4-way unrolled CPU loops,
src/treelearner/ocl/histogram16/64/256.cl workgroup-atomic GPU kernels,
src/treelearner/kernels/histogram_16_64_256.cu).

TPU constraints that shape the design (all measured on v5e):
- no fast atomics -> scatter-add formulations (XLA segment_sum) serialize on
  colliding indices: ~1.2 s per 1M x 28 pass at 255 slots;
- random per-row gathers/scatters run at ~30 ns/element, so sort/partition
  based layouts (the reference's per-leaf index lists) are off the table;
- the pure-XLA one-hot einsum formulation is MXU-bound but must materialize
  the [rows, features*bins] one-hot in HBM (~1.8 GB/level): a ~16 ms floor.

So: stream row tiles in place on the sequential TPU grid; per tile build the
bin one-hot [C, F*B] AND the slot one-hot [C, S] in VMEM only, then contract
per gh-channel on the MXU:

    hist[ch] += (slot_onehot * gh[:, ch])^T  @  bin_onehot     # [S, F*B]

Accumulation into the VMEM-resident output across grid steps is safe because
the TPU grid executes sequentially.  Cost scales with S (the slot dimension
rides the MXU), so callers pass the per-level live-slot count rather than a
global maximum.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import quantize
from .layout import feature_layout

NUM_CH = 3


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_feature_layout(num_features: int, max_bin: int) -> Tuple[int, int]:
    """(Fp, Bp) with Bp = pow2 >= max_bin and (Fp * Bp) % 128 == 0.
    Delegates to ops.layout.feature_layout — the ONE layout contract
    shared with the fused kernel, so an adaptive/packed layout change
    cannot drift between the standalone and fused formulations."""
    return feature_layout(num_features, max_bin)


def _hist_kernel(bins_ref, slot_ref, gh_ref, out_ref, oh_ref, *,
                 Bp: int, S: int, Sp: int, nch: int = NUM_CH,
                 quant: bool = False):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    C, Fp = bins_ref.shape
    oh_dt = jnp.int8 if quant else jnp.bfloat16
    acc_dt = jnp.int32 if quant else jnp.float32
    # ---- bin one-hot, built into VMEM scratch in 128-lane-aligned slabs
    # (Mosaic cannot shape-cast [C, Fp, Bp] to [C, Fp*Bp], and sub-128-lane
    # stores are slow); k features share one slab when Bp < 128
    k = max(1, 128 // Bp)
    slab = k * Bp
    iota = jax.lax.broadcasted_iota(jnp.int32, (C, slab), 1)
    bin_in_slab = iota % Bp if k > 1 else iota
    for f0 in range(0, Fp, k):
        sel = bins_ref[:, f0:f0 + 1]
        for j in range(1, k):
            sel = jnp.where(iota // Bp == j, bins_ref[:, f0 + j:f0 + j + 1],
                            sel)
        oh_ref[:, f0 * Bp:f0 * Bp + slab] = (sel == bin_in_slab) \
            .astype(oh_dt)

    # ---- slot one-hot [C, Sp] as a value (negative slot = no contribution)
    s_col = slot_ref[:]                                     # [C, 1]
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (C, Sp), 1)
    soh = (s_col == iota_s).astype(oh_dt)                   # [C, Sp]

    # ---- one MXU contraction per gh channel (quant: the native s8 x s8
    # -> s32 path with EXACT integer accumulation, ops/quantize.py)
    oh = oh_ref[:]
    for ch in range(nch):
        ghs = soh * gh_ref[:, ch:ch + 1].astype(oh_dt)
        part = jax.lax.dot_general(
            ghs, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=acc_dt)                  # [Sp, Fp*Bp]
        out_ref[ch * Sp:(ch + 1) * Sp, :] += part


def _run_hist_kernel(bins_i32, gh, row_slot, *, S, Bp, C, nch, quant,
                     interpret):
    """Shared pallas_call wrapper: [nch*Sp, Fp*Bp] raw accumulator."""
    R, Fp = bins_i32.shape
    Sp = _round_up(max(S, 8), 8)
    R_pad = _round_up(R, C)
    if R_pad != R:
        pad = R_pad - R
        bins_i32 = jnp.pad(bins_i32, ((0, pad), (0, 0)))
        gh = jnp.pad(gh, ((0, pad), (0, 0)))
        row_slot = jnp.pad(row_slot, (0, pad), constant_values=-1)
    T = R_pad // C
    oh_dt = jnp.int8 if quant else jnp.bfloat16
    acc_dt = jnp.int32 if quant else jnp.float32
    kernel = functools.partial(_hist_kernel, Bp=Bp, S=S, Sp=Sp, nch=nch,
                               quant=quant)
    out = pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((C, Fp), lambda t: (t, 0)),
            pl.BlockSpec((C, 1), lambda t: (t, 0)),
            pl.BlockSpec((C, nch), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((nch * Sp, Fp * Bp), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nch * Sp, Fp * Bp), acc_dt),
        scratch_shapes=[pltpu.VMEM((C, Fp * Bp), oh_dt)],
        interpret=interpret,
    )(bins_i32, row_slot[:, None], gh)
    return out.reshape(nch, Sp, Fp, Bp)


@functools.partial(
    jax.jit, static_argnames=("num_slots", "num_bins", "tile_rows",
                              "interpret"))
def build_histograms_pallas(bins_i32: jax.Array, gh3: jax.Array,
                            row_slot: jax.Array, *, num_slots: int,
                            num_bins: int, tile_rows: int = 512,
                            interpret: bool = False) -> jax.Array:
    """Histogram via the Pallas kernel.

    Args:
      bins_i32: [R, Fp] int32, Fp pre-padded so (Fp * num_bins) % 128 == 0,
        padded feature columns all-zero.
      gh3: [R, 3] float32 (grad, hess, weight). Masked rows are excluded
        by their SLOT alone: slot -1 matches no column of the slot
        one-hot, so a masked row contributes nothing even when its gh
        channels are nonzero (callers need not zero them; the XLA
        formulations route slot -1 to a dump bucket with the same
        guarantee — asserted by the masked-row unit tests).
      row_slot: [R] int32 target slot, -1 = ignored.

    Returns: [num_slots, Fp, num_bins, 3] float32.
    """
    S = num_slots
    hist = _run_hist_kernel(bins_i32, gh3, row_slot, S=S, Bp=num_bins,
                            C=tile_rows, nch=NUM_CH, quant=False,
                            interpret=interpret)[:, :S]
    return jnp.transpose(hist, (1, 2, 3, 0))


@functools.partial(
    jax.jit, static_argnames=("num_slots", "num_bins", "tile_rows",
                              "interpret"))
def build_histograms_pallas_cm(bins_i32: jax.Array, gh3: jax.Array,
                               row_slot: jax.Array, *, num_slots: int,
                               num_bins: int, tile_rows: int = 512,
                               interpret: bool = False):
    """Channel-major variant: returns (grad, hess, count) planes
    [S, Fp, Bp] each, avoiding the channel-minor transpose entirely.
    Masked (slot == -1) rows contribute nothing regardless of their gh
    values (see build_histograms_pallas)."""
    S = num_slots
    hist = _run_hist_kernel(bins_i32, gh3, row_slot, S=S, Bp=num_bins,
                            C=tile_rows, nch=NUM_CH, quant=False,
                            interpret=interpret)
    return hist[0, :S], hist[1, :S], hist[2, :S]


@functools.partial(
    jax.jit, static_argnames=("num_slots", "num_bins", "tile_rows",
                              "quant_bits", "interpret"))
def build_histograms_pallas_quant(bins_i32: jax.Array, gh3: jax.Array,
                                  row_slot: jax.Array, *, num_slots: int,
                                  num_bins: int, quant_bits: int = 16,
                                  seed=0, tile_rows: int = 512,
                                  interpret: bool = False):
    """Quantized-accumulator variant (``tpu_quantized_grad``): grad/hess
    stochastically rounded onto the fixed-point grid (ops/quantize.py),
    int8 channel x int8 one-hot MXU dots accumulate into int32 EXACTLY,
    and the per-level f32 rescale happens here at the decode boundary.
    Returns (grad, hess, count) f32 planes [S, Fp, Bp], like _cm."""
    S = num_slots
    g, h, w = gh3[:, 0], gh3[:, 1], gh3[:, 2]
    scales = quantize.quant_scales(g, h, quant_bits)
    qg, qh = quantize.quantize_gh(g, h, scales, quant_bits, seed)
    rows = quantize.encode_channels(qg, qh, w, quant_bits)
    nch = len(rows)
    gh_q = jnp.stack(rows, axis=1)                          # [R, nch] int8
    hist = _run_hist_kernel(bins_i32, gh_q, row_slot, S=S, Bp=num_bins,
                            C=tile_rows, nch=nch, quant=True,
                            interpret=interpret)
    Sp = hist.shape[1]
    Fp, Bp = hist.shape[2], hist.shape[3]
    planes = [hist[c].reshape(Sp, Fp * Bp).T for c in range(nch)]
    g_s, h_s, c_s = quantize.decode_sums(planes, scales, quant_bits)
    back = lambda x: x.T.reshape(Sp, Fp, Bp)[:S]
    return back(g_s), back(h_s), back(c_s)
