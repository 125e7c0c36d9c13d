"""On-device gradient/hessian histogram construction.

TPU-native replacement for the reference's histogram kernels — the hottest
loop of GBDT training (ref: src/io/dense_bin.hpp ConstructHistogram,
src/treelearner/ocl/histogram{16,64,256}.cl, src/treelearner/kernels/
histogram_16_64_256.cu).  The reference uses per-thread/per-workgroup
scatter-adds with atomics; TPUs have no fast atomics, so the formulations here
are dense-array programs XLA can tile:

- ``segment``: one ``jax.ops.segment_sum`` over a joint (slot, feature, bin)
  index per row-chunk, scanned over chunks.  Works for any number of target
  leaves (depth-wise frontier batches).
- ``onehot``: builds a ``[chunk, F, B]`` one-hot of the bin indices and
  contracts it with (grad, hess, count) on the MXU.  Fastest when targeting a
  single leaf (leaf-wise growth; the smaller-child + subtraction trick,
  ref: serial_tree_learner.cpp:423-425).

These serve the XLA growers (models/learner.py); the fused engine builds
its histograms inside ops/fused_level.level_pass.

Histograms are ``float32 [num_slots, F, B, 3]`` with channels (sum_grad,
sum_hess, count); the reference accumulates float64 on CPU and float32 on GPU
with acceptable AUC drift (ref: docs/GPU-Performance.rst:130-160) — we match
the GPU precision contract by default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# channels: grad, hess, count
NUM_CH = 3

# the one-hot / scatter-chunk byte budget the row-chunk size derives
# from (was a bare ``1 << 26`` literal; named so the telemetry the
# driver emits — hist.bytes_per_level — and this bound share a source)
HIST_CHUNK_BUDGET_BYTES = 1 << 26


def _choose_chunk(num_rows: int, num_features: int, num_bins: int,
                  elem_bytes: int = 4,
                  budget_bytes: int = HIST_CHUNK_BUDGET_BYTES) -> int:
    """Row-chunk size keeping the materialized one-hot under
    ``budget_bytes``.  ``elem_bytes`` is the accumulated element width —
    4 for the f32 default, 1/2 for the quantized int8/int16 grids
    (ops/quantize.quant_elem_bytes), so quantization buys
    proportionally larger chunks under the same budget."""
    c = budget_bytes // max(1, num_features * num_bins * elem_bytes)
    c = max(256, min(int(c), 1 << 15, max(256, num_rows)))
    # round to a multiple of 256 for clean tiling
    return max(256, (c // 256) * 256)


def _pad_rows(arrs, chunk: int, pad_values):
    n = arrs[0].shape[0]
    rem = (-n) % chunk
    if rem == 0:
        return arrs
    out = []
    for a, pv in zip(arrs, pad_values):
        pad_width = [(0, rem)] + [(0, 0)] * (a.ndim - 1)
        out.append(jnp.pad(a, pad_width, constant_values=pv))
    return out


@functools.partial(jax.jit, static_argnames=("num_slots", "num_bins", "impl",
                                             "quant_bits"))
def build_histograms(bins: jax.Array, gh: jax.Array, row_slot: jax.Array,
                     *, num_slots: int, num_bins: int,
                     impl: str = "auto", quant_bits: int = 0,
                     seed=0) -> jax.Array:
    """Histograms for a batch of target leaves.

    Args:
      bins: ``[R, F]`` uint8/uint16 binned features.
      gh: ``[R, 3]`` float32 (grad, hess, count-weight); rows excluded by
        bagging carry zeros (and rows with slot -1 contribute nothing
        regardless of their gh values — the dump-bucket route).
      row_slot: ``[R]`` int32 — target slot of each row, or -1 to ignore.
        (Computed by the caller as ``leaf_to_slot[row_leaf]``.)
      num_slots: static number of target leaves.
      num_bins: static padded bin count per feature.
      quant_bits: 0 (f32 accumulation, default), 8 or 16 — grad/hess
        stochastically rounded onto the fixed-point grid
        (ops/quantize.py) and accumulated EXACTLY in int32 via the
        segment formulation, rescaled to f32 here before return.

    Returns: ``[num_slots, F, num_bins, 3]`` float32.
    """
    from . import quantize
    R, F = bins.shape
    if quant_bits:
        scales = quantize.quant_scales(gh[:, 0], gh[:, 1], quant_bits)
        qg, qh = quantize.quantize_gh(gh[:, 0], gh[:, 1], scales,
                                      quant_bits, seed)
        qw = (gh[:, 2] > 0).astype(jnp.int32)
        gh = jnp.stack([qg, qh, qw], axis=1)        # int32 grid values
        impl = "segment"                            # int32 segment sums
    if impl == "auto":
        impl = "onehot" if num_slots <= 2 else "segment"
    chunk = _choose_chunk(R, F, num_bins,
                          elem_bytes=quantize.quant_elem_bytes(quant_bits))
    bins_p, gh_p, slot_p = _pad_rows(
        [bins, gh, row_slot], chunk, [0, 0.0, -1])
    n_chunks = bins_p.shape[0] // chunk
    bins_c = bins_p.reshape(n_chunks, chunk, F)
    gh_c = gh_p.reshape(n_chunks, chunk, NUM_CH)
    slot_c = slot_p.reshape(n_chunks, chunk)

    if impl == "segment":
        fb = F * num_bins
        f_off = (jnp.arange(F, dtype=jnp.int32) * num_bins)[None, :]

        def body(hist, xs):
            b, g, s = xs
            idx = jnp.where(s[:, None] >= 0,
                            s[:, None] * fb + f_off + b.astype(jnp.int32),
                            num_slots * fb)  # dump bucket
            data = jnp.broadcast_to(g[:, None, :], (chunk, F, NUM_CH))
            seg = jax.ops.segment_sum(data.reshape(-1, NUM_CH),
                                      idx.reshape(-1),
                                      num_segments=num_slots * fb + 1)
            return hist + seg[:num_slots * fb], None

        acc_dt = jnp.int32 if quant_bits else jnp.float32
        init = jnp.zeros((num_slots * fb, NUM_CH), acc_dt)
        hist, _ = jax.lax.scan(body, init, (bins_c, gh_c, slot_c))
        hist = hist.reshape(num_slots, F, num_bins, NUM_CH)
        if quant_bits:
            # the ONE f32 rescale boundary — everything downstream
            # (split search) is unchanged above it
            hist = jnp.stack(
                [hist[..., 0].astype(jnp.float32) * scales[0],
                 hist[..., 1].astype(jnp.float32) * scales[1],
                 hist[..., 2].astype(jnp.float32)], axis=-1)
        return hist

    # one-hot matmul formulation: contraction over rows rides the MXU
    iota_b = jnp.arange(num_bins, dtype=jnp.int32)

    def body(hist, xs):
        b, g, s = xs
        onehot = (b.astype(jnp.int32)[:, :, None] == iota_b).astype(jnp.float32)
        if num_slots == 1:
            ghm = jnp.where(s[:, None] == 0, g, 0.0)
            h = jnp.einsum("rfb,rc->fbc", onehot, ghm,
                           preferred_element_type=jnp.float32)
            return hist + h[None], None
        slot_oh = (s[:, None] == jnp.arange(num_slots, dtype=jnp.int32)
                   ).astype(jnp.float32)  # [C, S]
        ghs = slot_oh[:, :, None] * g[:, None, :]  # [C, S, 3]
        h = jnp.einsum("rfb,rsc->sfbc", onehot, ghs,
                       preferred_element_type=jnp.float32)
        return hist + h, None

    init = jnp.zeros((num_slots, F, num_bins, NUM_CH), jnp.float32)
    hist, _ = jax.lax.scan(body, init, (bins_c, gh_c, slot_c))
    return hist


def histogram_subtract(parent: jax.Array, child: jax.Array) -> jax.Array:
    """Sibling histogram via subtraction (ref: feature_histogram.hpp Subtract,
    serial_tree_learner.cpp:423-425 smaller/larger-leaf trick)."""
    return parent - child
