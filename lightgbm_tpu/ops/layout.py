"""Histogram-plane layout: the ONE source of truth for how (feature, bin)
pairs map onto the kernels' 128-lane-aligned flat axis.

Two layouts:

- **padded** (`feature_layout`): every feature widened to the global pow2
  bin count ``Bp`` and the feature count rounded so ``(Fp * Bp) % 128 ==
  0``.  The fused kernels and the driver's matrix layout both take it
  from here (``ops/fused_level`` re-exports it).
- **packed** (`packed_feature_layout`): adaptive per-feature bin widths
  (arxiv 2603.00326).  Each feature gets its own pow2 width ``>= its
  effective bin count`` and features are grouped by width class, each
  class region padded to the 128 lane quantum, instead of padding every
  feature to the global ``Bp``.  On heterogeneous-cardinality data this
  shrinks the ``[C, FB]`` one-hot scratch and the ``[FB, nch*Sp]``
  accumulator — the VMEM/HBM terms that set the fused kernel's floor.
  The packed layout is a pure re-indexing: per-(feature, bin) sums are
  accumulated in the same row-tile order as the padded layout, so the
  decoded histograms are BIT-IDENTICAL to the padded ones (the
  adaptive-bin A/B contract; the caller must keep the row-tile width at
  the padded formula for that to hold — see
  ``fused_level.level_pass``).

The byte model (`hist_plane_bytes`) quantifies what the histogram plane
reads, builds, and keeps per level pass — the figure the driver exports
as ``hist.bytes_per_level`` and the bench gates as
``hist_bytes_per_iter``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import numpy as np


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


LANE = 128
MIN_WIDTH = 8   # sublane quantum: a feature slab is never narrower


def feature_layout(num_features: int, max_bin: int) -> Tuple[int, int]:
    """(Fp, Bp) with Bp = pow2 >= max_bin and (Fp * Bp) % 128 == 0.

    Fp is the one-hot feature count (>= num_features); padded features
    must carry bin 0 everywhere and be masked out of the split scan.
    The single shared contract of the fused and standalone kernels.
    """
    Bp = max(MIN_WIDTH, _next_pow2(max_bin))
    quota = max(1, LANE // min(Bp, LANE))
    Fp = _round_up(max(num_features, 1), quota)
    return Fp, Bp


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Adaptive per-feature bin packing (hashable: rides jit static args).

    ``classes``: ordered (width, count) groups; features appear in the
    kernel's bin matrix in ``feat_order`` (grouped by width class), each
    class's flat region padded to the 128 lane quantum.  ``f_oh``/``bp``
    keep the LOGICAL padded layout the split search / pools / route
    tables stay on; only the kernel's flat axis is packed.
    """
    classes: Tuple[Tuple[int, int], ...]   # (width, n_features) per class
    feat_order: Tuple[int, ...]            # logical ids, kernel row order
    widths: Tuple[int, ...]                # per feat_order entry
    fb: int                                # packed flat width (% 128 == 0)
    f_oh: int                              # logical padded feature count
    bp: int                                # logical pow2 bin width

    # ---- derived static index maps (numpy, cached per layout) ----
    @functools.cached_property
    def flat_offsets(self) -> np.ndarray:
        """[len(feat_order)] flat offset of each packed feature's slab."""
        offs = np.zeros(len(self.feat_order), np.int64)
        o = 0
        j = 0
        for w, cnt in self.classes:
            for _ in range(cnt):
                offs[j] = o
                o += w
                j += 1
            o = _round_up(o, LANE)
        return offs

    @functools.cached_property
    def row_offsets(self) -> np.ndarray:
        """[n_classes] first bin-matrix row of each class region."""
        out = np.zeros(len(self.classes), np.int64)
        r = 0
        for i, (_, cnt) in enumerate(self.classes):
            out[i] = r
            r += cnt
        return out

    @functools.cached_property
    def class_flat_offsets(self) -> np.ndarray:
        """[n_classes] flat offset of each class region."""
        out = np.zeros(len(self.classes), np.int64)
        o = 0
        for i, (w, cnt) in enumerate(self.classes):
            out[i] = o
            o = _round_up(o + w * cnt, LANE)
        return out

    @functools.cached_property
    def row_of_feat(self) -> np.ndarray:
        """[f_oh] bin-matrix row of each logical feature (-1: dropped)."""
        pos = np.full(self.f_oh, -1, np.int32)
        pos[list(self.feat_order)] = np.arange(len(self.feat_order))
        return pos

    @functools.cached_property
    def padded_to_packed(self) -> np.ndarray:
        """[f_oh * bp] -> packed flat index (0 where invalid)."""
        idx = np.zeros(self.f_oh * self.bp, np.int32)
        for j, f in enumerate(self.feat_order):
            w = self.widths[j]
            o = int(self.flat_offsets[j])
            idx[f * self.bp: f * self.bp + w] = o + np.arange(w)
        return idx

    @functools.cached_property
    def padded_valid(self) -> np.ndarray:
        """[f_oh * bp] bool: position exists in the packed layout."""
        v = np.zeros(self.f_oh * self.bp, bool)
        for j, f in enumerate(self.feat_order):
            v[f * self.bp: f * self.bp + self.widths[j]] = True
        return v

    @functools.cached_property
    def packed_to_padded(self) -> np.ndarray:
        """[fb] -> padded flat index (0 where class padding)."""
        idx = np.zeros(self.fb, np.int32)
        for j, f in enumerate(self.feat_order):
            w = self.widths[j]
            o = int(self.flat_offsets[j])
            idx[o:o + w] = f * self.bp + np.arange(w)
        return idx

    @functools.cached_property
    def packed_valid(self) -> np.ndarray:
        v = np.zeros(self.fb, bool)
        for j in range(len(self.feat_order)):
            o = int(self.flat_offsets[j])
            v[o:o + self.widths[j]] = True
        return v

    @functools.cached_property
    def feat_of_packed(self) -> np.ndarray:
        """[fb] logical feature id per packed position (0 where pad)."""
        f = np.zeros(self.fb, np.int32)
        for j, fid in enumerate(self.feat_order):
            o = int(self.flat_offsets[j])
            f[o:o + self.widths[j]] = fid
        return f


def packed_feature_layout(num_bin_per_feat, max_bin: int,
                          f_oh: Optional[int] = None) -> PackedLayout:
    """Adaptive layout from per-feature effective bin counts.

    Features are grouped by pow2 width class (descending width, so the
    widest slabs come first and the leftovers pack the narrow tail);
    padding features (num_bin <= 0) are dropped from the kernel layout
    entirely — their decoded planes are zero by construction.
    """
    nb = np.asarray(num_bin_per_feat, np.int64)
    F = int(nb.shape[0])
    Fp, Bp = feature_layout(F, max_bin)
    if f_oh is None:
        f_oh = Fp
    widths_all = np.where(nb > 0,
                          np.maximum(MIN_WIDTH,
                                     2 ** np.ceil(np.log2(
                                         np.maximum(nb, 2))).astype(np.int64)),
                          0)
    classes = []
    feat_order = []
    widths = []
    for w in sorted({int(x) for x in widths_all if x > 0}, reverse=True):
        feats = [int(f) for f in np.nonzero(widths_all == w)[0]]
        classes.append((w, len(feats)))
        feat_order.extend(feats)
        widths.extend([w] * len(feats))
    fb = 0
    for w, cnt in classes:
        fb = _round_up(fb + w * cnt, LANE)
    fb = max(fb, LANE)
    return PackedLayout(classes=tuple(classes), feat_order=tuple(feat_order),
                        widths=tuple(widths), fb=int(fb), f_oh=int(f_oh),
                        bp=int(Bp))


def hist_plane_bytes(fb: int, nch: int, sp: int, rows_padded: int,
                     tile_rows: int, quant_bits: int) -> int:
    """Bytes the histogram plane touches per level pass: the one-hot
    (every [FB, C] of it is built once per row tile and read by the MXU:
    through the VMEM scratch in the table form, where both dots re-read
    it, slab by slab with no scratch in the bins form,
    ops/fused_level.level_build; the bytes built are the same), the
    [FB, nch*Sp] accumulator, and the [8, R] gh channel stream.
    Quantization (``tpu_quantized_grad``) halves the one-hot and gh
    element widths (int8 channels vs bf16); adaptive bins shrink ``fb``.
    The bins/leaf/W streams are layout-independent and excluded: this
    figure isolates exactly what the three histogram-plane cuts move."""
    oh_elem = 1 if quant_bits else 2
    gh_elem = 1 if quant_bits else 2
    acc_elem = 4   # f32 or int32 accumulator
    n_tiles = max(1, rows_padded // max(1, tile_rows))
    oh = fb * tile_rows * oh_elem * n_tiles
    acc = fb * nch * sp * acc_elem
    gh = 8 * rows_padded * gh_elem
    return int(oh + acc + gh)
