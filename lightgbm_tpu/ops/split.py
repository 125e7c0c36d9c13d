"""On-device best-split search over histograms.

TPU-native replacement for the reference's per-(leaf,feature) sequential
threshold scan (ref: src/treelearner/feature_histogram.hpp:85
FindBestThreshold, :858-1090 FindBestThresholdSequentially).  The reference
walks bins one-by-one per feature on the host; here the whole
``[slots, features, bins]`` tensor is scanned at once with cumulative sums and
an argmax — no host round trip per leaf (the design wart called out in
SURVEY.md §3.5).

Semantics replicated from the reference dispatch
(feature_histogram.hpp:158-200 FuncForNumricalL3):
- missing None  -> reverse scan only (default_left=True always).
- missing Zero  -> reverse + forward scans, the zero (default) bin excluded
  from the directional accumulation so its rows ride the default direction;
  threshold == default_bin (forward) / default_bin-1 (reverse) skipped.
- missing NaN   -> reverse + forward; the NaN bin (last) is excluded from the
  reverse accumulation so NaN rows go left; forward leaves it on the right.
- num_bin <= 2  -> single scan (forward iff missing NaN).
- Ties: reverse beats forward; earlier feature beats later; within forward the
  smallest threshold wins, within reverse the largest (scan orders).

Gain/leaf-output formulas are the closed-form Newton expressions with
L1 thresholding, max_delta_step clipping and path smoothing
(ref: feature_histogram.hpp:737-856 ThresholdL1 / CalculateSplittedLeafOutput /
GetLeafGain / GetSplitGains).

Precision contract: every scan in this module consumes f32 (grad, hess,
count) planes.  The quantized histogram path (``tpu_quantized_grad``,
ops/quantize.py) rescales its exact int32 fixed-point sums to f32 AT the
decode boundary (ops/fused_level.hist_planes) — this module is unchanged
above that boundary, so the split semantics are identical between the
f32 and quantized planes up to the quantization noise already present in
the sums.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


class SplitParams(NamedTuple):
    """Static split-finding hyper-parameters (subset of ref Config used by
    FeatureHistogram)."""
    lambda_l1: float = 0.0
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    lambda_l2: float = 0.0
    max_delta_step: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    path_smooth: float = 0.0
    monotone_penalty: float = 0.0
    # categorical split search (ref: config.h cat_l2/cat_smooth/...)
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    min_data_per_group: int = 100


def threshold_l1(s, l1):
    # ref: feature_histogram.hpp:737 ThresholdL1
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def calculate_leaf_output(sum_grad, sum_hess, p: SplitParams,
                          num_data=None, parent_output=0.0, l2=None):
    """Closed-form Newton leaf value
    (ref: feature_histogram.hpp:742 CalculateSplittedLeafOutput).
    ``l2`` overrides p.lambda_l2 (categorical splits add cat_l2)."""
    ret = -threshold_l1(sum_grad, p.lambda_l1) / (
        sum_hess + (p.lambda_l2 if l2 is None else l2))
    if p.max_delta_step > 0:
        ret = jnp.clip(ret, -p.max_delta_step, p.max_delta_step)
    if p.path_smooth > 0 and num_data is not None:
        n_s = num_data / p.path_smooth
        ret = ret * n_s / (n_s + 1.0) + parent_output / (n_s + 1.0)
    return ret


def leaf_gain_given_output(sum_grad, sum_hess, p: SplitParams, output,
                           l2=None):
    # ref: feature_histogram.hpp:846 GetLeafGainGivenOutput
    sg = threshold_l1(sum_grad, p.lambda_l1)
    return -(2.0 * sg * output
             + (sum_hess + (p.lambda_l2 if l2 is None else l2))
             * output * output)


def leaf_gain(sum_grad, sum_hess, p: SplitParams, num_data=None,
              parent_output=0.0, l2=None):
    # ref: feature_histogram.hpp:828 GetLeafGain
    if p.max_delta_step <= 0 and p.path_smooth <= 0:
        sg = threshold_l1(sum_grad, p.lambda_l1)
        return (sg * sg) / (sum_hess + (p.lambda_l2 if l2 is None else l2))
    out = calculate_leaf_output(sum_grad, sum_hess, p, num_data,
                                parent_output, l2)
    return leaf_gain_given_output(sum_grad, sum_hess, p, out, l2)


class BestSplit(NamedTuple):
    """Per-slot best split record — the SplitInfo analog
    (ref: src/treelearner/split_info.hpp:22)."""
    feature: jax.Array        # int32 [S], inner feature index, -1 if none
    threshold: jax.Array      # int32 [S], bin threshold (left: bin <= t)
    default_left: jax.Array   # bool  [S]
    gain: jax.Array           # f32   [S], gain minus shift; -inf if invalid
    left_output: jax.Array    # f32   [S]
    right_output: jax.Array
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array     # f32 (weighted count channel)
    right_sum_grad: jax.Array
    right_sum_hess: jax.Array
    right_count: jax.Array
    cat_flag: jax.Array       # bool [S] categorical split?
    cat_mask: jax.Array       # bool [S, B] bins routed left (cat only)


def _no_cat(S: int, B: int):
    return (jnp.zeros((S,), bool), jnp.zeros((S, B), bool))


@functools.partial(jax.jit, static_argnames=("params",))
def best_numerical_split(hist: jax.Array, num_bin_per_feat: jax.Array,
                         missing_type: jax.Array, default_bin: jax.Array,
                         feature_mask: jax.Array, monotone: jax.Array,
                         params: SplitParams,
                         parent_output: jax.Array) -> BestSplit:
    """Best numerical split per slot from a channel-minor histogram.

    Args:
      hist: ``[S, F, B, 3]`` float32 (grad, hess, count).
      (see best_numerical_split_cm for the remaining args)
    """
    return best_numerical_split_cm(
        hist[..., 0], hist[..., 1], hist[..., 2], num_bin_per_feat,
        missing_type, default_bin, feature_mask, monotone, params,
        parent_output)


@functools.partial(jax.jit,
                   static_argnames=("params", "per_feature_gains",
                                    "use_bounds"))
def best_numerical_split_cm(grad: jax.Array, hess: jax.Array,
                            cnt: jax.Array, num_bin_per_feat: jax.Array,
                            missing_type: jax.Array, default_bin: jax.Array,
                            feature_mask: jax.Array, monotone: jax.Array,
                            params: SplitParams,
                            parent_output: jax.Array,
                            per_feature_gains: bool = False,
                            use_bounds: bool = False,
                            bound_lo: jax.Array = None,
                            bound_hi: jax.Array = None,
                            leaf_depth: jax.Array = None,
                            cegb_delta: jax.Array = None,
                            bound_lo_plane: jax.Array = None,
                            bound_hi_plane: jax.Array = None) -> BestSplit:
    """Best numerical split per slot (channel-major inputs — TPU relayouts
    of channel-minor ``[..., 3]`` arrays are expensive, so the hot path keeps
    grad/hess/count as separate ``[S, F, B]`` planes).

    Args:
      grad/hess/cnt: ``[S, F, B]`` float32 histogram planes.
      num_bin_per_feat: ``[F]`` int32 actual bin counts (rest is padding).
      missing_type: ``[F]`` int32 (0 none / 1 zero / 2 nan).
      default_bin: ``[F]`` int32 (bin of value 0; the zero-missing bin).
      feature_mask: ``[F]`` bool — feature_fraction / interaction constraints.
      monotone: ``[F]`` int32 in {-1, 0, 1}.
      parent_output: ``[S]`` f32 leaf outputs (for path smoothing).

    Returns a ``BestSplit`` with per-slot winners.
    """
    S, F, B = grad.shape
    p = params
    # feature_mask may be [F] (global) or [S, F] (per-slot validity, used
    # by the voting-parallel learner whose shards only hold globally-summed
    # histograms for vote-winning features)
    fm3 = (feature_mask[None, :, None] if feature_mask.ndim == 1
           else feature_mask[:, :, None])

    t_iota = jnp.arange(B, dtype=jnp.int32)[None, None, :]
    nb = num_bin_per_feat[None, :, None]          # [1,F,1]
    mt = missing_type[None, :, None]
    db = default_bin[None, :, None]
    is_pad = t_iota >= nb

    # leaf totals: every feature's bins partition the same rows, so feature 0's
    # bin sums are the leaf totals (padding bins hold no mass)
    tot_g = jnp.sum(grad[:, 0, :], axis=1)[:, None, None]   # [S,1,1]
    tot_h = (jnp.sum(hess[:, 0, :], axis=1)
             + 2.0 * K_EPSILON)[:, None, None]
    tot_c = jnp.sum(cnt[:, 0, :], axis=1)[:, None, None]

    parent_out = parent_output[:, None, None]
    num_data = tot_c
    gain_shift = leaf_gain(tot_g, tot_h, p, num_data, parent_out)
    min_gain_shift = gain_shift + p.min_gain_to_split      # [S,1,1]

    nan_bin = nb - 1
    is_missing_bin_fwd = (mt == MISSING_ZERO) & (t_iota == db)
    is_missing_bin_rev = is_missing_bin_fwd | ((mt == MISSING_NAN)
                                               & (t_iota == nan_bin))

    def directional_best(excl_missing_mask, thresh_valid, reverse):
        """Cumulative scan in one direction; missing-bin mass excluded from
        the accumulated side so it rides the default direction."""
        m = (~is_pad) & (~excl_missing_mask)
        g = jnp.where(m, grad, 0.0)
        h = jnp.where(m, hess, 0.0)
        c = jnp.where(m, cnt, 0.0)
        if not reverse:
            left_g = jnp.cumsum(g, axis=2)
            left_h = jnp.cumsum(h, axis=2) + K_EPSILON
            left_c = jnp.cumsum(c, axis=2)
            right_g = tot_g - left_g
            right_h = tot_h - left_h
            right_c = tot_c - left_c
        else:
            # right side accumulates bins > t (scan from the right)
            rg = jnp.cumsum(g[..., ::-1], axis=2)[..., ::-1]
            rh = jnp.cumsum(h[..., ::-1], axis=2)[..., ::-1]
            rc = jnp.cumsum(c[..., ::-1], axis=2)[..., ::-1]
            # threshold t: right = bins >= t+1
            right_g = jnp.concatenate([rg[..., 1:], jnp.zeros_like(rg[..., :1])],
                                      axis=2)
            right_h = jnp.concatenate([rh[..., 1:], jnp.zeros_like(rh[..., :1])],
                                      axis=2) + K_EPSILON
            right_c = jnp.concatenate([rc[..., 1:], jnp.zeros_like(rc[..., :1])],
                                      axis=2)
            left_g = tot_g - right_g
            left_h = tot_h - right_h
            left_c = tot_c - right_c

        ok = (thresh_valid
              & (left_c >= p.min_data_in_leaf)
              & (right_c >= p.min_data_in_leaf)
              & (left_h >= p.min_sum_hessian_in_leaf)
              & (right_h >= p.min_sum_hessian_in_leaf)
              & fm3)

        mono = monotone[None, :, None]
        lo = calculate_leaf_output(left_g, left_h, p, left_c, parent_out)
        ro = calculate_leaf_output(right_g, right_h, p, right_c, parent_out)
        if bound_hi_plane is not None:
            # ADVANCED monotone mode: per-(feature, bin-SEGMENT) bounds
            # (ref: monotone_constraints.hpp:856 AdvancedLeafConstraints —
            # a constraint from an adjacent leaf applies only to the part
            # of this leaf's region the neighbor shadows, so a candidate
            # child that escapes the shadow escapes the bound). The
            # child's bound = extremum of the plane over the bins it
            # covers: prefix scans for the left child, suffix for the
            # right; the missing-bin mass rides the default direction and
            # folds its plane entry into that side.
            inf = jnp.inf
            hi_pl = jnp.where(is_pad, inf, bound_hi_plane)
            lo_pl = jnp.where(is_pad, -inf, bound_lo_plane)
            hi_pref = jax.lax.cummin(hi_pl, axis=2)
            lo_pref = jax.lax.cummax(lo_pl, axis=2)
            hi_suf = jax.lax.cummin(hi_pl[..., ::-1], axis=2)[..., ::-1]
            lo_suf = jax.lax.cummax(lo_pl[..., ::-1], axis=2)[..., ::-1]
            hi_right = jnp.concatenate(
                [hi_suf[..., 1:], jnp.full_like(hi_suf[..., :1], inf)],
                axis=2)
            lo_right = jnp.concatenate(
                [lo_suf[..., 1:], jnp.full_like(lo_suf[..., :1], -inf)],
                axis=2)
            mm = excl_missing_mask & ~is_pad
            miss_hi = jnp.min(jnp.where(mm, hi_pl, inf), axis=2,
                              keepdims=True)
            miss_lo = jnp.max(jnp.where(mm, lo_pl, -inf), axis=2,
                              keepdims=True)
            if reverse:     # missing rides LEFT
                l_hi = jnp.minimum(hi_pref, miss_hi)
                l_lo = jnp.maximum(lo_pref, miss_lo)
                r_hi, r_lo = hi_right, lo_right
            else:           # missing rides RIGHT
                l_hi, l_lo = hi_pref, lo_pref
                r_hi = jnp.minimum(hi_right, miss_hi)
                r_lo = jnp.maximum(lo_right, miss_lo)
            lo = jnp.clip(lo, l_lo, l_hi)
            ro = jnp.clip(ro, r_lo, r_hi)
            gains = (leaf_gain_given_output(left_g, left_h, p, lo)
                     + leaf_gain_given_output(right_g, right_h, p, ro))
        elif use_bounds:
            # per-leaf monotone bounds: candidate outputs are clipped into
            # the leaf's feasible interval and the gain recomputed with the
            # clipped outputs (ref: monotone_constraints.hpp BasicLeaf
            # Constraints + feature_histogram GetSplitGains USE_MC)
            blo = bound_lo[:, None, None]
            bhi = bound_hi[:, None, None]
            lo = jnp.clip(lo, blo, bhi)
            ro = jnp.clip(ro, blo, bhi)
            gains = (leaf_gain_given_output(left_g, left_h, p, lo)
                     + leaf_gain_given_output(right_g, right_h, p, ro))
        else:
            gains = (leaf_gain(left_g, left_h, p, left_c, parent_out)
                     + leaf_gain(right_g, right_h, p, right_c, parent_out))
        # monotone direction check (ref: GetSplitGains USE_MC -> 0)
        viol = ((mono > 0) & (lo > ro)) | ((mono < 0) & (lo < ro))
        gains = jnp.where(viol, 0.0, gains)
        gains = jnp.where(ok & (gains > min_gain_shift), gains, K_MIN_SCORE)

        if reverse:
            # prefer LARGEST threshold on ties (reverse scan visits high t
            # first and replaces only on strictly-greater gain)
            idx_rev = jnp.argmax(gains[..., ::-1], axis=2)
            t_best = B - 1 - idx_rev
        else:
            t_best = jnp.argmax(gains, axis=2)
        g_best = jnp.take_along_axis(gains, t_best[..., None], axis=2)[..., 0]
        pack = [left_g, left_h, left_c, right_g, right_h, right_c]
        picked = [jnp.take_along_axis(a, t_best[..., None], axis=2)[..., 0]
                  for a in pack]
        return t_best.astype(jnp.int32), g_best, picked

    # reverse scan (missing -> left; valid thresholds 0..nb-2-isNaN, skip
    # default_bin-1 for zero-missing); run unless (nb<=2 and missing NaN)
    rev_thresh_valid = ((t_iota <= nb - 2 - (mt == MISSING_NAN))
                        & ~((mt == MISSING_ZERO) & (t_iota == db - 1))
                        & ~((nb <= 2) & (mt == MISSING_NAN)))
    t_rev, g_rev, s_rev = directional_best(is_missing_bin_rev,
                                           rev_thresh_valid, reverse=True)

    # forward scan (missing -> right); run iff (nb>2 and missing != None) or
    # (nb<=2 and missing NaN)
    fwd_runs = jnp.where(nb > 2, mt != MISSING_NONE, mt == MISSING_NAN)
    fwd_thresh_valid = ((t_iota <= nb - 2)
                        & ~((mt == MISSING_ZERO) & (t_iota == db))
                        & fwd_runs)
    t_fwd, g_fwd, s_fwd = directional_best(is_missing_bin_fwd,
                                           fwd_thresh_valid, reverse=False)

    # reverse wins ties (it runs first in the reference)
    use_fwd = g_fwd > g_rev
    t_best = jnp.where(use_fwd, t_fwd, t_rev)                       # [S,F]
    g_best = jnp.where(use_fwd, g_fwd, g_rev)
    stats = [jnp.where(use_fwd, a, b) for a, b in zip(s_fwd, s_rev)]
    default_left = ~use_fwd
    if use_bounds and p.monotone_penalty > 0:
        # depth-based penalty on the NET gain of monotone-feature splits,
        # after validity gating on the gross gain (ref:
        # monotone_constraints.hpp:355 ComputeMonotoneSplitGainPenalty,
        # applied to SplitInfo.gain = best_gain - min_gain_shift)
        pen = p.monotone_penalty
        d = leaf_depth[:, None].astype(jnp.float32)
        factor = jnp.where(
            pen >= d + 1.0, K_EPSILON,
            jnp.where(pen <= 1.0,
                      1.0 - pen / jnp.exp2(d) + K_EPSILON,
                      1.0 - jnp.exp2(pen - 1.0 - d) + K_EPSILON))
        shift2 = min_gain_shift[:, :, 0]
        net = jnp.where(jnp.isfinite(g_best),
                        (g_best - shift2) * factor + shift2, g_best)
        g_best = jnp.where(monotone[None, :] != 0, net, g_best)
    if cegb_delta is not None:
        # cost-effective gradient boosting: per-(leaf,feature) acquisition
        # cost subtracted from the candidate gain before feature choice
        # (ref: cost_effective_gradient_boosting.hpp:66 DetlaGain,
        # serial_tree_learner.cpp:769-777)
        g_best = jnp.where(jnp.isfinite(g_best), g_best - cegb_delta,
                           g_best)
    if per_feature_gains:
        # voting-parallel wants the [S, F] gain plane, not the argmax
        # (ref: voting_parallel_tree_learner.cpp:151 votes by local gain)
        return g_best

    # across features: first feature wins ties (argmax picks first max)
    f_best = jnp.argmax(g_best, axis=1)                              # [S]
    take = lambda a: jnp.take_along_axis(a, f_best[:, None], axis=1)[:, 0]
    gain = take(g_best)
    lg, lh, lc, rg, rh, rc = [take(a) for a in stats]
    valid = jnp.isfinite(gain)

    left_out = calculate_leaf_output(lg, lh, p, lc, parent_output)
    right_out = calculate_leaf_output(rg, rh, p, rc, parent_output)
    if use_bounds:
        left_out = jnp.clip(left_out, bound_lo, bound_hi)
        right_out = jnp.clip(right_out, bound_lo, bound_hi)
    out_gain = jnp.where(valid, gain - min_gain_shift[:, 0, 0], K_MIN_SCORE)
    no_flag, no_mask = _no_cat(S, B)
    return BestSplit(
        feature=jnp.where(valid, f_best.astype(jnp.int32), -1),
        threshold=take(t_best),
        default_left=take(default_left),
        gain=out_gain,
        left_output=left_out,
        right_output=right_out,
        left_sum_grad=lg, left_sum_hess=lh - K_EPSILON, left_count=lc,
        right_sum_grad=rg, right_sum_hess=rh - K_EPSILON, right_count=rc,
        cat_flag=no_flag,
        cat_mask=no_mask,
    )


@functools.partial(jax.jit, static_argnames=("params",
                                             "per_feature_gains"))
def best_categorical_split_cm(grad: jax.Array, hess: jax.Array,
                              cnt: jax.Array, num_bin_per_feat: jax.Array,
                              cat_feature_mask: jax.Array,
                              params: SplitParams,
                              parent_output: jax.Array,
                              cegb_delta: jax.Array = None,
                              per_feature_gains: bool = False) -> BestSplit:
    """Best categorical split per slot (ref: feature_histogram.hpp:278-470
    FindBestThresholdCategoricalInner).

    Two modes, per the reference:
    - one-vs-rest when ``num_bin <= max_cat_to_onehot`` (plain lambda_l2);
    - otherwise: bins with count >= cat_smooth sorted by
      grad/(hess+cat_smooth), prefix scans from both ends up to
      ``min(max_cat_threshold, (used+1)//2)`` categories, gains with
      lambda_l2 + cat_l2 and min_data_per_group batching.

    Divergence from the reference, deliberate: the reference estimates bin
    counts as ``hess * num_data / sum_hess`` because its categorical
    histograms carry no count channel; ours do, so real counts are used.

    Bin 0 is the NaN/other catch-all (binning.py) and is never a member of
    the left set — matching the reference's ``bin_start = 1`` scan and the
    predict-side convention that unseen categories go right.

    Args:
      grad/hess/cnt: [S, F, B] float32 histogram planes.
      num_bin_per_feat: [F] int32.
      cat_feature_mask: [F] bool — True for categorical features that may
        be used (feature sampling already folded in).
      parent_output: [S] f32.

    Returns a BestSplit whose winners are categorical (cat_flag True,
    cat_mask = left-bin set, default_left False, threshold 0).
    """
    S, F, B = grad.shape
    p = params
    l2_cat = p.lambda_l2 + p.cat_l2
    eps = K_EPSILON

    b_iota = jnp.arange(B, dtype=jnp.int32)[None, None, :]
    nb = num_bin_per_feat[None, :, None]
    in_range = (b_iota >= 1) & (b_iota < nb)          # bin 0 = NaN/other

    tot_g = jnp.sum(grad, axis=2)                     # [S, F]
    tot_h = jnp.sum(hess, axis=2) + 2.0 * eps
    tot_c = jnp.sum(cnt, axis=2)
    parent_out = parent_output[:, None]

    gain_shift = leaf_gain(tot_g, tot_h, p, tot_c, parent_out)
    min_gain_shift = gain_shift + p.min_gain_to_split  # [S, F]

    # ---------------- one-vs-rest (ref :318-374)
    lg1 = grad
    lh1 = hess + eps
    lc1 = cnt
    rg1 = tot_g[..., None] - lg1
    rh1 = tot_h[..., None] - lh1 - eps
    rc1 = tot_c[..., None] - lc1
    ok1 = (in_range
           & (lc1 >= p.min_data_in_leaf) & (lh1 >= p.min_sum_hessian_in_leaf)
           & (rc1 >= p.min_data_in_leaf) & (rh1 >= p.min_sum_hessian_in_leaf))
    gains1 = (leaf_gain(lg1, lh1, p, lc1, parent_out[..., None])
              + leaf_gain(rg1, rh1, p, rc1, parent_out[..., None]))
    gains1 = jnp.where(ok1 & (gains1 > min_gain_shift[..., None]), gains1,
                       K_MIN_SCORE)
    t1 = jnp.argmax(gains1, axis=2)                   # [S, F]
    g1 = jnp.take_along_axis(gains1, t1[..., None], axis=2)[..., 0]
    onehot_allowed = (num_bin_per_feat <= p.max_cat_to_onehot)[None, :]
    g1 = jnp.where(onehot_allowed, g1, K_MIN_SCORE)

    # ---------------- sorted-subset (ref :376-473)
    ok_bin = in_range & (cnt >= p.cat_smooth)
    ratio = jnp.where(ok_bin, grad / (hess + p.cat_smooth), jnp.inf)
    order = jnp.argsort(ratio, axis=2, stable=True)   # filtered bins last
    sg = jnp.take_along_axis(grad, order, axis=2)
    sh = jnp.take_along_axis(hess, order, axis=2)
    sc = jnp.take_along_axis(cnt, order, axis=2)
    used = jnp.sum(ok_bin.astype(jnp.int32), axis=2)  # [S, F]
    max_num_cat = jnp.minimum(p.max_cat_threshold, (used + 1) // 2)

    def scan_dir(seq_g, seq_h, seq_c):
        """Prefix scan over the sorted sequence; returns per-position gains
        [S, F, B] (K_MIN_SCORE where not a candidate)."""
        def step(carry, xs):
            sum_g, sum_h, sum_c, grp = carry
            tg, th, tc, i = xs
            live = (i < used) & (i < max_num_cat)
            sum_g = sum_g + jnp.where(live, tg, 0.0)
            sum_h = sum_h + jnp.where(live, th, 0.0)
            sum_c = sum_c + jnp.where(live, tc, 0.0)
            grp = grp + jnp.where(live, tc, 0.0)
            rc = tot_c - sum_c
            rh = tot_h - sum_h - eps
            ok = (live
                  & (sum_c >= p.min_data_in_leaf)
                  & (sum_h + eps >= p.min_sum_hessian_in_leaf)
                  & (rc >= p.min_data_in_leaf)
                  & (rc >= p.min_data_per_group)
                  & (rh >= p.min_sum_hessian_in_leaf)
                  & (grp >= p.min_data_per_group))
            rg = tot_g - sum_g
            gain = (leaf_gain(sum_g, sum_h + eps, p, sum_c, parent_out,
                              l2_cat)
                    + leaf_gain(rg, rh, p, rc, parent_out, l2_cat))
            gain = jnp.where(ok & (gain > min_gain_shift), gain, K_MIN_SCORE)
            grp = jnp.where(ok, 0.0, grp)
            return (sum_g, sum_h, sum_c, grp), gain

        init = (jnp.zeros((S, F)), jnp.zeros((S, F)), jnp.zeros((S, F)),
                jnp.zeros((S, F)))
        xs = (jnp.moveaxis(seq_g, 2, 0), jnp.moveaxis(seq_h, 2, 0),
              jnp.moveaxis(seq_c, 2, 0),
              jnp.arange(B, dtype=jnp.int32))
        _, gains = jax.lax.scan(step, init, xs)
        return jnp.moveaxis(gains, 0, 2)              # [S, F, B]

    gains_fwd = scan_dir(sg, sh, sc)
    # reverse: walk the valid region from its end (position used-1-i)
    rev_idx = jnp.clip(used[..., None] - 1 - jnp.arange(B)[None, None, :],
                       0, B - 1)
    gains_rev = scan_dir(jnp.take_along_axis(sg, rev_idx, axis=2),
                         jnp.take_along_axis(sh, rev_idx, axis=2),
                         jnp.take_along_axis(sc, rev_idx, axis=2))

    i_fwd = jnp.argmax(gains_fwd, axis=2)
    g_fwd = jnp.take_along_axis(gains_fwd, i_fwd[..., None], axis=2)[..., 0]
    i_rev = jnp.argmax(gains_rev, axis=2)
    g_rev = jnp.take_along_axis(gains_rev, i_rev[..., None], axis=2)[..., 0]

    # ---------------- combine modes per feature, then across features
    # (onehot vs sorted are exclusive per feature; fwd beats rev on ties —
    # the reference scans fwd first and replaces only on strictly greater)
    use_rev = g_rev > g_fwd
    g_sorted = jnp.where(use_rev, g_rev, g_fwd)
    g_feat = jnp.where(onehot_allowed, g1, g_sorted)   # [S, F]
    if cegb_delta is not None:
        # CEGB acquisition costs apply to every candidate feature
        # (ref: serial_tree_learner.cpp:769-777)
        g_feat = jnp.where(jnp.isfinite(g_feat), g_feat - cegb_delta,
                           g_feat)
    cfm = (cat_feature_mask[None, :] if cat_feature_mask.ndim == 1
           else cat_feature_mask)
    g_feat = jnp.where(cfm, g_feat, K_MIN_SCORE)
    if per_feature_gains:
        # voting-parallel ranks categorical features in the vote too
        # (ref: voting_parallel_tree_learner.cpp:151 votes by local gain)
        return g_feat
    f_best = jnp.argmax(g_feat, axis=1)                # [S]
    take = lambda a: jnp.take_along_axis(a, f_best[:, None], axis=1)[:, 0]
    gain = take(g_feat)
    valid = jnp.isfinite(gain)

    is_onehot = take(onehot_allowed.astype(jnp.int32) *
                     jnp.ones((S, F), jnp.int32)) > 0
    tb = take(t1)                                      # [S] onehot bin
    ifw = take(i_fwd)
    irv = take(i_rev)
    urev = take(use_rev.astype(jnp.int32)) > 0
    usedb = take(used)

    # left-set membership mask over bins [S, B]
    rank = jnp.zeros((S, F, B), jnp.int32)
    rank = jnp.put_along_axis(
        rank, order, jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32),
                                      (S, F, B)), axis=2,
        inplace=False)
    rank_b = jnp.take_along_axis(
        rank, f_best[:, None, None].repeat(B, 2), axis=1)[:, 0, :]  # [S, B]
    okb_b = jnp.take_along_axis(
        ok_bin, f_best[:, None, None].repeat(B, 2), axis=1)[:, 0, :]
    mask_fwd = okb_b & (rank_b <= ifw[:, None])
    mask_rev = okb_b & (rank_b >= (usedb - 1 - irv)[:, None])
    mask_sorted = jnp.where(urev[:, None], mask_rev, mask_fwd)
    mask_onehot = jnp.arange(B)[None, :] == tb[:, None]
    cat_mask = jnp.where(is_onehot[:, None], mask_onehot, mask_sorted)
    cat_mask = cat_mask & valid[:, None]

    # left-side stats of the winner
    gb = jnp.take_along_axis(
        grad, f_best[:, None, None].repeat(B, 2), axis=1)[:, 0, :]
    hb = jnp.take_along_axis(
        hess, f_best[:, None, None].repeat(B, 2), axis=1)[:, 0, :]
    cb = jnp.take_along_axis(
        cnt, f_best[:, None, None].repeat(B, 2), axis=1)[:, 0, :]
    lg = jnp.sum(jnp.where(cat_mask, gb, 0.0), axis=1)
    lh = jnp.sum(jnp.where(cat_mask, hb, 0.0), axis=1) + eps
    lc = jnp.sum(jnp.where(cat_mask, cb, 0.0), axis=1)
    tg = take(tot_g)
    th = take(tot_h)
    tc = take(tot_c)
    rg = tg - lg
    rh = th - lh - eps
    rc = tc - lc

    l2_out = jnp.where(is_onehot, p.lambda_l2, l2_cat)
    left_out = calculate_leaf_output(lg, lh, p, lc, parent_output, l2_out)
    right_out = calculate_leaf_output(rg, rh, p, rc, parent_output, l2_out)
    out_gain = jnp.where(valid, gain - take(min_gain_shift), K_MIN_SCORE)
    return BestSplit(
        feature=jnp.where(valid, f_best.astype(jnp.int32), -1),
        threshold=jnp.zeros((S,), jnp.int32),
        default_left=jnp.zeros((S,), bool),
        gain=out_gain,
        left_output=left_out,
        right_output=right_out,
        left_sum_grad=lg, left_sum_hess=lh - eps, left_count=lc,
        right_sum_grad=rg, right_sum_hess=rh, right_count=rc,
        cat_flag=valid,
        cat_mask=cat_mask,
    )


@functools.partial(jax.jit,
                   static_argnames=("params", "has_cat", "use_bounds"))
def best_split_cm(grad: jax.Array, hess: jax.Array, cnt: jax.Array,
                  num_bin_per_feat: jax.Array, missing_type: jax.Array,
                  default_bin: jax.Array, feature_mask: jax.Array,
                  is_cat: jax.Array, monotone: jax.Array,
                  params: SplitParams, parent_output: jax.Array,
                  has_cat: bool = False, use_bounds: bool = False,
                  bound_lo: jax.Array = None, bound_hi: jax.Array = None,
                  leaf_depth: jax.Array = None,
                  cegb_delta: jax.Array = None,
                  bound_lo_plane: jax.Array = None,
                  bound_hi_plane: jax.Array = None) -> BestSplit:
    """Combined numerical + categorical best split per slot (the analog of
    FeatureHistogram::FindBestThreshold dispatch on bin_type,
    ref: feature_histogram.hpp:85). ``has_cat`` is static: all-numerical
    datasets skip the categorical scan entirely at trace time. Optional
    ``bound_*_plane`` [S, F, B] segment bounds select the ADVANCED
    monotone scan for numerical features (categorical winners keep the
    scalar whole-leaf clamp below)."""
    ic = is_cat[None, :] if feature_mask.ndim == 2 else is_cat
    num = best_numerical_split_cm(
        grad, hess, cnt, num_bin_per_feat, missing_type, default_bin,
        feature_mask & ~ic, monotone, params, parent_output,
        use_bounds=use_bounds, bound_lo=bound_lo, bound_hi=bound_hi,
        leaf_depth=leaf_depth, cegb_delta=cegb_delta,
        bound_lo_plane=bound_lo_plane, bound_hi_plane=bound_hi_plane)
    if not has_cat:
        return num
    # the categorical search has a scope of its own inside the caller's
    # (lgbm.grow > root, > level > split): its sort and its two prefix
    # scans are what a categorical job adds to the split search
    with jax.named_scope("cat"):
        cat = best_categorical_split_cm(
            grad, hess, cnt, num_bin_per_feat, feature_mask & ic, params,
            parent_output, cegb_delta=cegb_delta)
    if use_bounds:
        # categorical features carry no monotone direction, but the leaf's
        # feasible output interval still applies (winner-level clamp;
        # divergence: the reference clips per candidate)
        cat = cat._replace(
            left_output=jnp.clip(cat.left_output, bound_lo, bound_hi),
            right_output=jnp.clip(cat.right_output, bound_lo, bound_hi))
    use_cat = cat.gain > num.gain
    merged = [jnp.where(use_cat if a.ndim == 1 else use_cat[:, None], a, b)
              for a, b in zip(cat, num)]
    return BestSplit(*merged)


def per_feature_gains_cm(grad, hess, cnt, num_bin_per_feat, missing_type,
                         default_bin, feature_mask, is_cat, monotone,
                         params, parent_output,
                         has_cat: bool = False) -> jax.Array:
    """[S, F] best-candidate gain per feature — what voting-parallel
    shards rank locally before the vote (ref:
    voting_parallel_tree_learner.cpp:151 GlobalVoting). Categorical
    features rank by their categorical gain (one-hot / sorted-subset),
    numerical by the threshold scan."""
    ic = is_cat[None, :] if feature_mask.ndim == 2 else is_cat
    g = best_numerical_split_cm(
        grad, hess, cnt, num_bin_per_feat, missing_type, default_bin,
        feature_mask & ~ic, monotone, params, parent_output,
        per_feature_gains=True)
    if has_cat:
        with jax.named_scope("cat"):
            gc = best_categorical_split_cm(
                grad, hess, cnt, num_bin_per_feat, feature_mask & ic,
                params, parent_output, per_feature_gains=True)
        g = jnp.maximum(g, gc)
    return g
