"""On-device tree routing over binned features: the gather walk.

The whole row set advances one tree level per step, each step a handful
of row-length gathers (the reference walks pointer trees per row on the
host, gbdt.cpp UpdateScore / score_updater.hpp:88). On a TPU a
row-length gather costs about 8 ns per gathered element, so this is the
path for what has only a finished tree to go by:

- ``add_tree_score`` / ``route_rows_to_leaves`` over binned rows: the
  synchronous driver's validation and training score updates, rollback,
  DART / RF replays, the replay of a continued model onto a new
  validation set, recovery (``GBDT._add_tree_to_score``); and, on the
  fast paths, the validation sets whose storage the training kernels'
  route tables do not describe (``GBDT._valid_route``:
  ``route_rows_to_leaves``, then the kernels' ``table_lookup``);
- ``route_raw_rows_to_leaves`` over raw values: ``Booster.predict`` and
  ``serve/``.

A validation set of a fused-engine fast-path run in the training
matrix's own columns is NOT routed here: the grower hands out the route
tables it routed the training rows with and
``models/frontier2.replay_route_log`` runs them over the set with the
training kernels, to the same leaves bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _route_left(b, t, default_left, nb, mt, db):
    """Split decision on bin values with missing routing
    (ref: src/io/dense_bin.hpp Split)."""
    missing = (((mt == 1) & (b == db)) | ((mt == 2) & (b == nb - 1)))
    return jnp.where(missing, default_left, b <= t)


@functools.partial(jax.jit, static_argnames=("max_steps",))
def route_rows_to_leaves(bins: jax.Array, split_feature: jax.Array,
                         threshold_bin: jax.Array, default_left: jax.Array,
                         left_child: jax.Array, right_child: jax.Array,
                         num_bin: jax.Array, missing_type: jax.Array,
                         default_bin: jax.Array, max_steps: int,
                         cat_flag: jax.Array = None,
                         cat_mask: jax.Array = None,
                         bundle: tuple = None) -> jax.Array:
    """Leaf index per row for one tree (arrays follow the TreeArrays
    convention: child >= 0 internal node, child < 0 means ~leaf).

    ``max_steps`` must be >= tree depth.  Single-leaf trees (no node 0)
    are handled by the caller (leaf 0 for every row).
    ``cat_flag``/``cat_mask`` ([N], [N, B]) enable categorical bitset
    decisions (ref: tree.h CategoricalDecision on bin space).
    ``bundle``: (col_of_feat, offset_of_feat, most_freq_bin) when ``bins``
    holds EFB BUNDLE columns (sparse-built datasets) — the logical bin is
    decoded per node: in-window values shift by the feature's offset,
    out-of-window rows are bundle-default and carry the feature's most
    frequent bin (ops/efb.py encoding). Two more entries, (rows [M],
    logical bins [M, F]), give rows whose logical bins the bundles do not
    hold (a validation set's conflicting rows, TpuDataset.from_sparse):
    their leaves are walked over those bins instead (a row index past the
    end is padding).
    """
    if bundle is not None and len(bundle) == 5:
        leaves = route_rows_to_leaves(
            bins, split_feature, threshold_bin, default_left, left_child,
            right_child, num_bin, missing_type, default_bin, max_steps,
            cat_flag, cat_mask, bundle=bundle[:3])
        exact = route_rows_to_leaves(
            bundle[4], split_feature, threshold_bin, default_left,
            left_child, right_child, num_bin, missing_type, default_bin,
            max_steps, cat_flag, cat_mask)
        return leaves.at[bundle[3]].set(exact, mode="drop")
    R = bins.shape[0]
    node = jnp.zeros((R,), jnp.int32)

    def body(_, node):
        is_internal = node >= 0
        nd = jnp.maximum(node, 0)
        f = split_feature[nd]
        if bundle is None:
            b = jnp.take_along_axis(bins, f[:, None].astype(jnp.int32),
                                    axis=1)[:, 0].astype(jnp.int32)
        else:
            col_of_feat, offset_of_feat, mfb = bundle
            raw = jnp.take_along_axis(
                bins, col_of_feat[f][:, None].astype(jnp.int32),
                axis=1)[:, 0].astype(jnp.int32)
            off = offset_of_feat[f]
            in_win = (raw >= off) & (raw < off + num_bin[f])
            b = jnp.where(in_win, raw - off, mfb[f])
        go_left = _route_left(b, threshold_bin[nd], default_left[nd],
                              num_bin[f], missing_type[f], default_bin[f])
        if cat_flag is not None:
            cat_left = cat_mask[nd, b]
            go_left = jnp.where(cat_flag[nd], cat_left, go_left)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        return jnp.where(is_internal, nxt, node)

    node = jax.lax.fori_loop(0, max_steps, body, node)
    return jnp.where(node < 0, ~node, 0)


@functools.partial(jax.jit, static_argnames=("max_steps",))
def route_raw_rows_to_leaves(values: jax.Array, split_feature: jax.Array,
                             threshold: jax.Array, default_left: jax.Array,
                             missing_type: jax.Array, left_child: jax.Array,
                             right_child: jax.Array, max_steps: int,
                             cat_flag: jax.Array = None,
                             cat_mask: jax.Array = None) -> jax.Array:
    """Leaf index per row for one tree routed on RAW feature values —
    the serving-side variant for boosters without training BinMappers
    (model-file loads).  Mirrors the host walk exactly
    (ref: tree.h NumericalDecision / CategoricalDecision):

    - ``missing_type`` is PER NODE here (decoded from the model's
      decision_type bitfield), not per feature;
    - NaN with missing_type none/zero is treated as 0.0;
    - ``threshold`` must be pre-rounded to the largest float32 <= the
      model's float64 threshold (models/predictor.threshold_to_f32), so
      the float32 compare routes float32-representable inputs
      bit-identically to the float64 host compare;
    - ``cat_mask`` ([N, C]) is indexed by the raw integer category value
      (bounded by the packer); out-of-range/negative goes right.
    """
    R = values.shape[0]
    node = jnp.zeros((R,), jnp.int32)

    def body(_, node):
        is_internal = node >= 0
        nd = jnp.maximum(node, 0)
        f = split_feature[nd]
        v = jnp.take_along_axis(values, f[:, None].astype(jnp.int32),
                                axis=1)[:, 0]
        mt = missing_type[nd]
        nan_mask = jnp.isnan(v)
        zero_mask = jnp.abs(v) <= 1e-35          # kZeroThreshold
        is_missing = jnp.where(mt == 2, nan_mask,
                               jnp.where(mt == 1, zero_mask | nan_mask,
                                         False))
        v_eff = jnp.where(nan_mask & (mt != 2), jnp.float32(0.0), v)
        go_left = jnp.where(is_missing, default_left[nd],
                            v_eff <= threshold[nd])
        if cat_flag is not None:
            C = cat_mask.shape[1]
            # range-check BEFORE the int cast: float->int32 of values
            # past 2^31 is implementation-defined in XLA (wrap or
            # saturate), and a wrapped value could land inside [0, C)
            # and read mask garbage.  The bound is v <= -1, not v < 0:
            # the host walk truncates toward zero, so (-1, 0) becomes
            # category 0 there and must here too
            bad = nan_mask | (v <= -1.0) | (v >= jnp.float32(C))
            iv = jnp.where(bad, jnp.float32(-1), v).astype(jnp.int32)
            in_range = iv >= 0
            cat_left = cat_mask[nd, jnp.clip(iv, 0, C - 1)] & in_range
            go_left = jnp.where(cat_flag[nd], cat_left, go_left)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        return jnp.where(is_internal, nxt, node)

    node = jax.lax.fori_loop(0, max_steps, body, node)
    return jnp.where(node < 0, ~node, 0)


@functools.partial(jax.jit, static_argnames=("max_steps",))
def add_tree_score(score: jax.Array, bins: jax.Array, leaf_value: jax.Array,
                   split_feature: jax.Array, threshold_bin: jax.Array,
                   default_left: jax.Array, left_child: jax.Array,
                   right_child: jax.Array, num_bin: jax.Array,
                   missing_type: jax.Array, default_bin: jax.Array,
                   max_steps: int, cat_flag: jax.Array = None,
                   cat_mask: jax.Array = None,
                   bundle: tuple = None) -> jax.Array:
    """score += leaf_value[route(row)] in one fused pass."""
    leaves = route_rows_to_leaves(bins, split_feature, threshold_bin,
                                  default_left, left_child, right_child,
                                  num_bin, missing_type, default_bin,
                                  max_steps, cat_flag, cat_mask,
                                  bundle=bundle)
    return score + leaf_value[leaves]
