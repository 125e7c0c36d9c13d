"""Frontier grower — fused route+histogram level passes.

The fused engine's tree grower. One
``ops/fused_level.level_pass`` kernel invocation per tree level does the
routing AND the smaller-child histograms in a single streaming pass over
the binned matrix; everything else per level is small-tensor XLA glue:

- per-level slot counts are EXACT (1, 2, 4, ... capped at 128), so
  histogram flops track the real frontier width;
- split finding runs on the 2*S new children only, updating a cached
  per-leaf best-split table, instead of rescanning all ``num_leaves``
  slots every level (ref: serial_tree_learner.cpp:379-453 only scans the
  two fresh leaves too);
- the [L, F, B] histogram pool is read/written with one-hot f32 matmuls:
  XLA per-row gathers/scatters measured ~8-14 ns/element on TPU, which
  would cost ~100 ms/tree at 255 leaves — the one-hot contraction is
  ~100 us of MXU time instead;
- after the capped-pow2 main levels, ``extra_levels`` additional passes
  (64 slots each) let skewed trees keep splitting until the leaf budget
  is spent (a level-capped schedule alone stops skewed trees near depth
  log2(num_leaves)+1, short of leaf-wise growth).

Reference semantics preserved: smaller-child histogramming + sibling
subtraction (serial_tree_learner.cpp:283-323,423-425), leaf budget,
max_depth, missing routing, gain masks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.fused_level import (NCH_PRECISE, build_route_table,
                               build_route_table_bundled,
                               bundle_plane_views, expand_feature_mask,
                               hist_planes, level_pass, max_slot_cap,
                               pack_route_table, root_route_tables,
                               route_pass, route_table_columns,
                               table_lookup)
from ..ops.split import (BestSplit, SplitParams, best_split_cm,
                         calculate_leaf_output, per_feature_gains_cm)
from ..ops.collectives import record_psum
from .learner import (FeatureMeta, NEG_INF, _masked_gain, _masked_scatter,
                      merge_best_over_shards, meta_is_cat,
                      mono_child_bounds, mono_inter_level_update,
                      node_feature_mask, update_leaf_groups)
from .tree import TreeArrays, empty_tree


def level_caps(num_leaves: int, max_depth: int, extra_levels: int,
               slot_cap: int = 128):
    """Static per-level split caps: 1, 2, 4, ... (<= slot_cap) until the
    cumulative cap covers num_leaves-1, then ``extra_levels`` passes of
    min(64, slot_cap) more. The extras let skewed trees — and trees whose
    frontier outgrew slot_cap — spend the remaining leaf budget; levels
    with nothing to split are skipped at runtime (lax.cond), so extras
    cost compile time only."""
    caps = []
    cum = 0
    d = 0
    while cum < num_leaves - 1:
        if max_depth > 0 and d >= max_depth:
            break
        c = min(1 << d, slot_cap, num_leaves - 1)
        caps.append(c)
        cum += c
        d += 1
    caps.extend([min(64, slot_cap, num_leaves - 1)] * extra_levels)
    return tuple(caps)


def route_form(has_cat: bool, bundle_cols: int,
               num_bins: int) -> Tuple[str, str]:
    """(form, reason) of a grower's routing, from what is static about the
    job. THE place where the form is chosen: the grower asks here, and so
    does the driver for its ``route_form`` event.

    ``bins``: a split routes by the bin VALUE of its feature
    (ops/fused_level._left_from_bins): the slot table carries threshold,
    missing bin, default_left and the feature's row, and with ``has_cat``
    (the job has a categorical column) each slot's categorical flag and
    its left-going bin SET as 256 bits, which the kernels test the stored
    bin's membership in; no [Sp, FB] table is built, logged or
    multiplied. A job stored as EFB bundle columns (``bundle_cols > 0``)
    takes it too: the row is the split feature's bundle column and the
    slot table carries the feature's window, by which the kernels decode
    the stored bundle value to the feature's bin before the same tests.
    ``table``: ``W @ one_hot`` (build_route_table*), kept where a stored
    value (``num_bins`` a column of the kernels' matrix) can pass 255,
    which is not exact in bfloat16 and does not fit the slot table's
    256-bit set, with or without categorical columns; the reason names
    the column:

    - ``bundled``: an EFB bundle column of over 256 bins;
    - ``wide_bins``: a feature of over 256 bins."""
    if num_bins > 256:
        return "table", "bundled" if bundle_cols > 0 else "wide_bins"
    return "bins", None


def _onehot_dot(sel: jax.Array, mat: jax.Array) -> jax.Array:
    """sel @ mat with HIGHEST precision: sel is an exact 0/1 one-hot, so the
    f32-emulated TPU matmul reproduces the selected rows bit-for-bit (the
    default bf16-input MXU dot would round every pool histogram to ~8
    mantissa bits each level and wreck the sibling subtraction)."""
    return jax.lax.dot(sel, mat, precision=jax.lax.Precision.HIGHEST)


def _pool_read(pool_plane: jax.Array, leaf_of_slot: jax.Array,
               Sp: int) -> jax.Array:
    """pool[leaf_of_slot] as an exact one-hot f32 contraction."""
    L = pool_plane.shape[0]
    FB = pool_plane.shape[1] * pool_plane.shape[2]
    sel = (leaf_of_slot[:, None] ==
           jnp.arange(L, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    out = _onehot_dot(sel, pool_plane.reshape(L, FB))
    return out.reshape((Sp,) + pool_plane.shape[1:])


def _pool_write(pool_plane: jax.Array, idx: jax.Array, vals: jax.Array,
                mask: jax.Array) -> jax.Array:
    """pool[idx[k]] = vals[k] where mask[k], as dense one-hot blend."""
    L = pool_plane.shape[0]
    F_oh, B = pool_plane.shape[1], pool_plane.shape[2]
    idx_safe = jnp.where(mask, idx, -1)
    sel = (idx_safe[:, None] ==
           jnp.arange(L, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    upd = _onehot_dot(sel.T, vals.reshape(vals.shape[0], F_oh * B))  # [L,FB]
    hit = jnp.max(sel, axis=0)                                # [L] 0/1
    return (pool_plane * (1.0 - hit)[:, None, None]
            + upd.reshape(L, F_oh, B))


def _merge_best_many(best: BestSplit, idx: jax.Array, vals: BestSplit,
                     mask: jax.Array) -> BestSplit:
    return BestSplit(*[_masked_scatter(a, idx, v, mask)
                       for a, v in zip(best, vals)])


# the scope wraps the jit so that the call itself (and what the compiler
# derives from it) is named too, not only the operations inside
@jax.named_scope("lgbm.grow")
@functools.partial(
    jax.jit,
    static_argnames=("params", "num_leaves", "max_bins", "f_oh", "num_rows",
                     "nch", "max_depth", "extra_levels", "has_cat",
                     "use_mono_bounds", "use_node_masks", "interpret",
                     "bundle_cols", "bundle_col_bins", "psum_axis",
                     "mono_mode", "parallel_mode",
                     "top_k", "quant_bits", "packed", "mask_onehot",
                     "route_log"))
def grow_tree_fused(bins_T: jax.Array, gh_T: jax.Array, meta: FeatureMeta,
                    feature_mask: jax.Array, params: SplitParams,
                    num_leaves: int, max_bins: int, f_oh: int,
                    num_rows: int = 0, nch: int = NCH_PRECISE,
                    max_depth: int = -1, extra_levels: int = 3,
                    has_cat: bool = False, use_mono_bounds: bool = False,
                    use_node_masks: bool = False, node_masks=None,
                    bundle_cols: int = 0, bundle_col_bins: int = 0,
                    bundle_cfg=None, interpret: bool = False,
                    psum_axis: str = None, mono_mode: str = "basic",
                    parallel_mode: str = "data", top_k: int = 0,
                    feature_shard_mask: jax.Array = None,
                    quant_bits: int = 0, packed=None,
                    mask_onehot: bool = False, gh_scales: jax.Array = None,
                    route_log: bool = False):
    """Grow one tree with fused level passes.

    Args:
      bins_T: [Fp, Rp] int8/int16 transposed binned matrix; Rp a multiple
        of 2048 (the widest kernel tile — smaller pow2 multiples still
        work, the tile just shrinks to fit); padded feature rows
        all-zero; padded row COLUMNS can be
        anything (their gh is zero and their leaf starts at -1). With EFB
        (``bundle_cols > 0``) the rows are BUNDLE columns carrying
        ``bundle_col_bins`` bins each; splits/histograms stay logical.
      gh_T: [8, Rp] bfloat16 from ops.fused_level.pack_gh (zeros in padding
        columns).
      meta: FeatureMeta with arrays sized f_oh (padding features must carry
        num_bin=0 and feature_mask False).
      feature_mask: [f_oh] bool.
      num_rows: real row count R (0 = all Rp rows are real). Padding rows
        [R:] are pinned to leaf -1 so they never route, histogram, or
        receive score updates.
      bundle_cols/bundle_col_bins: kernel layout when the matrix holds EFB
        bundle columns (0 = unbundled); ``bundle_cfg`` is the
        models.learner.BundleCfg decode table plus meta.most-freq bins.
      psum_axis: when set (running under shard_map over a row-sharded
        mesh), every level histogram — ONE packed [FB, nch*Sp] f32 tensor
        per level — is allreduced over that mesh axis before the split
        scan, so all shards see GLOBAL sums and make identical split
        decisions; routing stays shard-local. This is the fused-engine
        analog of the reference's fast-path histogram reduction
        (ref: src/treelearner/data_parallel_tree_learner.cpp:185 — the
        GPU learner's histograms are what gets reduce-scattered, not a
        slow stand-in's). The hi/lo channel decode is linear, so psum
        before hist_planes preserves fp32-grade precision. Under
        psum_axis the caller passes ``num_rows=0`` and marks its local
        padding rows with zero gh weight instead (the global "real row"
        prefix has no meaning inside a shard).

      parallel_mode: composition with the distribution axis under
        psum_axis (ref: tree_learner.cpp:17-49 — the reference
        instantiates {Data,Voting,Feature}ParallelTreeLearner<GPU
        learner>; this is the fused engine's side of that matrix):
        - "data": full packed-histogram psum per level (round-2 path);
        - "voting": per-level top_k vote caps the exchanged columns —
          shards rank their local per-feature gains on the smaller-child
          planes, the 2*top_k global vote winners' [Sp, W, B, 3] planes
          are summed, everything else stays local-invalid; a per-leaf
          [L, f_oh] validity pool gates sibling subtraction and later
          scans (ref: voting_parallel_tree_learner.cpp:151-184). The
          root histogram is always a full exchange, like the XLA
          growers;
        - "feature": rows are REPLICATED on every shard (bins_T/gh_T in
          full), each shard scans only its feature_shard_mask columns
          and per-level best-split records are merged over the mesh
          (ref: feature_parallel_tree_learner.cpp:60-77
          SyncUpGlobalBestSplit). Zero histogram traffic; the histogram
          dot itself is NOT column-sliced in this engine (the fused
          kernel routes and histograms the same bins_T in one pass) —
          the XLA feature grower remains the compute-sliced path.
      top_k: voting-parallel vote width (2*top_k columns exchanged).
      feature_shard_mask: [f_oh] bool, this shard's owned columns
        (feature mode only).
      route_log: also return the tree's per-level route tables, the ones
        the training rows were routed with: (log_W [n_levels, Sp_max,
        kern_fb] bf16, log_tbl [n_levels, Sp_max, 128] int32), padded to
        the widest level (an all-(-2) table routes nothing); a level the
        runtime ``cond`` skipped keeps its all-(-2) table. In the bins form
        (:func:`route_form`) ``log_tbl`` holds the splits themselves and
        ``log_W`` is None. :func:`replay_route_log` over
        any matrix in the training layout finds each row's leaf in the
        FINISHED tree (the validation sets' path to their leaves).

    Returns (TreeArrays, row_leaf [Rp] int32 — caller slices to R; padding
    rows stay at -1). With route_log the pair (log_W, log_tbl) is appended
    as one last element.
    """
    Fp, Rp = bins_T.shape
    L = num_leaves
    B = max_bins
    use_bundles = bundle_cols > 0
    if use_bundles:
        k_foh, k_B = bundle_cols, bundle_col_bins   # kernel layout
    else:
        k_foh, k_B = f_oh, B
    # slot caps stay derived from the PADDED flat width so the level
    # schedule — and hence the grown tree — is invariant to the adaptive
    # packing (the adaptive-bin byte-identity A/B contract)
    caps = level_caps(L, max_depth, extra_levels,
                      slot_cap=max_slot_cap(k_foh * k_B, nch))
    kern_fb = packed.fb if packed is not None else k_foh * k_B
    bins_form = route_form(has_cat, bundle_cols, k_B)[0] == "bins"
    # the kernels decode bundle values by window (the bins form of a
    # bundled job); the table form's W is written over the bundle bins
    window_decode = use_bundles and bins_form

    def _decode(hist, Sp_):
        """Kernel accumulator -> (g, h, c) f32 planes on the logical
        padded layout: packed re-index (exact) + the quantized int32 ->
        f32 rescale boundary, both before any split search."""
        return hist_planes(hist, nch, Sp_, k_foh, k_B, packed=packed,
                           quant_bits=quant_bits, scales=gh_scales)

    if mask_onehot:
        # gain screening: masked features' one-hot slabs are zeroed in
        # the kernel. The leaf-totals column must survive: logical
        # feature 0 feeds the total sums (best_split_cm reads
        # grad[:, 0, :]) and the kernel's FIRST column carries the root
        # pass's every-row-left routing trick — keep both unmasked.
        keep0 = packed.feat_order[0] if packed is not None else 0
        fm_keep = feature_mask.at[0].set(True).at[keep0].set(True)
        fmask_fb = expand_feature_mask(fm_keep, k_foh, k_B, packed)
        fmask2d = jnp.broadcast_to(fmask_fb[:, None], (kern_fb, 128)) \
            .astype(jnp.int8 if quant_bits else jnp.bfloat16)
    else:
        fmask2d = None

    with jax.named_scope("root"):
        R = num_rows or Rp
        # padding rows sit at leaf -1; inactive slots use leaf_of_slot = -2 so
        # a -1 pad row never matches a slot
        leaf_T = jnp.where(jnp.arange(Rp)[None, :] < R, 0, -1) \
            .astype(jnp.int32)

        tree = empty_tree(L, B)
        pool_g = jnp.zeros((L, f_oh, B), jnp.float32)
        pool_h = jnp.zeros((L, f_oh, B), jnp.float32)
        pool_c = jnp.zeros((L, f_oh, B), jnp.float32)

        # ---------------- root pass: slot 0 collects the full-data histogram
        # (every row goes "left" on slot 0: root_route_tables)
        Sp0 = 8
        # (the first kernel column's width is the first packed
        # feature's slab under the adaptive layout)
        W0, tbl0 = root_route_tables(
            k_B, kern_fb, packed.widths[0] if packed is not None else k_B,
            bins_form, Sp0, bundled=window_decode)
        hist0, _ = level_pass(bins_T, leaf_T, gh_T, W0, tbl0, fmask2d,
                              num_slots=Sp0,
                              num_bins=k_B, f_oh=k_foh, nch=nch,
                              interpret=interpret, quant_bits=quant_bits,
                              packed=packed, has_cat=has_cat,
                              bundled=window_decode)
        # feature mode: rows are replicated, the local histogram IS the
        # global one (a psum would multiply by the shard count); voting:
        # the root is always a full exchange like the XLA growers
        if psum_axis is not None and parallel_mode != "feature":
            hist0 = record_psum(hist0, psum_axis)
        g0, h0, c0 = _decode(hist0, Sp0)
        if use_bundles:
            v = bundle_plane_views(jnp.stack([g0, h0, c0], axis=-1),
                                   bundle_cfg.flat_idx, bundle_cfg.valid,
                                   bundle_cfg.default_bin)
            g0, h0, c0 = v[..., 0], v[..., 1], v[..., 2]
        pool_g = pool_g.at[0].set(g0[0])
        pool_h = pool_h.at[0].set(h0[0])
        pool_c = pool_c.at[0].set(c0[0])
        root_g = jnp.sum(g0[0, 0, :])
        root_h = jnp.sum(h0[0, 0, :])
        root_c = jnp.sum(c0[0, 0, :])
        root_out = calculate_leaf_output(root_g, root_h, params, root_c, 0.0)
        tree = tree._replace(
            leaf_value=tree.leaf_value.at[0].set(root_out),
            leaf_count=tree.leaf_count.at[0].set(root_c),
            leaf_weight=tree.leaf_weight.at[0].set(root_h))

        leaf_lo = jnp.full((L,), -jnp.inf, jnp.float32)
        leaf_hi = jnp.full((L,), jnp.inf, jnp.float32)
        leaf_groups = jnp.full((L,), -1, jnp.int32)
        # intermediate monotone mode: per-leaf bin-space regions over the
        # LOGICAL features. Padded features (num_bin=0) get a fake [0, 1)
        # region so they always overlap — splits never touch them, and the
        # adjacency test needs overlap on every feature but one.
        reg_lo = jnp.zeros((L, f_oh), jnp.int32)
        reg_hi = jnp.broadcast_to(jnp.maximum(meta.num_bin, 1)[None, :],
                                  (L, f_oh)).astype(jnp.int32)
        feat_par = psum_axis is not None and parallel_mode == "feature"
        root_mask = feature_mask[None, :]
        if feat_par:
            root_mask = root_mask & feature_shard_mask[None, :]
        if use_node_masks:
            root_mask = root_mask & node_feature_mask(
                node_masks, leaf_groups[:1], jnp.zeros((1,), jnp.int32))
        root_best = best_split_cm(
            g0[:1], h0[:1], c0[:1], meta.num_bin, meta.missing_type,
            meta.default_bin, root_mask, meta_is_cat(meta), meta.monotone,
            params, tree.leaf_value[:1], has_cat=has_cat,
            use_bounds=use_mono_bounds, bound_lo=leaf_lo[:1],
            bound_hi=leaf_hi[:1], leaf_depth=tree.leaf_depth[:1])
        if feat_par:
            # global winner over the column shards (the fused layout is
            # replicated, so local indices ARE global — offset 0)
            root_best = merge_best_over_shards(root_best, psum_axis, 0)
        best = BestSplit(*[
            jnp.zeros((L,) + a.shape[1:], a.dtype).at[0].set(a[0])
            for a in root_best])
        best = best._replace(gain=best.gain.at[1:].set(NEG_INF))

    lpn = jnp.full((L,), -1, jnp.int32)   # leaf -> parent node
    lil = jnp.zeros((L,), bool)           # leaf is left child of its parent

    # per-(leaf, feature) global-validity pool: under voting only the
    # vote winners' columns hold GLOBAL sums; sibling subtraction and
    # later scans must not touch local-only columns (the XLA leaf-wise
    # voting keeps the same plane)
    pool_valid = jnp.ones((L, f_oh), bool)
    # the route log rides the state as its last element (None when the
    # caller does not ask: the grower's trace is then what it always was)
    log = None
    if route_log:
        # padded to the widest level; an all-(-2) table routes nothing
        Sp_max = max([8, *caps])
        log = (None if bins_form
               else jnp.zeros((len(caps), Sp_max, kern_fb), jnp.bfloat16),
               jnp.zeros((len(caps), Sp_max, 128), jnp.int32)
               .at[:, :, 0].set(-2))
    state = (tree, leaf_T, pool_g, pool_h, pool_c, best, lpn, lil,
             leaf_lo, leaf_hi, leaf_groups, reg_lo, reg_hi, pool_valid, log)
    for li, S_d in enumerate(caps):
        state = _one_level(state, bins_T, gh_T, meta, feature_mask, params,
                           L, B, f_oh, S_d, nch, max_depth, has_cat,
                           use_mono_bounds, use_node_masks, node_masks,
                           li + 1, li == len(caps) - 1,
                           bundle_cols, bundle_col_bins, bundle_cfg,
                           interpret, psum_axis,
                           mono_mode, parallel_mode, top_k,
                           feature_shard_mask,
                           quant_bits=quant_bits, packed=packed,
                           decode=_decode, fmask2d=fmask2d,
                           bins_form=bins_form)
    tree, leaf_T = state[0], state[1]
    out = (tree, leaf_T[0])
    if route_log:
        out += (state[-1],)
    return out


@jax.named_scope("level")
def _one_level(state, bins_T, gh_T, meta, feature_mask, params, L, B, f_oh,
               S_d, nch, max_depth, has_cat, use_mono_bounds,
               use_node_masks, node_masks, fold, is_last,
               bundle_cols, bundle_col_bins, bundle_cfg, interpret,
               psum_axis=None, mono_mode="basic", parallel_mode="data", top_k=0,
               feature_shard_mask=None, quant_bits=0, packed=None,
               decode=None, fmask2d=None, bins_form=False):
    (tree, leaf_T, pool_g, pool_h, pool_c, best, lpn, lil,
     leaf_lo, leaf_hi, leaf_groups, reg_lo, reg_hi, pool_valid,
     log) = state
    use_bundles = bundle_cols > 0
    window_decode = use_bundles and bins_form
    inter = use_mono_bounds and mono_mode == "intermediate"
    voting = psum_axis is not None and parallel_mode == "voting"
    # a vote covering every column is statically a full exchange: take
    # the data-parallel path verbatim (a gather+scatter round-trip would
    # leave XLA free to reduce in a different order — one-ULP drift for
    # zero saving)
    vote_live = voting and min(f_oh, 2 * top_k) < f_oh
    feat_par = psum_axis is not None and parallel_mode == "feature"
    Sp = max(8, S_d)
    slots = jnp.arange(L, dtype=jnp.int32)

    gains = _masked_gain(best, tree.leaf_depth, tree.num_leaves, max_depth, L)
    budget = L - tree.num_leaves
    order = jnp.argsort(-gains)
    rank = jnp.zeros((L,), jnp.int32).at[order].set(
        jnp.arange(L, dtype=jnp.int32))
    selected = (gains > 0.0) & (rank < budget) & (rank < S_d)
    n_sel = jnp.sum(selected.astype(jnp.int32))

    def do_level(op):
        return _apply_level(op, False)

    def do_level_route(op):
        # this pass's histograms can never be consumed (no split search
        # will ever run again): route rows + record the splits, skip the
        # histogram dot / pool updates / child scans (~60% of the cost of
        # a deep pass)
        return _apply_level(op, True)

    def _apply_level(op, route_only):
        (tree, leaf_T, pool_g, pool_h, pool_c, best, lpn, lil,
         leaf_lo, leaf_hi, leaf_groups, reg_lo, reg_hi, pool_valid,
         log) = op
        with jax.named_scope("route"):
            sel_i32 = selected.astype(jnp.int32)
            k_of_leaf = jnp.cumsum(sel_i32) - sel_i32
            new_of_leaf = jnp.where(selected, tree.num_leaves + k_of_leaf, -1)
            # node index base: a tree with N leaves has N-1 internal nodes
            node_of_leaf = jnp.where(selected,
                                     tree.num_leaves - 1 + k_of_leaf, -1)

            # ---- slot tables (leaf_of_slot = -2 marks inactive slots so they
            # can never match the -1 of padding rows)
            lof = _masked_scatter(
                jnp.full((Sp,), -2, jnp.int32),
                jnp.minimum(k_of_leaf, Sp - 1), slots,
                selected & (k_of_leaf < Sp))
            lof_on = lof >= 0
            lof_safe = jnp.maximum(lof, 0)
            feat_s = jnp.where(lof_on, best.feature[lof_safe], -1)
            thr_s = best.threshold[lof_safe]
            dl_s = best.default_left[lof_safe]
            cf_s = best.cat_flag[lof_safe] & lof_on
            cm_s = best.cat_mask[lof_safe]
            small_left_s = (best.left_count[lof_safe]
                            <= best.right_count[lof_safe])
            new_s = jnp.where(lof_on, tree.num_leaves + jnp.arange(Sp), 0)
            delta_s = jnp.where(lof_on, new_s - lof_safe, 0)

            tbl = jnp.zeros((Sp, 128), jnp.int32).at[:, :3].set(
                jnp.stack([lof, delta_s, small_left_s.astype(jnp.int32)],
                          axis=1))
            # a categorical slot's left-going bin set, in either form
            sets = dict(cat_flag=cf_s, cat_mask=cm_s) if has_cat else {}
            if bins_form:
                # (route_form) the splits ride the slot table, a
                # categorical one as its bin set, a bundled feature's
                # with its window; no [Sp, FB] table is built
                W = None
                tbl = route_table_columns(
                    tbl, feat_s, thr_s, dl_s, meta.num_bin,
                    meta.missing_type, meta.default_bin, packed,
                    bundle=((bundle_cfg.col_of_feat,
                             bundle_cfg.offset_of_feat,
                             bundle_cfg.default_bin)
                            if use_bundles else None), **sets)
            elif use_bundles:
                W = build_route_table_bundled(
                    feat_s, thr_s, dl_s, meta.num_bin, meta.missing_type,
                    meta.default_bin, bundle_cfg.default_bin,
                    bundle_cfg.col_of_feat, bundle_cfg.offset_of_feat,
                    bundle_cols, bundle_col_bins, **sets)
            else:
                W = build_route_table(feat_s, thr_s, dl_s, meta.num_bin,
                                      meta.missing_type, meta.default_bin,
                                      Sp, f_oh, B, **sets)
                if packed is not None:
                    # route tables are built on the logical padded layout and
                    # re-indexed onto the packed flat axis (exact 0/1 gather)
                    W = pack_route_table(W, packed)
            log2 = log
            if log is not None:
                # ``fold`` is this level's 1-based position in the schedule
                log2 = (None if W is None
                        else log[0].at[fold - 1, :Sp].set(W),
                        log[1].at[fold - 1, :Sp].set(tbl))

        k_foh = bundle_cols if use_bundles else f_oh
        k_B = bundle_col_bins if use_bundles else B
        with jax.named_scope("route" if route_only else "hist"):
            # ---- THE level pass: route (+ smaller-child histograms)
            if route_only:
                leaf_T2 = route_pass(bins_T, leaf_T, W, tbl, num_slots=Sp,
                                     num_bins=k_B, f_oh=k_foh,
                                     interpret=interpret, packed=packed,
                                     has_cat=has_cat,
                                     bundled=window_decode)
                pool_g2, pool_h2, pool_c2 = pool_g, pool_h, pool_c
                pool_valid2 = pool_valid
            else:
                hist, leaf_T2 = level_pass(
                    bins_T, leaf_T, gh_T, W, tbl, fmask2d, num_slots=Sp,
                    num_bins=k_B, f_oh=k_foh, nch=nch, interpret=interpret,
                    quant_bits=quant_bits, packed=packed, has_cat=has_cat,
                    bundled=window_decode)
                if psum_axis is not None and not vote_live and not feat_par:
                    hist = record_psum(hist, psum_axis)

                # ---- voting exchange: rank local per-feature gains on the
                # smaller-child planes, psum the votes, and sum only the
                # top-W winners' columns over the mesh; everything else is
                # zeroed and marked invalid for later scans
                # (ref: voting_parallel_tree_learner.cpp:151-184; same vote
                # rule as the XLA growers' _exchange)
                if vote_live:
                    # local decode just for the vote ranking
                    lg, lh, lc = decode(hist, Sp)
                    if use_bundles:
                        v = bundle_plane_views(
                            jnp.stack([lg, lh, lc], axis=-1),
                            bundle_cfg.flat_idx, bundle_cfg.valid,
                            bundle_cfg.default_bin)
                        lg, lh, lc = v[..., 0], v[..., 1], v[..., 2]
                    # the smaller child's own post-split output is its
                    # path-smoothing parent (matches the child-scan call)
                    sm_out = jnp.where(
                        small_left_s,
                        jnp.where(lof_on, best.left_output[lof_safe], 0.0),
                        jnp.where(lof_on, best.right_output[lof_safe], 0.0))
                    vote_mask = jnp.broadcast_to(feature_mask[None, :],
                                                 (Sp, f_oh)) & lof_on[:, None]
                    gains_loc = per_feature_gains_cm(
                        lg, lh, lc, meta.num_bin, meta.missing_type,
                        meta.default_bin, vote_mask, meta_is_cat(meta),
                        meta.monotone, params, sm_out, has_cat=has_cat)
                    k_v = min(top_k, f_oh)
                    W_vote = min(f_oh, 2 * top_k)
                    kth = jnp.sort(gains_loc, axis=1)[:, f_oh - k_v][:, None]
                    votes = (gains_loc >= kth) & jnp.isfinite(gains_loc)
                    votes = record_psum(votes.astype(jnp.int32), psum_axis)
                    score_f = jnp.sum(votes, axis=0)
                    _, w_idx = jax.lax.top_k(score_f, W_vote)
                    lvl_valid = jnp.zeros((f_oh,), bool).at[w_idx].set(True)
                    if use_bundles:
                        # logical features interleave inside bundle columns;
                        # exchange the DECODED logical planes (divergence vs
                        # the unbundled path: decode-then-psum rounds
                        # differently than psum-then-decode — documented,
                        # bundles+voting only)
                        stack = jnp.stack([lg, lh, lc], axis=-1)
                        sub = record_psum(jnp.take(stack, w_idx, axis=1),
                                           psum_axis)
                        stack = jnp.zeros_like(stack).at[:, w_idx].set(sub)
                        sm_g, sm_h, sm_c = (stack[..., 0], stack[..., 1],
                                            stack[..., 2])
                    else:
                        # exchange the PACKED hi/lo channels of the winning
                        # columns so the decode happens AFTER the global sum
                        # — bit-identical to the data-parallel path when
                        # every column wins (top_k >= F)
                        hr = hist.reshape(k_foh, k_B, -1)
                        sub = record_psum(jnp.take(hr, w_idx, axis=0),
                                           psum_axis)
                        hr = jnp.zeros_like(hr).at[w_idx].set(sub)
                        hist = hr.reshape(k_foh * k_B, -1)
                        sm_g, sm_h, sm_c = decode(hist, Sp)
                else:
                    lvl_valid = jnp.ones((f_oh,), bool)
                    sm_g, sm_h, sm_c = decode(hist, Sp)
                    if use_bundles:
                        v = bundle_plane_views(
                            jnp.stack([sm_g, sm_h, sm_c], axis=-1),
                            bundle_cfg.flat_idx, bundle_cfg.valid,
                            bundle_cfg.default_bin)
                        sm_g, sm_h, sm_c = v[..., 0], v[..., 1], v[..., 2]

                # ---- sibling by subtraction from the parent pool
                par_g = _pool_read(pool_g, lof_safe, Sp)
                par_h = _pool_read(pool_h, lof_safe, Sp)
                par_c = _pool_read(pool_c, lof_safe, Sp)
                sb_g, sb_h, sb_c = par_g - sm_g, par_h - sm_h, par_c - sm_c
                sl = small_left_s[:, None, None]
                left_g = jnp.where(sl, sm_g, sb_g)
                left_h = jnp.where(sl, sm_h, sb_h)
                left_c = jnp.where(sl, sm_c, sb_c)
                right_g = jnp.where(sl, sb_g, sm_g)
                right_h = jnp.where(sl, sb_h, sm_h)
                right_c = jnp.where(sl, sb_c, sm_c)

                pool_g2 = _pool_write(pool_g, lof_safe, left_g, lof_on)
                pool_g2 = _pool_write(pool_g2, new_s, right_g, lof_on)
                pool_h2 = _pool_write(pool_h, lof_safe, left_h, lof_on)
                pool_h2 = _pool_write(pool_h2, new_s, right_h, lof_on)
                pool_c2 = _pool_write(pool_c, lof_safe, left_c, lof_on)
                pool_c2 = _pool_write(pool_c2, new_s, right_c, lof_on)
                # validity: the exchanged (smaller) side is valid where the
                # vote summed it; the subtracted side additionally needs a
                # globally-valid parent (root is fully valid, so data/
                # feature modes stay all-true)
                if vote_live:
                    par_v = pool_valid[lof_safe]          # [Sp, f_oh]
                    sm_v = jnp.broadcast_to(lvl_valid[None, :], (Sp, f_oh))
                    sb_v = par_v & sm_v
                    sl2 = small_left_s[:, None]
                    left_v = jnp.where(sl2, sm_v, sb_v)
                    right_v = jnp.where(sl2, sb_v, sm_v)
                    pool_valid2 = _masked_scatter(pool_valid, lof_safe,
                                                  left_v, lof_on)
                    pool_valid2 = _masked_scatter(pool_valid2, new_s,
                                                  right_v, lof_on)
                else:
                    pool_valid2 = pool_valid

        with jax.named_scope("book"):
            # ---- tree bookkeeping (ref: tree.h:62 Tree::Split; same node
            # array conventions as models/learner.py's growers)
            f_l = best.feature
            new_depth = tree.leaf_depth + 1

            def w(arr, vals):
                return _masked_scatter(arr, node_of_leaf, vals, selected)
            sf = w(tree.split_feature, f_l)
            tb = w(tree.threshold_bin, best.threshold)
            dfl = w(tree.default_left, best.default_left)
            cfw = w(tree.cat_flag, best.cat_flag)
            cmw = w(tree.cat_mask, best.cat_mask)
            sg = w(tree.split_gain, best.gain)
            iv = w(tree.internal_value, tree.leaf_value)
            ic = w(tree.internal_count, tree.leaf_count)
            iw = w(tree.internal_weight, tree.leaf_weight)
            lc = w(tree.left_child, -slots - 1)
            rc = w(tree.right_child, -new_of_leaf - 1)
            wl = selected & (lpn >= 0) & lil
            wr = selected & (lpn >= 0) & ~lil
            lc = _masked_scatter(lc, lpn, node_of_leaf, wl)
            rc = _masked_scatter(rc, lpn, node_of_leaf, wr)
            lpn2 = jnp.where(selected, node_of_leaf, lpn)
            lil2 = jnp.where(selected, True, lil)
            lpn2 = _masked_scatter(lpn2, new_of_leaf, node_of_leaf, selected)
            lil2 = _masked_scatter(lil2, new_of_leaf, jnp.zeros((L,), bool),
                                   selected)

            def upd2(arr, lv, rv):
                arr = _masked_scatter(arr, slots, lv, selected)
                return _masked_scatter(arr, new_of_leaf, rv, selected)
            if inter:
                # intermediate monotone: sequential per-split clipping/fences
                # over [L]-state (models/learner.mono_inter_level_update);
                # clipped child outputs replace the raw scan outputs
                (lv_inter, leaf_lo2, leaf_hi2, reg_lo2, reg_hi2,
                 mono_changed) = mono_inter_level_update(
                    tree.leaf_value, leaf_lo, leaf_hi, reg_lo, reg_hi,
                    selected, k_of_leaf, best.feature, best.threshold,
                    best.cat_flag, best.left_output, best.right_output,
                    meta.monotone, tree.num_leaves, Sp)
                new_leaf_value = lv_inter
            else:
                new_leaf_value = upd2(tree.leaf_value, best.left_output,
                                      best.right_output)
                reg_lo2, reg_hi2 = reg_lo, reg_hi
                mono_changed = None
            tree2 = tree._replace(
                num_leaves=tree.num_leaves + n_sel,
                split_feature=sf, threshold_bin=tb, default_left=dfl,
                cat_flag=cfw, cat_mask=cmw,
                split_gain=sg, internal_value=iv, internal_count=ic,
                internal_weight=iw, left_child=lc, right_child=rc,
                leaf_value=new_leaf_value,
                leaf_count=upd2(tree.leaf_count, best.left_count,
                                best.right_count),
                leaf_weight=upd2(tree.leaf_weight, best.left_sum_hess,
                                 best.right_sum_hess),
                leaf_depth=upd2(tree.leaf_depth, new_depth, new_depth),
            )

            # ---- bound/group propagation (cheap [L]-sized state upkeep,
            # shared by both variants)
            if use_mono_bounds and not inter:
                mono_dir = jnp.where(
                    best.feature >= 0,
                    meta.monotone[jnp.maximum(best.feature, 0)], 0)
                # reference gates constraint updates on is_numerical_split
                mono_dir = jnp.where(best.cat_flag, 0, mono_dir)
                leaf_lo2, leaf_hi2 = mono_child_bounds(
                    leaf_lo, leaf_hi, leaf_lo, leaf_hi, selected, mono_dir,
                    best.left_output, best.right_output,
                    jnp.arange(L, dtype=jnp.int32), new_of_leaf)
            elif not use_mono_bounds:
                leaf_lo2, leaf_hi2 = leaf_lo, leaf_hi
            if use_node_masks:
                leaf_groups2 = update_leaf_groups(
                    node_masks, leaf_groups, best.feature, selected,
                    jnp.arange(L, dtype=jnp.int32), new_of_leaf)
            else:
                leaf_groups2 = leaf_groups

            if route_only:
                # no split search will ever run again; just bar the fresh
                # leaves (and the reused parent slots) from re-selection
                neg = jnp.full((L,), NEG_INF, jnp.float32)
                g2 = _masked_scatter(best.gain, slots, neg, selected)
                g2 = _masked_scatter(g2, new_of_leaf, neg, selected)
                best2 = best._replace(gain=g2)
                return (tree2, leaf_T2, pool_g2, pool_h2, pool_c2, best2,
                        lpn2, lil2, leaf_lo2, leaf_hi2, leaf_groups2,
                        reg_lo2, reg_hi2, pool_valid2, log2)

        with jax.named_scope("split"):
            # ---- best splits for the 2*Sp fresh children only; each child's
            # own post-split output is the parent_output for path smoothing of
            # its prospective grandchildren (matches learner.py:208 and ref
            # feature_histogram.hpp FindBestThreshold parent_output usage).
            # Intermediate mode reads the CLIPPED outputs from the tree.
            if inter:
                left_out = jnp.where(lof_on, tree2.leaf_value[lof_safe], 0.0)
                right_out = jnp.where(lof_on, tree2.leaf_value[new_s], 0.0)
            else:
                left_out = jnp.where(lof_on, best.left_output[lof_safe], 0.0)
                right_out = jnp.where(lof_on, best.right_output[lof_safe], 0.0)
            ch_g = jnp.concatenate([left_g, right_g], axis=0)
            ch_h = jnp.concatenate([left_h, right_h], axis=0)
            ch_c = jnp.concatenate([left_c, right_c], axis=0)
            if use_mono_bounds:
                ch_lo = jnp.concatenate([leaf_lo2[lof_safe], leaf_lo2[new_s]])
                ch_hi = jnp.concatenate([leaf_hi2[lof_safe], leaf_hi2[new_s]])
            else:
                ch_lo = ch_hi = None
            ch_mask = feature_mask[None, :]
            if vote_live:
                # scans must not read local-only (unexchanged) columns
                ch_mask = ch_mask & jnp.concatenate([left_v, right_v], axis=0)
            if feat_par:
                ch_mask = ch_mask & feature_shard_mask[None, :]
            if use_node_masks:
                ch_groups = jnp.concatenate([leaf_groups2[lof_safe],
                                             leaf_groups2[new_s]])
                # per-node sampling identity: creating node id + side bit
                ch_ids = jnp.concatenate([2 * (node_of_leaf[lof_safe] + 1) + 1,
                                          2 * (node_of_leaf[lof_safe] + 1)])
                ch_mask = ch_mask & node_feature_mask(node_masks, ch_groups,
                                                      ch_ids)
            ch_depth = jnp.concatenate([tree2.leaf_depth[lof_safe],
                                        tree2.leaf_depth[new_s]])
            bs = best_split_cm(
                ch_g, ch_h, ch_c, meta.num_bin, meta.missing_type,
                meta.default_bin, ch_mask, meta_is_cat(meta), meta.monotone,
                params, jnp.concatenate([left_out, right_out]),
                has_cat=has_cat, use_bounds=use_mono_bounds, bound_lo=ch_lo,
                bound_hi=ch_hi, leaf_depth=ch_depth)
            if feat_par:
                # per-level SyncUpGlobalBestSplit over the column shards
                # (ref: parallel_tree_learner.h:191); offset 0 — the fused
                # layout is replicated, local indices are global
                bs = merge_best_over_shards(bs, psum_axis, 0)
            left_bs = BestSplit(*[a[:Sp] for a in bs])
            right_bs = BestSplit(*[a[Sp:] for a in bs])
            best2 = _merge_best_many(best, lof_safe, left_bs, lof_on)
            best2 = _merge_best_many(best2, new_s, right_bs, lof_on)

            if inter:
                # stale-leaf recompute: pre-existing leaves whose bounds the
                # cross-tightening touched re-derive their cached best split
                # from the pool with the new bounds (ref:
                # serial_tree_learner.cpp:706-714 recompute of
                # leaves_to_update)
                def _rescan(b):
                    node_ids = 2 * (lpn2 + 1) + lil2.astype(jnp.int32)
                    m = feature_mask[None, :]
                    if use_node_masks:
                        m = m & node_feature_mask(node_masks, leaf_groups2,
                                                  node_ids)
                    bs_all = best_split_cm(
                        pool_g2, pool_h2, pool_c2, meta.num_bin,
                        meta.missing_type, meta.default_bin,
                        jnp.broadcast_to(m, (L, f_oh)) & pool_valid2,
                        meta_is_cat(meta),
                        meta.monotone, params, tree2.leaf_value,
                        has_cat=has_cat, use_bounds=True, bound_lo=leaf_lo2,
                        bound_hi=leaf_hi2, leaf_depth=tree2.leaf_depth)

                    def merge(old, newv):
                        mm = (mono_changed if old.ndim == 1
                              else mono_changed[:, None])
                        return jnp.where(mm, newv, old)
                    return BestSplit(*[merge(o, n) for o, n in zip(b, bs_all)])

                best2 = jax.lax.cond(jnp.any(mono_changed), _rescan,
                                     lambda b: b, best2)

        return (tree2, leaf_T2, pool_g2, pool_h2, pool_c2, best2, lpn2,
                lil2, leaf_lo2, leaf_hi2, leaf_groups2,
                reg_lo2, reg_hi2, pool_valid2, log2)

    op0 = (tree, leaf_T, pool_g, pool_h, pool_c, best, lpn, lil,
           leaf_lo, leaf_hi, leaf_groups, reg_lo, reg_hi, pool_valid, log)

    def dispatch(op):
        if is_last:
            # final scheduled pass: its histograms are never consumed
            return do_level_route(op)
        # dynamic: once the leaf budget will be exhausted by this level's
        # splits, no later split search can select anything
        budget_after = budget - n_sel
        return jax.lax.cond(budget_after > 0, do_level, do_level_route, op)

    return jax.lax.cond(n_sel > 0, dispatch, lambda op: op, op0)


def replay_route_log(bins_T: jax.Array, log, num_rows: int, *,
                     num_bins: int, f_oh: int, interpret: bool = False,
                     packed=None, has_cat: bool = False,
                     bundled: bool = False) -> jax.Array:
    """Leaf of every row of ``bins_T`` in the tree whose route log
    (``grow_tree_fused(route_log=True)``) is ``log``: start the
    ``num_rows`` real rows at leaf 0 (padding columns at -1) and run one
    ``route_pass`` per logged level that has an active slot. ``bins_T``
    is any [Fp, Rp] matrix in the layout the tables were written over
    (the grower's ``num_bins`` / ``f_oh`` / ``packed`` / ``has_cat``
    kernel layout; ``bundled``: EFB bundle columns, decoded by the
    windows the bins form's tables carry). The decisions are the training
    rows' own in either form (``W @ one_hot > 0.5``, or the bin value
    against the slot's threshold or bin set where the log holds no ``W``:
    exact arithmetic both), so over the training matrix this returns the
    grower's ``row_leaf``. Returns leaf_T [1, Rp] int32."""
    log_W, log_tbl = log
    Rp = bins_T.shape[1]
    leaf_T = jnp.where(jnp.arange(Rp)[None, :] < num_rows, 0, -1) \
        .astype(jnp.int32)

    def level(leaf_T, tables):
        W, tbl = tables
        return jax.lax.cond(
            jnp.any(tbl[:, 0] >= 0),
            lambda lt: route_pass(bins_T, lt, W, tbl,
                                  num_slots=tbl.shape[0], num_bins=num_bins,
                                  f_oh=f_oh, interpret=interpret,
                                  packed=packed, has_cat=has_cat,
                                  bundled=bundled and W is None),
            lambda lt: lt, leaf_T), None

    leaf_T, _ = jax.lax.scan(level, leaf_T, (log_W, log_tbl))
    return leaf_T


def tree_score_delta(tree: TreeArrays, row_leaf: jax.Array, shrinkage,
                     num_rows: int = 0,
                     interpret: bool = False) -> jax.Array:
    """Per-row training-score delta of one freshly grown tree:
    ``shrinkage * leaf_value[row_leaf]`` through the streaming lookup
    kernel, with a dried-up tree's (num_leaves <= 1) contribution zeroed
    — the sync path appends a constant tree for it instead
    (gbdt.cpp:421-437). Shared by the pipelined fast step and the
    megastep scan body so both paths stay bit-identical by
    construction."""
    vals = table_lookup(row_leaf[None, :], tree.leaf_value * shrinkage,
                        interpret=interpret)[0]
    if num_rows:
        vals = vals[:num_rows]
    return jnp.where(tree.num_leaves > 1, vals, 0.0)


def add_leaf_values_to_score(score: jax.Array, row_leaf: jax.Array,
                             leaf_value: jax.Array, shrinkage,
                             interpret: bool = False) -> jax.Array:
    """score += shrinkage * leaf_value[row_leaf] via the streaming lookup
    kernel (ref: score_updater.hpp:88 — O(n) leaf-value add). Padding rows
    (leaf -1) receive 0."""
    Rp = score.shape[0]
    vals = table_lookup(row_leaf[None, :], leaf_value,
                        interpret=interpret)[0]
    return score + shrinkage * vals
