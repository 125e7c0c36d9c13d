"""Fused route+histogram level kernel vs numpy oracle (interpret mode).

Covers the round-2 hot path (ops/fused_level.py): root histogram, mid-tree
routing + smaller-child histograms with missing-bin routing, categorical
route tables, hi/lo bf16 precision recombination, and the table_lookup
score-update kernel. Oracle is plain numpy over the same tables
(ref semantics: src/io/dense_bin.hpp Split + ConstructHistogram).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.fused_level import (NCH_FAST, NCH_PRECISE,
                                          build_route_table, feature_layout,
                                          hist_planes, level_pass, pack_gh,
                                          table_lookup)


def _np_route_left(b, thr, dl, nb, mt, db):
    missing = ((mt == 1) & (b == db)) | ((mt == 2) & (b == nb - 1))
    return np.where(missing, dl, b <= thr)


def _oracle(bins, leaf, grad, hess, w, slots, meta, F, B):
    """Per-slot smaller-child histograms + new leaf ids, in numpy."""
    nb, mt, db = meta
    R = bins.shape[0]
    Sp = len(slots)
    hist = np.zeros((Sp, F, B, 3), np.float64)
    new_leaf = leaf.copy()
    for k, (lf, feat, thr, dl, delta, small_left) in enumerate(slots):
        if lf < 0:
            continue
        on = leaf == lf
        b = bins[:, feat]
        left = _np_route_left(b, thr, dl, nb[feat], mt[feat], db[feat])
        go_right = on & ~left
        new_leaf = np.where(go_right, leaf + delta, new_leaf)
        in_small = on & (left == bool(small_left))
        for f in range(F):
            np.add.at(hist[k, f, :, 0], bins[in_small, f], grad[in_small])
            np.add.at(hist[k, f, :, 1], bins[in_small, f], hess[in_small])
            np.add.at(hist[k, f, :, 2], bins[in_small, f], w[in_small])
    return hist, new_leaf


def _setup(R=1024, F=5, B=16, seed=0):
    rng = np.random.RandomState(seed)
    nb = np.array([B, B - 3, B, 7, B], np.int32)[:F]
    mt = np.array([0, 1, 2, 0, 2], np.int32)[:F]
    db = np.array([0, 4, 0, 0, 0], np.int32)[:F]
    bins = np.stack([rng.randint(0, nb[f], size=R) for f in range(F)],
                    axis=1).astype(np.int8)
    grad = rng.randn(R).astype(np.float32)
    hess = np.abs(rng.randn(R)).astype(np.float32) + 0.1
    w = np.ones(R, np.float32)
    return bins, grad, hess, w, (nb, mt, db)


def _run_level(bins, leaf, grad, hess, w, slots, meta, F, B, nch):
    nb, mt, db = meta
    F_oh, Bp = feature_layout(F, B)
    assert Bp == B
    R = bins.shape[0]
    C = 256
    Rp = ((R + C - 1) // C) * C
    Fp = max(F_oh, 8)
    bins_T = np.zeros((Fp, Rp), np.int8)
    bins_T[:F, :R] = bins.T
    leaf_T = np.full((1, Rp), -1, np.int32)
    leaf_T[0, :R] = leaf
    gpad = np.zeros(Rp, np.float32)
    gpad[:R] = grad
    hpad = np.zeros(Rp, np.float32)
    hpad[:R] = hess
    wpad = np.zeros(Rp, np.float32)
    wpad[:R] = w

    Sp = len(slots)
    feat = jnp.asarray([s[1] if s[0] >= 0 else -1 for s in slots], jnp.int32)
    thr = jnp.asarray([s[2] for s in slots], jnp.int32)
    dl = jnp.asarray([bool(s[3]) for s in slots])
    W = build_route_table(feat, thr, dl, jnp.asarray(nb), jnp.asarray(mt),
                          jnp.asarray(db), Sp, F_oh, B)
    tbl = np.zeros((Sp, 128), np.int32)
    for k, (lf, _, _, _, delta, small_left) in enumerate(slots):
        tbl[k, 0] = lf
        tbl[k, 1] = delta
        tbl[k, 2] = int(small_left)

    gh_T = pack_gh(jnp.asarray(gpad), jnp.asarray(hpad), jnp.asarray(wpad),
                   nch)
    hist, new_leaf = level_pass(
        jnp.asarray(bins_T), jnp.asarray(leaf_T), gh_T, W,
        jnp.asarray(tbl), num_slots=Sp, num_bins=B, f_oh=F_oh, nch=nch,
        tile_rows=C, interpret=True)
    g, h, c = hist_planes(hist, nch, Sp, F_oh, B)
    got = np.stack([np.asarray(g), np.asarray(h), np.asarray(c)],
                   axis=-1)[:, :F]
    return got, np.asarray(new_leaf)[0, :R]


def test_root_pass_histogram():
    bins, grad, hess, w, meta = _setup()
    F, B = 5, 16
    leaf = np.zeros(bins.shape[0], np.int32)
    # root: slot 0 collects everything (W row routes all rows left)
    slots = [(0, 0, B - 1, True, 0, 1)] + [(-1, 0, 0, 0, 0, 0)] * 7
    got, new_leaf = _run_level(bins, leaf, grad, hess, w, slots, meta, F, B,
                               NCH_PRECISE)
    want, want_leaf = _oracle(bins, leaf, grad, hess, w, slots, meta, F, B)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(new_leaf, want_leaf)


@pytest.mark.parametrize("nch", [NCH_PRECISE, NCH_FAST])
def test_mid_level_route_and_hist(nch):
    bins, grad, hess, w, meta = _setup(R=2048)
    F, B = 5, 16
    rng = np.random.RandomState(1)
    leaf = rng.randint(0, 3, size=bins.shape[0]).astype(np.int32)
    # three active slots splitting leaves 0,1,2 on different features,
    # exercising zero- and nan-missing routing + both small sides
    slots = [
        (0, 1, 5, True, 3, 1),    # feature 1: zero-missing, default left
        (1, 2, 7, False, 3, 0),   # feature 2: nan-missing, default right
        (2, 3, 2, True, 3, 1),    # feature 3: 7 bins
    ] + [(-1, 0, 0, 0, 0, 0)] * 5
    got, new_leaf = _run_level(bins, leaf, grad, hess, w, slots, meta, F, B,
                               nch)
    want, want_leaf = _oracle(bins, leaf, grad, hess, w, slots, meta, F, B)
    tol = 1e-4 if nch == NCH_PRECISE else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=float(tol))
    np.testing.assert_array_equal(new_leaf, want_leaf)


def test_precision_hi_lo_beats_bf16():
    """The hi/lo split must recover ~fp32 sums where raw bf16 drifts."""
    bins, grad, hess, w, meta = _setup(R=4096, seed=3)
    F, B = 5, 16
    leaf = np.zeros(bins.shape[0], np.int32)
    slots = [(0, 0, B - 1, True, 0, 1)] + [(-1, 0, 0, 0, 0, 0)] * 7
    want, _ = _oracle(bins, leaf, grad, hess, w, slots, meta, F, B)
    got5, _ = _run_level(bins, leaf, grad, hess, w, slots, meta, F, B,
                         NCH_PRECISE)
    got3, _ = _run_level(bins, leaf, grad, hess, w, slots, meta, F, B,
                         NCH_FAST)
    err5 = np.abs(got5[..., 0] - want[..., 0]).max()
    err3 = np.abs(got3[..., 0] - want[..., 0]).max()
    assert err5 < 1e-3
    assert err5 < err3 / 4


def test_categorical_route_table():
    bins, grad, hess, w, meta = _setup(R=2048, seed=5)
    F, B = 5, 16
    rng = np.random.RandomState(2)
    leaf = rng.randint(0, 2, size=bins.shape[0]).astype(np.int32)
    nb, mt, db = meta
    cat_mask = np.zeros((8, B), bool)
    cat_mask[0, [1, 3, 4]] = True       # bins {1,3,4} of feature 0 go left
    slots = [(0, 0, 0, False, 2, 1)] + [(-1, 0, 0, 0, 0, 0)] * 7
    F_oh, _ = feature_layout(F, B)
    feat = jnp.asarray([0] + [-1] * 7, jnp.int32)
    W = build_route_table(
        feat, jnp.zeros(8, jnp.int32), jnp.zeros(8, bool),
        jnp.asarray(nb), jnp.asarray(mt), jnp.asarray(db), 8, F_oh, B,
        cat_flag=jnp.asarray([True] + [False] * 7),
        cat_mask=jnp.asarray(cat_mask))
    # numpy oracle with explicit membership
    on = leaf == 0
    left = cat_mask[0][bins[:, 0]]
    want_leaf = np.where(on & ~left, leaf + 2, leaf)

    C = 256
    R = bins.shape[0]
    Fp = max(F_oh, 8)
    bins_T = np.zeros((Fp, R), np.int8)
    bins_T[:F] = bins.T
    leaf_T = leaf[None, :].astype(np.int32)
    tbl = np.zeros((8, 128), np.int32)
    tbl[0] = 0
    tbl[0, 1] = 2
    tbl[0, 2] = 1
    tbl[1:, 0] = -1
    gh_T = pack_gh(jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(w),
                   NCH_FAST)
    hist, new_leaf = level_pass(
        jnp.asarray(bins_T), jnp.asarray(leaf_T), gh_T, W, jnp.asarray(tbl),
        num_slots=8, num_bins=B, f_oh=F_oh, nch=NCH_FAST, tile_rows=C,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(new_leaf)[0], want_leaf)
    # smaller-child (left side here) grad histogram of feature 0
    in_small = on & left
    want_g = np.zeros(B)
    np.add.at(want_g, bins[in_small, 0], grad[in_small])
    g, _, _ = hist_planes(hist, NCH_FAST, 8, F_oh, B)
    np.testing.assert_allclose(np.asarray(g)[0, 0], want_g, rtol=2e-2,
                               atol=2e-2)


def test_table_lookup():
    rng = np.random.RandomState(0)
    R, L = 4096, 37
    idx = rng.randint(-1, L, size=R).astype(np.int32)
    table = rng.randn(L).astype(np.float32)
    out = table_lookup(jnp.asarray(idx[None, :]), jnp.asarray(table),
                       tile_rows=1024, interpret=True)
    want = np.where(idx >= 0, table[np.clip(idx, 0, L - 1)], 0.0)
    np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-6)


# ---- the two routing forms (PR 28): W @ one_hot against the bin values
FORM_NUM_BIN = np.array([32, 32, 9, 9, 32, 5], np.int32)
FORM_MT = np.array([0, 1, 2, 0, 2, 1], np.int32)      # none, zero, NaN
FORM_DB = np.array([0, 3, 0, 0, 0, 1], np.int32)
FORM_CASES = (
    [f"missing_{name}_default_{'left' if dl else 'right'}"
     for name in ("none", "zero", "nan") for dl in (0, 1)]
    + ["threshold_at_last_bin", "root", "inactive_beside_padding", "packed"])


def _form_case(Sp, case):
    """One level's operands in both forms: (bins_T, leaf_T, W, tbl of the
    table form, tbl of the bins form, packed, the leaves numpy expects)."""
    from lightgbm_tpu.ops.fused_level import (pack_route_table,
                                              root_route_tables,
                                              route_table_columns)
    from lightgbm_tpu.ops.layout import packed_feature_layout
    rng = np.random.RandomState(Sp + len(case))
    F, B, R, Rp = len(FORM_NUM_BIN), 32, 1500, 2048
    F_oh, Bp = feature_layout(F, B)
    assert Bp == B
    bins = np.stack([rng.randint(0, nb, R) for nb in FORM_NUM_BIN], 1)
    pad_f = lambda a: jnp.asarray(np.pad(a, (0, F_oh - F)))
    meta = (pad_f(FORM_NUM_BIN), pad_f(FORM_MT), pad_f(FORM_DB))
    leaf = rng.randint(0, Sp, R).astype(np.int32)
    feat = rng.randint(0, F, Sp).astype(np.int32)
    thr = np.array([rng.randint(0, FORM_NUM_BIN[f]) for f in feat], np.int32)
    dl = rng.randint(0, 2, Sp).astype(bool)
    packed = None
    order = np.arange(F)
    if case.startswith("missing_"):
        _, name, _, side = case.split("_")
        mt_want = {"none": 0, "zero": 1, "nan": 2}[name]
        feat = rng.choice(np.nonzero(FORM_MT == mt_want)[0], Sp) \
            .astype(np.int32)
        thr = np.array([rng.randint(0, FORM_NUM_BIN[f]) for f in feat],
                       np.int32)
        dl[:] = side == "left"
    elif case == "threshold_at_last_bin":
        thr = FORM_NUM_BIN[feat] - 1
    elif case == "inactive_beside_padding":
        feat[1::2] = -1                   # every other slot is off
        leaf[rng.rand(R) < 0.2] = Sp + 3  # a leaf no slot holds
    elif case == "packed":
        packed = packed_feature_layout(FORM_NUM_BIN, B, f_oh=F_oh)
        order = np.asarray(packed.feat_order)
        assert not np.array_equal(order, np.arange(F))
    lof = np.where(feat >= 0, np.arange(Sp), -2).astype(np.int32)
    bins_T = np.zeros((max(F_oh, 8), Rp), np.int8)
    bins_T[:F, :R] = bins[:, order].T
    leaf_T = np.full((1, Rp), -1, np.int32)
    if case == "root":
        leaf[:] = 0
        kern_fb = F_oh * B
        W, tbl = root_route_tables(B, kern_fb, B, False, Sp)
        _, tbl_b = root_route_tables(B, kern_fb, B, True, Sp)
        want = leaf.copy()
    else:
        tbl = np.zeros((Sp, 128), np.int32)
        tbl[:, 0] = lof
        tbl[:, 1] = np.where(feat >= 0, Sp + np.arange(Sp) - lof, 0)
        tbl[:, 2] = rng.randint(0, 2, Sp)
        tbl = jnp.asarray(tbl)
        split = (jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(dl))
        W = build_route_table(*split, *meta, Sp, F_oh, B)
        if packed is not None:
            W = pack_route_table(W, packed)
        tbl_b = route_table_columns(tbl, *split, *meta, packed)
        want = leaf.copy()
        for k in np.nonzero(feat >= 0)[0]:
            f = feat[k]
            left = _np_route_left(bins[:, f], thr[k], dl[k], FORM_NUM_BIN[f],
                                  FORM_MT[f], FORM_DB[f])
            want[(leaf == lof[k]) & ~left] += Sp + k - lof[k]
    leaf_T[0, :R] = leaf
    return (jnp.asarray(bins_T), jnp.asarray(leaf_T), W, tbl, tbl_b, packed,
            np.pad(want, (0, Rp - R), constant_values=-1))


@pytest.mark.parametrize("case", FORM_CASES)
@pytest.mark.parametrize("Sp", [8, 64])
def test_bins_form_routes_and_histograms_like_the_table_form(Sp, case):
    """The same splits as a [Sp, FB] table and as the slot table's columns
    3-6: identical leaves from both kernels, identical histogram."""
    from lightgbm_tpu.ops.fused_level import route_pass
    bins_T, leaf_T, W, tbl, tbl_b, packed, want = _form_case(Sp, case)
    rng = np.random.RandomState(7)
    Rp = bins_T.shape[1]
    gh_T = pack_gh(jnp.asarray(rng.randn(Rp).astype(np.float32)),
                   jnp.asarray(rng.rand(Rp).astype(np.float32) + 0.1),
                   jnp.ones((Rp,), jnp.float32), NCH_PRECISE)
    F_oh, B = feature_layout(len(FORM_NUM_BIN), 32)
    kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, tile_rows=512,
              interpret=True, packed=packed)
    hist_t, leaf_t = level_pass(bins_T, leaf_T, gh_T, W, tbl, **kw)
    hist_b, leaf_b = level_pass(bins_T, leaf_T, gh_T, None, tbl_b, **kw)
    assert np.array_equal(np.asarray(leaf_t)[0], want)
    assert np.array_equal(np.asarray(leaf_b), np.asarray(leaf_t))
    assert np.array_equal(np.asarray(hist_b), np.asarray(hist_t))
    assert np.abs(np.asarray(hist_t)).sum() > 0
    for w, t in ((W, tbl), (None, tbl_b)):
        routed = route_pass(bins_T, leaf_T, w, t, **kw)
        assert np.array_equal(np.asarray(routed), np.asarray(leaf_t))
    moved = (want != np.asarray(leaf_T)[0]).sum()
    if case == "threshold_at_last_bin":
        assert moved < 100        # only missing rows that default right
    elif case != "root":
        assert moved > 100


# ---- the one-hot in slabs (PR 31): the bins form builds SLAB_ROWS rows of
# the one-hot at a time and has no [FB, C] scratch; the table form builds
# all of it through the same _onehot_slab into its scratch
def _slab_case(Sp, F, variant, R=1024, seed=0):
    """One level's operands at 63 bins a feature, in both forms: (kw of
    level_pass, operands of the table form, operands of the bins form,
    the float64 sums of the g channel [F_oh*B, Sp] on the padded layout)."""
    from lightgbm_tpu.ops.fused_level import (expand_feature_mask,
                                              pack_gh_quant,
                                              pack_route_table,
                                              route_table_columns)
    from lightgbm_tpu.ops.layout import packed_feature_layout
    rng = np.random.RandomState(seed + Sp + F)
    max_bin = 63
    F_oh, B = feature_layout(F, max_bin)
    num_bin = np.full(F, max_bin, np.int32)
    if variant == "packed":
        num_bin[1::2] = 9
    mt = rng.randint(0, 3, F).astype(np.int32)
    bins = np.stack([rng.randint(0, nb, R) for nb in num_bin]) \
        .astype(np.int8)                                       # [F, R]
    packed, order = None, np.arange(F)
    if variant == "packed":
        packed = packed_feature_layout(num_bin, max_bin, f_oh=F_oh)
        order = np.asarray(packed.feat_order)
    bins_T = np.zeros((max(F_oh, 8), R), np.int8)
    bins_T[:F] = bins[order]
    leaf = rng.randint(0, Sp, R).astype(np.int32)
    feat = rng.randint(0, F, Sp).astype(np.int32)
    feat[Sp - Sp // 4:] = -1
    thr = np.array([rng.randint(0, num_bin[max(f, 0)]) for f in feat],
                   np.int32)
    dl = rng.randint(0, 2, Sp).astype(bool)
    small_left = rng.randint(0, 2, Sp)
    lof = np.where(feat >= 0, np.arange(Sp), -2).astype(np.int32)
    tbl = np.zeros((Sp, 128), np.int32)
    tbl[:, 0], tbl[:, 1], tbl[:, 2] = lof, np.where(feat >= 0, Sp, 0), \
        small_left
    tbl = jnp.asarray(tbl)
    pad_f = lambda a: jnp.asarray(np.pad(a, (0, F_oh - F)))
    meta = (pad_f(num_bin), pad_f(mt), jnp.zeros((F_oh,), jnp.int32))
    split = (jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(dl))
    W = build_route_table(*split, *meta, Sp, F_oh, B)
    if packed is not None:
        W = pack_route_table(W, packed)
    tbl_b = route_table_columns(tbl, *split, *meta, packed)
    grad = jnp.asarray(rng.randn(R).astype(np.float32))
    hess = jnp.asarray(rng.rand(R).astype(np.float32) + 0.1)
    ones = jnp.ones((R,), jnp.float32)
    kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, interpret=True,
              packed=packed)
    if variant == "quant8":
        gh_T, _ = pack_gh_quant(grad, hess, ones, 8, jnp.uint32(5))
        kw.update(quant_bits=8, nch=3)
    else:
        gh_T = pack_gh(grad, hess, ones, NCH_PRECISE)
    fmask = None
    keep = np.ones(F_oh, bool)
    if variant == "fmask":
        keep[rng.rand(F_oh) < 0.4] = False
        keep[0] = True
        keep[feat[feat >= 0]] = True   # a screened feature is never split on
        fb = expand_feature_mask(jnp.asarray(keep), F_oh, B)
        fmask = jnp.broadcast_to(fb[:, None], (F_oh * B, 128)) \
            .astype(jnp.bfloat16)
    # float64 sums of what the kernels multiply: g_hi + g_lo per row
    g64 = (np.asarray(gh_T[0].astype(jnp.float32), np.float64)
           + np.asarray(gh_T[1].astype(jnp.float32), np.float64))
    want = np.zeros((F_oh * B, Sp))
    for k in np.nonzero(feat >= 0)[0]:
        f = feat[k]
        nbf = num_bin[f]
        left = _np_route_left(bins[f], thr[k], dl[k], nbf, mt[f], 0)
        rows = np.nonzero((leaf == lof[k]) & (left == bool(small_left[k])))[0]
        for j in np.nonzero(keep[:F])[0]:
            np.add.at(want[j * B:(j + 1) * B, k], bins[j, rows], g64[rows])
        if F_oh > F and keep[F]:       # padding features hold bin 0
            want[F * B::B, k] = g64[rows].sum()
    ops = (jnp.asarray(bins_T), jnp.asarray(leaf)[None, :], gh_T)
    return kw, ops + (W, tbl, fmask), ops + (None, tbl_b, fmask), want


@pytest.mark.parametrize("variant", ["plain", "fmask", "quant8", "packed"])
@pytest.mark.parametrize("F", [28, 137], ids=["28feat", "137feat_odd_tail"])
@pytest.mark.parametrize("Sp", [8, 64])
def test_slab_build_equals_the_whole_scratch_build(Sp, F, variant):
    """At an equal tile the bins form's slab-wise one-hot (137 features: 17
    slabs of 8 and a tail of 2) gives the histogram of the whole-scratch
    build bit for bit, and the same leaves."""
    kw, table_ops, bins_ops, _ = _slab_case(Sp, F, variant)
    hist_t, leaf_t = level_pass(*table_ops, tile_rows=512, **kw)
    hist_b, leaf_b = level_pass(*bins_ops, tile_rows=512, **kw)
    assert np.array_equal(np.asarray(leaf_b), np.asarray(leaf_t))
    assert np.array_equal(np.asarray(hist_b), np.asarray(hist_t))
    assert np.abs(np.asarray(hist_b)).sum() > 0


@pytest.mark.parametrize("F", [28, 137])
def test_slab_build_at_a_larger_tile_is_as_close_to_float64(F):
    """Where the tile differs (512 against the parent's 128) the float32
    partial sums group differently and the last bits may move: both stay
    within 1e-6 of the float64 sum of the same bf16 channel values,
    relative to the largest sum (on a v5e at 6.81M rows: 1.0e-6 the
    whole-scratch build at 128 rows, 0.4-0.7e-6 the slabs at 1,024-2,048;
    PERF.md section 6, PR 31)."""
    Sp = 16
    kw, table_ops, bins_ops, want = _slab_case(Sp, F, "plain", R=2048)
    errs = {}
    for name, ops, tile in (("scratch_128", table_ops, 128),
                            ("slab_512", bins_ops, 512)):
        hist, _ = level_pass(*ops, tile_rows=tile, **kw)
        g, _, _ = hist_planes(hist, NCH_PRECISE, Sp, kw["f_oh"],
                              kw["num_bins"])
        got = np.asarray(g, np.float64).reshape(Sp, -1).T
        errs[name] = np.abs(got - want).max() / np.abs(want).max()
    assert errs["scratch_128"] <= 1e-6, errs
    assert errs["slab_512"] <= 1e-6, errs


def test_packed_equals_padded_at_the_slab_builds_own_tile():
    """The adaptive layout's twin takes the padded layout's tile (2,048
    rows here, where the whole-scratch build took 128): the decoded planes
    are the padded kernel's bit for bit."""
    from lightgbm_tpu.ops.fused_level import default_tile_rows
    Sp, F = 16, 137
    kw_p, _, packed_ops, _ = _slab_case(Sp, F, "packed", R=4096, seed=3)
    packed = kw_p["packed"]
    F_oh, B = kw_p["f_oh"], kw_p["num_bins"]
    Fp = packed_ops[0].shape[0]
    assert default_tile_rows(Sp, F_oh * B, NCH_PRECISE, bins_rows=Fp) == 2048
    assert default_tile_rows(Sp, F_oh * B, NCH_PRECISE) == 128
    # the padded twin: the same rows in logical order, the splits' feature
    # rows mapped back
    order = np.asarray(packed.feat_order)
    bins_T = np.zeros_like(np.asarray(packed_ops[0]))
    bins_T[order] = np.asarray(packed_ops[0])[:len(order)]
    tbl_b = np.asarray(packed_ops[4]).copy()
    rows = tbl_b[:, 6]
    tbl_b[:, 6] = np.where(rows >= 0, order[np.maximum(rows, 0)], -1)
    padded_ops = (jnp.asarray(bins_T),) + packed_ops[1:4] \
        + (jnp.asarray(tbl_b), None)
    hist_k, leaf_k = level_pass(*packed_ops, **kw_p)
    hist_p, leaf_p = level_pass(*padded_ops, **dict(kw_p, packed=None))
    assert np.array_equal(np.asarray(leaf_k), np.asarray(leaf_p))
    # (the padding feature, all bin 0, has no row in the packed layout)
    for a, b in zip(hist_planes(hist_k, NCH_PRECISE, Sp, F_oh, B,
                                packed=packed),
                    hist_planes(hist_p, NCH_PRECISE, Sp, F_oh, B)):
        assert np.array_equal(np.asarray(a)[:, :F], np.asarray(b)[:, :F])
    assert np.abs(np.asarray(hist_p)).sum() > 0


# ---- which operand of the histogram dot the MXU streams (PR 36): the bins
# form's slab dot in both orders, and level_build's rule for the order
def _pass_in_order(monkeypatch, order, *ops, **kw):
    """``level_pass`` with level_build's ``dot`` forced to ``order``
    (the jitted wrapper caches on its static arguments, which do not
    include the rule: the plain function is called instead)."""
    from lightgbm_tpu.ops import fused_level as fl
    rule = fl.level_build
    with monkeypatch.context() as patch:
        patch.setattr(fl, "level_build",
                      lambda *a, **k: dict(rule(*a, **k), dot=order))
        out = fl.level_pass.__wrapped__(*ops, **kw)
    return [np.asarray(o) for o in out]


def _order_case(Sp, case):
    """One bins-form level at Higgs's width (28 features x 64 bins, FB
    1,792: three slabs of 512 and a tail of 256), 1,500 rows padded to
    2,048: (level_pass kw, operands, the leaves and the [FB, nch*Sp]
    float64 sums numpy expects from the slot table's own columns)."""
    from lightgbm_tpu.ops.fused_level import (TBL_CAT_FLAG, TBL_CAT_WORD0,
                                              expand_feature_mask,
                                              route_table_columns)
    rng = np.random.RandomState(Sp + len(case))
    F, max_bin, R, Rp = 28, 63, 1500, 2048
    F_oh, B = feature_layout(F, max_bin)
    bins = rng.randint(0, max_bin, (F, R))
    leaf = rng.randint(0, Sp, R).astype(np.int32)
    feat = rng.randint(0, F, Sp).astype(np.int32)
    if case == "inactive_beside_padding":
        feat[1::2] = -1                   # every other slot is off
        leaf[rng.rand(R) < 0.2] = Sp + 3  # a leaf no slot holds
    lof = np.where(feat >= 0, np.arange(Sp), -2).astype(np.int32)
    tbl = np.zeros((Sp, 128), np.int32)
    tbl[:, 0], tbl[:, 1] = lof, np.where(feat >= 0, Sp, 0)
    tbl[:, 2] = rng.randint(0, 2, Sp)
    split = (jnp.asarray(feat),
             jnp.asarray(rng.randint(0, max_bin - 1, Sp).astype(np.int32)),
             jnp.asarray(rng.randint(0, 2, Sp).astype(bool)))
    meta = (jnp.full((F_oh,), max_bin, jnp.int32),
            jnp.asarray(rng.randint(0, 3, F_oh).astype(np.int32)),
            jnp.zeros((F_oh,), jnp.int32))
    sets = {}
    if case == "has_cat":                 # the first ten columns: bin sets
        flag = (feat >= 0) & (feat < 10)
        mask = np.zeros((Sp, B), bool)
        for k in np.flatnonzero(flag):
            mask[k, rng.choice(max_bin, rng.randint(1, 33), False)] = True
        sets = dict(cat_flag=jnp.asarray(flag), cat_mask=jnp.asarray(mask))
    t = np.asarray(route_table_columns(jnp.asarray(tbl), *split, *meta,
                                       **sets))
    keep = np.ones(F_oh, bool)
    fmask = None
    if case == "fmask":
        keep[rng.rand(F_oh) < 0.4] = False
        keep[0] = True
        fb = expand_feature_mask(jnp.asarray(keep), F_oh, B)
        fmask = jnp.broadcast_to(fb[:, None], (F_oh * B, 128)) \
            .astype(jnp.bfloat16)
    pad = lambda a, fill: np.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Rp - R)],
                                 constant_values=fill)
    gh_T = pack_gh(jnp.asarray(pad(rng.randn(R).astype(np.float32), 0)),
                   jnp.asarray(pad(rng.rand(R).astype(np.float32) + .1, 0)),
                   jnp.asarray(pad(np.ones(R, np.float32), 0)), NCH_PRECISE)
    # numpy, from the table's columns 0-15 alone
    want_leaf = leaf.copy()
    want = np.zeros((F_oh * B, NCH_PRECISE * Sp))
    gh64 = np.asarray(gh_T.astype(jnp.float32), np.float64)[:, :R]
    for k in np.flatnonzero(t[:, 6] >= 0):
        v = bins[t[k, 6]]
        left = np.where(v == t[k, 4], t[k, 5] > 0, v <= t[k, 3])
        if t[k, TBL_CAT_FLAG]:
            words = t[k, TBL_CAT_WORD0:TBL_CAT_WORD0 + 8].view(np.uint32)
            left = (words[v >> 5] >> (v & 31).astype(np.uint32)) & 1 > 0
        on = leaf == t[k, 0]
        want_leaf[on & ~left] += t[k, 1]
        rows = np.flatnonzero(on & (left == (t[k, 2] > 0)))
        for f in np.flatnonzero(keep[:F]):
            for c in range(NCH_PRECISE):
                np.add.at(want[f * B:(f + 1) * B, c * Sp + k],
                          bins[f, rows], gh64[c, rows])
    bins_T = np.zeros((max(F_oh, 8), Rp), np.int8)
    bins_T[:F, :R] = bins
    kw = dict(num_slots=Sp, num_bins=B, f_oh=F_oh, tile_rows=512,
              interpret=True, has_cat=bool(sets))
    ops = (jnp.asarray(bins_T), jnp.asarray(pad(leaf, -1))[None, :], gh_T,
           None, jnp.asarray(t), fmask)
    return kw, ops, pad(want_leaf, -1), want


@pytest.mark.parametrize("case", ["numerical", "has_cat", "fmask",
                                  "inactive_beside_padding"])
@pytest.mark.parametrize("Sp", [8, 32, 64])
def test_both_operand_orders_of_the_histogram_dot(monkeypatch, Sp, case):
    """The slab dot with the one-hot streamed (``onehot``: the order until
    PR 36) and with the channels streamed into a transposed accumulator
    (``channels``): the same leaves, the same [FB, nch*Sp] histogram bit
    for bit in interpret mode, numpy's sums, and the wired kernel is one
    of the two."""
    kw, ops, want_leaf, want = _order_case(Sp, case)
    hist_o, leaf_o = _pass_in_order(monkeypatch, "onehot", *ops, **kw)
    hist_c, leaf_c = _pass_in_order(monkeypatch, "channels", *ops, **kw)
    assert np.array_equal(leaf_o[0], want_leaf)
    assert np.array_equal(leaf_c, leaf_o)
    assert hist_c.shape == hist_o.shape == want.shape
    assert np.array_equal(hist_c, hist_o)
    np.testing.assert_allclose(hist_o, want, rtol=1e-5, atol=1e-5)
    assert np.abs(want).sum() > 0
    hist_w, leaf_w = level_pass(*ops, **kw)
    assert np.array_equal(np.asarray(hist_w), hist_o)
    assert np.array_equal(np.asarray(leaf_w), leaf_o)


# a tree's histogram passes at the five cells' shapes (the root at 8 slots,
# then every level of the schedule but the one that spends the leaf budget)
# -> how many stream the channels
CELL_SHAPES = [
    ("higgs63", dict(features=28, max_bin=63), 9, 9),
    ("higgs63-goss", dict(features=28, max_bin=63), 9, 9),
    ("higgs63-dp4", dict(features=28, max_bin=63), 9, 9),
    ("expo255-cat", dict(features=8, max_bin=255, has_cat=True), 9, 9),
    ("msltr63", dict(features=137, max_bin=63), 19, 19),
    ("higgs63-table-form", dict(features=28, max_bin=63, bins_form=False),
     9, 0),
    ("higgs63-int8", dict(features=28, max_bin=63, quant=True), 9, 0),
    ("higgs63-3-channels", dict(features=28, max_bin=63, nch=NCH_FAST), 9,
     9),
]


@pytest.mark.parametrize("name,shape,passes,channels", CELL_SHAPES,
                         ids=[c[0] for c in CELL_SHAPES])
def test_level_build_streams_the_channels_in_the_bf16_slab_build(
        name, shape, passes, channels):
    """``level_build``'s rule, from static shapes alone: a pass of the bf16
    slab build streams the channels wherever its columns would pad an
    N-tile (all nine of a Higgs tree, all nineteen at the ranking cell's
    width: on the chip the order won at 8, 16, 32 and 64 slots alike);
    the table form and the int8 paths, which were not timed, keep the
    one-hot streamed. The build and the tile do not depend on the
    order."""
    from lightgbm_tpu.models.frontier2 import level_caps
    from lightgbm_tpu.ops.fused_level import level_build, max_slot_cap
    shape = dict(shape)
    f_oh, bp = feature_layout(shape.pop("features"), shape.pop("max_bin"))
    nch = shape.pop("nch", NCH_PRECISE)
    bins_form = shape.pop("bins_form", True)
    caps = level_caps(255, -1, 0, slot_cap=max_slot_cap(f_oh * bp,
                                                        NCH_PRECISE))
    sched = [8] + [max(8, c) for c in caps[:-1]]
    builds = [level_build(bins_form, sp, f_oh * bp, nch, max(f_oh, 8),
                          **shape) for sp in sched]
    dots = [b["dot"] for b in builds]
    assert len(sched) == passes
    assert dots.count("channels") == channels
    assert dots.count("onehot") == passes - channels
    assert {b["form"] for b in builds} \
        == {"slab" if bins_form else "scratch"}
    if bins_form:
        assert {b["tile_rows"] for b in builds} == {2048}
    # a whole number of N-tiles pads nothing: 128 slots x 5 channels = 640
    # columns keep the one-hot streamed (2.6 % faster on the chip)
    assert level_build(True, 128, 1024, NCH_PRECISE, 16)["dot"] == "onehot"


def test_pack_gh_splits_into_exact_halves():
    """The high half is the value rounded to bfloat16 on its bits (to
    nearest, ties to even: the float32 value whose low 16 bits are 0,
    whatever precision the compiler keeps an intermediate at), the low
    half the rest: together 17 bits of mantissa."""
    rng = np.random.RandomState(0)
    g = (rng.randn(4096) * 10.0 ** rng.uniform(-6, 3, 4096)) \
        .astype(np.float32)
    h = np.abs(g) + np.float32(1e-3)
    gh = pack_gh(jnp.asarray(g), jnp.asarray(h), jnp.ones(4096, jnp.float32),
                 NCH_PRECISE)
    out = np.asarray(gh.astype(jnp.float32))
    for x, hi, lo in ((g, out[0], out[1]), (h, out[2], out[3])):
        assert np.all(hi.view(np.uint32) & 0xFFFF == 0)
        assert np.array_equal(
            hi, np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                           .astype(jnp.float32)))
        np.testing.assert_allclose(hi + lo, x, rtol=2.0 ** -17, atol=0)
    assert np.array_equal(out[4], np.ones(4096))
