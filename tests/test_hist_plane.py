"""Histogram-plane cuts (ROADMAP item 4 / ISSUE 14): quantized gradient
histograms, EMA-FS gain screening, adaptive per-feature bins.

Contracts under test:
- the one layout rule (ops/layout.feature_layout) and the packed-layout
  index maps;
- masked (slot == -1 / leaf == -1) rows with NONZERO gh contribute
  nothing in the XLA formulations and in the fused level kernel;
- quantization: stochastic rounding determinism + integer exactness,
  int16/int8 channel encode/decode roundtrip, kernel-level parity
  (exact on an integer grid, bounded error on random grads),
  rerun determinism, and cross-driver statistical parity (cross-driver
  BIT identity is deliberately not claimed — see
  test_quant_deterministic_and_cross_driver_parity);
- adaptive bins: kernel- and model-level BYTE-IDENTITY vs the padded
  layout;
- screening: a feature screened out by an adversarial EMA re-enters
  through an exploration round; statistical parity (slow);
- composition: all three cuts ride the megastep at the same dispatch
  schedule, the analytic byte model halves, the psum payload shrinks
  under the adaptive layout, and the EMA survives a checkpoint
  round-trip bit-identically.
"""
import shutil

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import fused_level as fl
from lightgbm_tpu.ops import quantize
from lightgbm_tpu.ops.histogram import _choose_chunk, build_histograms
from lightgbm_tpu.ops.layout import (feature_layout, hist_plane_bytes,
                                     packed_feature_layout)

KNOBS = {"tpu_quantized_grad": 16, "tpu_gain_screening": True,
         "tpu_screening_warmup": 2, "tpu_screening_explore_period": 4,
         "tpu_adaptive_bins": True}
BASE = {"objective": "binary", "max_bin": 63, "num_leaves": 7,
        "min_data_in_leaf": 5, "verbose": -1, "metric": "None",
        "tpu_engine": "fused", "num_iterations": 4}


def _mixed_data(seed=0, n=512, f=8):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, f).astype(np.float32)
    X[:, f // 2:] = np.floor(X[:, f // 2:] * 8.0) / 8.0   # 8 levels
    y = (X @ rng.randn(f).astype(np.float32) > 0).astype(np.float32)
    return X, y


def _train(X, y, params, n=None, **kw):
    ds = lgb.Dataset(X, label=y, params={"max_bin": params.get(
        "max_bin", 63), "verbose": -1})
    p = dict(params)
    if n is not None:
        p["num_iterations"] = n
    return lgb.train(p, ds, **kw)


def _trees(bst):
    # the saved-parameters block echoes the knob values; the TREES are
    # what the byte-identity contracts cover
    return bst.model_to_string(num_iteration=-1).split("\nparameters:")[0]


# ---------------------------------------------------------------- layout
def test_shared_layout_contract():
    for F in (1, 3, 8, 28, 130):
        for mb in (2, 15, 63, 255, 300):
            Fp, Bp = feature_layout(F, mb)
            assert fl.feature_layout(F, mb) == (Fp, Bp)
            assert (Fp * Bp) % 128 == 0 and Fp >= F and Bp >= mb


def test_packed_layout_maps():
    nb = np.array([63, 9, 9, 2, 63, 17, 9, 9, 9, 9, 9, 0], np.int32)
    pk = packed_feature_layout(nb, 63)                   # 0 = padding feat
    assert pk.fb % 128 == 0
    assert set(pk.feat_order) == set(range(11))          # padding dropped
    # widths are pow2 >= num_bin, >= 8
    for j, f in enumerate(pk.feat_order):
        assert pk.widths[j] >= max(8, nb[f])
        assert pk.widths[j] & (pk.widths[j] - 1) == 0
    # round-trip: padded flat -> packed -> padded is identity where valid
    p2p = pk.padded_to_packed
    back = pk.packed_to_padded
    valid = pk.padded_valid
    idx = np.nonzero(valid)[0]
    assert np.array_equal(back[p2p[idx]], idx)
    # every real (feature, bin < num_bin) position is representable
    for f in range(11):
        for b in range(nb[f]):
            assert valid[f * pk.bp + b]
    # byte model shrinks vs padded and shrinks again under quantization
    Fp, Bp = feature_layout(len(nb), 63)
    assert pk.fb < Fp * Bp
    b_f32 = hist_plane_bytes(Fp * Bp, 5, 64, 4096, 1024, 0)
    b_cut = hist_plane_bytes(pk.fb, 5, 64, 4096, 1024, 16)
    assert b_cut < b_f32 / 2


def test_choose_chunk_scales_with_elem_width():
    c4 = _choose_chunk(10 ** 7, 28, 64, elem_bytes=4)
    c2 = _choose_chunk(10 ** 7, 28, 64, elem_bytes=2)
    c1 = _choose_chunk(10 ** 7, 28, 64, elem_bytes=1)
    assert c4 <= c2 <= c1
    assert c1 >= 2 * c4 or c1 == 1 << 15   # capped at the row-chunk max
    # in the scaling regime (between the 256 floor and the 2^15 cap) the
    # chunk grows with the inverse element width
    big = _choose_chunk(10 ** 7, 512, 64, elem_bytes=4)
    assert 256 < big < (1 << 15)
    assert _choose_chunk(10 ** 7, 512, 64, elem_bytes=1) >= 2 * big


# ------------------------------------------------------------ quantize
def test_stochastic_round_deterministic_and_exact_on_integers():
    x = jnp.asarray(np.random.RandomState(0).randn(4096) * 100)
    a = np.asarray(quantize.stochastic_round(x, 7))
    b = np.asarray(quantize.stochastic_round(x, 7))
    c = np.asarray(quantize.stochastic_round(x, 8))
    assert np.array_equal(a, b)           # deterministic given seed
    assert not np.array_equal(a, c)       # seed actually dithers
    assert np.max(np.abs(a - np.asarray(x))) <= 1.0   # floor/ceil only
    xi = jnp.asarray(np.arange(-2000, 2000, dtype=np.float32))
    assert np.array_equal(np.asarray(quantize.stochastic_round(xi, 3)),
                          np.arange(-2000, 2000))     # integers exact


@pytest.mark.parametrize("bits", [8, 16])
def test_quant_encode_decode_roundtrip(bits):
    rng = np.random.RandomState(1)
    qmax = quantize.QMAX[bits]
    q_g = rng.randint(-qmax, qmax + 1, 2048).astype(np.int32)
    q_h = rng.randint(-qmax, qmax + 1, 2048).astype(np.int32)
    w = (rng.rand(2048) < 0.8).astype(np.float32)
    q_g = (q_g * w).astype(np.int32)      # zero-weight rows carry zero
    q_h = (q_h * w).astype(np.int32)
    rows = quantize.encode_channels(jnp.asarray(q_g), jnp.asarray(q_h),
                                    jnp.asarray(w), bits)
    assert len(rows) == quantize.QNCH[bits]
    assert all(r.dtype == jnp.int8 for r in rows)
    # per-row sums through the channel decode == direct integer sums
    planes = [jnp.sum(r.astype(jnp.int32)).reshape(1, 1) for r in rows]
    scales = jnp.asarray([1.0, 1.0], jnp.float32)
    g, h, c = quantize.decode_sums(planes, scales, bits)
    assert int(g[0, 0]) == int(q_g.sum())
    assert int(h[0, 0]) == int(q_h.sum())
    assert int(c[0, 0]) == int(w.sum())


def test_decode_sums_no_int32_overflow_at_scale():
    """A root-level bin holding 200K rows of near-max hessian: the
    16-bit hi/lo recombination must happen in f32 — an int32
    ``256 * hi_sum`` would wrap at ~65K such rows (regression test for
    the review-caught overflow)."""
    n = 200_000
    q = np.full(n, quantize.QMAX[16], np.int32)     # non-canceling
    w = np.ones(n, np.float32)
    rows = quantize.encode_channels(jnp.asarray(q), jnp.asarray(q),
                                    jnp.asarray(w), 16)
    planes = [jnp.sum(r.astype(jnp.int32)).reshape(1, 1) for r in rows]
    scales = jnp.asarray([1.0, 1.0], jnp.float32)
    g, h, c = quantize.decode_sums(planes, scales, 16)
    expect = float(n) * quantize.QMAX[16]
    assert float(h[0, 0]) > 0
    assert abs(float(h[0, 0]) - expect) / expect < 1e-6
    assert abs(float(g[0, 0]) - expect) / expect < 1e-6
    assert float(c[0, 0]) == float(n)


# ---------------------------------------------------- masked-row contract
def _masked_row_inputs():
    rng = np.random.RandomState(2)
    R, F, B, S = 512, 4, 16, 3
    bins = rng.randint(0, B, (R, F)).astype(np.int32)
    gh = rng.randn(R, 3).astype(np.float32)   # NONZERO gh everywhere
    gh[:, 2] = 1.0
    slot = rng.randint(0, S, R).astype(np.int32)
    masked = rng.rand(R) < 0.3
    slot_m = np.where(masked, -1, slot).astype(np.int32)
    return bins, gh, slot, slot_m, masked, (R, F, B, S)


@pytest.mark.parametrize("impl", ["segment", "onehot"])
def test_masked_rows_contribute_nothing_xla(impl):
    bins, gh, slot, slot_m, masked, (R, F, B, S) = _masked_row_inputs()
    h_masked = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(slot_m),
        num_slots=S, num_bins=B, impl=impl))
    gh0 = gh.copy()
    gh0[masked] = 0.0
    h_zeroed = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh0), jnp.asarray(slot_m),
        num_slots=S, num_bins=B, impl=impl))
    assert np.array_equal(h_masked, h_zeroed)
    assert h_masked.sum() != 0.0


def test_masked_rows_contribute_nothing_level_pass():
    """Rows at leaf -1 (the padding rows' leaf) with NONZERO gh leave the
    fused kernel's histogram untouched: bit-equal to the same pass with
    their gh zeroed."""
    rng = np.random.RandomState(1)
    F, R, Sp = 4, 2048, 8
    F_oh, Bp = feature_layout(F, 16)
    bT = np.zeros((max(F_oh, 8), R), np.int8)
    bT[:F] = rng.randint(0, 16, (F, R))
    masked = rng.rand(R) < 0.3
    leaf = jnp.asarray(np.where(masked, -1, 0).astype(np.int32)[None, :])
    g = rng.randn(R).astype(np.float32)
    h = np.abs(rng.randn(R)).astype(np.float32)
    ones = np.ones(R, np.float32)
    _, tbl = fl.root_route_tables(Bp, F_oh * Bp, Bp, True, Sp)

    def hist(keep):
        gh_T = fl.pack_gh(jnp.asarray(g * keep), jnp.asarray(h * keep),
                          jnp.asarray(ones * keep), 5)
        return np.asarray(fl.level_pass(
            jnp.asarray(bT), leaf, gh_T, None, tbl, num_slots=Sp,
            num_bins=Bp, f_oh=F_oh, nch=5, interpret=True)[0])
    h_masked = hist(ones)
    assert np.array_equal(h_masked, hist((~masked).astype(np.float32)))
    assert np.abs(h_masked).sum() > 0.0


# -------------------------------------------------- quantized histograms
def test_xla_quantized_exact_on_integer_grid():
    """When grad/hess are integers whose max-abs equals the grid max,
    the scale is 1.0 and stochastic rounding is exact — the quantized
    histogram must equal the f32 one bit-for-bit."""
    rng = np.random.RandomState(3)
    R, F, B, S = 1024, 4, 16, 2
    bins = rng.randint(0, B, (R, F)).astype(np.int32)
    qmax = quantize.QMAX[16]
    g = rng.randint(-qmax, qmax + 1, R).astype(np.float32)
    g[np.argmax(np.abs(g))] = qmax        # pin the scale to exactly 1
    h = np.abs(rng.randint(-qmax, qmax + 1, R)).astype(np.float32)
    h[np.argmax(h)] = qmax
    gh = np.stack([g, h, np.ones(R, np.float32)], axis=1)
    slot = rng.randint(0, S, R).astype(np.int32)
    hq = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(slot),
        num_slots=S, num_bins=B, quant_bits=16))
    hf = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(slot),
        num_slots=S, num_bins=B, impl="segment"))
    assert np.array_equal(hq, hf)


def test_level_pass_quant_error_bound():
    """Random f32 grads: the quantized level pass reproduces the f32
    histogram within the quantization error model (|noise per row| <=
    scale, summed over a bin)."""
    rng = np.random.RandomState(5)
    F, R = 4, 2048
    bins = rng.randint(0, 16, (F, R)).astype(np.int8)
    F_oh, Bp = feature_layout(F, 16)
    Fp = max(F_oh, 8)
    bT = np.zeros((Fp, R), np.int8)
    bT[:F] = bins
    g = rng.randn(R).astype(np.float32)
    h = np.abs(rng.randn(R)).astype(np.float32)
    ones = np.ones(R, np.float32)
    leaf = jnp.zeros((1, R), jnp.int32)
    Sp = 8
    tbl = (jnp.zeros((Sp, 128), jnp.int32)
           .at[:, 0].set(-2).at[0, 0].set(0).at[0, 2].set(1))
    W = jnp.zeros((Sp, F_oh * Bp), jnp.bfloat16).at[0, :Bp].set(1)
    gh_T = fl.pack_gh(jnp.asarray(g), jnp.asarray(h), jnp.asarray(ones), 5)
    hist_f, _ = fl.level_pass(jnp.asarray(bT), leaf, gh_T, W, tbl,
                              num_slots=Sp, num_bins=Bp, f_oh=F_oh,
                              nch=5, interpret=True)
    gf, hf, cf = fl.hist_planes(hist_f, 5, Sp, F_oh, Bp)
    gh_q, scales = fl.pack_gh_quant(jnp.asarray(g), jnp.asarray(h),
                                    jnp.asarray(ones), 16, np.uint32(9))
    hist_q, _ = fl.level_pass(jnp.asarray(bT), leaf, gh_q, W, tbl,
                              num_slots=Sp, num_bins=Bp, f_oh=F_oh,
                              nch=5, interpret=True, quant_bits=16)
    gq, hq, cq = fl.hist_planes(hist_q, 5, Sp, F_oh, Bp, quant_bits=16,
                                scales=scales)
    assert np.array_equal(np.asarray(cq), np.asarray(cf))   # counts exact
    sg, sh = float(scales[0]), float(scales[1])
    rows_per_bin = np.asarray(cf)[0].max()
    assert float(jnp.max(jnp.abs(gq - gf))) <= sg * (rows_per_bin + 1)
    assert float(jnp.max(jnp.abs(hq - hf))) <= sh * (rows_per_bin + 1)
    # and the bulk is much tighter (sqrt(n) noise, not n)
    assert float(jnp.mean(jnp.abs(gq - gf))) \
        <= sg * np.sqrt(rows_per_bin) * 3


# -------------------------------------------------------- adaptive bins
def test_level_pass_packed_byte_identity():
    rng = np.random.RandomState(6)
    F, R = 8, 2048
    num_bin = np.array([63, 63, 63, 63, 9, 9, 9, 9], np.int32)
    bins = np.stack([rng.randint(0, nb, R) for nb in num_bin]) \
        .astype(np.int8)
    F_oh, Bp = feature_layout(F, 63)
    pk = packed_feature_layout(num_bin, 63, f_oh=F_oh)
    assert pk.fb < F_oh * Bp
    g = rng.randn(R).astype(np.float32)
    h = np.abs(rng.randn(R)).astype(np.float32)
    ones = np.ones(R, np.float32)
    gh_T = fl.pack_gh(jnp.asarray(g), jnp.asarray(h), jnp.asarray(ones), 5)
    leaf = jnp.zeros((1, R), jnp.int32)
    Sp = 8
    tbl = (jnp.zeros((Sp, 128), jnp.int32)
           .at[:, 0].set(-2).at[0, 0].set(0).at[0, 2].set(1))
    W = jnp.zeros((Sp, F_oh * Bp), jnp.bfloat16).at[0, :Bp].set(1)
    hp, _ = fl.level_pass(jnp.asarray(bins), leaf, gh_T, W, tbl,
                          num_slots=Sp, num_bins=Bp, f_oh=F_oh, nch=5,
                          interpret=True)
    ref = fl.hist_planes(hp, 5, Sp, F_oh, Bp)
    order = np.asarray(pk.feat_order)
    Wp = jnp.zeros((Sp, pk.fb), jnp.bfloat16).at[0, :pk.widths[0]].set(1)
    hk, _ = fl.level_pass(jnp.asarray(bins[order]), leaf, gh_T, Wp, tbl,
                          num_slots=Sp, num_bins=Bp, f_oh=F_oh, nch=5,
                          interpret=True, packed=pk)
    out = fl.hist_planes(hk, 5, Sp, F_oh, Bp, packed=pk)
    for a, b in zip(ref, out):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_adaptive_bins_byte_identity_e2e(mixed_models):
    m0, m1, *_ = mixed_models
    assert _trees(m0) == _trees(m1)


# ---------------------------------------------------------- e2e fixtures
@pytest.fixture(scope="module")
def mixed_models():
    """One shared training sweep over the knob matrix (module-scoped:
    interpret-mode compiles dominate, so every e2e assertion reads from
    this sweep instead of retraining)."""
    X, y = _mixed_data()
    m_base = _train(X, y, BASE)
    m_adapt = _train(X, y, dict(BASE, tpu_adaptive_bins=True))
    m_q16 = _train(X, y, dict(BASE, tpu_quantized_grad=16))
    m_q16_rep = _train(X, y, dict(BASE, tpu_quantized_grad=16))
    m_q16_sync = _train(X, y, dict(BASE, tpu_quantized_grad=16,
                                   tpu_fast_path=False))
    return m_base, m_adapt, m_q16, m_q16_rep, m_q16_sync, (X, y)


@pytest.mark.slow
def test_quant_deterministic_and_cross_driver_parity(mixed_models):
    """Quantized runs are DETERMINISTIC: the dither streams are keyed on
    (iteration, class tree) alone, so an identical rerun serializes
    byte-identical trees. Across DRIVERS the contract is parity, not
    bit identity: fast-path and sync-driver scores differ at the ulp
    level (f64-vs-f32 shrinkage rounding), the f32 histogram's bf16
    channels absorb that, but quantization divides it by the grid scale
    in the dither-threshold domain — a near-tie split can legitimately
    flip. The exactness half of the A/B lives at the kernel level
    (test_xla_quantized_exact_on_integer_grid and friends), the
    inexact half in the accuracy-curve suite."""
    m_base, _, m_q16, m_q16_rep, m_q16_sync, (X, y) = mixed_models
    assert _trees(m_q16) == _trees(m_q16_rep)
    acc_f = np.mean((m_q16.predict(X) > 0.5) == y)
    acc_s = np.mean((m_q16_sync.predict(X) > 0.5) == y)
    assert abs(acc_f - acc_s) <= 0.04
    assert m_q16_sync.num_trees() == m_q16.num_trees()


@pytest.mark.slow
def test_quant_changes_models_but_not_quality_much(mixed_models):
    m_base, _, m_q16, _, _, (X, y) = mixed_models
    # quantization legitimately changes the model (stochastic rounding)
    assert _trees(m_base) != _trees(m_q16)
    acc0 = np.mean((m_base.predict(X) > 0.5) == y)
    accq = np.mean((m_q16.predict(X) > 0.5) == y)
    assert accq >= acc0 - 0.05


# ------------------------------------------------------------- screening
def test_screening_reentry():
    """A decisive feature adversarially screened out (its EMA pinned to
    the bottom) must re-enter through an exploration round and win
    splits again."""
    rng = np.random.RandomState(8)
    n, f = 512, 6
    X = rng.rand(n, f).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)      # feature 0 is everything
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    params = dict(BASE, tpu_gain_screening=True, tpu_screening_warmup=0,
                  tpu_screening_keep_ratio=0.34,
                  tpu_screening_explore_period=3, num_iterations=6)
    bst = lgb.Booster(params=params, train_set=ds)
    g = bst._gbdt
    assert g.use_screening
    # adversarial EMA: the decisive feature 0 at the bottom, noise
    # features at the top — the non-exploration mask excludes feature 0
    ema = np.zeros(g.fused_f_oh, np.float32)
    ema[1:f] = 100.0
    g._gain_ema_dev = jnp.asarray(ema)
    for _ in range(6):
        bst.update()
    g.drain_pending()
    used = set()
    for ht in g.models:
        used.update(int(v) for v in np.asarray(ht.split_feature))
    assert 0 in used, "screened-out decisive feature never re-entered"
    # and its realized gains rebuilt the EMA above the noise floor
    ema_after = np.asarray(g._gain_ema_dev)
    assert ema_after[0] > 0.0


@pytest.mark.slow
def test_screening_trains_and_reports_active_features(tmp_path):
    X, y = _mixed_data(seed=9)
    tel = tmp_path / "tel.jsonl"
    params = dict(BASE, tpu_gain_screening=True, tpu_screening_warmup=1,
                  tpu_screening_keep_ratio=0.5, tpu_engine="fused",
                  tpu_megastep=True, telemetry_out=str(tel),
                  num_iterations=6)
    bst = _train(X, y, params)
    snap = bst.telemetry()
    gauges = snap.get("gauges", {})
    F = X.shape[1]
    active = gauges.get("screening.active_features")
    assert active is not None and 1 <= active <= F
    assert active <= int(round(0.5 * F)) + F // 2   # top-k (+ties)


def test_knobs_degrade_off_fused():
    """engine=xla: the cuts degrade with structured events and training
    proceeds unchanged (f32 plane)."""
    X, y = _mixed_data(seed=10)
    m = _train(X, y, dict(BASE, tpu_engine="xla", **KNOBS))
    g = m._gbdt
    assert g.quant_bits == 0 and not g.use_screening \
        and not g.use_adaptive_bins
    assert m.num_trees() == BASE["num_iterations"]


# ----------------------------------------------------------- composition
def test_megastep_all_cuts_dispatch_parity(tmp_path):
    """The acceptance gate: with int16 quantization, screening and
    adaptive bins all on, the megastep still measures the SAME dispatch
    schedule (0.125/iter at 8 iterations = one fused chunk), and the
    analytic histogram byte model drops >= 2x vs the f32 full plane."""
    X, y = _mixed_data(seed=11, n=768, f=10)
    tel0 = tmp_path / "t0.jsonl"
    tel1 = tmp_path / "t1.jsonl"
    p0 = dict(BASE, tpu_megastep=True, telemetry_out=str(tel0),
              num_leaves=15)
    b0 = _train(X, y, p0, n=8)
    c0 = b0.telemetry().get("counters", {})
    g0 = b0.telemetry().get("gauges", {})
    d0 = c0.get("train.dispatches", 0) / max(1, c0.get("iterations", 8))
    p1 = dict(p0, telemetry_out=str(tel1), **KNOBS)
    b1 = _train(X, y, p1, n=8)
    c1 = b1.telemetry().get("counters", {})
    g1 = b1.telemetry().get("gauges", {})
    d1 = c1.get("train.dispatches", 0) / max(1, c1.get("iterations", 8))
    assert d1 == d0 == 0.125
    assert g1.get("hist.quant_bits") == 16.0
    assert g1.get("hist.bytes_per_iter") > 0
    ratio = g0.get("hist.bytes_per_iter") / g1.get("hist.bytes_per_iter")
    assert ratio >= 2.0, f"histogram byte model only dropped {ratio:.2f}x"


def test_collectives_payload_shrinks_with_cuts():
    """The data-parallel per-level psum payload (trace-time recorder,
    ops/collectives.py) shrinks under the adaptive layout — what the
    multi-chip megastep would actually put on the wire."""
    from jax.sharding import Mesh, PartitionSpec as P
    from lightgbm_tpu.ops.collectives import CollectiveTrace
    from lightgbm_tpu.models.frontier2 import grow_tree_fused
    from lightgbm_tpu.models.learner import FeatureMeta
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.mesh import shard_map as _shard_map

    rng = np.random.RandomState(12)
    F, R = 8, 2048
    num_bin = np.array([63, 63, 63, 63, 9, 9, 9, 9], np.int32)
    bins = np.stack([rng.randint(0, nb, R) for nb in num_bin]) \
        .astype(np.int8)
    F_oh, Bp = feature_layout(F, 63)
    pk = packed_feature_layout(num_bin, 63, f_oh=F_oh)
    meta = FeatureMeta(
        num_bin=jnp.asarray(num_bin), missing_type=jnp.zeros(F, jnp.int32),
        default_bin=jnp.zeros(F, jnp.int32),
        monotone=jnp.zeros(F, jnp.int32), is_cat=jnp.zeros(F, bool))
    g = rng.randn(R).astype(np.float32)
    ones = np.ones(R, np.float32)
    params = SplitParams(min_data_in_leaf=5)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    fm = jnp.ones((F_oh,), bool).at[F:].set(False)

    def payload(packed, quant):
        if quant:
            gh_T, scales = fl.pack_gh_quant(
                jnp.asarray(g), jnp.asarray(np.abs(g)), jnp.asarray(ones),
                quant, np.uint32(0))
        else:
            gh_T = fl.pack_gh(jnp.asarray(g), jnp.asarray(np.abs(g)),
                              jnp.asarray(ones), 5)
            scales = None
        bt = bins if packed is None else bins[np.asarray(pk.feat_order)]

        def body(b_T, ghv):
            return grow_tree_fused(
                b_T, ghv, meta, fm, params, 7, Bp, F_oh, num_rows=0,
                nch=5 if not quant else quantize.QNCH[quant],
                interpret=True, psum_axis="data", parallel_mode="data",
                quant_bits=quant or 0, packed=packed, gh_scales=scales)
        fn = jax.jit(_shard_map(
            body, mesh=mesh, in_specs=(P(None, "data"), P(None, "data")),
            out_specs=(P(), P("data")), check_vma=False))
        with CollectiveTrace() as rec:
            fn(jnp.asarray(bt), gh_T)
        return rec.bytes, dict(rec.by_dtype)

    b_f32, d_f32 = payload(None, 0)
    b_cut, d_cut = payload(pk, 8)
    assert b_cut < b_f32
    # the quantized path psums int32 accumulators
    assert any(k.startswith("int32") for k in d_cut)


@pytest.mark.slow
def test_checkpoint_ema_roundtrip(tmp_path):
    """EMA-FS state joins the resilience extra-state: train n1 + resume
    to n2 under screening == train n2 straight through, byte-identical
    (the mask schedule depends on the EMA, so a dropped EMA would
    diverge)."""
    X, y = _mixed_data(seed=13, n=256)
    ck = tmp_path / "ck"
    params = dict(BASE, tpu_gain_screening=True, tpu_screening_warmup=1,
                  tpu_screening_keep_ratio=0.5,
                  tpu_screening_explore_period=3,
                  checkpoint_dir=str(ck), checkpoint_period=2)

    def run(n, resume=None):
        ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
        return lgb.train(dict(params), ds, num_boost_round=n,
                         resume_from=resume)

    ref = run(7)
    ref_str = ref.model_to_string(num_iteration=-1)
    ref_ema = np.asarray(ref._gbdt._gain_ema_dev)
    shutil.rmtree(ck)
    run(4)
    resumed = run(7, resume=str(ck))
    assert resumed.model_to_string(num_iteration=-1) == ref_str
    assert np.array_equal(np.asarray(resumed._gbdt._gain_ema_dev),
                          ref_ema)


# -------------------------------------------------- accuracy-curve A/Bs
@pytest.mark.slow
@pytest.mark.parametrize("objective,metric_gate", [
    ("binary", 0.05), ("regression", 0.15), ("multiclass", 0.08)])
def test_quant_accuracy_curves(objective, metric_gate):
    """int16 quantization holds the accuracy curve on binary,
    regression and multiclass; int8 is exercised for binary."""
    rng = np.random.RandomState(14)
    n, f = 1500, 10
    X = rng.rand(n, f).astype(np.float32)
    w = rng.randn(f).astype(np.float32)
    margin = X @ w + 0.5 * X[:, 0] * X[:, 1]
    params = dict(BASE, num_leaves=15, num_iterations=15)
    if objective == "binary":
        y = (margin + 0.3 * rng.randn(n) > np.median(margin)) \
            .astype(np.float32)
    elif objective == "regression":
        y = (margin + 0.1 * rng.randn(n)).astype(np.float32)
        params["objective"] = "regression"
    else:
        y = np.digitize(margin, np.quantile(margin, [0.33, 0.66])) \
            .astype(np.float32)
        params.update(objective="multiclass", num_class=3)

    def score(m):
        p = m.predict(X)
        if objective == "regression":
            return float(np.sqrt(np.mean((p - y) ** 2)))
        if objective == "multiclass":
            return 1.0 - float(np.mean(np.argmax(p, 1) == y))
        return 1.0 - float(np.mean((p > 0.5) == y))

    m_f32 = _train(X, y, params)
    bits = [16, 8] if objective == "binary" else [16]
    for b in bits:
        m_q = _train(X, y, dict(params, tpu_quantized_grad=b))
        assert score(m_q) <= score(m_f32) + metric_gate, \
            f"{objective} int{b} accuracy drifted past the gate"


@pytest.mark.slow
def test_screening_statistical_parity():
    """Screening holds predictive quality on data where half the
    features are noise (the regime it targets)."""
    rng = np.random.RandomState(15)
    n, f = 2000, 12
    X = rng.rand(n, f).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] - X[:, 2]) + 0.3 * rng.randn(n) > 0) \
        .astype(np.float32)
    params = dict(BASE, num_leaves=15, num_iterations=20)
    m0 = _train(X, y, params)
    m1 = _train(X, y, dict(params, tpu_gain_screening=True,
                           tpu_screening_warmup=3,
                           tpu_screening_keep_ratio=0.4,
                           tpu_screening_explore_period=5))
    acc0 = np.mean((m0.predict(X) > 0.5) == y)
    acc1 = np.mean((m1.predict(X) > 0.5) == y)
    assert acc1 >= acc0 - 0.04


@pytest.mark.slow
def test_quant_adaptive_deterministic():
    """Quantization + adaptive bins together: identical reruns on the
    same driver serialize byte-identical trees (shared dither streams,
    exact integer sums, exact layout re-index). Cross-driver bit
    identity is deliberately NOT claimed for quantized runs — see
    test_quant_deterministic_and_cross_driver_parity — and screening's
    cross-driver contract is statistical parity
    (test_screening_statistical_parity)."""
    X, y = _mixed_data(seed=16)
    knobs = {"tpu_quantized_grad": 16, "tpu_adaptive_bins": True}
    m_a = _train(X, y, dict(BASE, **knobs))
    m_b = _train(X, y, dict(BASE, **knobs))
    assert _trees(m_a) == _trees(m_b)
    m_sync = _train(X, y, dict(BASE, tpu_fast_path=False, **knobs))
    a_f = np.mean((m_a.predict(X) > 0.5) == y)
    a_s = np.mean((m_sync.predict(X) > 0.5) == y)
    assert abs(a_f - a_s) <= 0.05


@pytest.mark.slow
def test_all_cuts_statistical_parity():
    """All three knobs on, fast path vs sync driver: same accuracy
    regime (the bit-level contracts are covered per-cut above)."""
    X, y = _mixed_data(seed=17, n=1024)
    m_fast = _train(X, y, dict(BASE, num_iterations=10, **KNOBS))
    m_sync = _train(X, y, dict(BASE, num_iterations=10,
                               tpu_fast_path=False, **KNOBS))
    a_f = np.mean((m_fast.predict(X) > 0.5) == y)
    a_s = np.mean((m_sync.predict(X) > 0.5) == y)
    assert abs(a_f - a_s) <= 0.04
