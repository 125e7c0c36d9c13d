"""Batched on-device prediction.

Replaces the reference's per-row host tree walk for batch predict
(ref: predictor.hpp:30 Predictor, gbdt_prediction.cpp — OpenMP over rows,
pointer-chasing per tree) with stacked [T, nodes] tree tensors packed
ONCE per model state and a jit-compiled scan that advances every row one
tree level per pass.  Two routing variants share the scan:

- :class:`DevicePredictor` — **binned** routing: host-side binning
  through the training BinMappers (exactly the training-time
  quantization, so routing is bit-identical to the host walk), then
  threshold-bin compares on device.  Needs a live training dataset.
- :class:`RawDevicePredictor` — **raw-value** routing for boosters
  WITHOUT training BinMappers (model-file loads, the serving residency
  case): float32 compares against thresholds pre-rounded by
  :func:`threshold_to_f32` so any float32-representable input routes
  bit-identically to the float64 host compare; per-node missing
  semantics decoded from the model's decision_type bitfield.

Scores accumulate in float32 on device (the host path carries float64;
differences are ~1e-7 relative).  The Booster picks a device path only
above ``pred_device_min_work`` rows×trees; exact-parity flows (model IO
round-trips, SHAP) keep the host walk.  The jitted runners live at
module scope so every predictor instance — and every resident model in
``lightgbm_tpu.serve`` — shares ONE XLA cache entry per shape signature:
re-packing an evicted model recompiles nothing.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# raw-variant categorical vocabulary cap: the per-node mask becomes a
# [T, N, C] bool tensor over raw category values; a vocabulary past this
# is a degradation (host walk), not an allocation surprise
RAW_CAT_VALUE_CAP = 4096
# ... and so is a mask whose TOTAL size explodes (the vocabulary cap
# bounds C, but T*N*C can still blow up on deep many-tree models with a
# wide vocab): 64M bool elements ~= 64 MB
RAW_CAT_MASK_MAX_ELEMS = 64 * 1024 * 1024


def _round_up_pow2(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


def threshold_to_f32(thr: np.ndarray) -> np.ndarray:
    """Largest float32 <= each float64 threshold.  With thresholds
    rounded this way, ``v32 <= t32`` in float32 agrees with
    ``float64(v32) <= t64`` for EVERY float32 value v32 (same trick as
    binning.BinMapper._bounds_f32), so raw-value device routing is
    bit-identical to the host walk whenever the input is float32-
    representable — the documented serving contract."""
    t64 = np.asarray(thr, np.float64)
    t32 = t64.astype(np.float32)
    over = t32.astype(np.float64) > t64
    t32[over] = np.nextafter(t32[over], np.float32(-np.inf))
    return t32


# ---------------------------------------------------------------------------
# Shared jitted runners (module scope: one XLA cache entry per shape
# signature across ALL predictor instances / resident serve models).
# ---------------------------------------------------------------------------

def _run_binned_body(bins, sf, tb, dl, lc, rc, lv, tids, cf, cm,
                     num_bin, missing, default_bin, *, k, max_steps):
    from ..ops.predict import route_rows_to_leaves
    R = bins.shape[0]

    def tree_step(raw, xs):
        if cf is None:
            sf_t, tb_t, dl_t, lc_t, rc_t, lv_t, tid = xs
            cf_t = cm_t = None
        else:
            (sf_t, tb_t, dl_t, lc_t, rc_t, lv_t, tid, cf_t, cm_t) = xs
        leaves = route_rows_to_leaves(
            bins, sf_t, tb_t, dl_t, lc_t, rc_t, num_bin,
            missing, default_bin, max_steps, cf_t, cm_t)
        return raw.at[tid].add(lv_t[leaves]), None

    raw0 = jnp.zeros((k, R), jnp.float32)
    xs = (sf, tb, dl, lc, rc, lv, tids)
    if cf is not None:
        xs = xs + (cf, cm)
    raw, _ = jax.lax.scan(tree_step, raw0, xs)
    return raw


def _run_raw_body(values, sf, th, dl, mt, lc, rc, lv, tids, cf, cm,
                  *, k, max_steps):
    from ..ops.predict import route_raw_rows_to_leaves
    R = values.shape[0]

    def tree_step(raw, xs):
        if cf is None:
            sf_t, th_t, dl_t, mt_t, lc_t, rc_t, lv_t, tid = xs
            cf_t = cm_t = None
        else:
            (sf_t, th_t, dl_t, mt_t, lc_t, rc_t, lv_t, tid, cf_t,
             cm_t) = xs
        leaves = route_raw_rows_to_leaves(
            values, sf_t, th_t, dl_t, mt_t, lc_t, rc_t, max_steps,
            cf_t, cm_t)
        return raw.at[tid].add(lv_t[leaves]), None

    raw0 = jnp.zeros((k, R), jnp.float32)
    xs = (sf, th, dl, mt, lc, rc, lv, tids)
    if cf is not None:
        xs = xs + (cf, cm)
    raw, _ = jax.lax.scan(tree_step, raw0, xs)
    return raw


_RUN_FNS = {}


def stacked_run_fn(variant: str):
    """The shared jitted runner for a variant ('binned' | 'raw'). No
    donation: the only per-call operand is the encoded rows [R, F], no
    output has its shape ([k, R] f32), and on the chip XLA answered
    every bucket with "Some donated buffers were not usable"."""
    fn = _RUN_FNS.get(variant)
    if fn is None:
        body = _run_binned_body if variant == "binned" else _run_raw_body
        fn = jax.jit(body, static_argnames=("k", "max_steps"))
        _RUN_FNS[variant] = fn
    return fn


class _StackedPredictor:
    """Shared chunked predict loop over a packed tree stack."""

    variant = ""

    def __init__(self):
        self.ok = True
        self.reason = ""
        self.k = 1
        self.max_steps = 1
        self._packed: List[jax.Array] = []

    @property
    def packed_nbytes(self) -> int:
        """Device bytes held by the packed tree tensors (the serve
        residency manager's accounting unit)."""
        return int(sum(int(getattr(a, "nbytes", 0) or 0)
                       for a in self._packed if a is not None))

    def encode(self, X: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def run_args(self, lo: int, hi: int) -> Tuple:
        """Packed-tensor operand tuple for ``stacked_run_fn(variant)``
        covering trees [lo, hi) — everything after the encoded rows."""
        raise NotImplementedError

    def _predict_chunk(self, enc: jax.Array, lo: int, hi: int) -> jax.Array:
        return stacked_run_fn(self.variant)(
            enc, *self.run_args(lo, hi), k=self.k,
            max_steps=self.max_steps)

    def predict_raw(self, X: np.ndarray, lo: int, hi: int,
                    chunk_rows: int = 2_000_000) -> np.ndarray:
        """Sum of leaf values of trees [lo, hi) per class, [k, R] float64.

        scipy sparse input is densified PER CHUNK (prediction routes on
        logical values/bins regardless of the training-side bundle
        storage)."""
        try:
            import scipy.sparse as sp
            sparse_in = sp.issparse(X)
        except ImportError:  # pragma: no cover
            sparse_in = False
        if sparse_in:
            X = X.tocsr()
            chunk_rows = min(chunk_rows, 262_144)
        n = X.shape[0]
        out = np.zeros((self.k, n), np.float64)
        for c0 in range(0, n, chunk_rows):
            sl = slice(c0, min(n, c0 + chunk_rows))
            Xc = X[sl].toarray() if sparse_in else X[sl]
            enc = jnp.asarray(self.encode(Xc))
            raw = self._predict_chunk(enc, lo, hi)
            out[:, sl] = np.asarray(raw, np.float64)
        return out


class DevicePredictor(_StackedPredictor):
    """Stacked-tree device predictor routing on TRAINING BINS."""

    variant = "binned"

    def __init__(self, models: List, ds, num_tree_per_iteration: int):
        """models: HostTree list; ds: TpuDataset (mappers + used_features)."""
        super().__init__()
        self.ds = ds
        self.k = num_tree_per_iteration
        T = len(models)
        if T == 0:
            self.ok, self.reason = False, "no_trees"
            return
        if any(getattr(t, "is_linear", False) for t in models):
            # linear leaves compute base + coeff·x from RAW values; the
            # stacked leaf_value lookup cannot represent them
            self.ok, self.reason = False, "linear_tree"
            return
        if not ds.used_features:
            # every feature binned trivial (single-leaf-only models):
            # the routing kernel has no bin columns to gather from
            self.ok, self.reason = False, "no_used_features"
            return
        N = max(max(t.num_internal for t in models), 1)
        L = max(max(t.num_leaves for t in models), 2)
        B = int(max(m.num_bin for m in ds.mappers)) if ds.mappers else 2
        depth = 1
        sf = np.zeros((T, N), np.int32)
        tb = np.zeros((T, N), np.int32)
        dl = np.zeros((T, N), bool)
        lc = np.full((T, N), -1, np.int32)
        rc = np.full((T, N), -1, np.int32)
        lv = np.zeros((T, L), np.float32)
        has_cat = any(t.cat_threshold for t in models)
        cf = np.zeros((T, N), bool) if has_cat else None
        cm = np.zeros((T, N, B), bool) if has_cat else None

        for ti, t in enumerate(models):
            ni = t.num_internal
            if ni == 0:
                lv[ti, 0] = t.leaf_value[0]
                continue
            for i in range(ni):
                real_f = int(t.split_feature[i])
                inner = ds.inner_feature_index(real_f)
                if inner < 0:  # split on a filtered feature: cannot happen
                    self.ok = False  # for self-trained models; bail out
                    self.reason = "filtered_feature"
                    return
                sf[ti, i] = inner
                m = ds.mappers[real_f]
                d = int(t.decision_type[i])
                is_cat = bool(d & 1)
                if is_cat:
                    cf[ti, i] = True
                    # value bitset -> bin mask through the category vocab
                    cat_idx = int(t.threshold[i])
                    lo = t.cat_boundaries[cat_idx]
                    hi = t.cat_boundaries[cat_idx + 1]
                    words = t.cat_threshold[lo:hi]
                    for b, cat in enumerate(m.bin_2_categorical):
                        if cat < 0:
                            continue
                        w, bit = divmod(int(cat), 32)
                        if w < len(words) and (words[w] >> bit) & 1:
                            cm[ti, i, b] = True
                else:
                    tb[ti, i] = int(t.threshold_bin[i]) if \
                        len(t.threshold_bin) > i else \
                        int(m.value_to_bin(t.threshold[i]))
                    dl[ti, i] = bool(d & 2)
            lc[ti, :ni] = t.left_child
            rc[ti, :ni] = t.right_child
            lv[ti, :t.num_leaves] = t.leaf_value
            ld = getattr(t, "leaf_depth", None)
            # model-file trees parse with an all-zero leaf_depth (the
            # text format does not store depth): fall back to the
            # num_internal bound, never to a fake depth of 0
            if ld is not None and len(ld) and int(np.max(ld)) > 0:
                depth = max(depth, int(np.max(ld)))
            else:
                depth = max(depth, ni)

        self.max_steps = _round_up_pow2(depth + 1)
        self.sf = jnp.asarray(sf)
        self.tb = jnp.asarray(tb)
        self.dl = jnp.asarray(dl)
        self.lc = jnp.asarray(lc)
        self.rc = jnp.asarray(rc)
        self.lv = jnp.asarray(lv)
        self.cf = jnp.asarray(cf) if has_cat else None
        self.cm = jnp.asarray(cm) if has_cat else None
        self.num_bin = jnp.asarray(ds.num_bin_per_feat)
        self.missing = jnp.asarray(ds.missing_types)
        self.default_bin = jnp.asarray(
            np.array([ds.mappers[j].default_bin for j in ds.used_features],
                     np.int32))
        self._packed = [self.sf, self.tb, self.dl, self.lc, self.rc,
                        self.lv, self.cf, self.cm, self.num_bin,
                        self.missing, self.default_bin]
        # shape/dtype of the encoded-rows operand (the serve engine's
        # compile signature includes these: the tree-stack shapes alone
        # do not determine the compiled program)
        self.enc_width = ds.num_features
        self.enc_dtype = "int32"

    # ------------------------------------------------------------------
    def _bin_rows(self, X: np.ndarray) -> np.ndarray:
        ds = self.ds
        out = np.empty((X.shape[0], ds.num_features), np.int32)
        for k, j in enumerate(ds.used_features):
            out[:, k] = ds.mappers[j].value_to_bin(
                np.asarray(X[:, j], np.float64))
        return out

    def encode(self, X: np.ndarray) -> np.ndarray:
        return self._bin_rows(X)

    def run_args(self, lo: int, hi: int) -> Tuple:
        # full-range slice: hand out the packed arrays themselves — a
        # jnp slice materializes a device COPY, which doubled the serve
        # engine's true residency (the budget accounting drift the
        # serve fleet PR audited against live buffers)
        full = lo == 0 and hi >= self.sf.shape[0]
        sl = (lambda a: a) if full else (lambda a: a[lo:hi])
        tids = jnp.arange(lo, hi, dtype=jnp.int32) % self.k
        return (sl(self.sf), sl(self.tb), sl(self.dl), sl(self.lc),
                sl(self.rc), sl(self.lv), tids,
                None if self.cf is None else sl(self.cf),
                None if self.cm is None else sl(self.cm),
                self.num_bin, self.missing, self.default_bin)


class RawDevicePredictor(_StackedPredictor):
    """Stacked-tree device predictor routing on RAW feature values —
    the device path for boosters with no training dataset attached
    (model-file loads / serving residency)."""

    variant = "raw"

    def __init__(self, models: List, num_features: int,
                 num_tree_per_iteration: int,
                 cat_value_cap: int = RAW_CAT_VALUE_CAP):
        super().__init__()
        self.k = num_tree_per_iteration
        self.num_features = int(num_features)
        T = len(models)
        if T == 0:
            self.ok, self.reason = False, "no_trees"
            return
        if any(getattr(t, "is_linear", False) for t in models):
            self.ok, self.reason = False, "linear_tree"
            return
        N = max(max(t.num_internal for t in models), 1)
        L = max(max(t.num_leaves for t in models), 2)
        has_cat = any(t.cat_threshold for t in models)
        C = 0
        if has_cat:
            # pass 1: highest category value used by any bitset decides
            # the mask width; past the cap it is a degradation reason
            for t in models:
                for i in range(t.num_internal):
                    if not (int(t.decision_type[i]) & 1):
                        continue
                    ci = int(t.threshold[i])
                    words = t.cat_threshold[t.cat_boundaries[ci]:
                                            t.cat_boundaries[ci + 1]]
                    for wi in range(len(words) - 1, -1, -1):
                        w = int(words[wi])
                        if w:
                            C = max(C, wi * 32 + w.bit_length())
                            break
            if C > cat_value_cap:
                self.ok, self.reason = False, "cat_vocab_too_large"
                return
            C = max(C, 1)
            if T * N * C > RAW_CAT_MASK_MAX_ELEMS:
                # the vocab cap bounds C but not T*N*C: a deep many-tree
                # model with a wide vocab would allocate a multi-GB
                # mostly-zero mask — degrade instead
                self.ok, self.reason = False, "cat_mask_too_large"
                return
        depth = 1
        sf = np.zeros((T, N), np.int32)
        th = np.zeros((T, N), np.float32)
        dl = np.zeros((T, N), bool)
        mt = np.zeros((T, N), np.int32)
        lc = np.full((T, N), -1, np.int32)
        rc = np.full((T, N), -1, np.int32)
        lv = np.zeros((T, L), np.float32)
        cf = np.zeros((T, N), bool) if has_cat else None
        cm = np.zeros((T, N, C), bool) if has_cat else None

        for ti, t in enumerate(models):
            ni = t.num_internal
            if ni == 0:
                lv[ti, 0] = t.leaf_value[0]
                continue
            for i in range(ni):
                f = int(t.split_feature[i])
                if f >= self.num_features:
                    self.ok, self.reason = False, "feature_out_of_range"
                    return
                sf[ti, i] = f
                d = int(t.decision_type[i])
                dl[ti, i] = bool(d & 2)
                mt[ti, i] = (d >> 2) & 3
                if d & 1:
                    cf[ti, i] = True
                    ci = int(t.threshold[i])
                    words = t.cat_threshold[t.cat_boundaries[ci]:
                                            t.cat_boundaries[ci + 1]]
                    for wi, w in enumerate(words):
                        w = int(w)
                        while w:
                            bit = (w & -w).bit_length() - 1
                            cm[ti, i, wi * 32 + bit] = True
                            w &= w - 1
            # vectorized per tree; cat nodes' slots hold their (unused)
            # cat_boundaries index, routed via the mask instead
            th[ti, :ni] = threshold_to_f32(np.asarray(t.threshold[:ni]))
            lc[ti, :ni] = t.left_child
            rc[ti, :ni] = t.right_child
            lv[ti, :t.num_leaves] = t.leaf_value
            ld = getattr(t, "leaf_depth", None)
            # model-file trees parse with an all-zero leaf_depth (the
            # text format does not store depth): fall back to the
            # num_internal bound, never to a fake depth of 0
            if ld is not None and len(ld) and int(np.max(ld)) > 0:
                depth = max(depth, int(np.max(ld)))
            else:
                depth = max(depth, ni)

        self.max_steps = _round_up_pow2(depth + 1)
        self.sf = jnp.asarray(sf)
        self.th = jnp.asarray(th)
        self.dl = jnp.asarray(dl)
        self.mt = jnp.asarray(mt)
        self.lc = jnp.asarray(lc)
        self.rc = jnp.asarray(rc)
        self.lv = jnp.asarray(lv)
        self.cf = jnp.asarray(cf) if has_cat else None
        self.cm = jnp.asarray(cm) if has_cat else None
        self._packed = [self.sf, self.th, self.dl, self.mt, self.lc,
                        self.rc, self.lv, self.cf, self.cm]
        self.enc_width = self.num_features
        self.enc_dtype = "float32"
        # widest feature any split actually reads: narrower inputs than
        # the declared feature count are fine as long as they cover it
        # (the host walk accepts them, so the device path must too)
        self.max_split_feature = int(sf.max()) if T else -1

    def encode(self, X: np.ndarray) -> np.ndarray:
        X = np.asarray(X)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        nf = self.num_features
        if X.shape[1] < nf:
            if X.shape[1] <= self.max_split_feature:
                raise ValueError(
                    f"prediction data has {X.shape[1]} columns but the "
                    f"model splits on feature {self.max_split_feature}")
            # trailing unused features: pad to the canonical width (the
            # pad values route nowhere — no split reads them)
            X = np.concatenate(
                [X, np.zeros((X.shape[0], nf - X.shape[1]), X.dtype)],
                axis=1)
        # trim extra trailing columns (no split can reference them):
        # the encoded operand keeps ONE canonical width per model, so
        # wider inputs cannot fork extra compiled programs
        return np.ascontiguousarray(X[:, :nf], np.float32)

    def run_args(self, lo: int, hi: int) -> Tuple:
        # full-range slice returns the packed arrays themselves (a jnp
        # slice would allocate device copies — see DevicePredictor)
        full = lo == 0 and hi >= self.sf.shape[0]
        sl = (lambda a: a) if full else (lambda a: a[lo:hi])
        tids = jnp.arange(lo, hi, dtype=jnp.int32) % self.k
        return (sl(self.sf), sl(self.th), sl(self.dl), sl(self.mt),
                sl(self.lc), sl(self.rc), sl(self.lv), tids,
                None if self.cf is None else sl(self.cf),
                None if self.cm is None else sl(self.cm))
