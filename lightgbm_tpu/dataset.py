"""Binned dataset container + metadata.

TPU-native analog of the reference Dataset/DatasetLoader/Metadata
(ref: include/LightGBM/dataset.h:42,340, src/io/dataset_loader.cpp:203,
src/io/metadata.cpp).  Design deviation from the reference, on purpose:

- The reference stores per-feature-group ``Bin`` objects (dense uint8/16/32,
  4-bit packed, or delta-encoded sparse) and bundles exclusive sparse features
  (EFB) to cut CPU cache traffic.  On TPU the histogram kernel wants one dense
  ``[num_rows, num_features]`` integer matrix in HBM with static shape — dense
  uint8 at 255 bins is already the EFB-ideal layout for the MXU/VPU formulation,
  so feature bundling and sparse encodings are unnecessary; trivial features
  are simply dropped (same effect as the reference's pre-filter).
- Row-major layout matches the reference's multi-val (row-wise) path which it
  auto-selects for wide/fast cases (ref: src/io/dataset.cpp:591-680); the
  col-vs-row timing experiment collapses away because XLA tiles either way.
"""
from __future__ import annotations

import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN, BinMapper)
from .config import Config
from .obs.registry import Span
from .utils import log

# host binning of dense rows (TpuDataset.bin_rows): rows per transpose
# block and the most threads it takes
_BIN_BLOCK_ROWS = 1 << 16
_BIN_THREADS = 8


# the binning-defining keys a binary cache round-trips (the same family
# the C-API's UpdateParamChecking guards)
_DATASET_DEFINING_KEYS = (
    "max_bin", "max_bin_by_feature", "bin_construct_sample_cnt",
    "min_data_in_bin", "use_missing", "zero_as_missing",
    "feature_pre_filter", "min_data_in_leaf", "data_random_seed")


def dataset_defining_params(config: "Config") -> Dict[str, Any]:
    return {k: getattr(config, k) for k in _DATASET_DEFINING_KEYS}


class Metadata:
    """Label / weight / query-boundary / init-score holder
    (ref: include/LightGBM/dataset.h:42, src/io/metadata.cpp)."""

    def __init__(self, num_data: int):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None  # int32 [num_queries+1]
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label) -> None:
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        log.check(label.size == self.num_data,
                  f"label size {label.size} != num_data {self.num_data}")
        self.label = label

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        weight = np.asarray(weight, dtype=np.float32).reshape(-1)
        log.check(weight.size == self.num_data,
                  f"weight size {weight.size} != num_data {self.num_data}")
        log.check(bool(np.all(weight >= 0)), "weights should be non-negative")
        self.weight = weight

    def set_group(self, group) -> None:
        """``group`` is per-query sizes (like the reference's query file);
        converted to cumulative boundaries (ref: metadata.cpp query_boundaries_)."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        log.check(int(group.sum()) == self.num_data,
                  "sum of group sizes != num_data")
        self.query_boundaries = np.concatenate(
            [[0], np.cumsum(group)]).astype(np.int32)

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).reshape(-1)

    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


def _allgather_sample(sample: np.ndarray) -> np.ndarray:
    """Concatenate every process's binning sample (no-op single-process).

    process_allgather requires identical shapes on every rank, but row
    shards are unequal whenever the file row count doesn't divide evenly
    — gather the per-rank counts first, pad to the max, then slice each
    rank's real rows back out."""
    import jax
    if jax.process_count() <= 1:
        return sample
    from jax.experimental import multihost_utils
    n_proc = jax.process_count()
    cnt = np.array([sample.shape[0]], np.int64)
    cnts = np.asarray(multihost_utils.process_allgather(cnt)) \
        .reshape(n_proc)
    m = int(cnts.max())
    padded = np.pad(np.asarray(sample, np.float64),
                    ((0, m - sample.shape[0]), (0, 0)))
    gathered = np.asarray(multihost_utils.process_allgather(padded)) \
        .reshape(n_proc, m, sample.shape[1])
    return np.concatenate([gathered[p, :int(cnts[p])]
                           for p in range(n_proc)], axis=0)


def _sample_rows(num_data: int, sample_cnt: int, seed: int) -> np.ndarray:
    if num_data <= sample_cnt:
        return np.arange(num_data)
    rng = np.random.RandomState(seed)
    return np.sort(rng.choice(num_data, size=sample_cnt, replace=False))


class _ExactRows:
    """Every row of a sparse set, for ``ops.efb.find_bundles``: a feature
    the sample let into a bundle joins it only if no row of the whole set
    is non-default in it and in a member already there (the sample alone
    misses rare pairs: two rare one-hot columns that never meet in the
    200,000 sampled rows can meet a few times in millions). Rows are read
    from the CSC columns; a bundle's rows are kept as a boolean array,
    made when first asked about."""

    def __init__(self, csc, mappers, used_features, most_freq_bins, n):
        self.csc, self.mappers, self.used = csc, mappers, used_features
        self.mfb, self.n = most_freq_bins, n
        self.members: List[List[int]] = []
        self.taken: List[Optional[np.ndarray]] = []

    def rows(self, k: int) -> np.ndarray:
        """Rows non-default in used feature k."""
        return _nondefault(self.csc, self.mappers[self.used[k]],
                           self.used[k], int(self.mfb[k]), self.n)[0]

    def fits(self, k: int, bi: int) -> bool:
        if self.taken[bi] is None:
            t = np.zeros(self.n, bool)
            for m in self.members[bi]:
                t[self.rows(m)] = True
            self.taken[bi] = t
        return not self.taken[bi][self.rows(k)].any()

    def add(self, k: int, bi: int) -> None:
        if bi == len(self.members):
            self.members.append([])
            self.taken.append(None)
        self.members[bi].append(k)
        if self.taken[bi] is not None:
            self.taken[bi][self.rows(k)] = True


def _nondefault(csc, mapper, j: int, mfb: int, n: int):
    """(rows, bins) of the rows of CSC column j whose bin is not the
    most-frequent one ``mfb``, ascending."""
    lo, hi = csc.indptr[j], csc.indptr[j + 1]
    rows_j = csc.indices[lo:hi]
    # (a float32 column bins exactly against the mapper's float32 bounds)
    bins_nz = mapper.value_to_bin(csc.data[lo:hi]).astype(np.int64)
    zero_bin = int(mapper.value_to_bin(np.zeros(1))[0])
    if zero_bin == mfb:
        # implicit zeros are default: only the stored values can be not
        nd = bins_nz != mfb
        return rows_j[nd], bins_nz[nd]
    # zeros bin away from the most-frequent bin (e.g. zero_as_missing):
    # expand the column densely
    dense_bins = np.full(n, zero_bin, np.int64)
    dense_bins[rows_j] = bins_nz
    rows = np.nonzero(dense_bins != mfb)[0]
    return rows, dense_bins[rows]


def _encode_sparse_bundles(csc, mappers, used_features, layout,
                           most_freq_bins, n: int):
    """([R, C] bundle-column matrix, lost rows) straight from CSC
    columns — the dense [R, F] logical matrix is never materialised.
    Bundle bin 0 = the row is default (most-frequent bin) in every
    member; conflicts keep the first member's encoding (ops/efb.py
    contract). ``lost rows``: ascending, the rows where a later member's
    value was not stored that way."""
    C = layout.num_columns
    dtype = np.uint16 if max(layout.col_num_bin) > 255 else np.uint8
    # built a column at a time in [C, R] (contiguous writes), turned once
    outT = np.zeros((C, n), dtype)
    lost = []
    for ci, bundle in enumerate(layout.bundles):
        col = outT[ci]
        taken = np.zeros(n, bool)
        for k in bundle:
            j = used_features[k]
            rows, bins = _nondefault(csc, mappers[j], j,
                                     int(most_freq_bins[k]), n)
            keep = ~taken[rows]
            col[rows[keep]] = (int(layout.offset_of_feat[k])
                               + bins[keep]).astype(dtype)
            taken[rows[keep]] = True
            lost.append(rows[~keep])
    return (np.ascontiguousarray(outT.T),
            np.unique(np.concatenate(lost)) if lost
            else np.zeros(0, np.int64))


def _logical_bins(csc, mappers, used_features, n: int, dtype,
                  rows: np.ndarray = None) -> np.ndarray:
    """[len(rows), F] logical bins of ``rows`` (ascending; all n rows
    when None) straight from CSC columns."""
    m_rows = n if rows is None else len(rows)
    if rows is not None:
        # row -> its place among ``rows`` (-1: not asked for)
        place = np.full(n, -1, np.int64)
        place[rows] = np.arange(m_rows)
    out = np.zeros((m_rows, len(used_features)), dtype)
    for k, j in enumerate(used_features):
        m = mappers[j]
        lo, hi = csc.indptr[j], csc.indptr[j + 1]
        at, vals = csc.indices[lo:hi], csc.data[lo:hi]
        if rows is not None:
            at = place[at]
            vals, at = vals[at >= 0], at[at >= 0]
        out[:, k] = int(m.value_to_bin(np.zeros(1))[0])
        out[at, k] = m.value_to_bin(np.asarray(vals, np.float64)) \
            .astype(dtype)
    return out


class TpuDataset:
    """The binned training matrix living in (or bound for) TPU HBM.

    ``bins``: ``[num_data, num_used_features]`` uint8/uint16; per-feature bin
    counts and offsets drive the joint histogram index.  ``mappers`` holds one
    BinMapper per *original* feature (trivial ones included, for model IO and
    prediction parity).
    """

    def __init__(self):
        self.bins: Optional[np.ndarray] = None
        self.mappers: List[BinMapper] = []
        self.used_features: List[int] = []   # original idx of non-trivial features
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata: Optional[Metadata] = None
        self.max_num_bin: int = 1
        # per used feature
        self.num_bin_per_feat: np.ndarray = np.zeros(0, np.int32)
        self.bin_offsets: np.ndarray = np.zeros(0, np.int32)
        self.most_freq_bins: np.ndarray = np.zeros(0, np.int32)
        self.is_categorical: np.ndarray = np.zeros(0, bool)
        self.raw_data: "np.ndarray" = None  # retained for linear trees
        self.missing_types: np.ndarray = np.zeros(0, np.int32)
        self.monotone_constraints: Optional[np.ndarray] = None
        # sparse-built datasets: ``bins`` holds EFB BUNDLE columns and
        # this carries the ops.efb.BundleLayout decode (None = logical);
        # a validation set stored in its training set's bundles keeps the
        # rows whose values conflict there (a row non-default in two
        # members of one bundle) beside them, in logical bins: (rows
        # ascending, [rows, F] bins), none in a training set
        self.prebundled = None
        self.exact_rows = None
        # streaming-ingest bookkeeping (ingest/): counters published into
        # the training telemetry registry at booster init, and the flag
        # that routes host->device transfer through the double-buffered
        # prefetcher (also set for mmap-backed cache loads)
        self.ingest_stats: Optional[Dict[str, Any]] = None
        self.streamed: bool = False
        # resolved dataset-defining params captured at mapper build —
        # persisted in the binary cache (the reference's .bin stores its
        # config too) so a reloaded dataset's booster resolves/echoes
        # the same values the original build used
        self.dataset_params: Dict[str, Any] = {}
        # True when the bins were produced against ANOTHER dataset's
        # mappers (validation builds): a cache of such a dataset must
        # never be reused as standalone training data
        self.reference_binned: bool = False
        # the closed spans of this set's construction (``bin`` and its
        # children, obs/registry.Span): a Dataset is binned before any
        # Booster has a sink, so the Booster that trains on it (or takes
        # it as a validation set) publishes them, with their true start
        self.setup_spans: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------
    @classmethod
    def from_data(cls, data: np.ndarray, config: Config,
                  categorical_feature: Sequence[int] = (),
                  feature_names: Optional[List[str]] = None,
                  reference: Optional["TpuDataset"] = None,
                  forced_bounds: Optional[Dict[int, List[float]]] = None,
                  ) -> "TpuDataset":
        """Build from a dense float matrix.

        With ``reference`` set, reuse its bin mappers so validation data aligns
        with training bins (ref: dataset_loader.cpp:282
        LoadFromFileAlignWithOtherDataset).  Otherwise: sample rows, construct
        mappers per feature (ref: ConstructBinMappersFromTextData :988), then
        push binned values (ref: ExtractFeaturesFromMemory :1180).
        """
        from .utils.timer import global_timer as timer
        with timer.section("DatasetLoader::Construct"):
            return cls._from_data(data, config, categorical_feature,
                                  feature_names, reference, forced_bounds)

    @classmethod
    def _from_data(cls, data, config, categorical_feature=(),
                   feature_names=None, reference=None, forced_bounds=None):
        self = cls()
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("data must be 2-dimensional")
        n, f = data.shape
        self.num_data = n
        self.num_total_features = f
        self.feature_names = (list(feature_names) if feature_names
                              else [f"Column_{i}" for i in range(f)])
        self.metadata = Metadata(n)

        if reference is not None:
            self.mappers = reference.mappers
            self.used_features = reference.used_features
            self.dataset_params = dict(
                getattr(reference, "dataset_params", {}) or {})
            self.reference_binned = True
            self._finalize_feature_arrays()
            self._push_data(data)
            return self

        cat_set = set(int(c) for c in categorical_feature)
        with Span(None, "bin/sample", rows=n, features=f):
            sample_idx = _sample_rows(n, config.bin_construct_sample_cnt,
                                      config.data_random_seed)
            sample = np.asarray(data[sample_idx], dtype=np.float64)
        with Span(None, "bin/mappers", rows=len(sample_idx), features=f):
            self.build_mappers_from_sample(sample, config, cat_set,
                                           forced_bounds)
        self._push_data(data)
        if config.monotone_constraints:
            mc = np.asarray(config.monotone_constraints, dtype=np.int32)
            log.check(mc.size == f, "monotone_constraints length mismatch")
            self.monotone_constraints = mc
        return self

    def build_mappers_from_sample(self, sample: np.ndarray, config: Config,
                                  cat_set=frozenset(),
                                  forced_bounds=None) -> None:
        """Construct per-feature BinMappers from a float64 row sample and
        finalize the feature arrays.  The ONE mapper-construction path:
        the monolithic ``from_data`` and the chunked streaming ingest
        pipeline (ingest/pipeline.py, which collects the SAME sampled
        rows in bounded passes) both land here, so a streamed dataset's
        mappers are bit-identical to the monolithic build's by
        construction."""
        f = self.num_total_features
        self.dataset_params = dataset_defining_params(config)
        # distributed loading: every rank holds only its row shard — the
        # bin mappers must still be IDENTICAL everywhere, so the samples
        # are allgathered across processes before FindBin (the TPU-native
        # form of the reference's feature-sharded FindBin + mapper
        # allgather, ref: src/io/dataset_loader.cpp:1015,1146-1154)
        sample = _allgather_sample(sample)
        forced_bounds = forced_bounds or {}

        # per-feature bin budget override (ref: config.h
        # max_bin_by_feature, dataset_loader.cpp bin-mapper construction)
        mb_by_feat = list(config.max_bin_by_feature or [])
        if mb_by_feat and len(mb_by_feat) != f:
            log.fatal("max_bin_by_feature has %d entries but the data has "
                      "%d features" % (len(mb_by_feat), f))
        if any(int(b) <= 1 for b in mb_by_feat):
            log.fatal("max_bin_by_feature entries must be > 1")
        self.mappers = []
        for j in range(f):
            m = BinMapper()
            col = sample[:, j]
            bin_type = BIN_CATEGORICAL if j in cat_set else BIN_NUMERICAL
            # the reference feeds only the non-zero sampled values plus the
            # total count (zeros implicit); replicate that contract
            nz = col[(np.abs(col) > 1e-35) | np.isnan(col)]
            mb_j = int(mb_by_feat[j]) if mb_by_feat else config.max_bin
            m.find_bin(nz, total_sample_cnt=len(col), max_bin=mb_j,
                       min_data_in_bin=config.min_data_in_bin,
                       min_split_data=config.min_data_in_leaf if
                       config.feature_pre_filter else 0,
                       pre_filter=config.feature_pre_filter,
                       bin_type=bin_type, use_missing=config.use_missing,
                       zero_as_missing=config.zero_as_missing,
                       forced_bounds=forced_bounds.get(j))
            self.mappers.append(m)

        import jax as _jax
        if _jax.process_count() > 1:
            # retained (BINNED, 2 B/elem) for EFB: bundle layouts must be
            # IDENTICAL on every rank, so conflict masks come from this
            # shared sample (the reference also bundles from sampled
            # data, dataset_loader.cpp FindGroups over sample_indices)
            used = [j for j in range(f) if not self.mappers[j].is_trivial]
            if used:
                self.mp_sample_bins = np.stack(
                    [self.mappers[j].value_to_bin(sample[:, j])
                     for j in used], axis=1).astype(np.uint16)
        self.used_features = [j for j in range(f) if not self.mappers[j].is_trivial]
        if not self.used_features:
            # the reference keeps going and trains constant trees
            # (ref: src/io/dataset.cpp:336)
            log.warning("There are no meaningful features which satisfy "
                        "the provided configuration. Decrease Dataset "
                        "parameters min_data_in_bin or min_data_in_leaf "
                        "and re-construct Dataset might resolve this "
                        "warning.")
        self._finalize_feature_arrays()

    # ------------------------------------------------------------------
    @classmethod
    def from_sparse(cls, data, config: Config,
                    feature_names: Optional[List[str]] = None,
                    reference: Optional["TpuDataset"] = None,
                    ) -> "TpuDataset":
        """Build from a scipy CSR/CSC matrix WITHOUT materialising the
        dense [R, F] float matrix (ref: the reference's CSR/CSC dataset
        creation c_api.cpp:398-520 + sparse bin storage sparse_bin.hpp:73).

        The TPU-native storage answer differs from the reference's
        per-feature sparse bins: mutually-exclusive sparse features are
        bundled at INGESTION time (EFB, ref: dataset.cpp FindGroups/
        FastFeatureBundling) and only the [R, n_bundles] bundle-column
        matrix is ever materialised — histogram/scan work then scales
        with bundles, matching the role of the reference's MultiValBin.
        The resulting dataset is 'prebundled': ``bins`` holds BUNDLE
        columns and ``prebundled`` carries the decode layout.

        With ``reference`` (a validation set) the reference's mappers bin
        the values, into the reference's bundle columns where it has them
        (so the set is stored as the training matrix is, and the kernels'
        route logs replay over it), else as logical bins. Stored in
        bundles, a row non-default in two members of one bundle keeps the
        first one's value there, and the set keeps such rows' logical
        bins beside it (``exact_rows``): its leaves are read from those
        (GBDT._valid_exact), so the set's scores stay the trees' walk over
        its logical columns.

        Set-up spans: ``bin/sparse/csc`` (the column pass),
        ``bin/sparse/sample`` (the mappers from the sample),
        ``bin/bundle/find`` and ``bin/bundle/encode``; the column pass and
        the encode also sit under ``bin/rows``.
        """
        import scipy.sparse as sp

        from .ops.efb import BundleLayout, find_bundles
        from .utils.timer import global_timer as timer
        with timer.section("DatasetLoader::ConstructSparse"):
            self = cls()
            n, f = data.shape
            # the row-length work of the sparse path (this column pass
            # and the encode below) sits under ``bin/rows``, as the dense
            # path's binning of the rows does
            with Span(None, "bin/rows", rows=n, features=f), \
                    Span(None, "bin/sparse/csc", rows=n, features=f):
                csc = sp.csc_matrix(data)
                csc.sort_indices()
            self.num_data = n
            self.num_total_features = f
            self.feature_names = (list(feature_names) if feature_names
                                  else [f"Column_{i}" for i in range(f)])
            self.metadata = Metadata(n)

            if reference is not None:
                self.mappers = reference.mappers
                self.used_features = reference.used_features
                self.reference_binned = True
                self._finalize_feature_arrays()
                layout = reference.prebundled
                dtype = np.uint8 if self.max_num_bin <= 256 else np.uint16
                if layout is not None:
                    # in the training set's bundle columns, so that the
                    # kernels' route logs replay over it; the rows a
                    # conflict in a bundle leaves short keep their logical
                    # bins beside them
                    with Span(None, "bin/rows", rows=n, features=f), \
                            Span(None, "bin/bundle/encode", rows=n,
                                 columns=layout.num_columns):
                        self.bins, lost = _encode_sparse_bundles(
                            csc, self.mappers, self.used_features, layout,
                            self.most_freq_bins, n)
                        self.prebundled = layout
                        self.exact_rows = (lost, _logical_bins(
                            csc, self.mappers, self.used_features, n,
                            dtype, lost))
                    if lost.size:
                        log.info("Sparse EFB: %d rows of the validation set "
                                 "conflict in the training bundles; their "
                                 "logical bins are kept beside them",
                                 lost.size)
                    return self
                # routed only (never histogrammed): logical bins
                with Span(None, "bin/rows", rows=n, features=f):
                    self.bins = _logical_bins(csc, self.mappers,
                                              self.used_features, n, dtype)
                return self

            # ---- sample + per-feature mappers (zeros implicit, like the
            # dense path / ref dataset_loader.cpp:988); one pass also
            # collects the sample non-default masks for bundling
            with Span(None, "bin/sparse/sample", rows=n, features=f):
                sample_idx = np.sort(_sample_rows(
                    n, config.bin_construct_sample_cnt,
                    config.data_random_seed))
                n_sample = len(sample_idx)
                # row -> its place in the sample (-1: not sampled): one
                # gather a stored value finds its sampled rows by
                pos_of_row = np.full(n, -1, np.int32)
                pos_of_row[sample_idx] = np.arange(n_sample)
                self.mappers = []
                sample_masks = []
                for j in range(f):
                    lo, hi = csc.indptr[j], csc.indptr[j + 1]
                    pos = pos_of_row[csc.indices[lo:hi]]
                    hit = pos >= 0
                    nz = np.asarray(csc.data[lo:hi][hit], np.float64)
                    nz = nz[(np.abs(nz) > 1e-35) | np.isnan(nz)]
                    m = BinMapper()
                    m.find_bin(nz, total_sample_cnt=n_sample,
                               max_bin=config.max_bin,
                               min_data_in_bin=config.min_data_in_bin,
                               min_split_data=(config.min_data_in_leaf
                                               if config.feature_pre_filter
                                               else 0),
                               pre_filter=config.feature_pre_filter,
                               bin_type=BIN_NUMERICAL,
                               use_missing=config.use_missing,
                               zero_as_missing=config.zero_as_missing)
                    self.mappers.append(m)
                    if not m.is_trivial:
                        mask = np.zeros(n_sample, bool)
                        mask[pos[hit]] = True
                        sample_masks.append(mask)
                del pos_of_row
            self.used_features = [j for j in range(f)
                                  if not self.mappers[j].is_trivial]
            if not self.used_features:
                log.warning("There are no meaningful features which "
                            "satisfy the provided configuration.")
            self._finalize_feature_arrays()

            # ---- bundling on the SAMPLE rows (the reference also
            # bundles from its sample, dataset_loader.cpp FindGroups call
            # sites), every placement then checked on all the rows: no
            # conflict anywhere, so the bundle matrix is lossless
            nb = [int(x) for x in self.num_bin_per_feat]
            with Span(None, "bin/bundle/find", rows=n_sample,
                      features=len(nb)):
                bundles = find_bundles(
                    sample_masks, n_sample,
                    max_conflict_rate=0.0,
                    max_bundle_bins=int(config.tpu_max_bundle_bins),
                    num_bin_per_feat=nb,
                    exact=_ExactRows(csc, self.mappers, self.used_features,
                                     self.most_freq_bins, n))
                layout = BundleLayout(bundles, nb)
            self.prebundled = layout
            with Span(None, "bin/rows", rows=n, features=f), \
                    Span(None, "bin/bundle/encode", rows=n,
                         columns=layout.num_columns):
                # (no conflicts: every placement was checked on all rows)
                self.bins, _ = _encode_sparse_bundles(
                    csc, self.mappers, self.used_features, layout,
                    self.most_freq_bins, n)
            log.info("Sparse EFB: %d used features -> %d bundle columns "
                     "(max %d bins)", len(self.used_features),
                     layout.num_columns, max(layout.col_num_bin))
            if config.monotone_constraints:
                mc = np.asarray(config.monotone_constraints, dtype=np.int32)
                log.check(mc.size == f,
                          "monotone_constraints length mismatch")
                self.monotone_constraints = mc
            return self

    def _finalize_feature_arrays(self) -> None:
        from .binning import effective_bin_counts
        used = self.used_features
        self.num_bin_per_feat = effective_bin_counts(
            [self.mappers[j] for j in used])
        self.max_num_bin = int(self.num_bin_per_feat.max()) if used else 1
        self.bin_offsets = np.concatenate(
            [[0], np.cumsum(self.num_bin_per_feat)]).astype(np.int32)
        self.most_freq_bins = np.array(
            [self.mappers[j].most_freq_bin for j in used], np.int32)
        self.is_categorical = np.array(
            [self.mappers[j].bin_type == BIN_CATEGORICAL for j in used], bool)
        self.missing_types = np.array(
            [self.mappers[j].missing_type for j in used], np.int32)

    def bin_dtype(self):
        return np.uint8 if self.max_num_bin <= 256 else np.uint16

    def bin_rows(self, data: np.ndarray) -> np.ndarray:
        """Bin a [rows, num_total_features] float block against the
        finalized mappers -> packed [rows, num_used_features] uint8/16.
        The ONE binning hop for raw rows — the monolithic ``_push_data``
        and the chunked ingest pipeline both call it, so per-chunk
        binning is elementwise-identical to the whole-shard pass."""
        dtype = self.bin_dtype()
        # transpose copies on both sides keep every inner loop contiguous
        # (strided per-column access to the row-major matrices dominates
        # otherwise); float32 input stays float32 — value_to_bin bins it
        # exactly against pre-rounded f32 bounds. Columns (and the row
        # blocks of the two transposes) are independent and numpy drops
        # the GIL inside them, so a few threads share them: the same
        # bytes out, in a third of the time at millions of rows
        n = data.shape[0]
        used = list(enumerate(self.used_features))
        dataT = np.empty((data.shape[1], n), data.dtype)
        outT = np.empty((len(used), n), dtype=dtype)
        out = np.empty((n, len(used)), dtype=dtype)
        blocks = [(a, min(a + _BIN_BLOCK_ROWS, n))
                  for a in range(0, n, _BIN_BLOCK_ROWS)]

        def into_columns(span):
            dataT[:, span[0]:span[1]] = data[span[0]:span[1]].T

        def bin_column(kj):
            outT[kj[0]] = self.mappers[kj[1]].value_to_bin(
                dataT[kj[1]]).astype(dtype, copy=False)

        def into_rows(span):
            out[span[0]:span[1]] = outT[:, span[0]:span[1]].T

        with ThreadPoolExecutor(min(_BIN_THREADS,
                                    os.cpu_count() or 1)) as pool:
            for work, items in ((into_columns, blocks), (bin_column, used),
                                (into_rows, blocks)):
                list(pool.map(work, items))
        return out

    def _push_data(self, data: np.ndarray) -> None:
        with Span(None, "bin/rows", rows=int(data.shape[0]),
                  features=int(data.shape[1])):
            self.bins = self.bin_rows(data)

    # ------------------------------------------------------------------
    def add_features_from(self, other: "TpuDataset") -> None:
        """Append the other dataset's features column-wise (ref:
        dataset.h AddFeaturesFrom / basic.py add_features_from). Both
        datasets must be constructed with the same row count; the other's
        mappers and binned columns are adopted as new features."""
        if other.num_data != self.num_data:
            log.fatal("add_features_from: row counts differ (%d vs %d)"
                      % (self.num_data, other.num_data))
        base = len(self.mappers)
        self.num_total_features += other.num_total_features
        self.mappers.extend(other.mappers)
        self.used_features.extend(base + j for j in other.used_features)
        self.feature_names = list(self.feature_names) + [
            f"{n}" if n not in self.feature_names else f"{n}_2"
            for n in other.feature_names]
        dtype = (np.uint16 if max(self.max_num_bin, other.max_num_bin) > 256
                 else self.bins.dtype)
        self.bins = np.concatenate(
            [np.asarray(self.bins, dtype), np.asarray(other.bins, dtype)],
            axis=1)
        if self.monotone_constraints is not None or                 other.monotone_constraints is not None:
            a = (self.monotone_constraints if self.monotone_constraints
                 is not None else np.zeros(base, np.int32))
            b = (other.monotone_constraints
                 if other.monotone_constraints is not None
                 else np.zeros(len(other.mappers), np.int32))
            self.monotone_constraints = np.concatenate([a, b])
        self._finalize_feature_arrays()

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    def inner_feature_index(self, real_idx: int) -> int:
        """Original feature index -> used (inner) index, -1 if filtered
        (ref: dataset.h InnerFeatureIndex)."""
        try:
            return self.used_features.index(real_idx)
        except ValueError:
            return -1

    def real_feature_index(self, inner_idx: int) -> int:
        return self.used_features[inner_idx]

    def feature_infos(self) -> List[str]:
        """Per-original-feature info strings for the model text format
        (ref: gbdt_model_text.cpp feature_infos: ``[min:max]`` or categories)."""
        infos = []
        for m in self.mappers:
            if m.is_trivial:
                infos.append("none")
            elif m.bin_type == BIN_CATEGORICAL:
                cats = m.bin_2_categorical[1:]
                infos.append("[" + ":".join(str(c) for c in sorted(cats)) + "]")
            else:
                infos.append(f"[{m.min_val:g}:{m.max_val:g}]")
        return infos

    # ------------------------------------------------------------------
    def save_binary(self, path: str) -> None:
        """Binary dataset cache (analog of ref: dataset_loader.cpp:336
        LoadFromBinFile / Dataset::SaveBinaryFile).  Writes the sharded
        v2 artifact (ingest/cache.py): hash-manifested, versioned,
        written streaming + atomically, and mmap-able on reload so a
        cache-hit startup never re-parses text or re-bins."""
        from .ingest.cache import save_dataset_cache
        save_dataset_cache(self, path)

    @classmethod
    def load_binary(cls, path: str) -> "TpuDataset":
        """Load a binary dataset cache: the current v2 artifact
        (``LGBMTPU2``, mmap + manifest verification) or the legacy v1
        pickle (``LGBMTPU1``) written by earlier versions."""
        from .ingest.cache import CACHE_MAGIC, load_dataset_cache
        with open(path, "rb") as fh:
            magic = fh.read(8)
        if magic == CACHE_MAGIC:
            return load_dataset_cache(path)
        log.check(magic == b"LGBMTPU1", f"{path} is not a lightgbm_tpu "
                  "binary dataset file")
        with open(path, "rb") as fh:
            fh.read(8)
            payload = pickle.load(fh)
        self = cls()
        self.bins = payload["bins"]
        self.mappers = [BinMapper.from_dict(d) for d in payload["mappers"]]
        self.used_features = list(payload["used_features"])
        self.num_data = payload["num_data"]
        self.num_total_features = payload["num_total_features"]
        self.feature_names = payload["feature_names"]
        self.metadata = Metadata(self.num_data)
        if payload["label"] is not None:
            self.metadata.set_label(payload["label"])
        self.metadata.weight = payload["weight"]
        self.metadata.query_boundaries = payload["query_boundaries"]
        self.metadata.init_score = payload["init_score"]
        self.monotone_constraints = payload.get("monotone_constraints")
        self._finalize_feature_arrays()
        return self

    # ------------------------------------------------------------------
    def subset(self, row_indices: np.ndarray) -> "TpuDataset":
        """Row subset sharing mappers (ref: dataset.cpp CopySubrow — used by
        cv folds and bagging-subset paths)."""
        row_indices = np.asarray(row_indices)
        out = TpuDataset()
        out.bins = self.bins[row_indices]
        out.mappers = self.mappers
        out.used_features = self.used_features
        out.dataset_params = dict(self.dataset_params)
        out.num_data = len(row_indices)
        out.num_total_features = self.num_total_features
        out.feature_names = self.feature_names
        out.metadata = Metadata(out.num_data)
        md = self.metadata
        if md is not None:
            if md.label is not None:
                out.metadata.set_label(md.label[row_indices])
            if md.weight is not None:
                out.metadata.set_weight(md.weight[row_indices])
            if md.init_score is not None:
                init = md.init_score
                if init.size == self.num_data:
                    out.metadata.set_init_score(init[row_indices])
                else:
                    # flat [n*k] class-major init score: subset per class
                    k = init.size // self.num_data
                    sub = init.reshape(k, self.num_data)[:, row_indices]
                    out.metadata.set_init_score(sub.reshape(-1))
            if md.query_boundaries is not None:
                # rebuild query sizes over the kept rows (fold selections
                # keep whole queries; partial queries shrink consistently)
                # run-length encode query ids IN ROW ORDER so group sizes
                # stay aligned with the (possibly unsorted) subset rows
                qb = md.query_boundaries
                row_query = np.searchsorted(qb, row_indices, side="right") - 1
                if len(row_query):
                    change = np.concatenate(
                        [[True], row_query[1:] != row_query[:-1]])
                    starts = np.nonzero(change)[0]
                    seen = row_query[starts]
                    if len(np.unique(seen)) != len(seen):
                        log.warning(
                            "subset rows interleave query groups: a query's "
                            "rows are not contiguous in the subset, so it "
                            "is split into multiple groups — sort subset "
                            "indices by query to avoid this")
                    sizes = np.diff(np.concatenate([starts,
                                                    [len(row_query)]]))
                    out.metadata.set_group(sizes)
        out._finalize_feature_arrays()
        out.monotone_constraints = self.monotone_constraints
        return out
