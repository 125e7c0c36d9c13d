"""User-facing Dataset and Booster.

Behavioral analog of ref: python-package/lightgbm/basic.py (Dataset :1122,
Booster :2512).  There is no ctypes/C-API hop: the "library" is the in-process
TPU runtime, so `_safe_call`/handle plumbing collapses away while the public
surface (lazy construction, reference-aligned binning, update/eval/predict,
model IO, continued training) is preserved.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Union

import numpy as np

from .boosting import create_boosting
from .config import Config
from .dataset import TpuDataset
from .io import model_io
from .metric import create_metric, default_metric_for_objective
from .models.tree import HostTree
from .objective import create_objective, create_objective_from_string
from .obs.registry import Span
from .utils import log
from .utils.log import LightGBMError

__all__ = ["Dataset", "Booster", "Sequence"]

# host-walk sparse fallback densifies in bounded row chunks (a tall CSR
# predict must be a loop, not a whole-matrix todense)
_HOST_SPARSE_CHUNK_ROWS = 65_536


class Sequence:
    """Generic chunked data-access interface for dataset construction
    (ref: basic.py:605 Sequence ABC): implement ``__len__``,
    ``__getitem__`` for slices, and optionally ``batch_size``. The matrix
    is assembled in ``batch_size`` slices (the source never has to hand
    over one giant array; the assembled matrix itself is in RAM — the
    binned representation is what training keeps).

    A list of Sequences concatenates row-wise (multi-file datasets)."""

    batch_size = 4096

    def __len__(self) -> int:  # pragma: no cover - interface
        raise NotImplementedError

    def __getitem__(self, idx):  # pragma: no cover - interface
        raise NotImplementedError


def _materialize_sequences(seqs) -> np.ndarray:
    """Assemble a row-major float64 matrix from Sequence chunks (float64
    so binning matches the equivalent ndarray input exactly)."""
    if isinstance(seqs, Sequence):
        seqs = [seqs]
    chunks = []
    for seq in seqs:
        n = len(seq)
        bs = int(getattr(seq, "batch_size", None) or 4096)
        for lo in range(0, n, bs):
            chunks.append(np.asarray(seq[lo:min(n, lo + bs)], np.float64))
    if not chunks:
        raise ValueError("Sequence dataset has 0 rows")
    return np.concatenate(chunks, axis=0)


def host_walk_raw(models, X, lo: int, hi: int, k: int) -> np.ndarray:
    """Exact float64 host tree walk over trees [lo, hi): raw scores
    [k, n].  The ONE implementation of the host fallback — Booster
    ``_predict_raw`` and the serving engine's degraded path both route
    here, so the densify-in-bounded-chunks behavior (a tall sparse
    predict must be a loop, not a whole-matrix todense) cannot
    diverge."""
    n = X.shape[0]
    raw = np.zeros((k, n), np.float64)
    if _is_scipy_sparse(X):
        X = X.tocsr()
        step = _HOST_SPARSE_CHUNK_ROWS
        for c0 in range(0, n, step):
            sl = slice(c0, min(n, c0 + step))
            Xc = np.asarray(X[sl].todense(), np.float64)
            for i, t in enumerate(models[lo:hi]):
                raw[(lo + i) % k, sl] += t.predict_rows(Xc)
        return raw
    X = np.asarray(X, np.float64)
    for i, t in enumerate(models[lo:hi]):
        raw[(lo + i) % k] += t.predict_rows(X)
    return raw


def finalize_raw_predictions(raw: np.ndarray, k: int, objective,
                             average_output: bool, num_iteration: int,
                             raw_score: bool) -> np.ndarray:
    """Raw [k, n] scores -> the user-facing prediction array: RF score
    averaging, objective output transform, multiclass transpose.  The
    ONE implementation of the output contract — ``Booster.predict`` and
    the serving engine both end here, so serving results cannot drift
    from the Booster's."""
    if average_output and num_iteration > 0:
        raw = raw / num_iteration
    if not raw_score and objective is not None:
        if k > 1:
            return objective.convert_output(raw.T)
        return np.asarray(objective.convert_output(raw[0]))
    return raw[0] if k == 1 else raw.T


def pred_trees_stale(pred, booster) -> bool:
    # a monotonically-bumped version survives rollback+update swaps where
    # both the length and (recycled) id of the tail tree can repeat
    return getattr(pred, "model_version", -1) != booster._model_version


def _mappers_match(ref_inner, inner) -> bool:
    """Do two constructed datasets bin identically?  Identical mapper
    list objects short-circuit (streamed-with-reference builds, a
    dataset referencing itself); otherwise compare the full mapper
    digests.  The ONE alignment predicate both cache-acceptance paths
    (explicit .bin refusal, auto-sidecar miss) share."""
    if ref_inner.mappers is inner.mappers:
        return True
    from .binning import mappers_digest
    return mappers_digest(ref_inner.mappers) == mappers_digest(
        inner.mappers)


def _cohort_votes(flag: bool):
    """Allgather a boolean vote -> (any_true, all_true).  Cache hit/miss
    decisions must be cohort-consistent under multi-process loading:
    the rebuild path enters the binning-sample allgather, so a split
    vote (one rank's shard valid, another's missing/corrupt) would
    leave the hitting ranks outside a collective their peers are
    blocked in — every rank sees the split and can act on it."""
    import jax
    if jax.process_count() <= 1:
        return flag, flag
    from jax.experimental import multihost_utils
    votes = np.asarray(multihost_utils.process_allgather(
        np.array([1 if flag else 0], np.int32)))
    return bool(votes.max() == 1), bool(votes.min() == 1)


def _cohort_all_agree(flag: bool) -> bool:
    return _cohort_votes(flag)[1]


def _is_scipy_sparse(data) -> bool:
    try:
        import scipy.sparse as sp
    except ImportError:  # pragma: no cover
        return False
    return sp.issparse(data)


def _to_2d_numpy(data) -> np.ndarray:
    if _is_scipy_sparse(data):
        # chunk-free densify is only acceptable at prediction-batch sizes;
        # Dataset construction routes sparse input to from_sparse instead
        return np.asarray(data.todense(), np.float64)
    if hasattr(data, "values") and not isinstance(data, np.ndarray):
        data = data.values  # pandas
    arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.dtype == object:
        arr = arr.astype(np.float64)
    return arr


class Dataset:
    """Training dataset with lazy construction
    (ref: basic.py:1122 Dataset)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name="auto", categorical_feature="auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params) if params else {}
        self.free_raw_data = free_raw_data
        self._inner: Optional[TpuDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        """(ref: basic.py Dataset.construct / _lazy_init)"""
        if self._inner is not None:
            return self
        # the ``bin`` span and its children close into a list of their
        # own: no Booster has a sink yet (TpuDataset.setup_spans)
        spans: List[Dict[str, Any]] = []
        with Span(None, "bin", hold=spans) as span:
            self._construct()
            span.set(rows=self._inner.num_data,
                     features=self._inner.num_total_features)
        self._inner.setup_spans = spans
        return self

    def _construct(self) -> None:
        cfg = Config(self.params)
        if isinstance(self.data, Sequence) or (
                isinstance(self.data, list) and self.data
                and all(isinstance(x, Sequence) for x in self.data)):
            # chunked out-of-core assembly (ref: Sequence streaming push)
            self.data = _materialize_sequences(self.data)
        pending_cache = None
        if isinstance(self.data, (str, os.PathLike)):
            if self._construct_from_file(cfg):
                return
            pending_cache = self._pending_cache_write
            self._pending_cache_write = None
        is_sparse = _is_scipy_sparse(self.data)
        data = self.data if is_sparse else _to_2d_numpy(self.data)
        cats, feature_names = self._resolve_cats_names(self.data)
        ref_inner = None
        if self.reference is not None:
            ref_inner = self.reference.construct()._inner
        if is_sparse:
            # CSR/CSC ingestion without densifying (ref: c_api.cpp:398-520
            # DatasetCreateFromCSR/CSC; storage answer: ingestion-time EFB,
            # see TpuDataset.from_sparse)
            if cats:
                raise LightGBMError(
                    "categorical features are not supported for sparse "
                    "input yet; densify those columns")
            if bool(cfg.linear_tree):
                raise LightGBMError(
                    "linear_tree needs retained raw data and is not "
                    "supported for sparse input")
            self._inner = TpuDataset.from_sparse(
                data, cfg, feature_names=feature_names,
                reference=ref_inner)
        else:
            self._inner = TpuDataset.from_data(
                data, cfg, categorical_feature=cats,
                feature_names=feature_names, reference=ref_inner)
        with Span(None, "bin/finalize", rows=self._inner.num_data):
            if not is_sparse and bool(cfg.linear_tree):
                # linear leaves fit ridge models on RAW feature values
                # (ref: dataset raw-data retention for linear_tree)
                self._inner.raw_data = np.asarray(data, np.float32)
            if self.label is not None:
                self._inner.metadata.set_label(np.asarray(self.label))
            if self.weight is not None:
                self._inner.metadata.set_weight(np.asarray(self.weight))
            if self.group is not None:
                self._inner.metadata.set_group(np.asarray(self.group))
            if self.init_score is not None:
                self._inner.metadata.set_init_score(
                    np.asarray(self.init_score))
            if pending_cache is not None:
                self._write_sidecar_cache(*pending_cache)

    # ------------------------------------------------------------------
    def _apply_explicit_metadata(self) -> None:
        """Explicitly-passed metadata overrides a cache/stream-loaded
        copy (the reference's LoadFromBinFile + SetField sequence
        behaves the same way); absent overrides adopt the loaded
        values onto the facade attributes."""
        # a cache used as validation data must share its reference's
        # bin mappers (it was built with reference= at save time, or it
        # is the train cache itself) — anything else would route eval
        # rows through foreign bins silently (the reference's
        # CheckAlign contract)
        if self.reference is not None:
            if not _mappers_match(self.reference.construct()._inner,
                                  self._inner):
                raise LightGBMError(
                    "cached dataset was binned with different mappers "
                    "than its reference dataset; rebuild the cache from "
                    "text with reference= the training data")
        elif getattr(self._inner, "reference_binned", False):
            # a validation cache carries ANOTHER dataset's mappers —
            # training on it standalone would bin against foreign
            # boundaries silently
            raise LightGBMError(
                "this dataset cache was binned against a reference "
                "(validation) dataset; pass reference= the training "
                "data, or rebuild the cache from text standalone")
        # the cache round-trips the binning-defining params (like the
        # reference's .bin): a booster built on the reloaded dataset
        # resolves the SAME values the original build used — explicit
        # user params still win
        for k, v in (getattr(self._inner, "dataset_params", None)
                     or {}).items():
            self.params.setdefault(k, v)
        md = self._inner.metadata
        if self.label is not None:
            md.set_label(np.asarray(self.label))
        elif md is not None:
            self.label = md.label
        if self.weight is not None:
            md.set_weight(np.asarray(self.weight))
        elif md.weight is not None:
            self.weight = md.weight
        if self.group is not None:
            md.set_group(np.asarray(self.group, np.int64))
        if self.init_score is not None:
            md.set_init_score(np.asarray(self.init_score))
        elif md.init_score is not None:
            self.init_score = md.init_score

    def _resolve_cats_names(self, columns_source=None):
        """(categorical index list, feature names or None) — the ONE
        resolution of user feature names / string categoricals, shared
        by the generic construct tail (``columns_source`` supplies
        pandas column names) and the streamed file build (which needs
        them BEFORE mapper construction)."""
        feature_names = None
        if self.feature_name != "auto" and self.feature_name is not None:
            feature_names = list(self.feature_name)
        elif columns_source is not None \
                and hasattr(columns_source, "columns"):
            feature_names = [str(c) for c in columns_source.columns]
        cats = []
        if self.categorical_feature != "auto" \
                and self.categorical_feature is not None:
            for c in self.categorical_feature:
                if isinstance(c, str):
                    if feature_names and c in feature_names:
                        cats.append(feature_names.index(c))
                else:
                    cats.append(int(c))
        return cats, feature_names

    def _construct_from_file(self, cfg) -> bool:
        """File-based construction routing (ref:
        DatasetLoader::LoadFromFile / LoadFromBinFile).  Returns True
        when ``_inner`` is fully built (binary-cache hit or streamed
        chunked ingest); False to fall through to the monolithic tail
        with ``self.data`` holding the parsed shard.  Multi-process:
        each rank reads its contiguous row slice unless pre_partition
        says the file already IS this rank's partition
        (ref: dataset_loader.cpp:203 + config.h pre_partition)."""
        import jax as _jax

        from .ingest.cache import (CACHE_MAGIC, CacheError,
                                   cache_shard_path, load_dataset_cache,
                                   read_manifest, source_fingerprint)
        from .ingest.pipeline import (dataset_params_digest,
                                      ingest_text_streamed,
                                      streaming_eligible)
        self._pending_cache_write = None
        path = str(self.data)
        rank, nm = 0, 1
        if _jax.process_count() > 1 and not bool(cfg.pre_partition):
            rank, nm = _jax.process_index(), _jax.process_count()

        def _magic(p):
            try:
                with open(p, "rb") as fh:
                    return fh.read(8)
            except OSError:
                return b""

        # ---- explicit binary-cache input short-circuits the text
        # loader entirely (the cache magic is checked before any
        # parsing). Multi-process ranks resolve their own shard file
        # (<path>.rank<r>of<w>) first; the take-the-cache decision must
        # be UNANIMOUS across the cohort — a rank whose shard is
        # missing would fall through to the text path and block in a
        # binning-sample collective its cache-hitting peers never join
        shard = cache_shard_path(path, rank, nm)
        head = _magic(path)
        local_cache = None
        if nm > 1 and _magic(shard) == CACHE_MAGIC:
            local_cache = shard
        elif head in (CACHE_MAGIC, b"LGBMTPU1"):
            local_cache = path
        if nm > 1:
            any_hit, all_hit = _cohort_votes(local_cache is not None)
            if any_hit and not all_hit:
                # EVERY rank raises (both sides see the split), so the
                # cohort fails together instead of hanging
                raise CacheError(
                    f"binary cache shards for {path} exist on some "
                    "ranks only — rebuild every rank's shard "
                    "(save_binary under the current launcher layout) "
                    "or point data= at the text source")
            if not all_hit:
                local_cache = None
        if local_cache is not None:
            if _magic(local_cache) == b"LGBMTPU1":   # legacy v1 pickle
                self._inner = TpuDataset.load_binary(local_cache)
            else:
                self._inner = load_dataset_cache(
                    local_cache, expect_rank=rank, expect_world=nm)
            self._apply_explicit_metadata()
            return True

        # ---- auto-maintained sidecar cache (save_binary=true): hit
        # only when the source fingerprint (size/mtime/dataset params),
        # rank layout AND binning provenance (standalone vs
        # reference-binned) still match — anything else rebuilds.
        # Multi-process: the hit/miss decision must be COHORT-WIDE —
        # the rebuild path joins the binning-sample allgather, so one
        # rank hitting while another rebuilds would deadlock the
        # collective; every rank reaches the agreement allgather below
        # whether or not its own shard file exists.
        cats, feature_names = self._resolve_cats_names()
        auto_cache = None
        if bool(cfg.save_binary):
            auto_cache = cache_shard_path(path + ".bin", rank, nm)
            loaded = None
            if os.path.exists(auto_cache):
                try:
                    manifest = read_manifest(auto_cache)
                    cur = source_fingerprint(
                        path, dataset_params_digest(cfg, cats))
                    if manifest.get("source") == cur \
                            and int(manifest.get("world", 1)) == nm \
                            and bool(manifest.get("reference_binned",
                                                  False)) \
                            == (self.reference is not None):
                        # full load INCLUDING hash verification here, so
                        # a corrupt-bins shard counts as a miss at the
                        # agreement point instead of crashing post-vote
                        loaded = load_dataset_cache(
                            auto_cache, expect_rank=rank,
                            expect_world=nm)
                    else:
                        log.info("binary cache %s is stale (source, "
                                 "params, layout or provenance "
                                 "changed); rebuilding", auto_cache)
                except CacheError as e:
                    log.warning("ignoring unusable binary cache: %s", e)
            if loaded is not None and self.reference is not None:
                # an auto (validation) sidecar whose reference dataset
                # was itself rebuilt carries outdated mappers: on this
                # best-effort path that is a MISS to rebuild, not the
                # hard error the explicitly-passed-cache path raises
                if not _mappers_match(self.reference.construct()._inner,
                                      loaded):
                    log.info("binary cache %s no longer matches its "
                             "reference dataset's mappers; rebuilding",
                             auto_cache)
                    loaded = None
            hit = loaded is not None
            if nm > 1:
                hit = _cohort_all_agree(hit)
            if hit:
                self._inner = loaded
                self._apply_explicit_metadata()
                return True

        eligible, _reason = streaming_eligible(cfg, path)
        if eligible:
            ref_inner = None
            if self.reference is not None:
                ref_inner = self.reference.construct()._inner
            def _stream(cache_to):
                return ingest_text_streamed(
                    path, cfg,
                    label_column=self.params.get("label_column"),
                    rank=rank, num_machines=nm,
                    categorical_feature=cats,
                    feature_names=feature_names, reference=ref_inner,
                    cache_out=cache_to, world=nm)
            try:
                inner, y, _side = _stream(auto_cache)
            except (CacheError, OSError) as e:
                if auto_cache is None:
                    raise
                # the sidecar cache is best-effort: a full disk or a
                # read-only data directory must not kill the build —
                # re-stream assembling in memory instead
                log.warning("binary cache not written (%s); streaming "
                            "without a cache", e)
                inner, y, _side = _stream(None)
            self._inner = inner
            self._apply_explicit_metadata()
            return True

        # ---- monolithic fallback: parse the shard as one array and
        # let the generic tail bin it; with save_binary the built
        # dataset is cached after construction
        from .io.file_loader import load_text_file
        X, y, side = load_text_file(
            path, label_column=self.params.get("label_column"),
            rank=rank, num_machines=nm)
        self.data = X
        if self.label is None and y is not None:
            self.label = y
        if self.weight is None and "weight" in side:
            self.weight = side["weight"]
        if self.group is None and "group" in side:
            self.group = side["group"]
        if self.init_score is None and "init_score" in side:
            self.init_score = side["init_score"]
        if auto_cache is not None:
            self._pending_cache_write = (
                auto_cache, path, rank, nm,
                dataset_params_digest(cfg, cats))
        return False

    def _write_sidecar_cache(self, cache_path: str, src_path: str,
                             rank: int, world: int,
                             params_digest: str) -> None:
        """Post-construction cache write for the monolithic path
        (streamed ingest writes during pass 2 instead)."""
        from .ingest.cache import (CacheError, save_dataset_cache,
                                   source_fingerprint)
        try:
            save_dataset_cache(
                self._inner, cache_path, rank=rank, world=world,
                source=source_fingerprint(src_path, params_digest))
            # marker for callers (cli task=save_binary): the artifact at
            # this path is fresh and fingerprinted — do not rewrite it
            self._inner.sidecar_cache_path = cache_path
        except (CacheError, OSError) as e:
            # best-effort: ineligible datasets (CacheError) and write
            # failures (disk full, read-only dir) warn, never abort a
            # successfully-built construct
            log.warning("binary cache not written: %s", e)

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None and label is not None:
            self._inner.metadata.set_label(np.asarray(label))
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weight(
                None if weight is None else np.asarray(weight))
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None and group is not None:
            self._inner.metadata.set_group(np.asarray(group))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(
                None if init_score is None else np.asarray(init_score))
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        """(ref: basic.py Dataset.set_field)"""
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "group":
            return self.set_group(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        raise ValueError(f"Unknown field name: {field_name}")

    def get_field(self, field_name: str):
        md = self.construct()._inner.metadata
        if field_name == "label":
            return md.label
        if field_name == "weight":
            return md.weight
        if field_name == "group":
            return md.query_boundaries
        if field_name == "init_score":
            return md.init_score
        raise ValueError(f"Unknown field name: {field_name}")

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_init_score(self):
        return self.get_field("init_score")

    def get_group(self):
        # boundaries -> per-query sizes (ref: basic.py:2321 get_group diffs)
        boundaries = self.get_field("group")
        return None if boundaries is None else np.diff(boundaries)

    # ------------------------------------------------------------------
    def add_features_from(self, other: "Dataset") -> "Dataset":
        """(ref: basic.py Dataset.add_features_from)"""
        self.construct()
        other.construct()
        self._inner.add_features_from(other._inner)
        return self

    def num_data(self) -> int:
        return self.construct()._inner.num_data

    def num_feature(self) -> int:
        return self.construct()._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        return self.construct()._inner.feature_names

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing bin mappers (ref: basic.py Dataset.subset)."""
        self.construct()
        sub = Dataset.__new__(Dataset)
        sub.data = None
        sub.label = None
        sub.reference = self
        sub.weight = None
        sub.group = None
        sub.init_score = None
        sub.feature_name = self.feature_name
        sub.categorical_feature = self.categorical_feature
        sub.params = dict(self.params)
        if params:
            sub.params.update(params)
        sub.free_raw_data = self.free_raw_data
        sub.used_indices = np.asarray(used_indices)
        sub._inner = self._inner.subset(sub.used_indices)
        sub._predictor = None
        if self.data is not None:
            sub.data = _to_2d_numpy(self.data)[sub.used_indices]
        return sub

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        """(ref: basic.py Dataset.create_valid)"""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params)

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()._inner.save_binary(filename)
        return self


class Booster:
    """Booster: training + prediction handle (ref: basic.py:2512)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._gbdt = None
        self.models: List[HostTree] = []
        self.objective = None
        self.config: Optional[Config] = None
        self.train_set: Optional[Dataset] = None
        self.valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self.loaded_parameter = ""
        self.average_output = False
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.max_feature_idx = 0
        self.feature_names: List[str] = []
        self._model_version = 0  # bumped on every model-list mutation
        self.feature_infos: List[str] = []
        self.monotone_constraints = None
        self.label_index = 0
        # drift & lineage plane (obs/drift.py): the training-data
        # profile and provenance record ride the model artifact and
        # checkpoint payloads; None for pre-plane artifacts (serving
        # degrades structurally — see docs/Observability.md §13)
        self.data_profile: Optional[Dict[str, Any]] = None
        self.provenance: Optional[Dict[str, Any]] = None

        if train_set is not None:
            self._init_train(train_set)
        elif model_file is not None:
            with open(model_file, "r") as fh:
                self._load_model_string(fh.read())
        elif model_str is not None:
            self._load_model_string(model_str)

    # ------------------------------------------------------------------
    def _init_train(self, train_set: Dataset) -> None:
        if not isinstance(train_set, Dataset):
            raise TypeError("Training data should be Dataset instance")
        # (config, objective and its per-row state, the driver object:
        # what runs before the driver's registry exists closes into the
        # span around it, engine.train's)
        with Span(None, "init/config_objective"):
            merged = dict(train_set.params)
            merged.update(self.params)
            self.config = Config(merged)
            train_set.params = merged
            train_set.construct()
            self.train_set = train_set
            inner = train_set._inner
            # a binary-cache-loaded dataset restores the binning-defining
            # params it was built with (construction may have happened just
            # now, AFTER the config snapshot above): fold them in unless
            # the user explicitly set a conflicting value, so the resolved
            # config (and the serialized parameters echo) matches the
            # original build's
            restored = {k: v for k, v in (getattr(inner, "dataset_params",
                                                  None) or {}).items()
                        if not self.config.was_set(k)}
            if restored:
                self.config.update(restored)
                train_set.params.update(restored)
            self.objective = create_objective(self.config)
            if self.objective is not None:
                if inner.metadata.label is None:
                    raise ValueError("Label should not be None")
                self.objective.init(inner.metadata, inner.num_data)
            self.num_class = max(1, int(self.config.num_class))
            self._gbdt = create_boosting(self.config)
            train_metrics = []
            if self.config.is_provide_training_metric:
                train_metrics = self._make_metrics(inner)
        self._gbdt.init(self.config, inner, self.objective, train_metrics)
        self.num_tree_per_iteration = self._gbdt.num_tree_per_iteration
        self.average_output = getattr(self._gbdt, "average_output", False)
        self.models = self._gbdt.models
        self.max_feature_idx = inner.num_total_features - 1
        self.feature_names = inner.feature_names
        self.feature_infos = inner.feature_infos()
        if inner.monotone_constraints is not None:
            self.monotone_constraints = inner.monotone_constraints
        if bool(getattr(self.config, "drift_profile", True)):
            with self._gbdt.telemetry.timed("init/profile",
                                            rows=inner.num_data):
                self._capture_profile(train_set, inner)

    def _capture_profile(self, train_set: Dataset, inner) -> None:
        """Capture the DataProfile + provenance record at train init
        (the packed bins and frozen mappers exist; one bincount per
        feature, no device work).  Mirrored onto the driver so
        checkpoint payloads and the run report carry them."""
        try:
            from .ingest.pipeline import dataset_params_digest
            from .obs import drift as _drift
            try:
                import jax as _jax
                world = int(_jax.process_count())
            except Exception:
                world = 1
            if world > 1:
                # multiprocess ranks hold rank-local row shards: a
                # per-rank profile would make the rank artifacts
                # diverge, breaking the cross-rank model-identity
                # contract. Skip embedding — serving such a model takes
                # the structural drift_unavailable degrade path.
                log.debug("drift profile skipped: %d-process training "
                          "shards rows rank-locally", world)
                return
            cats = [int(j) for k, j in enumerate(inner.used_features)
                    if inner.is_categorical[k]]
            self.data_profile = _drift.build_profile(inner)
            # run_id is left for build_provenance to content-derive:
            # embedding the (per-process) telemetry run_id would break
            # byte-equality of identical trainings' model strings
            self.provenance = _drift.build_provenance(
                params_digest=dataset_params_digest(self.config, cats),
                source=_drift.source_fingerprint(train_set.data,
                                                 self.data_profile),
                parent_checkpoint="",
                profile=self.data_profile)
            self._gbdt.data_profile = self.data_profile
            self._gbdt.provenance = self.provenance
        except Exception as exc:  # never fail training over telemetry
            log.warning("data-profile capture failed: %s", exc)

    def _make_metrics(self, inner: TpuDataset) -> List:
        names = [str(m) for m in self.config.metric]
        if not names:
            default = default_metric_for_objective(self.config.objective)
            names = [default] if default else []
        metrics = []
        for name in names:
            m = create_metric(name, self.config)
            if m is not None:
                m.init(inner.metadata, inner.num_data)
                metrics.append(m)
        return metrics

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        """(ref: basic.py Booster.add_valid)"""
        if self._gbdt is None:
            raise Exception("Booster was not trained with a train_set")
        if data.reference is not self.train_set:
            data.reference = self.train_set
        data.construct()
        with self._gbdt.telemetry.timed("valid/metrics", valid_set=name):
            metrics = self._make_metrics(data._inner)
        self._gbdt.add_valid_data(data._inner, name, metrics)
        self.valid_sets.append(data)
        self.name_valid_sets.append(name)
        return self

    # ------------------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; True if no further splits possible
        (ref: basic.py:2936 Booster.update)."""
        if train_set is not None and train_set is not self.train_set:
            raise Exception("Replacing train_set is not supported yet")
        self._model_version += 1
        if fobj is None:
            return self._gbdt.train_one_iter()
        if self.objective is not None:
            raise Exception(
                "Cannot use custom objective when the booster was created "
                "with a built-in objective; set objective='none'")
        grad, hess = fobj(self.__inner_predict_train(), self.train_set)
        return self.__boost(grad, hess)

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, np.float32).reshape(
            self.num_tree_per_iteration, -1)
        hess = np.asarray(hess, np.float32).reshape(
            self.num_tree_per_iteration, -1)
        return self._gbdt.train_one_iter(grad, hess)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        self._model_version += 1
        return self

    # ------------------------------------------------------------------
    def telemetry(self) -> Dict[str, Any]:
        """Snapshot of the training telemetry registry (obs/): counters,
        gauges, per-section timing distributions and the recent
        structured-event ring. Empty dict for model-file boosters (no
        live driver); {"enabled": False, ...} shell when telemetry was
        never enabled (enable it with the ``telemetry_out`` param or the
        ``record_telemetry`` callback). See docs/Observability.md."""
        if self._gbdt is None:
            return {}
        self._gbdt.drain_pending()
        return self._gbdt.telemetry.snapshot()

    def _finalize_telemetry(self) -> None:
        """End-of-training telemetry epilogue (engine.train calls this):
        profiler stop + summary event + trace export + JSONL flush."""
        if self._gbdt is not None:
            if self.data_profile is not None \
                    and "score" not in self.data_profile:
                # final train-margin distribution: ONE fetch of the
                # training scores to the host, then numpy
                with self._gbdt.telemetry.timed("finish/score_profile"):
                    self._capture_score_profile()
            self._gbdt.finalize_telemetry()

    def _capture_score_profile(self) -> None:
        try:
            from .obs.drift import add_score_distribution, profile_digest
            scores = getattr(self._gbdt, "scores", None)
            if scores is not None:
                add_score_distribution(self.data_profile,
                                       np.asarray(scores))
                if self.provenance is not None:
                    self.provenance["profile_digest"] = \
                        profile_digest(self.data_profile)
        except Exception as exc:
            log.warning("score-profile capture failed: %s", exc)

    def _dump_crash(self, exc: BaseException) -> None:
        """Crash flight recorder hook (engine.train calls this when an
        exception unwinds out of the train loop): dump the telemetry
        ring + section stack + config to <telemetry_out>.crash.json."""
        if self._gbdt is not None:
            self._gbdt.dump_crash(exc)

    def _drain(self) -> None:
        """Materialise any device trees still queued by the training fast
        path before reading the host model list."""
        if self._gbdt is not None:
            self._gbdt.drain_pending()

    def current_iteration(self) -> int:
        """Iterations trained so far. PROVISIONAL under the pipelined
        driver: queued-but-undrained iterations count, and a later drain
        may discard some of them via the deferred no-split stop — poll
        num_trees() (which drains) for a settled count."""
        return self._gbdt.iter if self._gbdt is not None else \
            len(self.models) // max(1, self.num_tree_per_iteration)

    def num_trees(self) -> int:
        self._drain()
        return len(self.models)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def __inner_predict_train(self) -> np.ndarray:
        g = self._gbdt
        if getattr(g, "mp", None) is not None:
            # multi-process: fobj is rank-local like the reference's
            # distributed custom objective — this rank's rows only
            loc = g.mp.local_block(g.scores, axis=1)[:, :g.mp.local_real]
            return np.asarray(loc, np.float64).reshape(-1)
        return np.asarray(g.scores, np.float64).reshape(-1)

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        return self._eval_set("training", None, feval)

    def eval_valid(self, feval=None) -> List:
        out = []
        for i, name in enumerate(self.name_valid_sets):
            out.extend(self._eval_set(name, i, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        if data is self.train_set:
            return self.eval_train(feval)
        for i, vs in enumerate(self.valid_sets):
            if vs is data:
                return self._eval_set(self.name_valid_sets[i], i, feval)
        raise Exception("Data should be added with add_valid first")

    def _eval_set(self, name: str, valid_idx: Optional[int], feval) -> List:
        """Returns [(dataset_name, metric_name, value, is_higher_better)].

        Metrics with a device formulation evaluate on the live device
        scores without draining the pipelined driver or pulling the score
        matrix (one batched scalar fetch at the end); host-only metrics,
        custom ``feval``s, and RF score averaging take the classic path."""
        import jax
        g = self._gbdt
        out = []
        if valid_idx is None:
            score_dev = g.scores
            metrics = g.training_metrics
            dataset = self.train_set
        else:
            score_dev = g.valid_scores[valid_idx]
            metrics = g.valid_metrics[valid_idx]
            dataset = self.valid_sets[valid_idx]
        if getattr(g, "average_output", False) or feval is not None:
            self._drain()   # needs the settled model count / host scores
            # re-capture: the drain may apply the deferred no-split-stop
            # subtraction, so the device rows captured above are stale
            score_dev = (g.scores if valid_idx is None
                         else g.valid_scores[valid_idx])
        if getattr(g, "average_output", False):
            score_dev = score_dev / max(1, g.num_iterations_trained)
        out.extend(g.eval_metric_set(name, metrics, score_dev))
        if feval is not None:
            if not getattr(score_dev, "is_fully_addressable", True):
                raise ValueError(
                    "custom feval needs the full score matrix on one "
                    "host; not supported with multi-process training")
            host_score = np.asarray(score_dev, np.float64)
            for f in (feval if isinstance(feval, list) else [feval]):
                ret = f(host_score.reshape(-1), dataset)
                rets = ret if isinstance(ret, list) else [ret]
                for mn, v, hb in rets:
                    out.append((name, mn, v, hb))
        fetched = jax.device_get([v for (_, _, v, _) in out])
        return [(d, n, float(v), b)
                for (d, n, _, b), v in zip(out, fetched)]

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                **kwargs) -> np.ndarray:
        """(ref: basic.py:3449 Booster.predict → predictor.hpp)"""
        from .utils.timer import global_timer as _timer
        with _timer.section("Predictor::Predict"):
            return self._predict_body(
                data, start_iteration, num_iteration, raw_score, pred_leaf,
                pred_contrib, pred_early_stop, pred_early_stop_freq,
                pred_early_stop_margin)

    def _predict_body(self, data, start_iteration, num_iteration, raw_score,
                      pred_leaf, pred_contrib, pred_early_stop,
                      pred_early_stop_freq,
                      pred_early_stop_margin) -> np.ndarray:
        self._drain()
        # float32 sources are exactly representable in the raw-value
        # device predictor's compares; remember before the f64 upcast
        f32_input = getattr(data, "dtype", None) == np.float32
        if _is_scipy_sparse(data):
            # the batch predictor densifies per chunk; host-walk paths
            # (pred_leaf/contrib/early-stop) densify below as needed
            X = data.tocsr()
        else:
            X = _to_2d_numpy(data).astype(np.float64)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        # only num_iteration=None means "use best_iteration"; an explicit
        # <=0 means all trees (ref: basic.py predict num_iteration handling)
        if num_iteration is None:
            num_iteration = self.best_iteration \
                if self.best_iteration > 0 else -1
        total_iter = len(self.models) // max(1, k)
        if num_iteration <= 0:
            num_iteration = total_iter - start_iteration
        num_iteration = min(num_iteration, total_iter - start_iteration)
        lo = start_iteration * k
        hi = (start_iteration + num_iteration) * k

        if _is_scipy_sparse(X) and (pred_leaf or pred_contrib
                                    or pred_early_stop):
            # host-walk paths operate row-wise on raw values
            X = np.asarray(X.todense(), np.float64)

        if pred_leaf:
            out = np.zeros((n, hi - lo), np.int32)
            for i, t in enumerate(self.models[lo:hi]):
                out[:, i] = t.predict_leaf_index(X)
            return out
        if pred_contrib:
            from .io.shap import predict_contrib
            return predict_contrib(self, X, lo, hi)

        if pred_early_stop and self.num_tree_per_iteration >= 1 \
                and not self.average_output:
            raw = self._predict_raw_early_stop(
                X, lo, hi, pred_early_stop_freq, pred_early_stop_margin)
        else:
            raw = self._predict_raw(X, lo, hi, f32_input=f32_input)
        return finalize_raw_predictions(raw, k, self.objective,
                                        self.average_output,
                                        num_iteration, raw_score)

    # ------------------------------------------------------------------
    def _predict_raw_early_stop(self, X: np.ndarray, lo: int, hi: int,
                                freq: int, margin: float) -> np.ndarray:
        """Margin-based prediction early stopping (ref:
        src/boosting/prediction_early_stop.cpp — binary: |raw| > margin;
        multiclass: top1 - top2 > margin; checked every ``freq`` trees).
        Rows whose margin clears the threshold stop accumulating trees."""
        n = X.shape[0]
        k = self.num_tree_per_iteration
        raw = np.zeros((k, n), np.float64)
        active = np.ones(n, bool)
        for i, t in enumerate(self.models[lo:hi]):
            if not active.any():
                break
            idx = np.nonzero(active)[0]
            raw[(lo + i) % k, idx] += t.predict_rows(X[idx])
            if (i + 1) % (freq * k) == 0:
                if k == 1:
                    done = np.abs(raw[0, idx]) > margin
                else:
                    part = np.sort(raw[:, idx], axis=0)
                    done = (part[-1] - part[-2]) > margin
                active[idx[done]] = False
        return raw

    def _pred_device_min_work(self) -> int:
        """Resolved ``pred_device_min_work`` threshold (rows x trees at
        or above which predict routes through the device predictor) —
        from the live training config when one exists, else from the
        booster params (model-file boosters)."""
        if self.config is not None:
            return int(self.config.pred_device_min_work)
        cached = getattr(self, "_pred_min_work_cache", None)
        if cached is None:
            # resolve the ONE key by hand — constructing a full Config
            # here would re-run its _post_process side effects (global
            # log level!) on every first predict of a model-file booster
            cached = 2_000_000
            for key, value in self.params.items():
                if Config.resolve_key(str(key)) == "pred_device_min_work" \
                        and value is not None:
                    cached = int(float(value))
            self._pred_min_work_cache = cached
        return cached

    def _pred_min_work_user_set(self) -> bool:
        """Did the user explicitly set ``pred_device_min_work``?  An
        explicit value is the opt-in that lets float64 input take the
        float32 raw-routing device path."""
        if self.config is not None:
            return self.config.was_set("pred_device_min_work")
        return any(Config.resolve_key(str(key)) == "pred_device_min_work"
                   for key in self.params)

    def _predict_raw(self, X: np.ndarray, lo: int, hi: int,
                     f32_input: bool = False) -> np.ndarray:
        """Raw scores [k, n]: device batch path for big jobs (one jit
        scan over a stacked tree tensor — ref: predictor.hpp:30 replaced
        per SURVEY §3.3; binned routing through the training mappers
        when a training dataset is attached, raw-value-threshold routing
        otherwise, so model-file boosters get the device path too), host
        tree walk below ``pred_device_min_work`` rows x trees (exact
        float64 accumulation).

        The raw-routing variant compares in float32: leaf routing is
        bit-identical to the host walk only for float32-representable
        input, so it auto-engages only when the source data was float32
        — float64 callers keep the exact host walk unless they opted in
        by setting ``pred_device_min_work`` themselves."""
        n = X.shape[0]
        k = self.num_tree_per_iteration
        n_trees = hi - lo
        if n * max(n_trees, 1) >= self._pred_device_min_work():
            has_train = (self.train_set is not None
                         and self.train_set._inner is not None)
            if not has_train and not f32_input \
                    and not self._pred_min_work_user_set():
                return host_walk_raw(self.models, X, lo, hi, k)
            pred = getattr(self, "_device_predictor", None)
            if pred is None or pred_trees_stale(pred, self):
                if has_train:
                    from .models.predictor import DevicePredictor
                    pred = DevicePredictor(self.models,
                                           self.train_set._inner, k)
                else:
                    from .models.predictor import RawDevicePredictor
                    pred = RawDevicePredictor(self.models,
                                              self.max_feature_idx + 1, k)
                # cache failed packs too: the ineligibility decision
                # (linear trees, oversized cat vocab) is per model
                # state, and re-scanning every tree per predict call
                # would tax exactly the repeated-predict workloads the
                # device path exists for
                pred.model_version = self._model_version
                self._device_predictor = pred
            if pred.ok:
                return pred.predict_raw(X, lo, hi)
        return host_walk_raw(self.models, X, lo, hi, k)

    # ------------------------------------------------------------------
    def set_network(self, machines: str, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """Multi-host setup shim (ref: basic.py:2687 Booster.set_network);
        maps the reference's machine-list parameters onto
        jax.distributed.initialize — see parallel/distributed.py."""
        from .parallel import distributed
        distributed.set_network(machines, local_listen_port, num_machines,
                                listen_time_out)
        return self

    def free_network(self) -> "Booster":
        """(ref: basic.py:2721)"""
        from .parallel import distributed
        distributed.free_network()
        return self

    # ------------------------------------------------------------------
    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """(ref: basic.py Booster.reset_parameter → gbdt.cpp ResetConfig)"""
        self.params.update(params)
        # model-file boosters resolve predict-time keys from params —
        # drop the cached threshold so the new value takes effect
        self._pred_min_work_cache = None
        if self._gbdt is not None:
            self.config.update(params)
            self._gbdt.reset_config(self.config)
        return self

    # ------------------------------------------------------------------
    def model_to_string(self, start_iteration: int = 0,
                        num_iteration: Optional[int] = None,
                        importance_type: Union[int, str] = "split") -> str:
        self._drain()
        if num_iteration is None:
            # stock semantics: default to the early-stopped best iteration
            # (an explicit <= 0 still means "all trees")
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        it = 0 if importance_type in (0, "split") else 1
        return model_io.save_model_to_string(self, start_iteration,
                                             num_iteration, it)

    def save_model(self, filename: str, start_iteration: int = 0,
                   num_iteration: Optional[int] = None,
                   importance_type: Union[int, str] = "split") -> "Booster":
        # serialize first, then atomic write-then-rename: a crash mid-
        # snapshot (the engine's snapshot_freq files double as resume
        # checkpoints) can never leave a truncated model file behind
        from .resilience.atomicio import atomic_write_text
        text = self.model_to_string(start_iteration, num_iteration,
                                    importance_type)
        atomic_write_text(str(filename), text)
        return self

    def dump_model(self, start_iteration: int = 0,
                   num_iteration: Optional[int] = None) -> dict:
        self._drain()
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        import json as _json
        return _json.loads(model_io.dump_model_json(self, start_iteration,
                                                    num_iteration))

    def _load_model_string(self, model_str: str) -> None:
        header, trees, params = model_io.parse_model_string(model_str)
        self.models = trees
        self.loaded_parameter = params
        self.num_class = int(header.get("num_class", 1))
        self.num_tree_per_iteration = int(
            header.get("num_tree_per_iteration", 1))
        self.max_feature_idx = int(header.get("max_feature_idx", 0))
        self.label_index = int(header.get("label_index", 0))
        self.average_output = header.get("average_output", "0") == "1"
        self.feature_names = header.get("feature_names", "").split()
        self.feature_infos = header.get("feature_infos", "").split()
        obj_str = header.get("objective", "none")
        self._objective_str = obj_str
        self.objective = create_objective_from_string(obj_str)
        # pre-plane artifacts have neither block -> None (serving emits
        # one drift_unavailable event instead of monitoring)
        self.data_profile = model_io.extract_data_profile(model_str)
        self.provenance = model_io.extract_provenance(model_str)

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        it = 0 if importance_type == "split" else 1
        self._drain()
        models = self.models
        if iteration is not None and iteration > 0:
            models = models[:iteration * self.num_tree_per_iteration]
        return model_io.feature_importance(models, self.max_feature_idx + 1,
                                           it)

    def feature_name(self) -> List[str]:
        return self.feature_names

    def num_feature(self) -> int:
        return self.max_feature_idx + 1

    # ------------------------------------------------------------------
    def refit(self, data, label, decay_rate: float = 0.9, **kwargs):
        """Refit leaf values on new data (ref: basic.py:3506 Booster.refit,
        gbdt.cpp:287 RefitTree)."""
        X = _to_2d_numpy(data).astype(np.float64)
        label = np.asarray(label, np.float64).reshape(-1)
        import copy
        self._drain()
        new_booster = copy.deepcopy(self)
        # leaf assignment per tree, then leaf values blended:
        # new = decay * old + (1-decay) * newly-fitted mean residual value
        cfg = Config(self.params) if self.params else Config({})
        obj = self.objective
        k = self.num_tree_per_iteration
        n = X.shape[0]
        scores = np.zeros((k, n))
        if obj is not None:
            import jax.numpy as jnp
            from .dataset import Metadata
            md = Metadata(n)
            md.set_label(label)
            obj.init(md, n)
        for i, t in enumerate(new_booster.models):
            tid = i % k
            leaves = t.predict_leaf_index(X)
            if obj is not None:
                g, h = obj.get_gradients(jnp.asarray(scores, jnp.float32))
                g, h = np.asarray(g), np.asarray(h)
            else:
                g = scores - label[None, :]
                h = np.ones_like(g)
            for leaf in range(t.num_leaves):
                rows = leaves == leaf
                if rows.any():
                    sum_g = g[tid, rows].sum()
                    sum_h = h[tid, rows].sum()
                    new_out = -sum_g / (sum_h + cfg.lambda_l2) \
                        * t.shrinkage if sum_h > 0 else 0.0
                    t.leaf_value[leaf] = (decay_rate * t.leaf_value[leaf]
                                          + (1.0 - decay_rate) * new_out)
            scores[tid] += t.predict_rows(X)
        return new_booster

    def reset_training_data(self, train_set: "Dataset") -> "Booster":
        """Attach (or replace) training data on an existing model
        (ref: c_api.cpp:1631 LGBM_BoosterResetTrainingData ->
        gbdt.cpp:686 GBDT::ResetTrainingData): previously loaded/merged
        trees become the init segment (scores NOT replayed, matching the
        reference's iter_-only replay loop), while trees trained in this
        booster's own lifetime are kept trainable and their scores are
        replayed on the new data. New data must share bin mappers with
        the old (CheckAlign)."""
        self._drain()
        old_models = list(self.models) if self.models else []
        old_g = getattr(self, "_gbdt", None)
        post = []              # (host, device) trees trained post-init
        init_models = old_models
        if old_g is not None:
            k = max(1, old_g.num_tree_per_iteration)
            n_init = old_g.num_init_iteration * k
            init_models = old_models[:n_init]
            post = list(zip(old_g.models[n_init:],
                            old_g.device_trees[n_init:]))
            train_set.construct()
            if self.train_set is not None \
                    and train_set is not self.train_set \
                    and train_set._inner.feature_infos() \
                    != self.train_set._inner.feature_infos():
                raise ValueError(
                    "Cannot reset training data, since new training data "
                    "has different bin mappers")
        # a model-file/string booster carries its objective in the header,
        # not in params — restore name AND sub-parameters ("binary
        # sigmoid:2" -> objective=binary, sigmoid=2) so _init_train
        # rebuilds the same one
        if "objective" not in self.params \
                and getattr(self, "_objective_str", None):
            toks = self._objective_str.split()
            self.params["objective"] = toks[0]
            for t in toks[1:]:
                if ":" in t:
                    k, v = t.split(":", 1)
                    self.params.setdefault(k, v)
        if self.num_class > 1:
            self.params.setdefault("num_class", self.num_class)
        self._init_train(train_set)
        g = self._gbdt
        if init_models:
            g.adopt_init_models(init_models)
        # post-init trees: keep trainable, replay scores on the new data
        # (binned thresholds stay valid under the CheckAlign contract)
        for idx, (ht, dt) in enumerate(post):
            tid = idx % g.num_tree_per_iteration
            g.models.append(ht)
            g.device_trees.append(dt)
            g.scores = g._add_tree_to_score(g.scores, g.bins_dev, dt, tid,
                                            bundle=g._train_bundle())
        g.iter = len(post) // max(1, g.num_tree_per_iteration)
        self.models = g.models
        self._model_version += 1
        return self

    def refit_by_leaf_preds(self, leaf_preds: np.ndarray) -> "Booster":
        """In-place leaf-value refit from a precomputed leaf-assignment
        matrix (ref: c_api.cpp:1665 LGBM_BoosterRefit -> gbdt.cpp:287
        RefitTree). Needs live training data — load the model, then
        reset_training_data() first."""
        if getattr(self, "_gbdt", None) is None:
            raise ValueError(
                "BoosterRefit needs training data; call "
                "reset_training_data()/LGBM_BoosterResetTrainingData first")
        self._gbdt.refit_by_leaf_preds(
            np.asarray(leaf_preds, np.int32).reshape(
                self._gbdt.num_data, -1))
        self._model_version += 1
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        model_str = self.model_to_string(num_iteration=-1)
        booster = Booster(model_str=model_str)
        booster.params = dict(self.params)
        return booster
