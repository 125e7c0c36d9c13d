"""GBDT training driver + DART / GOSS / RF variants.

TPU-native analog of the reference boosting layer (ref: src/boosting/gbdt.cpp,
dart.hpp, goss.hpp, rf.hpp).  Orchestration (per-iteration bookkeeping, model
list, bagging index logic, early stopping) runs on host; all O(num_data) math
— gradients, histograms, tree growth, score updates — runs jit-compiled on
device.  Semantics follow gbdt.cpp:371 TrainOneIter:

    boost-from-average -> gradients -> bagging -> per-class tree train ->
    renew leaf outputs -> shrinkage -> score update -> (bias on first iter)
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import TpuDataset
from ..models.learner import FeatureMeta, grow_tree_depthwise, grow_tree_leafwise
from ..models.tree import HostTree, TreeArrays
from ..obs import Telemetry
from ..ops.predict import add_tree_score
from ..ops.split import SplitParams, calculate_leaf_output
from ..utils import log
from ..parallel.mesh import donate_argnums as _donate
from ..parallel.mesh import shard_map as _shard_map
from ..utils.timer import global_timer as timer
from ..utils import random as ref_random

K_EPSILON = 1e-15


@jax.jit
def _count_nonfinite(grad, hess):
    """NaN/Inf element counts for the numerical guards (one fused
    reduction; on sharded inputs the replicated scalars come back to
    every rank, so the guard works unchanged under multi-process)."""
    return (jnp.sum(~jnp.isfinite(grad)), jnp.sum(~jnp.isfinite(hess)))


class _SecHandle:
    """Late-bound sync target for a timed section: the arrays to block
    on are produced INSIDE the section body (``with self._sec(..) as s:
    ...; s.sync(tree)``), so the handle carries them to section exit —
    the honest-attribution idiom timer.section(sync=...) can't express
    for values that don't exist yet."""

    __slots__ = ("_sync",)

    def __init__(self):
        self._sync = None

    def sync(self, arrays) -> None:
        self._sync = arrays


class _NullSecHandle:
    """Disabled-path handle: sync() must NOT store its argument — a
    module-level global retaining the last score matrix would pin its
    device buffer for the process lifetime."""

    __slots__ = ()

    def sync(self, arrays) -> None:
        pass


# shared no-op handle: zero per-section allocation when telemetry and
# the TIMETAG timer are both off
_NULL_SEC = _NullSecHandle()
# in place of a ``first_call`` span where a step's signature is not new
_NO_SPAN = contextlib.nullcontext()


def feature_meta_from_dataset(ds: TpuDataset) -> FeatureMeta:
    default_bins = np.array([ds.mappers[j].default_bin for j in
                             ds.used_features], np.int32)
    if ds.monotone_constraints is not None:
        mono = ds.monotone_constraints[ds.used_features].astype(np.int32)
    else:
        mono = np.zeros(ds.num_features, np.int32)
    return FeatureMeta(
        num_bin=jnp.asarray(ds.num_bin_per_feat),
        missing_type=jnp.asarray(ds.missing_types),
        default_bin=jnp.asarray(default_bins),
        monotone=jnp.asarray(mono),
        # already per-USED-feature (unlike monotone_constraints, which the
        # user supplies per original column)
        is_cat=jnp.asarray(ds.is_categorical))


def split_params_from_config(config: Config) -> SplitParams:
    return SplitParams(
        lambda_l1=float(config.lambda_l1),
        lambda_l2=float(config.lambda_l2),
        max_delta_step=float(config.max_delta_step),
        min_data_in_leaf=int(config.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
        min_gain_to_split=float(config.min_gain_to_split),
        path_smooth=float(config.path_smooth),
        monotone_penalty=float(config.monotone_penalty),
        max_cat_to_onehot=int(config.max_cat_to_onehot),
        max_cat_threshold=int(config.max_cat_threshold),
        cat_l2=float(config.cat_l2),
        cat_smooth=float(config.cat_smooth),
        min_data_per_group=int(config.min_data_per_group),
        cegb_tradeoff=float(config.cegb_tradeoff),
        cegb_penalty_split=float(config.cegb_penalty_split))


class _DeviceTree:
    """Per-model device arrays for score updates/re-routing (DART)."""

    __slots__ = ("leaf_value", "split_feature", "threshold_bin",
                 "default_left", "left_child", "right_child", "max_depth",
                 "num_leaves", "cat_flag", "cat_mask")

    def __init__(self, host_tree: HostTree, inner_feature: np.ndarray,
                 cat_flag: np.ndarray = None, cat_mask: np.ndarray = None):
        self.num_leaves = host_tree.num_leaves
        self.max_depth = (int(host_tree.leaf_depth.max())
                          if getattr(host_tree, "leaf_depth", None) is not None
                          and len(host_tree.leaf_depth) else
                          max(1, host_tree.num_leaves - 1))
        self.leaf_value = jnp.asarray(host_tree.leaf_value, jnp.float32)
        self.split_feature = jnp.asarray(inner_feature, jnp.int32)
        self.threshold_bin = jnp.asarray(host_tree.threshold_bin, jnp.int32)
        self.default_left = jnp.asarray(
            (host_tree.decision_type & 2).astype(bool))
        self.left_child = jnp.asarray(host_tree.left_child, jnp.int32)
        self.right_child = jnp.asarray(host_tree.right_child, jnp.int32)
        # binned-space categorical decisions for on-device valid routing
        if cat_flag is not None and np.any(cat_flag):
            self.cat_flag = jnp.asarray(cat_flag.astype(bool))
            self.cat_mask = jnp.asarray(cat_mask.astype(bool))
        else:
            self.cat_flag = None
            self.cat_mask = None


def _round_up_pow2(n: int) -> int:
    return 1 << max(1, (n - 1).bit_length())


def _fused_layout_T(rows_dev: jax.Array, Fp: int, Rp: int, dtype,
                    feat_order=None) -> jax.Array:
    """[R, n_cols] device bin matrix -> the level kernels' [Fp, Rp]
    layout: transposed, cast, the feature rows permuted into width-class
    order under the adaptive layout (the logical order is recovered at
    plane decode), zero-padded. The ONE builder of the training matrix
    and of every validation set's passenger matrix (GBDT._valid_route),
    so the route tables written over the one read the other."""
    src = rows_dev.T.astype(dtype)
    if feat_order is not None:
        src = jnp.take(src, jnp.asarray(feat_order, jnp.int32), axis=0)
    n_cols, R = src.shape
    return jnp.zeros((Fp, Rp), dtype).at[:n_cols, :R].set(src)


def _screening_mask_fn(ema: jax.Array, explore, F: int,
                       keep_k: int) -> jax.Array:
    """EMA-FS screening mask [F_oh]: keep the top ``keep_k`` REAL
    features by gain EMA (ties kept), or everything on an exploration
    round.  Pure/traced — shared by the sync driver's cached mask and
    the fast paths' in-scan mask so the two cannot drift.  A dataset
    whose features were all pre-filtered (F == 0 — e.g. a
    min_data_in_leaf past the row count) has nothing to screen."""
    if F <= 0 or keep_k >= F:
        return jnp.ones(ema.shape, bool)
    kth = jnp.sort(ema[:F])[F - keep_k]
    return (ema >= kth) | explore


def _tree_gain_vec(split_feature: jax.Array, split_gain: jax.Array,
                   F_oh: int) -> jax.Array:
    """Realized per-feature split gains of one iteration's trees
    ([k, L-1] or [L-1] node arrays) — what feeds the gain EMA.  The
    frontier grower materializes split_gain per node; unused nodes
    carry feature -1 / gain 0 and contribute nothing."""
    sf = split_feature.reshape(-1)
    sg = split_gain.reshape(-1).astype(jnp.float32)
    ok = (sf >= 0) & jnp.isfinite(sg) & (sg > 0)
    return jnp.zeros((F_oh,), jnp.float32) \
        .at[jnp.clip(sf, 0, F_oh - 1)].add(jnp.where(ok, sg, 0.0))


class GBDT:
    """Gradient Boosting Decision Tree driver (ref: src/boosting/gbdt.h:35)."""

    name = "gbdt"

    def __init__(self):
        self.config: Optional[Config] = None
        self.train_data: Optional[TpuDataset] = None
        self.objective = None
        self.models: List[HostTree] = []
        self.device_trees: List[_DeviceTree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.average_output = False
        self._last_cat = None  # host cat arrays from the latest _to_host_tree
        # async pipeline state (see _train_one_iter_fast): device trees not
        # yet materialised as HostTrees, scores checkpoint for stop rollback.
        # Entries are (stacked TreeArrays, [init_scores per iteration],
        # batch, metrics) — batch > 1 for megastep entries ([B, k, ...]
        # arrays); metrics is the scan's [B, n_slots] on-device eval
        # matrix when a drain-replay consumer is armed, else None.
        self._pending: List[Tuple] = []
        self._pending_iters = 0
        # per pending sampled step (GOSS), the traced (top, other) row
        # counts of its iterations; fetched with the trees at the drain
        self._sample_counts: List = []
        self._goss_layout_said = False
        self._fast_step_fns = {}
        self._fast_ok_cache = None
        self._sample_plan_cache = None
        self._stopped_early = False
        # multi-iteration megastep state (see _train_one_megastep): armed
        # only by driver loops that tolerate train_one_iter advancing
        # more than one iteration per call
        self._megastep_armed = False
        self._megastep_fns: Dict[int, object] = {}
        self._megastep_fm: Dict[int, object] = {}
        # on-device eval inside the megastep (metric/traced.py): the
        # drain-replay consumer a driver loop registered via
        # arm_megastep(eval_consumer=...), the traced eval plan built by
        # megastep_eval_precheck, its cached operand pytree, the
        # device-resident early-stop carry (best metric / best round /
        # stopped flag / stop iteration threaded through the scan), and
        # the host-side "early stop confirmed at drain" latch
        self._eval_consumer = None
        self._traced_plan = None
        self._plan_ops = None
        self._es_spec = None
        self._es_carry = None
        self._es_finished = False
        # megastep_evicted dedup: one structured event per distinct
        # eviction reason, not one per iteration
        self._evict_reported = set()
        # batch-granularity telemetry window: wall/perf stamps of the
        # first dispatch since the last drain, and how many of the
        # pending iterations came from fused megastep chunks
        self._batch_t0 = None
        self._batch_w0 = None
        self._batch_fused = 0
        # histogram-plane cuts (ROADMAP item 4): quantized gradient
        # histograms, adaptive per-feature bins, EMA-FS gain screening
        self.quant_bits = 0
        self.use_adaptive_bins = False
        self.use_screening = False
        self.fused_packed = None
        self._gain_ema_dev = None      # [F_oh] f32 gain EMA (screening)
        self._iter_gain_acc = None     # sync driver: per-iteration gains
        self._screen_mask_cache = None
        self._hist_stats = None
        # distribution axis (ref: tree_learner.cpp:17-49 factory matrix)
        self.parallel_mode = "serial"
        self.mesh = None
        self.n_shards = 1
        self.axis_name = None
        self._par_fns: Dict[str, object] = {}
        # measured in-trace collective profiles (ops/collectives.py):
        # (count, bytes) recorded from the traced static shapes at the
        # first call of each fresh grower jit — per fused iteration
        # (fast step / megastep scan body, k trees) and per sync-driver
        # grow call (one tree)
        self._coll_per_iter = None
        self._coll_per_grow = None
        # telemetry registry (obs/): disabled by default — every record
        # call is a single attribute check until telemetry_out or
        # record_telemetry enables it
        self.telemetry = Telemetry()
        self._health = None
        self._metrics = None           # live OpenMetrics exporter
        self._mem_watermarks = True
        self._tel_gran = "batch"
        self._trace_out = ""
        self._trace_written = False
        self._prof_dir = ""
        self._prof_start = 0
        self._prof_n = -1
        self._prof_active = False
        self._prof_opened_at = 0
        self._prof_done = False
        # on-demand profiling control plane (POST /profile on the
        # metrics exporter): the armed-request handoff and the open
        # window's bookkeeping ({dir, it0, iters}); windows open/close
        # only at drain boundaries / iteration edges, so an armed-but-
        # idle endpoint is dispatch-neutral by construction
        self._profile_ctl = None
        self._ctl_window = None
        self._ctl_no_open = False
        # SLO plane (obs/slo.py): declarative objectives evaluated on a
        # host-side ticker plus at the same drain-boundary sync points
        # the profile control polls — dispatch-neutral by the same
        # construction
        self._slo = None
        # device-time cost ledger (obs/cost.py): fresh executable
        # signatures queue here at dispatch, analyses run at drains
        self._cost = None
        self._run_report_out = ""
        # resilience (resilience/): async checkpoint manager, cadence
        # bookkeeping, the engine's extra-state hook (callback closures'
        # early-stop state rides the checkpoint), fault registry
        self._ckpt = None
        self._ckpt_period = 0
        self._last_ckpt_iter = 0
        self._ckpt_busy = False
        self._ckpt_extra = None
        self._faults = None

    # ------------------------------------------------------------------
    def init(self, config: Config, train_data: TpuDataset, objective,
             training_metrics: Sequence = ()) -> None:
        self.config = config
        self.train_data = train_data
        self.objective = objective
        from ..utils import platform
        if config.compilation_cache_dir:   # before the first trace
            platform.compilation_cache_dir(
                str(config.compilation_cache_dir))
        # the fused/Pallas paths are the TPU throughput modes; leafwise is
        # the exact reference-parity mode (and the CPU test default)
        self.on_tpu = platform.on_tpu()
        self._setup_telemetry(config)
        self._setup_resilience(config)
        tel = self.telemetry
        # the Dataset was binned before this registry existed
        tel.publish_spans(getattr(train_data, "setup_spans", ()))
        with tel.timed("init/meta"):
            self.training_metrics = list(training_metrics)
            self.num_data = train_data.num_data
            self.num_tree_per_iteration = (objective.num_model_per_iteration
                                           if objective is not None else
                                           max(1, int(config.num_class)))
            self.shrinkage_rate = float(config.learning_rate)
            self.max_leaves = max(2, int(config.num_leaves))
            # static padded bin count shared by all jit instances
            self.max_bins = int(train_data.max_num_bin)
            self.params = split_params_from_config(config)
            self.meta = feature_meta_from_dataset(train_data)
            self.has_cat = bool(np.any(train_data.is_categorical))
            self.use_mono_bounds = bool(np.any(np.asarray(self.meta.monotone)
                                               != 0))
            self._setup_cegb(config)
            self._setup_forced_splits(config, train_data)
            self._setup_bundles(config, train_data)
            # NOTE: computed before _setup_engine, which reads them
            ic = config.interaction_constraints
            bynode = float(config.feature_fraction_bynode)
            self.use_node_masks = bool(ic) or (0.0 < bynode < 1.0)
            self.node_masks = None
            if self.use_node_masks:
                from ..models.learner import make_node_mask_cfg
                # constraints are in REAL feature indices; map to inner
                inner_ic = []
                for g in (ic or []):
                    gi = [train_data.inner_feature_index(int(f)) for f in g]
                    inner_ic.append([f for f in gi if f >= 0])
                self.node_masks = make_node_mask_cfg(
                    train_data.num_features, inner_ic, bynode,
                    int(config.feature_fraction_seed) + 12345)
            # lazy: the parallel XLA path holds a SHARDED copy (bins_par) and
            # only rollback/stop-subtract/DART replay need this replicated one
            self._bins_dev = None
            self._setup_parallel(config)
        self._setup_engine(config)

        with tel.timed("init/state"):
            md = self._mp_metadata if self.mp is not None else train_data.metadata
            k, n = self.num_tree_per_iteration, self.num_data
            self.has_init_score = md.init_score is not None
            from jax.sharding import PartitionSpec as P
            if self.has_init_score:
                init = np.asarray(md.init_score, np.float64)
                if init.size == n * k:
                    scores = init.reshape(k, n, order="C")
                else:
                    scores = np.tile(init.reshape(1, n), (k, 1))
                self.scores = (self.mp.shard_full(scores.astype(np.float32),
                                                  P(None, self.axis_name))
                               if self.mp is not None
                               else jnp.asarray(scores, jnp.float32))
            elif self.mp is not None:
                self.scores = self.mp.zeros_sharded((k, n),
                                                    P(None, self.axis_name))
            else:
                self.scores = jnp.zeros((k, n), jnp.float32)

            self.valid_data: List[TpuDataset] = []
            self.valid_bins: List = []
            self._valid_routes: Dict[int, Tuple] = {}   # see _valid_route
            self._valid_exact_dev: Dict[int, Tuple] = {}  # _valid_exact
            self._valid_route_said: set = set()
            self._route_form_said: set = set()          # see _route_form
            self.valid_scores: List = []
            self.valid_metrics: List[List] = []
            self.valid_names: List[str] = []

            self.class_need_train = [
                objective.class_need_train(i) if objective is not None else True
                for i in range(self.num_tree_per_iteration)]

            # bagging state (ref: gbdt.cpp:686-758 ResetBaggingConfig)
            # reference-parity streams (ref: utils/random.h LCG; gbdt.cpp:804
            # per-block bagging generators; col_sampler.hpp:26 by-tree stream)
            self.bag_streams = ref_random.BlockBaggingStreams(
                int(config.bagging_seed), n)
            self._bag_round_cache = None
            self.feat_rng = ref_random.Random(int(config.feature_fraction_seed))
            self.balanced_bagging = False
            self.is_bagging = False
            if config.bagging_freq > 0:
                if config.bagging_fraction < 1.0:
                    self.is_bagging = True
                elif (self.objective is not None
                      and self.objective.name == "binary"
                      and (config.pos_bagging_fraction < 1.0
                           or config.neg_bagging_fraction < 1.0)):
                    self.is_bagging = True
                    self.balanced_bagging = True
            self.bag_weight = self._bag_ones()  # 1=in bag (mp: 0 on pad rows)
            self.bag_cnt = n

            self.best_score: Dict[Tuple[int, str], float] = {}
            self.best_iter: Dict[Tuple[int, str], int] = {}
            self.early_stopping_round = int(config.early_stopping_round)
            self.es_first_metric_only = bool(config.first_metric_only)
            self._rank_layout_said = False
            self._publish_rank_layout()

    def _publish_rank_layout(self) -> None:
        """A ranking objective's query layout, once per run: the exact
        ``rank.*`` counters (from shapes) and a ``rank_layout`` event with
        the bucket table the gradient's planes are built from."""
        layout = getattr(self.objective, "planes", None)
        tel = self.telemetry
        if layout is None or self._rank_layout_said or not tel.enabled:
            return
        self._rank_layout_said = True
        tel.inc("rank.queries", layout.num_queries)
        tel.inc("rank.max_docs", layout.max_docs)
        pairs = getattr(self.objective, "pairs_per_iter", None)
        if pairs is not None:
            tel.inc("rank.pairs_per_iter", pairs)
        tel.event("rank_layout", iteration=self.iter,
                  objective=self.objective.name, queries=layout.num_queries,
                  max_docs=layout.max_docs, rows=layout.rows,
                  padded_rows=layout.padded_rows,
                  buckets=[list(b) for b in zip(layout.widths,
                                                layout.queries,
                                                layout.capacity)],
                  **({"pairs_per_iter": pairs} if pairs is not None
                     else {}))


    @property
    def bins_dev(self):
        if self._bins_dev is None:
            self._bins_dev = self._dataset_bins_to_device(self.train_data)
        return self._bins_dev

    def _dataset_bins_to_device(self, ds, span: str = "init/upload"):
        """Host->device transfer of a dataset's bin matrix, under the
        span ``span`` (closed when the copy has landed).  Streamed /
        mmap-cached datasets (ingest/) go through the double-buffered
        chunk prefetcher — the next chunk's host read (page faults on a
        cache mmap) overlaps the in-flight copy, at most two chunks
        live host-side, and the counters/watermarks land in telemetry —
        instead of faulting the whole artifact into RAM for one giant
        ``jnp.asarray``.  The result is elementwise-identical either
        way (prefetch is a transfer schedule, not a data transform)."""
        tel = self.telemetry
        with tel.timed(span, rows=int(ds.bins.shape[0]),
                       bytes=int(ds.bins.nbytes)) as sp:
            if getattr(ds, "streamed", False) \
                    and bool(getattr(self.config, "ingest_prefetch", True)):
                from ..ingest.prefetch import stream_to_device
                out = stream_to_device(
                    ds.bins, int(self.config.ingest_chunk_rows), tel=tel)
                if tel.enabled and getattr(self, "_mem_watermarks", False):
                    # the prefetch assembly is where a streamed dataset's
                    # HBM residency materializes — watermark it like the
                    # drain boundary
                    from ..obs.jaxmon import memory_watermarks
                    memory_watermarks(tel, where="prefetch")
            else:
                out = jnp.asarray(ds.bins)
            sp.sync(out)
        return out

    def _publish_ingest(self, ds) -> None:
        """Fold a dataset's ingest counters (chunked parse/bin stats,
        cache hit, max-live-chunks watermark) into the telemetry
        registry — ingest runs before the booster owns a registry, so
        the stats ride the dataset and land here exactly once."""
        stats = getattr(ds, "ingest_stats", None)
        if not stats or getattr(ds, "_ingest_published", False) \
                or not self.telemetry.enabled:
            return
        from ..ingest.prefetch import publish_ingest_stats
        publish_ingest_stats(self.telemetry, stats)
        ds._ingest_published = True

    # ------------------------------------------------------------------
    def _setup_telemetry(self, config: Config) -> None:
        """Telemetry registry + profiler window from the config (re-run
        by reset_config so reset_parameter can turn either on). Runs
        FIRST in init so mode/engine degradation events route through
        the registry."""
        tel = self.telemetry
        out = str(getattr(config, "telemetry_out", "") or "")
        self._trace_out = str(getattr(config, "trace_out", "") or "")
        period = int(getattr(config, "health_check_period", 0) or 0)
        metrics_port = int(getattr(config, "metrics_port", 0) or 0)
        self._mem_watermarks = bool(getattr(config, "memory_watermarks",
                                            True))
        self._run_report_out = str(getattr(config, "run_report_out", "")
                                   or "")
        if out or self._trace_out or period > 0 or metrics_port > 0 \
                or self._run_report_out:
            # enable() attaches the sink even when the registry is
            # already on sink-less (record_telemetry first, then
            # reset_parameter(telemetry_out=...) must still get a file);
            # it reports whether THIS call attached a new sink, so the
            # enablement event fires once per stream, and trace_out /
            # health_check_period enable the registry sink-less
            newly_attached = tel.enable(sink_path=out or None,
                                        trace=bool(self._trace_out))
            if newly_attached:
                tel.event("telemetry_enabled", sink=out)
        elif tel.enabled:
            # every observability key cleared on an already-enabled
            # registry (reset_parameter round trip): span collection
            # must stop too, or each section keeps paying the append
            # with no exporter left to drain it
            tel.enable(trace=False)
        # live OpenMetrics endpoint (obs/export.py): one exporter per
        # booster at metrics_port + rank; a config reset that keeps the
        # same port keeps the running server (re-binding would drop a
        # scraper mid-run), any other change stops the old one first.
        # The exporter outlives finalize_telemetry deliberately — "live"
        # means scrapeable for as long as the process holds the booster.
        want_port = metrics_port + tel.rank if metrics_port > 0 else 0
        if self._metrics is not None and (
                want_port <= 0
                or self._metrics.requested_port != want_port):
            self._metrics.stop()
            self._metrics = None
        if want_port > 0 and self._metrics is None:
            from ..obs.export import MetricsExporter, ProfileControl
            if self._profile_ctl is None:
                self._profile_ctl = ProfileControl()
                # overlap refusal extends to the config-keyed window: a
                # pending/active profile_dir trace owns the profiler
                self._profile_ctl.conflict_check = (
                    lambda: "config:profile_dir window pending"
                    if (self._prof_active
                        or (self._prof_dir and not self._prof_done))
                    else None)
            self._metrics = MetricsExporter(
                tel, want_port, profile_control=self._profile_ctl,
                report_fn=self.build_run_report,
                roofline_fn=lambda: getattr(self, "_roofline_last",
                                            None))
            if self._metrics.start() < 0:
                # total bind failure (not the in-use fallback): drop
                # the dead exporter so a later reset_parameter round
                # trip RETRIES the bind instead of matching
                # requested_port against a server that never existed
                self._metrics = None
        self._health = None
        if period > 0:
            from ..obs.health import HealthAuditor
            self._health = HealthAuditor(
                tel, period,
                float(getattr(config, "health_skew_threshold", 2.0)),
                resync_fn=self._health_resync,
                auto_resync=bool(getattr(config, "health_auto_resync",
                                         True)),
                checkpoint_fn=lambda it: self.maybe_checkpoint(force=True),
                straggler_checkpoint=bool(getattr(
                    config, "health_checkpoint_on_straggler", False)))
        self._prof_dir = str(getattr(config, "profile_dir", "") or "")
        self._prof_start = max(
            0, int(getattr(config, "profile_start_iteration", 0)))
        self._prof_n = int(getattr(config, "profile_num_iterations", -1))
        gran = str(getattr(config, "telemetry_granularity", "batch")
                   or "batch")
        if gran not in ("batch", "iteration", "section"):
            log.warning("unknown telemetry_granularity=%s; using batch",
                        gran)
            gran = "batch"
        self._tel_gran = gran
        # device-time cost ledger: one per registry lifetime (keeps the
        # analyzed-signature dedup across reset_parameter round trips);
        # mode changes re-derive it
        cost_mode = str(getattr(config, "cost_ledger", "hlo") or "hlo")
        if not tel.enabled or cost_mode == "off":
            self._cost = None
        elif self._cost is None or self._cost.mode != cost_mode:
            from ..obs.cost import CostLedger
            self._cost = CostLedger(tel, cost_mode)
        # roofline plane (obs/kernelstats.py): measured samples from
        # every closed profile window accumulate in the shape-keyed
        # perf database when perf_db is set (obs/perfdb.py)
        self._perf_db_path = str(getattr(config, "perf_db", "") or "")
        # SLO plane (obs/slo.py): one engine per registry lifetime,
        # rebuilt when a reset_config changes the arming keys.  The
        # engine only reads host-side snapshots — arming it is
        # dispatch-neutral exactly like the profile control.
        slo_cfg = str(getattr(config, "slo_config", "") or "")
        slo_on = bool(getattr(config, "slo_enabled", False)) or bool(slo_cfg)
        if self._slo is not None:
            self._slo.stop()
            self._slo = None
        if slo_on and tel.enabled:
            from ..obs.slo import SloEngine
            self._slo = SloEngine(
                tel, source="train", config_path=slo_cfg,
                tick_period_s=float(getattr(config, "slo_tick_period_s",
                                            5.0)),
                incident_base=out,
                context_fn=self._slo_context)
            self._slo.start()
        if self._metrics is not None:
            self._metrics.alerts_fn = (self._slo.alerts_payload
                                       if self._slo is not None else None)
        # streamed/cached datasets carry their ingest counters from
        # before the registry existed; fold them in now (init and any
        # reset_config that turns telemetry on)
        if getattr(self, "train_data", None) is not None:
            self._publish_ingest(self.train_data)
            for vd in getattr(self, "valid_data", []) or []:
                self._publish_ingest(vd)

    def _slo_context(self):
        """Incident-artifact context: where training stood when the
        alert fired (host attribute reads only)."""
        return {
            "iteration": int(getattr(self, "iter", 0)),
            "models": len(getattr(self, "models", []) or []),
            "last_checkpoint_iter": int(self._last_ckpt_iter),
        }

    def _slo_step(self) -> None:
        """Heartbeat + time-gated SLO evaluation at the drain-boundary
        sync points the driver already owns (same contract as
        _profile_ctl_step: host flags only, no dispatch)."""
        slo = self._slo
        if slo is None:
            return
        slo.note_training_heartbeat(self.iter)
        slo.step()

    def _tel_granularity(self) -> str:
        """Effective time-attribution granularity. trace_out (spans come
        from synced sections) and the health auditor (needs the sync
        driver's per-iteration records) imply 'section' regardless of the
        configured value — EXCEPT under the multi-chip megastep, where
        the health audit moves to drain boundaries (_health_at_drain)
        instead of evicting the one configuration that needs dispatch
        amortization most."""
        if self._trace_out:
            return "section"
        if self._health is not None and not self._health_at_drain():
            return "section"
        return self._tel_gran

    def _health_at_drain(self) -> bool:
        """Multi-process fused runs audit at drain boundaries: the model
        list and score carries are host-synced there already, so the
        hash allgather costs zero extra dispatches and the megastep
        keeps its 1-dispatch-per-chunk contract (section times are not
        collected on the fast path, so the straggler skew check reads
        empty sections — drain wall times still land in the batch
        record). The sync drivers (XLA growers, non-batch granularity)
        keep the per-iteration audit with real section times."""
        return (getattr(self, "mp", None) is not None
                and getattr(self, "use_fused", False)
                and bool(getattr(self.config, "tpu_mp_megastep", True))
                and self._tel_gran == "batch")

    @contextlib.contextmanager
    def _sec(self, name: str):
        """Dual-sink timed section: one measurement feeds both the
        TIMETAG global timer (as GBDT::<name>) and the telemetry
        registry's per-iteration record. Yields a handle whose
        ``sync(arrays)`` blocks before the section closes, attributing
        asynchronous device work honestly (the timer.section(sync=...)
        idiom, late-bound). No-op when both sinks are off."""
        tel = self.telemetry
        timing = timer.enabled
        if not (tel.enabled or timing):
            yield _NULL_SEC
            return
        h = _SecHandle()
        tel.push_section(name)   # crash flight recorder's "where"
        w0 = tel.wall_now()
        t0 = time.perf_counter()
        # everything below the yield runs on CLEAN exit only: an
        # exception must leave the section on the stack so the crash
        # flight recorder can dump where training was (a finally-pop
        # would erase the evidence during unwind)
        yield h
        if h._sync is not None:
            jax.block_until_ready(h._sync)
        dt = time.perf_counter() - t0
        tel.pop_section()
        if timing:
            timer.add("GBDT::" + name, dt)
        if tel.enabled:
            tel.section(name, dt, wall_start=w0)

    def _profiler_step(self) -> None:
        """Open/close the jax.profiler trace window at iteration edges
        (profile_dir + profile_start_iteration + profile_num_iterations:
        a TensorBoard/Perfetto trace of iterations K..K+n is one config
        key away)."""
        self._profile_ctl_step()
        self._slo_step()
        self._profiler_window(1)

    def _profiler_window(self, ahead: int) -> None:
        """The config-keyed window at a dispatch edge; ``ahead`` is the
        number of iterations the coming dispatch covers. Under the
        megastep the edges are chunk boundaries, so the window snaps
        OUTWARD to them: it opens before the chunk that holds
        profile_start_iteration and closes at the first boundary at or
        after the last requested iteration. The start/stop events carry
        the iterations actually covered."""
        if self._prof_done or not self._prof_dir \
                or self._ctl_window is not None:
            return
        it = self.iter
        if not self._prof_active:
            if it + ahead > self._prof_start:
                try:
                    jax.block_until_ready(self.scores)
                    jax.profiler.start_trace(self._prof_dir)
                except Exception as e:
                    log.warning("profiler trace failed to start: %s", e)
                    self._prof_done = True
                    return
                self._prof_active = True
                self._prof_opened_at = it
                self.telemetry.event("profiler_trace_start", iteration=it,
                                     log_dir=self._prof_dir)
        elif 0 <= self._prof_n <= it - self._prof_start:
            self._profiler_stop()

    def _profiler_stop(self) -> None:
        if not getattr(self, "_prof_active", False):
            return
        try:
            jax.block_until_ready(self.scores)
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("profiler trace failed to stop: %s", e)
        self._prof_active = False
        self._prof_done = True
        self.telemetry.event("profiler_trace_stop", iteration=self.iter,
                             first_iteration=self._prof_opened_at,
                             iterations=self.iter - self._prof_opened_at,
                             log_dir=self._prof_dir)
        self._roofline_capture(self._prof_dir)

    # ------------------------------------------- on-demand profile windows
    def _profile_ctl_step(self) -> None:
        """Advance the on-demand profiling state machine (POST /profile
        on the metrics exporter) at the driver's existing sync points:
        megastep drain boundaries (_drain_body tail) and iteration
        edges (_profiler_step).  An open window closes at the first
        boundary >= ``iters`` iterations after it opened; an armed
        request opens only when no device work is pending and no
        config-keyed window owns the profiler.  Everything here is host
        flag-reads and (rarely) jax.profiler start/stop — zero device
        dispatches, which is the neutrality contract the bench gates."""
        ctl = self._profile_ctl
        if ctl is None:
            return
        win = self._ctl_window
        if win is not None:
            if self.iter - win["it0"] >= win["iters"]:
                self._close_ctl_window()
            return
        if self._prof_active or self._pending \
                or getattr(self, "_ctl_no_open", False):
            # a config window owns the profiler, dispatches are in
            # flight (mid-pipeline edge), or finalize is running (no
            # later boundary would ever stop a window opened now):
            # wait for an honest boundary
            return
        req = ctl.take()
        if req is None:
            return
        if not req.get("dir"):
            # default trace dir minted only now, when the window really
            # opens — an armed-but-never-fired request leaks nothing
            import tempfile
            req["dir"] = tempfile.mkdtemp(prefix="lgbm_profile_")
        try:
            jax.profiler.start_trace(req["dir"])
        except Exception as e:
            log.warning("on-demand profiler window failed to start: %s",
                        e)
            self.telemetry.event("profile_window", state="failed",
                                 iteration=self.iter, dir=req["dir"],
                                 error=str(e)[:200])
            ctl.done()
            return
        self._ctl_window = {"dir": req["dir"], "it0": self.iter,
                            "iters": int(req["iters"])}
        self.telemetry.event("profile_window", state="open",
                             iteration=self.iter, dir=req["dir"],
                             iters=int(req["iters"]))

    def _close_ctl_window(self, state: str = "closed") -> None:
        win, self._ctl_window = self._ctl_window, None
        if win is None:
            return
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.warning("on-demand profiler window failed to stop: %s",
                        e)
            state = "failed"
        self.telemetry.event("profile_window", state=state,
                             iteration=self.iter, dir=win["dir"],
                             iters=win["iters"],
                             covered=self.iter - win["it0"])
        self._roofline_capture(win["dir"])
        if self._profile_ctl is not None:
            self._profile_ctl.done()

    # --------------------------------------------------- roofline plane
    def _shape_class(self) -> str:
        """Perfdb shape key: rows bucketed to the next power of two
        (padding-invariant across minor row-count jitter), feature
        count and bin budget — what determines which measured samples
        are comparable (obs/perfdb.py)."""
        rows = max(1, int(getattr(self, "num_data", 0) or 1))
        rows_p2 = 1 << (rows - 1).bit_length()
        feats = int(getattr(getattr(self, "train_data", None),
                            "num_features", 0) or 0)
        max_bin = int(getattr(self.config, "max_bin", 0) or 0)
        return f"r{rows_p2}.f{feats}.b{max_bin}"

    def _roofline_capture(self, trace_dir: str) -> None:
        """Post-window measurement hook, both window flavors
        (profile_dir config window and POST /profile): record the trace
        dir size/count gauges (an empty or truncated capture must be
        observable, not silently parsed to zero kernels), parse the
        Chrome trace via obs/kernelstats.py, join it to the cost
        ledger's analytic entries, publish the roofline gauges + one
        ``roofline`` event, and append measured samples to the perf
        database when ``perf_db`` is set.  Pure host work at a point
        the profiler already synced — zero device dispatches — and
        exception-proof: measurement must never kill training."""
        tel = self.telemetry
        if not trace_dir or not tel.enabled:
            return
        try:
            from ..obs import kernelstats
            n_files, n_bytes = kernelstats.dir_stats(trace_dir)
            tel.gauge("profile.trace_files", float(n_files))
            tel.gauge("profile.trace_bytes", float(n_bytes))
            if self._cost is not None:
                self._cost.flush()   # analyses queued since last drain
            compile_evs = [e for e in tel.snapshot().get("events", [])
                           if e.get("event") == "compile_executable"]
            roof = kernelstats.roofline_from_dir(
                trace_dir,
                cost_entries=(self._cost.entries()
                              if self._cost is not None else None),
                compile_entries=compile_evs)
            tel.gauge("roofline.join_coverage",
                      float(roof["join_coverage"]))
            tel.gauge("roofline.joined_executables",
                      float(roof["joined_executables"]))
            tel.gauge("roofline.anchor_dispatches",
                      float(roof["anchor_dispatches"]))
            # measured occupancy of the training executable's host
            # span — the measured complement to the analytic
            # cost.achieved_fraction gauge
            fracs = [r["measured_fraction"]
                     for r in roof["executables"]
                     if r["kind"] in ("megastep", "fast_step")
                     and isinstance(r.get("measured_fraction"),
                                    (int, float))]
            if fracs:
                tel.gauge("cost.measured_fraction", max(fracs))
            top = roof["kernels"][0] if roof["kernels"] else None
            tel.event(
                "roofline", iteration=self.iter, dir=trace_dir,
                join_coverage=roof["join_coverage"],
                joined_executables=roof["joined_executables"],
                anchor_dispatches=roof["anchor_dispatches"],
                total_device_time_us=roof["total_device_time_us"],
                measured_fraction=(max(fracs) if fracs else None),
                top_kernel=(top["name"] if top else None),
                top_kernel_us=(top["time_us"] if top else None),
                trace_files=roof["trace_files"],
                trace_bytes=roof["trace_bytes"],
                parse_errors=roof["parse_errors"])
            self._roofline_last = roof
            if self._perf_db_path:
                from ..obs import perfdb
                try:
                    import jax as _jax
                    backend = _jax.default_backend()
                    world = int(_jax.process_count())
                except Exception:
                    backend, world = "unknown", 1
                # packed hist layout = the feature-bin axis was padded
                # to a lane multiple (hist.fb_padded gauge > hist.fb)
                hs = getattr(self, "_hist_stats", None) or {}
                packed = bool(hs.get("fb_padded", 0) > hs.get("fb", 0))
                rows = perfdb.samples_from_roofline(
                    roof, shape_class=self._shape_class(),
                    backend=backend,
                    quant_bits=int(getattr(self, "quant_bits", 0) or 0),
                    packed_layout=packed,
                    world_size=world, source="profile_window",
                    run_id=tel.run_id)
                n = perfdb.PerfDB(self._perf_db_path).append(rows)
                tel.inc("perfdb.samples_written", n)
                tel.event("perfdb_append", path=self._perf_db_path,
                          samples=n)
        except Exception as e:   # measurement must never kill training
            log.warning("roofline capture of %s failed: %s",
                        trace_dir, e)
            tel.event("roofline", dir=trace_dir, error=str(e)[:200])

    def finalize_telemetry(self) -> None:
        """End-of-training hook: stop an open profiler trace, emit the
        summary event (per-rank counters aggregated at rank 0 under
        multi-process — SPMD: every rank calls this at the same point),
        write the consolidated run report (run_report_out), flush the
        JSONL sink."""
        # no NEW on-demand window may open past this point: the tail
        # drain below runs _profile_ctl_step at its boundary, and a
        # request taken there would open a trace with no later boundary
        # to stop it (busy forever, leaked profiler session)
        self._ctl_no_open = True
        try:
            self._finalize_telemetry_body()
        finally:
            # a kept booster can resume training (update loop after
            # engine.train finalized) — windows must re-arm then
            self._ctl_no_open = False

    def _finalize_telemetry_body(self) -> None:
        tel = self.telemetry
        if self._prof_active:
            with tel.timed("finish/profiler_stop"):
                self._profiler_stop()
        if self._ckpt is not None:
            # join the in-flight write: a checkpoint enqueued at the
            # last drain must commit before the process can exit
            with tel.timed("finish/checkpoint_wait"):
                try:
                    self._ckpt.wait()
                except Exception as e:
                    log.warning("checkpoint writer drain failed: %s", e)
        if not tel.enabled:
            self._close_ctl_window("closed_at_finalize")
            return
        with tel.timed("finish/drain"):
            self.drain_pending()
        if self._slo is not None:
            # one forced final evaluation so even a sub-tick-period run
            # gets a non-vacuous slo.ticks count, then disarm the
            # training-liveness watchdog (clean finalize is not a stall)
            # and the ticker thread
            with tel.timed("finish/slo"):
                self._slo.note_training_heartbeat(self.iter)
                self._slo.step(force=True)
                self._slo.note_training_done()
                self._slo.stop()
        # the tail drain may have closed an elapsed window at its
        # boundary; anything still open ends here, after the last
        # iterations it covered are drained
        self._close_ctl_window("closed_at_finalize")
        if self._cost is not None:
            with tel.timed("finish/cost_flush"):
                self._cost.flush()   # analyses queued since the last drain
        with tel.timed("finish/summary"):
            snap, rank_sections = self._summary_event()
        with tel.timed("finish/report"):
            self._write_run_report(snap, rank_sections)
        with tel.timed("finish/trace_export"):
            self._export_trace()
        with tel.timed("finish/flush"):
            tel.flush()

    def _summary_event(self):
        """The ``summary`` event (per-rank counters gathered at rank 0
        under multi-process); returns the snapshot it was made from and
        the per-rank report sections that rode the same allgather."""
        tel = self.telemetry
        snap = tel.snapshot()
        rank_sections = None
        if getattr(self, "mp", None) is not None:
            from ..obs import allgather_json
            from ..obs import report as report_mod
            # ONE allgather carries both the summary counters and the
            # compact per-rank report section (zero new collectives —
            # the payload just grew)
            per_rank = allgather_json({
                "rank": snap["rank"], "counters": snap["counters"],
                "report_section": report_mod.rank_section(
                    snap, snap["rank"],
                    evicted=self._evicted_snapshot())})
            rank_sections = [p.get("report_section") for p in per_rank
                             if isinstance(p.get("report_section"), dict)]
            if tel.rank == 0:
                tel.event("summary", iteration=self.iter,
                          counters=snap["counters"],
                          timings=snap["timings"],
                          ranks=[{k: p.get(k)
                                  for k in ("rank", "counters")}
                                 for p in per_rank])
        else:
            tel.event("summary", iteration=self.iter,
                      counters=snap["counters"],
                      timings=snap["timings"])
        return snap, rank_sections

    # -------------------------------------------------------- run report
    def _evicted_snapshot(self):
        """Race-tolerant copy of the eviction-reason set: GET /report is
        served from the exporter's HTTP threads WHILE training mutates
        `_evict_reported`, and iterating a set across a concurrent add
        raises RuntimeError in CPython.  The set only ever grows (a few
        entries per run), so a short retry converges immediately."""
        for _ in range(8):
            try:
                return sorted(self._evict_reported)
            except RuntimeError:
                continue
        return []

    def build_run_report(self, snapshot=None, rank_sections=None):
        """Consolidated run report (obs/report.py) from the LIVE
        registry — the exporter's GET /report source and the
        run_report_out artifact builder."""
        from ..obs import report as report_mod
        tel = self.telemetry
        try:
            import jax as _jax
            world = int(_jax.process_count())
        except Exception:
            world = 1
        extra = None
        prov = getattr(self, "provenance", None)
        if prov is not None:
            # lineage section: the training run's provenance record
            # (run_id, source fingerprint, parent checkpoint, profile
            # digest) — the training end of the rollover chain
            extra = {"lineage": {"training": dict(prov)}}
        return report_mod.build_report(
            snapshot if snapshot is not None else tel.snapshot(),
            run_id=tel.run_id, rank=tel.rank, world_size=world,
            evicted=self._evicted_snapshot(),
            cost_entries=self._cost.entries() if self._cost else None,
            roofline=getattr(self, "_roofline_last", None),
            extra=extra, ranks=rank_sections)

    def _write_run_report(self, snap, rank_sections) -> None:
        """Write run_report.json (+ .md) at finalize.  Multi-process:
        rank 0 writes the aggregated report (per-rank sections rode the
        finalize allgather); other ranks write nothing — one artifact
        per run, like the merged trace."""
        out = self._run_report_out
        if not out or self.telemetry.rank != 0:
            return
        from ..obs import report as report_mod
        try:
            report = self.build_run_report(snap, rank_sections)
            report_mod.write_report(out, report)
        except Exception as e:   # the report must never kill finalize
            log.warning("run report write to %s failed: %s", out, e)
            return
        self.telemetry.event("run_report_written", path=out,
                             schema=report_mod.SCHEMA)
        log.info("run report written to %s", out)

    def _export_trace(self) -> None:
        """Write the Chrome-trace timeline (trace_out): drain this
        rank's spans, allgather them under multi-process (SPMD — every
        rank reaches finalize), and let rank 0 write the merged file
        with one track per rank."""
        tel = self.telemetry
        if not self._trace_out or self._trace_written:
            return
        self._trace_written = True
        # each rank ships its dropped-span count with its spans: a ring
        # overflow on ANY rank truncates that rank's track, so rank 0's
        # local counter alone cannot vouch for the merged file
        local = {"spans": tel.drain_spans(),
                 "dropped": int(tel.snapshot()["counters"].get(
                     "trace.spans_dropped", 0))}
        if getattr(self, "mp", None) is not None:
            from ..obs import allgather_json
            payloads = allgather_json(local)
        else:
            payloads = [local]
        if tel.rank != 0:
            return
        from ..obs import trace as trace_mod
        per_rank = [p["spans"] for p in payloads]
        try:
            trace_mod.write_trace(self._trace_out, per_rank)
        except Exception as e:
            log.warning("trace export to %s failed: %s",
                        self._trace_out, e)
            return
        dropped = sum(int(p.get("dropped", 0)) for p in payloads)
        tel.event("trace_written", path=self._trace_out,
                  spans=sum(len(s) for s in per_rank), dropped=dropped)
        if dropped:
            log.warning("trace span ring overflowed: %d spans were "
                        "evicted across ranks, %s starts mid-run",
                        dropped, self._trace_out)
        log.info("Chrome trace written to %s", self._trace_out)

    def dump_crash(self, exc: BaseException) -> Optional[str]:
        """Crash flight recorder: on an exception unwinding out of the
        train loop, dump the telemetry event ring, the live section
        stack, the counter/gauge state and a config snapshot to
        ``<telemetry_out>.crash.json`` (rank-suffixed like the JSONL
        sink) so a dead run leaves evidence, not just a traceback.
        Returns the path written, or None (recorder off / no
        telemetry_out). Must never raise — it runs on the unwind path."""
        tel = self.telemetry
        out = (str(getattr(self.config, "telemetry_out", "") or "")
               if self.config is not None else "")
        if not tel.enabled or not out:
            return None
        import json as _json
        import traceback as _tb
        # rank-suffixed BEFORE the extension (concurrent multi-rank
        # crashes each get their own dump; rank 0 keeps the bare path
        # the single-process tooling watches)
        path = (out + ".crash.json" if not tel.rank
                else out + f".crash.rank{tel.rank}.json")
        try:
            payload = {
                "ts": time.time(),
                "rank": tel.rank,
                "iteration": int(self.iter),
                "exception": {
                    "type": type(exc).__name__,
                    "message": str(exc)[:4000],
                    "traceback": _tb.format_exception(
                        type(exc), exc, exc.__traceback__, limit=50),
                },
                "config": self.config.to_dict(),
                "telemetry": tel.crash_payload(),
                # the resume hint: the newest checkpoint THIS rank
                # committed (None = no checkpointing / nothing written
                # yet) — the first thing an operator needs from a dump
                "checkpoint": (self._ckpt.last_written
                               if self._ckpt is not None else None),
            }
            tel.flush()
            with open(path, "w") as fh:
                _json.dump(payload, fh, indent=1, default=str)
        except Exception as dump_err:
            log.warning("crash flight recorder failed: %s", dump_err)
            return None
        log.warning("training crashed (%s); flight record written to %s",
                    type(exc).__name__, path)
        return path

    # ------------------------------------------------------------------
    # Resilience: async checkpoints + resume + auditor auto-recovery
    # (resilience/; docs/Reliability.md). Checkpoint capture happens at
    # host consistency boundaries only (drain boundaries on the fast
    # path, iteration edges on the sync driver) so the 0.125-dispatch
    # megastep contract is untouched — the bench guard asserts
    # dispatches_per_iter is identical with checkpointing on.
    def _setup_resilience(self, config: Config) -> None:
        from ..resilience import comms
        from ..resilience.checkpoint import CheckpointManager
        from ..resilience.faults import registry_from_env
        comms.set_collective_policy(
            float(getattr(config, "collective_timeout", 0.0) or 0.0),
            int(getattr(config, "collective_retries", 2)))
        self._faults = registry_from_env()
        self._ckpt_period = int(getattr(config, "checkpoint_period", 0)
                                or 0)
        root = str(getattr(config, "checkpoint_dir", "") or "")
        if not root:
            if self._ckpt_period > 0:
                log.warning("checkpoint_period=%d set without "
                            "checkpoint_dir; checkpointing is off",
                            self._ckpt_period)
            if self._ckpt is not None:
                # reset_parameter dropped checkpoint_dir: drain + stop
                # the writer instead of orphaning its thread
                try:
                    self._ckpt.close()
                except Exception as e:
                    log.warning("checkpoint writer shutdown failed: %s",
                                e)
            self._ckpt = None
            return
        if self._ckpt_period <= 0 and not bool(getattr(
                config, "health_checkpoint_on_straggler", False)):
            # dir without period writes nothing on its own (only the
            # auditor's checkpoint-now would) — say so, mirroring the
            # inverse misconfiguration's warning above
            log.warning("checkpoint_dir=%s set without "
                        "checkpoint_period; no periodic checkpoints "
                        "will be written", root)
        if self._ckpt is not None and self._ckpt.root == root:
            return   # reset_parameter round trip: keep the writer
        if self._ckpt is not None:
            # checkpoint_dir changed on a reset: drain + stop the old
            # writer so its in-flight checkpoint commits and its thread
            # does not leak (one parked thread per reset otherwise)
            try:
                self._ckpt.close()
            except Exception as e:
                log.warning("old checkpoint writer shutdown failed: %s", e)
        tel = self.telemetry
        self._ckpt = CheckpointManager(
            root, rank=tel.rank, world=jax.process_count(),
            keep=int(getattr(config, "checkpoint_keep", 2)),
            telemetry=tel)

    def set_checkpoint_extra(self, provider) -> None:
        """Engine hook: a callable returning JSON-able state to ride the
        checkpoint (callback closures' early-stop lists, the last eval
        list) so a resumed engine loop continues bit-identically."""
        self._ckpt_extra = provider

    def maybe_checkpoint(self, force: bool = False) -> bool:
        """Capture + enqueue a checkpoint when one is due. Called at
        drain boundaries (_drain_body), after each sync-driver iteration
        (engine.train / _train_loop_body) and by the auditor's
        checkpoint-now action (force=True). Collective-free; a capture
        or write failure degrades to telemetry, never kills training."""
        if self._ckpt is None or self._ckpt_busy:
            return False
        if self._stopped_early or self._es_finished:
            return False
        if self.iter <= self._last_ckpt_iter:
            return False
        if not force and (self._ckpt_period <= 0
                          or self.iter - self._last_ckpt_iter
                          < self._ckpt_period):
            return False
        self._ckpt_busy = True
        try:
            # no-op when called from inside _drain_body (pending already
            # taken); drains first otherwise so the snapshot covers a
            # settled model list + score carries
            self.drain_pending()
            from ..resilience import state as rstate
            payload, arrays = rstate.capture(self)
            self._ckpt.save(self.iter, payload, arrays)
            self._last_ckpt_iter = self.iter
            return True
        except Exception as e:
            log.warning("checkpoint capture at iteration %d failed: %s",
                        self.iter, e)
            if self.telemetry.enabled:
                self.telemetry.inc("ckpt.failed")
                self.telemetry.event("checkpoint_failed",
                                     iteration=self.iter,
                                     error=f"{type(e).__name__}: "
                                           f"{e}"[:500])
            return False
        finally:
            self._ckpt_busy = False

    def _device_tree_for_resume(self, ht: HostTree) -> "_DeviceTree":
        """Device tree for a checkpoint/resync-restored HostTree: the
        model-file rebin path, but with the TRAINING-time threshold_bin
        kept verbatim (the checkpoint stores it) so post-resume replay
        ops route bit-identically to the original run."""
        dt = self._device_tree_from_host(ht)
        tb = np.asarray(ht.threshold_bin)
        if tb.size == max(0, ht.num_leaves - 1) and tb.size:
            dt.threshold_bin = jnp.asarray(tb.astype(np.int32))
        return dt

    def _capture_boosting_extra(self) -> Tuple[Dict, Dict]:
        """Boosting-mode state beyond the base driver's (payload dict,
        npz arrays); DART/GOSS override."""
        return {}, {}

    def _restore_boosting_extra(self, payload: Dict, arrays) -> None:
        pass

    def _health_resync(self, it: int, per_rank) -> bool:
        from ..resilience import recovery
        self.drain_pending()
        return recovery.resync_from_rank0(self, it, per_rank)

    # ------------------------------------------------------------------
    def _setup_bundles(self, config: Config, train_data) -> None:
        """Exclusive feature bundling for the fused and depthwise growers
        (ref: src/io/dataset.cpp FindGroups/FastFeatureBundling). On by
        default like the reference's enable_bundle; engages only when
        bundling actually reduces the column count (dense data is
        unaffected — conflict-free bundles simply don't form)."""
        self.use_bundles = False
        self._replay_bundle = None
        pb = getattr(train_data, "prebundled", None)
        if pb is not None:
            # sparse-built dataset: the bundle matrix IS the storage — the
            # layout arrives from ingestion (TpuDataset.from_sparse), it
            # is not optional and not recomputed here
            if getattr(self, "n_forced", 0) > 0:
                log.fatal("forced splits are not supported on sparse-built "
                          "(prebundled) datasets")
            self._install_bundle_layout(
                train_data, pb,
                np.asarray(train_data.bins),
                np.asarray(train_data.most_freq_bins, np.int32))
            # bundle-aware replay routing for rollback/DART/stop-subtract/
            # valid updates (ops/predict.route_rows_to_leaves decode)
            self._replay_bundle = (
                jnp.asarray(pb.col_of_feat),
                jnp.asarray(pb.offset_of_feat),
                jnp.asarray(np.asarray(train_data.most_freq_bins,
                                       np.int32)))
            return
        if not (bool(config.tpu_enable_bundle)
                and bool(config.enable_bundle)):
            return
        if "tpu_enable_bundle" not in getattr(config, "_user_set", set()):
            # default-on only where it cannot change the grow policy: the
            # fused engine is depth-wise regardless. On the xla engine
            # bundling would force depth-wise growth and silently diverge
            # from the leaf-wise reference default on sparse data, so
            # there it stays opt-in.
            eng = config.tpu_engine
            if not (eng == "fused" or (eng == "auto" and self.on_tpu)):
                return
        if getattr(self, "n_forced", 0) > 0:
            return  # forced splits route through the leaf-wise grower
        from ..ops.efb import (BundleLayout, dropped_values, encode_bundles,
                               find_bundles)
        bins_np = np.asarray(train_data.bins)
        mfb = getattr(train_data, "most_freq_bins", None)
        if mfb is None:
            mfb = np.array([train_data.mappers[j].most_freq_bin
                            for j in train_data.used_features], np.int32)
        if jax.process_count() > 1:
            # multi-process: bundle layouts must be IDENTICAL on every
            # rank — conflict masks come from the allgathered binning
            # sample (the reference also bundles from sampled data,
            # dataset_loader.cpp FindGroups over sample_indices); the
            # local rows are then encoded with the shared layout
            sb = getattr(train_data, "mp_sample_bins", None)
            if sb is None:
                log.warning("no shared binning sample retained; skipping "
                            "EFB for this multi-process run")
                self.telemetry.degrade("efb_no_shared_sample")
                return
            masks = [sb[:, k] != mfb[k]
                     for k in range(train_data.num_features)]
            n_for_rate = sb.shape[0]
        else:
            masks = [bins_np[:, k] != mfb[k]
                     for k in range(train_data.num_features)]
            n_for_rate = self.num_data
        nb_all = [int(x) for x in np.asarray(self.meta.num_bin)]
        # reference-parity bundling: tolerated conflicts at the
        # single_val_max_conflict_cnt rate (ref: dataset.cpp:108
        # total/10000). The reference's jagged per-group offsets have no
        # kernel analog here — every bundle column is padded to the
        # widest (the one-hot bin extraction needs a uniform per-column
        # stride) — so the width cap is chosen ADAPTIVELY: start
        # uncapped like the reference, and only tighten when the
        # uniform padding would inflate the stored matrix
        # 32767 = int16 ceiling of the fused kernel's transposed bin
        # matrix (a wider bundle would wrap negative in _init_fused's
        # astype(int16) and zero the one-hot); the reference is uncapped
        # because its jagged storage never widens a column
        for cap in (32767, 8 * self.max_bins, 4 * self.max_bins):
            bundles = find_bundles(masks, n_for_rate,
                                   max_conflict_rate=1e-4,
                                   max_bundle_bins=cap,
                                   num_bin_per_feat=nb_all)
            if len(bundles) >= train_data.num_features:
                return  # nothing to gain
            widths = [1 + sum(nb_all[f] for f in b) for b in bundles]
            padded = len(bundles) * max(widths)
            if padded <= 2 * sum(widths):
                break  # padding waste bounded; keep this layout
        layout = BundleLayout(bundles, nb_all)
        enc = encode_bundles(bins_np, mfb, layout)
        self._install_bundle_layout(train_data, layout, enc,
                                    np.asarray(mfb, np.int32),
                                    dropped_values(masks, bundles))
        log.info("EFB: %d features bundled into %d columns",
                 train_data.num_features, layout.num_columns)
        self.telemetry.event("efb", features=train_data.num_features,
                             columns=layout.num_columns)

    def _install_bundle_layout(self, train_data, layout, enc_np,
                               mfb_np, conflict_rows: int = 0) -> None:
        """BundleCfg + device bundle matrix from a BundleLayout (shared by
        the dense default-on EFB path and sparse-built prebundled
        datasets); ``conflict_rows``: the values the encode dropped (a row
        non-default in k members of one bundle keeps the first: k - 1),
        said with the layout (``_route_form``)."""
        nb = [int(x) for x in train_data.num_bin_per_feat]
        Bc = max(layout.col_num_bin)
        B = self.max_bins
        F = train_data.num_features
        flat_idx = np.zeros((F, B), np.int32)
        valid = np.zeros((F, B), bool)
        for f in range(F):
            ci = int(layout.col_of_feat[f])
            off = int(layout.offset_of_feat[f])
            for b in range(nb[f]):
                flat_idx[f, b] = ci * Bc + off + b
                valid[f, b] = True
        from ..models.learner import BundleCfg
        # FixHistogram residual lands on each feature's MOST FREQUENT bin
        # (the rows encoded as bundle-default), not the zero-default bin
        self.bundle_cfg = BundleCfg(
            flat_idx=jnp.asarray(flat_idx), valid=jnp.asarray(valid),
            default_bin=jnp.asarray(mfb_np),
            col_of_feat=jnp.asarray(layout.col_of_feat),
            offset_of_feat=jnp.asarray(layout.offset_of_feat))
        enc_small = enc_np.astype(np.uint8 if Bc <= 256 else np.uint16)
        # host copy only where the multi-process placement paths read it
        self.bundle_bins_host = (enc_small if jax.process_count() > 1
                                 else None)
        self.bundle_bins_dev = jnp.asarray(enc_small)
        self.bundle_col_bins = int(Bc)
        self.use_bundles = True
        self._efb_layout = {"features": F, "columns": layout.num_columns,
                            "max_bins": int(Bc),
                            "conflict_rows": int(conflict_rows)}

    # ------------------------------------------------------------------
    def _setup_forced_splits(self, config: Config, train_data) -> None:
        """BFS schedule from the forced-splits JSON (ref: gbdt.cpp:72-80
        load + serial_tree_learner.cpp:455 ForceSplits). Leaf numbering
        follows the leaf-wise grower: splitting leaf l keeps l as the left
        child, the right child gets the next fresh id."""
        self.n_forced = 0
        path = str(config.forcedsplits_filename or "")
        if not path:
            return
        import json as _json
        with open(path) as f:
            root = _json.load(f)
        leaves, feats, thrs = [], [], []
        queue = [(root, 0)]
        next_id = 1
        while queue:
            node, leaf = queue.pop(0)
            real_f = int(node["feature"])
            inner = train_data.inner_feature_index(real_f)
            if inner < 0:
                log.warning("forced split on filtered feature %d skipped",
                            real_f)
                continue
            if bool(train_data.is_categorical[inner]):
                log.fatal("forced splits on categorical features are not "
                          "supported (feature %d)", real_f)
            m = train_data.mappers[real_f]
            tbin = int(m.value_to_bin(float(node["threshold"])))
            leaves.append(leaf)
            feats.append(inner)
            thrs.append(tbin)
            right_id = next_id
            next_id += 1
            if "left" in node and node["left"]:
                queue.append((node["left"], leaf))
            if "right" in node and node["right"]:
                queue.append((node["right"], right_id))
        n = min(len(leaves), self.max_leaves - 1)
        self.n_forced = n
        if n:
            self.forced_leaf = jnp.asarray(
                np.asarray(leaves[:n], np.int32))
            self.forced_feat = jnp.asarray(np.asarray(feats[:n], np.int32))
            self.forced_thr = jnp.asarray(np.asarray(thrs[:n], np.int32))
            log.info("Loaded %d forced splits from %s", n, path)

    # ------------------------------------------------------------------
    def _setup_cegb(self, config: Config) -> None:
        """CEGB enablement and per-feature cost arrays (ref:
        cost_effective_gradient_boosting.hpp:26 IsEnable). Re-run by
        reset_config so reset_parameter can change the penalties."""
        train_data = self.train_data
        coupled = list(config.cegb_penalty_feature_coupled or [])
        lazy = list(config.cegb_penalty_feature_lazy or [])
        self.use_cegb = (config.cegb_tradeoff < 1.0
                         or config.cegb_penalty_split > 0.0
                         or bool(coupled) or bool(lazy))
        if not self.use_cegb:
            return
        cp = np.zeros(train_data.num_features, np.float32)
        for real_f, pen in enumerate(coupled):
            inner = train_data.inner_feature_index(real_f)
            if inner >= 0:
                cp[inner] = pen
        self.cegb_coupled = jnp.asarray(cp)
        if not hasattr(self, "cegb_used"):
            self.cegb_used = np.zeros(train_data.num_features, bool)
        # per-(row, feature) lazy penalties (ref:
        # cost_effective_gradient_boosting.hpp:22 — charged per data
        # point in the leaf that has not used the feature on its path
        # yet; the used bitmap persists across the whole boosting run)
        lp = np.zeros(train_data.num_features, np.float32)
        for real_f, pen in enumerate(lazy):
            inner = train_data.inner_feature_index(real_f)
            if inner >= 0:
                lp[inner] = pen
        self.use_cegb_lazy = bool(np.any(lp > 0))
        self.cegb_lazy = jnp.asarray(lp)
        if self.use_cegb_lazy and not hasattr(self, "cegb_used_rf"):
            self.cegb_used_rf = jnp.zeros(
                (train_data.num_data, train_data.num_features), bool)

    # ------------------------------------------------------------------
    def _setup_parallel(self, config: Config) -> None:
        """Distribution axis of the learner factory (ref:
        src/treelearner/tree_learner.cpp:17-49 — the learner_type x
        device_type composition matrix). ``tree_learner=data|voting|
        feature`` makes every tree grow through shard_map over a named
        device mesh so ``lgb.train()`` works unchanged across the chips
        (SURVEY.md north star):

        - data: rows sharded, per-level histogram psum, split decisions
          replicated by construction (ref:
          data_parallel_tree_learner.cpp:126-276);
        - voting: rows sharded, per-level top-k vote caps the exchanged
          histogram columns (ref: voting_parallel_tree_learner.cpp:151-184);
        - feature: columns sharded, zero histogram traffic, per-level
          best-split record merge (ref:
          feature_parallel_tree_learner.cpp:60-77).

        Combinations the distributed growers don't implement degrade to
        data-parallel (still distributed, same trees) with a warning.
        """
        self.parallel_mode = "serial"
        self.mesh = None
        self.n_shards = 1
        self.axis_name = None
        self.mp = None
        self._par_fns = {}
        # external collective functions coordinate the HOST plane only;
        # training a "distributed" model without the jax process runtime
        # up would silently produce rank-local models — fail loudly
        # instead (see parallel/extnet.py module docstring)
        from ..parallel import extnet
        if extnet.is_active() \
                and jax.process_count() < extnet.num_machines():
            log.fatal(
                "LGBM_NetworkInitWithFunctions registered %d machines but "
                "the jax process runtime spans %d process(es); external "
                "function pointers cannot be spliced into XLA's device "
                "collectives — additionally bring up jax.distributed "
                "(parallel.distributed.init_distributed / set_network / "
                "the launcher) so device psums span the machines",
                extnet.num_machines(), jax.process_count())
        if not bool(getattr(config, "is_parallel", False)):
            return
        mode = str(config.tree_learner)
        n_dev = jax.device_count()
        if n_dev < 2:
            log.warning(
                "tree_learner=%s requested but only one device is visible; "
                "training serially (multi-chip needs a TPU slice or "
                "XLA_FLAGS=--xla_force_host_platform_device_count)", mode)
            self.telemetry.degrade("parallel_single_device",
                                   requested=mode, to="serial")
            return
        if getattr(self, "use_cegb_lazy", False):
            log.warning("cegb_penalty_feature_lazy keeps a per-(row, "
                        "feature) bitmap on one device and is not wired "
                        "into the distributed growers; dropping the lazy "
                        "penalties for this parallel run")
            self.telemetry.degrade("cegb_lazy_not_distributed")
            self.use_cegb_lazy = False
        if jax.process_count() > 1 and mode == "feature":
            # feature-parallel replicates rows on every shard; multi-
            # process runs hold one rank-local row shard per process
            log.warning("tree_learner=feature needs row-replicated data; "
                        "multi-process runs shard rows per rank — using "
                        "data-parallel")
            self.telemetry.degrade("feature_parallel_multiproc_rows",
                                   requested="feature", to="data")
            # megastep-taxonomy twin of the degrade event: names the
            # remaining multi-process limitation in the same reason
            # namespace the eviction matrix documents
            self._report_eviction("engine:multiproc_feature_parallel_rows",
                                  to="data")
            mode = "data"
        # feature-parallel composition: the FUSED feature engine keeps
        # the whole replicated layout (global feature indices), so EFB
        # and interaction/bynode constraints compose on it; the sliced
        # XLA feature grower cannot mix local/global indexing — degrade
        # only the combinations that genuinely force the XLA growers
        fused_capable = (str(config.tpu_engine) == "fused"
                         or (str(config.tpu_engine) == "auto"
                             and self.on_tpu))
        if mode == "feature" and getattr(self, "use_cegb", False):
            log.warning("CEGB gain accounting is wired into the depthwise "
                        "XLA grower, whose feature-parallel column "
                        "slicing cannot carry the global per-feature "
                        "cost state; using data-parallel")
            self.telemetry.degrade("feature_parallel_cegb",
                                   requested="feature", to="data")
            mode = "data"
        if mode == "feature" and getattr(self, "n_forced", 0):
            log.warning("forced splits run on the leaf-wise grower; "
                        "feature-parallel is depth-wise — using "
                        "data-parallel")
            self.telemetry.degrade("feature_parallel_forced_splits",
                                   requested="feature", to="data")
            mode = "data"
        if mode == "feature" and not fused_capable \
                and (self.use_node_masks
                     or getattr(self, "use_bundles", False)):
            log.warning("the sliced XLA feature-parallel grower does not "
                        "compose with interaction/bynode constraints or "
                        "EFB (local/global feature indexing); set "
                        "tpu_engine=fused (replicated layout) or use "
                        "data-parallel — using data-parallel")
            self.telemetry.degrade("feature_parallel_xla_constraints",
                                   requested="feature", to="data")
            mode = "data"
        from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS, make_mesh
        axis = FEATURE_AXIS if mode == "feature" else DATA_AXIS
        self.mesh = make_mesh(axis_name=axis)
        self.axis_name = axis
        self.n_shards = n_dev
        self.parallel_mode = mode
        n = self.num_data
        # device placement is LAZY (_place_par_data): the fused engine
        # reads only its own sharded fused_bins_T — materialising a second
        # padded copy of the binned matrix would waste O(dataset) HBM on
        # the flagship path
        self._par_placed = False
        self.bins_par = None
        self.bundle_bins_par = None
        if mode in ("data", "voting"):
            self.par_rows = ((n + n_dev - 1) // n_dev) * n_dev
        else:
            # feature mode: rows replicated, columns padded so every shard
            # owns an equal slice; pad features are trivial + masked off
            F = self.train_data.num_features
            self.par_feats = ((F + n_dev - 1) // n_dev) * n_dev
            padF = self.par_feats - F

            def padv(a, fill=0):
                a = np.asarray(a)
                return jnp.asarray(np.pad(a, (0, padF),
                                          constant_values=fill))
            self.par_meta = FeatureMeta(
                num_bin=padv(self.meta.num_bin, 2),
                missing_type=padv(self.meta.missing_type),
                default_bin=padv(self.meta.default_bin),
                monotone=padv(self.meta.monotone),
                is_cat=jnp.asarray(np.pad(
                    np.asarray(self.meta.is_cat), (0, padF))))
        if jax.process_count() > 1:
            self._init_multiproc(config)
        log.info("Using %s-parallel tree learner over %d devices", mode,
                 n_dev)

    def _init_multiproc(self, config: Config) -> None:
        """Joint multi-process training: one global model over per-rank
        row shards (the v5e-pod / DCN analog of the reference's multi-
        machine mode, data_parallel_tree_learner.cpp:126-276 — see
        parallel/multiproc.py for the layout contract)."""
        from ..parallel.multiproc import MultiProcLayout
        if bool(config.linear_tree):
            # REFERENCE PARITY: the reference also refuses this —
            # "Linear tree learner must be serial" (config.cpp:348
            # forces tree_learner=serial + device=cpu under linear_tree)
            log.fatal("linear_tree is serial-only (the reference forces "
                      "tree_learner=serial for linear trees too); not "
                      "supported with multi-process training")
        # DART/GOSS/RF compose since round 5: drop-set and bagging
        # streams are seeded identically on every rank (SPMD control
        # flow), GOSS resampling is rank-local like the reference's
        # (goss.hpp:103 samples each machine's own rows), and score
        # replay routes on the row-sharded global matrix
        # leaf-renewing objectives (L1/quantile/huber/MAPE) compose since
        # round 5: rank-local percentiles averaged over contributing
        # workers — the reference's own distributed semantics
        # (_renew_tree_output_mp; serial_tree_learner.cpp:744-755)
        if getattr(self.train_data, "prebundled", None) is not None:
            log.fatal("sparse-built (prebundled) datasets derive their "
                      "bundle layout from rank-local CSC columns and are "
                      "not supported with multi-process training; dense "
                      "EFB (enable_bundle on dense data) composes — its "
                      "layout comes from the shared binning sample")
        # the fused engine needs per-device row slices aligned to its
        # widest kernel tile (engine resolution happens later, so key on
        # the config request; "auto" resolves to fused only on TPU)
        wants_fused = (str(config.tpu_engine) == "fused"
                       or (str(config.tpu_engine) == "auto"
                           and self.on_tpu))
        self.mp = MultiProcLayout(self.mesh, self.axis_name,
                                  self.train_data.num_data,
                                  row_align=2048 if wants_fused else 1,
                                  telemetry=self.telemetry)
        self.num_data = self.mp.Np
        self.par_rows = self.mp.Np
        self._mp_real_mask = self.mp.real_mask_np()
        self._mp_metadata = self.mp.global_metadata(self.train_data.metadata)
        # objectives/metrics were inited with the rank-local shard; re-init
        # on the global view so label statistics (class counts, averages,
        # metric weights) are global — the reference's GlobalSyncUp* paths.
        # num_data = REAL global rows (statistics), arrays are [Np] padded
        # with zero weight.
        if self.objective is not None:
            self.objective.init(self._mp_metadata, self.mp.total_real)
        for m in self.training_metrics:
            m.init(self._mp_metadata, self.mp.total_real)

    def _place_par_data(self) -> None:
        """Mesh placement of the binned matrix for the XLA parallel
        growers, deferred to first use (the fused engine never needs it)."""
        if self._par_placed:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P
        axis = self.axis_name
        bins_np = np.asarray(self.train_data.bins)
        if self.mp is not None:
            # the one per-rank-DISTINCT operand: rank-local binned rows
            # into their block of the global row-sharded matrix
            if getattr(self, "use_bundles", False):
                self.bundle_bins_par = self.mp.shard_local(
                    np.asarray(self.bundle_bins_host))
            else:
                self.bins_par = self.mp.shard_local(bins_np)
            self._par_placed = True
            return
        if self.parallel_mode in ("data", "voting"):
            pad = self.par_rows - self.num_data
            if getattr(self, "use_bundles", False):
                # the bundled grower only ever reads the bundle matrix
                bb = np.asarray(self.bundle_bins_dev)
                if pad:
                    bb = np.pad(bb, ((0, pad), (0, 0)))
                self.bundle_bins_par = jax.device_put(
                    bb, NamedSharding(self.mesh, P(axis, None)))
            else:
                if pad:
                    bins_np = np.pad(bins_np, ((0, pad), (0, 0)))
                self.bins_par = jax.device_put(
                    bins_np, NamedSharding(self.mesh, P(axis, None)))
        else:
            padF = self.par_feats - self.train_data.num_features
            if padF:
                bins_np = np.pad(bins_np, ((0, 0), (0, padF)))
            self.bins_par = jax.device_put(
                bins_np, NamedSharding(self.mesh, P()))
        self._par_placed = True

    def _get_par_fn(self, kind: str):
        fn = self._par_fns.get(kind)
        if fn is None:
            fn = self._build_par_fn(kind)
            self._par_fns[kind] = fn
        return fn

    def _build_par_fn(self, kind: str):
        """shard_map-wrapped jitted tree growth for the sync path. The
        small per-tree state (meta, params, bundle tables) rides as
        closures — replicated constants; the O(rows) operands are
        explicit sharded arguments."""
        from jax.sharding import PartitionSpec as P
        axis = self.axis_name
        params = self.params
        L, B = self.max_leaves, self.max_bins
        md = int(self.config.max_depth)
        if kind == "fused_sync":
            from ..models.frontier2 import grow_tree_fused
            self._route_form()
            interp = self.fused_interpret
            use_nm = self.use_node_masks
            mode = self.parallel_mode
            top_k = int(self.config.top_k) if mode == "voting" else 0
            f_oh = self.fused_f_oh
            n_sh = self.n_shards

            quant = self.quant_bits

            def per_shard(bins_T, gh_T, fm_pad, *rest):
                ri = 0
                scales = None
                if quant:
                    scales = rest[0]
                    ri = 1
                nm = rest[ri:]
                fsm = None
                if mode == "feature":
                    # this shard owns an equal contiguous block of the
                    # padded one-hot feature axis (replicated layout,
                    # global indices — merge offset 0)
                    sid = jax.lax.axis_index(axis)
                    Fs = (f_oh + n_sh - 1) // n_sh
                    fi = jnp.arange(f_oh, dtype=jnp.int32)
                    fsm = (fi >= sid * Fs) & (fi < (sid + 1) * Fs)
                return grow_tree_fused(
                    bins_T, gh_T, self.fused_meta, fm_pad, params, L,
                    self.fused_Bp, f_oh, num_rows=0,
                    nch=self.fused_nch, max_depth=md,
                    extra_levels=int(self.config.tpu_extra_levels),
                    has_cat=self.has_cat,
                    use_mono_bounds=self.use_mono_bounds,
                    use_node_masks=use_nm,
                    node_masks=nm[0] if use_nm else None,
                    bundle_cols=self.fused_bundle_cols,
                    bundle_col_bins=self.fused_bundle_col_bins,
                    bundle_cfg=self.fused_bundle_cfg,
                    interpret=interp, psum_axis=axis,
                    mono_mode=getattr(self, "mono_mode", "basic"),
                    parallel_mode=mode, top_k=top_k,
                    feature_shard_mask=fsm,
                    quant_bits=quant, packed=self.fused_packed,
                    mask_onehot=self._mask_onehot(), gh_scales=scales)
            q_specs = (P(),) if quant else ()
            if mode == "feature":
                # rows replicated on every shard; records merge in-jit,
                # every shard emits the identical tree and row_leaf
                in_specs = (P(), P(), P()) + q_specs \
                    + ((P(),) if use_nm else ())
                out_specs = (P(), P())
            else:
                in_specs = (P(None, axis), P(None, axis), P()) + q_specs \
                    + ((P(),) if use_nm else ())
                out_specs = (P(), P(axis))
            # the packed gh block is rebuilt every call — donate it so
            # the sharded operand recycles its per-device buffers
            return jax.jit(_shard_map(
                per_shard, mesh=self.mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False),
                donate_argnums=_donate(1))

        if kind == "xla_sync":
            mode = self.parallel_mode
            grow = (grow_tree_leafwise if self.grow_policy == "leafwise"
                    and mode in ("data", "voting")
                    else grow_tree_depthwise)
            hist_impl = self.config.tpu_histogram_impl
            use_nm = self.use_node_masks
            use_cegb = self.use_cegb
            ub = getattr(self, "use_bundles", False)
            # forced splits compose with data- AND voting-parallel since
            # round 5 (the vote exchange always sums the forced features'
            # columns); feature-parallel degraded earlier
            n_forced = (getattr(self, "n_forced", 0)
                        if mode in ("data", "voting") else 0)

            if mode == "feature":
                n_sh = self.n_shards
                Fp = self.par_feats
                Fs = Fp // n_sh

                def per_shard(bins_full, gh, fm_pad):
                    sid = jax.lax.axis_index(axis)
                    f0 = sid * Fs
                    bins_loc = jax.lax.dynamic_slice_in_dim(
                        bins_full, f0, Fs, axis=1)
                    sl = lambda a: jax.lax.dynamic_slice_in_dim(
                        a, f0, Fs, axis=0)
                    meta_loc = FeatureMeta(
                        num_bin=sl(self.par_meta.num_bin),
                        missing_type=sl(self.par_meta.missing_type),
                        default_bin=sl(self.par_meta.default_bin),
                        monotone=sl(self.par_meta.monotone),
                        is_cat=sl(self.par_meta.is_cat))
                    return grow_tree_depthwise(
                        bins_loc, gh, meta_loc, sl(fm_pad), params, L, B,
                        md, hist_impl=hist_impl, psum_axis=axis,
                        has_cat=self.has_cat, parallel_mode="feature",
                        route_bins=bins_full, route_meta=self.par_meta,
                        feature_offset=f0,
                        use_mono_bounds=self.use_mono_bounds)
                return jax.jit(_shard_map(
                    per_shard, mesh=self.mesh, in_specs=(P(), P(), P()),
                    out_specs=(P(), P()), check_vma=False),
                    donate_argnums=_donate(1))

            kw = {"mono_mode": getattr(self, "mono_mode", "basic")}
            if mode == "voting":
                kw.update(parallel_mode="voting",
                          top_k=int(self.config.top_k))
            else:
                kw.update(parallel_mode="data")
            if ub:
                kw.update(use_bundles=True, bundle_cfg=self.bundle_cfg,
                          bundle_col_bins=self.bundle_col_bins)
            if grow is grow_tree_leafwise:
                # leaf-wise accepts parallel_mode/top_k since round 4
                # (voting under best-first growth); forced splits remain
                # data-mode-only
                kw["mono_mode"] = getattr(self, "mono_mode", "basic")
                if n_forced:
                    kw.update(n_forced=n_forced,
                              forced_leaf=self.forced_leaf,
                              forced_feat=self.forced_feat,
                              forced_thr=self.forced_thr)

            def per_shard(bins, gh, fm, *extra):
                i = 0
                nm = None
                if use_nm:
                    nm = extra[i]
                    i += 1
                kw2 = dict(kw)
                if use_cegb:
                    kw2.update(use_cegb=True,
                               cegb_coupled=self.cegb_coupled,
                               cegb_used=extra[i])
                    i += 1
                return grow(bins, gh, self.meta, fm, params, L, B, md,
                            hist_impl=hist_impl, psum_axis=axis,
                            has_cat=self.has_cat,
                            use_mono_bounds=self.use_mono_bounds,
                            use_node_masks=use_nm, node_masks=nm, **kw2)
            in_specs = (P(axis, None), P(axis, None), P()) \
                + ((P(),) if use_nm else ()) \
                + ((P(),) if use_cegb else ())
            return jax.jit(_shard_map(
                per_shard, mesh=self.mesh, in_specs=in_specs,
                out_specs=(P(), P(axis)), check_vma=False),
                donate_argnums=_donate(1))
        raise KeyError(kind)

    @contextlib.contextmanager
    def _maybe_record_collectives(self, fresh: bool):
        """Trace-time collective payload recorder around the FIRST call
        of a fresh grower jit (tracing happens exactly once per jit
        signature, so the recorded static shapes are the program's real
        per-call collective schedule — ops/collectives.py). Yields the
        recorder, or None when there is nothing to measure (serial mode
        or an already-traced function)."""
        if not fresh or self.parallel_mode == "serial":
            yield None
            return
        from ..ops.collectives import CollectiveTrace
        with CollectiveTrace() as rec:
            yield rec

    def _grow_parallel(self, gh, tid: int = 0):
        """Sync-path tree growth through the mesh (driver semantics of
        ref: data_parallel_tree_learner.cpp:126-276 — local histograms,
        global sums, replicated split decisions). ``gh`` is [n, 3]
        (grad*w, hess*w, w); pad rows carry zero weight so they never
        contribute to histograms or counts."""
        n = self.num_data
        fm = self._feature_mask()
        extra = []
        if self.use_node_masks:
            extra.append(self._node_masks_padded() if self.use_fused
                         else self._node_masks_for_iter())
        if self.use_fused:
            from ..ops.fused_level import pack_gh, pack_gh_quant
            pad = self.fused_Rp - n
            g_p = jnp.pad(gh[:, 0], (0, pad))
            h_p = jnp.pad(gh[:, 1], (0, pad))
            w_p = jnp.pad(gh[:, 2], (0, pad))
            qextra = ()
            if self.quant_bits:
                # the max-abs scale reduces over the GLOBAL (sharded)
                # operand, so every shard quantizes on the same grid
                gh_T, scales = pack_gh_quant(
                    g_p, h_p, w_p, self.quant_bits,
                    self._quant_seed(self.iter, tid))
                qextra = (scales,)
            else:
                gh_T = pack_gh(g_p, h_p, w_p, self.fused_nch)
            fm_pad = jnp.zeros((self.fused_f_oh,), bool) \
                .at[:fm.shape[0]].set(fm)
            smask = self._screen_mask_for_iter()
            if smask is not None:
                fm_pad = fm_pad & smask
            fresh = "fused_sync" not in self._par_fns
            fn = self._get_par_fn("fused_sync")
            with self._maybe_record_collectives(fresh) as rec:
                tree, row_leaf = fn(self.fused_bins_T, gh_T, fm_pad,
                                    *qextra, *extra)
            if rec is not None:
                self._coll_per_grow = rec.profile
            self._note_tree_gains(tree)
            return tree, row_leaf[:n]
        if self.use_cegb:
            extra.append(jnp.asarray(self.cegb_used))
        self._place_par_data()
        fresh = "xla_sync" not in self._par_fns
        if self.parallel_mode == "feature":
            Fp = self.par_feats
            fm_pad = jnp.zeros((Fp,), bool).at[:fm.shape[0]].set(fm)
            fn = self._get_par_fn("xla_sync")
            with self._maybe_record_collectives(fresh) as rec:
                tree, row_leaf = fn(self.bins_par, gh, fm_pad, *extra)
            if rec is not None:
                self._coll_per_grow = rec.profile
            return tree, row_leaf
        pad = self.par_rows - n
        gh_p = jnp.pad(gh, ((0, pad), (0, 0)))
        bins = (self.bundle_bins_par if getattr(self, "use_bundles", False)
                else self.bins_par)
        fn = self._get_par_fn("xla_sync")
        with self._maybe_record_collectives(fresh) as rec:
            tree, row_leaf = fn(bins, gh_p, fm, *extra)
        if rec is not None:
            self._coll_per_grow = rec.profile
        return tree, row_leaf[:n]

    # ------------------------------------------------------------------
    def _setup_engine(self, config: Config) -> None:
        """Resolve tpu_engine/grow_policy into the learner flags (called by
        init and again by reset_config so reset_parameter can switch
        engines)."""
        self._fast_step_fns = {}      # engine/params changed: re-derive
        self._fast_ok_cache = None
        self._sample_plan_cache = None
        self._megastep_fns = {}       # megastep closes over params too
        self._megastep_fm = {}
        self._fast_fm_pads = None
        self._par_fns = {}            # parallel growers close over params
        self._valid_upd_fns = None    # close over shrinkage/depth bound
        self._valid_routes = {}       # path and layout are the engine's
        self._coll_per_iter = None    # re-measured on the fresh traces
        self._coll_per_grow = None
        engine = config.tpu_engine
        if engine == "auto":
            engine = "fused" if self.on_tpu else "xla"
        # both engines compose with every distribution mode (ref:
        # tree_learner.cpp:17-49 — the reference instantiates its device
        # learner under data/voting/feature distribution too)
        # intermediate/advanced monotone modes need the stale-leaf
        # recompute, implemented on the leaf-wise grower (the reference
        # implements them in SerialTreeLearner too,
        # monotone_constraints.hpp:514,856)
        self.mono_mode = "basic"
        if getattr(self, "use_mono_bounds", False):
            method = str(self.config.monotone_constraints_method)
            if method in ("intermediate", "advanced"):
                # round 4: intermediate on ALL growers (leaf-wise inline,
                # depthwise/fused via mono_inter_level_update); advanced
                # (per-segment bound planes) on the leaf-wise grower
                self.mono_mode = method
        if getattr(self, "n_forced", 0) > 0 and engine != "xla":
            log.info("forced splits use the leaf-wise XLA engine")
            self.telemetry.degrade("forced_splits_need_xla",
                                   requested=engine, to="xla")
            engine = "xla"
        if getattr(self, "use_cegb", False) and engine != "xla":
            # CEGB gain deltas are wired into the depthwise XLA grower;
            # must override BEFORE the engine flags are derived
            log.info("cost-effective gradient boosting uses the "
                     "depthwise XLA engine")
            self.telemetry.degrade("cegb_needs_xla", requested=engine,
                                   to="xla")
            engine = "xla"
        self.use_fused = engine == "fused"
        self.fused_interpret = self.use_fused and not self.on_tpu
        default_policy = ("depthwise" if (self.use_fused
                                          or getattr(self, "use_cegb", False))
                          else "leafwise")
        self.grow_policy = {"auto": default_policy}.get(config.grow_policy,
                                                        config.grow_policy)
        if self.parallel_mode == "feature" \
                and self.grow_policy != "depthwise":
            # voting composes with leaf-wise growth since round 4 (the
            # reference's voting learner runs best-first too,
            # voting_parallel_tree_learner.cpp:151-184); feature-parallel
            # stays on the depthwise column-slice exchange
            log.warning("tree_learner=feature is implemented on the "
                        "depthwise grower; switching grow_policy")
            self.telemetry.degrade("feature_parallel_needs_depthwise",
                                   to="depthwise")
            self.grow_policy = "depthwise"
        if self.mono_mode == "advanced" and self.grow_policy != "leafwise":
            log.warning("monotone_constraints_method=advanced (segment "
                        "bound planes) runs on the leaf-wise grower; this "
                        "configuration uses intermediate instead")
            self.telemetry.degrade("mono_advanced_needs_leafwise",
                                   to="intermediate")
            self.mono_mode = "intermediate"
        if self.mono_mode in ("intermediate", "advanced") \
                and self.parallel_mode == "feature" and not self.use_fused:
            # the sliced XLA feature grower tracks per-leaf bin regions
            # only for its LOCAL feature slice; cross-leaf adjacency
            # needs every feature's region. The fused feature engine
            # (replicated layout) and voting (validity-masked rescans)
            # compose since round 5.
            log.warning("the intermediate/advanced monotone recompute "
                        "needs full per-feature leaf regions, which the "
                        "sliced feature-parallel grower does not hold; "
                        "this configuration enforces the basic mode "
                        "(tpu_engine=fused composes)")
            self.telemetry.degrade("mono_inter_needs_full_regions",
                                   to="basic")
            self.mono_mode = "basic"
        if getattr(self, "use_cegb", False) \
                and self.grow_policy != "depthwise":
            log.warning("CEGB is implemented on the depthwise grower; "
                        "switching grow_policy")
            self.telemetry.degrade("cegb_needs_depthwise", to="depthwise")
            self.grow_policy = "depthwise"
        if getattr(self, "use_bundles", False) \
                and getattr(self, "n_forced", 0) > 0:
            if getattr(self.train_data, "prebundled", None) is not None:
                # reset_config can reach here after init: the bundle
                # matrix IS the storage — it cannot be switched off
                log.fatal("forced splits are not supported on sparse-"
                          "built (prebundled) datasets")
            log.warning("forced splits disable feature bundling")
            self.telemetry.degrade("forced_splits_disable_efb")
            self.use_bundles = False
        if getattr(self, "n_forced", 0) > 0 \
                and self.grow_policy != "leafwise":
            log.warning("forced splits are implemented on the leaf-wise "
                        "grower; switching grow_policy")
            self.telemetry.degrade("forced_splits_need_leafwise",
                                   to="leafwise")
            self.grow_policy = "leafwise"
        if getattr(self, "n_forced", 0) > 0 \
                and getattr(self, "use_cegb", False):
            log.warning("CEGB penalties are not applied when forced splits "
                        "are enabled (leaf-wise grower); disabling CEGB")
            self.telemetry.degrade("forced_splits_disable_cegb")
            self.use_cegb = False
        if self.grow_policy != "depthwise":
            self.use_fused = False
        # ---- histogram-plane cuts (ROADMAP item 4). Each gates
        # independently; all three are fused-engine features — other
        # engines degrade with a structured event and train unchanged.
        qb = int(getattr(config, "tpu_quantized_grad", 0) or 0)
        if qb not in (0, 8, 16):
            log.fatal("tpu_quantized_grad must be 0, 8 or 16; got %s", qb)
        if qb and not self.use_fused:
            log.info("tpu_quantized_grad requires the fused engine; "
                     "training with f32 histograms")
            self.telemetry.degrade("quantized_grad_needs_fused",
                                   requested=qb)
            qb = 0
        self.quant_bits = qb
        adaptive = bool(getattr(config, "tpu_adaptive_bins", False))
        if adaptive and not self.use_fused:
            self.telemetry.degrade("adaptive_bins_needs_fused")
            adaptive = False
        if adaptive and getattr(self, "use_bundles", False):
            # EFB already owns the packed flat axis (bundle columns)
            log.info("tpu_adaptive_bins is subsumed by feature bundling; "
                     "keeping the bundle layout")
            self.telemetry.degrade("adaptive_bins_with_efb")
            adaptive = False
        if adaptive and self.parallel_mode == "voting":
            # the voting exchange slices the flat axis per LOGICAL
            # feature (reshape(F, B)) — incompatible with class packing
            self.telemetry.degrade("adaptive_bins_with_voting")
            adaptive = False
        self.use_adaptive_bins = adaptive
        scr = bool(getattr(config, "tpu_gain_screening", False))
        if scr and not self.use_fused:
            self.telemetry.degrade("gain_screening_needs_fused")
            scr = False
        self.use_screening = scr
        self._screen_mask_cache = None
        self._iter_gain_acc = None
        if self.use_fused:
            if not hasattr(self, "fused_bins_T") \
                    or getattr(self, "_fused_built_mode", None) \
                    != (self.parallel_mode, self.use_adaptive_bins):
                # (re)build: the row padding, mesh placement and packing
                # of the transposed matrix depend on the parallel mode
                # and the adaptive layout
                self._init_fused(self.train_data)
            else:
                from ..ops.fused_level import NCH_FAST, NCH_PRECISE
                self.fused_nch = (NCH_FAST
                                  if config.tpu_hist_precision == "bf16"
                                  else NCH_PRECISE)
            if self.quant_bits:
                # quantized channel layout overrides tpu_hist_precision:
                # 8 -> (g, h, w) int8; 16 -> int8 hi/lo split (5 ch)
                from ..ops.quantize import QNCH
                self.fused_nch = QNCH[self.quant_bits]
            self._publish_hist_gauges()

    # ------------------------------------------------------------------
    def _mp_fused_bins_T(self, local_rows_np: np.ndarray, Fp: int,
                         Rp: int, bins_per_col: int) -> jax.Array:
        """Global transposed fused matrix from process-local row blocks
        (the same rank-blocked layout contract as bins_par,
        parallel/multiproc.py). mp.S is fused-aligned so Rp == mp.Np."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if Rp != self.mp.Np:
            log.fatal("fused multi-process row padding mismatch: Rp=%d "
                      "vs layout Np=%d (mp.S must be 2048-aligned)",
                      Rp, self.mp.Np)
        np_dt = np.int8 if bins_per_col <= 128 else np.int16
        n_cols = local_rows_np.shape[1]
        loc = np.zeros((Fp, self.mp.block), np_dt)
        loc[:n_cols, :self.mp.local_real] = local_rows_np.T.astype(np_dt)
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P(None, self.axis_name)), loc)

    def _init_fused(self, train_data: TpuDataset) -> None:
        """int8 transposed bin matrix + f_oh-padded metadata for the fused
        route+histogram level kernel (ops/fused_level.py). With EFB the
        matrix holds bundle COLUMNS (kernel layout) while split search
        stays on the logical feature layout."""
        tel = self.telemetry
        # (the first import of the kernels' module brings Pallas in: a
        # second on the host, once a process)
        with tel.timed("init/kernels_import"):
            from ..ops.fused_level import (NCH_FAST, NCH_PRECISE,
                                           feature_layout)
        F = train_data.num_features
        F_oh, Bp = feature_layout(F, self.max_bins)
        R = self.num_data
        # adaptive per-feature bin widths (tpu_adaptive_bins): pack each
        # feature's slab at ITS pow2 width instead of the global Bp; the
        # bin matrix rows are permuted into width-class order so the
        # kernel builds each class with one bulk repeat+compare
        self.fused_packed = None
        feat_order = None
        if getattr(self, "use_adaptive_bins", False) \
                and not getattr(self, "use_bundles", False) and F > 0:
            from ..ops.layout import packed_feature_layout
            self.fused_packed = packed_feature_layout(
                np.asarray(train_data.num_bin_per_feat), self.max_bins,
                f_oh=F_oh)
            feat_order = np.asarray(self.fused_packed.feat_order, np.int64)
        # row-sharded modes (data/voting) need kernel-tile-aligned local
        # rows per shard; 2048 = the widest shallow-pass tile
        # (default_tile_rows cap), so shallow levels can actually run at
        # the bigger tile. Multi-process layouts pre-align (mp.S) so
        # Rp == mp.Np already.
        blk = 2048 * (self.n_shards
                      if self.parallel_mode in ("data", "voting") else 1)
        Rp = ((R + blk - 1) // blk) * blk
        if not getattr(self, "use_bundles", False) and self.mp is None:
            rows_dev = self.bins_dev    # (its upload is a span of its own)
        # the transposed kernel layout; closed when the device has it
        with tel.timed("init/pack", rows=R) as sp:
            if getattr(self, "use_bundles", False):
                n_cols = int(self.bundle_bins_dev.shape[1])
                C_oh, Bc_p = feature_layout(n_cols, self.bundle_col_bins)
                Fp = max(C_oh, 8)
                dtype = jnp.int8 if Bc_p <= 128 else jnp.int16
                if self.mp is not None:
                    self.fused_bins_T = self._mp_fused_bins_T(
                        np.asarray(self.bundle_bins_host), Fp, Rp, Bc_p)
                else:
                    self.fused_bins_T = _fused_layout_T(self.bundle_bins_dev,
                                                        Fp, Rp, dtype)
                self.fused_bundle_cols = C_oh
                self.fused_bundle_col_bins = Bc_p
                # decode tables padded to the logical f_oh (padding features:
                # invalid everywhere, residual suppressed by bundle_plane_views)
                from ..models.learner import BundleCfg
                bc = self.bundle_cfg
                # logical plane layout is [f_oh, Bp] (pow2-padded bins like the
                # unbundled fused pool); kernel flat stride is the padded Bc_p
                fi = jnp.zeros((F_oh, Bp), jnp.int32)
                va = jnp.zeros((F_oh, Bp), bool)
                db = jnp.zeros((F_oh,), jnp.int32)
                cof = jnp.full((F_oh,), -1, jnp.int32)
                off = jnp.zeros((F_oh,), jnp.int32)
                col = bc.col_of_feat
                offs = bc.offset_of_feat
                b_i = jnp.arange(Bp, dtype=jnp.int32)[None, :]
                fi = fi.at[:F].set(jnp.minimum(
                    col[:, None] * Bc_p + offs[:, None] + b_i,
                    C_oh * Bc_p - 1))
                va = va.at[:F, :bc.valid.shape[1]].set(bc.valid)
                db = db.at[:F].set(bc.default_bin)
                cof = cof.at[:F].set(col)
                off = off.at[:F].set(offs)
                self.fused_bundle_cfg = BundleCfg(
                    flat_idx=fi, valid=va, default_bin=db, col_of_feat=cof,
                    offset_of_feat=off)
            elif self.mp is not None:
                Fp = max(F_oh, 8)
                dtype = jnp.int8 if Bp <= 128 else jnp.int16
                rows_np = np.asarray(self.train_data.bins)
                if feat_order is not None:
                    rows_np = rows_np[:, feat_order]
                self.fused_bins_T = self._mp_fused_bins_T(
                    rows_np, Fp, Rp, Bp)
                self.fused_bundle_cols = 0
                self.fused_bundle_col_bins = 0
                self.fused_bundle_cfg = None
            else:
                Fp = max(F_oh, 8)
                # int8 covers bins <= 127; larger max_bin needs int16 (a uint8
                # bin index >= 128 would wrap negative in int8 and corrupt the
                # one-hot)
                dtype = jnp.int8 if Bp <= 128 else jnp.int16
                # transpose + pad ON DEVICE from the already-uploaded bin
                # matrix instead of a second 300+ MB host transpose + upload.
                # Three full copies are live on device 0 while this runs
                # (the [R, F] source, the zeros target, the .at[].set
                # result); data-parallel runs reshard only afterwards.
                self.fused_bins_T = _fused_layout_T(rows_dev, Fp, Rp,
                                                    dtype, feat_order)
                self.fused_bundle_cols = 0
                self.fused_bundle_col_bins = 0
                self.fused_bundle_cfg = None
            sp.sync(self.fused_bins_T)
            sp.set(bytes=int(self.fused_bins_T.nbytes))
        from jax.sharding import NamedSharding, PartitionSpec as P
        # row-sharded modes place the transposed matrix sharded once, not
        # per call; feature-parallel replicates rows (zero histogram
        # traffic; per-level record merge instead): the matrix too
        spec = (P(None, self.axis_name)
                if self.parallel_mode in ("data", "voting")
                and self.mp is None
                else P() if self.parallel_mode == "feature" else None)
        if spec is not None:
            with tel.timed("init/reshard", shards=self.n_shards,
                           bytes=int(self.fused_bins_T.nbytes)) as sp:
                self.fused_bins_T = jax.device_put(
                    self.fused_bins_T, NamedSharding(self.mesh, spec))
                sp.sync(self.fused_bins_T)
        # the replicated [R, F] copy served only as the transpose source;
        # release it so HBM holds one binned matrix (the property rebuilds
        # it on the rare rollback/stop-subtract/DART replay paths)
        self._bins_dev = None
        self.fused_f_oh = F_oh
        self.fused_Bp = Bp
        self.fused_Rp = Rp
        self._fused_built_mode = (self.parallel_mode,
                                  bool(self.use_adaptive_bins))
        self.fused_nch = (NCH_FAST if self.config.tpu_hist_precision == "bf16"
                          else NCH_PRECISE)
        # the gain EMA is sized to the padded feature axis; keep a live
        # EMA across reset_config (continued training) unless the shape
        # moved
        if self._gain_ema_dev is None \
                or self._gain_ema_dev.shape[0] != F_oh:
            self._gain_ema_dev = jnp.zeros((F_oh,), jnp.float32)
        nb = np.zeros(F_oh, np.int32)
        nb[:F] = np.asarray(self.meta.num_bin)
        mt = np.zeros(F_oh, np.int32)
        mt[:F] = np.asarray(self.meta.missing_type)
        db = np.zeros(F_oh, np.int32)
        db[:F] = np.asarray(self.meta.default_bin)
        mono = np.zeros(F_oh, np.int32)
        mono[:F] = np.asarray(self.meta.monotone)
        ic = np.zeros(F_oh, bool)
        ic[:F] = np.asarray(self.meta.is_cat)
        self.fused_meta = FeatureMeta(jnp.asarray(nb), jnp.asarray(mt),
                                      jnp.asarray(db), jnp.asarray(mono),
                                      jnp.asarray(ic))

    # ------------------------------------------------------------------
    def add_valid_data(self, valid_data: TpuDataset, name: str,
                       metrics: Sequence) -> None:
        """(ref: gbdt.cpp AddValidDataset)"""
        if getattr(self, "mp", None) is not None \
                and self.early_stopping_round > 0:
            # metrics evaluate on the rank-LOCAL valid shard (the
            # reference's metrics are not distributed-aware either,
            # SURVEY §2.8); divergent stop decisions would desync the
            # ranks' collective schedules and hang the mesh
            log.warning("multi-process early stopping requires IDENTICAL "
                        "validation data on every rank — per-rank valid "
                        "shards may stop ranks at different iterations "
                        "and deadlock the collectives")
        self.drain_pending()          # replay below needs the full model
        self._fast_ok_cache = None    # (valid sets ride the fast path now)
        self._megastep_fns = {}       # valid-set count is baked into the
        # megastep signature; the per-iteration step keeps the route log
        # a new set may ask for
        self._fast_step_fns = {}
        if self._eval_consumer is not None:
            # the traced eval plan enumerated the old valid-set list; a
            # new set mid-run invalidates it (cannot happen through
            # engine.train, which adds every set before arming)
            log.warning("valid set added while a drain-replay eval "
                        "consumer was armed; disabling on-device eval")
            self.arm_megastep(self._megastep_armed, eval_consumer=None)
        self.valid_data.append(valid_data)
        self._publish_ingest(valid_data)
        self.telemetry.publish_spans(getattr(valid_data, "setup_spans", ()))
        self.valid_bins.append(
            self._dataset_bins_to_device(valid_data, span="valid/upload"))
        k = self.num_tree_per_iteration
        n = valid_data.num_data
        md = valid_data.metadata
        if md is not None and md.init_score is not None:
            init = np.asarray(md.init_score, np.float64)
            if init.size == n * k:
                s = init.reshape(k, n, order="C")
            else:
                s = np.tile(init.reshape(1, n), (k, 1))
            self.valid_scores.append(jnp.asarray(s, jnp.float32))
        else:
            self.valid_scores.append(jnp.zeros((k, n), jnp.float32))
        self.valid_metrics.append(list(metrics))
        self.valid_names.append(name)
        # the passenger matrix, where the training kernels can route this
        # set, is part of the set's upload
        self._valid_route(len(self.valid_data) - 1)
        # replay existing model onto the new valid set (continued training)
        if self.device_trees:
            with self.telemetry.timed("valid/replay",
                                      trees=len(self.device_trees)):
                for i, dt in enumerate(self.device_trees):
                    tree_id = i % self.num_tree_per_iteration
                    self.valid_scores[-1] = self._add_tree_to_score(
                        self.valid_scores[-1], self.valid_bins[-1], dt,
                        tree_id,
                        bundle=self._valid_bundle(len(self.valid_data) - 1))

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        """(ref: gbdt.cpp:346 BoostFromAverage)"""
        cfg = self.config
        if (self.models or self._pending or self.has_init_score
                or self.objective is None):
            return 0.0
        if not (cfg.boost_from_average or self.train_data.num_features == 0):
            if self.objective.name in ("regression_l1", "quantile", "mape"):
                log.warning("Disabling boost_from_average in %s may cause the "
                            "slow convergence", self.objective.name)
            return 0.0
        init_score = self.objective.boost_from_score(class_id)
        if abs(init_score) > K_EPSILON:
            if update_scorer:
                self.scores = self.scores.at[class_id].add(init_score)
                for vi in range(len(self.valid_scores)):
                    self.valid_scores[vi] = \
                        self.valid_scores[vi].at[class_id].add(init_score)
            log.info("Start training from score %f", init_score)
            return init_score
        return 0.0

    def _boosting_scores(self):
        """Scores used for gradient computation (DART overrides)."""
        return self.scores

    def _get_gradients(self):
        scores = self._boosting_scores()
        grad, hess = self.objective.get_gradients(scores)
        return grad, hess

    # ------------------------------------------------------------------
    def _bag_ones(self):
        """All-rows-in-bag weight vector ([n] f32). Multi-process: the
        real-row mask (pad rows carry zero weight so they never touch
        histograms, counts or leaf sums), sharded over the global mesh."""
        if getattr(self, "mp", None) is not None:
            return self.mp.shard_full(self._mp_real_mask)
        return jnp.ones((self.num_data,), jnp.float32)

    def _bag_mask_for(self, it: int):
        """In-bag mask effective at iteration ``it``. Rounds fire at
        iterations where it % bagging_freq == 0 and are drawn strictly in
        stream order, cached by firing iteration. The two most recent
        rounds are kept because the checkpoint format stores them
        (resilience/state.py; rollback_one_iter re-applies the previous
        round's mask)."""
        cfg = self.config
        fire = (it // cfg.bagging_freq) * cfg.bagging_freq
        cache = getattr(self, "_bag_round_cache", None)
        if cache is None:
            cache = self._bag_round_cache = {}
        if fire not in cache:
            # requests arrive in nondecreasing firing order, so drawing on
            # first sight preserves the stream sequence (and a fresh
            # stream after reset_config starts over at its first round)
            # reference-parity draws: one float per row per round from the
            # row's 1024-block LCG stream (ref: gbdt.cpp:192
            # BaggingHelper) — the in-bag SET matches the reference
            # bit-for-bit. The float draws are compared against the
            # DOUBLE fraction, matching the reference's float-vs-double
            # promotion (gbdt.cpp:192).
            draws = self.bag_streams.next_floats()
            if self.balanced_bagging:
                label = (self._mp_metadata.label
                         if getattr(self, "mp", None) is not None
                         else self.train_data.metadata.label)
                frac = np.where(label > 0,
                                np.float64(cfg.pos_bagging_fraction),
                                np.float64(cfg.neg_bagging_fraction))
                mask = draws.astype(np.float64) < frac
            else:
                mask = draws.astype(np.float64) < np.float64(
                    cfg.bagging_fraction)
            cache[fire] = mask
            for old in [key for key in cache
                        if key < fire - cfg.bagging_freq]:
                del cache[old]
        return cache[fire]

    def _bagging(self, it: int, grad, hess):
        """Recompute the in-bag weight vector (ref: gbdt.cpp:230 Bagging).
        Returns possibly-modified (grad, hess) (GOSS multiplies)."""
        cfg = self.config
        if not self.is_bagging or cfg.bagging_freq <= 0 \
                or it % cfg.bagging_freq != 0:
            return grad, hess
        mask = self._bag_mask_for(it)
        if getattr(self, "mp", None) is not None:
            m = mask.astype(np.float32) * self._mp_real_mask
            self.bag_cnt = int(m.sum())
            self._bag_weight_host = m    # rank-local renewal reads this
            self.bag_weight = self.mp.shard_full(m)
        else:
            self.bag_cnt = int(mask.sum())
            self.bag_weight = jnp.asarray(mask.astype(np.float32))
        log.debug("Re-bagging, using %d data to train", self.bag_cnt)
        return grad, hess

    # ------------------------------------------------------------------
    def _make_fused_step(self):
        """One jit-compiled dispatch per tree: bagging fold-in + growth.
        Eager per-op dispatch latency dominates otherwise (each jnp op is a
        separate dispatch)."""
        grow = (grow_tree_depthwise if self.grow_policy == "depthwise"
                else grow_tree_leafwise)

        @jax.jit
        def step(grad_row, hess_row, bag_weight, fm):
            gh = jnp.stack([grad_row * bag_weight,
                            hess_row * bag_weight, bag_weight], axis=1)
            return grow(self.bins_dev, gh, self.meta, fm, self.params,
                        self.max_leaves, self.max_bins,
                        int(self.config.max_depth),
                        hist_impl=self.config.tpu_histogram_impl)
        return step

    def _fused_step(self, grad_row, hess_row):
        if getattr(self, "_fused_step_fn", None) is None:
            self._fused_step_fn = self._make_fused_step()
            self._score_add_fn = self._make_score_add()
        return self._fused_step_fn(grad_row, hess_row, self.bag_weight,
                                   self._feature_mask())

    def _make_score_add(self):
        @jax.jit
        def add(scores, tid, leaf_value, row_leaf):
            return scores.at[tid].add(leaf_value[row_leaf])
        return add

    # ------------------------------------------------------------------
    def _grow(self, gh, tid: int = 0):
        if self.parallel_mode != "serial":
            return self._grow_parallel(gh, tid)
        fm = self._feature_mask()
        if self.use_fused:
            from ..models.frontier2 import grow_tree_fused
            from ..ops.fused_level import pack_gh, pack_gh_quant
            self._route_form()
            n = self.num_data
            pad = self.fused_Rp - n
            g_p = jnp.pad(gh[:, 0], (0, pad))
            h_p = jnp.pad(gh[:, 1], (0, pad))
            w_p = jnp.pad(gh[:, 2], (0, pad))
            scales = None
            if self.quant_bits:
                gh_T, scales = pack_gh_quant(
                    g_p, h_p, w_p, self.quant_bits,
                    self._quant_seed(self.iter, tid))
            else:
                gh_T = pack_gh(g_p, h_p, w_p, self.fused_nch)
            fm_pad = jnp.zeros((self.fused_f_oh,), bool) \
                .at[:fm.shape[0]].set(fm)
            smask = self._screen_mask_for_iter()
            if smask is not None:
                fm_pad = fm_pad & smask
            tree, row_leaf = grow_tree_fused(
                self.fused_bins_T, gh_T, self.fused_meta, fm_pad,
                self.params, self.max_leaves, self.fused_Bp,
                self.fused_f_oh, num_rows=n, nch=self.fused_nch,
                max_depth=int(self.config.max_depth),
                extra_levels=int(self.config.tpu_extra_levels),
                has_cat=self.has_cat,
                use_mono_bounds=self.use_mono_bounds,
                use_node_masks=self.use_node_masks,
                node_masks=self._node_masks_padded(),
                bundle_cols=self.fused_bundle_cols,
                bundle_col_bins=self.fused_bundle_col_bins,
                bundle_cfg=self.fused_bundle_cfg,
                interpret=self.fused_interpret,
                mono_mode=getattr(self, "mono_mode", "basic"),
                quant_bits=self.quant_bits, packed=self.fused_packed,
                mask_onehot=self._mask_onehot(), gh_scales=scales)
            self._note_tree_gains(tree)
            return tree, row_leaf[:n]
        if self.grow_policy == "depthwise":
            ub = getattr(self, "use_bundles", False)
            lazy = getattr(self, "use_cegb_lazy", False)
            out = grow_tree_depthwise(
                self.bundle_bins_dev if ub else self.bins_dev, gh,
                self.meta, fm, self.params,
                self.max_leaves, self.max_bins,
                int(self.config.max_depth),
                hist_impl=self.config.tpu_histogram_impl,
                has_cat=self.has_cat,
                use_mono_bounds=self.use_mono_bounds,
                use_node_masks=self.use_node_masks,
                node_masks=self._node_masks_for_iter(),
                use_cegb=self.use_cegb,
                cegb_coupled=(self.cegb_coupled if self.use_cegb else None),
                cegb_used=(jnp.asarray(self.cegb_used)
                           if self.use_cegb else None),
                use_bundles=ub,
                bundle_cfg=self.bundle_cfg if ub else None,
                bundle_col_bins=(self.bundle_col_bins if ub else 0),
                mono_mode=getattr(self, "mono_mode", "basic"),
                use_cegb_lazy=lazy,
                cegb_lazy=self.cegb_lazy if lazy else None,
                cegb_used_rf=self.cegb_used_rf if lazy else None)
            if lazy:
                tree, row_leaf, self.cegb_used_rf = out
                return tree, row_leaf
            return out
        n_forced = getattr(self, "n_forced", 0)
        ub = getattr(self, "use_bundles", False)
        return grow_tree_leafwise(
            self.bundle_bins_dev if ub else self.bins_dev, gh,
            self.meta, fm, self.params,
            self.max_leaves, self.max_bins, int(self.config.max_depth),
            hist_impl=self.config.tpu_histogram_impl,
            has_cat=self.has_cat,
            use_mono_bounds=self.use_mono_bounds,
            use_node_masks=self.use_node_masks,
            node_masks=self._node_masks_for_iter(),
            n_forced=n_forced,
            forced_leaf=self.forced_leaf if n_forced else None,
            forced_feat=self.forced_feat if n_forced else None,
            forced_thr=self.forced_thr if n_forced else None,
            use_bundles=ub,
            bundle_cfg=self.bundle_cfg if ub else None,
            bundle_col_bins=(self.bundle_col_bins if ub else 0),
            mono_mode=getattr(self, "mono_mode", "basic"))

    def _node_masks_for_iter(self):
        """Per-tree bynode randomness: fold the boosting iteration into the
        sampling key so each tree draws fresh per-node feature subsets."""
        if self.node_masks is None:
            return None
        import jax.random as jrandom
        return self.node_masks._replace(
            key=jrandom.fold_in(self.node_masks.key, self.iter))

    def _node_masks_padded(self):
        """NodeMaskCfg padded to the fused engine's f_oh feature count,
        with the per-tree key fold."""
        if self.node_masks is None:
            return None
        from ..models.learner import NodeMaskCfg
        nm = self._node_masks_for_iter()
        F_oh = self.fused_f_oh
        F = nm.group_feat.shape[1]
        if F == F_oh:
            return nm
        gf = jnp.zeros((nm.group_feat.shape[0], F_oh), bool) \
            .at[:, :F].set(nm.group_feat)
        gwf = jnp.zeros((F_oh,), jnp.int32).at[:F].set(nm.groups_with_f)
        return NodeMaskCfg(gf, gwf, nm.bynode_k, nm.key)

    def _feature_mask(self):
        """Per-tree column sampling (ref: col_sampler.hpp:20)."""
        F = self.train_data.num_features
        frac = float(self.config.feature_fraction)
        mp = getattr(self, "mp", None) is not None
        if frac >= 1.0:
            # mp: host numpy — multi-process jit treats host operands as
            # replicated (every rank computes the identical mask)
            return np.ones(F, bool) if mp else jnp.ones((F,), bool)
        # reference-parity by-tree sampling: one persistent LCG stream,
        # Sample(valid_count, RoundInt(count*fraction)) per tree
        # (ref: col_sampler.hpp:33 GetCnt, :78 ResetByTree)
        k = max(ref_random.round_int(F * frac), min(1, F))
        chosen = self.feat_rng.sample(F, k)
        mask = np.zeros(F, bool)
        mask[chosen] = True
        return mask if mp else jnp.asarray(mask)

    # ---------------------------------------- histogram-plane cuts
    def _mask_onehot(self) -> bool:
        """Screened-out features' one-hot slabs are zeroed in the fused
        kernel (bundle columns interleave logical features, so EFB runs
        keep the full build and screen at the split scan only)."""
        return bool(self.use_screening) \
            and not getattr(self, "fused_bundle_cols", 0)

    def _screening_keep_k(self) -> int:
        F = self.train_data.num_features
        ratio = float(self.config.tpu_screening_keep_ratio)
        return max(1, min(F, int(round(F * ratio))))

    def _screening_explore(self, it: int) -> bool:
        """Exploration rounds keep the full feature set eligible so a
        feature useless early but decisive late re-enters the mask."""
        cfg = self.config
        if it < int(cfg.tpu_screening_warmup):
            return True
        p = int(cfg.tpu_screening_explore_period)
        return p > 0 and it % p == 0

    def _ensure_gain_ema(self):
        F_oh = self.fused_f_oh
        if self._gain_ema_dev is None \
                or self._gain_ema_dev.shape[0] != F_oh:
            self._gain_ema_dev = jnp.zeros((F_oh,), jnp.float32)
        return self._gain_ema_dev

    def _screen_mask_for_iter(self):
        """Sync driver's screening mask (device [F_oh] bool), cached per
        iteration so all k class trees share one mask like the fast
        paths do. None = screening off."""
        if not self.use_screening:
            return None
        cached = self._screen_mask_cache
        if cached is not None and cached[0] == self.iter:
            return cached[1]
        m = _screening_mask_fn(
            self._ensure_gain_ema(),
            jnp.asarray(self._screening_explore(self.iter)),
            self.train_data.num_features, self._screening_keep_k())
        self._screen_mask_cache = (self.iter, m)
        return m

    def _note_tree_gains(self, tree) -> None:
        """Sync driver: accumulate one tree's realized split gains; the
        EMA applies once per iteration (_finish_screen_iter) so the
        update order matches the fast paths' once-per-iteration form."""
        if not self.use_screening:
            return
        g = _tree_gain_vec(tree.split_feature, tree.split_gain,
                           self.fused_f_oh)
        acc = self._iter_gain_acc
        self._iter_gain_acc = g if acc is None else acc + g

    def _finish_screen_iter(self) -> None:
        if not self.use_screening or self._iter_gain_acc is None:
            return
        a = jnp.float32(float(self.config.tpu_screening_ema_alpha))
        self._gain_ema_dev = (a * self._ensure_gain_ema()
                              + (1.0 - a) * self._iter_gain_acc)
        self._iter_gain_acc = None
        self._screen_mask_cache = None

    def _quant_seed(self, it: int, tid: int = 0) -> np.uint32:
        """Stochastic-rounding dither seed: one stream per (iteration,
        class tree), shared by the sync driver / pipelined fast path /
        megastep so all drivers quantize on the same dither streams
        (identical reruns and checkpoint resumes are byte-identical;
        ACROSS drivers ulp-level score differences can still flip
        rounds at the dither threshold — docs/Performance.md
        'Histogram plane')."""
        return np.uint32((it * self.num_tree_per_iteration + tid)
                         & 0xFFFFFFFF)

    def _megastep_aux(self, chunk: int):
        """Per-chunk screening/quantization scan operands: the EMA
        carry, the per-iteration exploration flags, and the per-
        iteration dither seed base (xs)."""
        k = self.num_tree_per_iteration
        ema0 = self._ensure_gain_ema() if self.use_screening else None
        explore_B = None
        if self.use_screening:
            explore_B = jnp.asarray(
                [self._screening_explore(self.iter + b)
                 for b in range(chunk)])
        seeds_B = None
        if self.quant_bits:
            seeds_B = jnp.asarray(
                (np.arange(self.iter, self.iter + chunk,
                           dtype=np.int64) * k) & 0xFFFFFFFF,
                dtype=jnp.uint32)
        return ema0, explore_B, seeds_B

    def _hist_plane_stats(self) -> Dict[str, int]:
        """Deterministic byte model of the histogram plane under the
        CURRENT layout/quantization (ops/layout.hist_plane_bytes): what
        the bench records as hist_bytes_per_iter and the exporter
        scrapes as hist.bytes_per_level."""
        from ..ops.layout import hist_plane_bytes
        kF, kB, caps = self._fused_plane()
        fb_padded = kF * kB
        fb = (self.fused_packed.fb if self.fused_packed is not None
              else fb_padded)
        nch = self.fused_nch
        sp_max = max([8] + [max(8, c) for c in caps])
        tile = min(self.fused_Rp, self._level_build(sp_max)["tile_rows"])
        per_level = hist_plane_bytes(fb, nch, sp_max, self.fused_Rp,
                                     tile, self.quant_bits)
        n_levels = len(caps) + 1   # + the root pass
        return {"bytes_per_level": per_level,
                "bytes_per_iter": per_level * n_levels
                * self.num_tree_per_iteration,
                "fb": fb, "fb_padded": fb_padded, "levels": n_levels}

    def _publish_hist_gauges(self) -> None:
        if not self.use_fused:
            return
        try:
            self._hist_stats = st = self._hist_plane_stats()
        except Exception as e:   # a gauge must never kill training
            log.debug("hist plane stats failed: %s", e)
            return
        tel = self.telemetry
        tel.gauge("hist.bytes_per_level", float(st["bytes_per_level"]))
        tel.gauge("hist.bytes_per_iter", float(st["bytes_per_iter"]))
        tel.gauge("hist.quant_bits", float(self.quant_bits))
        tel.gauge("hist.fb", float(st["fb"]))
        tel.gauge("hist.fb_padded", float(st["fb_padded"]))

    # ------------------------------------------------------------------
    def _to_host_tree(self, tree: TreeArrays, shrinkage: float) -> Tuple[
            HostTree, np.ndarray, np.ndarray]:
        """Device TreeArrays -> HostTree with real thresholds.

        Returns (host_tree, inner_split_feature, row_leaf placeholder unused).
        """
        ds = self.train_data
        # single host round trip for the whole tree struct (per-field
        # np.asarray costs one D2H transfer each)
        tree = jax.device_get(tree)
        nl = int(tree.num_leaves)
        ht = HostTree(nl, shrinkage=1.0)
        ni = max(0, nl - 1)
        sf_inner = np.asarray(tree.split_feature)[:ni]
        tb = np.asarray(tree.threshold_bin)[:ni]
        dl = np.asarray(tree.default_left)[:ni]
        ht.split_feature = np.array(
            [ds.real_feature_index(int(f)) if f >= 0 else 0
             for f in sf_inner], np.int32)
        cat_flag = np.asarray(tree.cat_flag)[:ni]
        cat_mask = np.asarray(tree.cat_mask)[:ni]
        thr = np.zeros(ni, np.float64)
        dt = np.zeros(ni, np.int32)
        cat_boundaries = [0]
        cat_threshold: List[int] = []
        for i in range(ni):
            f = int(sf_inner[i])
            if f < 0:
                continue
            m = ds.mappers[ds.real_feature_index(f)]
            if bool(cat_flag[i]):
                # bin-space left set -> category-value bitset
                # (ref: tree.cpp Tree::SplitCategorical cat_boundaries_)
                cats = [int(m.bin_2_categorical[b])
                        for b in np.nonzero(cat_mask[i])[0]
                        if b < len(m.bin_2_categorical)
                        and m.bin_2_categorical[b] >= 0]
                n_words = (max(cats) // 32 + 1) if cats else 1
                words = [0] * n_words
                for c in cats:
                    words[c // 32] |= (1 << (c % 32))
                thr[i] = len(cat_boundaries) - 1  # index into boundaries
                cat_threshold.extend(words)
                cat_boundaries.append(len(cat_threshold))
                dt[i] = HostTree.make_decision_type(
                    True, False, int(m.missing_type))
            else:
                thr[i] = m.bin_to_value(int(tb[i]))
                dt[i] = HostTree.make_decision_type(
                    False, bool(dl[i]), int(m.missing_type))
        if len(cat_boundaries) > 1:
            ht.cat_boundaries = cat_boundaries
            ht.cat_threshold = cat_threshold
        ht.threshold = thr
        ht.threshold_bin = tb.astype(np.int32)
        ht.decision_type = dt
        ht.left_child = np.asarray(tree.left_child)[:ni].astype(np.int32)
        ht.right_child = np.asarray(tree.right_child)[:ni].astype(np.int32)
        ht.split_gain = np.asarray(tree.split_gain)[:ni].astype(np.float64)
        ht.internal_value = np.asarray(
            tree.internal_value)[:ni].astype(np.float64)
        ht.internal_weight = np.asarray(
            tree.internal_weight)[:ni].astype(np.float64)
        ht.internal_count = np.asarray(
            tree.internal_count)[:ni].astype(np.int64)
        ht.leaf_value = np.asarray(tree.leaf_value)[:nl].astype(np.float64)
        ht.leaf_weight = np.asarray(tree.leaf_weight)[:nl].astype(np.float64)
        ht.leaf_count = np.asarray(tree.leaf_count)[:nl].astype(np.int64)
        ht.leaf_depth = np.asarray(tree.leaf_depth)[:nl].astype(np.int32)
        self._last_cat = (cat_flag, cat_mask) if self.has_cat else None
        # what the drained trees split on (docs/Observability.md)
        self.telemetry.inc("split.nodes", ni)
        self.telemetry.inc("split.cat_nodes", int(np.count_nonzero(cat_flag)))
        return ht, sf_inner

    # ------------------------------------------------------------------
    def _renew_tree_output(self, ht: HostTree, row_leaf: np.ndarray,
                           class_id: int) -> None:
        """Leaf renewal for L1-family objectives (ref:
        serial_tree_learner.cpp:717 RenewTreeOutput; in-bag rows only)."""
        obj = self.objective
        if obj is None or not obj.is_renew_tree_output:
            return
        label = self.train_data.metadata.label
        score = np.asarray(self.scores[class_id], np.float64)
        in_bag = np.asarray(self.bag_weight) > 0
        residual = label.astype(np.float64) - score
        # one argsort groups rows by leaf — O(n log n) instead of the
        # O(num_leaves * n) per-leaf scans of round 1 (VERDICT weak #7)
        sel = np.nonzero(in_bag)[0]
        order = sel[np.argsort(row_leaf[sel], kind="stable")]
        leaves_sorted = row_leaf[order]
        starts = np.searchsorted(leaves_sorted,
                                 np.arange(ht.num_leaves + 1))
        for leaf in range(ht.num_leaves):
            rows = order[starts[leaf]:starts[leaf + 1]]
            if len(rows) == 0:
                continue
            new_out = obj.renew_tree_output(ht.leaf_value[leaf],
                                            residual[rows], rows)
            ht.leaf_value[leaf] = new_out

    def _mp_in_bag_local(self) -> np.ndarray:
        """[local_real] bool in-bag mask for THIS rank's rows."""
        mp = self.mp
        bwl = getattr(self, "_bag_weight_local", None)
        if bwl is not None:             # GOSS keeps a rank-local mask
            return bwl[:mp.local_real] > 0
        bw = getattr(self, "_bag_weight_host", None)
        if bw is not None:              # synced-stream bagging: global
            off = mp.process_index * mp.block
            return bw[off:off + mp.local_real] > 0
        return np.ones(mp.local_real, bool)

    def _mp_avg_leaf_renewal(self, ht: HostTree, rl: np.ndarray,
                             residual: np.ndarray, in_bag: np.ndarray
                             ) -> None:
        """Distributed leaf renewal = the AVERAGE of rank-local
        percentile outputs over the workers that have rows in the leaf —
        the reference's own distributed semantics (NOT an exact global
        percentile): serial_tree_learner.cpp:744-755 computes the local
        RenewTreeOutput then GlobalSum(outputs)/GlobalSum(nonzero).
        ``rl``/``residual``/``in_bag`` are rank-local [local_real]."""
        obj = self.objective
        mp = self.mp
        off = mp.process_index * mp.block   # global row base: the
        # objective's weight vector is the allgathered rank-blocked one
        L = ht.num_leaves
        outputs = np.zeros(L, np.float64)
        nonzero = np.zeros(L, np.int64)
        sel = np.nonzero(in_bag)[0]
        order = sel[np.argsort(rl[sel], kind="stable")]
        starts = np.searchsorted(rl[order], np.arange(L + 1))
        for leaf in range(L):
            rows = order[starts[leaf]:starts[leaf + 1]]
            if len(rows) == 0:
                continue
            outputs[leaf] = obj.renew_tree_output(
                ht.leaf_value[leaf], residual[rows], rows + off)
            nonzero[leaf] = 1
        from jax.experimental import multihost_utils
        allg = np.asarray(multihost_utils.process_allgather(
            np.concatenate([outputs, nonzero.astype(np.float64)])))
        allg = allg.reshape(mp.process_count, 2, L)
        tot_out = allg[:, 0, :].sum(axis=0)
        tot_nz = allg[:, 1, :].sum(axis=0)
        renewed = np.where(tot_nz > 0, tot_out / np.maximum(tot_nz, 1),
                           np.asarray(ht.leaf_value[:L], np.float64))
        ht.leaf_value[:L] = renewed

    def _renew_tree_output_mp(self, ht: HostTree, row_leaf, class_id: int
                              ) -> None:
        mp = self.mp
        rl = mp.local_block(row_leaf)[:mp.local_real]
        score = mp.local_block(self.scores, axis=1)[class_id,
                                                    :mp.local_real]
        label = np.asarray(self.train_data.metadata.label, np.float64)
        residual = label - np.asarray(score, np.float64)
        self._mp_avg_leaf_renewal(ht, rl, residual, self._mp_in_bag_local())

    # ------------------------------------------------------------------
    def _fit_linear_leaves(self, ht: HostTree, row_leaf: np.ndarray,
                           grad, hess) -> None:
        """Per-leaf weighted ridge on the raw path features (ref:
        linear_tree_learner.cpp CalculateLinear, Eq 3 of
        arXiv:1802.05640): coeff = -(X^T H X + lambda I)^-1 X^T g with an
        intercept column; the first tree keeps constants only. Rows with
        NaN in the leaf's features are excluded from the fit (they fall
        back to the constant leaf output at predict time)."""
        raw = self.train_data.raw_data
        if raw is None:
            log.warning("linear_tree needs retained raw data; keeping "
                        "constant leaves")
            return
        ht.is_linear = True
        L = ht.num_leaves
        ht.leaf_const = ht.leaf_value.astype(np.float64).copy()
        ht.leaf_features = [[] for _ in range(L)]
        ht.leaf_coeff = [[] for _ in range(L)]
        if len(self.models) < self.num_tree_per_iteration:
            return  # first tree: constants only (ref: is_first_tree)
        g = np.asarray(grad, np.float64)
        h = np.asarray(hess, np.float64)
        in_bag = np.asarray(self.bag_weight) > 0
        lam = float(self.config.linear_lambda)
        paths = ht.branch_features()
        is_cat = self.train_data.is_categorical   # per USED feature
        for leaf in range(L):
            # paths[] carry inner indices; filter on those BEFORE mapping
            # to the real column ids the raw matrix is indexed by
            inner_feats = [f for f in paths[leaf] if not is_cat[f]]
            feats = [self.train_data.real_feature_index(f)
                     for f in inner_feats]
            if not feats:
                continue
            rows = np.nonzero((row_leaf == leaf) & in_bag)[0]
            if len(rows) < len(feats) + 2:
                continue
            Xl = raw[np.ix_(rows, feats)].astype(np.float64)
            ok = ~np.isnan(Xl).any(axis=1)
            rows = rows[ok]
            if len(rows) < len(feats) + 2:
                continue
            Xl = np.concatenate([Xl[ok], np.ones((len(rows), 1))], axis=1)
            hw = h[rows]
            gw = g[rows]
            XtHX = (Xl * hw[:, None]).T @ Xl
            XtHX[np.diag_indices_from(XtHX)] += lam
            Xtg = Xl.T @ gw
            try:
                coef = -np.linalg.solve(XtHX, Xtg)
            except np.linalg.LinAlgError:
                continue
            if not np.isfinite(coef).all():
                continue
            ht.leaf_features[leaf] = [int(f) for f in feats]
            ht.leaf_coeff[leaf] = [float(c) for c in coef[:-1]]
            ht.leaf_const[leaf] = float(coef[-1])

    # ------------------------------------------------------------------
    def _add_tree_to_score(self, score, bins_dev, dt: _DeviceTree,
                           tree_id: int, scale: float = 1.0,
                           bundle=None):
        """``bundle`` must be self._replay_bundle when ``bins_dev`` holds
        EFB bundle columns (sparse-built datasets), None for logical
        bins."""
        if dt.num_leaves <= 1:
            return score.at[tree_id].add(float(dt.leaf_value[0]) * scale)
        steps = _round_up_pow2(dt.max_depth + 1)
        lv = dt.leaf_value * scale if scale != 1.0 else dt.leaf_value
        new_row = add_tree_score(
            score[tree_id], bins_dev, lv, dt.split_feature, dt.threshold_bin,
            dt.default_left, dt.left_child, dt.right_child,
            self.meta.num_bin, self.meta.missing_type, self.meta.default_bin,
            max_steps=steps, cat_flag=dt.cat_flag, cat_mask=dt.cat_mask,
            bundle=bundle)
        return score.at[tree_id].set(new_row)

    def _train_bundle(self):
        """Replay-decode args for the TRAIN bin matrix (None unless the
        dataset is sparse-built)."""
        return getattr(self, "_replay_bundle", None)

    def _train_bins_replay(self):
        """Bin matrix for score add/subtract replay (rollback, DART
        drop/normalize): the replicated copy single-process, the
        row-sharded global matrix under multi-process (per-row routing
        partitions cleanly over the mesh)."""
        if getattr(self, "mp", None) is not None:
            self._place_par_data()
            if self.bins_par is None:
                # bundled mp runs place only the bundle matrix; replay
                # decodes logical bins, so place those on first use
                self.bins_par = self.mp.shard_local(
                    np.asarray(self.train_data.bins))
            return self.bins_par
        return self.bins_dev

    def _valid_bundle(self, vi: int):
        """Replay-decode args of validation set ``vi``'s bins (None for
        logical bins); a set stored in bundle columns adds its
        conflicting rows (_valid_exact)."""
        if self.valid_data[vi].prebundled is None:
            return None
        return self._replay_bundle + self._valid_exact(vi)

    def _valid_exact(self, vi: int):
        """(rows [M], logical bins [M, F]) on the device of the rows of
        validation set ``vi`` that its bundle columns cannot hold
        (TpuDataset.from_sparse's ``exact_rows``), whose leaves are
        walked over these bins; None for a set stored in logical bins.
        M is a power of two of at least 8, the rows past the set's own
        are padding (row index past every set's end), so the step's
        shapes do not move with how many rows conflict."""
        exact = getattr(self.valid_data[vi], "exact_rows", None)
        if exact is None:
            return None
        got = self._valid_exact_dev.get(vi)
        if got is None:
            rows, bins = exact
            M = max(8, 1 << (max(len(rows), 1) - 1).bit_length())
            rows_p = np.full(M, np.iinfo(np.int32).max, np.int32)
            rows_p[:len(rows)] = rows
            bins_p = np.zeros((M, bins.shape[1]), bins.dtype)
            bins_p[:len(rows)] = bins
            got = self._valid_exact_dev[vi] = (jnp.asarray(rows_p),
                                               jnp.asarray(bins_p))
        return got

    # ------------------------------------------------------------------
    # Async pipelined fast path.
    #
    # Every host synchronisation stalls the device queue; the reference's
    # per-tree host bookkeeping (gbdt.cpp:371 TrainOneIter is all host
    # code) translated naively into 2-3 blocking syncs per tree
    # (int(num_leaves), device_get(tree), score-update data dependency).
    # Instead: ONE jit-compiled step per iteration
    # (gradients -> gh pack -> tree growth -> on-device score update) with
    # NO host read-back; the device TreeArrays are queued and materialised
    # as HostTrees in batches ("drained") only when something actually
    # needs the host model list. Device->host copies are started
    # asynchronously at enqueue time so drains mostly find the data ready.
    _FAST_SYNC_EVERY = 32

    def _sample_plan(self):
        """ops/goss.GossPlan of a job whose trees, from the plan's
        ``first_iter`` on, grow on a sample of the rows that the step
        itself draws and compacts (GOSS overrides); its ``evict_reason``
        names what the job has that the compact matrix does not compose
        with. None: the job does not sample."""
        return None

    def _step_sample(self, it: int):
        """The plan when iteration ``it`` of a fast-path job is a sampled
        one, else None."""
        plan = self._sample_plan()
        return plan if plan is not None and it >= plan.first_iter else None

    def _fast_path_ok(self) -> bool:
        """Per-tree host work forces the synchronous path: subclass drivers
        (DART drop-sets, RF), leaf renewal, linear leaves,
        CEGB feature accounting, forced splits, and per-node mask key
        folding. Valid sets stay on the fast path since round 3: their
        score updates run in-jit from the device TreeArrays
        (_update_valid_from_trees) and eval pulls scalars, not matrices."""
        if self.telemetry.enabled \
                and self._tel_granularity() == "section":
            # per-SECTION attribution blocks on each phase — only the
            # synchronous driver can do that honestly (same reason the
            # reference's TIMETAG is sync). batch/iteration granularity
            # attribute at coarser sync points and keep the fast path
            # (docs/Performance.md). Checked outside the cache so a
            # callback can enable telemetry mid-training.
            self._report_eviction("config:telemetry_granularity=section")
            return False
        if self._fast_ok_cache is None:
            obj = self.objective
            # the row-sharded distribution modes (data, voting) ride the
            # fast path on the FUSED engine since round 12: the
            # shard_map growers compose with the pipelined step and the
            # megastep scan, and multi-process runs (one global mesh
            # over the pod) keep the same trace — the histogram psum /
            # vote exchange already lives inside the jit, so no
            # per-iteration host collective remains (tpu_mp_megastep=
            # false restores the pre-round-12 sync eviction for A/B).
            # feature-parallel stays on the sync driver: its contract is
            # bit-equality with the serial model (replicated rows), and
            # the fast path's f32 leaf-value shrink would break it.
            plan = self._sample_plan()
            self._fast_ok_cache = bool(
                # (DART's drop sets and RF's fixed gradients are per-tree
                # host work; GOSS draws its sample inside the step)
                (type(self) is GBDT
                 or (plan is not None and plan.evict_reason is None))
                and bool(self.config.tpu_fast_path)
                and self.use_fused
                and self.parallel_mode in ("serial", "data", "voting")
                and (getattr(self, "mp", None) is None
                     or bool(getattr(self.config, "tpu_mp_megastep",
                                     True)))
                and obj is not None
                and not obj.is_renew_tree_output
                and not bool(self.config.linear_tree)
                and not getattr(self, "use_cegb", False)
                and not getattr(self, "n_forced", 0)
                and not self.use_node_masks
                and all(self.class_need_train))
        if not self._fast_ok_cache and self.telemetry.enabled:
            self._report_eviction(self._fast_path_reason()
                                  or "fast_path:unknown")
        return self._fast_ok_cache

    def _fast_path_reason(self) -> Optional[str]:
        """The SPECIFIC feature evicting training off the pipelined fast
        path, or None when eligible — docs/Performance.md used to tell
        users to guess; the megastep_evicted event names it instead."""
        if self.telemetry.enabled \
                and self._tel_granularity() == "section":
            return "config:telemetry_granularity=section"
        plan = self._sample_plan()
        if type(self) is not GBDT and plan is None:
            return f"boosting:{self.name}"
        if not bool(self.config.tpu_fast_path):
            return "config:tpu_fast_path=false"
        if not self.use_fused:
            if getattr(self, "mp", None) is not None:
                # the XLA growers' sync driver is the only multi-process
                # path off the fused engine (the megastep composes with
                # the shard_map growers through grow_tree_fused only)
                return "engine:multiproc_xla_growers"
            return f"engine:{self.config.tpu_engine}"
        if plan is not None and plan.evict_reason is not None:
            return plan.evict_reason
        if getattr(self, "mp", None) is not None \
                and not bool(getattr(self.config, "tpu_mp_megastep", True)):
            return "config:tpu_mp_megastep=false"
        if self.parallel_mode not in ("serial", "data", "voting"):
            # feature-parallel: bit-equality with the serial model is its
            # contract (replicated rows) — the fast path's f32 leaf-value
            # shrink would break it, so it stays on the sync driver
            return f"tree_learner:{self.parallel_mode}"
        obj = self.objective
        if obj is None:
            return "fobj"
        if obj.is_renew_tree_output:
            return f"objective_leaf_renewal:{obj.name}"
        if bool(self.config.linear_tree):
            return "config:linear_tree"
        if getattr(self, "use_cegb", False):
            return "config:cegb"
        if getattr(self, "n_forced", 0):
            return "config:forcedsplits_filename"
        if self.use_node_masks:
            return "config:interaction_constraints/feature_fraction_bynode"
        if not all(self.class_need_train):
            return f"objective_class_skip:{obj.name}"
        return None

    def _report_eviction(self, feature: str, **attrs) -> None:
        """Structured `megastep_evicted` telemetry event naming the
        specific evicting feature (callback / feval / fobj / config
        key), emitted once per distinct reason per run."""
        if not self.telemetry.enabled or feature in self._evict_reported:
            return
        self._evict_reported.add(feature)
        self.telemetry.event("megastep_evicted", iteration=self.iter,
                             feature=feature, **attrs)

    def _fast_tree_depth_bound(self) -> int:
        """Static routing-step bound for trees grown by the fused engine:
        depth cannot exceed the number of scheduled level passes."""
        return len(self._fused_plane()[2]) + 1

    def _fused_plane(self) -> Tuple[int, int, Tuple[int, ...]]:
        """(columns, bins a column, level caps) of the fused kernels'
        plane: the EFB bundle columns where the job is bundled, else the
        padded features; the caps are the grower's level schedule
        (frontier2.level_caps under ops/fused_level.max_slot_cap)."""
        from ..models.frontier2 import level_caps
        from ..ops.fused_level import max_slot_cap
        kF = self.fused_bundle_cols or self.fused_f_oh
        kB = (self.fused_bundle_col_bins if self.fused_bundle_cols
              else self.fused_Bp)
        caps = level_caps(self.max_leaves, int(self.config.max_depth),
                          int(self.config.tpu_extra_levels),
                          slot_cap=max_slot_cap(kF * kB, self.fused_nch))
        return kF, kB, caps

    def _level_build(self, Sp: int) -> dict:
        """ops/fused_level.level_build of this job's ``level_pass`` at
        ``Sp`` slots."""
        from ..models.frontier2 import route_form
        from ..ops.fused_level import level_build
        kF, kB, _ = self._fused_plane()
        bins_form = route_form(self.has_cat, self.fused_bundle_cols,
                               kB)[0] == "bins"
        return level_build(bins_form, Sp, kF * kB, self.fused_nch,
                           max(kF, 8), wide_bins=kB > 256,
                           has_cat=self.has_cat, quant=self.quant_bits > 0,
                           bundled=bins_form and self.fused_bundle_cols > 0)

    def _valid_route_reason(self, vi: int) -> Optional[str]:
        """Why validation set ``vi`` keeps the gather walk, or None when
        the training kernels can route it: the fast paths replay a route
        log only from the fused grower, and the log's tables are written
        over the TRAINING matrix's columns, so the set must be stored in
        the same ones — logical bins beside an unbundled training
        matrix, or the training set's own sparse-built bundle columns.
        Dense EFB (bundles made from a logical-bin training set) leaves
        the validation set in logical bins: the walk stays for it."""
        reason = self._fast_path_reason()
        if reason is not None:
            return "no_route_log:" + reason
        valid_pre = self.valid_data[vi].prebundled is not None
        if self.fused_bundle_cols:
            train = ("prebundled" if self.train_data.prebundled is not None
                     else "efb")
            same = train == "prebundled" and valid_pre
        else:
            train, same = "logical", not valid_pre
        if same:
            return None
        return "layout:train=%s,valid=%s" % (
            train, "prebundled" if valid_pre else "logical")

    def _valid_route(self, vi: int) -> Tuple[Optional[jax.Array],
                                             Optional[str]]:
        """(passenger matrix, None) when validation set ``vi`` is routed
        by the training kernels, (None, reason) when it keeps the gather
        walk. The passenger is the set's second device layout, [Fp, Rvp]
        in the training matrix's dtype, feature order and 2048-row
        padding, built once per fused layout by the training matrix's own
        builder; the row-major ``valid_bins`` stay for what replays host
        trees (rollback, a continued model, recovery). Says which path
        the set took once per run: the ``valid.route_*_sets`` counters
        and a ``valid_route`` event (not a ``degrade``: nothing that was
        asked for is lost, both paths give the same bits), with
        ``exact_rows`` where the set is stored in the training bundles:
        the rows whose values conflict there, whose leaves are walked
        over their logical bins (_valid_exact)."""
        route = self._valid_routes.get(vi)
        if route is None:
            reason = self._valid_route_reason(vi)
            mat = None
            if reason is None:
                Rv = int(self.valid_bins[vi].shape[0])
                with self.telemetry.timed("valid/pack", rows=Rv) as sp:
                    mat = _fused_layout_T(
                        self.valid_bins[vi], self.fused_bins_T.shape[0],
                        -(-Rv // 2048) * 2048, self.fused_bins_T.dtype,
                        self.fused_packed.feat_order
                        if self.fused_packed is not None else None)
                    sp.sync(mat)
            route = self._valid_routes[vi] = (mat, reason)
        tel = self.telemetry
        if tel.enabled and vi not in self._valid_route_said:
            self._valid_route_said.add(vi)
            path = "gather" if route[1] else "kernel"
            tel.inc("valid.route_%s_sets" % path)
            exact = getattr(self.valid_data[vi], "exact_rows", None)
            tel.event("valid_route", iteration=self.iter,
                      valid_set=self.valid_names[vi], path=path,
                      **({"reason": route[1]} if route[1] else {}),
                      **({"exact_rows": len(exact[0])} if exact is not None
                         else {}))
        return route

    def _route_form(self) -> None:
        """Say the kernels' forms of a step that grows trees on the fused
        engine, once per run and distinct payload; called where each
        such step is built. The ROUTING form (models/frontier2.route_form
        decides; this only tells): counter ``route.form_<form>`` and a
        ``route_form`` event; a job with a categorical column in the bins
        form, whose kernels test a slot's bin SET, adds ``membership:
        true`` and the counter ``route.cat_membership``. How
        ``level_pass`` BUILDS its one-hot, which
        follows from it (ops/fused_level.level_build decides): counter
        ``level.build_<form>`` and a ``level_build`` event with the slab
        size and, per distinct slot count of the level schedule, the row
        tile and which operand of the histogram dot the MXU streams
        (``dot``); the counters ``level.dot_stream_channels`` /
        ``level.dot_stream_onehot`` count the histogram passes of one
        full tree in each order: the root and every level of the static
        schedule up to the one that spends the leaf budget, which only
        routes (the ``tpu_extra_levels`` passes behind it run only for
        a skewed tree and are not counted). The ``reason`` of a
        ``scratch`` build is the table form's. A job stored as EFB bundle
        columns says its layout beside them, once: an ``efb_layout``
        event with the logical features, the bundle columns, the largest
        column's bins, the values the encode dropped (``conflict_rows``)
        and the form (``bins``: the kernels decode the bundle values by
        window)."""
        from ..models.frontier2 import route_form
        _, kB, caps = self._fused_plane()
        said = route_form(self.has_cat, self.fused_bundle_cols, kB)
        tel = self.telemetry
        if not tel.enabled or said in self._route_form_said:
            return
        self._route_form_said.add(said)
        form, reason = said
        why = {"reason": reason} if reason else {}
        tel.inc("route.form_%s" % form)
        membership = self.has_cat and form == "bins"
        if membership:
            tel.inc("route.cat_membership")
        tel.event("route_form", iteration=self.iter, form=form, **why,
                  **({"membership": True} if membership else {}))
        if self.fused_bundle_cols:
            tel.event("efb_layout", iteration=self.iter,
                      **self._efb_layout, form=form)
        builds = {sp: self._level_build(sp)
                  for sp in sorted({8} | {max(8, c) for c in caps})}
        build = dict(builds[8])
        build["tile_rows"] = {str(sp): min(self.fused_Rp, b["tile_rows"])
                              for sp, b in builds.items()}
        build["dot"] = {str(sp): b["dot"] for sp, b in builds.items()}
        tel.inc("level.build_%s" % build["form"])
        full_tree = caps[:len(caps) - int(self.config.tpu_extra_levels)]
        for sp in [8] + [max(8, c) for c in full_tree[:-1]]:
            tel.inc("level.dot_stream_%s" % builds[sp]["dot"])
        tel.event("level_build", iteration=self.iter, **build, **why)
        if self.has_cat:
            # the columns the categorical search runs over, once per job
            ds = self.train_data
            cols = [int(ds.real_feature_index(j))
                    for j in np.flatnonzero(ds.is_categorical)]
            tel.event("cat_layout", iteration=self.iter, columns=cols,
                      bins=[int(ds.mappers[c].num_bin) for c in cols])

    def _wants_route_log(self) -> bool:
        """Some validation set is routed by the kernels: the steps that
        grow trees keep their route logs."""
        return any(self._valid_route(vi)[1] is None
                   for vi in range(len(self.valid_bins)))

    def _valid_operands(self) -> Tuple:
        """Per validation set, the matrix its traced score update reads:
        the passenger on the kernel path, the row-major bins on the
        gather path; a set stored in bundle columns gives (matrix,
        *_valid_exact)."""
        ops = []
        for vi, vb in enumerate(self.valid_bins):
            m = self._valid_route(vi)[0]
            exact = self._valid_exact(vi)
            m = vb if m is None else m
            ops.append(m if exact is None else (m,) + exact)
        return tuple(ops)

    def _make_valid_apply(self, vi: int):
        """Traced valid-score update of validation set ``vi`` for one
        iteration's stacked [k, ...] TreeArrays: the ONE body both the
        per-iteration fast path (_update_valid_from_trees jits it per
        valid set) and the megastep scan inline — shared so the two
        paths cannot drift apart.

        ``apply_trees(vscore, vmat, trees, logs)`` finds each row's leaf
        one of two ways and ends both the same way, so leaves and scores
        are bit-identical between them:

        - kernel path (``logs``: the k route logs of
          ``grow_tree_fused(route_log=True)``; ``vmat``: the set's
          passenger matrix): the tables that routed the training rows
          route the validation rows, one ``route_pass`` per level the
          tree actually grew (models/frontier2.replay_route_log), in
          the grower's own routing form (frontier2.route_form: a log
          of slot tables alone in the bins form);
        - gather path (``logs`` unused; ``vmat``: the row-major bins):
          ops/predict.route_rows_to_leaves walks the tree node by node,
          a static ``_fast_tree_depth_bound()`` levels of row-length
          gathers. Serves the sets whose storage the tables do not
          describe (``_valid_route_reason``);
        - a set stored in bundle columns (``vmat`` = (matrix, rows,
          logical bins): _valid_operands) has the leaves of the rows its
          bundles cannot hold walked over their logical bins, as the
          gather path walks;
        - then one ``table_lookup`` of the shrunk leaf values and the
          add, the training scores' own formula (tree_score_delta). The
          product leaf_value * shrink is rounded before the lookup on
          both paths: with the add next to it a backend that contracts
          the two into one fused multiply-add rounds differently (XLA's
          CPU backend did, in ops/predict.add_tree_score's fusion)."""
        from ..models.frontier2 import replay_route_log
        from ..ops.fused_level import table_lookup
        from ..ops.predict import route_rows_to_leaves
        k = self.num_tree_per_iteration
        shrink = jnp.float32(self.shrinkage_rate)
        steps = self._fast_tree_depth_bound()
        meta = self.meta
        has_cat = self.has_cat
        kernel = self._valid_route(vi)[1] is None
        bundle = (self._replay_bundle
                  if self.valid_data[vi].prebundled is not None else None)
        n_valid = int(self.valid_bins[vi].shape[0])
        interp = self.fused_interpret

        # (scoped themselves: a shard_map region does not inherit the
        # scope it is called under)
        scope = jax.named_scope("lgbm.valid_apply")

        @scope
        def replay(vmat, log):
            return replay_route_log(
                vmat, log, n_valid,
                num_bins=(self.fused_bundle_col_bins
                          if self.fused_bundle_cols else self.fused_Bp),
                f_oh=self.fused_bundle_cols or self.fused_f_oh,
                interpret=interp, packed=self.fused_packed,
                has_cat=has_cat, bundled=self.fused_bundle_cols > 0)

        @scope
        def lookup(leaf_T, leaf_value):
            return table_lookup(leaf_T, leaf_value,
                                interpret=interp)[0, :n_valid]
        if self.parallel_mode in ("data", "voting"):
            # the set, the log and the leaves are replicated over the
            # mesh: every chip routes all of the set's rows, in regions
            # of the kernels' own so the partitioner has nothing to
            # decide about them
            from jax.sharding import PartitionSpec as P
            replay = _shard_map(replay, mesh=self.mesh,
                                in_specs=(P(), P()), out_specs=P(),
                                check_vma=False)
            lookup = _shard_map(lookup, mesh=self.mesh,
                                in_specs=(P(), P()), out_specs=P(),
                                check_vma=False)

        @scope
        def apply_trees(vscore, vmat, trees, logs=None):
            exact = None
            if isinstance(vmat, tuple):
                vmat, *exact = vmat

            def walk(bins, tid, bundle):
                return route_rows_to_leaves(
                    bins, trees.split_feature[tid],
                    trees.threshold_bin[tid], trees.default_left[tid],
                    trees.left_child[tid], trees.right_child[tid],
                    meta.num_bin, meta.missing_type, meta.default_bin,
                    max_steps=steps,
                    cat_flag=trees.cat_flag[tid] if has_cat else None,
                    cat_mask=trees.cat_mask[tid] if has_cat else None,
                    bundle=bundle)
            for tid in range(k):
                if kernel:
                    leaf_T = replay(vmat, logs[tid])
                else:
                    leaf_T = walk(vmat, tid, bundle)[None, :]
                if exact is not None:
                    leaf_T = leaf_T.at[0, exact[0]].set(
                        walk(exact[1], tid, None), mode="drop")
                new_row = vscore[tid] + lookup(
                    leaf_T, trees.leaf_value[tid] * shrink)
                # dried class: zero contribution (matches the training
                # score handling)
                new_row = jnp.where(trees.num_leaves[tid] > 1, new_row,
                                    vscore[tid])
                vscore = vscore.at[tid].set(new_row)
            return vscore
        return apply_trees

    def _update_valid_from_trees(self, trees, logs=None) -> None:
        """In-jit valid-score updates straight from the stacked device
        TreeArrays — no HostTree materialisation, no per-iteration sync
        (ref: gbdt.cpp:493 UpdateScore over valid ScoreUpdaters).
        ``logs``: the trees' route logs, which the pipelined fast step
        keeps when some set is routed by the kernels
        (_wants_route_log)."""
        if not self.valid_scores:
            return
        if not getattr(self, "_valid_upd_fns", None):
            self._valid_upd_fns = {}
        operands = self._valid_operands()
        for vi in range(len(self.valid_scores)):
            if vi not in self._valid_upd_fns:
                # the old valid-score buffer is dead the moment the
                # update returns — donate it so XLA writes in place
                # instead of allocating a fresh [k, n_valid] f32 every
                # iteration
                self._valid_upd_fns[vi] = jax.jit(
                    self._make_valid_apply(vi), donate_argnums=_donate(0))
            self.telemetry.inc("train.dispatches")
            self.valid_scores[vi] = self._valid_upd_fns[vi](
                self.valid_scores[vi], operands[vi], trees, logs)

    def _make_fused_tree_loop(self, sample=None):
        """Traced per-iteration tree-growing core: gh pack -> fused
        growth -> score delta for each of the k class trees, returning
        the updated scores, the stacked [k, ...] TreeArrays, the gain
        EMA, the k trees' route logs (None unless a validation set is
        routed by the kernels: _valid_route) and the sample's traced
        counts (None in a step that does not sample). The ONE body the
        per-iteration fast step and the megastep scan share, so the
        megastep stays bit-identical to the fast path by
        construction.

        ``sample`` (ops/goss.GossPlan; GOSS from its first sampled
        iteration on): the body draws the iteration's sample, grows each
        tree on the COMPACT matrix of the in-bag rows and updates the
        scores of all rows by replaying the tree's route log over the
        full matrix (``_sampled_growth``). Such a step has one operand
        more, the iteration ``it`` (the draw's counter), and one result
        more, ``counts``: the traced (top, other) rows of the sample. It
        is built apart from the plain step, which lowers as it did."""
        from ..models.frontier2 import grow_tree_fused, tree_score_delta
        from ..ops.fused_level import pack_gh, table_lookup
        k = self.num_tree_per_iteration
        n = self.num_data
        pad = self.fused_Rp - n
        shrink = jnp.float32(self.shrinkage_rate)
        max_depth = int(self.config.max_depth)
        extra = int(self.config.tpu_extra_levels)
        interp = self.fused_interpret

        # distributed modes on the fast path (data/voting — feature
        # keeps the sync driver, its contract is bit-equality with the
        # serial model): the grow + leaf-value lookup run inside a
        # shard_map region (rows sharded, per-level histogram psum /
        # vote exchange inside grow_tree_fused); the [L]-sized tree
        # comes out replicated, the per-row delta row-sharded. Under a
        # multi-process layout the SAME shard_map spans the global
        # ICI/DCN mesh — the collectives cross processes inside the
        # jit, so the megastep scan composes unchanged (ref:
        # data_parallel_tree_learner.cpp:185 reduces the FAST engine's
        # histograms — the flagship kernel stays in play on the pod)
        mode = self.parallel_mode
        par = mode in ("data", "voting")
        quant = self.quant_bits
        screening = self.use_screening
        mask_oh = self._mask_onehot()
        packed = self.fused_packed
        route_log = self._wants_route_log() or sample is not None
        self._route_form()
        if sample is not None:
            draw_sample, compact = self._sampled_growth(sample)
        if quant:
            from ..ops.fused_level import pack_gh_quant
        if screening:
            alpha = jnp.float32(float(self.config.tpu_screening_ema_alpha))
            keep_k = self._screening_keep_k()
            F_real = self.train_data.num_features
        F_oh = self.fused_f_oh
        if par:
            from jax.sharding import PartitionSpec as P
            axis = self.axis_name
            top_k = int(self.config.top_k) if mode == "voting" else 0

            def grow_one(bins_T, gh_T, fm_pad, *qrest):
                tree, row_leaf, *log = grow_tree_fused(
                    bins_T, gh_T, self.fused_meta, fm_pad,
                    self.params, self.max_leaves, self.fused_Bp,
                    self.fused_f_oh, num_rows=0, nch=self.fused_nch,
                    max_depth=max_depth, extra_levels=extra,
                    has_cat=self.has_cat,
                    use_mono_bounds=self.use_mono_bounds,
                    bundle_cols=self.fused_bundle_cols,
                    bundle_col_bins=self.fused_bundle_col_bins,
                    bundle_cfg=self.fused_bundle_cfg,
                    interpret=interp, psum_axis=axis,
                    mono_mode=getattr(self, "mono_mode", "basic"),
                    parallel_mode=mode, top_k=top_k,
                    quant_bits=quant, packed=packed,
                    mask_onehot=mask_oh,
                    gh_scales=qrest[0] if quant else None,
                    route_log=route_log)
                with jax.named_scope("lgbm.score_update"):
                    delta = table_lookup(row_leaf[None, :],
                                         tree.leaf_value * shrink,
                                         interpret=interp)[0]
                return (tree, delta, *log)
            # (the log is made from the global splits, like the tree:
            # it leaves the region replicated)
            grow_one_sharded = _shard_map(
                grow_one, mesh=self.mesh,
                in_specs=(P(None, axis), P(None, axis), P())
                + ((P(),) if quant else ()),
                out_specs=(P(), P(axis)) + ((P(),) if route_log else ()),
                check_vma=False)

        def grow_k_trees(bins_T, scores, grad, hess, bag_weight, fm_pads,
                         ema=None, explore=None, seed=None, it=None):
            smask = None
            if screening:
                # EMA-FS screening (arxiv 2606.26337): one in-trace
                # top-k mask per iteration over the gain-EMA carry,
                # composed with the feature_fraction masks; exploration
                # rounds keep the mask fully open
                smask = _screening_mask_fn(ema, explore, F_real, keep_k)
            trees, logs = [], []
            grow_rows, grow_bins, counts = n, bins_T, None
            if sample is not None:
                mult, bag_weight, counts = draw_sample(grad, hess, it)
                grow_rows = sample.bag_rows
            for tid in range(k):
                fm_t = fm_pads[tid] & smask if screening \
                    else fm_pads[tid]
                scales = None
                with jax.named_scope("lgbm.gh_pack"):
                    w_g = bag_weight if sample is None else mult
                    g_p = jnp.pad(grad[tid] * w_g, (0, pad))
                    h_p = jnp.pad(hess[tid] * w_g, (0, pad))
                    w_p = jnp.pad(bag_weight, (0, pad))
                    if quant:
                        gh_T, scales = pack_gh_quant(
                            g_p, h_p, w_p, quant,
                            seed + jnp.uint32(tid))
                    else:
                        gh_T = pack_gh(g_p, h_p, w_p, self.fused_nch)
                if sample is not None:
                    grow_bins, gh_T = compact(bins_T, gh_T, bag_weight)
                if par:
                    args = (bins_T, gh_T, fm_t) \
                        + ((scales,) if quant else ())
                    # (the shard_map boundary is the grower's; the lookup
                    # inside names itself: the innermost lgbm. scope wins)
                    with jax.named_scope("lgbm.grow"):
                        tree, delta, *log = grow_one_sharded(*args)
                else:
                    tree, row_leaf, *log = grow_tree_fused(
                        grow_bins, gh_T, self.fused_meta, fm_t,
                        self.params, self.max_leaves, self.fused_Bp,
                        self.fused_f_oh, num_rows=grow_rows,
                        nch=self.fused_nch,
                        max_depth=max_depth, extra_levels=extra,
                        has_cat=self.has_cat,
                        use_mono_bounds=self.use_mono_bounds,
                        bundle_cols=self.fused_bundle_cols,
                        bundle_col_bins=self.fused_bundle_col_bins,
                        bundle_cfg=self.fused_bundle_cfg,
                        interpret=interp,
                        mono_mode=getattr(self, "mono_mode", "basic"),
                        quant_bits=quant, packed=packed,
                        mask_onehot=mask_oh, gh_scales=scales,
                        route_log=route_log)
                with jax.named_scope("lgbm.score_update"):
                    if sample is not None:
                        # the tree was grown on the sample: the leaves
                        # of ALL rows, out-of-bag too, from its route log
                        row_leaf = self._replay_train_rows(bins_T, log[0])
                    if par:
                        # a dried-up class (no split found) contributes
                        # NOTHING: the sync path appends a zero constant
                        # tree for it (gbdt.cpp:421-437 beyond the first
                        # iteration) and keeps boosting the other classes
                        delta = jnp.where(tree.num_leaves > 1, delta[:n],
                                          0.0)
                    else:
                        delta = tree_score_delta(tree, row_leaf, shrink,
                                                 num_rows=n,
                                                 interpret=interp)
                    scores = scores.at[tid].add(delta)
                trees.append(tree)
                logs.extend(log)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *trees)
            if screening:
                # once-per-iteration EMA update from the realized split
                # gains the trees materialize (same order as the sync
                # driver's _finish_screen_iter)
                gvec = _tree_gain_vec(stacked.split_feature,
                                      stacked.split_gain, F_oh)
                ema = alpha * ema + (1.0 - alpha) * gvec
            return scores, stacked, ema, tuple(logs) or None, counts
        return grow_k_trees

    def _replay_train_rows(self, bins_T, log) -> jax.Array:
        """[Rp] leaf of every training row in the tree whose route log is
        ``log`` (a tree grown on a sample of the rows)."""
        from ..models.frontier2 import replay_route_log
        return replay_route_log(
            bins_T, log, self.num_data,
            num_bins=(self.fused_bundle_col_bins
                      if self.fused_bundle_cols else self.fused_Bp),
            f_oh=self.fused_bundle_cols or self.fused_f_oh,
            interpret=self.fused_interpret, packed=self.fused_packed,
            has_cat=self.has_cat, bundled=self.fused_bundle_cols > 0)[0]

    def _sampled_growth(self, sample):
        """The two traced stages a sampled step adds (ops/goss.py), under
        ``lgbm.sample``: ``draw(grad, hess, it)`` -> (multiplier on
        gradient and hessian [n], in-bag 0/1 [n], (top, other) counts);
        ``compact(bins_T, gh_T, inbag)`` -> the in-bag columns of both
        at the front of [.., capacity]. Says the layout once per job."""
        from ..ops import goss
        # (compact_rows' precondition: the draw's count is exact)
        assert 0 <= sample.capacity - sample.bag_rows < goss.ROW_ALIGN
        tel = self.telemetry
        if tel.enabled and not self._goss_layout_said:
            self._goss_layout_said = True
            tel.gauge("goss.capacity", float(sample.capacity))
            tel.event("goss_layout", iteration=self.iter, n=sample.n,
                      top_k=sample.top_k, other_k=sample.other_k,
                      capacity=sample.capacity,
                      first_sampled_iteration=sample.first_iter,
                      compaction="staircase",
                      tile_rows=goss.compact_tile(     # (8 channel rows)
                          self.fused_bins_T.shape[0],
                          self.fused_bins_T.dtype.itemsize, 8,
                          self.fused_Rp))
        interp = self.fused_interpret

        @jax.named_scope("lgbm.sample")
        def draw(grad, hess, it):
            with jax.named_scope("select"):
                abs_gh = jnp.sum(jnp.abs(grad * hess), axis=0)
            top, other = goss.goss_sample(abs_gh, it, sample.seed,
                                          sample.top_k, sample.other_k)
            with jax.named_scope("draw"):
                mult, inbag = goss.sample_weights(top, other,
                                                  sample.multiply)
                counts = jnp.stack([jnp.sum(top.astype(jnp.int32)),
                                    jnp.sum(other.astype(jnp.int32))])
            return mult, inbag, counts

        @jax.named_scope("lgbm.sample")
        def compact(bins_T, gh_T, inbag):
            with jax.named_scope("compact"):
                return goss.compact_rows(bins_T, gh_T, inbag > 0,
                                         capacity=sample.capacity,
                                         interpret=interp)
        return draw, compact

    def _make_fast_step(self, sample=None):
        obj = self.objective
        in_jit_grads = (obj is not None
                        and obj.supports_traced_gradients())
        grow_k = self._make_fused_tree_loop(sample)

        # bins_T/gradient operands are ARGUMENTS, not closures: a
        # closed-over device array of O(rows) size would be embedded in
        # the lowered program as a constant (bins alone: 336 MB of HLO at
        # 10.5M rows) and stall remote compilation. Objectives exposing
        # the gradient_operands protocol compute gradients IN-jit (XLA
        # fuses them with the gh pack); others compute eagerly outside.
        # The score matrix is donated: the previous buffer dies at the
        # call, so XLA updates the [k, n] f32 in place instead of
        # round-tripping a fresh allocation through HBM each iteration.
        ext = bool(self.use_screening or self.quant_bits)
        if not ext:
            # (``it`` / ``counts``: a sampled step's; None in the plain
            # step, where they are no operand and no result)
            def step(bins_T, scores, grad_in, hess_in, bag_weight,
                     fm_pads, it=None):
                if in_jit_grads:
                    with jax.named_scope("lgbm.gradients"):
                        grad, hess = obj.gradients_from(scores, grad_in)
                else:
                    grad, hess = grad_in, hess_in
                scores, stacked, _, logs, counts = grow_k(
                    bins_T, scores, grad, hess, bag_weight, fm_pads, it=it)
                return scores, stacked, logs, counts
            return jax.jit(step, donate_argnums=_donate(1))

        def step_ext(bins_T, scores, grad_in, hess_in, bag_weight,
                     fm_pads, ema, explore, seed, it=None):
            if in_jit_grads:
                with jax.named_scope("lgbm.gradients"):
                    grad, hess = obj.gradients_from(scores, grad_in)
            else:
                grad, hess = grad_in, hess_in
            scores, stacked, ema, logs, counts = grow_k(
                bins_T, scores, grad, hess, bag_weight, fm_pads, ema,
                explore, seed, it)
            return scores, stacked, logs, ema, counts
        return jax.jit(step_ext, donate_argnums=_donate(1))

    def _train_one_iter_fast(self) -> bool:
        tel = self.telemetry
        # iteration granularity: the fast path stays (one jit dispatch),
        # but each iteration is synced and timed whole — no per-section
        # split, no eviction to the synchronous driver
        per_iter = tel.enabled and self._tel_granularity() == "iteration"
        it = self.iter
        if per_iter:
            w0 = tel.wall_now()
            t0 = time.perf_counter()
        with timer.section("GBDT::TrainOneIterFast"):
            stop = self._fast_iter_body()
        if per_iter:
            jax.block_until_ready(self.scores)
            dt = time.perf_counter() - t0
            nl = []
            if self._pending:
                nl = [int(x) for x in
                      np.asarray(self._pending[-1][0].num_leaves)]
            tel.begin_iteration(it)
            tel.section("fast_iteration", dt, wall_start=w0)
            tel.end_iteration(it, num_leaves=nl, engine="fused",
                              mode=self.parallel_mode, pipelined=True)
        if stop is None:    # batch full: drain outside the fast section
            self.drain_pending()
            return self._stopped_early
        return stop

    def _fast_iter_body(self):
        k = self.num_tree_per_iteration
        init_scores = [self._boost_from_average(tid, True)
                       for tid in range(k)]
        operands = (self.objective.gradient_operands()
                    if self.objective is not None
                    and self.objective.supports_traced_gradients()
                    else None)
        # (one step per kind of iteration: GOSS's sampled iterations
        # grow on another row count than its first, unsampled ones)
        sample = self._step_sample(self.iter)
        if operands is not None:     # gradients traced into the step
            grad_in, hess_in = operands, None
            self._bagging(self.iter, None, None)
        else:
            grad_in, hess_in = self._get_gradients()
            if sample is not None:
                # the step draws the sample itself, from these gradients
                # as the objective gave them
                self._bagging(self.iter, None, None)
            else:
                grad_in, hess_in = self._bagging(self.iter, grad_in,
                                                 hess_in)
        step_fn = self._fast_step_fns.get(sample)
        fresh_step = step_fn is None
        if fresh_step:
            step_fn = self._fast_step_fns[sample] = \
                self._make_fast_step(sample)
        F_oh = self.fused_f_oh
        if float(self.config.feature_fraction) >= 1.0:
            if getattr(self, "_fast_fm_pads", None) is None:
                self._fast_fm_pads = jnp.ones((k, F_oh), bool).at[
                    :, self.train_data.num_features:].set(False)
            fm_pads = self._fast_fm_pads
        else:
            fm_pads = jnp.stack([
                jnp.zeros((F_oh,), bool).at[:self.train_data.num_features]
                .set(self._feature_mask()) for _ in range(k)])
        its = () if sample is None else (np.int32(self.iter),)
        self.telemetry.inc("train.dispatches")
        self._place_carries()
        ext = bool(self.use_screening or self.quant_bits)
        sig = f"fast_step[k={k},ext={ext}" \
            + (",sampled]" if sample is not None else "]")
        t_call0 = time.perf_counter() if fresh_step else 0.0
        with (self.telemetry.timed("first_call", adopt=True, signature=sig,
                                   iter=self.iter)
              if fresh_step else _NO_SPAN), \
                self._maybe_record_collectives(fresh_step) as rec, \
                jax.profiler.StepTraceAnnotation("fast_step",
                                                 step_num=self.iter):
            # the kind-named anchor span the roofline plane
            # (obs/kernelstats.py) attributes fast-step kernels to
            if ext:
                ema = (self._ensure_gain_ema() if self.use_screening
                       else None)
                explore = (jnp.asarray(self._screening_explore(self.iter))
                           if self.use_screening else None)
                seed = (jnp.uint32(self._quant_seed(self.iter))
                        if self.quant_bits else None)
                call_args = (self.fused_bins_T, self.scores, grad_in,
                             hess_in, self.bag_weight, fm_pads, ema,
                             explore, seed) + its
                self.scores, trees, logs, ema2, counts = \
                    step_fn(*call_args)
                if self.use_screening:
                    self._gain_ema_dev = ema2
            else:
                call_args = (self.fused_bins_T, self.scores, grad_in,
                             hess_in, self.bag_weight, fm_pads) + its
                self.scores, trees, logs, counts = step_fn(*call_args)
        if counts is not None:
            self._sample_counts.append(counts)
        if rec is not None:
            self._coll_per_iter = rec.profile
        if fresh_step and self.telemetry.enabled:
            # fast-step compile accounting, same contract as the
            # megastep's: the first call traces + compiles before the
            # async dispatch returns, so its wall is the compile cost;
            # the cost-ledger note defers fn.lower() to the next drain
            op_bytes = sum(int(getattr(a, "nbytes", 0))
                           for a in call_args if a is not None)
            self.telemetry.compile_executable(
                sig, (time.perf_counter() - t_call0) * 1000.0, op_bytes,
                iteration=self.iter)
            if self._cost is not None:
                self._cost.note(step_fn, call_args, sig,
                                kind="fast_step", scale=1,
                                operand_bytes=op_bytes,
                                iteration=self.iter)
        return self._finish_fast_iter(trees, init_scores, logs)

    def _finish_fast_iter(self, trees, init_scores, logs=None):
        """Pipelining tail of the fast iteration body: async host copies,
        in-jit valid updates, pending append, batch-drain signalling."""
        for leaf in jax.tree_util.tree_leaves(trees):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self._update_valid_from_trees(trees, logs)
        if not self._pending:
            self._batch_w0 = self.telemetry.wall_now()
            self._batch_t0 = time.perf_counter()
        self._pending.append((trees, [init_scores], 1, None))
        self._pending_iters += 1
        self.iter += 1
        if self._pending_iters >= self._FAST_SYNC_EVERY:
            return None     # signal the wrapper to drain
        return False

    def drain_pending(self) -> None:
        """Materialise queued device trees as HostTrees (ref bookkeeping of
        gbdt.cpp:393-445, deferred). Detects the no-more-splits stop
        condition after the fact: the stopping iteration contributed
        nothing to the scores (dried deltas are zeroed in-jit), and later
        iterations' contributions are subtracted back out of the live
        scores (bin-space routing is training-identical, so each
        subtraction reverses the training add up to f32 rounding)."""
        if not self._pending:
            return
        with timer.section("GBDT::DrainPending"):
            self._drain_body()

    def _drain_body(self) -> None:
        pend, self._pending = self._pending, []
        self._pending_iters = 0
        k = self.num_tree_per_iteration
        self.telemetry.inc("train.drains")
        # one batched fetch for trees, metric rows AND the early-stop
        # latch — the drain is the single host sync point per chunk; a
        # second device_get would be a second blocking round trip
        es_state = (None if (self._eval_consumer is None
                             or self._es_carry is None)
                    else (self._es_carry[2], self._es_carry[3]))
        # (Fetch is the wait for the device, not host work)
        counts, self._sample_counts = self._sample_counts, []
        with timer.section("GBDT::Drain::Fetch"):
            trees_host, metrics_host, es_host, counts_host = jax.device_get(
                ([t for t, _, _, _ in pend],
                 [m for _, _, _, m in pend if m is not None],
                 es_state, counts))
        # flatten megastep entries ([B, k, ...] stacked trees covering B
        # iterations) and per-iteration entries ([k, ...], batch == 1)
        # into one per-iteration sequence of host TreeArrays fields,
        # with the per-iteration [n_slots] metric row alongside (None
        # where the entry carried no on-device eval)
        flat: List[Tuple] = []
        flat_metrics: List = []
        mi = 0
        for (_, init_list, batch, mB), trees_h in zip(pend, trees_host):
            arrays = [np.asarray(a) for a in trees_h]
            if batch == 1 and mB is None:
                # pipelined fast-path entry: [k, ...], no batch axis.
                # A length-1 megastep entry (mB is not None — consumer
                # horizon/bagging tails run chunk-1 scans) still carries
                # the leading [B=1, ...] axis and must unstack below.
                flat.append((arrays, init_list[0]))
            else:
                for b in range(batch):
                    flat.append(([a[b] for a in arrays], init_list[b]))
            if mB is None:
                flat_metrics.extend([None] * batch)
            else:
                rows = np.asarray(metrics_host[mi])
                mi += 1
                flat_metrics.extend(rows[b] for b in range(batch))
        base_iter = self.iter - len(flat)
        streamed = self._count_streamed_rows(base_iter, len(flat),
                                             counts_host)
        # scan-native early stop: the device latch decides the
        # bookkeeping below — iterations past the latch were frozen
        # in-jit (their score deltas masked to zero), so they must be
        # neither appended to the model nor score-subtracted
        es_cut = None
        if es_host is not None and bool(es_host[0]):
            es_cut = int(es_host[1]) - base_iter
        gain_acc: List[np.ndarray] = []
        stop_i = None
        converted = []   # per drained iteration: [(ht, dt, grew)] * k
        for i, (trees_h, init_scores) in enumerate(flat):
            iter_models = []
            dried_first = []   # tids of first-k constant trees
            any_grew = False
            for tid in range(k):
                ta = TreeArrays(*[np.asarray(a)[tid] for a in trees_h])
                if int(ta.num_leaves) <= 1:
                    # dried-up class (the fast step zeroed its delta
                    # in-jit): zero constant tree — except within the
                    # first k models, where the reference stores the init
                    # score in it and adds it to the scorer on top of
                    # BoostFromAverage's update (gbdt.cpp:421-437);
                    # applied after the loop once the iteration is known
                    # to be kept
                    ht = HostTree(1)
                    if stop_i is None \
                            and len(self.models) + len(iter_models) < k:
                        dried_first.append(tid)
                    iter_models.append((ht, None, False))
                    continue
                any_grew = True
                with timer.section("GBDT::Drain::HostTree"):
                    ht, sf_inner = self._to_host_tree(ta,
                                                      self.shrinkage_rate)
                    # numerical guards stay live on the fast path: the
                    # host tree is already materialised here, so the
                    # non-finite checks cost numpy only (no extra device
                    # sync)
                    self._guard_tree(base_iter + i, tid, ht, gain_acc)
                    ht.apply_shrinkage(self.shrinkage_rate)
                cf, cm = self._last_cat or (None, None)
                with timer.section("GBDT::Drain::DeviceTree"):
                    dt = _DeviceTree(ht, sf_inner, cat_flag=cf, cat_mask=cm)
                if abs(init_scores[tid]) > K_EPSILON:
                    ht.add_bias(init_scores[tid])
                    dt.leaf_value = jnp.asarray(ht.leaf_value, jnp.float32)
                iter_models.append((ht, dt, True))
            converted.append(iter_models)
            if stop_i is not None:
                continue
            if es_cut is not None and i > es_cut:
                # scan-frozen early-stop tail: score deltas were masked
                # to zero in-jit past the latch, so these trees are
                # neither appended nor subtracted — the drained model
                # ends at the latch iteration bit-identically to the
                # synchronous driver's early-stopped model
                continue
            if not any_grew:
                stop_i = i
                continue
            for tid in dried_first:
                ht = iter_models[tid][0]
                ht.leaf_value[0] = init_scores[tid]
                self.scores = self.scores.at[tid].add(
                    float(init_scores[tid]))
                for vi in range(len(self.valid_scores)):
                    self.valid_scores[vi] = self.valid_scores[vi] \
                        .at[tid].add(float(init_scores[tid]))
            for ht, dt, _ in iter_models:
                if dt is None:
                    dt = _DeviceTree(ht, np.zeros(0, np.int32))
                self.models.append(ht)
                self.device_trees.append(dt)
        if stop_i is not None:
            with timer.section("GBDT::Drain::Rollback"):
                # the stopping iteration contributed nothing to the scores
                # (every class's delta was zeroed in-jit); iterations after it
                # must be discarded — subtract their contributions from the
                # live scores (bin-space routing is training-identical, so
                # each subtraction reverses the training add up to f32
                # rounding)
                scores = self.scores
                # replay bins: the replicated copy single-process, the
                # row-sharded global matrix under multi-process (the
                # rank-local bins_dev cannot route the [k, Np] score carry)
                replay_bins = self._train_bins_replay()
                for conv_i in range(stop_i + 1, len(converted)):
                    if es_cut is not None and conv_i > es_cut:
                        continue   # frozen tail: contributed nothing
                    iter_models = converted[conv_i]
                    for tid, (_, dt, grew) in enumerate(iter_models):
                        if grew:
                            scores = self._add_tree_to_score(
                                scores, replay_bins, dt, tid, scale=-1.0,
                                bundle=self._train_bundle())
                            for vi in range(len(self.valid_scores)):
                                self.valid_scores[vi] = \
                                    self._add_tree_to_score(
                                        self.valid_scores[vi],
                                        self.valid_bins[vi], dt, tid,
                                        scale=-1.0,
                                        bundle=self._valid_bundle(vi))
                if not self.models:
                    # first-ever iteration stopped outright: the reference
                    # keeps one constant tree per class carrying the init
                    # score, updating the scorer a second time on top of
                    # BoostFromAverage (gbdt.cpp:377,433 — 2x init total;
                    # matched bug-for-bug by the synchronous path)
                    init_scores = flat[stop_i][1]
                    for tid in range(k):
                        ht = HostTree(1)
                        ht.leaf_value[0] = init_scores[tid]
                        scores = scores.at[tid].add(float(init_scores[tid]))
                        for vi in range(len(self.valid_scores)):
                            # the sync path's constant-tree branch updates the
                            # valid scorers too (gbdt.cpp:422-441)
                            self.valid_scores[vi] = self.valid_scores[vi] \
                                .at[tid].add(float(init_scores[tid]))
                        self.models.append(ht)
                        self.device_trees.append(
                            _DeviceTree(ht, np.zeros(0, np.int32)))
                self.scores = scores
                self.iter = base_iter + stop_i
                self._stopped_early = True
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                # structured stop record (the sync path emits the same event
                # inline). `discarded` lets iteration-granularity consumers
                # reconcile: iteration records numbered >= this event's
                # `iter` were rolled back and produced no trees
                self.telemetry.event("stopped_no_splits", iteration=self.iter,
                                     discarded=len(flat) - stop_i)
        with timer.section("GBDT::Drain::Replay"):
            self._replay_drained_eval(flat_metrics, base_iter, len(flat),
                                      stop_i, es_cut)
        tel = self.telemetry
        if tel.enabled and flat and self.parallel_mode != "serial":
            # measured in-trace collective traffic of the drained batch:
            # per-iteration (count, bytes) recorded from the scan's /
            # fast step's STATIC traced shapes at compile time
            # (ops/collectives.py) — the traced program runs its full
            # static level schedule for every iteration, frozen or not,
            # so the batch payload is per-iteration x iterations
            meas = getattr(self, "_coll_per_iter", None)
            if meas is not None:
                tel.collective("psum_" + self.parallel_mode,
                               meas[0] * len(flat), meas[1] * len(flat))
        if tel.enabled and flat and self._health is not None \
                and self._health_at_drain():
            # drain-boundary health audit (multi-chip megastep): the
            # model list just settled and every rank drains at the same
            # iteration (SPMD), so the hash allgather pairs here with
            # zero extra device dispatches. One audit per drain window
            # that crossed a period boundary.
            # exceptions propagate: a one-sided bail would desync every
            # later host collective on the mesh (same contract as the
            # sync driver's multi-process handler re-raising)
            period = self._health.period
            if period > 0 and any((base_iter + i + 1) % period == 0
                                  for i in range(len(flat))):
                self._health.check(self.iter - 1, self.models, {})
        if tel.enabled and flat and self._tel_granularity() == "batch":
            # batch-granularity record: one megastep/pipelined batch of
            # `len(flat)` iterations, wall time measured first-dispatch
            # -> drain-complete (the one honest sync point the fast path
            # has). `kept` < iterations means the no-more-splits stop
            # rewound the tail.
            secs = {"batch": (time.perf_counter() - self._batch_t0
                              if self._batch_t0 is not None else 0.0)}
            tel.megastep(base_iter, iterations=len(flat),
                         kept=self.iter - base_iter, sections=secs,
                         wall_start=self._batch_w0, engine="fused",
                         mode=self.parallel_mode,
                         fused_iterations=self._batch_fused,
                         stopped=self._stopped_early, **streamed)
            if gain_acc:
                gains = np.concatenate(gain_acc)
                if gains.size:
                    tel.observe("batch.split_gain_mean",
                                float(gains.mean()))
        if tel.enabled and flat and self._mem_watermarks:
            # the drain is the fast path's one honest sync point — the
            # allocator's peak over the whole drained batch is settled
            # here, so this is where the HBM watermarks move
            from ..obs.jaxmon import memory_watermarks
            memory_watermarks(tel, where="drain")
        if tel.enabled and flat and self.use_screening \
                and self._gain_ema_dev is not None:
            # screening visibility: how many features the NEXT non-
            # exploration mask keeps (host mirror of _screening_mask_fn
            # over the just-settled EMA; the drain already synced)
            try:
                ema = np.asarray(self._gain_ema_dev)
                F = self.train_data.num_features
                keep_k = self._screening_keep_k()
                kth = np.sort(ema[:F])[F - keep_k]
                tel.gauge("screening.active_features",
                          float(np.sum(ema[:F] >= kth)))
            except Exception as e:   # a gauge must never kill training
                log.debug("screening gauge failed: %s", e)
        if tel.enabled and flat:
            self._publish_hist_gauges()
        if tel.enabled and flat and self._cost is not None:
            # cost-ledger join for the drained batch: the deferred
            # fn.lower() analyses run HERE (host-sync point), then one
            # record marries analytic flops/bytes-per-iter with the
            # batch's measured wall, the measured collective payload
            # and the hist.* analytic plane model
            meas = getattr(self, "_coll_per_iter", None)
            self._cost.ledger_record(
                base_iter, len(flat),
                wall_s=(time.perf_counter() - self._batch_t0
                        if self._batch_t0 is not None else None),
                hist_bytes_per_iter=(self._hist_stats or {}).get(
                    "bytes_per_iter"),
                coll_bytes_per_iter=(float(meas[1]) if meas is not None
                                     else None))
        self._batch_t0 = self._batch_w0 = None
        self._batch_fused = 0
        # drain boundaries are the fast path's natural consistency
        # points: the model list is settled, the score carries just
        # synced, the eval replay ran — checkpoint here captures full
        # training state without any extra device dispatch
        if flat and self._ckpt is not None:
            self.maybe_checkpoint()
        # ... and the on-demand profiling window (POST /profile) opens
        # and closes at exactly these boundaries on the megastep driver,
        # and the SLO watchdogs take their training-liveness heartbeat
        if flat:
            self._profile_ctl_step()
            self._slo_step()

    def _count_streamed_rows(self, base_iter: int, n_iters: int,
                             counts_host) -> Dict[str, int]:
        """Counters of a drained batch of the fused fast path:
        ``level.rows_streamed`` (rows every ``level_pass`` launch of a
        tree streams, summed over the batch's trees) beside
        ``level.trees``, and for the sampled iterations (GOSS) the
        sample's rows as the step counted them: ``goss.top_rows``,
        ``goss.other_rows``, ``goss.bag_rows``, ``goss.iterations``.
        Returns the batch's ``rows_streamed`` and ``trees`` for its
        ``megastep`` event."""
        tel = self.telemetry
        if not tel.enabled or not n_iters:
            return {}
        k = self.num_tree_per_iteration
        sample = self._step_sample(base_iter + n_iters - 1)
        n_sampled = 0 if sample is None \
            else n_iters - max(0, sample.first_iter - base_iter)
        streamed = k * ((n_iters - n_sampled) * self.fused_Rp
                        + n_sampled * (sample.capacity if sample else 0))
        tel.inc("level.trees", k * n_iters)
        tel.inc("level.rows_streamed", streamed)
        if counts_host:
            got = np.concatenate([np.asarray(c).reshape(-1, 2)
                                  for c in counts_host]).sum(axis=0)
            tel.inc("goss.iterations", n_sampled)
            tel.inc("goss.top_rows", int(got[0]))
            tel.inc("goss.other_rows", int(got[1]))
            tel.inc("goss.bag_rows", int(got[0] + got[1]))
        return {"rows_streamed": streamed, "trees": k * n_iters}

    def _replay_drained_eval(self, flat_metrics, base_iter: int,
                             n_flat: int, stop_i: Optional[int],
                             es_cut: Optional[int]) -> None:
        """Drain-time consumer feed: replay the armed loop's callbacks
        in iteration order against the scan's per-iteration metric rows
        (callback.DrainEvalReplay), then reconcile the scan-native
        early-stop latch with the host replay's verdict. No score fetch
        and no re-predict happen here — only the [B, n_slots] scalars
        already pulled by the drain."""
        consumer = self._eval_consumer
        if consumer is None or n_flat == 0:
            return
        limit = n_flat
        if stop_i is not None:
            # the stopping (dried) iteration still gets its eval and
            # callbacks — the sync loop also evaluates after a finished
            # update; rows past it reflect score contributions the
            # drain just subtracted, so they must not replay
            limit = min(limit, stop_i + 1)
        if es_cut is not None:
            limit = min(limit, es_cut + 1)
        es_j = None
        n_replayed = 0
        for ii in range(limit):
            row = flat_metrics[ii]
            if row is None:
                log.warning("megastep drain: no metric row for iteration "
                            "%d; eval replay truncated", base_iter + ii)
                break
            n_replayed = ii + 1
            if consumer.replay(base_iter + ii, row):
                es_j = ii
                break
        tel = self.telemetry
        if tel.enabled and n_replayed:
            # per-batch eval record (docs/Observability.md §9): which
            # slots were evaluated on device, the last replayed row, and
            # whether a REAL early stop latched inside this batch. The
            # device latch is the discriminator: the callback's
            # final-iteration "did not meet early stopping" raise is
            # normal end-of-training control flow, not a stop.
            tel.event("eval_batch", iteration=base_iter,
                      iterations=n_replayed,
                      slots=[f"{ds}/{name}"
                             for ds, name, _ in consumer.slots],
                      last=[float(v)
                            for v in flat_metrics[n_replayed - 1]],
                      stopped=es_cut is not None)
        if es_cut is not None and stop_i is None:
            if es_j != es_cut:
                # should be unreachable: the device latch and the host
                # replay run the same comparisons on the same f32 values
                log.error("scan early-stop latch (iteration %d) "
                          "disagrees with the callback replay (%s); "
                          "model truncated at the device latch",
                          base_iter + es_cut,
                          "no stop" if es_j is None
                          else f"iteration {base_iter + es_j}")
            # nothing past the latch was appended (frozen tail), so the
            # early stop needs no score arithmetic — just the counter
            self.iter = base_iter + es_cut + 1
            self._es_finished = True
        elif es_j is not None:
            # host-side stop without a device latch: the final-iteration
            # "did not meet early stopping" check, or a stop on the
            # dried no-splits iteration — model and scores are already
            # consistent, only the stop signal needs latching
            self._es_finished = True
        if es_cut is not None and consumer.stop is not None:
            # emitted only on a rounds-based stop (the device latch);
            # the final-iteration EarlyStopException still records
            # best_iteration through consumer.stop but is a completed
            # run, not an early-stopped one
            tel.event("early_stopping", iteration=self.iter,
                      best_iteration=consumer.stop[0])

    # ------------------------------------------------------------------
    # Multi-iteration megastep: up to tpu_megastep_iters boosting
    # iterations chained inside ONE jit via lax.scan over the fused
    # tree-growing step — gradients (traced from the objective's
    # operands), tree growth, training-score and valid-score updates all
    # stay on device; the scan emits stacked TreeArrays [B, k, ...] that
    # drain_pending converts like any other pending batch. Dispatches
    # are the remaining host-side overhead after the kernel work: the
    # per-iteration fast path still pays >= 1 dispatch per iteration
    # plus per-valid-set updates; the megastep pays ~1 per B iterations.
    def arm_megastep(self, on: bool = True, eval_consumer=None) -> None:
        """Permission from a driver loop that (a) treats train_one_iter
        as 'advance training', not 'advance exactly one iteration', and
        (b) stops when it returns True. Only such loops (engine.train,
        the CLI train loop) may consume multi-iteration megasteps; the
        bare Booster.update contract stays one iteration per call.

        ``eval_consumer`` (callback.DrainEvalReplay) additionally opts
        the loop into ON-DEVICE evaluation: the scan computes every
        configured metric per iteration, and the drain replays the
        loop's callbacks against the stacked metric matrix
        (megastep_eval_precheck must have succeeded first)."""
        if not on and self._eval_consumer is not None:
            # replay any still-queued metric rows before unbinding the
            # consumer — a tail left pending here would drain later with
            # nobody to feed, silently dropping callback invocations.
            # Defensive catch: disarm runs in the engine's `finally`, so
            # a drain failure here must not mask an exception already
            # unwinding through the train loop.
            try:
                self.drain_pending()
            except Exception as e:
                log.warning("drain at consumer disarm failed: %s", e)
        had = self._eval_consumer is not None
        self._megastep_armed = bool(on)
        self._eval_consumer = eval_consumer if on else None
        if (self._eval_consumer is not None) != had:
            # the eval plan is baked into the scan trace; a consumer
            # change invalidates every cached megastep signature
            self._megastep_fns = {}
        if self._eval_consumer is not None:
            if self._traced_plan is None:
                log.fatal("arm_megastep(eval_consumer=...) requires a "
                          "successful megastep_eval_precheck first")
            self._eval_consumer.bind(self._traced_plan.slots)
        else:
            self._traced_plan = None
            self._plan_ops = None
            self._es_spec = None
            self._es_carry = None
            # the drain-replay stop verdict lives on in the consumer
            # (engine.train applies best_iteration from it); the GBDT
            # itself must return to the trainable one-iteration-per-
            # update contract once disarmed, like the synchronous
            # early-stop path does
            self._es_finished = False

    def megastep_eval_precheck(self, include_training: bool,
                               es_spec=None) -> Tuple[bool, Optional[str]]:
        """Decide BEFORE the first iteration whether this run's metrics
        can evaluate on device inside the megastep with callbacks
        replayed at drain. Returns ``(True, None)`` and stores the
        traced plan, or ``(False, reason)`` naming the specific blocker
        (the caller should emit/log it and fall back to the classic
        per-iteration loop).

        ``es_spec`` is ``(stopping_rounds, first_metric_only)`` when an
        early-stopping callback is registered — the scan then carries
        best-metric/rounds-since-best state and freezes training past
        the stopping point so the drained model stays bit-identical to
        the synchronous driver's early-stopped model."""
        if not bool(getattr(self.config, "tpu_traced_eval", True)):
            return False, "config:tpu_traced_eval=false"
        if self._tel_gran != "batch":
            # a replayed record_telemetry can enable the registry
            # mid-run; a non-batch granularity would then evict training
            # with the consumer already committed — reject upfront
            return False, f"config:telemetry_granularity={self._tel_gran}"
        reason = self._fast_path_reason()
        if reason is not None:
            return False, reason
        reason = self._megastep_static_reason()
        if reason is not None:
            return False, reason
        reason = self._mp_valid_agreement_reason()
        if reason is not None:
            return False, reason
        from ..metric.traced import build_plan
        plan, err = build_plan(self, include_training)
        if plan is None:
            return False, err
        from ..metric import AUCMetric
        sets = list(zip(self.valid_names, self.valid_metrics))
        if include_training and self.training_metrics:
            sets.insert(0, ("training", self.training_metrics))
        for ds_name, metrics in sets:
            for m in metrics:
                if isinstance(m, AUCMetric):
                    # which form of the traced AUC runs: the sort carries
                    # 2 operands, 3 when weighted (no metric reads it)
                    self.telemetry.event(
                        "auc_form", iteration=self.iter, dataset=ds_name,
                        rows=int(m.num_data),
                        weighted=m.weight is not None)
        self._traced_plan = plan
        self._plan_ops = None
        self._es_spec = es_spec
        self._es_carry = None
        self._es_finished = False
        return True, None

    def _mp_valid_agreement_reason(self) -> Optional[str]:
        """Multi-process on-device eval requires IDENTICAL validation
        data on every rank: the traced metrics read each rank's LOCAL
        valid arrays inside the SPMD program, and divergent values would
        freeze the early-stop latch at different iterations per rank —
        silent model divergence with no collective to catch it. One
        host allgather of a per-rank digest at precheck (not per
        iteration) enforces the contract; None = agreed or not
        applicable. SPMD: every rank runs the same precheck, so the
        collective pairs."""
        if getattr(self, "mp", None) is None or not self.valid_data:
            return None
        import hashlib
        h = hashlib.sha256()
        for vd in self.valid_data:
            h.update(np.ascontiguousarray(
                np.asarray(vd.bins)).tobytes())
            md = vd.metadata
            for arr in ((md.label, md.weight, md.init_score)
                        if md is not None else ()):
                if arr is not None:
                    h.update(np.ascontiguousarray(
                        np.asarray(arr, np.float64)).tobytes())
        digest = np.frombuffer(h.digest(), np.uint8).copy()
        allg = np.asarray(self.mp._allgather(digest)) \
            .reshape(self.mp.process_count, -1)
        if not bool((allg == allg[0]).all()):
            return "engine:multiproc_divergent_valid_data"
        return None

    def _megastep_static_reason(self) -> Optional[str]:
        """Megastep blockers beyond fast-path eligibility that are fixed
        for the run (config keys, objective protocol)."""
        obj = self.objective
        if not bool(getattr(self.config, "tpu_megastep", True)):
            return "config:tpu_megastep=false"
        # interpret-mode fused (off-TPU emulation) has no dispatch
        # latency to amortize — the scan would only add compile time —
        # so there the megastep is explicit opt-in (tests, micro bench);
        # on a real chip the default engages it
        if self.fused_interpret and not self.config.was_set("tpu_megastep"):
            return "interpret_mode_without_tpu_megastep_optin"
        if obj is None or not obj.supports_traced_gradients():
            return "objective_untraced_gradients:" + \
                (obj.name if obj is not None else "custom")
        if self.telemetry.enabled \
                and self._tel_granularity() == "iteration":
            return "config:telemetry_granularity=iteration"
        return None

    def _megastep_ok(self) -> bool:
        if not self._megastep_armed:
            return False
        if not self._fast_path_ok():   # reports its own eviction reason
            return False
        reason = self._megastep_static_reason()
        if reason is None and self._eval_consumer is None:
            # without a drain-replay consumer, per-iteration
            # observability needs per-iteration steps: GBDT-level early
            # stopping evaluates metrics after every iteration, and
            # snapshots fire on iteration numbers. A consumer handles
            # both at drain time.
            if self.early_stopping_round > 0:
                reason = "config:early_stopping_round"
            elif int(getattr(self.config, "snapshot_freq", -1) or -1) > 0:
                reason = "config:snapshot_freq"
        if reason is not None:
            self._report_eviction(reason, stage="megastep")
            return False
        return True

    def _megastep_chunk(self) -> int:
        """Iterations the next megastep may fuse: bounded by
        tpu_megastep_iters, the pipeline drain batch, the
        num_iterations horizon, and the current bagging round's window
        (the in-bag weight vector must be constant inside one jit —
        chunks never cross a re-bagging boundary, so the reference-
        parity LCG draws keep their exact firing order)."""
        if not self._megastep_ok():
            return 0
        chunk = min(int(self.config.tpu_megastep_iters),
                    self._FAST_SYNC_EVERY,
                    int(self.config.num_iterations) - self.iter)
        cfg = self.config
        if self.is_bagging and cfg.bagging_freq > 0:
            next_fire = ((self.iter // cfg.bagging_freq) + 1) \
                * cfg.bagging_freq
            chunk = min(chunk, next_fire - self.iter)
        plan = self._sample_plan()
        if plan is not None and self.iter < plan.first_iter:
            # GOSS: the unsampled and the sampled iterations are two
            # steps (they grow on different row counts)
            chunk = min(chunk, plan.first_iter - self.iter)
        return chunk

    def _place_carries(self) -> None:
        """Single-process row-sharded modes: commit the carries a step
        hands back — train scores, valid scores, the early-stop state —
        to the mesh in the layout they come back in (train rows sharded
        when they divide over the shards, everything else replicated),
        so the FIRST dispatch already has the steady-state signature.
        Left on device 0 they changed sharding across the first call and
        the second dispatch recompiled the whole step: ~100 s on four
        v5e chips (PR 21). A no-op once placed."""
        if self.mp is not None \
                or self.parallel_mode not in ("data", "voting"):
            return
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(self.mesh, P())
        rows = (NamedSharding(self.mesh, P(None, self.axis_name))
                if self.num_data % self.n_shards == 0 else rep)
        self.scores = jax.device_put(self.scores, rows)
        self.valid_scores = [jax.device_put(v, rep)
                             for v in self.valid_scores]
        # (the passenger matrices never come back from a step; placed
        # here they are not broadcast from device 0 at every dispatch)
        self._valid_routes = {
            vi: (m if m is None else jax.device_put(m, rep), reason)
            for vi, (m, reason) in self._valid_routes.items()}
        self._valid_exact_dev = {
            vi: jax.device_put(x, rep)
            for vi, x in self._valid_exact_dev.items()}
        if self._es_carry is not None:
            self._es_carry = jax.device_put(self._es_carry, rep)

    def _train_one_megastep(self, chunk: int) -> bool:
        tel = self.telemetry
        self._profiler_window(chunk)
        t0 = time.perf_counter()
        with timer.section("GBDT::TrainMegastep"):
            self._megastep_body(chunk)
        # dispatch (host enqueue) cost of the fused chunk; the batch's
        # wall time is attributed by the drain's batch record
        tel.observe("megastep.dispatch", time.perf_counter() - t0)
        # batch-granularity attribution syncs once per megastep by
        # draining immediately (one sync amortized over `chunk`
        # iterations, which also emits the batch record); a drain-replay
        # consumer drains per chunk too — callbacks (logging, early
        # stopping) replay promptly and a scan-frozen early-stop tail
        # never spans more than one chunk. Without either, the drain
        # keeps its usual pipeline cadence.
        if tel.enabled or self._eval_consumer is not None \
                or self._pending_iters >= self._FAST_SYNC_EVERY:
            self.drain_pending()
        return self._stopped_early or self._es_finished

    def _megastep_body(self, chunk: int) -> None:
        k = self.num_tree_per_iteration
        # (a chunk never crosses GOSS's first sampled iteration:
        # _megastep_chunk; a sampled chunk is a step of its own)
        sample = self._step_sample(self.iter)
        fn_key = chunk if sample is None else (chunk, sample)
        fresh_fn = fn_key not in self._megastep_fns
        plan = self._traced_plan if self._eval_consumer is not None \
            else None
        tel = self.telemetry
        # a NEW step signature: the Python before its first call is the
        # span ``first_call/build``, the call itself ``first_call``
        with tel.timed("first_call/build") if fresh_fn else _NO_SPAN:
            init0 = [self._boost_from_average(tid, True)
                     for tid in range(k)]
            operands = self.objective.gradient_operands()
            self._publish_rank_layout()
            self._bagging(self.iter, None, None)   # chunk-aligned: a
            # round can fire only at the chunk's first iteration
            if fresh_fn:
                self._megastep_fns[fn_key] = (
                    self._make_megastep(chunk) if sample is None
                    else self._make_megastep(chunk, sample))
            fn = self._megastep_fns[fn_key]
            F_oh = self.fused_f_oh
            F = self.train_data.num_features
            if float(self.config.feature_fraction) >= 1.0:
                fm_pads = self._megastep_fm.get(chunk)
                if fm_pads is None:
                    fm_pads = self._megastep_fm[chunk] = \
                        jnp.ones((chunk, k, F_oh), bool) \
                        .at[:, :, F:].set(False)
            else:
                # host LCG draws in exactly the per-iteration order
                # (iteration-major, then tree) so column sampling stays
                # reference-parity across the fused chunk
                masks = np.zeros((chunk, k, F_oh), bool)
                for b in range(chunk):
                    for tid in range(k):
                        masks[b, tid, :F] = np.asarray(
                            self._feature_mask())
                fm_pads = jnp.asarray(masks)
            tel.inc("train.dispatches")
            if plan is not None:
                if self._plan_ops is None:
                    self._plan_ops = plan.operands()
                if self._es_carry is None:
                    self._es_carry = self._init_es_carry(plan.n_slots)
            self._place_carries()
        metrics_B = None
        sig = f"megastep[chunk={chunk},k={k},eval={plan is not None}" \
            + (",sampled]" if sample is not None else "]")
        # (``first_call`` has the bounds of ``compile_executable``'s
        # ``compile_ms``; jax's trace / lower / compile time spans inside
        # it become its children: obs/jaxmon.py)
        t_call0 = time.perf_counter() if fresh_fn else 0.0
        # profiler users see the fused chunk as one annotated step
        # (profile_dir / jax.profiler traces); free when no trace is on
        with (tel.timed("first_call", adopt=True, signature=sig,
                        iter=self.iter)
              if fresh_fn else _NO_SPAN), \
                jax.profiler.StepTraceAnnotation("megastep",
                                                 step_num=self.iter), \
                self._maybe_record_collectives(fresh_fn) as coll_rec:
            ext = bool(self.use_screening or self.quant_bits)
            base_args = (self.fused_bins_T, self.scores,
                         self._valid_operands(),
                         tuple(self.valid_scores),
                         operands, self.bag_weight, fm_pads)
            # host arange: jnp.arange(start > 0) is an eager add,
            # i.e. one more executable compiled in the second chunk
            iters_B = np.arange(self.iter, self.iter + chunk,
                                dtype=np.int32)
            if plan is None:
                # (a sampled step takes the iterations: its draws' counter)
                its = () if sample is None else (iters_B,)
                if ext:
                    ema0, explore_B, seeds_B = self._megastep_aux(chunk)
                    call_args = base_args + (ema0, explore_B, seeds_B) + its
                    scores, vscores, trees_B, ema2, counts_B = \
                        fn(*call_args)
                    if self.use_screening:
                        self._gain_ema_dev = ema2
                else:
                    call_args = base_args + its
                    scores, vscores, trees_B, counts_B = fn(*call_args)
            else:
                if ext:
                    ema0, explore_B, seeds_B = self._megastep_aux(chunk)
                    call_args = base_args + (iters_B, self._plan_ops,
                                             self._es_carry, ema0,
                                             explore_B, seeds_B)
                    (scores, vscores, self._es_carry, trees_B,
                     metrics_B, ema2, counts_B) = fn(*call_args)
                    if self.use_screening:
                        self._gain_ema_dev = ema2
                else:
                    call_args = base_args + (iters_B, self._plan_ops,
                                             self._es_carry)
                    (scores, vscores, self._es_carry, trees_B,
                     metrics_B, counts_B) = fn(*call_args)
        if coll_rec is not None:
            # the scan traces its body ONCE regardless of chunk length,
            # so the recorded totals are the per-iteration schedule
            self._coll_per_iter = coll_rec.profile
        if fresh_fn and self.telemetry.enabled:
            # the first call of a new chunk signature traces + compiles
            # synchronously before the async dispatch returns, so its
            # wall time IS the compile cost; operand bytes estimated
            # from the arrays actually passed (the exporter's
            # recompile-rate / headroom record, obs/export.py)
            op_bytes = sum(
                int(getattr(a, "nbytes", 0)) for a in
                [self.fused_bins_T, self.scores, self.bag_weight,
                 fm_pads, *jax.tree_util.tree_leaves(base_args[2]),
                 *self.valid_scores])
            self.telemetry.compile_executable(
                sig, (time.perf_counter() - t_call0) * 1000.0, op_bytes,
                iteration=self.iter)
            if self._cost is not None:
                # queue the fresh signature for the cost ledger: aval
                # capture only here (cheap, donation-safe); the
                # fn.lower() analysis runs at the next drain boundary,
                # off the dispatch path (obs/cost.py)
                self._cost.note(fn, call_args, sig, kind="megastep",
                                scale=chunk, operand_bytes=op_bytes,
                                iteration=self.iter)
        self.scores = scores
        self.valid_scores = list(vscores)
        if counts_B is not None:
            self._sample_counts.append(counts_B)
        for leaf in jax.tree_util.tree_leaves(trees_B):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        init_list = [init0] + [[0.0] * k for _ in range(chunk - 1)]
        if not self._pending:
            self._batch_w0 = self.telemetry.wall_now()
            self._batch_t0 = time.perf_counter()
        self._pending.append((trees_B, init_list, chunk, metrics_B))
        self._pending_iters += chunk
        self._batch_fused += chunk
        self.iter += chunk

    @staticmethod
    def _init_es_carry(n_slots: int):
        """Fresh scan-native early-stop carry: per-slot best (signed so
        higher is always better), per-slot best round (-1 = no eval
        seen yet, mirroring the callback's best_score_list[i] is None),
        plus the latched stop flag and the latch iteration."""
        return (jnp.full((n_slots,), -jnp.inf, jnp.float32),
                jnp.full((n_slots,), -1, jnp.int32),
                jnp.zeros((), bool),
                jnp.full((), -1, jnp.int32))

    def _make_megastep(self, chunk: int, sample=None):
        """The jitted megastep of ``chunk`` iterations (_megastep_step).
        A job stored as bundle columns takes its dataset's tables (the
        padded and logical feature metadata, the bundle decode) as an
        operand rather than as constants of the program: the bundle
        layout moves with every dataset, and as constants each one would
        make a new program (_jit_over_tables)."""
        if not self.fused_bundle_cols:
            step, donate = self._megastep_step(chunk, sample)
            return jax.jit(step, donate_argnums=_donate(*donate))
        return self._jit_over_tables(
            lambda: self._megastep_step(chunk, sample))

    # the per-dataset tables a bundled job's step reads (_jit_over_tables)
    _STEP_TABLES = ("fused_meta", "fused_bundle_cfg", "meta",
                    "_replay_bundle")

    def _jit_over_tables(self, build):
        """jax.jit of the step ``build()`` returns (with the indices of
        its donated arguments), called with ``_STEP_TABLES`` as a leading
        operand: the step is traced with those attributes bound to the
        operand's tracers, so the program holds the tables' shapes and
        not their values, and a dataset of the same shapes is served by
        the same compiled program. Returns a callable of the step's own
        arguments (with a ``lower`` of them, for the cost ledger)."""
        names = self._STEP_TABLES
        donate = build()[1]

        def tables():
            return tuple(getattr(self, n) for n in names)

        def step(tabs, *args):
            saved = tables()
            try:
                for n, t in zip(names, tabs):
                    setattr(self, n, t)
                return build()[0](*args)
            finally:
                for n, t in zip(names, saved):
                    setattr(self, n, t)
        jitted = jax.jit(step,
                         donate_argnums=_donate(*(i + 1 for i in donate)))

        def call(*args):
            return jitted(tables(), *args)
        call.lower = lambda *args: jitted.lower(tables(), *args)
        return call

    def _megastep_step(self, chunk: int, sample=None):
        """(step, indices of its donated arguments): the megastep's
        traced body (_make_megastep jits it)."""
        obj = self.objective
        grow_k = self._make_fused_tree_loop(sample)
        valid_appliers = [self._make_valid_apply(vi)
                          for vi in range(len(self.valid_scores))]

        ext = bool(self.use_screening or self.quant_bits)

        def one_iteration(bins_T, scores, vbins, vscores, grad_ops,
                          bag_weight, fm_pads, ema=None, explore=None,
                          seed=None, it=None):
            """The SAME traced bodies as the per-iteration fast path —
            _make_fused_tree_loop for growth/score updates and
            _make_valid_apply per valid set — scanned, so the megastep
            is bit-identical to the pipelined path by construction."""
            with jax.named_scope("lgbm.gradients"):
                grad, hess = obj.gradients_from(scores, grad_ops)
            scores, stacked, ema, logs, counts = grow_k(
                bins_T, scores, grad, hess, bag_weight, fm_pads, ema,
                explore, seed, it)
            vscores = tuple(
                apply_v(vscore, vb, stacked, logs)
                for apply_v, vscore, vb in zip(valid_appliers, vscores,
                                               vbins))
            # (``counts``: a sampled step's; None, and so no result of
            # the scan, in the plain step)
            return scores, vscores, (stacked, counts), ema

        plan = self._traced_plan if self._eval_consumer is not None \
            else None
        if plan is None:
            if not ext:
                def step(bins_T, scores, vbins, vscores, grad_ops,
                         bag_weight, fm_pads_B, iters_B=None):
                    def body(carry, xs):
                        scores, vscores = carry
                        fm_pads, it = xs
                        scores, vscores, grown, _ = one_iteration(
                            bins_T, scores, vbins, vscores, grad_ops,
                            bag_weight, fm_pads, it=it)
                        return (scores, vscores), grown
                    (scores, vscores), (trees_B, counts_B) = jax.lax.scan(
                        body, (scores, vscores), (fm_pads_B, iters_B))
                    return scores, vscores, trees_B, counts_B
                # donate the score carry and every valid-score buffer:
                # the scan rewrites them in place across the whole chunk
                return step, (1, 3)

            def step_ext(bins_T, scores, vbins, vscores, grad_ops,
                         bag_weight, fm_pads_B, ema0, explore_B,
                         seeds_B, iters_B=None):
                # the gain EMA rides the scan CARRY (screening feedback
                # within the chunk); exploration flags and dither seeds
                # ride as xs alongside the feature masks
                def body(carry, xs):
                    scores, vscores, ema = carry
                    fm_pads, explore, seed, it = xs
                    scores, vscores, grown, ema = one_iteration(
                        bins_T, scores, vbins, vscores, grad_ops,
                        bag_weight, fm_pads, ema, explore, seed, it)
                    return (scores, vscores, ema), grown
                (scores, vscores, ema), (trees_B, counts_B) = jax.lax.scan(
                    body, (scores, vscores, ema0),
                    (fm_pads_B, explore_B, seeds_B, iters_B))
                return scores, vscores, trees_B, ema, counts_B
            return step_ext, (1, 3)

        # ---- on-device eval variant: the scan additionally computes
        # every configured metric per iteration (traced reductions over
        # the score carries it already holds) and threads the early-stop
        # state; past the stopping point the carries freeze, so the
        # frozen tail's trees contribute NOTHING and the drain discards
        # them without any score arithmetic — the drained model is
        # bit-identical to the synchronous driver's early-stopped one.
        slots = plan.slots
        sign = jnp.asarray([1.0 if bigger else -1.0
                            for (_, _, bigger) in slots], jnp.float32)
        if self._es_spec is not None and slots:
            es_rounds, fmo = self._es_spec
            first_name = slots[0][1]
            # mirrors callback.early_stopping's stop check: training
            # slots never stop, first_metric_only tracks only the first
            # metric's slots (best-state still updates for every slot)
            mask_np = [ds != "training"
                       and (not fmo or name == first_name)
                       for (ds, name, _) in slots]
        else:
            es_rounds, mask_np = (1 << 30), [False] * len(slots)
        es_mask = jnp.asarray(np.asarray(mask_np, bool))
        es_rounds = jnp.int32(es_rounds)

        @jax.named_scope("lgbm.early_stop")
        def es_update(es, mvals, it, active):
            best, bround, stopped, stop_it = es
            signed = mvals * sign
            # first-ever eval always records (bround < 0), like the
            # callback's best_score_list[i]-is-None branch; afterwards a
            # plain signed compare (min_delta != 0 is rejected at
            # precheck — f32-vs-f64 boundary rounding would break the
            # bit-identity contract)
            upd = active & ((bround < 0) | (signed > best))
            best = jnp.where(upd, signed, best)
            bround = jnp.where(upd, it, bround)
            trigger = active & jnp.any(es_mask
                                       & ((it - bround) >= es_rounds))
            stop_it = jnp.where(stopped | ~trigger, stop_it, it)
            return (best, bround, stopped | trigger, stop_it)

        if not ext:
            def step(bins_T, scores, vbins, vscores, grad_ops, bag_weight,
                     fm_pads_B, iters_B, metric_ops, es0):
                def body(carry, xs):
                    scores, vscores, es = carry
                    fm_pads, it = xs
                    active = ~es[2]
                    new_scores, new_vscores, grown, _ = one_iteration(
                        bins_T, scores, vbins, vscores, grad_ops,
                        bag_weight, fm_pads, it=it)
                    # freeze past the stop latch: the tree still comes
                    # out of the scan (static shapes) but contributes
                    # nothing
                    with jax.named_scope("lgbm.freeze"):
                        scores = jnp.where(active, new_scores, scores)
                        vscores = tuple(jnp.where(active, nv, v)
                                        for nv, v in zip(new_vscores,
                                                         vscores))
                    mvals = plan.eval_in_scan(scores, vscores, metric_ops)
                    es = es_update(es, mvals, it, active)
                    return (scores, vscores, es), (grown, mvals)
                (scores, vscores, es), ((trees_B, counts_B), metrics_B) = \
                    jax.lax.scan(body, (scores, vscores, es0),
                                 (fm_pads_B, iters_B))
                return scores, vscores, es, trees_B, metrics_B, counts_B
            return step, (1, 3, 9)

        def step_ext(bins_T, scores, vbins, vscores, grad_ops, bag_weight,
                     fm_pads_B, iters_B, metric_ops, es0, ema0,
                     explore_B, seeds_B):
            def body(carry, xs):
                scores, vscores, es, ema = carry
                fm_pads, it, explore, seed = xs
                active = ~es[2]
                (new_scores, new_vscores, grown,
                 new_ema) = one_iteration(
                    bins_T, scores, vbins, vscores, grad_ops,
                    bag_weight, fm_pads, ema, explore, seed, it)
                with jax.named_scope("lgbm.freeze"):
                    scores = jnp.where(active, new_scores, scores)
                    vscores = tuple(jnp.where(active, nv, v)
                                    for nv, v in zip(new_vscores, vscores))
                    if new_ema is not None:
                        # frozen tail: the latched model stops realizing
                        # gains, so the EMA freezes with it
                        ema = jnp.where(active, new_ema, ema)
                mvals = plan.eval_in_scan(scores, vscores, metric_ops)
                es = es_update(es, mvals, it, active)
                return (scores, vscores, es, ema), (grown, mvals)
            (scores, vscores, es, ema), ((trees_B, counts_B), metrics_B) = \
                jax.lax.scan(body, (scores, vscores, es0, ema0),
                             (fm_pads_B, iters_B, explore_B, seeds_B))
            return scores, vscores, es, trees_B, metrics_B, ema, counts_B
        return step_ext, (1, 3, 9)

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        """One boosting iteration (ref: gbdt.cpp:371 TrainOneIter) — or,
        when a megastep-armed driver loop permits it, one fused chunk of
        iterations (see arm_megastep). Returns True if training should
        stop."""
        if self._faults:
            from ..resilience import faults as _faults
            _faults.on_training_step(self)   # crash/hang chaos hooks
        self._profiler_step()
        if gradients is None and hessians is None \
                and not self._stopped_early and not self._es_finished:
            if self._megastep_armed \
                    and self.iter >= int(self.config.num_iterations):
                # the armed loop counts calls, not iterations: signal
                # completion once the megastep chunks covered the horizon
                self.drain_pending()
                return True
            chunk = self._megastep_chunk()
            # a drain-replay consumer needs EVERY iteration to flow
            # through the scan (the metrics are computed there), so
            # horizon/bagging tail chunks of one iteration still run as
            # a length-1 megastep instead of the bare fast step
            if chunk >= 2 or (chunk == 1
                              and self._eval_consumer is not None):
                return self._train_one_megastep(chunk)
            if self._eval_consumer is not None:
                # should be unreachable: megastep_eval_precheck vetted
                # every blocker before the consumer was armed. Fail safe
                # by falling back to the classic driver WITHOUT eval
                # replay (the engine loop detects the dropped consumer
                # and resumes inline evaluation).
                log.warning("megastep eval consumer dropped mid-run "
                            "(megastep no longer eligible); falling back "
                            "to per-iteration evaluation")
                self._report_eviction("consumer_dropped_mid_run")
                self.arm_megastep(self._megastep_armed, eval_consumer=None)
            if self._fast_path_ok():
                return self._train_one_iter_fast()
        self.drain_pending()
        if self._stopped_early or self._es_finished:
            return True
        with timer.section("GBDT::TrainOneIter"):
            return self._sync_iter_body(gradients, hessians)

    def _sync_iter_body(self, gradients, hessians) -> bool:
        k, n = self.num_tree_per_iteration, self.num_data
        tel = self.telemetry
        it = self.iter
        tel.begin_iteration(it)
        init_scores = [0.0] * k
        with self._sec("boosting") as s:
            if gradients is None or hessians is None:
                if self.objective is None:
                    log.fatal("Cannot train without an objective: pass a "
                              "built-in objective or supply gradients via "
                              "Booster.update(fobj=...)")
                for tid in range(k):
                    init_scores[tid] = self._boost_from_average(tid, True)
                grad, hess = self._get_gradients()
            elif getattr(self, "mp", None) is not None:
                # custom gradients are per-ROW data: each rank's fobj
                # returns [k, local_real] for its own shard (the
                # reference's distributed custom objective is rank-local
                # the same way); pad rows carry zero grad/hess and zero
                # bag weight
                mp = self.mp
                gl = np.asarray(gradients, np.float32).reshape(
                    k, mp.local_real)
                hl = np.asarray(hessians, np.float32).reshape(
                    k, mp.local_real)
                pad = mp.block - mp.local_real
                grad = mp.shard_local_cols(np.pad(gl, ((0, 0), (0, pad))))
                hess = mp.shard_local_cols(np.pad(hl, ((0, 0), (0, pad))))
            else:
                # single-process custom gradients: [k, n] host arrays
                # from Booster.__boost
                grad = jnp.asarray(np.asarray(gradients, np.float32)
                                   .reshape(k, n))
                hess = jnp.asarray(np.asarray(hessians, np.float32)
                                   .reshape(k, n))

            grad, hess = self._bagging(self.iter, grad, hess)
            s.sync((grad, hess))
        tel.inc("train.dispatches")   # eager gradient/bagging launch
        self._guard_gradients(it, grad, hess)

        should_continue = False
        nl_per_class = []
        gain_acc: List[np.ndarray] = []
        for tid in range(k):
            if self.class_need_train[tid] and self.train_data.num_features > 0:
                gh = jnp.stack([grad[tid] * self.bag_weight,
                                hess[tid] * self.bag_weight,
                                self.bag_weight], axis=1)
                # histogram build + split eval run fused inside the
                # jitted grower — one section attributes them jointly
                # (profile_dir splits them at the XLA op level)
                with self._sec("histogram_split") as s:
                    tel.inc("train.dispatches")
                    tree, row_leaf = self._grow(gh, tid)
                    s.sync((tree, row_leaf))
                nl = int(tree.num_leaves)
            else:
                nl = 1
            nl_per_class.append(nl)

            if nl > 1:
                should_continue = True
                with self._sec("tree_materialize"):
                    ht, sf_inner = self._to_host_tree(tree,
                                                      self.shrinkage_rate)
                    self._guard_tree(it, tid, ht, gain_acc)
                    if self.use_cegb:
                        for f in sf_inner:
                            if f >= 0:
                                self.cegb_used[int(f)] = True
                    row_leaf_np = None
                    if bool(self.config.linear_tree):
                        row_leaf_np = np.asarray(row_leaf)
                        self._fit_linear_leaves(ht, row_leaf_np, grad[tid],
                                                hess[tid])
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    with self._sec("renew_leaf"):
                        if getattr(self, "mp", None) is not None:
                            self._renew_tree_output_mp(ht, row_leaf, tid)
                        else:
                            row_leaf_np = np.asarray(row_leaf)
                            self._renew_tree_output(ht, row_leaf_np, tid)
                # shrinkage then score update (ref: gbdt.cpp:414-419)
                ht.apply_shrinkage(self.shrinkage_rate)
                with self._sec("score_update") as s:
                    tel.inc("train.dispatches",
                            1 + len(self.valid_scores))
                    if bool(self.config.linear_tree) and ht.is_linear \
                            and self.train_data.raw_data is not None:
                        # linear leaves: per-row outputs on host raw data
                        rl = (row_leaf_np if row_leaf_np is not None
                              else np.asarray(row_leaf))
                        delta_lin = ht._linear_outputs(
                            self.train_data.raw_data, rl)
                        self.scores = self.scores.at[tid].add(
                            jnp.asarray(delta_lin, jnp.float32))
                        dt = _DeviceTree(ht, sf_inner)
                        for vi in range(len(self.valid_scores)):
                            if self.valid_data[vi].raw_data is not None:
                                vp = ht.predict_rows(
                                    self.valid_data[vi].raw_data)
                                self.valid_scores[vi] = \
                                    self.valid_scores[vi].at[tid].add(
                                        jnp.asarray(vp, jnp.float32))
                            else:
                                self.valid_scores[vi] = \
                                    self._add_tree_to_score(
                                        self.valid_scores[vi],
                                        self.valid_bins[vi],
                                        dt, tid,
                                        bundle=self._valid_bundle(vi))
                        if abs(init_scores[tid]) > K_EPSILON:
                            ht.add_bias(init_scores[tid])
                            dt.leaf_value = jnp.asarray(ht.leaf_value,
                                                        jnp.float32)
                        self.models.append(ht)
                        self.device_trees.append(dt)
                        s.sync(self.scores)
                        continue
                    lv_dev = jnp.asarray(ht.leaf_value, jnp.float32)
                    if self.parallel_mode != "serial":
                        # sharded row_leaf: plain sharded gather (the
                        # pallas lookup kernel is not SPMD-partitionable
                        # from outside a shard_map region)
                        delta = lv_dev[row_leaf]
                    elif self.use_fused:
                        # per-row gathers are slow on TPU; streaming lookup
                        from ..ops.fused_level import table_lookup
                        delta = table_lookup(
                            row_leaf[None, :], lv_dev,
                            interpret=self.fused_interpret)[0]
                    else:
                        delta = lv_dev[row_leaf]
                    self.scores = self.scores.at[tid].add(delta)
                    cf, cm = self._last_cat or (None, None)
                    dt = _DeviceTree(ht, sf_inner, cat_flag=cf, cat_mask=cm)
                    for vi in range(len(self.valid_scores)):
                        self.valid_scores[vi] = self._add_tree_to_score(
                            self.valid_scores[vi], self.valid_bins[vi],
                            dt, tid, bundle=self._valid_bundle(vi))
                    if abs(init_scores[tid]) > K_EPSILON:
                        ht.add_bias(init_scores[tid])
                        dt.leaf_value = jnp.asarray(ht.leaf_value,
                                                    jnp.float32)
                    self.models.append(ht)
                    self.device_trees.append(dt)
                    s.sync(self.scores)
            else:
                # constant tree (ref: gbdt.cpp:422-441)
                ht = HostTree(1)
                if len(self.models) < k:
                    if not self.class_need_train[tid]:
                        output = (self.objective.boost_from_score(tid)
                                  if self.objective is not None else 0.0)
                    else:
                        output = init_scores[tid]
                    ht.leaf_value[0] = output
                    self.scores = self.scores.at[tid].add(output)
                    for vi in range(len(self.valid_scores)):
                        self.valid_scores[vi] = \
                            self.valid_scores[vi].at[tid].add(output)
                self.models.append(ht)
                self.device_trees.append(
                    _DeviceTree(ht, np.zeros(0, np.int32)))

        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            tel.event("stopped_no_splits", iteration=it)
            if len(self.models) > k:
                for _ in range(k):
                    self.models.pop()
                    self.device_trees.pop()
            return True
        if self._faults:
            from ..resilience import faults as _faults
            _faults.maybe_diverge(self, it)   # chaos: corrupt this rank
        if tel.enabled:
            rec = self._emit_iteration_record(it, nl_per_class, gain_acc)
            if self._health is not None and self._health.due(it):
                try:
                    self._health.check(it, self.models,
                                       rec.get("sections") or {})
                except Exception as e:
                    # rank-local failures degrade to a sentinel INSIDE
                    # check (so the collective still pairs up); reaching
                    # here means the allgather itself failed. Single
                    # process that is survivable — disable and move on.
                    # Multi-process it is NOT: a one-sided failure (e.g.
                    # a timeout) leaves peers blocked in — or past — the
                    # audit collective, and any rank-local recovery
                    # desynchronizes every later host collective, so
                    # re-raise and let the crash flight recorder dump
                    if getattr(self, "mp", None) is not None:
                        raise
                    self._health = None
                    log.warning("health check failed at iteration %d; "
                                "auditing disabled for the rest of the "
                                "run: %s", it, e)
        self._finish_screen_iter()
        self.iter += 1
        return False

    # ------------------------------------------------ numerical guards
    def _guard_gradients(self, it: int, grad, hess) -> None:
        """NaN/Inf detection on the gradient/hessian tensors (sync path
        only — gated on the registry like the sections; one fused device
        reduction per iteration)."""
        if not self.telemetry.enabled:
            return
        try:
            bad_g, bad_h = _count_nonfinite(grad, hess)
            bad_g, bad_h = int(bad_g), int(bad_h)
        except Exception as e:      # a guard must never kill training
            log.debug("gradient guard failed: %s", e)
            return
        if bad_g or bad_h:
            self.telemetry.anomaly("nonfinite_grad_hess", iteration=it,
                                   grad=bad_g, hess=bad_h)

    def _guard_tree(self, it: int, tid: int, ht: HostTree,
                    gain_acc: List[np.ndarray]) -> None:
        """Post-materialize guards: non-finite leaf values / leaf
        weights (hessian sums — the histogram outputs' downstream image)
        or split gains raise an anomaly event; finite gains accumulate
        for the iteration record's split-gain distribution stats."""
        if not self.telemetry.enabled:
            return
        gains = np.asarray(ht.split_gain, np.float64)
        bad = {"leaf_values": int(np.count_nonzero(
                   ~np.isfinite(np.asarray(ht.leaf_value, np.float64)))),
               "leaf_weights": int(np.count_nonzero(
                   ~np.isfinite(np.asarray(ht.leaf_weight, np.float64)))),
               "gains": int(np.count_nonzero(~np.isfinite(gains)))}
        if any(bad.values()):
            self.telemetry.anomaly("nonfinite_tree", iteration=it,
                                   tree=tid, **bad)
        if gains.size:
            gain_acc.append(gains[np.isfinite(gains)])

    def _emit_iteration_record(self, it: int, nl_per_class: List[int],
                               gain_acc: Optional[List[np.ndarray]] = None
                               ) -> Dict:
        """Close iteration ``it``'s telemetry record: estimated collective
        traffic for the distributed growers (the multiproc host-plane
        allgathers are counted for real by MultiProcLayout), device
        memory, per-class leaf counts, split-gain distribution stats."""
        tel = self.telemetry
        if self.parallel_mode != "serial":
            # MEASURED in-jit psum payloads: (count, bytes) recorded
            # from the grower's traced static shapes at its first call
            # (ops/collectives.py), applied once per dispatched grow —
            # the traced program runs its full static level schedule
            # whether or not a tree dried up. Falls back to the analytic
            # per-learner profile only before any grower has traced
            # (cannot happen on this record path: _grow ran first).
            k = self.num_tree_per_iteration
            n_grown = (sum(1 for t in range(k) if self.class_need_train[t])
                       if self.train_data.num_features > 0 else 0)
            if self._coll_per_grow is not None and n_grown:
                cnt, nbytes = self._coll_per_grow
                tel.collective("psum_" + self.parallel_mode,
                               cnt * n_grown, nbytes * n_grown)
            else:
                from ..parallel import collective_profile
                for nl in nl_per_class:
                    if nl > 1:
                        cnt, nbytes = collective_profile(
                            self.parallel_mode, num_leaves=nl,
                            num_features=self.train_data.num_features,
                            max_bins=self.max_bins,
                            top_k=int(self.config.top_k),
                            leafwise=self.grow_policy == "leafwise")
                        tel.collective("psum_" + self.parallel_mode,
                                       cnt, nbytes)
        extra = {"num_leaves": nl_per_class,
                 "bag_cnt": int(self.bag_cnt),
                 "engine": "fused" if self.use_fused else "xla",
                 "mode": self.parallel_mode}
        if gain_acc is not None:
            # the key is always present so count == 0 (no finite gains
            # at all — the broken-gradients symptom the docs point
            # monitoring at) is an observable value, not a missing field
            gains = (np.concatenate(gain_acc) if gain_acc
                     else np.empty(0, np.float64))
            sg = {"count": int(gains.size)}
            if gains.size:
                sg.update(min=float(gains.min()), max=float(gains.max()),
                          mean=float(gains.mean()))
            extra["split_gain"] = sg
        if self._mem_watermarks:
            from ..obs.jaxmon import memory_watermarks
            mem = memory_watermarks(tel)   # per-device gauges; None=CPU
            if mem:
                extra["memory"] = {f"d{d}": st for d, st in mem.items()}
                # back-compat headline gauge: the first device's live
                # bytes (docs ≤ §2 schema; dashboards keyed on it keep
                # working while the per-device series ramp up)
                tel.gauge("device.bytes_in_use",
                          mem[min(mem)].get("bytes_in_use", 0))
        return tel.end_iteration(it, **extra)

    # ------------------------------------------------------------------
    def reset_config(self, config: Config) -> None:
        """Re-derive training state from an updated config
        (ref: gbdt.cpp:686-839 ResetConfig/ResetBaggingConfig)."""
        self.drain_pending()
        self.config = config
        self.shrinkage_rate = float(config.learning_rate)
        self.max_leaves = max(2, int(config.num_leaves))
        self.params = split_params_from_config(config)
        self._stopped_early = False   # a relaxed config may split again
        self._es_finished = False
        self._es_carry = None
        self._evict_reported = set()  # reasons may change with the config
        self._setup_telemetry(config)
        self._setup_resilience(config)
        self._setup_cegb(config)
        self._setup_forced_splits(config, self.train_data)
        # mode-compatibility guards must re-fire: a reset can enable CEGB/
        # forced splits under tree_learner=feature|voting, which degrades
        # the mode to data-parallel (the cached shard_map signatures and
        # data placement change with it)
        self._setup_parallel(config)
        self._setup_engine(config)
        n = self.num_data
        self.is_bagging = False
        self.balanced_bagging = False
        if config.bagging_freq > 0:
            if config.bagging_fraction < 1.0:
                self.is_bagging = True
            elif (self.objective is not None
                  and self.objective.name == "binary"
                  and (config.pos_bagging_fraction < 1.0
                       or config.neg_bagging_fraction < 1.0)):
                self.is_bagging = True
                self.balanced_bagging = True
        if not self.is_bagging:
            self.bag_weight = self._bag_ones()
            self.bag_cnt = n
        # the reference recreates its per-block bagging generators on
        # every config reset (gbdt.cpp ResetBaggingConfig)
        self.bag_streams = ref_random.BlockBaggingStreams(
            int(config.bagging_seed), n)
        self._bag_round_cache = None   # round cache follows the streams
        self.early_stopping_round = int(config.early_stopping_round)
        self.es_first_metric_only = bool(config.first_metric_only)

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """(ref: gbdt.cpp:456 RollbackOneIter). Multi-process: the score
        subtraction routes each device tree on the row-sharded global
        bin matrix (bins_par) — per-row routing partitions cleanly over
        the mesh, so the same in-jit replay works rank-sharded."""
        self.drain_pending()
        # _bag_round_cache is RETAINED: entries are keyed by firing
        # iteration and stay valid, so a rollback within the cache's
        # two-round window replays the exact round it used before.
        # Deeper rollbacks fall off the eviction window and draw the
        # next stream round on retrain, which is also what the reference
        # does at ANY depth (gbdt.cpp:456+230 never rewinds the RNG) —
        # so beyond the window we diverge from the unfused engine's
        # replay but not from reference-style stream semantics.
        if self.iter <= 0:
            return
        train_bins = self._train_bins_replay()
        k = self.num_tree_per_iteration
        for tid in range(k):
            idx = len(self.models) - k + tid
            dt = self.device_trees[idx]
            self.scores = self._add_tree_to_score(
                self.scores, train_bins, dt, tid, scale=-1.0,
                bundle=self._train_bundle())
            for vi in range(len(self.valid_scores)):
                self.valid_scores[vi] = self._add_tree_to_score(
                    self.valid_scores[vi], self.valid_bins[vi], dt, tid,
                    scale=-1.0, bundle=self._valid_bundle(vi))
        del self.models[-k:]
        del self.device_trees[-k:]
        self.iter -= 1

    # ------------------------------------------------------------------
    def eval_metrics(self) -> List[Tuple[str, str, float, bool]]:
        """All (dataset_name, metric_name, value, is_higher_better) tuples.

        Metrics with a device formulation evaluate on the live device
        scores and only their SCALARS cross to host (one batched fetch);
        the rest pull the score matrix once per dataset (the reference's
        behavior, gbdt.cpp:519 OutputMetric -> Metric::Eval on host)."""
        out = []
        if self.training_metrics:
            out.extend(self.eval_metric_set("training",
                                            self.training_metrics,
                                            self.scores))
        for vi, metrics in enumerate(self.valid_metrics):
            out.extend(self.eval_metric_set(self.valid_names[vi], metrics,
                                            self.valid_scores[vi]))
        # one batched device->host fetch for every device scalar
        fetched = jax.device_get([v for (_, _, v, _) in out])
        return [(d, n, float(v), b)
                for (d, n, _, b), v in zip(out, fetched)]

    def eval_metric_set(self, ds_name, metrics, score_dev):
        """Shared device-first metric protocol (also used by
        Booster._eval_set): values may be 0-d device arrays — the caller
        batches the host fetch."""
        out = []
        host_score = None
        # one conversion / one host fetch per (eval set, iteration),
        # shared across the set's metrics: the per-metric cache threads
        # through eval_device so e.g. binary_logloss and binary_error
        # sigmoid the score row once, not once each, and host-form
        # metrics reuse one pulled matrix
        dev_cache: Dict = {}
        for m in metrics:
            vals = m.eval_device(score_dev, self.objective, dev_cache)
            if vals is None and getattr(self, "mp", None) is not None:
                # distributed host form (per-query ranking metrics:
                # rank-local sums + allreduce)
                vals = m.eval_mp(score_dev, self.objective, self.mp)
            if vals is None:
                if host_score is None:
                    if not getattr(score_dev, "is_fully_addressable", True):
                        # multi-process sharded scores cannot be pulled to
                        # one host; only device-form metrics apply
                        warned = getattr(self, "_mp_metric_warned", set())
                        if m.names[0] not in warned:
                            log.warning(
                                "metric %s has no device formulation and "
                                "is skipped under multi-process training",
                                m.names[0])
                            warned.add(m.names[0])
                            self._mp_metric_warned = warned
                        continue
                    host_score = np.asarray(score_dev, np.float64)
                vals = m.eval(host_score, self.objective)
            for name, v in zip(m.names, vals):
                out.append((ds_name, name, v, m.is_bigger_better))
        return out

    def output_metric(self, it: int) -> bool:
        """Print metrics and run early stopping (ref: gbdt.cpp:519
        OutputMetric).  Returns True if early stopping fired."""
        results = self.eval_metrics()
        if it % self.config.metric_freq == 0:
            for ds_name, name, v, _ in results:
                log.info("Iteration:%d, %s %s : %g", it, ds_name, name, v)
        if self.early_stopping_round <= 0:
            return False
        stop = False
        first_name = None
        for ds_name, name, v, bigger in results:
            if ds_name == "training":
                continue
            if self.es_first_metric_only:
                # the FIRST metric is tracked on EVERY valid set; later
                # metrics are skipped (ref: gbdt.cpp:560 early-stopping
                # loop over valid sets with first_metric_only)
                if first_name is None:
                    first_name = name
                elif name != first_name:
                    continue
            key = (ds_name, name)
            cmp = v if bigger else -v
            if key not in self.best_score or cmp > self.best_score[key]:
                self.best_score[key] = cmp
                self.best_iter[key] = it
            elif it - self.best_iter[key] >= self.early_stopping_round:
                stop = True
        return stop

    def train(self) -> None:
        """Full training loop (ref: gbdt.cpp:266 Train). Snapshotting lives
        in engine.train (the driver that owns output paths). Any
        exception unwinding out of the loop triggers the crash flight
        recorder (dump_crash) before re-raising."""
        try:
            self._train_loop()
        except BaseException as exc:
            # BaseException: a Ctrl-C on a wedged run must still dump
            self.dump_crash(exc)
            raise
        self.finalize_telemetry()

    def _train_loop(self) -> None:
        # this loop satisfies the megastep contract: it checks the
        # returned `finished` every call and reads iteration counts off
        # self.iter, so train_one_iter may fuse multiple iterations per
        # call (_megastep_ok still bars configs needing per-iteration
        # observation — GBDT-level early stopping, iteration-granularity
        # telemetry, snapshots). Configured metrics keep per-iteration
        # steps: this loop's output_metric runs once per call, and the
        # reference CLI prints every metric_freq iterations — fusing
        # would silently skip 31 of every 32 metric lines.
        self.arm_megastep(not self.training_metrics
                          and not any(self.valid_metrics))
        try:
            self._train_loop_body()
        finally:
            self.arm_megastep(False)

    def _train_loop_body(self) -> None:
        for it in range(self.iter, int(self.config.num_iterations)):
            finished = self.train_one_iter()
            if not finished:
                finished = self.output_metric(self.iter)
                if finished:
                    self.drain_pending()   # the pop below needs host trees
                    best = min(self.best_iter.values()) \
                        if self.best_iter else self.iter
                    log.info("Early stopping at iteration %d, the best "
                             "iteration round is %d", self.iter, best)
                    self.telemetry.event("early_stopping",
                                         iteration=self.iter,
                                         best_iteration=best)
                    # drop trees after the best iteration
                    extra = (self.iter - best) * self.num_tree_per_iteration
                    for _ in range(extra):
                        self.models.pop()
                        self.device_trees.pop()
                    self.iter = best
            if not finished:
                # sync-driver checkpoint cadence (the megastep path
                # checkpoints at its drain boundaries; the period gate
                # makes a second call after a drain a no-op)
                self.maybe_checkpoint()
            if finished:
                break

    # ------------------------------------------------------------------
    @property
    def num_iterations_trained(self) -> int:
        self.drain_pending()
        return len(self.models) // max(1, self.num_tree_per_iteration)

    # ------------------------------------------------------------------
    # ABI lifecycle: adopt pre-trained trees / refit by leaf assignment
    # (ref: gbdt.h:63 MergeFrom, gbdt.cpp:287 RefitTree,
    # gbdt.cpp:686 ResetTrainingData)
    def _device_tree_from_host(self, ht: HostTree) -> _DeviceTree:
        """Re-bin a raw-threshold HostTree (model-file/string loaded)
        against THIS dataset's mappers so it can route on device bins.
        Valid whenever the mappers match the ones the tree was trained
        with — the CheckAlign precondition ResetTrainingData enforces
        (ref: gbdt.cpp:688)."""
        td = self.train_data
        nn = max(0, ht.num_leaves - 1)
        if nn == 0:
            return _DeviceTree(ht, np.zeros(0, np.int32))
        sf_inner = np.zeros(nn, np.int32)
        thr_bin = np.zeros(nn, np.int32)
        cat_flag = np.zeros(nn, bool)
        cat_mask = np.zeros((nn, self.max_bins), bool)
        for i in range(nn):
            f = int(ht.split_feature[i])
            fi = td.inner_feature_index(f)
            if fi < 0:
                log.fatal("tree splits on feature %d which is trivial "
                          "(unused) in the new training data; bin mappers "
                          "do not align", f)
            sf_inner[i] = fi
            mapper = td.mappers[f]
            if int(ht.decision_type[i]) & 1:   # categorical bitset node
                cat_flag[i] = True
                ci = int(ht.threshold[i])      # index into cat_boundaries
                lo = ht.cat_boundaries[ci]
                hi = ht.cat_boundaries[ci + 1]
                for b, cat in enumerate(mapper.bin_2_categorical):
                    if cat < 0:
                        continue
                    word, bit = divmod(int(cat), 32)
                    if word < hi - lo and \
                            (ht.cat_threshold[lo + word] >> bit) & 1:
                        cat_mask[i, b] = True
            else:
                thr_bin[i] = int(mapper.value_to_bin(float(ht.threshold[i])))
        dt = _DeviceTree(ht, sf_inner)
        dt.threshold_bin = jnp.asarray(thr_bin, jnp.int32)
        # loaded trees may lack leaf_depth; device routing truncates at
        # max_depth steps, so compute the true depth from the topology
        depth = np.zeros(nn, np.int32)
        max_d = 1
        for i in range(nn):           # parents precede children
            for c in (int(ht.left_child[i]), int(ht.right_child[i])):
                if c >= 0:
                    depth[c] = depth[i] + 1
            max_d = max(max_d, int(depth[i]) + 1)
        dt.max_depth = max_d
        if np.any(cat_flag):
            dt.cat_flag = jnp.asarray(cat_flag)
            dt.cat_mask = jnp.asarray(cat_mask)
        return dt

    def adopt_init_models(self, host_trees: List[HostTree]) -> None:
        """Install already-trained trees as the init segment: models are
        PREPENDED and scores are NOT replayed — the reference replays only
        post-init iterations on reset (ref: gbdt.cpp:715 loops over iter_,
        offset by num_init_iteration_), and a fresh reset has none."""
        self.drain_pending()
        k = max(1, self.num_tree_per_iteration)
        if len(host_trees) % k:
            log.fatal("cannot adopt %d trees with %d trees per iteration",
                      len(host_trees), k)
        dts = [self._device_tree_from_host(ht) for ht in host_trees]
        self.models[:0] = host_trees
        self.device_trees[:0] = dts
        self.num_init_iteration += len(host_trees) // k

    def refit_by_leaf_preds(self, leaf_preds: np.ndarray) -> None:
        """Refit every tree's leaf values on the current training data
        from a precomputed [num_data, num_models] leaf-assignment matrix
        (ref: gbdt.cpp:287 RefitTree + serial_tree_learner.cpp:212
        FitByExistingTree): scores start at the init score, each
        iteration's gradients are taken at the running scores, leaf
        outputs are the closed-form Newton values blended with
        refit_decay_rate, and the refitted tree's output is added back
        into the scores before the next iteration."""
        self.drain_pending()
        k = max(1, self.num_tree_per_iteration)
        n = int(self.num_data)
        n_models = len(self.models)
        if leaf_preds.shape != (n, n_models):
            log.fatal("leaf_preds shape %s does not match "
                      "[num_data=%d, num_models=%d]",
                      leaf_preds.shape, n, n_models)
        cfg = self.config
        decay = float(cfg.refit_decay_rate)
        md = self.train_data.metadata
        if md.init_score is not None:
            init = np.asarray(md.init_score, np.float64)
            scores = (init.reshape(k, n, order="C") if init.size == n * k
                      else np.tile(init.reshape(1, n), (k, 1)))
        else:
            scores = np.zeros((k, n), np.float64)
        num_iters = n_models // k
        for it in range(num_iters):
            if self.objective is not None:
                g, h = self.objective.get_gradients(
                    jnp.asarray(scores, jnp.float32))
                g = np.asarray(g, np.float64).reshape(k, n)
                h = np.asarray(h, np.float64).reshape(k, n)
            else:
                g = scores - np.asarray(md.label, np.float64)[None, :]
                h = np.ones_like(g)
            for tid in range(k):
                mi = it * k + tid
                ht = self.models[mi]
                L = ht.num_leaves
                lp = leaf_preds[:, mi]
                if int(lp.max(initial=0)) >= L or int(lp.min(initial=0)) < 0:
                    log.fatal("leaf_preds column %d references leaf %d of "
                              "a %d-leaf tree", mi, int(lp.max()), L)
                sum_g = np.bincount(lp, weights=g[tid], minlength=L)
                # kEpsilon floor matches FitByExistingTree's sum_hess init
                sum_h = np.bincount(lp, weights=h[tid], minlength=L) + 1e-15
                out = np.asarray(jax.device_get(calculate_leaf_output(
                    jnp.asarray(sum_g), jnp.asarray(sum_h), self.params)),
                    np.float64)
                new_vals = (decay * np.asarray(ht.leaf_value, np.float64)
                            + (1.0 - decay) * out * float(ht.shrinkage))
                ht.leaf_value[:] = new_vals[:len(ht.leaf_value)]
                dt = self.device_trees[mi]
                dt.leaf_value = jnp.asarray(ht.leaf_value, jnp.float32)
                scores[tid] += new_vals[lp]
        # live device scores must match the refitted model for subsequent
        # training/eval
        self.scores = jnp.asarray(scores, jnp.float32)


class DART(GBDT):
    """DART dropout boosting (ref: src/boosting/dart.hpp:23)."""

    name = "dart"
    def init(self, config, train_data, objective, training_metrics=()):
        super().init(config, train_data, objective, training_metrics)
        self.drop_rng = ref_random.Random(int(config.drop_seed))
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self.drop_index: List[int] = []

    def _boosting_scores(self):
        # drop trees then compute gradients on the reduced score
        # (ref: dart.hpp:77-86 GetTrainingScore → DroppingTrees)
        self._dropping_trees()
        return self.scores

    def _dropping_trees(self):
        cfg = self.config
        self.drop_index = []
        is_skip = self.drop_rng.next_float() < cfg.skip_drop
        if not is_skip:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop:
                if self.sum_weight > 0:
                    inv_avg = len(self.tree_weight) / self.sum_weight
                    if cfg.max_drop > 0:
                        drop_rate = min(drop_rate,
                                        cfg.max_drop * inv_avg
                                        / self.sum_weight)
                    for i in range(self.iter):
                        if (self.drop_rng.next_float()
                                < drop_rate * self.tree_weight[i] * inv_avg):
                            self.drop_index.append(self.num_init_iteration + i)
                            if len(self.drop_index) >= cfg.max_drop > 0:
                                break
            else:
                if cfg.max_drop > 0 and self.iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter)
                for i in range(self.iter):
                    if self.drop_rng.next_float() < drop_rate:
                        self.drop_index.append(self.num_init_iteration + i)
                        if len(self.drop_index) >= cfg.max_drop > 0:
                            break
        # remove dropped trees from the training score (ref: dart.hpp:131-137)
        k = self.num_tree_per_iteration
        for i in self.drop_index:
            for tid in range(k):
                dt = self.device_trees[i * k + tid]
                self.scores = self._add_tree_to_score(
                    self.scores, self._train_bins_replay(), dt, tid,
                    scale=-1.0, bundle=self._train_bundle())
        nd = len(self.drop_index)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + nd)
        else:
            self.shrinkage_rate = (cfg.learning_rate if nd == 0 else
                                   cfg.learning_rate
                                   / (cfg.learning_rate + nd))

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        ret = super().train_one_iter(gradients, hessians)
        if ret:
            return ret
        self._normalize()
        if not self.config.uniform_drop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
        return False

    def _normalize(self):
        """(ref: dart.hpp:150-199 Normalize)"""
        cfg = self.config
        nd = len(self.drop_index)
        if nd == 0:
            return
        k = self.num_tree_per_iteration
        for i in self.drop_index:
            for tid in range(k):
                idx = i * k + tid
                ht = self.models[idx]
                dt = self.device_trees[idx]
                if not cfg.xgboost_dart_mode:
                    # dropped tree rescaled to k/(k+1) of its old weight
                    ht.apply_shrinkage(nd / (nd + 1.0))
                    # valid score gets -1/(k+1) of old; train gets +k/(k+1)
                    for vi in range(len(self.valid_scores)):
                        self.valid_scores[vi] = self._add_tree_to_score(
                            self.valid_scores[vi], self.valid_bins[vi], dt,
                            tid, scale=-1.0 / (nd + 1.0),
                            bundle=self._valid_bundle(vi))
                    self.scores = self._add_tree_to_score(
                        self.scores, self._train_bins_replay(), dt, tid,
                        scale=nd / (nd + 1.0),
                        bundle=self._train_bundle())
                else:
                    lr = cfg.learning_rate
                    factor = nd / (nd + lr)
                    ht.apply_shrinkage(factor)
                    for vi in range(len(self.valid_scores)):
                        self.valid_scores[vi] = self._add_tree_to_score(
                            self.valid_scores[vi], self.valid_bins[vi], dt,
                            tid, scale=-(1.0 - factor),
                            bundle=self._valid_bundle(vi))
                    self.scores = self._add_tree_to_score(
                        self.scores, self._train_bins_replay(), dt, tid,
                        scale=factor, bundle=self._train_bundle())
                dt.leaf_value = jnp.asarray(ht.leaf_value, jnp.float32)
            if not cfg.uniform_drop:
                j = i - self.num_init_iteration
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[j] / (nd + 1.0)
                    self.tree_weight[j] *= nd / (nd + 1.0)
                else:
                    # (ref: dart.hpp:191-194)
                    lr = cfg.learning_rate
                    self.sum_weight -= self.tree_weight[j] / (nd + lr)
                    self.tree_weight[j] *= nd / (nd + lr)

    def output_metric(self, it):
        # DART never early-stops (ref: dart.hpp:90-93)
        super().output_metric(it)
        return False

    def _capture_boosting_extra(self):
        # drop-set stream position + per-tree weights: the whole DART
        # state beyond the (mutated-in-place, hence checkpointed) models
        payload = {"drop_rng_x": int(self.drop_rng.x),
                   "sum_weight": float(self.sum_weight)}
        return payload, {"dart_tree_weight": np.asarray(self.tree_weight,
                                                       np.float64)}

    def _restore_boosting_extra(self, payload, arrays):
        if "drop_rng_x" in payload:
            self.drop_rng.x = int(payload["drop_rng_x"])
            self.sum_weight = float(payload.get("sum_weight", 0.0))
            self.tree_weight = [float(x)
                                for x in arrays["dart_tree_weight"]]
            self.drop_index = []


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (ref: src/boosting/goss.hpp:25;
    Ke et al., NeurIPS 2017, Algorithm 2).

    From iteration int(1 / learning_rate) on, every iteration keeps
    exactly ``top_k`` rows of the largest |g * h| (summed over the
    classes; ties by the lower row index), draws exactly ``other_k`` of
    the others from a counter-based stream keyed by ``bagging_seed`` and
    the iteration, multiplies their gradient and hessian by (n - top_k) /
    other_k and gives every other row weight 0 (ops/goss.py: one traced
    sampler for every driver). goss.hpp samples per thread block with a
    sequential acceptance probability, so its counts vary; these do not.

    On the fused fast path (one process) the sample is drawn INSIDE the
    step and each tree is grown on the compact matrix of the in-bag rows;
    all rows' scores are updated from the tree's route log
    (``_make_fused_tree_loop``). The synchronous driver calls the same
    sampler jitted alone and takes the sample as a weight vector, on
    every engine. Multi-process:
    sampling is rank-LOCAL over this rank's rows, like the reference's
    per-machine GOSS; thresholds and draws differ per rank by design and
    touch rank-local rows only, so the SPMD control flow stays identical."""

    name = "goss"

    def init(self, config, train_data, objective, training_metrics=()):
        super().init(config, train_data, objective, training_metrics)
        if config.top_rate + config.other_rate > 1.0:
            log.fatal("top_rate + other_rate cannot be larger than 1.0 in GOSS")
        if config.top_rate <= 0 or config.other_rate <= 0:
            log.fatal("top_rate and other_rate should be positive in GOSS")
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS")
        self.is_bagging = False
        self._goss_jit = None

    def _goss_plan(self, n: int, evict_reason: Optional[str] = None):
        from ..ops.goss import goss_plan
        cfg = self.config
        return goss_plan(n, float(cfg.top_rate), float(cfg.other_rate),
                         float(cfg.learning_rate), int(cfg.bagging_seed),
                         evict_reason)

    def _sample_plan(self):
        if self._sample_plan_cache is None:
            # what the compact matrix does not compose with in this job
            # (the job then takes the synchronous driver and the sample
            # as a weight vector)
            what = None
            if getattr(self, "mp", None) is not None:
                what = "multiproc"
            elif self.parallel_mode != "serial":
                what = f"tree_learner={self.parallel_mode}"
            elif getattr(self, "fused_bundle_cols", 0):
                what = "efb"
            elif getattr(self, "quant_bits", 0):
                what = "tpu_quantized_grad"
            elif getattr(self, "fused_packed", None) is not None:
                what = "tpu_adaptive_bins"
            elif getattr(self, "fused_Bp", 0) > 256:
                what = "wide_bins"
            self._sample_plan_cache = self._goss_plan(
                self.num_data, what and f"boosting:goss+{what}")
        return self._sample_plan_cache

    def _capture_boosting_extra(self):
        # the sampler's stream is a function of (bagging_seed, iteration)
        # and the scores, which a resume recomputes: nothing to carry but
        # the stream's identity, checked at restore
        return {"goss_stream": {"kind": "counter_hash_v1",
                                "seed": int(self.config.bagging_seed)}}, {}

    def _restore_boosting_extra(self, payload, arrays):
        st = payload.get("goss_stream")
        if st is None or st.get("kind") != "counter_hash_v1":
            log.warning("checkpoint holds no GOSS counter stream (written "
                        "before the traced sampler): the resumed run "
                        "samples from bagging_seed and the iteration")
        elif int(st["seed"]) != int(self.config.bagging_seed):
            log.warning("bagging_seed %d differs from the checkpoint's %d: "
                        "the resumed run's samples differ",
                        int(self.config.bagging_seed), int(st["seed"]))

    def _sampler(self, n: int):
        """The traced sampler (ops/goss.py) jitted alone, for the
        synchronous driver: (grad [k, n], hess [k, n], it) -> (multiplier
        [n], in-bag 0/1 [n])."""
        if self._goss_jit is None or self._goss_jit[0] != n:
            from ..ops import goss
            plan = self._goss_plan(n)

            @jax.jit
            def sample(grad, hess, it, seed):
                top, other = goss.goss_sample(
                    jnp.sum(jnp.abs(grad * hess), axis=0), it, seed,
                    plan.top_k, plan.other_k)
                return goss.sample_weights(top, other, plan.multiply)
            self._goss_jit = (n, sample, plan)
        return self._goss_jit[1:]

    def _bagging(self, it, grad, hess):
        """(ref: goss.hpp:103-159 BaggingHelper/Bagging.) ``grad`` None:
        a step that draws its own sample (the fast paths)."""
        mp = getattr(self, "mp", None)
        if it < self._sample_plan().first_iter:
            # no subsampling in the first 1/learning_rate iterations
            self.bag_weight = self._bag_ones()
            self.bag_cnt = mp.total_real if mp is not None else self.num_data
            return grad, hess
        if grad is None:
            self.bag_cnt = self._sample_plan().bag_rows
            return grad, hess
        seed = int(self.config.bagging_seed)
        if mp is None:
            sample, plan = self._sampler(self.num_data)
            mult, self.bag_weight = sample(grad, hess, np.int32(it),
                                           np.uint32(seed))
            self.bag_cnt = plan.bag_rows
            return grad * mult[None, :], hess * mult[None, :]
        n = mp.local_real
        maskp = np.zeros(mp.block, np.float32)
        multp = np.ones(mp.block, np.float32)
        if n > 0:
            # (a rank can hold zero rows, query-aligned shards; it
            # contributes nothing but keeps the SPMD control flow)
            sample, plan = self._sampler(n)
            mult, inbag = sample(
                jnp.asarray(mp.local_block(grad, axis=1))[:, :n],
                jnp.asarray(mp.local_block(hess, axis=1))[:, :n],
                np.int32(it), np.uint32(seed + mp.process_index))
            maskp[:n] = np.asarray(inbag)
            multp[:n] = np.where(maskp[:n] > 0, np.asarray(mult), 1.0)
        self._bag_weight_local = maskp
        self.bag_weight = mp.shard_local(maskp)
        from jax.experimental import multihost_utils
        cnts = np.asarray(multihost_utils.process_allgather(
            np.asarray([maskp.sum()], np.int64)))
        self.bag_cnt = int(cnts.sum())
        mult_dev = mp.shard_local(multp)[None, :]
        return grad * mult_dev, hess * mult_dev


class RF(GBDT):
    """Random forest mode (ref: src/boosting/rf.hpp:25).

    No shrinkage; gradients always taken at the constant init score; the
    stored prediction is the average over trees."""

    name = "rf"
    def init(self, config, train_data, objective, training_metrics=()):
        if not (config.bagging_freq > 0 and 0.0 < config.bagging_fraction
                < 1.0):
            log.fatal("RF mode requires bagging "
                      "(bagging_freq > 0, bagging_fraction in (0,1))")
        super().init(config, train_data, objective, training_metrics)
        self.shrinkage_rate = 1.0
        self.average_output = True
        if objective is None:
            log.fatal("RF mode do not support custom objective function, "
                      "please use built-in objectives.")
        # gradients fixed at the init score (ref: rf.hpp:82-100 Boosting)
        self.init_scores = [self._rf_init_score(tid)
                            for tid in range(self.num_tree_per_iteration)]
        base_np = np.tile(np.asarray(self.init_scores, np.float32)[:, None],
                          (1, self.num_data))
        if getattr(self, "mp", None) is not None:
            from jax.sharding import PartitionSpec as P
            base = self.mp.shard_full(base_np, P(None, self.axis_name))
        else:
            base = jnp.asarray(base_np)
        self._fixed_grad, self._fixed_hess = objective.get_gradients(base)

    def _rf_init_score(self, tid):
        cfg = self.config
        if self.has_init_score or not cfg.boost_from_average:
            return 0.0
        return self.objective.boost_from_score(tid)

    def _boost_from_average(self, class_id, update_scorer):
        return 0.0

    def _get_gradients(self):
        return self._fixed_grad, self._fixed_hess

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        self._profiler_step()
        k = self.num_tree_per_iteration
        tel = self.telemetry
        it = self.iter
        tel.begin_iteration(it)
        nl_per_class = []
        with self._sec("boosting") as s:
            grad, hess = (self._get_gradients() if gradients is None
                          else (jnp.asarray(gradients)
                                .reshape(k, self.num_data),
                                jnp.asarray(hessians)
                                .reshape(k, self.num_data)))
            grad, hess = self._bagging(self.iter, grad, hess)
            s.sync((grad, hess))
        should_continue = False
        for tid in range(k):
            gh = jnp.stack([grad[tid] * self.bag_weight,
                            hess[tid] * self.bag_weight,
                            self.bag_weight], axis=1)
            with self._sec("histogram_split") as s:
                tree, row_leaf = self._grow(gh)
                s.sync((tree, row_leaf))
            nl = int(tree.num_leaves)
            nl_per_class.append(nl)
            if nl > 1:
                should_continue = True
                ht, sf_inner = self._to_host_tree(tree, 1.0)
                if (self.objective is not None
                        and self.objective.is_renew_tree_output):
                    if getattr(self, "mp", None) is not None:
                        self._renew_tree_output_rf_mp(ht, row_leaf, tid)
                    else:
                        self._renew_tree_output_rf(ht, np.asarray(row_leaf),
                                                   tid)
                # bias folded into every tree; the averaged score then
                # carries it once (ref: rf.hpp:136-138 AddBias)
                if abs(self.init_scores[tid]) > K_EPSILON:
                    ht.add_bias(self.init_scores[tid])
                lv_dev = jnp.asarray(ht.leaf_value, jnp.float32)
                # scores accumulate the SUM; prediction averages
                self.scores = self.scores.at[tid].add(lv_dev[row_leaf])
                cf, cm = self._last_cat or (None, None)
                dt = _DeviceTree(ht, sf_inner, cat_flag=cf, cat_mask=cm)
                for vi in range(len(self.valid_scores)):
                    self.valid_scores[vi] = self._add_tree_to_score(
                        self.valid_scores[vi], self.valid_bins[vi], dt, tid,
                        bundle=self._valid_bundle(vi))
                self.models.append(ht)
                self.device_trees.append(dt)
            else:
                ht = HostTree(1)
                self.models.append(ht)
                self.device_trees.append(_DeviceTree(ht,
                                                     np.zeros(0, np.int32)))
        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            tel.event("stopped_no_splits", iteration=it)
            if len(self.models) > k:
                for _ in range(k):
                    self.models.pop()
                    self.device_trees.pop()
            return True
        if tel.enabled:
            self._emit_iteration_record(it, nl_per_class)
        self.iter += 1
        return False

    def _renew_tree_output_rf(self, ht, row_leaf, tid):
        # residual against the constant init score (ref: rf.hpp:135-139)
        label = self.train_data.metadata.label
        in_bag = np.asarray(self.bag_weight) > 0
        residual = label.astype(np.float64) - self.init_scores[tid]
        for leaf in range(ht.num_leaves):
            rows = np.nonzero((row_leaf == leaf) & in_bag)[0]
            if len(rows):
                ht.leaf_value[leaf] = self.objective.renew_tree_output(
                    ht.leaf_value[leaf], residual[rows], rows)

    def _renew_tree_output_rf_mp(self, ht, row_leaf, tid):
        mp = self.mp
        rl = mp.local_block(row_leaf)[:mp.local_real]
        label = np.asarray(self.train_data.metadata.label, np.float64)
        residual = label - self.init_scores[tid]
        self._mp_avg_leaf_renewal(ht, rl, residual, self._mp_in_bag_local())

    def eval_metrics(self):
        """Metrics see the AVERAGED score in RF mode."""
        it = max(1, self.num_iterations_trained)
        if getattr(self, "mp", None) is not None:
            # sharded scores cannot be pulled to host; divide on device
            # and ride the parent's device-form eval
            saved, saved_v = self.scores, list(self.valid_scores)
            self.scores = self.scores / it
            self.valid_scores = [v / it for v in saved_v]
            try:
                return super().eval_metrics()
            finally:
                self.scores, self.valid_scores = saved, saved_v
        out = []
        if self.training_metrics:
            score = np.asarray(self.scores, np.float64) / it
            for m in self.training_metrics:
                for name, v in zip(m.names, m.eval(score, self.objective)):
                    out.append(("training", name, v, m.is_bigger_better))
        for vi, metrics in enumerate(self.valid_metrics):
            score = np.asarray(self.valid_scores[vi], np.float64) / it
            for m in metrics:
                for name, v in zip(m.names, m.eval(score, self.objective)):
                    out.append((self.valid_names[vi], name, v,
                                m.is_bigger_better))
        return out
