"""Traced (jit-compatible) formulations of the built-in metrics.

The megastep (boosting/gbdt.py `_make_megastep`) chains whole boosting
iterations inside one ``lax.scan``; evaluating metrics per iteration on
host would force a score fetch per iteration and evict the most common
production config (train + eval sets + early stopping + logging) off
the 0.125-dispatch fast path.  This module re-expresses the built-in
metrics as pure reductions over the device-resident score carries the
scan already maintains, so the whole eval loop runs inside the jit and
only the stacked ``[B, n_slots]`` metric matrix leaves the device at
drain time.

Contract per builder: ``(ops, fn)`` where ``ops`` is a pytree of device
arrays (labels, weights, rank tables) passed as jit OPERANDS — an O(n)
array closed over instead would be embedded in the lowered HLO as a
constant (the same rule the fast step applies to the bin matrix) — and
``fn(score, ops) -> [scalar, ...]`` is a pure traced function returning
one 0-d value per metric name.  Values are f32 on device; parity with
the f64 host metrics is tolerance-tested (tests/test_traced_eval.py),
the same accuracy class the reference GPU build accepts
(docs/GPU-Performance.rst:130-160).

Numbers that are static given the dataset (ideal DCGs, discount/gain
tables, per-slot rank positions, sum of weights) are precomputed on
host exactly like the host metrics do, so the traced forms match the
reference semantics bin-for-bin where the math is discrete (error
counts, rank positions) and to float tolerance elsewhere.

Multi-process (multi-chip megastep, round 12): the training-score carry
is ROW-SHARDED over the global mesh, so a training metric's reductions
are partitioned by GSPMD and finished with the compiler's own
cross-chip psum — every rank sees the identical scalar. Valid-set
arrays are REPLICATED per rank and must be identical on every rank
(the driver enforces this with one digest allgather at precheck —
`engine:multiproc_divergent_valid_data`); the metric values, and
therefore the scan-native early-stop latch, are then identical on
every rank by construction, with no per-iteration collective needed.
The metric operands come from objects re-inited with the GLOBAL
metadata (MultiProcLayout.global_metadata), so label statistics and
weight sums are pod-wide, with pad rows carrying zero weight.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import (AUCMetric, BinaryErrorMetric, BinaryLoglossMetric,
               HuberLossMetric, L1Metric, L2Metric, MAPEMetric,
               MultiErrorMetric, MultiSoftmaxLoglossMetric, NDCGMetric,
               QuantileMetric, RMSEMetric, K_EPSILON, _weighted_auc_jnp)
from ..utils import dcg
from ..utils.query_planes import QueryPlanes


class TracedMetric(NamedTuple):
    """One metric's traced form: names it produces, its operand pytree,
    and the pure eval function."""

    names: Tuple[str, ...]
    ops: tuple
    fn: Callable


def _label_weight_ops(metric) -> tuple:
    label = jnp.asarray(np.asarray(metric.label), jnp.float32)
    weight = (jnp.asarray(np.asarray(metric.weight), jnp.float32)
              if metric.weight is not None else None)
    return (label, weight)


def _traced_row_converter(objective):
    """Traced analog of the host eval's ``objective.convert_output`` for
    [n] score rows, or None when the objective has no traced form (the
    builder then rejects the metric and the driver evicts with a named
    reason)."""
    if objective is None:
        return lambda s: s
    probe = objective.convert_output_jnp(jnp.zeros((1,), jnp.float32))
    if probe is None:
        return None
    return objective.convert_output_jnp


def _weighted_mean(pt, weight, sum_weights: float):
    s = jnp.sum(pt * weight) if weight is not None else jnp.sum(pt)
    return s / jnp.float32(sum_weights)


def _pointwise_builder(metric, objective) -> Optional[TracedMetric]:
    """Regression/binary pointwise-loss family: weighted sum of the
    metric's own ``loss_jnp`` over converted scores, finished by the
    metric's ``average_jnp`` (the traced mirror of `average` — RMSE's
    sqrt, the default sum/weights) so host and traced forms share one
    final-transform definition."""
    convert = _traced_row_converter(objective) \
        if getattr(metric, "convert", True) else (lambda s: s)
    if convert is None:
        return None
    if metric.loss_jnp(jnp.zeros((1,), jnp.float32),
                       jnp.zeros((1,), jnp.float32)) is None:
        return None
    sum_weights = float(metric.sum_weights)

    def fn(score, ops):
        label, weight = ops
        pt = metric.loss_jnp(label, convert(score[0]))
        sl = jnp.sum(pt * weight) if weight is not None else jnp.sum(pt)
        if hasattr(metric, "average_jnp"):     # regression family
            return [metric.average_jnp(sl, jnp.float32(sum_weights))]
        return [sl / jnp.float32(sum_weights)]  # binary family
    return TracedMetric(tuple(metric.names), _label_weight_ops(metric), fn)


def _auc_builder(metric, objective) -> Optional[TracedMetric]:
    def fn(score, ops):
        label, weight = ops
        return [_weighted_auc_jnp(label, score[0], weight)]
    return TracedMetric(tuple(metric.names), _label_weight_ops(metric), fn)


def _multiclass_probs(objective, score):
    """Traced class-probability conversion matching the host metric's
    ``objective.convert_output(score.T)`` branch; ``score`` is [k, n],
    returns [k, n] probabilities, or None when the objective form is
    unknown."""
    if objective is None or objective.name in ("multiclass", "softmax"):
        m = score - jnp.max(score, axis=0, keepdims=True)
        e = jnp.exp(m)
        return e / jnp.sum(e, axis=0, keepdims=True)
    if objective.name == "multiclassova":
        return 1.0 / (1.0 + jnp.exp(-float(objective.sigmoid) * score))
    return None


def _multi_logloss_builder(metric, objective) -> Optional[TracedMetric]:
    if _multiclass_probs(objective, jnp.zeros((2, 1), jnp.float32)) is None:
        return None
    sum_weights = float(metric.sum_weights)
    li = jnp.asarray(np.asarray(metric.label, np.int32))
    _, weight = _label_weight_ops(metric)

    def fn(score, ops):
        li, weight = ops
        probs = _multiclass_probs(objective, score)
        n = score.shape[1]
        p = jnp.clip(probs[li, jnp.arange(n)], K_EPSILON, None)
        return [_weighted_mean(-jnp.log(p), weight, sum_weights)]
    return TracedMetric(tuple(metric.names), (li, weight), fn)


def _multi_error_builder(metric, objective) -> Optional[TracedMetric]:
    sum_weights = float(metric.sum_weights)
    top_k = int(metric.config.multi_error_top_k)
    li = jnp.asarray(np.asarray(metric.label, np.int32))
    _, weight = _label_weight_ops(metric)

    def fn(score, ops):
        li, weight = ops
        n = score.shape[1]
        true_score = score[li, jnp.arange(n)]
        num_larger = jnp.sum(score >= true_score[None, :], axis=0)
        err = (num_larger > top_k).astype(jnp.float32)
        return [_weighted_mean(err, weight, sum_weights)]
    return TracedMetric(tuple(metric.names), (li, weight), fn)


def _ndcg_builder(metric, objective) -> Optional[TracedMetric]:
    """NDCG@k over the queries' length buckets (``utils/query_planes.py``):
    the scores go into one ``[queries_b, width_b]`` plane per bucket and
    ``lax.top_k`` along the short axis names each query's first
    max(eval_at) documents (equal scores: the lower row first, as the
    reference's std::stable_sort ranks them); their gains times the
    discounts, summed cumulatively, are the DCGs. Operands are O(rows)
    (the gains' planes) and O(queries x cutoffs). A query of one document
    is ranked perfectly whatever its score and is in no plane.

    One global two-key sort by (query, -score) does the same in 2.9 ms at
    753,611 rows, and takes the TPU compiler 64 s (PERF.md section 6, PR
    27); the planes' top_k compile in a tenth of that."""
    qb = np.asarray(metric.query_boundaries, np.int64)
    if qb is None or len(qb) < 2:
        return None
    if getattr(metric, "query_row_map", None) is not None:
        return None        # multi-process compacted layout: host path
    num_q = len(qb) - 1
    planes = QueryPlanes(qb, min_docs=2)
    gains = np.asarray(metric.label_gain, np.float64)
    row_gain = gains[np.asarray(metric.label).astype(np.int64)]
    ks = [int(k) for k in metric.eval_at]
    inv_max = np.asarray(metric.inv_max_dcgs, np.float64)   # [num_q, n_k]
    degenerate = inv_max <= 0
    unranked = num_q - sum(planes.queries)

    ops = (planes.operands(),
           tuple(jnp.asarray(g.astype(np.float32))
                 for g in planes.pad_host(row_gain)),
           tuple(jnp.asarray(x.astype(np.float32)) for x in
                 planes.of_queries(np.where(degenerate, 0.0, inv_max))),
           tuple(jnp.asarray(x) for x in planes.of_queries(degenerate)))

    def fn(score, ops):
        layout, gain_planes, inv_maxes, degens = ops
        totals = [jnp.float32(unranked)] * len(ks)
        for s_pad, gain, count, inv, degen in zip(
                planes.to_planes(score[0], layout), gain_planes, layout[1],
                inv_maxes, degens):
            width = s_pad.shape[1]
            top = min(max(ks), width)
            lane = jnp.arange(width, dtype=jnp.int32)[None, :]
            _, at = jax.lax.top_k(
                jnp.where(lane < count[:, None], s_pad, -jnp.inf), top)
            disc = jnp.asarray(dcg.discounts(top).astype(np.float32))
            cum = jnp.cumsum(
                jnp.where(lane[:, :top] < count[:, None],
                          jnp.take_along_axis(gain, at, axis=1) * disc, 0.0),
                axis=1)
            for ki, k in enumerate(ks):
                totals[ki] = totals[ki] + jnp.sum(jnp.where(
                    degen[:, ki], 1.0, cum[:, min(k, top) - 1] * inv[:, ki]))
        return [t / jnp.float32(num_q) for t in totals]
    return TracedMetric(tuple(metric.names), ops, fn)


_BUILDERS = {
    L2Metric: _pointwise_builder,
    RMSEMetric: _pointwise_builder,
    L1Metric: _pointwise_builder,
    QuantileMetric: _pointwise_builder,
    HuberLossMetric: _pointwise_builder,
    MAPEMetric: _pointwise_builder,
    BinaryLoglossMetric: _pointwise_builder,
    BinaryErrorMetric: _pointwise_builder,
    AUCMetric: _auc_builder,
    MultiSoftmaxLoglossMetric: _multi_logloss_builder,
    MultiErrorMetric: _multi_error_builder,
    NDCGMetric: _ndcg_builder,
}


def build_traced_metric(metric, objective) -> Optional[TracedMetric]:
    """Traced form of one host metric instance, or None when this
    metric (or its objective conversion) has no traced formulation."""
    builder = _BUILDERS.get(type(metric))
    if builder is None:
        return None
    try:
        return builder(metric, objective)
    except Exception:
        return None


class TracedEvalPlan:
    """The megastep's per-iteration eval program: every (eval set,
    metric) pair flattened into an ordered slot list matching the
    synchronous engine's ``evaluation_result_list`` exactly (training
    slots first when the train set rides in ``valid_sets``, then each
    valid set's metrics in order), plus the operand pytree the scan
    passes through jit."""

    def __init__(self, groups, slots):
        # groups: [(score_index, [TracedMetric, ...])] where score_index
        # is -1 for the training scores, else the valid-set index
        self._groups = groups
        self.slots = slots            # [(ds_name, metric_name, bigger)]

    @property
    def n_slots(self) -> int:
        return len(self.slots)

    def operands(self) -> tuple:
        return tuple(tuple(tm.ops for tm in metrics)
                     for _, metrics in self._groups)

    def eval_in_scan(self, scores, vscores, metric_ops):
        """[n_slots] f32 metric vector for one iteration's updated score
        carries; runs inside the megastep scan trace."""
        vals = []
        with jax.named_scope("lgbm.eval"):
            for (si, metrics), group_ops in zip(self._groups, metric_ops):
                sc = scores if si < 0 else vscores[si]
                for tm, ops in zip(metrics, group_ops):
                    # the metric's own name as the child scope:
                    # lgbm.eval/auc, lgbm.eval/ndcg (one for its cutoffs)
                    with jax.named_scope(tm.names[0].split("@")[0]):
                        vals.extend(tm.fn(sc, ops))
            if not vals:
                return jnp.zeros((0,), jnp.float32)
            return jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])


def build_plan(gbdt, include_training: bool):
    """(plan, None) when every configured metric has a traced form;
    (None, reason) naming the first untraceable metric otherwise."""
    groups = []
    slots = []

    def add(ds_name, si, metrics):
        traced = []
        for m in metrics:
            tm = build_traced_metric(m, gbdt.objective)
            if tm is None:
                return f"metric:{m.names[0]}"
            traced.append(tm)
            for name in tm.names:
                slots.append((ds_name, name, bool(m.is_bigger_better)))
        groups.append((si, traced))
        return None

    if include_training and gbdt.training_metrics:
        err = add("training", -1, gbdt.training_metrics)
        if err:
            return None, err
    for vi, metrics in enumerate(gbdt.valid_metrics):
        err = add(gbdt.valid_names[vi], vi, metrics)
        if err:
            return None, err
    return TracedEvalPlan(groups, slots), None
