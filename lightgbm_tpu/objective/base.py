"""Objective function interface.

TPU-native analog of ref: include/LightGBM/objective_function.h.  The contract
the boosting layer depends on:

- ``init(metadata, num_data)``: bind label/weight/query arrays (host numpy).
- ``get_gradients(score) -> (grad, hess)``: jnp arrays shaped like ``score``
  (``[k, n]`` with k = num_model_per_iteration).
- ``boost_from_score(class_id)``: initial score (host scalar).
- ``convert_output(raw)``: raw score -> output space (sigmoid/softmax/exp...).
- ``renew_tree_output(...)``: optional leaf-value recomputation (L1/quantile/
  MAPE/Huber) — see booster for the call site.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from ..utils import log

K_EPSILON = 1e-15


def percentile(data: np.ndarray, alpha: float) -> float:
    """Unweighted percentile with the reference's interpolation
    (ref: src/objective/regression_objective.hpp:18 PercentileFun)."""
    cnt = len(data)
    if cnt <= 1:
        return float(data[0]) if cnt else 0.0
    float_pos = (1.0 - alpha) * cnt
    pos = int(float_pos)
    sorted_desc = np.sort(data)[::-1]
    if pos < 1:
        return float(sorted_desc[0])
    if pos >= cnt:
        return float(sorted_desc[-1])
    bias = float_pos - pos
    v1 = float(sorted_desc[pos - 1])
    v2 = float(sorted_desc[pos])
    return v1 - (v1 - v2) * bias


def weighted_percentile(data: np.ndarray, weight: np.ndarray,
                        alpha: float) -> float:
    """Weighted percentile (ref: regression_objective.hpp:50
    WeightedPercentileFun — including its interpolation quirks)."""
    cnt = len(data)
    if cnt <= 1:
        return float(data[0]) if cnt else 0.0
    order = np.argsort(data, kind="stable")
    sdata = np.asarray(data, dtype=np.float64)[order]
    cdf = np.cumsum(np.asarray(weight, dtype=np.float64)[order])
    threshold = cdf[-1] * alpha
    pos = int(np.searchsorted(cdf, threshold, side="right"))
    pos = min(pos, cnt - 1)
    if pos == 0 or pos == cnt - 1:
        return float(sdata[pos])
    v1, v2 = float(sdata[pos - 1]), float(sdata[pos])
    if cdf[pos + 1] - cdf[pos] >= 1.0:
        return (threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos]) * (v2 - v1) + v1
    return v2


class ObjectiveFunction:
    """Base objective (ref: include/LightGBM/objective_function.h:22)."""

    name = "base"

    def __init__(self, config):
        self.config = config
        self.num_data = 0
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self._traced_ok: Optional[bool] = None

    # ------------------------------------------------------------------
    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = metadata.label
        self.weight = metadata.weight
        self._traced_ok = None   # operands are rebuilt from the new data

    def get_gradients(self, score) -> Tuple:
        raise NotImplementedError

    # -- in-jit gradient protocol -------------------------------------
    # The boosting fast path traces gradients into its per-iteration jit.
    # O(num_data) arrays must enter that jit as ARGUMENTS (closed-over
    # device arrays embed into the lowered program as constants — 100s of
    # MB of HLO at Higgs scale). Objectives that support this return
    # their large arrays from gradient_operands() and compute from them
    # in gradients_from(); get_gradients stays the eager entry point.
    def gradient_operands(self):
        """Pytree of device arrays for gradients_from, or None if this
        objective's gradients cannot be traced (host state, RNG)."""
        return None

    def gradients_from(self, score, operands) -> Tuple:
        raise NotImplementedError

    def convert_output_jnp(self, raw):
        """Traced (jnp) analog of convert_output for on-device metric
        evaluation, or None when no device form exists (those metrics
        fall back to the host numpy path)."""
        return None

    def supports_traced_gradients(self) -> bool:
        """True only when the class providing the most-derived
        get_gradients ALSO provides its own gradients_from — a subclass
        overriding just get_gradients (huber/fair/poisson/... on top of
        L2) must not inherit the base pair, or the traced path would
        silently train with the base objective's gradients. Cached per
        data binding: the fast path and the megastep chunker consult
        this every iteration."""
        if self._traced_ok is None:
            self._traced_ok = False
            for k in type(self).__mro__:
                if "get_gradients" in k.__dict__:
                    self._traced_ok = (
                        "gradients_from" in k.__dict__
                        and self.gradient_operands() is not None)
                    break
        return self._traced_ok

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, raw):
        return raw

    def to_string(self) -> str:
        return self.name

    # ------------------------------------------------------------------
    @property
    def num_model_per_iteration(self) -> int:
        return 1

    @property
    def num_prediction_per_row(self) -> int:
        return 1

    @property
    def is_constant_hessian(self) -> bool:
        return False

    @property
    def is_renew_tree_output(self) -> bool:
        return False

    def renew_tree_output(self, leaf_pred: float, residuals: np.ndarray,
                          row_idx: np.ndarray) -> float:
        """New output for one leaf given residuals (label-score) of its rows
        (ref: objective_function.h RenewTreeOutput)."""
        return leaf_pred

    @property
    def need_accurate_prediction(self) -> bool:
        return True

    def class_need_train(self, class_id: int) -> bool:
        return True

    def check_label(self) -> None:
        pass

    def _weights_or_ones(self) -> np.ndarray:
        if self.weight is not None:
            return self.weight
        return np.ones(self.num_data, dtype=np.float32)
