"""Regression objectives.

TPU-native analog of ref: src/objective/regression_objective.hpp.  Gradients
are single fused jnp expressions over the whole score vector (the reference's
OpenMP loops, vectorized).  Formula citations per class below.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..utils import log
from .base import ObjectiveFunction, percentile, weighted_percentile


def _sign(x):
    return jnp.where(x > 0, 1.0, jnp.where(x < 0, -1.0, 0.0))


class RegressionL2Loss(ObjectiveFunction):
    """L2 loss; grad = score - label, hess = 1
    (ref: regression_objective.hpp:127-141)."""

    name = "regression"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = bool(getattr(config, "reg_sqrt", False))
        self._raw_label = None

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            self._raw_label = self.label
            self.label = (np.sign(self.label)
                          * np.sqrt(np.abs(self.label))).astype(np.float32)
        self._label_j = jnp.asarray(self.label)
        self._weight_j = (jnp.asarray(self.weight)
                          if self.weight is not None else None)

    def get_gradients(self, score):
        return self.gradients_from(score, self.gradient_operands())

    def gradient_operands(self):
        return (self._label_j, self._weight_j)

    def gradients_from(self, score, operands):
        label, weight = operands
        diff = score - label[None, :]
        if weight is None:
            return diff, jnp.ones_like(diff)
        w = weight[None, :]
        return diff * w, jnp.broadcast_to(w, diff.shape)

    def boost_from_score(self, class_id):
        # ref: regression_objective.hpp:173 — weighted label mean
        if self.weight is not None:
            return float(np.sum(self.label * self.weight) / np.sum(self.weight))
        return float(np.mean(self.label))

    def convert_output(self, raw):
        if self.sqrt:
            return np.sign(raw) * raw * raw
        return raw

    def convert_output_jnp(self, raw):
        # valid for any subclass whose effective convert_output is the one
        # defined HERE (poisson/gamma/tweedie override it with exp)
        for k in type(self).__mro__:
            if "convert_output" in k.__dict__:
                if k is not RegressionL2Loss:
                    return None
                break
        if self.sqrt:
            return jnp.sign(raw) * raw * raw
        return raw

    def to_string(self):
        return self.name + (" sqrt" if self.sqrt else "")

    @property
    def is_constant_hessian(self):
        return self.weight is None


class RegressionL1Loss(RegressionL2Loss):
    """L1; grad = sign(diff); leaves renewed to weighted median of residuals
    (ref: regression_objective.hpp:217-293)."""

    name = "regression_l1"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = False

    def get_gradients(self, score):
        diff = score - self._label_j[None, :]
        g = _sign(diff)
        if self._weight_j is None:
            return g, jnp.ones_like(g)
        w = self._weight_j[None, :]
        return g * w, jnp.broadcast_to(w, g.shape)

    def boost_from_score(self, class_id):
        if self.weight is not None:
            return weighted_percentile(self.label, self.weight, 0.5)
        return percentile(self.label, 0.5)

    @property
    def is_renew_tree_output(self):
        return True

    def renew_tree_output(self, leaf_pred, residuals, row_idx):
        if self.weight is not None:
            return weighted_percentile(residuals, self.weight[row_idx], 0.5)
        return percentile(residuals, 0.5)

    @property
    def is_constant_hessian(self):
        return self.weight is None

    def to_string(self):
        return self.name


class RegressionHuberLoss(RegressionL2Loss):
    """Huber; grad clipped at alpha (ref: regression_objective.hpp:313-338)."""

    name = "huber"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = False
        self.alpha = float(config.alpha)
        if self.alpha <= 0:
            log.fatal("alpha should be greater than 0 in huber loss")

    def get_gradients(self, score):
        diff = score - self._label_j[None, :]
        g = jnp.clip(diff, -self.alpha, self.alpha)
        if self._weight_j is None:
            return g, jnp.ones_like(g)
        w = self._weight_j[None, :]
        return g * w, jnp.broadcast_to(w, g.shape)

    def to_string(self):
        return self.name

    @property
    def is_constant_hessian(self):
        return self.weight is None


class RegressionFairLoss(RegressionL2Loss):
    """Fair loss; grad = c·x/(|x|+c), hess = c²/(|x|+c)²
    (ref: regression_objective.hpp:362-381)."""

    name = "fair"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = False
        self.c = float(config.fair_c)

    def get_gradients(self, score):
        x = score - self._label_j[None, :]
        ax_c = jnp.abs(x) + self.c
        g = self.c * x / ax_c
        h = self.c * self.c / (ax_c * ax_c)
        if self._weight_j is not None:
            w = self._weight_j[None, :]
            g, h = g * w, h * w
        return g, h

    def to_string(self):
        return self.name

    @property
    def is_constant_hessian(self):
        return False


class RegressionPoissonLoss(RegressionL2Loss):
    """Poisson; grad = exp(s) - y, hess = exp(s + max_delta_step)
    (ref: regression_objective.hpp:440-466)."""

    name = "poisson"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = False
        self.max_delta_step = float(config.poisson_max_delta_step)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.check_label()

    def check_label(self):
        if np.min(self.label) < 0.0:
            log.fatal("[%s]: at least one target label is negative", self.name)
        if np.sum(self.label) == 0.0:
            log.fatal("[%s]: sum of labels is zero", self.name)

    def get_gradients(self, score):
        exp_s = jnp.exp(score)
        g = exp_s - self._label_j[None, :]
        h = jnp.exp(score + self.max_delta_step)
        if self._weight_j is not None:
            w = self._weight_j[None, :]
            g, h = g * w, h * w
        return g, h

    def boost_from_score(self, class_id):
        mean = RegressionL2Loss.boost_from_score(self, class_id)
        return float(np.log(max(mean, 1e-300)))

    def convert_output(self, raw):
        return np.exp(raw)

    def to_string(self):
        return self.name

    @property
    def is_constant_hessian(self):
        return False


class RegressionQuantileLoss(RegressionL2Loss):
    """Quantile (pinball); renews leaves to the alpha-quantile of residuals
    (ref: regression_objective.hpp:480-571)."""

    name = "quantile"

    def __init__(self, config):
        super().__init__(config)
        self.sqrt = False
        self.alpha = float(config.alpha)
        if not (0.0 < self.alpha < 1.0):
            log.fatal("alpha should be in (0, 1) for quantile objective")

    def get_gradients(self, score):
        delta = score - self._label_j[None, :]
        g = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        if self._weight_j is None:
            return g, jnp.ones_like(g)
        w = self._weight_j[None, :]
        return g * w, jnp.broadcast_to(w, g.shape)

    def boost_from_score(self, class_id):
        if self.weight is not None:
            return weighted_percentile(self.label, self.weight, self.alpha)
        return percentile(self.label, self.alpha)

    @property
    def is_renew_tree_output(self):
        return True

    def renew_tree_output(self, leaf_pred, residuals, row_idx):
        if self.weight is not None:
            return weighted_percentile(residuals, self.weight[row_idx],
                                       self.alpha)
        return percentile(residuals, self.alpha)

    def to_string(self):
        return f"{self.name} alpha:{self.alpha}"

    @property
    def is_constant_hessian(self):
        return self.weight is None


class RegressionMAPELoss(RegressionL1Loss):
    """MAPE; L1 with per-row weight 1/max(1, |label|)
    (ref: regression_objective.hpp:580-668)."""

    name = "mape"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(np.abs(self.label) < 1):
            log.warning("Some label values are < 1 in absolute value. MAPE is "
                        "unstable with such values, so LightGBM rounds them to "
                        "1.0 when calculating MAPE.")
        lw = 1.0 / np.maximum(1.0, np.abs(self.label))
        if self.weight is not None:
            lw = lw * self.weight
        self.label_weight = lw.astype(np.float32)
        self._label_weight_j = jnp.asarray(self.label_weight)

    def get_gradients(self, score):
        diff = score - self._label_j[None, :]
        g = _sign(diff) * self._label_weight_j[None, :]
        if self._weight_j is None:
            return g, jnp.ones_like(g)
        w = self._weight_j[None, :]
        return g, jnp.broadcast_to(w, g.shape)

    def boost_from_score(self, class_id):
        return weighted_percentile(self.label, self.label_weight, 0.5)

    def renew_tree_output(self, leaf_pred, residuals, row_idx):
        return weighted_percentile(residuals, self.label_weight[row_idx], 0.5)

    @property
    def is_constant_hessian(self):
        return True

    def to_string(self):
        return self.name


class RegressionGammaLoss(RegressionPoissonLoss):
    """Gamma; grad = 1 - y·exp(-s), hess = y·exp(-s)
    (ref: regression_objective.hpp:687-706)."""

    name = "gamma"

    def get_gradients(self, score):
        e = jnp.exp(-score)
        y = self._label_j[None, :]
        g = 1.0 - y * e
        h = y * e
        if self._weight_j is not None:
            w = self._weight_j[None, :]
            g, h = g * w, h * w
        return g, h


class RegressionTweedieLoss(RegressionPoissonLoss):
    """Tweedie with variance power rho
    (ref: regression_objective.hpp:723-744)."""

    name = "tweedie"

    def __init__(self, config):
        super().__init__(config)
        self.rho = float(config.tweedie_variance_power)

    def get_gradients(self, score):
        y = self._label_j[None, :]
        e1 = jnp.exp((1.0 - self.rho) * score)
        e2 = jnp.exp((2.0 - self.rho) * score)
        g = -y * e1 + e2
        h = -y * (1.0 - self.rho) * e1 + (2.0 - self.rho) * e2
        if self._weight_j is not None:
            w = self._weight_j[None, :]
            g, h = g * w, h * w
        return g, h
