"""Objective gradient tests: analytic grad/hess vs finite differences of the
corresponding loss (the reference encodes the same closed forms,
src/objective/*.hpp)."""
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.objective import create_objective, create_objective_from_string


def setup_obj(name, label, params=None, weight=None, group=None):
    cfg = Config(dict({"objective": name}, **(params or {})))
    obj = create_objective(cfg)
    md = Metadata(len(label))
    md.set_label(np.asarray(label, np.float32))
    if weight is not None:
        md.set_weight(weight)
    if group is not None:
        md.set_group(group)
    obj.init(md, len(label))
    return obj


def numeric_grad(loss_fn, score, eps=1e-4):
    g = np.zeros_like(score)
    for i in range(len(score)):
        sp = score.copy()
        sp[i] += eps
        sm = score.copy()
        sm[i] -= eps
        g[i] = (loss_fn(sp) - loss_fn(sm)) / (2 * eps)
    return g


@pytest.mark.parametrize("name,loss", [
    ("regression", lambda y, s: 0.5 * np.sum((s - y) ** 2)),
    ("binary", lambda y, s: np.sum(np.log1p(np.exp(-(2 * y - 1) * s)))),
    ("poisson", lambda y, s: np.sum(np.exp(s) - y * s)),
    ("gamma", lambda y, s: np.sum(y * np.exp(-s) + s)),
    ("cross_entropy",
     lambda y, s: -np.sum(y * np.log(1 / (1 + np.exp(-s)))
                          + (1 - y) * np.log(1 - 1 / (1 + np.exp(-s))))),
])
def test_gradient_matches_finite_difference(name, loss):
    rng = np.random.RandomState(0)
    n = 20
    if name in ("poisson", "gamma"):
        label = rng.rand(n).astype(np.float32) + 0.5
    elif name in ("binary",):
        label = (rng.rand(n) > 0.5).astype(np.float32)
    elif name == "cross_entropy":
        label = rng.rand(n).astype(np.float32)
    else:
        label = rng.randn(n).astype(np.float32)
    obj = setup_obj(name, label)
    score = rng.randn(n).astype(np.float64) * 0.5
    g, h = obj.get_gradients(jnp.asarray(score[None, :], jnp.float32))
    g_num = numeric_grad(lambda s: loss(label.astype(np.float64), s), score)
    np.testing.assert_allclose(np.asarray(g)[0], g_num, rtol=2e-2, atol=2e-3)
    assert (np.asarray(h)[0] >= 0).all()


def test_l2_boost_from_score_is_mean():
    label = np.array([1.0, 2.0, 3.0, 4.0])
    obj = setup_obj("regression", label)
    assert obj.boost_from_score(0) == pytest.approx(2.5)
    w = np.array([1.0, 0.0, 0.0, 1.0], np.float32)
    obj = setup_obj("regression", label, weight=w)
    assert obj.boost_from_score(0) == pytest.approx(2.5)


def test_binary_boost_from_score_logit():
    label = np.array([1.0] * 30 + [0.0] * 10)
    obj = setup_obj("binary", label)
    assert obj.boost_from_score(0) == pytest.approx(np.log(0.75 / 0.25))


def test_l1_renew_is_median():
    label = np.zeros(5, np.float32)
    obj = setup_obj("regression_l1", label)
    res = np.array([1.0, 5.0, 2.0, 8.0, 3.0])
    assert obj.is_renew_tree_output
    out = obj.renew_tree_output(0.0, res, np.arange(5))
    # the reference PercentileFun interpolates between the 2nd and 3rd
    # largest: 5 - (5-3)*0.5 = 4 (ref: regression_objective.hpp:18-47)
    assert out == pytest.approx(4.0)
    # when float_pos lands on an integer, bias=0 picks the pos-1 largest
    out2 = obj.renew_tree_output(0.0, np.array([1.0, 2.0, 3.0, 4.0]),
                                 np.arange(4))
    assert out2 == pytest.approx(3.0)


def test_quantile_renew_is_percentile():
    label = np.zeros(101, np.float32)
    obj = setup_obj("quantile", label, {"alpha": 0.9})
    res = np.arange(101, dtype=np.float64)
    out = obj.renew_tree_output(0.0, res, np.arange(101))
    assert 88 <= out <= 92


def test_multiclass_gradients_sum_zero():
    rng = np.random.RandomState(1)
    label = rng.randint(0, 3, 30)
    obj = setup_obj("multiclass", label, {"num_class": 3})
    score = jnp.asarray(rng.randn(3, 30), jnp.float32)
    g, h = obj.get_gradients(score)
    np.testing.assert_allclose(np.asarray(g).sum(axis=0), 0.0, atol=1e-5)
    assert (np.asarray(h) > 0).all()


def test_lambdarank_zero_gradient_when_perfect_separation_saturates():
    # lambdas push high-label docs up: with equal scores, gradient of the
    # top-label doc must be negative (boosting subtracts gradients)
    label = np.array([2, 1, 0, 0], np.float32)
    obj = setup_obj("lambdarank", label, group=[4])
    g, h = obj.get_gradients(jnp.zeros((1, 4), jnp.float32))
    g = np.asarray(g)[0]
    assert g[0] < 0          # top doc pushed up
    assert g[2] > 0 or g[3] > 0  # low docs pushed down
    assert abs(g.sum()) < 1e-5


@pytest.mark.parametrize("name", ["lambdarank", "rank_xendcg"])
@pytest.mark.parametrize("params", [
    {}, {"lambdarank_norm": False}, {"lambdarank_truncation_level": 3}],
    ids=["default", "nonorm", "trunc3"])
def test_lambdarank_lambdas_balance_within_each_query(name, params):
    """No finite differences (the lambdas are no gradient of a loss that
    could be written down): within every query they sum to zero, every
    hessian is >= 0, and a query of one document or of equal labels
    (lambdarank: no pair differs) gets zeros."""
    rng = np.random.RandomState(4)
    sizes = [1, 6, 40, 150, 9, 2]
    qb = np.r_[0, np.cumsum(sizes)]
    label = rng.randint(0, 4, qb[-1]).astype(np.float32)
    label[qb[1]:qb[2]] = 3.0                      # equal labels
    obj = setup_obj(name, label, params, group=sizes)
    g, h = obj.get_gradients(jnp.asarray(rng.randn(1, qb[-1]), jnp.float32))
    g, h = np.asarray(g)[0], np.asarray(h)[0]
    assert np.isfinite(g).all() and (h >= 0).all()
    for a, b in zip(qb[:-1], qb[1:]):
        # float32 sums of up to 150 terms of size <= 4
        assert abs(g[a:b].sum()) < 2e-5 * max(1.0, np.abs(g[a:b]).sum())
    assert g[0] == 0 and h[0] == 0
    if name == "lambdarank":
        assert (g[qb[1]:qb[2]] == 0).all() and (h[qb[1]:qb[2]] == 0).all()
        assert np.abs(g[qb[2]:]).max() > 0


def test_rank_xendcg_gradients_finite():
    rng = np.random.RandomState(2)
    label = rng.randint(0, 4, 20).astype(np.float32)
    obj = setup_obj("rank_xendcg", label, group=[10, 10])
    g, h = obj.get_gradients(jnp.asarray(rng.randn(1, 20), jnp.float32))
    assert np.isfinite(np.asarray(g)).all()
    assert np.isfinite(np.asarray(h)).all()


def test_objective_tostring_roundtrip():
    label = (np.arange(20) % 2).astype(np.float32)
    obj = setup_obj("binary", label, {"sigmoid": 2.0})
    s = obj.to_string()
    obj2 = create_objective_from_string(s)
    assert obj2.name == "binary"
    assert obj2.sigmoid == pytest.approx(2.0)


def test_unbalance_weights():
    label = np.array([1.0] * 10 + [0.0] * 90, np.float32)
    obj = setup_obj("binary", label, {"is_unbalance": True})
    g, h = obj.get_gradients(jnp.zeros((1, 100), jnp.float32))
    g = np.asarray(g)[0]
    # positive-class gradient magnified by 9x
    assert abs(g[0]) == pytest.approx(9 * abs(g[-1]), rel=1e-5)
