"""The yardstick's own arithmetic: the seeded generator, the numpy walk,
the rank AUC, the work model and the peaks table."""
import numpy as np
import pytest

from harness import data, reference, work


def test_same_seed_same_bytes_and_a_prefix_is_the_same_data():
    a = data.make_data(3, 5000, 700, 28)
    b = data.make_data(3, 5000, 700, 28)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    longer = data.make_data(3, data.BLOCK + 10, 700, 28)
    assert np.array_equal(longer[0][:5000], a[0])
    assert np.array_equal(longer[1][:5000], a[1])
    assert np.array_equal(longer[2], a[2])          # validation unmoved
    other = data.make_data(4, 5000, 700, 28)
    assert not np.array_equal(other[0], a[0])
    # another seed is another sample of the SAME problem: the margin the
    # labels follow does not move with the seed
    w = data.weights(28)
    for X, y in (a[:2], other[:2]):
        assert 0.75 < (((X - 0.5) @ w > 0) == (y > 0)).mean() < 0.95
    assert a[0].dtype == np.float32 and 0.4 < a[1].mean() < 0.6


def _stump(feature, threshold, lo, hi, missing="None", default_left=True):
    return {"num_leaves": 2, "tree_structure": {
        "split_index": 0, "split_feature": feature, "threshold": threshold,
        "decision_type": "<=", "default_left": default_left,
        "missing_type": missing,
        "left_child": {"leaf_index": 0, "leaf_value": lo},
        "right_child": {"leaf_index": 1, "leaf_value": hi}}}


def test_walk_routes_like_the_reference_decision():
    deep = {"num_leaves": 3, "tree_structure": {
        "split_index": 0, "split_feature": 0, "threshold": 0.5,
        "decision_type": "<=", "default_left": True, "missing_type": "None",
        "left_child": {"leaf_index": 0, "leaf_value": 1.0},
        "right_child": {
            "split_index": 1, "split_feature": 1, "threshold": 0.25,
            "decision_type": "<=", "default_left": True,
            "missing_type": "None",
            "left_child": {"leaf_index": 1, "leaf_value": 10.0},
            "right_child": {"leaf_index": 2, "leaf_value": 100.0}}}}
    single = {"num_leaves": 1, "tree_structure": {"leaf_value": 0.5}}
    nan_right = _stump(1, 0.5, -1.0, -2.0, missing="NaN",
                       default_left=False)
    trees = reference.flatten({"tree_info": [deep, single, nan_right]})
    X = np.array([[0.5, 0.9], [0.6, 0.25], [0.6, 0.3], [0.1, np.nan]],
                 np.float32)
    np.testing.assert_allclose(
        reference.walk(trees, X),
        [1.0 + 0.5 - 2.0, 10.0 + 0.5 - 1.0, 100.0 + 0.5 - 1.0,
         1.0 + 0.5 - 2.0])


def test_walk_refuses_what_it_cannot_route():
    cat = _stump(0, 1.0, 0.0, 1.0)
    cat["tree_structure"]["decision_type"] = "=="
    with pytest.raises(ValueError, match="numerical"):
        reference.flatten({"tree_info": [cat]})


def test_rank_auc_against_the_pair_count():
    rng = np.random.default_rng(0)
    y = rng.random(300) < 0.4
    s = np.round(rng.random(300) + 0.3 * y, 2)      # with ties
    pos, neg = s[y], s[~y]
    pairs = ((pos[:, None] > neg[None, :]).sum()
             + 0.5 * (pos[:, None] == neg[None, :]).sum())
    assert reference.rank_auc(y, s) == pytest.approx(
        pairs / (pos.size * neg.size), abs=1e-12)


def test_work_model_and_peaks():
    assert work.padded_bins(63) == 64 and work.padded_bins(255) == 256
    # ROADMAP A1: a 255-leaf tree over 10.5M x 28 x 64 bins, 5 channels
    assert work.onehot_ops(10.5e6, 28, 63, 255) == pytest.approx(
        2 * 10.5e6 * 1792 * 5 * 255)
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert work.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert work.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
