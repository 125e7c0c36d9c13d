"""BinMapper unit tests (behavior mirrors ref: src/io/bin.cpp FindBin)."""
import numpy as np
import pytest

from lightgbm_tpu.binning import (BIN_CATEGORICAL, BIN_NUMERICAL, MISSING_NAN,
                                  MISSING_NONE, MISSING_ZERO, BinMapper)


def make_mapper(values, total=None, max_bin=255, min_data_in_bin=3,
                bin_type=BIN_NUMERICAL, use_missing=True,
                zero_as_missing=False):
    m = BinMapper()
    values = np.asarray(values, dtype=np.float64)
    nz = values[(np.abs(values) > 1e-35) | np.isnan(values)]
    m.find_bin(nz, total_sample_cnt=total or len(values), max_bin=max_bin,
               min_data_in_bin=min_data_in_bin, min_split_data=0,
               pre_filter=False, bin_type=bin_type, use_missing=use_missing,
               zero_as_missing=zero_as_missing)
    return m


def test_bins_are_order_preserving():
    rng = np.random.RandomState(0)
    vals = rng.randn(5000)
    m = make_mapper(vals, max_bin=63)
    bins = m.value_to_bin(vals)
    order = np.argsort(vals)
    assert (np.diff(bins[order]) >= 0).all()


def test_bin_count_capped():
    rng = np.random.RandomState(1)
    vals = rng.randn(10000)
    m = make_mapper(vals, max_bin=16)
    assert m.num_bin <= 16


def test_distinct_values_get_own_bins():
    vals = np.repeat([1.0, 2.0, 3.0], 100)
    m = make_mapper(vals, min_data_in_bin=1)
    bins = m.value_to_bin(np.array([1.0, 2.0, 3.0]))
    assert len(set(bins.tolist())) == 3


def test_nan_goes_to_last_bin():
    vals = np.concatenate([np.random.RandomState(2).randn(1000),
                           [np.nan] * 50])
    m = make_mapper(vals)
    assert m.missing_type == MISSING_NAN
    assert m.value_to_bin(np.nan) == m.num_bin - 1
    assert m.value_to_bin(0.0) < m.num_bin - 1


def test_no_missing():
    vals = np.random.RandomState(3).randn(500) + 10
    m = make_mapper(vals)
    assert m.missing_type == MISSING_NONE


def test_zero_as_missing():
    vals = np.concatenate([np.random.RandomState(4).randn(500), [0.0] * 400])
    m = make_mapper(vals, zero_as_missing=True)
    assert m.missing_type == MISSING_ZERO


def test_zero_bin_is_default():
    # sparse feature: zeros dominate, default bin holds them
    vals = np.concatenate([np.random.RandomState(5).rand(100) + 1.0,
                           np.zeros(900)])
    m = make_mapper(vals)
    assert m.value_to_bin(0.0) == m.default_bin
    assert m.most_freq_bin == m.default_bin


def test_trivial_constant_feature():
    m = make_mapper(np.ones(100) * 5.0)
    assert not m.is_trivial  # one distinct nonzero value + implicit zero
    m2 = make_mapper(np.zeros(100))
    assert m2.is_trivial


def test_categorical_count_sorted():
    rng = np.random.RandomState(6)
    vals = rng.choice([3, 7, 11], size=1000, p=[0.6, 0.3, 0.1])
    m = make_mapper(vals.astype(float), bin_type=BIN_CATEGORICAL,
                    min_data_in_bin=1)
    # most frequent category gets bin 1 (bin 0 reserved for NaN/other)
    assert m.bin_2_categorical[1] == 3
    assert m.value_to_bin(3.0) == 1
    assert m.value_to_bin(7.0) == 2


def test_serialization_roundtrip():
    vals = np.random.RandomState(7).randn(1000)
    m = make_mapper(vals, max_bin=31)
    m2 = BinMapper.from_dict(m.to_dict())
    x = np.random.RandomState(8).randn(100)
    assert (m.value_to_bin(x) == m2.value_to_bin(x)).all()
    assert m2.num_bin == m.num_bin


def test_min_data_in_bin_respected():
    # with min_data_in_bin=50 over 200 samples, at most 4 numeric bins
    vals = np.random.RandomState(9).rand(200) + 1.0
    m = make_mapper(vals, max_bin=255, min_data_in_bin=50)
    bins = m.value_to_bin(vals)
    counts = np.bincount(bins, minlength=m.num_bin)
    # every non-empty interior bin holds >= min_data_in_bin
    nonzero = counts[counts > 0]
    assert (nonzero >= 40).all()  # greedy packing allows slight undershoot


def test_max_bin_by_feature():
    """(ref: config.h max_bin_by_feature)"""
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import TpuDataset
    rng = np.random.RandomState(0)
    X = rng.rand(2000, 3)
    cfg = Config({"max_bin": 255, "max_bin_by_feature": [8, 255, 16],
                  "verbose": -1})
    ds = TpuDataset.from_data(X, cfg)
    assert ds.mappers[0].num_bin <= 8
    assert ds.mappers[1].num_bin > 100
    assert ds.mappers[2].num_bin <= 16


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_threaded_row_binning_gives_the_same_bytes(monkeypatch, dtype):
    """``TpuDataset.bin_rows`` shares the columns and the row blocks of
    the two transposes among a few threads; the packed matrix is the one a
    single thread gives, NaNs and a categorical column included."""
    import lightgbm_tpu.dataset as dataset
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(5)
    n = 3 * 4096 + 17
    X = rng.randn(n, 9).astype(dtype)
    X[rng.rand(n, 9) < 0.03] = np.nan
    X[:, 4] = rng.randint(0, 12, n)
    cfg = Config({"max_bin": 63, "verbose": -1,
                  "categorical_feature": [4]})
    monkeypatch.setattr(dataset, "_BIN_BLOCK_ROWS", 4096)
    threaded = dataset.TpuDataset.from_data(X, cfg, categorical_feature=[4])
    monkeypatch.setattr(dataset, "_BIN_THREADS", 1)
    serial = dataset.TpuDataset.from_data(X, cfg, categorical_feature=[4])
    assert threaded.bins.dtype == serial.bins.dtype == np.uint8
    assert threaded.bins.flags.c_contiguous
    np.testing.assert_array_equal(threaded.bins, serial.bins)
    assert threaded.bins.shape == (n, 9)
