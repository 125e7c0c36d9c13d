"""ops/scan.blocked_scan against numpy's running sum, max and min."""
import numpy as np
import pytest

import jax

from lightgbm_tpu.ops.scan import blocked_scan

_NP = {"sum": np.cumsum, "max": np.maximum.accumulate,
       "min": np.minimum.accumulate}


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("n,block", [(1, 8), (7, 8), (8, 8), (1000, 64),
                                     (5000, 2048)])
def test_blocked_scan_matches_numpy(op, reverse, dtype, n, block):
    rng = np.random.default_rng(n)
    x = (rng.integers(-50, 50, n) if dtype is np.int32
         else rng.standard_normal(n) * 10).astype(dtype)
    got = np.asarray(jax.jit(
        lambda v: blocked_scan(v, op, block, reverse=reverse))(x))
    want = _NP[op](x[::-1])[::-1] if reverse else _NP[op](x)
    assert got.dtype == dtype
    if dtype is np.int32:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-6 * np.abs(x).sum())
