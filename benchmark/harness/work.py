"""What the algorithm has to compute and move, from shapes alone, and the
chip's peaks: the two halves of a roofline share. Kept with the benchmark
so that no change to the program can move the yardstick.

The model of the level kernels is ROADMAP A1's, written down once: the
masked one-hot formulation multiplies every row's [features x padded
bins] one-hot into every channel of every live slot, and a tree's live
slots sum to its leaves (one root histogram, then one smaller-child
histogram per split; the sibling comes by subtraction).
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

# accumulators per histogram bin in the model: gradient and hessian, each
# as a bf16 high and low part so that the MXU's sums are float32-exact,
# and the row count
CHANNELS = 5


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. An unknown device is an
    error: a roofline share against a guessed peak means nothing."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/harness/peaks.json ({sorted(table)}); "
                       "add a row with its source")
    return table[device_kind]


def padded_bins(max_bin: int) -> int:
    """Bins per feature in the flat one-hot axis: the power of two at or
    above max_bin, at least 8 (63 -> 64, 255 -> 256)."""
    return max(8, 1 << (int(max_bin) - 1).bit_length())


def onehot_ops(rows: int, features: int, max_bin: int,
               leaves: int) -> float:
    """Multiply-adds x 2 the one-hot formulation needs for ONE tree with
    ``leaves`` leaves over ``rows`` rows (per chip: pass that chip's
    rows)."""
    return 2.0 * rows * features * padded_bins(max_bin) * CHANNELS * leaves


def level_bytes(rows: int, features: int, levels: int) -> float:
    """HBM bytes ONE tree's level passes must move: each of ``levels``
    passes streams the int8 bin matrix, the bf16 channels and the int32
    leaf ids in and the new leaf ids out."""
    return float(levels) * rows * (features + 2 * CHANNELS + 4 + 4)


def roofline_seconds(ops: float, nbytes: float, peak: dict,
                     flops_key: str = "bf16_flops_per_s"):
    """(least seconds the chip could take, which bound sets it)."""
    t_ops = ops / peak[flops_key]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
