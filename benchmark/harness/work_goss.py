"""What a row compaction has to move, from shapes alone: the yardstick of
``kernels.compact_roofline``, kept with the benchmark so that no change to
the program can move it.

Compacting the in-bag rows of a transposed bin matrix ``[features, rows]``
and of the packed gradient channels reads, for EVERY row, where it goes
(one int32) and its column of both, and writes the kept columns. It is
bound by memory: there is nothing to compute but the move.
"""
from __future__ import annotations

# what one row's column holds in the program's layout
CHANNEL_ROWS = 8        # the packed bf16 channel block is 8 sublanes tall
CHANNEL_BYTES = 2
DESTINATION_BYTES = 4


def padded_features(features: int) -> int:
    """Rows of the transposed bin matrix: the features rounded up to whole
    groups of 8 sublanes (28 -> 32)."""
    return -(-int(features) // 8) * 8


def compact_bytes(rows: int, kept: int, features: int,
                  bin_bytes: int = 1) -> float:
    """HBM bytes ONE compaction of ``rows`` rows into ``kept`` columns must
    move."""
    column = padded_features(features) * bin_bytes \
        + CHANNEL_ROWS * CHANNEL_BYTES
    return float(rows) * (DESTINATION_BYTES + column) + float(kept) * column
