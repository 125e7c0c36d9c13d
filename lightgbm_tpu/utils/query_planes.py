"""Query-grouped rows as a few dense planes: the layout the ranking
objectives and the traced NDCG share.

Queries are grouped into LENGTH BUCKETS (power-of-two widths from
``MIN_WIDTH`` lanes up); a bucket is a ``[queries_b, width_b]`` plane with
one query per row, its documents in their original order in the first
lanes. Sorting, ranking and pairing then run along the short axis of each
plane, work and memory follow the rows, and nothing is padded to the
longest query.

A query's documents are contiguous in the flat per-row vector, so a
plane's row is one window of it. The window is moved as WHOLE ROWS: the two
width-aligned tiles it spans are gathered and turned left by the window's
offset; back, the plane's row is turned right and added onto the same two
tiles. (On the v5e an element-by-element gather costs 7.3 ns an element,
80 ms for the 11M slots of 6.8M rows; a row gather costs 3.9 ns a row, and
the planes of those rows are filled in 3.0 ms: PERF.md section 6, PR 27.)

A plane's row count is the bucket's CAPACITY: its query count rounded up to
a power of two (at least ``MIN_QUERIES``), the rest FILLER queries of no
documents, which every lane mask (``counts``) already hides. The shapes a
job compiles therefore follow the size of the problem and not the exact
count of queries of each length: a refreshed dataset, or another draw of
the same distribution, runs the program that is already compiled. The
planes' work is a few thousandths of a ranking iteration, so up to twice
the rows of it costs nothing that shows.

Everything a traced function needs is handed to it as jit OPERANDS
(``operands()``), all O(queries) but the multi-process row map.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

# narrowest bucket: one vector register's lanes. A narrower plane is padded
# to 128 lanes in memory and in registers anyway, so it would save nothing
MIN_WIDTH = 128
# fewest rows of a plane. A small count varies most from one draw of a
# dataset to the next (by its square root), and a bucket of a dozen long
# queries would cross a power of two at every other refresh
MIN_QUERIES = 32


def _capacity(queries: int) -> int:
    """Power of two >= ``queries``, at least MIN_QUERIES."""
    return max(MIN_QUERIES, 1 << (int(queries) - 1).bit_length())


def _bucket_width(sizes: np.ndarray) -> np.ndarray:
    """Power-of-two width >= n, at least MIN_WIDTH."""
    w = np.full(sizes.shape, MIN_WIDTH, np.int64)
    while (w < sizes).any():
        w = np.where(w < sizes, w * 2, w)
    return w


def _tiles(vec: jnp.ndarray, width: int) -> jnp.ndarray:
    """``vec`` as ``[tiles, width]`` rows, zero-padded so that a window of
    ``width`` starting anywhere in it spans two whole rows."""
    n = vec.shape[0]
    tiles = -(-n // width) + 1
    return jnp.pad(vec, (0, tiles * width - n)).reshape(tiles, width)


def _turn_lanes(x: jnp.ndarray, by: jnp.ndarray, sign: int) -> jnp.ndarray:
    """Each row of ``x`` rolled along its lanes by its own ``by`` (left
    for ``sign`` -1, right for +1): one static roll per bit of ``by``."""
    for bit in range(int(x.shape[1] // 2 - 1).bit_length()):
        x = jnp.where(((by >> bit) & 1)[:, None] == 1,
                      jnp.roll(x, sign * (1 << bit), axis=1), x)
    return x


class QueryPlanes:
    """The static (data-dependent, score-independent) bucket table of one
    set of query boundaries, and the ways in and out of its planes.
    Queries of fewer than ``min_docs`` documents are in no bucket."""

    def __init__(self, query_boundaries, row_map=None, min_docs: int = 1):
        qb = np.asarray(query_boundaries, np.int64)
        self._qb = qb
        self.sizes = sizes = np.diff(qb)
        self.num_queries = int(sizes.size)
        self.max_docs = int(sizes.max()) if sizes.size else 0
        self.num_rows = int(qb[-1])             # compacted rows
        # Multi-process: boundaries are over COMPACTED real rows and
        # row_map carries each one's PADDED global row index
        self.row_map = (None if row_map is None
                        else jnp.asarray(np.asarray(row_map, np.int32)))
        live = sizes >= max(1, int(min_docs))
        width = _bucket_width(sizes)
        self.buckets = [(int(w), np.flatnonzero(live & (width == w)))
                        for w in sorted(set(width[live].tolist()))]
        self.widths = tuple(w for w, _ in self.buckets)
        self.queries = tuple(int(qs.size) for _, qs in self.buckets)
        self.capacity = tuple(_capacity(q) for q in self.queries)
        self.padded_rows = sum(w * c for w, c in zip(self.widths,
                                                     self.capacity))
        self.rows = int(sizes[live].sum())      # documents in the planes
        # a filler starts where the bucket's last query does (the starts
        # stay sorted) and holds no document
        self._starts = tuple(
            jnp.asarray(self._filled(qb[qs], c, qb[qs[-1]]).astype(np.int32))
            for (_, qs), c in zip(self.buckets, self.capacity))
        self._counts = tuple(jnp.asarray(x.astype(np.int32))
                             for x in self.of_queries(sizes))

    @staticmethod
    def _filled(real: np.ndarray, capacity: int, fill=0) -> np.ndarray:
        """``real`` (queries first) with filler rows up to ``capacity``."""
        pad = [(0, capacity - real.shape[0])] + [(0, 0)] * (real.ndim - 1)
        return np.pad(real, pad, constant_values=fill)

    def pairs(self, truncation: int) -> int:
        """|{(i, j): i < j, i < truncation}| summed over the queries."""
        n = self.sizes
        m = np.minimum(n, int(truncation))
        return int(np.sum(m * n - m * (m + 1) // 2))

    def of_queries(self, per_query: np.ndarray) -> List[np.ndarray]:
        """A per-query host array, one slice per bucket (fillers zero)."""
        return [self._filled(np.asarray(per_query)[qs], c)
                for (_, qs), c in zip(self.buckets, self.capacity)]

    def pad_host(self, compact: np.ndarray, fill=0) -> List[np.ndarray]:
        """A compacted per-row host array in every bucket's plane."""
        out = []
        for (w, qs), c in zip(self.buckets, self.capacity):
            lane = np.arange(w, dtype=np.int64)[None, :]
            idx = np.minimum(self._qb[qs][:, None] + lane, len(compact) - 1)
            out.append(self._filled(np.where(lane < self.sizes[qs][:, None],
                                             compact[idx], fill), c, fill))
        return out

    # -- traced ----------------------------------------------------------
    def operands(self) -> tuple:
        """(starts, counts, row_map) for ``to_planes`` / ``to_rows``;
        ``counts`` is also what masks a plane's lanes."""
        return (self._starts, self._counts, self.row_map)

    def to_planes(self, vec: jnp.ndarray, operands) -> List[jnp.ndarray]:
        """Flat per-row ``vec`` as one ``[capacity_b, width_b]`` plane per
        bucket. Lanes past a query's length hold whatever follows it in
        the vector and are masked by the caller."""
        starts, _, row_map = operands
        if row_map is not None:
            vec = vec[row_map]
        planes = []
        for w, start in zip(self.widths, starts):
            tiles = _tiles(vec, w)
            at = start // w
            two = jnp.concatenate([tiles[at], tiles[at + 1]], axis=1)
            planes.append(_turn_lanes(two, start % w, -1)[:, :w])
        return planes

    def to_rows(self, planes: List[jnp.ndarray], n_out: int,
                operands) -> jnp.ndarray:
        """Per-bucket float32 planes (the plane's own lane order, ZERO
        past each query's length) back as one flat ``[n_out]`` row
        vector. An element gets one non-zero term, so the sum is exact;
        rows of unbucketed queries read zero."""
        starts, _, row_map = operands
        rows = jnp.zeros((self.num_rows,), jnp.float32)
        for plane, start in zip(planes, starts):
            w = plane.shape[1]
            wide = _turn_lanes(jnp.pad(plane, ((0, 0), (0, w))), start % w, 1)
            at = start // w
            tiles = jnp.zeros_like(_tiles(rows, w)) \
                .at[at].add(wide[:, :w], indices_are_sorted=True) \
                .at[at + 1].add(wide[:, w:], indices_are_sorted=True)
            rows = rows + tiles.reshape(-1)[:self.num_rows]
        if row_map is not None:
            rows = jnp.zeros((n_out,), jnp.float32).at[row_map].set(rows)
        return rows
