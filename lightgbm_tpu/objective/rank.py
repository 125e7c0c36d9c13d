"""Learning-to-rank objectives.

TPU-native analog of ref: src/objective/rank_objective.hpp (LambdarankNDCG,
RankXENDCG).  The reference iterates pairs per query on the host with OpenMP.
Here the queries are grouped into a few LENGTH BUCKETS (power-of-two widths
from 128 lanes up, ``utils/query_planes.py``): a bucket is a ``[queries_b,
width_b]`` plane, filled from the flat score vector by whole-row window
gathers, sorted along its short axis, and the pairs of a query are formed
against its own top
``lambdarank_truncation_level`` documents only, one truncation position at a
time (``lax.scan`` over i, a ``[queries_b, width_b]`` plane per step).  Work
and memory follow sum_q min(T, n_q) * n_q and the rows; nothing is padded to
the longest query and no ``[Q, D, D]`` plane exists.  The reference's sigmoid
lookup table (a CPU speed hack, rank_objective.hpp:240) is replaced by the
exact sigmoid.

Everything O(rows) or O(queries) the gradient reads is a jit OPERAND
(``gradient_operands``), so the same traced ``gradients_from`` serves the
eager entry point, the pipelined fast step and the megastep scan.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import dcg, log
from ..utils.query_planes import QueryPlanes
from .base import K_EPSILON, ObjectiveFunction


class RankingObjective(ObjectiveFunction):
    """Shared query handling (ref: rank_objective.hpp:25-93): the queries'
    length buckets (``utils/query_planes.py``) and the row weights."""

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        self.query_boundaries = metadata.query_boundaries
        # Multi-process: boundaries are over COMPACTED real rows;
        # query_row_map carries each compacted row's PADDED global row
        # index (rank blocks leave gaps — parallel/multiproc
        # .GlobalMetadata), so the planes read and the result lands on
        # the true score rows.
        row_map = getattr(metadata, "query_row_map", None)
        # a query of one document has no pair and no listwise gradient:
        # it is in no bucket and its rows read zero
        self.planes = QueryPlanes(
            self.query_boundaries, row_map, min_docs=2)
        self.num_queries = self.planes.num_queries
        self.max_docs = self.planes.max_docs
        label = np.asarray(self.label)
        self._label_compact = label if row_map is None \
            else label[np.asarray(row_map)]
        self._weight_j = (jnp.asarray(self.weight)
                          if self.weight is not None else None)

    def to_string(self):
        return self.name

    @property
    def need_accurate_prediction(self):
        return False


class LambdarankNDCG(RankingObjective):
    """Pairwise lambdas weighted by |ΔNDCG|
    (ref: rank_objective.hpp:96-277)."""

    name = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            log.fatal("Sigmoid param %f should be greater than zero",
                      self.sigmoid)
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        self.label_gain = dcg.default_label_gain(config.label_gain)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        dcg.check_label(self._label_compact, len(self.label_gain))
        qb = np.asarray(self.query_boundaries, np.int64)
        # inverse max DCG per query (ref: rank_objective.hpp:124-135)
        max_dcg = dcg.max_dcg_table([self.truncation_level],
                                    self._label_compact, qb,
                                    self.label_gain)[:, 0]
        inv = np.where(max_dcg > 0, 1.0 / np.where(max_dcg > 0, max_dcg, 1.0),
                       0.0)
        self._inv_max_dcg = [jnp.asarray(x.astype(np.float32))
                             for x in self.planes.of_queries(inv)]
        labels = self._label_compact.astype(np.int64)
        self._num_labels = int(labels.max()) + 1 if labels.size else 1
        self._labels_pad = [jnp.asarray(p.astype(np.int8))
                            for p in self.planes.pad_host(labels)]
        self.pairs_per_iter = self.planes.pairs(self.truncation_level)

    # -- in-jit gradient protocol -----------------------------------------
    def _operands(self):
        return (self.planes.operands(), tuple(self._labels_pad),
                tuple(self._inv_max_dcg), self._weight_j)

    def gradient_operands(self):
        # multi-process: the compacted layout's row map is rank-global
        # host state of the sync driver; that path keeps its eviction
        if self.planes.row_map is not None:
            return None
        return self._operands()

    def get_gradients(self, score):
        return self.gradients_from(score, self._operands())

    def _bucket_lambdas(self, s_pad, labels, count, inv_max_dcg):
        """Lambdas and hessians of one bucket, ``[queries_b, width_b]``
        in the plane's own (unsorted) lane order
        (ref: rank_objective.hpp:139-230 GetGradientsForOneQuery)."""
        Qb, D = s_pad.shape
        T = min(self.truncation_level, D)
        sig = jnp.float32(self.sigmoid)
        neg_inf = jnp.float32(-jnp.inf)
        lane = jnp.arange(D, dtype=jnp.int32)[None, :]
        inside = lane < count[:, None]
        # label and lane ride the sort as ONE integer passenger (a second
        # gather per plane costs more than the sort does), and as its
        # second KEY: lanes are distinct, so the order is the stable one
        # (ties in original row order) without the iota operand a stable
        # sort adds, which the TPU compiler takes a third longer over
        shift = max(1, int(self._num_labels - 1).bit_length())
        with jax.named_scope("rank_sort"):
            key = jnp.where(inside, -s_pad, jnp.float32(jnp.inf))
            passenger = (lane << shift) | labels.astype(jnp.int32)
            key, passenger = jax.lax.sort((key, passenger), dimension=1,
                                          num_keys=2, is_stable=False)
        with jax.named_scope("rank_pairs"):
            # padded lanes are keyed +inf and sit in the highest lanes:
            # slot j < count holds a document, -inf scores last among them
            S = jnp.where(inside, -key, neg_inf)
            lab = passenger & ((1 << shift) - 1)
            perm = passenger >> shift
            gain = jnp.zeros_like(S)
            for k in range(1, self._num_labels):
                gain = jnp.where(lab == k, jnp.float32(self.label_gain[k]),
                                 gain)
            if self.label_gain[0] != 0.0:
                gain = jnp.where(lab == 0, jnp.float32(self.label_gain[0]),
                                 gain)
            ok = inside & (S > neg_inf)
            disc = jnp.asarray(dcg.discounts(D).astype(np.float32))[None, :]
            # best/worst scores (ref: :158-166 — worst skips one kMinScore)
            best = S[:, 0]
            last = jnp.max(jnp.where(lane == count[:, None] - 1, S, neg_inf),
                           axis=1)
            prev = jnp.max(jnp.where(lane == count[:, None] - 2, S, neg_inf),
                           axis=1)
            worst = jnp.where((count > 1) & (last == neg_inf), prev, last)
            scale_by_gap = (best != worst)[:, None]
            inv = inv_max_dcg[:, None]

            def one_position(carry, t):
                lam_j, hes_j = carry
                pick = lambda a: jax.lax.dynamic_slice_in_dim(a, t, 1, 1)
                s_i, lab_i, g_i, ok_i = pick(S), pick(lab), pick(gain), \
                    pick(ok)
                pair = (lane > t) & ok_i & ok & (lab != lab_i)
                i_high = lab_i > lab
                ds = jnp.where(i_high, s_i - S, S - s_i)
                delta = jnp.abs(g_i - gain) \
                    * jnp.abs(pick(disc) - disc) * inv
                if self.norm:
                    delta = jnp.where(scale_by_gap,
                                      delta / (0.01 + jnp.abs(ds)), delta)
                rho = 1.0 / (1.0 + jnp.exp(sig * ds))     # GetSigmoid(ds)
                lam = jnp.where(pair, sig * delta * rho, 0.0)
                hes = jnp.where(pair, sig * sig * delta * rho * (1.0 - rho),
                                0.0)
                # the higher label is pushed up (-), the lower down (+)
                to_i = jnp.where(i_high, -lam, lam)
                return (lam_j - to_i, hes_j + hes), \
                    (jnp.sum(to_i, axis=1), jnp.sum(hes, axis=1),
                     jnp.sum(lam, axis=1))

            zeros = jnp.zeros_like(S)
            (lam_j, hes_j), (lam_i, hes_i, lam_abs) = jax.lax.scan(
                one_position, (zeros, zeros), jnp.arange(T, dtype=jnp.int32))
            top = ((0, 0), (0, D - T))
            lam_s = lam_j + jnp.pad(lam_i.T, top)
            hes_s = hes_j + jnp.pad(hes_i.T, top)
            if self.norm:
                sum_lambdas = 2.0 * jnp.sum(lam_abs, axis=0)
                factor = jnp.where(
                    sum_lambdas > 0,
                    jnp.log2(1.0 + sum_lambdas)
                    / jnp.maximum(sum_lambdas, K_EPSILON), 1.0)[:, None]
                lam_s, hes_s = lam_s * factor, hes_s * factor
        with jax.named_scope("rank_scatter"):
            # back to the plane's lane order: the sort's own permutation
            # is the key of a second sort
            _, lam_s, hes_s = jax.lax.sort((perm, lam_s, hes_s),
                                           dimension=1, num_keys=1)
        return lam_s, hes_s

    def gradients_from(self, score, operands):
        layout, labels, inv_max_dcg, weight = operands
        n_out = score.shape[-1]
        with jax.named_scope("rank_sort"):
            planes = self.planes.to_planes(score[0], layout)
        lams, hess = [], []
        for s_pad, lab, cnt, inv in zip(planes, labels, layout[1],
                                        inv_max_dcg):
            lam, hes = self._bucket_lambdas(s_pad, lab, cnt, inv)
            lams.append(lam)
            hess.append(hes)
        with jax.named_scope("rank_scatter"):
            g = self.planes.to_rows(lams, n_out, layout)
            h = self.planes.to_rows(hess, n_out, layout)
            if weight is not None:
                g, h = g * weight, h * weight
        return g[None, :], h[None, :]


class RankXENDCG(RankingObjective):
    """XE_NDCG listwise objective [arxiv.org/abs/1911.09798]
    (ref: rank_objective.hpp:284-363). Its per-iteration uniform draws
    come from host-held RNG state, so it offers no ``gradient_operands``
    and stays off the megastep (``objective_untraced_gradients``)."""

    name = "rank_xendcg"

    def __init__(self, config):
        super().__init__(config)
        self.seed = int(config.objective_seed)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._labels_pad = [
            jnp.asarray(p.astype(np.float32))
            for p in self.planes.pad_host(
                self._label_compact.astype(np.float64))]
        self._rng_key = jax.random.PRNGKey(self.seed)
        self._grad_fn = jax.jit(self._lambdas)

    @staticmethod
    def _bucket_lambdas(s, y, valid, gumbel_u):
        neg_inf = jnp.float32(-jnp.inf)
        sm = jnp.where(valid, s, neg_inf)
        # softmax over valid docs (ref: :315 Common::Softmax)
        rho = jax.nn.softmax(sm, axis=1)
        rho = jnp.where(valid, rho, 0.0)
        # Phi(l, u) = 2^l - u (ref: :355-357)
        params = jnp.where(valid, jnp.exp2(y) - gumbel_u, 0.0)
        inv_denom = 1.0 / jnp.maximum(
            K_EPSILON, jnp.sum(params, axis=1, keepdims=True))
        # first order (ref: :332-339)
        term1 = -params * inv_denom + rho
        lam = term1
        one_m_rho = jnp.maximum(1.0 - rho, K_EPSILON)
        params1 = jnp.where(valid, term1 / one_m_rho, 0.0)
        sum_l1 = jnp.sum(params1, axis=1, keepdims=True)
        # second order (ref: :341-348)
        term2 = rho * (sum_l1 - params1)
        lam = lam + term2
        params2 = jnp.where(valid, term2 / one_m_rho, 0.0)
        sum_l2 = jnp.sum(params2, axis=1, keepdims=True)
        # third order (ref: :349-352)
        lam = lam + rho * (sum_l2 - params2)
        hes = rho * (1.0 - rho)
        return jnp.where(valid, lam, 0.0), jnp.where(valid, hes, 0.0)

    def _lambdas(self, s, u, layout, labels):
        lams, hess = [], []
        for s_pad, u_pad, y, cnt in zip(self.planes.to_planes(s, layout),
                                        self.planes.to_planes(u, layout),
                                        labels, layout[1]):
            valid = jnp.arange(s_pad.shape[1])[None, :] < cnt[:, None]
            lam, hes = self._bucket_lambdas(s_pad, y, valid, u_pad)
            lams.append(lam)
            hess.append(hes)
        return (self.planes.to_rows(lams, s.shape[0], layout),
                self.planes.to_rows(hess, s.shape[0], layout))

    def get_gradients(self, score):
        s = score[0]
        self._rng_key, sub = jax.random.split(self._rng_key)
        u = jax.random.uniform(sub, s.shape)
        g, h = self._grad_fn(s, u, self.planes.operands(),
                             tuple(self._labels_pad))
        if self._weight_j is not None:
            g, h = g * self._weight_j, h * self._weight_j
        return g[None, :], h[None, :]
