"""Learning-to-rank objectives: LambdaRank (NDCG) and XE-NDCG.

Reference: src/objective/rank_objective.hpp.  The reference iterates queries
with OpenMP and pairs with nested loops + a precomputed sigmoid table.  Here
the work follows the pairs the reference visits, not the longest query
(ISSUE 29; the earlier form padded every query to the longest and computed a
dense ``[G, G]`` pair matrix per query under ``lax.map``):

* queries are grouped by length into a few BUCKETS of fixed width (powers of
  two from ``_MIN_BUCKET`` up to the longest query), each bucket one batched
  computation over ``[queries, width]``; all buckets sit in the one gradient
  program ``models/gbdt.py`` jits;
* a query's rows are adjacent rows of the row axis, so a bucket reads its
  scores as one window per query - rows ``start + [0, width)``, the tail
  masked by the query's length - by a gather of the bucket's padded slots
  (all buckets together: under twice the rows, 1.43 n at the MS LTR
  lengths), and the per-row results of all buckets leave through ONE fixed
  row-sized gather (``n`` indexes, built once by ``init``).  No ``[queries,
  longest]`` index gather (10 n there) and no scatter-add.  (A gather of
  ``queries`` start rows with a slice of ``width`` reads the same windows
  with fewer indexes and ran 7.6 ms faster of 39 on the chip, but XLA makes
  it a loop of one step a query - 170,000 steps a pass - and a profiler
  slice over it took 18-22 s to stop, against 10.7 s; PERF.md, PR 29);
* inside a bucket LambdaRank sorts each query by score (stable, descending,
  ties in row order: ``std::stable_sort`` in the header), carrying labels
  and gains through the sort, and computes the pairs ``[min(trunc, width),
  width]``: the top ``lambdarank_truncation_level`` ranks against every
  lower rank, each unordered pair once - the header's loops, and the same
  sums as the mask ``min(rank_i, rank_j) < trunc`` of the dense form.  A
  second sort by the carried row position takes the sums back to row order;
* ``init`` is numpy without a loop over queries (padding, inverse max-DCG).

Semantics kept: label gains ``2^l - 1``, position discount ``1/log2(2+rank)``,
pair truncation at ``lambdarank_truncation_level``, delta-NDCG normalisation
by max-DCG@trunc, score-distance regularisation and the log2(1+sum) lambda
renormalisation under ``lambdarank_norm``; the sigmoid is exact (no table).
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log
from .base import ObjectiveFunction

_MIN_BUCKET = 8


class _Bucket(NamedTuple):
    width: int                 # padded query length of this bucket
    start: jnp.ndarray         # [Qb] i32 first row of each query
    length: jnp.ndarray        # [Qb] i32 rows of each query
    query: np.ndarray          # [Qb] query ids (host; init-time only)


def bucket_widths(longest: int) -> List[int]:
    """Powers of two from ``_MIN_BUCKET`` up to the first one that holds
    the longest query."""
    out = [_MIN_BUCKET]
    while out[-1] < longest:
        out.append(out[-1] * 2)
    return out


class RankingObjective(ObjectiveFunction):
    """Bucketing, the windows in and the row gather out; a subclass
    supplies ``_bucket_gradients``."""

    def _pair_rows(self, length):
        """Rows of the block a query of this length (or a bucket of this
        width) computes against its documents: one for a listwise
        objective, the truncation level's ranks for a pairwise one."""
        return np.minimum(1, length)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        qb = np.asarray(metadata.query_boundaries, dtype=np.int64)
        self._qb = qb
        sizes = np.diff(qb)
        self.num_queries = len(sizes)
        widths = np.asarray(bucket_widths(int(sizes.max())))
        which = np.searchsorted(widths, sizes)        # bucket of a query
        self._buckets: List[_Bucket] = []
        slot0 = np.zeros(self.num_queries, np.int64)  # query's first slot
        base = 0
        for b, width in enumerate(widths):
            q = np.flatnonzero(which == b)
            if not len(q):
                continue
            self._buckets.append(_Bucket(
                int(width), jnp.asarray(qb[q], jnp.int32),
                jnp.asarray(sizes[q], jnp.int32), q))
            slot0[q] = base + np.arange(len(q)) * int(width)
            base += len(q) * int(width)
        # the padded slot of every row: the one gather out
        qid = np.repeat(np.arange(self.num_queries), sizes)
        self._slot_of_row = jnp.asarray(
            slot0[qid] + np.arange(num_data) - qb[qid], jnp.int32)
        self._counters = {
            "queries": int(self.num_queries),
            "buckets": len(self._buckets),
            "pairs_visited": int(np.sum(self._pair_rows(sizes) * sizes)),
            "pair_slots": int(sum(
                len(b.query) * self._pair_rows(b.width) * b.width
                for b in self._buckets)),
        }

    def span_args(self) -> Dict[str, int]:
        """The args of the ``Boosting`` span, ``init``'s own arithmetic:
        ``queries``, ``buckets``, ``pairs_visited`` (sum over queries of
        ``min(trunc, G) * G``: what the reference visits) and
        ``pair_slots`` (what the bucketed program computes, padding
        included)."""
        return dict(self._counters)

    # ---- rows <-> buckets ---------------------------------------------
    def _windows(self, per_row, bucket: _Bucket, fill):
        """[Qb, width] windows of a per-row vector, one a query; slots
        past a query's length read ``fill``."""
        at = (bucket.start[:, None]
              + jnp.arange(bucket.width, dtype=jnp.int32)[None, :])
        return jnp.where(self._valid(bucket),
                         jnp.take(per_row, at, mode="clip"), fill)

    @staticmethod
    def _valid(bucket: _Bucket):
        return (jnp.arange(bucket.width, dtype=jnp.int32)[None, :]
                < bucket.length[:, None])

    def _host_windows(self, per_row: np.ndarray, bucket: _Bucket, fill):
        """``_windows`` in numpy, for the constants ``init`` builds."""
        pos = np.arange(bucket.width)[None, :]
        start = self._qb[bucket.query][:, None]
        valid = pos < np.diff(self._qb)[bucket.query][:, None]
        idx = np.minimum(start + pos, len(per_row) - 1)
        return np.where(valid, per_row[idx], fill)

    def _to_rows(self, lam_b, hes_b):
        """Per-bucket [Qb, width] results -> per-row (grad, hess)."""
        slots = jnp.stack(
            [jnp.concatenate([x.reshape(-1) for x in lam_b]),
             jnp.concatenate([x.reshape(-1) for x in hes_b])], axis=1)
        out = jnp.take(slots, self._slot_of_row, axis=0)
        return self._apply_weight(out[:, 0], out[:, 1])

    def get_gradients(self, score):
        extra = self._per_call()
        lam_b, hes_b = [], []
        for i, bucket in enumerate(self._buckets):
            lam, hes = self._bucket_gradients(
                i, bucket, self._windows(score, bucket, -jnp.inf), extra)
            lam_b.append(lam)
            hes_b.append(hes)
        return self._to_rows(lam_b, hes_b)

    def _per_call(self):
        return None

    def _bucket_gradients(self, i, bucket, score_q, extra):
        raise NotImplementedError


class LambdarankNDCG(RankingObjective):
    NAME = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        self.norm = config.lambdarank_norm
        self.trunc = config.lambdarank_truncation_level

    def _pair_rows(self, length):
        return np.minimum(int(self.trunc), length)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = np.asarray(metadata.label)
        max_label = int(label.max())
        gains = self.config.label_gain
        if not gains:
            gains = [float((1 << i) - 1) for i in range(max(max_label + 1, 2))]
        if max_label >= len(gains):
            log.fatal("Label %d exceeds label_gain size %d", max_label, len(gains))
        gains = np.asarray(gains, dtype=np.float64)
        gain = gains[label.astype(np.int64)]
        # inverse max DCG at the truncation level, every query at once:
        # labels descending within their query, discounted by position
        sizes = np.diff(self._qb)
        qid = np.repeat(np.arange(self.num_queries), sizes)
        order = np.lexsort((-label, qid))
        pos = np.arange(num_data) - self._qb[qid]
        dcg = np.bincount(
            qid, weights=np.where(pos < self.trunc,
                                  gain[order] / np.log2(pos + 2.0), 0.0),
            minlength=self.num_queries)
        inv = np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)
        self._inv_max_dcg = [jnp.asarray(inv[b.query], jnp.float32)
                             for b in self._buckets]
        self._label_q = [jnp.asarray(self._host_windows(label, b, -1.0),
                                     jnp.float32) for b in self._buckets]
        self._gain_q = [jnp.asarray(self._host_windows(gain, b, 0.0),
                                    jnp.float32) for b in self._buckets]
        # |discount(i) - discount(j)| of the pair block, from float64:
        # neighbouring ranks' discounts agree to three digits, and a
        # float32 log2 on the device is good to ~1e-6, which left 2e-4
        # of error in a query's lambdas (my chip run, PR 29)
        disc = 1.0 / np.log2(2.0 + np.arange(self._buckets[-1].width))
        self._pair_disc = [
            jnp.asarray(disc[:int(self._pair_rows(b.width)), None]
                        - disc[None, :b.width], jnp.float32)
            for b in self._buckets]

    def _bucket_gradients(self, i, bucket, score_q, extra):
        width, t = bucket.width, int(self._pair_rows(bucket.width))
        pos = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32),
                               score_q.shape)
        # rank order: stable, descending, ties in row order; the padding
        # (-inf) sorts last.  Labels, gains and row positions ride along.
        neg_s, lab, gain, at = jax.lax.sort(
            (-score_q, self._label_q[i], self._gain_q[i], pos),
            dimension=1, is_stable=True, num_keys=1)
        s = -neg_s
        valid = self._valid(bucket)                  # by rank, too
        best = s[:, 0]
        worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
        # the top t ranks (i) against every lower rank (j)
        ri = jnp.arange(t, dtype=jnp.int32)[None, :, None]
        rj = jnp.arange(width, dtype=jnp.int32)[None, None, :]
        s_i, s_j = s[:, :t, None], s[:, None, :]
        lab_i, lab_j = lab[:, :t, None], lab[:, None, :]
        pair = ((rj > ri) & valid[:, None, :] & (lab_i != lab_j))
        i_high = lab_i > lab_j
        dist = jnp.where(pair, s_i - s_j, 0.0)       # >= 0: i ranks higher
        ds = jnp.where(i_high, dist, -dist)          # high label - low label
        gap = gain[:, :t, None] - gain[:, None, :]
        delta = (jnp.where(i_high, gap, -gap) * self._pair_disc[i][None]
                 * self._inv_max_dcg[i][:, None, None])
        if self.norm:
            delta = jnp.where((best != worst)[:, None, None],
                              delta / (0.01 + dist), delta)
        sig = 1.0 / (1.0 + jnp.exp(self.sigmoid * ds))
        p_lambda = jnp.where(pair, -self.sigmoid * delta * sig, 0.0)
        p_hess = jnp.where(
            pair, self.sigmoid * self.sigmoid * delta * sig * (1.0 - sig),
            0.0)
        to_i = jnp.where(i_high, p_lambda, -p_lambda)  # the low side: minus
        rest = ((0, 0), (0, width - t))
        lam = jnp.pad(to_i.sum(axis=2), rest) - to_i.sum(axis=1)
        hes = jnp.pad(p_hess.sum(axis=2), rest) + p_hess.sum(axis=1)
        if self.norm:
            sum_lambdas = -2.0 * p_lambda.sum(axis=(1, 2))
            factor = jnp.where(
                sum_lambdas > 0,
                jnp.log2(1.0 + sum_lambdas) / jnp.maximum(sum_lambdas, 1e-20),
                1.0)[:, None]
            lam, hes = lam * factor, hes * factor
        # back to row order
        _, lam, hes = jax.lax.sort((at, lam, hes), dimension=1, num_keys=1)
        return lam, hes


class RankXENDCG(RankingObjective):
    NAME = "rank_xendcg"
    # per-iteration Gumbel noise: the PRNG key depends on Python-side
    # _iteration state, so the gradient pass must NOT be traced once and
    # cached (a cached jit would freeze iteration 0's key forever)
    STATEFUL_GRADIENTS = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        label = np.asarray(metadata.label, np.float32)
        self._label_q = [jnp.asarray(self._host_windows(label, b, 0.0))
                         for b in self._buckets]
        self._iteration = 0

    def _per_call(self):
        """This iteration's uniform draw, one number a row."""
        key = jax.random.PRNGKey(self.config.objective_seed + self._iteration)
        self._iteration += 1
        return jax.random.uniform(key, (self.num_data,))

    def _bucket_gradients(self, i, bucket, score_q, gumbel_row):
        valid = self._valid(bucket)
        gumbel_u = self._windows(gumbel_row, bucket, 0.0)
        rho = jax.nn.softmax(score_q, axis=1, where=valid)
        rho = jnp.where(valid, rho, 0.0)
        phi = jnp.where(valid, jnp.exp2(self._label_q[i]) - gumbel_u, 0.0)
        inv_den = 1.0 / jnp.maximum(phi.sum(axis=1, keepdims=True), 1e-15)
        # third-order XE-NDCG gradient approximation (rank_objective.hpp:330)
        one_m_rho = jnp.maximum(1.0 - rho, 1e-15)
        t1 = -phi * inv_den + rho
        params = jnp.where(valid, t1 / one_m_rho, 0.0)
        sum_l1 = params.sum(axis=1, keepdims=True)
        t2 = rho * (sum_l1 - params)
        params2 = jnp.where(valid, t2 / one_m_rho, 0.0)
        sum_l2 = params2.sum(axis=1, keepdims=True)
        lam = t1 + t2 + rho * (sum_l2 - params2)
        hes = rho * (1.0 - rho)
        # groups with <= 1 docs get zero gradients
        live = (bucket.length[:, None] > 1) & valid
        return jnp.where(live, lam, 0.0), jnp.where(live, hes, 0.0)
