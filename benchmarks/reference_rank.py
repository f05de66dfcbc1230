"""The plain references of the ranking cells: float64 numpy, independent
of the program (nothing here imports ``lightgbm_tpu``).

- ``lambdarank_query_loops``: LambdaRank lambdas and hessians of ONE
  query by explicit loops over its pairs, a transcription of
  ``LambdarankNDCG::GetGradientsForOneQuery`` (LightGBM
  ``src/objective/rank_objective.hpp``): stable sort by score
  descending, the outer loop over the top ``truncation_level`` ranks,
  the inner loop over every lower rank, label gains, position discounts,
  inverse max-DCG, both parts of ``lambdarank_norm``.
- ``lambdarank_query``: the same sums with the loops as one numpy pair
  block per query; ``benchmarks/tests`` holds it to the loops.  It is
  what makes a pass over 18,919 queries seconds.
- ``lambdarank``: either, over all queries of a row-grouped data set,
  times the row weights.
- ``ndcg_at``: mean NDCG@k over queries, LightGBM's ``NDCGMetric``.
- ``root_split_gh``: ``reference.root_split`` for given gradient and
  hessian arrays instead of a binary objective's closed form.

Departures from the header, each on purpose:

1. the sigmoid is ``1 / (1 + exp(sigmoid * x))`` evaluated exactly; the
   header reads it from a table of 1,048,576 entries over [-50, 50]
   (``GetSigmoid``), an approximation of the same function;
2. sums are float64 throughout; the header accumulates a query's
   lambdas in ``score_t`` (float32) and the pair terms in double;
3. documents whose score is ``kMinScore`` (-inf) are not special-cased:
   no caller here passes one;
4. ``label_gain`` defaults to ``2^l - 1`` for as many labels as the data
   holds (the header's table has 31 entries; the values agree);
5. position-bias debiasing (``lambdarank_position_bias_regularization``)
   is left out: the program has none.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from reference import RootSplit, TextTree, _leaf_gain


def default_label_gain(max_label: int) -> np.ndarray:
    return np.array([float((1 << i) - 1)
                     for i in range(max(max_label + 1, 2))], np.float64)


def discount(rank) -> np.ndarray:
    """``DCGCalculator::GetDiscount``: 1 / log2(2 + rank)."""
    return 1.0 / np.log2(2.0 + np.asarray(rank, np.float64))


def max_dcg_at(k: int, label: np.ndarray, label_gain: np.ndarray) -> float:
    """``DCGCalculator::CalMaxDCGAtK``: the labels in descending order."""
    top = np.sort(np.asarray(label).astype(np.int64))[::-1][:k]
    return float(np.sum(label_gain[top] * discount(np.arange(len(top)))))


def _stable_desc(score: np.ndarray) -> np.ndarray:
    """``std::stable_sort`` with ``score[a] > score[b]``: descending,
    ties in index order."""
    return np.argsort(-score, kind="stable")


def lambdarank_query_loops(score, label, label_gain, *, sigmoid=1.0,
                           truncation_level=30, norm=True):
    """(lambdas, hessians) of one query, float64, by the header's loops
    (Python floats are float64; plain lists keep the loops quick)."""
    cnt = len(score)
    sorted_idx = [int(v) for v in _stable_desc(np.asarray(score, np.float64))]
    # by rank, as the header reads them through sorted_idx
    s = [float(score[d]) for d in sorted_idx]
    lab = [int(label[d]) for d in sorted_idx]
    gain = [float(label_gain[v]) for v in lab]
    disc = [1.0 / math.log2(2.0 + r) for r in range(cnt)]
    lam = [0.0] * cnt
    hes = [0.0] * cnt
    max_dcg = max_dcg_at(truncation_level, np.asarray(lab),
                         np.asarray(label_gain, np.float64))
    inverse_max_dcg = 1.0 / max_dcg if max_dcg > 0 else 0.0
    best_score, worst_score = s[0], s[cnt - 1]
    sum_lambdas = 0.0
    for i in range(min(cnt - 1, truncation_level)):
        for j in range(i + 1, cnt):
            if lab[i] == lab[j]:
                continue
            high, low = (i, j) if lab[i] > lab[j] else (j, i)
            delta_score = s[high] - s[low]
            dcg_gap = gain[high] - gain[low]
            paired_discount = abs(disc[high] - disc[low])
            delta_pair_ndcg = dcg_gap * paired_discount * inverse_max_dcg
            if norm and best_score != worst_score:
                delta_pair_ndcg /= 0.01 + abs(delta_score)
            x = sigmoid * delta_score
            p_lambda = 1.0 / (1.0 + math.exp(x)) if x < 700.0 else 0.0
            p_hessian = p_lambda * (1.0 - p_lambda)
            p_lambda *= -sigmoid * delta_pair_ndcg
            p_hessian *= sigmoid * sigmoid * delta_pair_ndcg
            lam[low] -= p_lambda
            hes[low] += p_hessian
            lam[high] += p_lambda
            hes[high] += p_hessian
            sum_lambdas -= 2.0 * p_lambda
    lambdas = np.zeros(cnt, np.float64)
    hessians = np.zeros(cnt, np.float64)
    lambdas[sorted_idx] = lam
    hessians[sorted_idx] = hes
    if norm and sum_lambdas > 0:
        norm_factor = math.log2(1.0 + sum_lambdas) / sum_lambdas
        lambdas *= norm_factor
        hessians *= norm_factor
    return lambdas, hessians


def lambdarank_query(score, label, label_gain, *, sigmoid=1.0,
                     truncation_level=30, norm=True):
    """``lambdarank_query_loops`` with both loops as one numpy block:
    rows the top ``truncation_level`` ranks, columns every rank, the
    pairs those with column > row."""
    score = np.asarray(score, np.float64)
    cnt = len(score)
    lambdas = np.zeros(cnt, np.float64)
    hessians = np.zeros(cnt, np.float64)
    if cnt < 2:
        return lambdas, hessians
    max_dcg = max_dcg_at(truncation_level, label, label_gain)
    inverse_max_dcg = 1.0 / max_dcg if max_dcg > 0 else 0.0
    sorted_idx = _stable_desc(score)
    s = score[sorted_idx]
    lab = np.asarray(label)[sorted_idx].astype(np.int64)
    gain = np.asarray(label_gain, np.float64)[lab]
    disc = discount(np.arange(cnt))
    t = min(cnt - 1, truncation_level)
    ri, rj = np.arange(t)[:, None], np.arange(cnt)[None, :]
    pair = (rj > ri) & (lab[:t, None] != lab[None, :])
    i_high = lab[:t, None] > lab[None, :]
    dist = s[:t, None] - s[None, :]
    delta_score = np.where(i_high, dist, -dist)
    dcg_gap = gain[:t, None] - gain[None, :]
    delta = (np.where(i_high, dcg_gap, -dcg_gap)
             * np.abs(disc[:t, None] - disc[None, :]) * inverse_max_dcg)
    if norm and s[0] != s[-1]:
        delta = delta / (0.01 + np.abs(delta_score))
    with np.errstate(over="ignore"):
        sig = 1.0 / (1.0 + np.exp(sigmoid * delta_score))
    p_lambda = np.where(pair, -sigmoid * delta * sig, 0.0)
    p_hess = np.where(pair, sigmoid * sigmoid * delta * sig * (1.0 - sig),
                      0.0)
    to_i = np.where(i_high, p_lambda, -p_lambda)
    lam_r = -to_i.sum(axis=0)
    hes_r = p_hess.sum(axis=0)
    lam_r[:t] += to_i.sum(axis=1)
    hes_r[:t] += p_hess.sum(axis=1)
    sum_lambdas = -2.0 * p_lambda.sum()
    if norm and sum_lambdas > 0:
        norm_factor = np.log2(1.0 + sum_lambdas) / sum_lambdas
        lam_r *= norm_factor
        hes_r *= norm_factor
    lambdas[sorted_idx] = lam_r
    hessians[sorted_idx] = hes_r
    return lambdas, hessians


def lambdarank(score, label, query_boundaries, *, label_gain=None,
               weight=None, sigmoid=1.0, truncation_level=30, norm=True,
               queries: Optional[Sequence[int]] = None, loops=False):
    """(gradients, hessians) over the rows of a query-grouped data set
    (``RankingObjective::GetGradients``: every query, then the row
    weights).  ``queries`` restricts the pass; other rows read 0."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label)
    qb = np.asarray(query_boundaries, np.int64)
    if label_gain is None:
        label_gain = default_label_gain(int(label.max()))
    one = lambdarank_query_loops if loops else lambdarank_query
    grad = np.zeros(len(score), np.float64)
    hess = np.zeros(len(score), np.float64)

    # one thread: a query's arrays are small, so threads would only
    # queue for the interpreter's lock (4 threads ran 3x slower)
    for q in (range(len(qb) - 1) if queries is None else queries):
        lo, hi = qb[q], qb[q + 1]
        grad[lo:hi], hess[lo:hi] = one(
            score[lo:hi], label[lo:hi], label_gain, sigmoid=sigmoid,
            truncation_level=truncation_level, norm=norm)
    if weight is not None:
        grad *= np.asarray(weight, np.float64)
        hess *= np.asarray(weight, np.float64)
    return grad, hess


def ndcg_at(k: int, score, label, query_boundaries, *,
            label_gain=None) -> float:
    """Mean over queries of DCG@k / maxDCG@k (``NDCGMetric::Eval`` without
    query weights): documents in stable descending score order, ties in
    index order (``DCGCalculator::CalDCGAtK``); a query whose labels are
    all zero counts as 1."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label).astype(np.int64)
    qb = np.asarray(query_boundaries, np.int64)
    if label_gain is None:
        label_gain = default_label_gain(int(label.max()))
    sizes = np.diff(qb)
    qid = np.repeat(np.arange(len(sizes)), sizes)
    pos = np.arange(len(score)) - qb[qid]
    disc = np.where(pos < k, discount(pos), 0.0)
    # within every query: by score descending, then by index
    by_score = np.lexsort((np.arange(len(score)), -score, qid))
    dcg = np.bincount(qid, weights=label_gain[label[by_score]] * disc,
                      minlength=len(sizes))
    by_label = np.lexsort((-label, qid))
    best = np.bincount(qid, weights=label_gain[label[by_label]] * disc,
                       minlength=len(sizes))
    return float(np.mean(np.where(best > 0, dcg / np.where(best > 0, best, 1),
                                  1.0)))


def root_split_gh(x: np.ndarray, grad: np.ndarray, hess: np.ndarray,
                  tree0: TextTree, *, lambda_l2: float = 0.0,
                  min_data_in_leaf: int = 20,
                  min_sum_hessian_in_leaf: float = 1e-3, grid: int = 31,
                  sample: int = 100_000,
                  threads: Optional[int] = None) -> RootSplit:
    """Tree 0's root split against the raw data and the given float64
    gradients and hessians: the gain of the recorded split recomputed,
    and the best over a grid of quantile thresholds on every feature."""
    grad = np.asarray(grad, np.float64)
    hess = np.asarray(hess, np.float64)
    n = len(grad)
    g_all, h_all = float(grad.sum()), float(hess.sum())

    def gain(n_left, g_l, h_l):
        n_left = np.asarray(n_left, np.float64)
        g_r, h_r = g_all - g_l, h_all - h_l
        ok = ((n_left >= min_data_in_leaf) & (n - n_left >= min_data_in_leaf)
              & (h_l >= min_sum_hessian_in_leaf)
              & (h_r >= min_sum_hessian_in_leaf))
        with np.errstate(divide="ignore", invalid="ignore"):
            out = (_leaf_gain(g_l, h_l, lambda_l2)
                   + _leaf_gain(g_r, h_r, lambda_l2)
                   - _leaf_gain(g_all, h_all, lambda_l2))
        return np.where(ok, out, -np.inf)

    f0, thr0 = int(tree0.split_feature[0]), float(tree0.threshold[0])
    left = x[:, f0].astype(np.float64) <= thr0
    recomputed = float(gain(left.sum(), grad[left].sum(), hess[left].sum()))

    step = max(n // sample, 1)
    qs = np.linspace(0.0, 1.0, grid + 2)[1:-1]

    def best_of(f: int) -> float:
        col = np.ascontiguousarray(x[:, f])
        edges = np.unique(np.quantile(col[::step], qs).astype(col.dtype))
        b = np.searchsorted(edges, col, side="left")
        m = len(edges)
        cnt = np.bincount(b, minlength=m + 1)[:m].cumsum()
        gs = np.bincount(b, weights=grad, minlength=m + 1)[:m].cumsum()
        hs = np.bincount(b, weights=hess, minlength=m + 1)[:m].cumsum()
        return float(np.max(gain(cnt, gs, hs)))

    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        best = list(pool.map(best_of, range(x.shape[1])))
    grid_best = float(np.max(best))
    recorded = float(tree0.split_gain[0])
    return RootSplit(
        gain_recorded=recorded, gain_recomputed=recomputed,
        gain_grid_best=grid_best, grid_feature=int(np.argmax(best)),
        rel_err=abs(recomputed - recorded) / abs(recorded),
        grid_ratio=recomputed / grid_best)
