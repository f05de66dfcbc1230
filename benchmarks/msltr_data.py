"""The ranking cells' data: an MS-LTR-shaped task from a seed.

Microsoft Learning to Rank as the reference's experiment ran it
(``docs/Experiments.rst``; MSLR-WEB30K, Fold1 train, recalled - no
network here) is 2,270,296 documents in 18,919 queries of 1 to 1,251
documents, 137 features, relevance grades 0-4.  This generator keeps
those shapes and, by the lessons of ``higgs_data.py``, freezes the task:

- ONE fixed task for every seed (``TASK_STREAM``): the kind and scale of
  each of the 137 columns, the hidden relevance function, the grade
  thresholds AND the queries - their lengths, training and held-out,
  their query-level feature values and their grade offsets - are one
  draw.  So every seed has the same queries, the same pair count and
  compiles the same programs; the seed draws the documents;
- lengths: log-normal (heavy right tail), clipped to [1, longest], then
  pinned: the shortest is 1, the longest exactly ``longest``, and the
  training lengths sum exactly to ``total_rows`` (mean ~120 at the
  published shape);
- columns, float32, of four kinds (MSLR's are a mix of all four): dense
  continuous; non-negative counts with few distinct values; mostly-zero
  columns; and columns constant within a query, as its query-level
  features are;
- the hidden relevance is a fixed linear field over a subset of the
  columns plus three non-linear terms; grades 0-4 cut a noisy copy of it
  (row noise plus a per-query offset) at fixed thresholds, so that the
  grade shares are about 0.52 / 0.32 / 0.13 / 0.02 / 0.01;
- the noiseless relevance is returned as the oracle score: the best any
  model can rank these rows, so quality is a share of what a seed allows;
- rows are drawn in fixed chunks of 131,072, each from its own stream
  ``default_rng([seed, 0, chunk])``, on a few threads.  The rows a
  seed gives do not depend on the thread count.

Frozen once accepted: later PRs measure on the same rows.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 17
N_FEATURES = 137
TASK_STREAM = (20261001, 29)
TRAIN_QUERIES, HELD_QUERIES = 18_919, 2_000
TRAIN_ROWS, LONGEST = 2_270_296, 1_251
GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)

# column kinds, in column order
DENSE, COUNT, SPARSE, QUERY = 0, 1, 2, 3
KIND_COUNTS = ((DENSE, 64), (COUNT, 32), (SPARSE, 24), (QUERY, 17))
ROW_NOISE, QUERY_NOISE = 0.9, 0.35


def query_lengths(n_train: int = TRAIN_QUERIES, n_held: int = HELD_QUERIES,
                  longest: int = LONGEST, total_rows=TRAIN_ROWS):
    """(training lengths, held-out lengths), int64: the fixed draw.
    ``total_rows`` None leaves the training sum where it falls (the
    rehearsal sizes)."""
    rng = np.random.default_rng(TASK_STREAM + (1,))
    raw = rng.lognormal(mean=np.log(95.0), sigma=0.68,
                        size=n_train + n_held)
    sizes = np.clip(np.rint(raw), 1, longest).astype(np.int64)
    train, held = sizes[:n_train].copy(), sizes[n_train:].copy()
    train[int(np.argmax(train))] = longest
    train[int(np.argmin(train))] = 1
    held[int(np.argmin(held))] = 1
    if total_rows is not None:
        # spread the difference over the middle queries, one row each, in
        # the fixed order of a task-stream permutation
        free = np.flatnonzero((train > 2) & (train < longest - 1))
        order = rng.permutation(free)
        diff = int(total_rows - train.sum())
        step = 1 if diff > 0 else -1
        k = 0
        while diff:
            q = order[k % len(order)]
            if 2 < train[q] + step < longest:
                train[q] += step
                diff -= step
            k += 1
    return train, held


@functools.lru_cache(maxsize=None)
def task():
    """The fixed task: column kinds and scales, relevance weights, grade
    thresholds."""
    rng = np.random.default_rng(TASK_STREAM + (0,))
    kind = np.concatenate([np.full(c, k) for k, c in KIND_COUNTS])
    kind = kind[rng.permutation(N_FEATURES)]
    scale = rng.uniform(0.5, 2.0, size=N_FEATURES)
    # a third of the row-level columns carry relevance
    w = rng.normal(size=N_FEATURES) * (rng.random(N_FEATURES) < 0.33)
    w[kind == QUERY] = 0.0
    w /= np.sqrt(np.sum(w * w))
    dense = np.flatnonzero(kind == DENSE)
    t = {"kind": kind, "scale": scale, "w": w, "pick": dense[:4],
         "count_col": int(np.flatnonzero(kind == COUNT)[0])}
    # thresholds: quantiles of the noisy relevance over a fixed sample
    cal = np.random.default_rng(TASK_STREAM + (2,))
    z = cal.standard_normal((200_000, N_FEATURES), np.float32)
    x = _shape_columns(z, t)
    noisy = (relevance(x, t) + ROW_NOISE * cal.standard_normal(len(x))
             + QUERY_NOISE * cal.standard_normal(len(x)))
    t["cuts"] = np.quantile(noisy, np.cumsum(GRADE_SHARES)[:-1])
    return t


def _shape_columns(z: np.ndarray, t) -> np.ndarray:
    """Standard-normal draws -> the columns' kinds, in place."""
    kind, scale = t["kind"], t["scale"].astype(np.float32)
    c = kind == COUNT
    # (abs, one product and floor round exactly: the same on every CPU)
    z[:, c] = np.minimum(np.floor(np.float32(2.5) * np.abs(z[:, c])), 15.0)
    s = kind == SPARSE
    z[:, s] = np.maximum(z[:, s] - 0.85, 0.0)      # ~80% exact zeros
    z *= scale
    return z


def relevance(x: np.ndarray, t) -> np.ndarray:
    """The noiseless relevance, float64."""
    def col(i):
        return x[:, i].astype(np.float64) / t["scale"][i]

    used = np.flatnonzero(t["w"])
    a, b, c, d = (col(i) for i in t["pick"])
    return ((x[:, used].astype(np.float64) / t["scale"][used])
            @ t["w"][used]
            + 0.5 * a * b - 0.4 * np.abs(c)
            + 0.3 * np.minimum(col(t["count_col"]), 4.0) * d)


def make_msltr_like(seed: int = 0, *, n_train: int = TRAIN_QUERIES,
                    n_held: int = HELD_QUERIES, longest: int = LONGEST,
                    total_rows=TRAIN_ROWS):
    """(x float32 [n, 137], grade float32 [n], oracle float64 [n],
    training lengths, held-out lengths); the training queries' rows come
    first, the held-out queries' after them."""
    t = task()
    train, held = query_lengths(n_train, n_held, longest, total_rows)
    sizes = np.concatenate([train, held])
    n_rows = int(sizes.sum())
    qid = np.repeat(np.arange(len(sizes)), sizes)
    qrng = np.random.default_rng(TASK_STREAM + (3,))   # the queries: fixed
    q_feat = qrng.standard_normal((len(sizes), N_FEATURES), np.float32)
    q_off = QUERY_NOISE * qrng.standard_normal(len(sizes))
    is_q = t["kind"] == QUERY
    x = np.empty((n_rows, N_FEATURES), np.float32)
    grade = np.empty((n_rows,), np.float32)
    oracle = np.empty((n_rows,), np.float64)

    def fill(c: int) -> None:
        lo = c * CHUNK_ROWS
        hi = min(lo + CHUNK_ROWS, n_rows)
        rng = np.random.default_rng([seed, 0, c])
        z = rng.standard_normal((hi - lo, N_FEATURES), np.float32)
        q = qid[lo:hi]
        z[:, is_q] = q_feat[q][:, is_q]
        x[lo:hi] = _shape_columns(z, t)
        oracle[lo:hi] = relevance(x[lo:hi], t)
        noisy = (oracle[lo:hi] + ROW_NOISE * rng.standard_normal(hi - lo)
                 + q_off[q])
        grade[lo:hi] = np.searchsorted(t["cuts"], noisy)

    n_chunks = -(-n_rows // CHUNK_ROWS)
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(n_chunks)))
    return x, grade, oracle, train, held
