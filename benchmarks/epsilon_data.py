"""The Epsilon cell's data: a wide dense binary task from a seed.

The reference's GPU benchmark page (LightGBM ``docs/GPU-Performance.rst``)
trains on the PASCAL Large Scale Learning Challenge's ``epsilon`` set:
400,000 training rows x 2,000 dense real features, balanced classes,
every row normalised.  No network, so this is not that file
(``assumed`` in ``configs/epsilon-dense.json``); what is kept is its
shape:

- 2,000 dense float32 columns in 100 correlated blocks of 20: a block
  shares one latent factor, which each of its columns carries with a
  loading of its own (0.6 to 0.95) beside noise of its own; then every
  row is scaled to unit L2 norm, as ``epsilon_normalized`` is, so no
  value is missing and a column's scale is about 1 / sqrt(2000);
- the label: a noiseless logit over the 300
  columns of the first 15 blocks - a weighted sum of them (each block's
  20 weights one fixed draw), plus products of two blocks' means for
  ten pairs and a step of one block's mean that flips the sign of
  another's for five more (the non-additive part a deep tree has to
  find) - and logistic noise.  The effects are ONE fixed draw
  (``TASK_STREAM``): the task is the same for every seed.  Its scale
  (``LOGIT_SCALE``) is an assumption, no number of the page sets it:
  with it the noiseless logit's AUC on held-out rows is about 0.957.
  The label is balanced by symmetry (every term has mean 0).  The
  noiseless logit is returned for ``auc_share``'s oracle;
- **the training table is ONE table for every seed, as the Epsilon
  file is one file** (``table_rows``, from ``TABLE_STREAM``); the seed
  draws the rows after it, the held-out ones (``expo_data.py`` says why
  a seed's own table moves the work a tree does).

Rows are drawn in fixed chunks, each from its own stream
(``default_rng([*TABLE_STREAM, chunk])`` for the table's,
``default_rng([seed, 0, chunk])`` for the seed's own), on a few
threads; the rows a seed gives do not depend on the thread count.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

N_FEATURES = 2000
BLOCK = 20
N_BLOCKS = N_FEATURES // BLOCK
SIGNAL_BLOCKS = 15                  # the first 300 columns carry the label
N_PRODUCTS, N_FLIPS = 10, 5
CHUNK_ROWS = 1 << 14
TASK_STREAM = (20261015, 40)
TABLE_STREAM = (20261015, 41)
LOGIT_SCALE = 2.3


class Task:
    """The fixed draw every seed shares: loadings and the logit's
    effects."""

    def __init__(self):
        rng = np.random.default_rng(TASK_STREAM)
        self.loading = rng.uniform(0.6, 0.95, N_FEATURES).astype(np.float32)
        self.noise = np.sqrt(1.0 - self.loading ** 2).astype(np.float32)
        self.weights = rng.normal(0.0, 1.0, (SIGNAL_BLOCKS, BLOCK))
        pairs = rng.permutation(SIGNAL_BLOCKS * SIGNAL_BLOCKS)
        pairs = [(int(p // SIGNAL_BLOCKS), int(p % SIGNAL_BLOCKS))
                 for p in pairs if p // SIGNAL_BLOCKS != p % SIGNAL_BLOCKS]
        self.products = pairs[:N_PRODUCTS]
        self.flips = pairs[N_PRODUCTS:N_PRODUCTS + N_FLIPS]

    def logit(self, x: np.ndarray) -> np.ndarray:
        """The noiseless logit of normalised rows ``x`` [n, 2000]."""
        u = np.asarray(x[:, :SIGNAL_BLOCKS * BLOCK], np.float64) * np.sqrt(
            N_FEATURES)
        u = u.reshape(len(u), SIGNAL_BLOCKS, BLOCK)
        means = u.mean(axis=2)                       # [n, blocks]
        # each block's weighted sum, scaled to about unit variance
        lin = np.einsum("nbk,bk->n", u, self.weights) / np.sqrt(
            SIGNAL_BLOCKS * BLOCK * 0.36)
        z = lin
        for a, b in self.products:
            z = z + means[:, a] * means[:, b] * 2.0
        for a, b in self.flips:
            z = z + np.where(means[:, a] > 0.0, 1.0, -1.0) * means[:, b] * 2.0
        return LOGIT_SCALE * z / np.sqrt(1.0 + 0.4 * (N_PRODUCTS + N_FLIPS))


def make_epsilon_like(n_rows: int, seed: int = 0, table_rows: int = 0):
    """(x float32 [n, 2000], y float32 [n], logit float64 [n]).

    The first ``table_rows`` rows are the fixed table's first
    ``table_rows``; the rows after them are the seed's own draw."""
    task = Task()
    table_rows = min(int(table_rows), n_rows)
    x = np.empty((n_rows, N_FEATURES), np.float32)
    y = np.empty((n_rows,), np.float32)
    logit = np.empty((n_rows,), np.float64)

    def fill(job) -> None:
        stream, lo, hi = job
        # a whole chunk is drawn whatever part of it is kept, so that a
        # row does not depend on how many rows were asked for
        m, keep = CHUNK_ROWS, hi - lo
        rng = np.random.default_rng(stream)
        factor = rng.standard_normal((m, N_BLOCKS), np.float32)
        block = np.repeat(factor, BLOCK, axis=1)
        v = rng.standard_normal((m, N_FEATURES), np.float32)
        v *= task.noise
        v += block * task.loading
        v = v[:keep]
        v /= np.sqrt(np.einsum("ij,ij->i", v, v))[:, None]
        x[lo:hi] = v
        logit[lo:hi] = task.logit(v)
        y[lo:hi] = logit[lo:hi] + rng.logistic(size=m)[:keep] > 0

    def chunks(stream, lo, hi):
        return [((*stream, c), a, min(a + CHUNK_ROWS, hi))
                for c, a in enumerate(range(lo, hi, CHUNK_ROWS))]

    jobs = (chunks(TABLE_STREAM, 0, table_rows)
            + chunks((seed, 0), table_rows, n_rows))
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, jobs))
    return x, y, logit
