"""The benchmark's data: a Higgs-shaped binary task from a seed.

The functional form is ``bench.make_higgs_like``'s (28 standard-normal
"kinematic" features, a hidden linear field plus three nonlinear terms,
logistic label noise).  Three things differ from that function, and all
are frozen here so that later PRs measure on the same rows:

- the hidden weights are ONE fixed draw (``TASK_STREAM``), not the
  seed's: the task is the same for every seed and the seed draws the
  rows, as the real Higgs file is one task.  With the seed's own weights
  the ceiling a model can reach moved between 0.83 and 0.87 AUC and the
  share of it that 10 trees recover between 75 and 84 (my CPU run, PR
  25), which no bound under 10% can sit on; tree shapes, and with them
  the iteration time, moved with it;
- rows are drawn in fixed chunks of 262,144, each from its own stream
  ``default_rng([seed, 0, chunk])``, in float32, on a few threads.  The
  rows a seed gives do not depend on the thread count.  Drawing 10.6M x
  28 in one float64 stream costs ~20 s of every run's set-up; this is
  ~2 s;
- the noiseless logit is returned too: it is the best score any model
  can reach on these rows, so ``auc_share`` is quality as a share of
  what the seed allows (the ceiling moves with the hidden weights).

No network, so this is not the UCI file (``assumed`` in
``configs/higgs.json``).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18
N_FEATURES = 28
TASK_STREAM = (20260928, 1)


def hidden_weights(n_features: int = N_FEATURES) -> np.ndarray:
    return np.random.default_rng(TASK_STREAM).normal(size=(n_features,))


def noiseless_logit(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return (x @ w * 0.3
            + 0.8 * x[:, 0] * x[:, 1]
            - 0.6 * np.abs(x[:, 2])
            + 0.5 * x[:, 3] ** 2)


def make_higgs_like(n_rows: int, seed: int = 0,
                    n_features: int = N_FEATURES):
    """(x float32 [n, F], y float32 [n], logit float64 [n])."""
    w = hidden_weights(n_features)
    x = np.empty((n_rows, n_features), np.float32)
    y = np.empty((n_rows,), np.float32)
    logit = np.empty((n_rows,), np.float64)

    def fill(c: int) -> None:
        lo = c * CHUNK_ROWS
        hi = min(lo + CHUNK_ROWS, n_rows)
        rng = np.random.default_rng([seed, 0, c])
        x[lo:hi] = rng.standard_normal((hi - lo, n_features), np.float32)
        logit[lo:hi] = noiseless_logit(x[lo:hi], w)
        y[lo:hi] = logit[lo:hi] + rng.logistic(size=hi - lo) > 0

    n_chunks = -(-n_rows // CHUNK_ROWS)
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(n_chunks)))
    return x, y, logit
