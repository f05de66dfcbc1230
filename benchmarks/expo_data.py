"""The Expo cell's data: an airline-on-time-shaped binary task from a seed.

The reference's Expo experiment (LightGBM ``docs/Experiments.rst``: the
2009 Data Expo table, 11,000,000 rows x 700 features, "the categorical
fields one-hot coded") is a handful of raw fields of which the
categorical ones become 0/1 columns.  No network, so this is not that
file (``assumed`` in ``configs/expo-onehot.json``); what is kept is its
shape:

- six categorical fields of 12 / 31 / 7 / 22 / 313 / 313 levels (month,
  day of month, weekday, carrier, origin, destination), one-hot coded:
  698 columns, exactly one 1.0 a field a row; then departure time (hours,
  a two-peaked day) and distance (miles, log-normal) as numeric columns
  698 and 699.  700 columns, 8 stored entries a row, as a scipy CSR
  matrix of float32;
- level popularity: uniform for the three calendar fields, Zipf for
  carrier (exponent 0.7) and the airports (exponent 1.0), so no level
  holds more than ~0.16 of the rows - under the 0.2 non-default share
  EFB's ``sparse_threshold`` allows a bundled column;
- the label, as ISSUE 36 sets it out: a hidden logit of per-level
  effects of every field, two smooth terms of the numeric columns (the
  day's build-up of delays, long hauls making up time) and a carrier x
  origin interaction on a tenth of the pairs, plus logistic noise.  The
  effects are ONE fixed draw (``TASK_STREAM``): the task is the same for
  every seed (``higgs_data.py`` says why).  The noiseless logit is
  returned for ``auc_share``'s oracle.
- **the training table is ONE table for every seed, as the Expo file is
  one file** (``table_rows``, from ``TABLE_STREAM``); the seed draws the
  rows after it, the held-out ones.  A tree's cost in this system is
  the rows of its parents, summed, and a one-hot tree is a chain whose
  length follows the order in which levels are peeled.  With the
  training rows the seed's own draw the 32 timed trees took 642.7 to
  654.9 row visits a row over fifteen seeds and the rate spread 1.0%
  in two sets of six of the driver's (0.28% / 1.04% in mine), over
  what admits a cell.  Nothing less than the same table gives every
  seed the same work (my runs, PR 36): the same rows in another order
  get other bin edges, since ``lgb.Dataset`` finds bins from a sample
  of row POSITIONS, and other trees from the root down (300k rows on
  the CPU, two orders: 644.9 / 636.4 visits); the same rows in the
  same order with the levels of each field renamed get other bundles,
  the finder sums a column's bins in another order, and a gain that
  ties within float32 rounding flips (on the chip, three seeds: the
  first 20 trees alike digit for digit, then 642.5 / 639.7 / 641.7
  visits, rates 0.8106 / 0.8146 / 0.8122).

Rows are drawn in fixed chunks, each from its own stream
(``default_rng([*TABLE_STREAM, chunk])`` for the table's,
``default_rng([seed, 0, chunk])`` for the seed's own), on a few threads;
the rows a seed gives do not depend on the thread count.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18
# (name, levels, Zipf exponent of the level popularity; 0 = uniform)
FIELDS = (("month", 12, 0.0), ("day", 31, 0.0), ("weekday", 7, 0.0),
          ("carrier", 22, 0.7), ("origin", 313, 1.0), ("dest", 313, 1.0))
N_ONEHOT = sum(levels for _, levels, _ in FIELDS)          # 698
N_FEATURES = N_ONEHOT + 2                                   # 700
COL_DEP_TIME, COL_DISTANCE = N_ONEHOT, N_ONEHOT + 1
STORED_PER_ROW = len(FIELDS) + 2                            # 8
TASK_STREAM = (20261003, 36)
TABLE_STREAM = (20261003, 37)
# the standard deviation of every term of the logit (the module
# docstring says what sets it)
EFFECT_SD = 0.45
INTERACTION_SHARE = 0.1     # of the carrier x origin pairs
INTERCEPT = -1.25


def field_offsets() -> np.ndarray:
    """First one-hot column of each field."""
    return np.concatenate(
        [[0], np.cumsum([levels for _, levels, _ in FIELDS])[:-1]])


def level_cdfs():
    """Cumulative level popularity of each field."""
    out = []
    for _, levels, expo in FIELDS:
        w = 1.0 / np.arange(1, levels + 1, dtype=np.float64) ** expo
        out.append(np.cumsum(w / w.sum()))
    return out


class Task:
    """The fixed draw every seed shares."""

    def __init__(self):
        rng = np.random.default_rng(TASK_STREAM)
        self.effects = [rng.normal(0.0, EFFECT_SD, levels)
                        for _, levels, _ in FIELDS]
        pairs = (FIELDS[3][1], FIELDS[4][1])
        self.interaction = (rng.normal(0.0, EFFECT_SD, pairs)
                            * (rng.random(pairs) < INTERACTION_SHARE))

    def logit(self, levels, dep_time, distance) -> np.ndarray:
        """The noiseless logit from the six level indices ([6, n]) and
        the two numeric columns."""
        z = np.full(levels.shape[1], INTERCEPT)
        for eff, lv in zip(self.effects, levels):
            z += eff[lv]
        z += self.interaction[levels[3], levels[4]]
        hours = np.asarray(dep_time, np.float64)
        # delays build up over the day (a sine of amplitude a has the
        # standard deviation a / sqrt 2 over a day); long hauls make up
        # time (log distance has the standard deviation 0.7)
        z += EFFECT_SD * np.sqrt(2.0) * np.sin((hours - 9.0) * (np.pi / 15.0))
        z -= EFFECT_SD / 0.7 * np.log(np.asarray(distance, np.float64) / 700.0)
        return z


def make_expo_like(n_rows: int, seed: int = 0, table_rows: int = 0):
    """(x scipy CSR float32 [n, 700], y float32 [n], logit float64 [n]).

    The first ``table_rows`` rows are the fixed table's first
    ``table_rows``; the rows after them are the seed's own draw."""
    import scipy.sparse as sp
    task = Task()
    cdfs = level_cdfs()
    offs = field_offsets()
    table_rows = min(int(table_rows), n_rows)
    k = STORED_PER_ROW
    indices = np.empty((n_rows, k), np.int32)
    data = np.ones((n_rows, k), np.float32)
    y = np.empty((n_rows,), np.float32)
    logit = np.empty((n_rows,), np.float64)

    def fill(job) -> None:
        stream, lo, hi = job
        # a whole chunk is drawn whatever part of it is kept, so that a
        # row does not depend on how many rows were asked for
        m, keep = CHUNK_ROWS, hi - lo
        rng = np.random.default_rng(stream)
        levels = np.stack([
            np.minimum(np.searchsorted(cdf, rng.random(m)), len(cdf) - 1)
            for cdf in cdfs])[:, :keep]
        peak = rng.random(m) < 0.5
        dep = np.where(peak, rng.normal(8.5, 2.0, m),
                       rng.normal(17.0, 2.5, m))[:keep] % 24.0
        dist = np.clip(np.exp(rng.normal(6.45, 0.7, m)[:keep]), 31.0, 4962.0)
        dep, dist = dep.astype(np.float32), np.round(dist).astype(np.float32)
        indices[lo:hi, :6] = (levels + offs[:, None]).T
        indices[lo:hi, 6] = COL_DEP_TIME
        indices[lo:hi, 7] = COL_DISTANCE
        data[lo:hi, 6] = dep
        data[lo:hi, 7] = dist
        logit[lo:hi] = task.logit(levels, dep, dist)
        y[lo:hi] = logit[lo:hi] + rng.logistic(size=m)[:keep] > 0

    def chunks(stream, lo, hi):
        return [((*stream, c), a, min(a + CHUNK_ROWS, hi))
                for c, a in enumerate(range(lo, hi, CHUNK_ROWS))]

    jobs = (chunks(TABLE_STREAM, 0, table_rows)
            + chunks((seed, 0), table_rows, n_rows))
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, jobs))
    indptr = np.arange(0, (n_rows + 1) * k, k, dtype=np.int64)
    x = sp.csr_matrix((data.reshape(-1), indices.reshape(-1), indptr),
                      shape=(n_rows, N_FEATURES))
    x.has_sorted_indices = True     # a field's columns follow the last's
    return x, y, logit
