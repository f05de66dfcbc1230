"""The plain reference of the wide dense cell: tree 0's root split over
every column, in float64 over RAW values.

It knows a table of dense float columns and a model text, nothing of
how the program lays them out.  With the constant ``boost_from_average``
start score every row has gradient ``p - y`` and hessian ``p (1 - p)``
(``reference.root_split`` has the same closed form), so a split's two
sums are a count and a label sum.  The candidates of a column are the
reference's own: a plain equal-count grid of ``GRID_BINS`` bins over
the column's raw float64 values (``quantile_grid``; ``x <= edge`` goes
left), the configuration's ``max_bin``, built here and not read from
the program's bins - so a fault in the program's binning, wrong or
merged bounds, shows as a root split under the best of the grid.  The
best over all columns is what tree 0's root split is held to.  The
leaf-by-leaf part of tree 0 is
``reference_mesh.binary_leaf_sums``: a walk of every row and float64
sums by leaf.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

import numpy as np

from reference_efb import RootGains
from reference_mesh import to_bf16

GRID_BINS = 63


@dataclass
class WideRootGains(RootGains):
    # the best root gain of every OTHER column over the best: what a
    # finder that never saw the best column would read against the floor
    runner_up_ratio: float = 0.0


def quantile_grid(col: np.ndarray, bins: int = GRID_BINS) -> np.ndarray:
    """The inner edges of an equal-count grid of ``bins`` bins over one
    column's raw values, in float64: its ``bins - 1`` quantiles at
    ``k / bins``, ascending and distinct."""
    return np.unique(np.quantile(np.asarray(col, np.float64),
                                 np.arange(1, bins) / bins))


def root_gains(x: np.ndarray, y: np.ndarray, tree0, *,
               lambda_l2: float = 0.0, min_data_in_leaf: int = 20,
               min_sum_hessian_in_leaf: float = 1e-3,
               grid_bins: int = GRID_BINS,
               threads: Optional[int] = None) -> WideRootGains:
    """Tree 0's recorded root split against the float64 gain of the
    same split over raw values, and against the best root gain over
    every column on ``quantile_grid(x[:, j], grid_bins)``."""
    y = np.asarray(y, np.float64)
    n = len(y)
    p = float(y.mean())
    hess = p * (1.0 - p)
    g_all, h_all = p * n - float(y.sum()), hess * n

    def gain(n_left, y_left):
        n_left = np.asarray(n_left, np.float64)
        g_l, h_l = p * n_left - y_left, hess * n_left
        g_r, h_r = g_all - g_l, h_all - h_l
        ok = ((n_left >= min_data_in_leaf) & (n - n_left >= min_data_in_leaf)
              & (h_l >= min_sum_hessian_in_leaf)
              & (h_r >= min_sum_hessian_in_leaf))
        with np.errstate(divide="ignore", invalid="ignore"):
            out = (g_l * g_l / (h_l + lambda_l2)
                   + g_r * g_r / (h_r + lambda_l2)
                   - g_all * g_all / (h_all + lambda_l2))
        return np.where(ok, out, -np.inf)

    def best_of(j: int) -> float:
        col = x[:, j].astype(np.float64)
        bounds = quantile_grid(col, grid_bins)
        if not len(bounds):
            return -np.inf
        # rows with x <= bounds[k] lie in bins 0..k
        b = np.searchsorted(bounds, col, side="left")
        k = len(bounds)
        c_l = np.bincount(b, minlength=k + 1)[:k].cumsum()
        y_l = np.bincount(b, weights=y, minlength=k + 1)[:k].cumsum()
        return float(np.max(gain(c_l, y_l)))

    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        best = np.array(list(pool.map(best_of, range(x.shape[1]))))
    f0, thr0 = int(tree0.split_feature[0]), float(tree0.threshold[0])
    left = x[:, f0].astype(np.float64) <= thr0
    recomputed = float(gain(left.sum(), y[left].sum()))
    recorded = float(tree0.split_gain[0])
    top = int(np.argmax(best))
    return WideRootGains(
        gain_recorded=recorded, gain_recomputed=recomputed,
        gain_best=float(best[top]), best_column=top, root_column=f0,
        rel_err=abs(recomputed - recorded) / abs(recorded),
        best_ratio=recomputed / float(best[top]),
        runner_up_ratio=float(np.delete(best, top).max() / best[top]))


def root_gain_of_bf16_sums(x: np.ndarray, y: np.ndarray, tree0, *,
                           lambda_l2: float = 0.0) -> float:
    """The gain of tree 0's root split with each side's two sums
    rounded to bfloat16 before the gain is taken: the precision below
    the float32 sums the configuration states, for the root-gain
    limit's second reading."""
    y = np.asarray(y, np.float64)
    p = float(y.mean())
    hess = p * (1.0 - p)
    f0, thr0 = int(tree0.split_feature[0]), float(tree0.threshold[0])
    left = x[:, f0].astype(np.float64) <= thr0
    n_l, n = float(left.sum()), len(y)
    g_l, g_all = p * n_l - float(y[left].sum()), p * n - float(y.sum())
    sides = [(g_l, hess * n_l), (g_all - g_l, hess * (n - n_l))]
    g_t, h_t = g_all, hess * n
    out = -g_t * g_t / (h_t + lambda_l2)
    for g, h in sides:
        g, h = float(to_bf16(g)), float(to_bf16(h))
        out += g * g / (h + lambda_l2)
    return out
