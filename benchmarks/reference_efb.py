"""The plain reference of the Expo cell: tree 0 against RAW sparse rows.

Float64 numpy over the scipy CSR matrix the program was handed, knowing
nothing of bins or bundles.  A table of one-hot columns is right when a
split on column c sends exactly the rows that STORE a non-zero value in
c one way and every other row the other, whatever shared bin column the
program kept c in; so the reference reads stored entries and never a
bin:

- ``root_gains``: LightGBM's gain of the root split of every column at
  the constant ``boost_from_average`` start score (gradient ``p - y``,
  hessian ``p (1 - p)``, ``p = mean y``: a split's sums are a count and
  a label sum).  A 0/1 column has one split, zero against stored, and
  its sums are the count and the label sum of its stored entries; a
  column with other values (the two numeric ones) takes the 31-quantile
  grid of ``reference.root_split``;
- ``leaf_of_rows``: the leaf of every row under a tree of the model
  text (``reference.parse_model``), node by node: a row's value in the
  split column is what it stores there, else 0.0, and ``value <=
  threshold`` goes left;
- ``walk``: raw scores of the first trees, for ``auc_share`` and for
  the comparison with ``Booster.predict``.

Leaf sums and leaf values come from ``reference_mesh.LeafSums`` over
these leaves: the same closed form, the same stated precision (bf16
operands, sums above float32).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np


class Columns:
    """Column access to a CSR matrix: its CSC form, made once."""

    def __init__(self, x_csr):
        self.n, self.f = x_csr.shape
        csc = x_csr.tocsc()
        self.indptr, self.rows, self.data = (csc.indptr, csc.indices,
                                             csc.data)

    def stored(self, col: int):
        lo, hi = self.indptr[col], self.indptr[col + 1]
        return self.rows[lo:hi], self.data[lo:hi]

    def dense(self, col: int) -> np.ndarray:
        """Column ``col`` of every row, float64: stored value or 0."""
        out = np.zeros(self.n, np.float64)
        rows, vals = self.stored(col)
        out[rows] = vals
        return out


def leaf_of_rows(tree, cols: Columns) -> np.ndarray:
    """The leaf index of every row (rows hold no NaN here)."""
    leaf = np.zeros(cols.n, np.int64)
    if tree.num_leaves < 2:
        return leaf
    # rows of each open node; children are numbered after their parent
    at = {0: np.arange(cols.n, dtype=np.int64)}
    for node in range(tree.num_leaves - 1):
        rows = at.pop(node)
        col = cols.dense(int(tree.split_feature[node]))
        left = col[rows] <= float(tree.threshold[node])
        for child, part in ((tree.left_child[node], rows[left]),
                            (tree.right_child[node], rows[~left])):
            if child >= 0:
                at[int(child)] = part
            else:
                leaf[part] = ~child
    return leaf


def walk(trees: Sequence, cols: Columns) -> np.ndarray:
    """Raw score of every row: the sum of one leaf a tree."""
    out = np.zeros(cols.n, np.float64)
    for t in trees:
        out += t.leaf_value[leaf_of_rows(t, cols)]
    return out


@dataclass
class RootGains:
    gain_recorded: float        # tree 0's root gain in the model text
    gain_recomputed: float      # the same split's gain, float64, raw rows
    gain_best: float            # the best root gain over every column
    best_column: int
    root_column: int
    rel_err: float              # |recomputed - recorded| / recorded
    best_ratio: float           # recomputed / best

    def ok(self, rel_tol: float, floor: float) -> bool:
        return self.rel_err <= rel_tol and self.best_ratio >= floor


def root_gains(cols: Columns, y: np.ndarray, tree0, *,
               lambda_l2: float = 0.0, min_data_in_leaf: int = 20,
               min_sum_hessian_in_leaf: float = 1e-3, grid: int = 31,
               sample: int = 100_000) -> RootGains:
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

    # every column's count and label sum of stored non-zero entries
    nz = cols.data != 0
    col_of = np.repeat(np.arange(cols.f), np.diff(cols.indptr))[nz]
    cnt = np.bincount(col_of, minlength=cols.f).astype(np.float64)
    ysum = np.bincount(col_of, weights=y[cols.rows[nz]], minlength=cols.f)
    binary = np.array([np.all(cols.stored(c)[1] == 1.0)
                       for c in range(cols.f)])
    # zero goes left, stored goes right
    best = np.where(binary, gain(n - cnt, y.sum() - ysum), -np.inf)
    qs = np.linspace(0.0, 1.0, grid + 2)[1:-1]
    step = max(n // sample, 1)
    for c in np.flatnonzero(~binary):
        col = cols.dense(int(c))
        edges = np.quantile(col[::step], qs)
        b = np.searchsorted(edges, col, side="left")
        c_l = np.bincount(b, minlength=grid + 1)[:grid].cumsum()
        y_l = np.bincount(b, weights=y, minlength=grid + 1)[:grid].cumsum()
        best[c] = float(np.max(gain(c_l, y_l)))

    f0, thr0 = int(tree0.split_feature[0]), float(tree0.threshold[0])
    left = cols.dense(f0) <= thr0
    recomputed = float(gain(left.sum(), y[left].sum()))
    recorded = float(tree0.split_gain[0])
    return RootGains(
        gain_recorded=recorded, gain_recomputed=recomputed,
        gain_best=float(best.max()), best_column=int(np.argmax(best)),
        root_column=f0,
        rel_err=abs(recomputed - recorded) / abs(recorded),
        best_ratio=recomputed / float(best.max()))
