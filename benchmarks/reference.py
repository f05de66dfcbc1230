"""The plain references ``correct`` is decided against.

All float64 numpy over RAW feature values, independent of the program:

- ``parse_model`` / ``walk``: LightGBM model text -> per-tree arrays ->
  raw scores by a level-by-level walk (``x <= threshold`` goes left);
- ``auc``: rank AUC with ties averaged;
- ``root_split``: for a binary objective started from the constant
  ``boost_from_average`` score (the log-odds of the label mean), the
  gain of any root split in LightGBM's formula, the gain of the split
  the model recorded for tree 0, and the best gain over a coarse grid of
  quantile thresholds on every feature.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

_INT_KEYS = ("split_feature", "decision_type", "left_child", "right_child")
_FLOAT_KEYS = ("threshold", "leaf_value", "split_gain")


@dataclass
class TextTree:
    num_leaves: int
    split_feature: np.ndarray
    threshold: np.ndarray
    decision_type: np.ndarray
    left_child: np.ndarray
    right_child: np.ndarray
    leaf_value: np.ndarray
    split_gain: np.ndarray

    def depth(self) -> int:
        """Internal nodes on the longest root-to-leaf path."""
        if self.num_leaves < 2:
            return 0
        d = np.zeros(self.num_leaves - 1, np.int64)
        # children are numbered after their parent, so one forward pass
        for node in range(self.num_leaves - 1):
            for child in (self.left_child[node], self.right_child[node]):
                if child >= 0:
                    d[child] = d[node] + 1
        return int(d.max()) + 1


def parse_model(text: str) -> List[TextTree]:
    trees: List[TextTree] = []
    for block in text.split("\nTree=")[1:]:
        block = block.split("\nend of trees")[0]
        kv: Dict[str, str] = {}
        for line in block.splitlines()[1:]:
            key, sep, val = line.partition("=")
            if sep:
                kv[key] = val
        n = int(kv["num_leaves"])
        if int(kv.get("num_cat", "0")) or int(kv.get("is_linear", "0")):
            raise ValueError("the reference walks plain numeric trees only")
        arr = {}
        for key in _INT_KEYS + _FLOAT_KEYS:
            dtype = np.int64 if key in _INT_KEYS else np.float64
            arr[key] = np.array(kv.get(key, "").split(), dtype=dtype)
        if np.any(arr["decision_type"] & 1):
            raise ValueError("categorical split in a numeric-only model")
        trees.append(TextTree(num_leaves=n, **arr))
    return trees


def walk(trees: Sequence[TextTree], x: np.ndarray) -> np.ndarray:
    """Raw score of every row of ``x``: the sum of one leaf per tree.
    Rows hold no NaN here, so the missing-value bits do not matter."""
    x = np.asarray(x, np.float64)
    rows = np.arange(len(x))
    out = np.zeros(len(x), np.float64)
    for t in trees:
        if t.num_leaves < 2:
            out += t.leaf_value[0]
            continue
        node = np.zeros(len(x), np.int64)
        live = rows
        while len(live):
            cur = node[live]
            left = x[live, t.split_feature[cur]] <= t.threshold[cur]
            node[live] = np.where(left, t.left_child[cur],
                                  t.right_child[cur])
            live = live[node[live] >= 0]
        out += t.leaf_value[~node]
    return out


def auc(score: np.ndarray, y: np.ndarray) -> float:
    score = np.asarray(score, np.float64)
    pos = np.asarray(y) > 0
    _, inv, cnt = np.unique(score, return_inverse=True, return_counts=True)
    last = np.cumsum(cnt)
    rank = (last - (cnt - 1) / 2.0)[inv]
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        raise ValueError("AUC needs both classes")
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


@dataclass
class RootSplit:
    gain_recorded: float
    gain_recomputed: float
    gain_grid_best: float
    grid_feature: int
    rel_err: float          # |recomputed - recorded| / recorded
    grid_ratio: float       # recomputed / grid best

    def ok(self, rel_tol: float, grid_floor: float) -> bool:
        return self.rel_err <= rel_tol and self.grid_ratio >= grid_floor


def _leaf_gain(g, h, l2):
    return g * g / (h + l2)


def root_split(x: np.ndarray, y: np.ndarray, tree0: TextTree, *,
               lambda_l2: float = 0.0, min_data_in_leaf: int = 20,
               min_sum_hessian_in_leaf: float = 1e-3,
               grid: int = 31, sample: int = 100_000,
               threads: Optional[int] = None) -> RootSplit:
    """Tree 0's root split against the raw data.  With the constant
    starting score s0 = logit(mean y) every row has gradient p - y and
    hessian p (1 - p), p = mean y, so a split's two sums are a count and
    a label sum."""
    y = np.asarray(y, np.float64)
    n = len(y)
    p = float(y.mean())
    hess = p * (1.0 - p)
    sum_y = float(y.sum())
    g_all, h_all = p * n - sum_y, hess * n

    def gain(n_left, y_left):
        n_left = np.asarray(n_left, np.float64)
        g_l = p * n_left - y_left
        h_l = hess * n_left
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
    recomputed = float(gain(left.sum(), y[left].sum()))

    step = max(n // sample, 1)
    qs = np.linspace(0.0, 1.0, grid + 2)[1:-1]

    def best_of(f: int) -> float:
        col = np.ascontiguousarray(x[:, f])
        edges = np.quantile(col[::step], qs).astype(col.dtype)
        # rows with col <= edges[k] land in bins 0..k
        b = np.searchsorted(edges, col, side="left")
        cnt = np.bincount(b, minlength=grid + 1)[:grid].cumsum()
        ysum = np.bincount(b, weights=y, minlength=grid + 1)[:grid].cumsum()
        return float(np.max(gain(cnt, ysum)))

    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        best = list(pool.map(best_of, range(x.shape[1])))
    grid_best = float(np.max(best))
    recorded = float(tree0.split_gain[0])
    return RootSplit(
        gain_recorded=recorded, gain_recomputed=recomputed,
        gain_grid_best=grid_best, grid_feature=int(np.argmax(best)),
        rel_err=abs(recomputed - recorded) / abs(recorded),
        grid_ratio=recomputed / grid_best)
