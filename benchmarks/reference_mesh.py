"""The plain reference of the data-parallel cell: tree 0, leaf by leaf.

Float64 numpy over RAW feature values, independent of the program.  A
mesh learner is right when rows on every shard were partitioned and
summed as ONE data set, so the reference has no shards: given tree 0 of
the model text (``reference.parse_model``) and all training rows, it
walks every row to its leaf and returns, per leaf, the row count and
the sums of the gradients and hessians the tree was grown from.

For a binary objective started from the constant ``boost_from_average``
score (``reference.root_split`` has the same closed form) every row has
gradient ``p - y`` and hessian ``p (1 - p)`` with ``p = mean y``, so a
leaf's sums are a count and a label sum, exact in float64.  The leaf's
raw output is then ``-G / (H + lambda_l2)``; the model text holds it
times the learning rate, plus the start score, which the program folds
into the first tree's leaves.

The configuration states its precision (``PERF.md``, section 2): the
histograms take their OPERANDS in bfloat16 and sum them in float32.
With two gradient values and one hessian value in the whole data set
that statement is three numbers, so ``operands="bf16"`` rounds those
three (``to_bf16``, round to nearest even, by bits) and still sums in
float64: what is left between it and the program is float32 summation.
``merged_in_bf16`` is the precision below: each shard's leaf sums
rounded to bfloat16 before they are added, which is what a histogram
merged in bfloat16 would hand the leaves.

``tree0_leaf_counts`` reads what ``reference.TextTree`` does not keep:
the ``leaf_count`` line of the model text's first tree.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

import numpy as np

BLOCK_ROWS = 1 << 20


def to_bf16(v) -> np.ndarray:
    """``v`` rounded to the nearest bfloat16 (ties to even), as float64."""
    bits = np.atleast_1d(np.asarray(v, np.float32)).view(np.uint32)
    kept = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return kept.view(np.float32).astype(np.float64).reshape(np.shape(v))


def leaf_of_rows(tree, x: np.ndarray, threads: Optional[int] = None
                 ) -> np.ndarray:
    """The leaf index of every row of ``x`` (``x <= threshold`` goes
    left; rows hold no NaN here), walked level by level in blocks of
    rows, the blocks on a few threads."""
    n = len(x)
    if tree.num_leaves < 2:
        return np.zeros(n, np.int64)
    thr = np.asarray(tree.threshold, np.float64)

    def walk(lo: int) -> np.ndarray:
        xb = x[lo:lo + BLOCK_ROWS]
        node = np.zeros(len(xb), np.int64)
        live = np.arange(len(xb))
        while len(live):
            cur = node[live]
            left = xb[live, tree.split_feature[cur]] <= thr[cur]
            node[live] = np.where(left, tree.left_child[cur],
                                  tree.right_child[cur])
            live = live[node[live] >= 0]
        return ~node

    with ThreadPoolExecutor(threads or min(8, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(walk, range(0, n, BLOCK_ROWS)))
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


@dataclass
class LeafSums:
    """Tree 0 by leaf, from all training rows."""
    count: np.ndarray       # int64 [num_leaves]
    sum_y: np.ndarray       # float64: the leaf's positive labels
    p: float                # mean y: every row's start probability
    leaf: np.ndarray        # int64 [rows]: the leaf of every row
    y: np.ndarray           # float64 [rows]: the labels

    @property
    def start_score(self) -> float:
        return float(np.log(self.p / (1.0 - self.p)))

    def sums(self, operands: str = "f64", count=None, sum_y=None):
        """(G, H) by leaf from counts and label sums (the whole data
        set's, or a part's): gradient ``p - 1`` on a positive row, ``p``
        on a negative one, hessian ``p (1 - p)`` on both."""
        count = self.count if count is None else count
        sum_y = self.sum_y if sum_y is None else sum_y
        g_neg, g_pos, h = self.p, self.p - 1.0, self.p * (1.0 - self.p)
        if operands == "bf16":
            g_neg, g_pos, h = (float(to_bf16(v)) for v in (g_neg, g_pos, h))
        elif operands != "f64":
            raise ValueError(f"operands: f64 or bf16, not {operands!r}")
        return g_neg * (count - sum_y) + g_pos * sum_y, h * count

    def leaf_values(self, learning_rate: float, lambda_l2: float = 0.0,
                    operands: str = "f64") -> np.ndarray:
        """What the model text's tree 0 should hold."""
        g, h = self.sums(operands)
        return -learning_rate * g / (h + lambda_l2) + self.start_score

    def merged_in_bf16(self, learning_rate: float, shards: int,
                       lambda_l2: float = 0.0):
        """(leaf values, leaf counts) if every shard's sums (rows in
        ``shards`` equal contiguous blocks, bf16 operands) were rounded
        to bfloat16 before the merge added them.  The counts are the
        model's kind: the merged hessian over one row's hessian, which
        is how the program (and the reference library) counts a leaf."""
        n, leaves = len(self.leaf), len(self.count)
        g = np.zeros(leaves)
        h = np.zeros(leaves)
        for rows in np.array_split(np.arange(n), shards):
            cnt = np.bincount(self.leaf[rows], minlength=leaves)
            sy = np.bincount(self.leaf[rows], weights=self.y[rows],
                             minlength=leaves)
            g_s, h_s = self.sums("bf16", cnt, sy)
            g += to_bf16(g_s)
            h += to_bf16(h_s)
        one_row = float(to_bf16(self.p * (1.0 - self.p)))
        return (-learning_rate * g / (h + lambda_l2) + self.start_score,
                np.floor(h / one_row + 0.5).astype(np.int64))


def binary_leaf_sums(tree0, x: np.ndarray, y: np.ndarray) -> LeafSums:
    y = np.asarray(y, np.float64)
    leaf = leaf_of_rows(tree0, x)
    count = np.bincount(leaf, minlength=tree0.num_leaves)
    sum_y = np.bincount(leaf, weights=y, minlength=tree0.num_leaves)
    return LeafSums(count=count.astype(np.int64), sum_y=sum_y,
                    p=float(y.mean()), leaf=leaf, y=y)


def tree0_leaf_counts(model_text: str) -> np.ndarray:
    """The ``leaf_count`` line of the first tree of a model text."""
    block = model_text.split("\nTree=")[1].split("\nend of trees")[0]
    for line in block.splitlines():
        key, sep, val = line.partition("=")
        if sep and key == "leaf_count":
            return np.array(val.split(), dtype=np.int64)
    raise ValueError("tree 0 of the model text has no leaf_count line")


def root_histogram(x_col_bins: np.ndarray, y: np.ndarray, n_bins: int):
    """Float64 (gradient, hessian) histogram of one binned column over
    all rows at the start score: what the shards' local histograms of
    the root have to add up to."""
    y = np.asarray(y, np.float64)
    p = float(y.mean())
    cnt = np.bincount(x_col_bins, minlength=n_bins).astype(np.float64)
    sy = np.bincount(x_col_bins, weights=y, minlength=n_bins)
    return np.stack([p * cnt - sy, p * (1.0 - p) * cnt], axis=1)
