"""The plain reference of a job with a validation set: what the booster's
valid score and its recorded AUC should be.

Float64 numpy over RAW feature values, independent of the program: it
knows nothing of bins, combs or the score tail.  Given the model text
(``model_to_string()``) and the valid rows:

- ``parse_model``: every tree's arrays, categorical bitsets and the
  missing-value bits of ``decision_type`` included;
- ``leaves``: the leaf each row reaches in each tree, walked over the
  raw values by LightGBM's own decision rules (``tree.h``
  ``NumericalDecision`` / ``CategoricalDecision``): a NaN where the
  split's missing type is not NaN reads as 0; a row at the split's
  missing value (NaN, or zero under ``missing_type`` zero) goes the
  default direction; else ``x <= threshold`` goes left; a categorical
  split sends a row left when its value, truncated to an int, is a
  member of the split's bitset, and a NaN or negative value right;
- ``scores_after``: each row's score after each tree, the float64 sum
  of the leaf values it reached (the model text folds the start score
  into tree 0's leaves, so the sum is the whole score);
- ``auc``: the midrank (tie-averaged) AUC of a score vector;
- ``auc_in_bf16``: the same AUC with its arithmetic one precision below
  the float32 the program's AUC takes: the midranks rounded to bfloat16
  and summed pairwise in bfloat16 (the control's AUC).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from reference import auc  # noqa: F401  (midrank AUC, float64)
from reference_mesh import to_bf16

BLOCK_ROWS = 1 << 16
K_ZERO_THRESHOLD = 1e-35
MISSING_ZERO, MISSING_NAN = 1, 2


@dataclass
class ValidTree:
    num_leaves: int
    split_feature: np.ndarray
    threshold: np.ndarray
    decision_type: np.ndarray
    left_child: np.ndarray
    right_child: np.ndarray
    leaf_value: np.ndarray
    cat_boundaries: np.ndarray
    cat_threshold: np.ndarray


def parse_model(text: str) -> List[ValidTree]:
    trees = []
    for block in text.split("\nTree=")[1:]:
        block = block.split("\nend of trees")[0]
        kv = {}
        for line in block.splitlines()[1:]:
            key, sep, val = line.partition("=")
            if sep:
                kv[key] = val

        def arr(key, dtype):
            return np.array(kv.get(key, "").split(), dtype=dtype)
        if int(kv.get("is_linear", "0")):
            raise ValueError("the reference walks constant-leaf trees only")
        trees.append(ValidTree(
            num_leaves=int(kv["num_leaves"]),
            split_feature=arr("split_feature", np.int64),
            threshold=arr("threshold", np.float64),
            decision_type=arr("decision_type", np.int64),
            left_child=arr("left_child", np.int64),
            right_child=arr("right_child", np.int64),
            leaf_value=arr("leaf_value", np.float64),
            cat_boundaries=arr("cat_boundaries", np.int64),
            cat_threshold=arr("cat_threshold", np.uint32)))
    return trees


def _go_left(t: ValidTree, node: np.ndarray, x: np.ndarray) -> np.ndarray:
    """One decision for each row at its node; ``x`` its raw value."""
    x = np.asarray(x, np.float64)
    dt = t.decision_type[node]
    isnan = np.isnan(x)
    missing = (dt >> 2) & 3
    v = np.where(isnan & (missing != MISSING_NAN), 0.0, x)
    at_default = np.where(missing == MISSING_ZERO,
                          np.abs(v) <= K_ZERO_THRESHOLD,
                          (missing == MISSING_NAN) & isnan)
    left = np.where(at_default, (dt & 2) > 0, v <= t.threshold[node])
    cat = (dt & 1) > 0
    if cat.any():
        slot = t.threshold[node[cat]].astype(np.int64)
        lo, hi = t.cat_boundaries[slot], t.cat_boundaries[slot + 1]
        xc = x[cat]
        ok = np.isfinite(xc) & (xc >= 0)
        iv = np.where(ok, xc, 0).astype(np.int64)
        ok &= iv < (hi - lo) * 32
        word = t.cat_threshold[np.where(ok, lo + iv // 32, 0)]
        left[cat] = ok & (((word >> (iv % 32).astype(np.uint32)) & 1) > 0)
    return left


def leaf_of_rows(t: ValidTree, x: np.ndarray) -> np.ndarray:
    """The leaf of every row of ``x`` in tree ``t``, in blocks of rows."""
    n = len(x)
    if t.num_leaves < 2:
        return np.zeros(n, np.int64)
    out = np.empty(n, np.int64)
    for lo in range(0, n, BLOCK_ROWS):
        xb = x[lo:lo + BLOCK_ROWS]
        node = np.zeros(len(xb), np.int64)
        live = np.arange(len(xb))
        while len(live):
            cur = node[live]
            left = _go_left(t, cur, xb[live, t.split_feature[cur]])
            node[live] = np.where(left, t.left_child[cur],
                                  t.right_child[cur])
            live = live[node[live] >= 0]
        out[lo:lo + len(xb)] = ~node
    return out


def leaves(trees: List[ValidTree], x: np.ndarray) -> np.ndarray:
    """``[trees, rows]``: the leaf each row reaches in each tree."""
    return np.stack([leaf_of_rows(t, x) for t in trees]) if trees \
        else np.zeros((0, len(x)), np.int64)


def scores_after(trees: List[ValidTree], leaf: np.ndarray) -> np.ndarray:
    """``[trees, rows]``: row r's float64 score after tree i, the sum
    of the values of the leaves ``leaf[:i + 1, r]``."""
    values = np.stack([t.leaf_value[leaf[i]] for i, t in enumerate(trees)])
    return np.cumsum(values, axis=0)


def auc_in_bf16(score: np.ndarray, y: np.ndarray) -> float:
    """``auc`` with the midranks rounded to bfloat16 and summed in
    bfloat16, pairwise, as an accelerator reduces."""
    pos = np.asarray(y) > 0
    _, inv, cnt = np.unique(np.asarray(score, np.float64),
                            return_inverse=True, return_counts=True)
    part = to_bf16((np.cumsum(cnt) - (cnt - 1) / 2.0)[inv][pos])
    while len(part) > 1:
        if len(part) % 2:
            part = np.append(part, 0.0)
        part = to_bf16(part[0::2] + part[1::2])
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    top = to_bf16(part[0] - to_bf16(n_pos * (n_pos + 1) / 2.0))
    return float(to_bf16(top / to_bf16(float(n_pos) * n_neg)))
