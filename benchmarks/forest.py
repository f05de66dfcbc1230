"""A serving forest from a seed, as LightGBM model text.

The predict cells do not train their model (500 iterations of set-up in
every run): they make one of the reference experiment's size, 500 trees
x 255 leaves over 28 features, from ``--seed``.  Each tree is grown on a
seeded 4,096-row sample of ``higgs_data``: the leaf to split is drawn
with probability proportional to its row count among leaves with at
least two rows, the feature uniformly, the threshold at a uniform
0.1-0.9 quantile of the leaf's rows snapped up to one of the feature's
254 bin upper bounds (quantiles of a seeded 100k-row sample) - a
``max_bin=255`` model has at most 254 distinct thresholds a feature, and
the serving forest stores ``threshold_bin`` in a byte.  Leaf values are
``0.1 * N(0, 0.1)``.  Trees are independent streams
``default_rng([seed, 2, tree])``; the 500 take ~3 s on one thread (more
threads only fight over the interpreter lock).
"""
from __future__ import annotations

import numpy as np

import higgs_data

SAMPLE_ROWS = 4096
BOUND_ROWS = 100_000
MAX_BOUNDS = 254


def bin_upper_bounds(seed: int) -> np.ndarray:
    """[F, 254] float64, every value exact in float32."""
    x, _, _ = higgs_data.make_higgs_like(BOUND_ROWS, seed=seed)
    qs = np.linspace(0.0, 1.0, MAX_BOUNDS + 2)[1:-1]
    b = np.quantile(x, qs, axis=0).T.astype(np.float32)
    return np.sort(b, axis=1).astype(np.float64)


def grow_tree(xs: np.ndarray, bounds: np.ndarray, leaves: int,
              rng: np.random.Generator) -> dict:
    n, n_feat = xs.shape
    u = rng.random((leaves - 1, 3))
    cols = np.ascontiguousarray(xs.T)
    rows_of = [np.arange(n)]            # by leaf index
    size = np.zeros(leaves, np.int64)
    size[0] = n
    feat, thr, left, right, counts = [], [], [], [], []
    slot = {0: None}                    # leaf -> (parent node, side)
    for s in range(leaves - 1):
        cum = np.cumsum(np.where(size >= 2, size, 0))
        if not cum[-1]:
            break
        leaf = int(np.searchsorted(cum, u[s, 0] * cum[-1], side="right"))
        rows = rows_of[leaf]
        f = int(u[s, 1] * n_feat)
        vals = cols[f][rows]
        k = int((0.1 + 0.8 * u[s, 2]) * (len(rows) - 1))
        raw = np.partition(vals, k)[k]
        t = bounds[f, min(int(np.searchsorted(bounds[f], raw, "left")),
                          bounds.shape[1] - 1)]
        go_left = vals <= t
        node = len(feat)
        feat.append(f)
        thr.append(t)
        counts.append(len(rows))
        new_leaf = len(rows_of)
        left.append(~leaf)              # the left child keeps the leaf
        right.append(~new_leaf)
        if slot[leaf] is not None:
            parent, side = slot[leaf]
            (left if side == 0 else right)[parent] = node
        slot[leaf], slot[new_leaf] = (node, 0), (node, 1)
        rows_of[leaf] = rows[go_left]
        rows_of.append(rows[~go_left])
        size[leaf], size[new_leaf] = len(rows_of[leaf]), len(rows_of[-1])
    n_leaves = len(rows_of)
    return {
        "num_leaves": n_leaves,
        "split_feature": np.array(feat, np.int64),
        "threshold": np.array(thr, np.float64),
        "left_child": np.array(left, np.int64),
        "right_child": np.array(right, np.int64),
        "internal_count": np.array(counts, np.int64),
        "leaf_count": np.array([len(r) for r in rows_of], np.int64),
        "leaf_value": 0.1 * rng.normal(0.0, 0.1, size=n_leaves),
    }


def _fmt(a) -> str:
    return " ".join(map(repr, np.asarray(a).tolist()))


def tree_text(index: int, t: dict) -> str:
    ni = t["num_leaves"] - 1
    lines = [f"Tree={index}", f"num_leaves={t['num_leaves']}", "num_cat=0"]
    if ni:
        lines += [
            "split_feature=" + _fmt(t["split_feature"]),
            "split_gain=" + _fmt(np.ones(ni)),
            "threshold=" + _fmt(t["threshold"]),
            "decision_type=" + _fmt(np.zeros(ni, np.int64)),
            "left_child=" + _fmt(t["left_child"]),
            "right_child=" + _fmt(t["right_child"]),
        ]
    lines += ["leaf_value=" + _fmt(t["leaf_value"])]
    if ni:
        lines += [
            "leaf_weight=" + _fmt(t["leaf_count"].astype(np.float64) / 4),
            "leaf_count=" + _fmt(t["leaf_count"]),
            "internal_value=" + _fmt(np.zeros(ni)),
            "internal_weight=" + _fmt(
                t["internal_count"].astype(np.float64) / 4),
            "internal_count=" + _fmt(t["internal_count"]),
        ]
    lines += ["is_linear=0", "shrinkage=0.1", "", ""]
    return "\n".join(lines)


def make_forest(seed: int, trees: int = 500, leaves: int = 255):
    """The forest as LightGBM model text."""
    xs, _, _ = higgs_data.make_higgs_like(SAMPLE_ROWS, seed=seed)
    xs = xs.astype(np.float64)
    bounds = bin_upper_bounds(seed)
    n_feat = xs.shape[1]

    grown = [grow_tree(xs, bounds, leaves,
                       np.random.default_rng([seed, 2, i]))
             for i in range(trees)]
    blocks = [tree_text(i, t) for i, t in enumerate(grown)]
    names = " ".join(f"Column_{j}" for j in range(n_feat))
    infos = " ".join(f"[{lo!r}:{hi!r}]" for lo, hi in
                     zip(xs.min(axis=0).tolist(), xs.max(axis=0).tolist()))
    head = ["tree", "version=v4", "num_class=1", "num_tree_per_iteration=1",
            "label_index=0", f"max_feature_idx={n_feat - 1}",
            "objective=binary sigmoid:1", "feature_names=" + names,
            "feature_infos=" + infos,
            "tree_sizes=" + " ".join(str(len(b)) for b in blocks), "", ""]
    tail = ["end of trees", "", "feature_importances:", "", "parameters:",
            "[objective: binary]", "end of parameters", ""]
    return "\n".join(head) + "".join(blocks) + "\n".join(tail)
