"""The end-of-tree hand-off (ISSUE 33): per-row leaf ids and leaf values
come from compares against the leaf-sized table (ops/leaf_lookup.py),
not from a gather into it; bit for bit what the gather gave.

``SELECT_MAX = 0`` sends every table down the helper's fallback, which
is the formula this replaced (``argsort`` + ``jnp.repeat`` + ``take``):
the end-to-end cases train once on each side of it.
"""
import os
import sys

import numpy as np
import pytest

from conftest import restore_env_knobs as _restore_env
from conftest import save_env_knobs as _save_env


def _bits(a):
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("length", [1, 2, 255, 256, 257])
def test_leaf_table_lookup_is_the_gather(length, dtype):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.leaf_lookup import SELECT_MAX, leaf_table_lookup
    rng = np.random.default_rng(length)
    if dtype == "int32":
        table = rng.integers(-2**31, 2**31, size=length).astype(np.int32)
    else:
        table = rng.normal(size=length).astype(np.float32)
        table[rng.integers(length)] = -0.0      # a float sum would lose it
    idx = rng.integers(0, length, size=1000).astype(np.int32)
    idx[:length] = np.arange(length)[:1000]     # every entry is asked for
    got = jax.jit(leaf_table_lookup)(jnp.asarray(table), jnp.asarray(idx))
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(_bits(got), _bits(table[idx]))
    # the form follows the table's static length
    text = str(jax.make_jaxpr(leaf_table_lookup)(
        jnp.asarray(table), jnp.asarray(idx)))
    assert ("gather" in text) == (length > SELECT_MAX)


def _segments(n, rows_by_leaf, order):
    """A segment table: ``rows_by_leaf[l]`` rows for leaf l, the live
    leaves laid over [0, n) in ``order``; empty slots begin at 0, as
    the grow state leaves them."""
    seg = np.zeros((len(rows_by_leaf), 2), np.int32)
    at = 0
    for leaf in order:
        seg[leaf] = (at, rows_by_leaf[leaf])
        at += rows_by_leaf[leaf]
    assert at == n
    return seg


def _segment_cases():
    rng = np.random.default_rng(5)
    n = 5 * 512 + 77                    # not a multiple of the block
    cuts = np.sort(rng.choice(np.arange(1, n), size=6, replace=False))
    rows7 = np.diff(np.concatenate([[0], cuts, [n]])).tolist()
    return {
        # num_leaves == 1: one segment of n rows, 254 unused slots
        "stump": (n, _segments(n, [n] + [0] * 254, [0])),
        # unused slots (begin 0, rows 0) share their begin with the
        # live leaf at 0, which is NOT leaf 0
        "unused_slots": (n, _segments(
            n, rows7 + [0] * 8, [3, 0, 6, 1, 5, 2, 4])),
        "one_row_leaf": (n, _segments(
            n, [1, n - 3, 1, 1], [1, 0, 3, 2])),
        "out_of_leaf_order": (n, _segments(
            n, rows7, [6, 5, 4, 3, 2, 1, 0])),
        "full_255": (255 * 9, _segments(
            255 * 9, [9] * 255, rng.permutation(255).tolist())),
        # past SELECT_MAX: the expand-by-span fallback
        "long_table_300": (300 * 3 + 5, _segments(
            300 * 3 + 5, [3] * 299 + [8], rng.permutation(300).tolist())),
    }


@pytest.mark.parametrize("case", sorted(_segment_cases()))
def test_leaf_of_position_is_the_sort_and_repeat(case):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.leaf_lookup import leaf_of_position
    n, seg = _segment_cases()[case]
    L = seg.shape[0]
    lv = np.random.default_rng(1).normal(size=L).astype(np.float32)
    lv[0] = -0.0

    def old(seg, lv):
        order = jnp.argsort(seg[:, 0]).astype(jnp.int32)
        leaf_of_pos = jnp.repeat(order, seg[order, 1],
                                 total_repeat_length=n)
        return leaf_of_pos, jnp.take(lv, leaf_of_pos)

    want = jax.jit(old)(jnp.asarray(seg), jnp.asarray(lv))
    got = jax.jit(lambda s, t: leaf_of_position(s, n, (t,)))(
        jnp.asarray(seg), jnp.asarray(lv))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(_bits(got[1]), _bits(want[1]))
    alone, = jax.jit(lambda s: leaf_of_position(s, n))(jnp.asarray(seg))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(want[0]))
    # and the plain reading: position p lies in its leaf's segment
    leaf = np.asarray(got[0])
    p = np.arange(n)
    assert ((seg[leaf, 0] <= p) & (p < seg[leaf, 0] + seg[leaf, 1])).all()


# ---------------------------------------------------------------------
# end to end: the select form trains the trees and the scores of the
# formula it replaced, on every route and at both comb widths
# ---------------------------------------------------------------------
_ROUTES = {
    "stream": {"LGBM_TPU_PHYS": "interpret"},
    "physical": {"LGBM_TPU_PHYS": "interpret", "LGBM_TPU_STREAM": "0"},
    "row_order": {"LGBM_TPU_PHYS": "0"},
}


def _train(route, f, select_max, rounds=5, n=1500):
    """The parity matrix's data (tests/test_physical.py) through
    ``lgb.train``; returns (model text, train_score bytes, route facts).
    ``f`` past 122 makes the comb line two 128-lane planes."""
    saved = _save_env()
    os.environ.pop("LGBM_TPU_STREAM", None)
    os.environ.update(_ROUTES[route])
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        from lightgbm_tpu.ops import leaf_lookup
        if select_max is not None:
            leaf_lookup.SELECT_MAX = select_max
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        y = (np.nan_to_num(x[:, 0])
             + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]) > 0).astype(
                 np.float32)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1}, lgb.Dataset(x, label=y),
                        num_boost_round=rounds)
        inner = bst._inner
        facts = (bool(inner._stream_grad),
                 getattr(inner.grow, "_C", None))
        return (bst.model_to_string(),
                np.asarray(inner.train_score).tobytes(), facts)
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_trees_and_scores_of_the_gather_formula(route, planes):
    f = {1: 6, 2: 130}[planes]
    new = _train(route, f, None)
    old = _train(route, f, 0)
    assert new[2] == old[2] == (
        route == "stream", None if route == "row_order" else 128 * planes)
    assert "Tree=4" in new[0] and "num_leaves=15" in new[0]
    assert new[0] == old[0], "model_to_string() differs"
    assert new[1] == old[1], "train_score differs bitwise"
