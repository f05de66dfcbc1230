"""The ranking objectives against the benchmark's plain reference.

``benchmarks/reference_rank.py`` is float64 numpy written from the
header (``rank_objective.hpp``) and imports nothing from the program;
the program buckets queries by length, sorts, and computes the pairs
``[min(trunc, width), width]`` in float32 (``objective/rank.py``).

Tolerance (``REL``): the largest difference over a query's rows as a
share of the query's largest value.  A float32 pair term carries about
1e-6 of rounding (one exp, two divisions) and a row sums at most a few
hundred of them here, so 2e-5 has an order of magnitude of room; pair
arithmetic in bf16 reads 4e-3 and a dropped ``lambdarank_norm`` factor
tens of percent, and both fail it.
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmarks"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import reference_rank  # noqa: E402

from lightgbm_tpu.config import Config  # noqa: E402
from lightgbm_tpu.objective.rank import (LambdarankNDCG, RankXENDCG,  # noqa: E402
                                         bucket_widths)

REL = 2e-5
# 1 and 2; 8 | 9 and 16 | 17 and 32 | 33 sit at a bucket's edge and just
# past it; 70 is inside a bucket; 130 is past the library's truncation
SIZES = np.array([1, 2, 8, 9, 16, 17, 32, 33, 70, 3, 130, 5, 1, 64])


def _data(seed, sizes=SIZES, tied=False, equal_labels=False):
    rng = np.random.default_rng(seed)
    n = int(sizes.sum())
    qb = np.concatenate([[0], np.cumsum(sizes)])
    label = rng.integers(0, 4, n).astype(np.float32)
    label[rng.integers(0, n, 3)] = 4.0           # grade-4 documents
    if equal_labels:
        label[:] = 2.0
    score = rng.standard_normal(n).astype(np.float32)
    if tied:
        # whole queries tied, and runs of ties inside the others
        score[qb[4]:qb[5]] = 0.25
        score[qb[10]:qb[10] + 40] = -1.0
        score[::3] = np.float32(0.5)
    return qb, label, score


def _program(params, qb, label, weight=None, cls=LambdarankNDCG):
    obj = cls(Config.from_params(params))
    obj.init(SimpleNamespace(label=label, weight=weight,
                             query_boundaries=qb), len(label))
    return obj


def _worst_rel(got, want, qb):
    worst = 0.0
    for q in range(len(qb) - 1):
        g, w = got[qb[q]:qb[q + 1]], want[qb[q]:qb[q + 1]]
        top = np.max(np.abs(w))
        err = np.max(np.abs(g - w))
        worst = max(worst, err / top if top > 0 else
                    (0.0 if err == 0 else np.inf))
    return worst


CASES = {
    "default": dict(),
    "tied_scores": dict(tied=True),
    "equal_labels": dict(equal_labels=True),
    "trunc_below_length": dict(trunc=5),
    "trunc_above_length": dict(trunc=1000),
    "norm_off": dict(norm=False),
    "norm_off_tied": dict(norm=False, tied=True),
    "weights": dict(weights=True),
    "sigmoid_2": dict(sigmoid=2.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lambdarank_matches_the_float64_loops(case):
    kw = dict(CASES[case])
    trunc, norm = kw.pop("trunc", 30), kw.pop("norm", True)
    sigmoid, weights = kw.pop("sigmoid", 1.0), kw.pop("weights", False)
    qb, label, score = _data(11, **kw)
    weight = (np.random.default_rng(5).random(len(label)).astype(np.float32)
              + 0.5 if weights else None)
    obj = _program({"objective": "lambdarank", "lambdarank_norm": norm,
                    "lambdarank_truncation_level": trunc,
                    "sigmoid": sigmoid}, qb, label, weight)
    grad, hess = (np.asarray(a, np.float64) for a in
                  jax.jit(obj.get_gradients)(jnp.asarray(score)))
    g_ref, h_ref = reference_rank.lambdarank(
        score, label, qb, weight=weight, sigmoid=sigmoid,
        truncation_level=trunc, norm=norm, loops=True)
    assert np.abs(g_ref).max() > 0 or case == "equal_labels"
    if case == "equal_labels":
        assert not grad.any() and not hess.any() and not g_ref.any()
    assert _worst_rel(grad, g_ref, qb) <= REL
    assert _worst_rel(hess, h_ref, qb) <= REL
    # a query of one document, and the reference's own two forms
    assert grad[0] == 0.0 and hess[0] == 0.0
    g_np, h_np = reference_rank.lambdarank(
        score, label, qb, weight=weight, sigmoid=sigmoid,
        truncation_level=trunc, norm=norm)
    np.testing.assert_allclose(g_np, g_ref, rtol=0, atol=1e-12)
    np.testing.assert_allclose(h_np, h_ref, rtol=0, atol=1e-12)


def test_xendcg_buckets_match_the_formula_query_by_query():
    """The bucketed XE-NDCG against rank_objective.hpp's formula in
    numpy, one query at a time, with the program's own uniform draw (one
    number a row, from ``objective_seed`` + the iteration)."""
    qb, label, score = _data(3)
    n = len(label)
    obj = _program({"objective": "rank_xendcg", "objective_seed": 9}, qb,
                   label, cls=RankXENDCG)
    for it in range(2):
        u = np.asarray(jax.random.uniform(jax.random.PRNGKey(9 + it), (n,)),
                       np.float64)
        grad, hess = (np.asarray(a, np.float64)
                      for a in obj.get_gradients(jnp.asarray(score)))
        g_ref, h_ref = np.zeros(n), np.zeros(n)
        for q in range(len(qb) - 1):
            lo, hi = qb[q], qb[q + 1]
            if hi - lo <= 1:
                continue
            s = score[lo:hi].astype(np.float64)
            rho = np.exp(s - s.max())
            rho /= rho.sum()
            phi = 2.0 ** label[lo:hi].astype(np.float64) - u[lo:hi]
            inv = 1.0 / max(phi.sum(), 1e-15)
            t1 = -phi * inv + rho
            p1 = t1 / (1.0 - rho)
            t2 = rho * (p1.sum() - p1)
            p2 = t2 / (1.0 - rho)
            g_ref[lo:hi] = t1 + t2 + rho * (p2.sum() - p2)
            h_ref[lo:hi] = rho * (1.0 - rho)
        assert _worst_rel(grad, g_ref, qb) <= 1e-4
        assert _worst_rel(hess, h_ref, qb) <= 1e-4


def test_gradient_program_holds_no_padded_gather_and_no_scatter():
    """At ragged lengths the traced ``get_gradients`` reads each bucket's
    padded slots in (under twice the rows in all) and leaves through ONE
    row-sized gather (the old form gathered queries x longest indexes,
    three times over); nothing scatters, nothing loops over queries."""
    from lightgbm_tpu.analysis.jaxpr_tools import walk_eqns
    sizes = np.concatenate([SIZES, [400]])
    qb, label, score = _data(1, sizes=sizes)
    n, longest = len(label), int(sizes.max())
    assert len(sizes) * longest > 4 * n
    obj = _program({"objective": "lambdarank"}, qb, label)
    eqns = list(walk_eqns(jax.make_jaxpr(obj.get_gradients)(
        jnp.asarray(score))))
    names = {e.primitive.name for e in eqns}
    assert not {n_ for n_ in names if n_.startswith("scatter")}, names
    assert "while" not in names and "scan" not in names, names
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    indexes = [int(np.prod(e.invars[1].aval.shape[:-1])) for e in gathers]
    assert indexes.count(n) == 1                 # the one way out
    indexes.remove(n)
    assert sum(indexes) < 2 * n < len(sizes) * longest, indexes
    # no array is larger than the buckets' pair slots, which are nowhere
    # near queries x longest^2
    slots = obj.span_args()["pair_slots"]
    assert slots * 8 < len(sizes) * longest ** 2
    for e in eqns:
        for v in e.outvars:
            shape = getattr(v.aval, "shape", ())
            assert int(np.prod(shape)) <= slots, (e.primitive.name, shape)
    src = open(os.path.join(os.path.dirname(__file__), os.pardir,
                            "lightgbm_tpu", "objective", "rank.py")).read()
    assert "lax.map" not in src.split('"""', 2)[2]


def test_span_args_follow_the_pairs_at_the_ms_ltr_lengths():
    """``init``'s counters at the published shape: the slots the buckets
    compute are within 4x of the pairs LightGBM visits (sum of
    min(trunc, G) x G), nowhere near queries x longest^2."""
    import msltr_data
    train, _ = msltr_data.query_lengths()
    qb = np.concatenate([[0], np.cumsum(train)])
    label = np.zeros(int(train.sum()), np.float32)
    label[::7] = 1.0
    obj = _program({"objective": "lambdarank"}, qb, label)
    c = obj.span_args()
    assert c["queries"] == 18_919 and c["buckets"] == len(
        bucket_widths(1_251)) == 9
    assert c["pairs_visited"] == int(np.sum(np.minimum(30, train) * train))
    assert c["pairs_visited"] <= c["pair_slots"] <= 4 * c["pairs_visited"]
    assert c["pair_slots"] * 100 < 18_919 * 1_251 ** 2


def test_lambdarank_trains_the_same_trees_on_both_paths():
    """~200 generated queries through ``lgb.train``: the physical path
    (kernels in interpret mode) and the row_order path grow the same
    trees, and the library's ndcg@10 is the reference's."""
    import lightgbm_tpu as lgb
    import msltr_data
    import reference
    from conftest import restore_env_knobs, save_env_knobs
    x, grade, _, train, _ = msltr_data.make_msltr_like(
        4, n_train=200, n_held=1, longest=60, total_rows=None)
    n = int(train.sum())
    x, grade = x[:n, :20], grade[:n]
    params = {"objective": "lambdarank", "num_leaves": 15, "max_bin": 63,
              "metric": "ndcg", "ndcg_eval_at": [10], "verbosity": -1,
              "min_data_in_leaf": 5}
    saved = save_env_knobs()
    trees, evals, routes = {}, {}, {}
    try:
        for phys in ("interpret", "0"):
            os.environ["LGBM_TPU_PHYS"] = phys
            d = lgb.Dataset(x, label=grade, group=train,
                            params={"max_bin": 63})
            res = {}
            bst = lgb.train(params, d, num_boost_round=5, valid_sets=[d],
                            valid_names=["train"],
                            callbacks=[lgb.record_evaluation(res)])
            routes[phys] = bst._inner._routing
            trees[phys] = reference.parse_model(bst.model_to_string())
            (of_train,) = res.values()
            evals[phys] = (of_train["ndcg@10"][-1],
                           bst.predict(x, raw_score=True))
    finally:
        restore_env_knobs(saved)
    assert routes["interpret"].path == "physical"
    assert list(routes["interpret"].reasons) == ["objective_not_streamable"]
    assert routes["0"].path == "row_order"
    assert len(trees["interpret"]) == len(trees["0"]) == 5
    for a, b in zip(trees["interpret"], trees["0"]):
        assert a.num_leaves == b.num_leaves > 1
        np.testing.assert_array_equal(a.split_feature, b.split_feature)
        np.testing.assert_allclose(a.threshold, b.threshold, rtol=1e-12)
        # float32 sums in another row order, tests/test_physical.py's
        # tolerance between the two paths
        np.testing.assert_allclose(a.leaf_value, b.leaf_value, rtol=2e-3,
                                   atol=1e-4)
    qb = np.concatenate([[0], np.cumsum(train)])
    for phys, (ndcg, raw) in evals.items():
        assert abs(ndcg - reference_rank.ndcg_at(10, raw, grade, qb)) < 1e-6
