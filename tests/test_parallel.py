"""Distributed-learner equivalence tests on the virtual 8-device CPU mesh.

Mirrors the reference's distributed test strategy
(tests/distributed/_test_distributed.py + test_dask.py): run the SAME
training through each tree_learner and assert the distributed result matches
the serial one.  Collectives here are real XLA collectives over the forced
8-device host platform.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _make_binary(n=600, f=10, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f))
    logit = x[:, 0] * 1.5 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    return x, y


BASE_PARAMS = {
    "objective": "binary",
    "num_leaves": 15,
    "min_data_in_leaf": 5,
    "max_bin": 31,
    "learning_rate": 0.2,
    "verbosity": -1,
    "metric": "auc",
}


def _train_predict(extra, x, y, rounds=5):
    params = dict(BASE_PARAMS, **extra)
    ds = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
    bst = lgb.train(params, ds, num_boost_round=rounds)
    return bst.predict(x, raw_score=True)


@pytest.fixture(scope="module")
def problem():
    x, y = _make_binary()
    serial = _train_predict({"tree_learner": "serial"}, x, y)
    return x, y, serial


def _auc(y, s):
    order = np.argsort(s)
    r = np.empty_like(order, dtype=np.float64)
    r[order] = np.arange(len(s))
    pos = y > 0
    return ((r[pos].sum() - pos.sum() * (pos.sum() - 1) / 2)
            / (pos.sum() * (~pos).sum()))


def test_data_parallel_matches_serial(problem):
    x, y, serial = problem
    pred = _train_predict({"tree_learner": "data"}, x, y)
    # identical split decisions up to f32 reduction order
    np.testing.assert_allclose(pred, serial, rtol=1e-4, atol=5e-4)


def test_feature_parallel_matches_serial(problem):
    x, y, serial = problem
    pred = _train_predict({"tree_learner": "feature"}, x, y)
    np.testing.assert_allclose(pred, serial, rtol=1e-4, atol=5e-4)


def test_feature_parallel_hybrid_mesh(problem):
    x, y, serial = problem
    pred = _train_predict(
        {"tree_learner": "feature", "tpu_mesh_axes": "data:2,feature:4"},
        x, y)
    np.testing.assert_allclose(pred, serial, rtol=1e-4, atol=5e-4)


def test_voting_parallel_full_vote_matches_serial(problem):
    # top_k >= num_features: every feature is elected, voting == data
    x, y, serial = problem
    pred = _train_predict({"tree_learner": "voting", "top_k": 16}, x, y)
    np.testing.assert_allclose(pred, serial, rtol=1e-4, atol=5e-4)


def test_voting_parallel_small_k_quality(problem):
    # top_k=2 restricts comm; the model is approximate but must still learn
    x, y, serial = problem
    pred = _train_predict({"tree_learner": "voting", "top_k": 2}, x, y)
    assert _auc(y, pred) > 0.90
    assert _auc(y, serial) > 0.95


def test_feature_parallel_with_monotone(problem):
    # regression: constraint arrays must be sized to the feature-parallel
    # padding (8 column shards re-pad the feature axis)
    x, y, _ = problem
    mono = [1] + [0] * (x.shape[1] - 1)
    p1 = _train_predict(
        {"tree_learner": "serial", "monotone_constraints": mono}, x, y)
    p2 = _train_predict(
        {"tree_learner": "feature", "monotone_constraints": mono}, x, y)
    np.testing.assert_allclose(p2, p1, rtol=1e-4, atol=5e-4)


def test_voting_with_monotone_constraints(problem):
    # regression: per_feature_best_gain must receive the monotone array
    x, y, _ = problem
    mono = [1] + [0] * (x.shape[1] - 1)
    pred = _train_predict(
        {"tree_learner": "voting", "monotone_constraints": mono}, x, y)
    assert _auc(y, pred) > 0.85


def test_voting_with_feature_fraction(problem):
    # regression: the vote must respect the per-tree column-sampling mask
    x, y, _ = problem
    pred = _train_predict(
        {"tree_learner": "voting", "top_k": 3, "feature_fraction": 0.5},
        x, y)
    assert _auc(y, pred) > 0.85


def test_data_parallel_physical_matches_serial(problem, monkeypatch):
    """Mesh-physical fast path (per-shard streaming partition +
    comb-direct histograms inside shard_map, psum/psum_scatter merges):
    LGBM_TPU_PHYS=interpret forces the physical code path onto the CPU
    mesh; the result must match serial physical training."""
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    x, y, _ = problem
    serial = _train_predict({"tree_learner": "serial"}, x, y)
    pred = _train_predict({"tree_learner": "data"}, x, y)
    np.testing.assert_allclose(pred, serial, rtol=2e-4, atol=2e-4)


def test_data_parallel_physical_scatter_off(problem, monkeypatch):
    """Same with the reduce-scatter merge disabled (full psum path)."""
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    monkeypatch.setenv("LGBM_TPU_HIST_SCATTER", "0")
    x, y, _ = problem
    serial = _train_predict({"tree_learner": "serial"}, x, y)
    pred = _train_predict({"tree_learner": "data"}, x, y)
    np.testing.assert_allclose(pred, serial, rtol=2e-4, atol=2e-4)


def test_data_parallel_hlo_has_reduce_scatter():
    """The data-parallel learner must actually EMIT the reduce-scatter
    collective (the reference's Network::ReduceScatter histogram merge,
    data_parallel_tree_learner.cpp:185) — a silent fallback to psum
    would double ICI traffic without failing any equivalence test."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import SplitHyperParams
    from lightgbm_tpu.parallel.data_parallel import DataParallelGrower

    hp = SplitHyperParams(min_data_in_leaf=2)
    grower = DataParallelGrower(
        hp, num_leaves=7, padded_bins=64, rows_per_block=64)
    assert grower.hist_scatter
    n, f = 64 * grower.num_shards, 16
    args = (jnp.zeros((n, f), jnp.uint8), jnp.zeros(n), jnp.ones(n),
            jnp.ones(n), jnp.ones(f),
            jnp.full((f,), 8, jnp.int32), jnp.zeros(f, bool),
            jnp.zeros(f, bool), jnp.int32(0))
    txt = grower._sharded_grow.lower(*args).compile().as_text()
    assert "reduce-scatter" in txt, "psum_scatter missing from HLO"


def test_pad_features_to_shards_contract():
    """The lcm padding keeps BOTH contracts (histogram group multiple
    AND shard divisibility) at the minimal width — the ROADMAP-item-3
    fix for hist_scatter_psum_fallback, guarded statically by the
    analysis mesh configs (analysis/entries.py)."""
    from lightgbm_tpu.ops.device_data import pad_features_to_shards
    for f in (1, 5, 10, 28, 100, 250):
        for group in (8, 16):
            for shards in (1, 2, 3, 4, 8, 16):
                p = pad_features_to_shards(f, group, shards)
                assert p >= f
                assert p % group == 0
                assert shards <= 1 or p % shards == 0
                # minimality: one lcm step below would violate a
                # contract or undershoot f
                import math
                m = (group if shards <= 1
                     else group * shards // math.gcd(group, shards))
                assert p - m < f
    # the motivating case: f=28, group=8, 8 shards used to pad to 64
    # (group x shards granularity); the lcm padding ships 32
    assert pad_features_to_shards(28, 8, 8) == 32


def test_data_parallel_padded_fast_path(problem):
    """Feature counts that do NOT divide over 8 shards stay on the
    reduce-scatter fast path via the lcm padding: the
    hist_scatter_psum_fallback event must never fire on the padded
    path (ISSUE 8 satellite / acceptance)."""
    from lightgbm_tpu.obs import events as obs_events
    x, y = _make_binary(n=640, f=10, seed=3)   # 10 % 8 != 0
    params = dict(BASE_PARAMS, tree_learner="data")
    ds = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
    bst = lgb.Booster(params=params, train_set=ds)
    grower = bst._inner.grow
    assert grower.hist_scatter, "reduce-scatter did not engage"
    assert bst._inner.dd.f_log % grower.num_shards == 0
    before = obs_events.totals().get("hist_scatter_psum_fallback", 0)
    bst.update()
    after = obs_events.totals().get("hist_scatter_psum_fallback", 0)
    assert after == before == 0, (
        "psum fallback fired on the padded fast path")
