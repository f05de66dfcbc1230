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


# ---------------------------------------------------------------------
# the data-parallel learner against the benchmark's float64 reference
# (benchmarks/reference_mesh.py: no shards, all rows, tree 0 leaf by
# leaf), and what a traced mesh run says and dispatches (ISSUE 34)
# ---------------------------------------------------------------------
MESH_ROWS = 16384       # 8 shards x one 2,048-row block (PHYS_ROW_PAD): no padding


def _bench_reference():
    import os
    import sys
    bench = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import reference
    import reference_mesh
    return reference, reference_mesh


@pytest.mark.parametrize("features", [10, 124], ids=["1plane", "2planes"])
@pytest.mark.parametrize("scatter", ["1", "0"], ids=["scatter", "psum"])
def test_data_parallel_tree0_against_float64_reference(
        monkeypatch, scatter, features):
    """Tree 0 of ``tree_learner=data`` on the chip's route (per-shard
    comb, fused scan, merged histograms) against all rows walked and
    summed in float64: leaf counts exact, leaf values to float32
    rounding; both merges, and a comb of one and of two 128-lane
    planes."""
    reference, reference_mesh = _bench_reference()
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    monkeypatch.setenv("LGBM_TPU_HIST_SCATTER", scatter)
    x, y = _make_binary(n=MESH_ROWS, f=features, seed=11)
    params = dict(BASE_PARAMS, tree_learner="data")
    ds = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
    bst = lgb.train(params, ds, num_boost_round=1)
    r = bst._inner._routing
    assert (r.path, r.learner, r.n_shards) == ("physical", "data", 8)
    assert r.hist_merge == ("scatter" if scatter == "1" else "psum")
    text = bst.model_to_string()
    tree0 = reference.parse_model(text)[0]
    ref = reference_mesh.binary_leaf_sums(tree0, x, y)
    assert tree0.num_leaves == params["num_leaves"]
    assert reference_mesh.tree0_leaf_counts(text).tolist() \
        == ref.count.tolist()
    assert ref.count.sum() == MESH_ROWS
    want = ref.leaf_values(params["learning_rate"])
    np.testing.assert_allclose(tree0.leaf_value, want, rtol=0, atol=2e-5)


def test_shards_root_histograms_add_up_to_the_whole(problem):
    """The share test: the shards' local root histograms, summed in
    float64, are the reference's histogram of ALL rows; and the tree
    the data-parallel learner grows from them is the serial learner's."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import build_histogram
    _, reference_mesh = _bench_reference()
    x, y, _ = problem
    params = dict(BASE_PARAMS)
    ds = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
    trees = {}
    for learner in ("serial", "data"):
        bst = lgb.train(dict(params, tree_learner=learner), ds,
                        num_boost_round=1)
        t = bst._models[0]
        trees[learner] = (t.num_leaves, t.split_feature.tolist(),
                          t.threshold_bin.tolist())
    assert trees["data"] == trees["serial"]
    inner = bst._inner
    n_shards = inner.grow.num_shards
    bins = np.asarray(inner.dd.bins)[:len(y)]
    p = float(y.mean())
    vals = np.stack([p - y, np.full(len(y), p * (1 - p))], 1).astype(
        np.float32)
    pb = int(inner.dd.padded_bins)
    total = np.zeros((bins.shape[1], pb, 2), np.float64)
    for rows in np.array_split(np.arange(len(y)), n_shards):
        total += np.asarray(build_histogram(
            jnp.asarray(bins[rows]), jnp.asarray(vals[rows]),
            padded_bins=pb, rows_per_block=128), np.float64)
    for f in range(x.shape[1]):
        want = reference_mesh.root_histogram(bins[:, f], y, pb)
        np.testing.assert_allclose(total[f], want, rtol=0, atol=1e-4)


@pytest.fixture(scope="module")
def traced_mesh_run():
    """One booster on the chip's mesh route: two iterations untraced,
    then two with the tracer on.  Returns (the tracer's events of the
    traced two, what JAX built during them)."""
    import os

    # the library generation that is current NOW: other test files
    # purge and re-import lightgbm_tpu mid-session, and the grower
    # resolves its tracer at call time
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import tracer
    saved = {k: os.environ.get(k)
             for k in ("LGBM_TPU_PHYS", "LGBM_TPU_HIST_SCATTER")}
    os.environ["LGBM_TPU_PHYS"] = "interpret"
    os.environ.pop("LGBM_TPU_HIST_SCATTER", None)
    try:
        x, y = _make_binary(n=MESH_ROWS, f=10, seed=11)
        params = dict(BASE_PARAMS, tree_learner="data")
        ds = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(2):
            bst.update()
        float(np.asarray(bst._inner.train_score).sum())
        tracer.enable(None)
        try:
            for _ in range(2):
                bst.update()
            events = list(tracer.events)
        finally:
            tracer.disable()
            tracer.reset()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return events


def test_traced_mesh_run_builds_no_program_of_its_own(traced_mesh_run):
    """Turning the tracer on dispatches no extra program on the mesh
    route either: every program the two traced iterations ran had been
    built by the two untraced ones, so JAX lowered and compiled nothing
    (the tracer hears JAX's builds as ``jax::*`` events) - not for the
    tables of what the programs' instruction names mean either (ISSUE
    38, ``Program::table``, once a program in the first traced
    iteration): handed the dispatch's own arguments, ``lower`` and
    ``compile`` find its trace and its executable in JAX's caches.  The
    per-shard rows of the collective ledger come from the grow
    program's own output, not from a reduction dispatched for them."""
    built = [e for e in traced_mesh_run if e["name"].startswith("jax::")]
    assert [e["name"] for e in built
            if e["name"] != "jax::trace"] == []
    assert {e["args"].get("parent") for e in built} <= {"Program::table"}
    tables = [e for e in traced_mesh_run if e["name"] == "Program::table"]
    assert sorted(e["args"]["program"] for e in tables) == [
        "gradients", "grow", "score"]
    second = [e["ts"] for e in traced_mesh_run
              if e["name"] == "GBDT::TrainOneIter"][-1]
    assert all(e["ts"] < second for e in tables)
    ledger = [e for e in traced_mesh_run if e["name"] == "collective"]
    assert len(ledger) == 2 and all(
        e["args"]["skew_max"] == e["args"]["skew_min"] == MESH_ROWS / 8
        for e in ledger)


def test_tree_grow_span_says_what_the_mesh_did(traced_mesh_run):
    grows = [e["args"] for e in traced_mesh_run
             if e["name"] == "Tree::grow"]
    assert len(grows) == 2
    for a in grows:
        assert a["shards"] == 8 and a["hist_merge"] == "scatter"
        assert a["merges"] == a["splits"] + 1
        assert a["merge_bytes"] > 0
        assert len(a["shard_rows_partitioned"]) == 8
        # no padding rows at this size, so the shards' own counts are
        # the tree's row visits, split eight ways: exactly, where
        # rows_partitioned is made of the tree's internal_count, the
        # reference's hessian-derived estimate (off by under 1% here)
        assert sum(a["shard_rows_partitioned"]) == pytest.approx(
            a["rows_partitioned"], rel=0.02)
        assert a["shard_rows_max"] == max(a["shard_rows_partitioned"])
