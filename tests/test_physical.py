"""Physical row-partition mode: equivalence with the row_order path.

The Pallas streaming partition kernel only compiles on TPU; on CPU the
mode runs its pure-XLA reference implementation
(ops/pallas/partition_kernel.py), which these tests exercise via
``LGBM_TPU_PHYS=interpret``.  On TPU the compiled kernel was verified to
produce bit-identical trees to the f32 row_order path.
"""
import os
import sys

import numpy as np
import pytest


from conftest import restore_env_knobs as _restore_env
from conftest import save_env_knobs as _save_env


def _fresh_train(env_phys, n=3000, f=6, rounds=4, **params):
    saved = _save_env()
    os.environ["LGBM_TPU_PHYS"] = env_phys
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        y = (np.nan_to_num(x[:, 0])
             + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]) > 0).astype(
                 np.float32)
        p = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
        p.update(params)
        ds = lgb.Dataset(x, label=y)
        bst = lgb.train(p, ds, num_boost_round=rounds)
        trees = [(int(t.num_leaves),
                  t.split_feature[:int(t.num_leaves) - 1].tolist(),
                  t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
                  np.asarray(t.leaf_value[:int(t.num_leaves)]))
                 for t in bst._models]
        return bst.predict(x), trees
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_trees_close(t_ref, t_phy):
    assert len(t_ref) == len(t_phy)
    for i, (a, b) in enumerate(zip(t_ref, t_phy)):
        assert a[0] == b[0], f"tree {i} num_leaves {a[0]} != {b[0]}"
        assert a[1] == b[1], f"tree {i} split features differ"
        assert a[2] == b[2], f"tree {i} thresholds differ"
        # leaf values accumulate histogram sums in a different row order
        # (rows are physically permuted), so allow f32 rounding drift
        np.testing.assert_allclose(a[3], b[3], rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("params", [
    {},
    {"bagging_fraction": 0.7, "bagging_freq": 1},
    {"lambda_l1": 0.5, "lambda_l2": 2.0, "min_data_in_leaf": 40},
])
def test_physical_matches_row_order(params):
    p_ref, t_ref = _fresh_train("0", **params)
    p_phy, t_phy = _fresh_train("interpret", **params)
    _assert_trees_close(t_ref, t_phy)
    np.testing.assert_allclose(p_ref, p_phy, rtol=5e-3, atol=1e-3)


def _train_scheme(partition, fused, learner, monotone, n=1500, f=6,
                  rounds=2, phys="interpret"):
    """Train through the REAL partition kernels (Pallas interpreter,
    compiled row order) under one (scheme, fused, learner, monotone)
    cell of the ISSUE-3 equivalence matrix; returns the trees, leaf
    values as (bytes, array).  ``f`` is the feature count: past 122 the comb
    line is two 128-lane planes.  ``phys="0"`` trains the same
    configuration on the ``row_order`` path, the plain reference."""
    env = {"LGBM_TPU_PHYS": phys,
           "LGBM_TPU_PART_INTERP": "kernel",
           "LGBM_TPU_PARTITION": partition,
           "LGBM_TPU_FUSED": fused}
    saved = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        if v == "":
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        y = (np.nan_to_num(x[:, 0])
             + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]) > 0).astype(
                 np.float32)
        p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        if learner == "data":
            p.update({"tree_learner": "data", "max_bin": 31,
                      "min_data_in_leaf": 5})
        if monotone:
            p["monotone_constraints"] = monotone
        ds = lgb.Dataset(x, label=y,
                         params={"max_bin": p.get("max_bin", 255)})
        bst = lgb.train(p, ds, num_boost_round=rounds)
        if phys != "0":
            g = bst._inner.grow
            assert (g._pieces.C if learner == "data" else g._C) \
                == 128 * (1 + (f > 122))
        return [(int(t.num_leaves),
                 t.split_feature[:int(t.num_leaves) - 1].tolist(),
                 t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
                 np.asarray(t.leaf_value).tobytes(),
                 np.asarray(t.leaf_value[:int(t.num_leaves)]))
                for t in bst._models]
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


@pytest.mark.parametrize("fused,learner,monotone", [
    ("1", "serial", None),
    ("0", "serial", None),
    ("1", "serial", [1, -1, 0, 0, 0, 0]),
    ("0", "serial", [1, -1, 0, 0, 0, 0]),
    ("1", "data", None),
    ("0", "data", None),
])
def test_partition_scheme_equivalence_matrix(fused, learner, monotone):
    """ISSUE-3 acceptance: LGBM_TPU_PARTITION=permute grows trees
    BIT-IDENTICAL to matmul — through the real kernel bodies (Pallas
    interpreter), across fused on/off, serial and 8-shard data-parallel
    mesh, monotone constraints on/off.  The permute packing reproduces
    the matmul scheme's exact row layout (reversed right segments), so
    every downstream float accumulates in the same order."""
    t_p = _train_scheme("permute", fused, learner, monotone)
    t_m = _train_scheme("matmul", fused, learner, monotone)
    assert len(t_p) == len(t_m)
    for i, (a, b) in enumerate(zip(t_p, t_m)):
        assert a[0] == b[0], f"tree {i}: num_leaves {a[0]} != {b[0]}"
        assert a[1] == b[1], f"tree {i}: split features differ"
        assert a[2] == b[2], f"tree {i}: thresholds differ"
        assert a[3] == b[3], f"tree {i}: leaf values differ bitwise"


_WIDE_F = 130       # + 6 value / row-id columns: a 256-lane comb line
_MONO_W = [1, -1] + [0] * (_WIDE_F - 2)
_WIDE = {}          # trained cells of the width matrix, this process


def _train_wide(partition, fused, learner, monotone, phys="interpret"):
    key = (partition, fused, learner, bool(monotone), phys)
    if key not in _WIDE:
        _WIDE[key] = _train_scheme(partition, fused, learner, monotone,
                                   f=_WIDE_F, phys=phys)
    return _WIDE[key]


@pytest.mark.parametrize("partition,fused,learner,monotone", [
    ("permute", "1", "serial", None),
    ("permute", "0", "serial", _MONO_W),
    ("matmul", "1", "serial", None),
    ("matmul", "0", "serial", None),
    ("permute", "1", "serial", _MONO_W),
    ("permute", "1", "data", None),
    ("permute", "0", "data", None),
    ("matmul", "1", "data", None),
], ids=lambda v: "mono" if isinstance(v, list) else str(v))
def test_width_parity_matrix(partition, fused, learner, monotone):
    """The comb at the width ``msltr-train-2m`` runs: 130 feature
    columns make a line of two 128-lane planes, with the value and
    row-id columns in the second.  Trained through the real kernel
    bodies (Pallas interpreter, LGBM_TPU_PART_INTERP=kernel), across
    permute/matmul, fused on/off, serial and 8-shard data-parallel
    mesh, monotone on/off, every cell grows the trees the ``row_order``
    path grows (structure equal, leaf values to f32 rounding), and the
    matmul cells grow BITWISE the trees of the permute compaction."""
    got = _train_wide(partition, fused, learner, monotone)
    ref = _train_wide(partition, fused, learner, monotone, phys="0")
    _assert_trees_close([t[:3] + t[4:] for t in ref],
                        [t[:3] + t[4:] for t in got])
    assert any(t[0] > 1 for t in got)
    if partition == "matmul":
        perm = _train_wide("permute", fused, learner, monotone)
        assert [t[:4] for t in got] == [t[:4] for t in perm]


def _train_counters(tmp_path, n=1200, f=_WIDE_F, rounds=2):
    """Serial physical train with the tracer live; returns (per-model
    structure, device counter totals)."""
    trace = os.path.join(str(tmp_path), "ctr.jsonl")
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel",
           "LGBM_TPU_TRACE": trace}
    saved = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import counters as obs_counters
        rng = np.random.default_rng(4)
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = (x[:, 0] - 0.4 * x[:, 1] > 0).astype(np.float32)
        ds = lgb.Dataset(x, label=y)
        bst = lgb.Booster(params={"objective": "binary",
                                  "num_leaves": 7, "verbosity": -1},
                          train_set=ds)
        for _ in range(rounds):
            bst.update()
        bst._inner._flush_pending()
        models = bst._inner.models
        splits = sum(int(t.num_leaves) - 1 for t in models)
        rows_part = sum(int(np.asarray(t.internal_count).sum())
                        for t in models if int(t.num_leaves) > 1)
        assert bst._inner.grow._C == 256
        return (splits, rows_part), obs_counters.totals()
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def test_two_plane_counters_count_rows(tmp_path):
    """Device counters at two planes count ROWS (a plane-major comb
    holds each row in two matrices): rows_partitioned equals the
    models' internal_count sum exactly, splits the models' splits."""
    (splits, rows_part), tot = _train_counters(tmp_path)
    assert splits > 0 and rows_part > 0
    assert int(tot["splits"]) == splits
    assert int(tot["rows_partitioned"]) == rows_part


def test_physical_categorical_and_forced():
    # categorical split routing goes through the partition predicate
    for m in [k for k in list(sys.modules) if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]
    saved = {"LGBM_TPU_PHYS": os.environ.get("LGBM_TPU_PHYS")}
    os.environ["LGBM_TPU_PHYS"] = "interpret"
    try:
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(1)
        n = 2000
        xc = rng.integers(0, 8, size=n)
        x = np.stack([xc.astype(np.float32),
                      rng.normal(size=n).astype(np.float32)], axis=1)
        y = (np.isin(xc, [1, 3, 5])).astype(np.float32)
        ds = lgb.Dataset(x, label=y, categorical_feature=[0])
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": -1, "min_data_in_leaf": 5,
                         "max_cat_to_onehot": 32}, ds, num_boost_round=8)
        acc = ((bst.predict(x) > 0.5) == (y > 0.5)).mean()
        assert acc > 0.99, acc
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
