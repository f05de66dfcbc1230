"""Categorical sorted-subset splits on the physical fast path (ISSUE 16).

Graduation contract: high-cardinality categorical splits ride the SAME
partition / fused / mesh kernels as numerical ones.  The
winning subset's membership travels as bitset words APPENDED to the
SMEM split descriptor (the exact ``ops/predict.py`` serving encoding,
one bit per padded bin), decoded per row inside the kernel bodies —
so ``categorical_feature`` must not change which kernels run:

* bit-parity matrix: permute vs matmul trees
  BYTE-IDENTICAL on cat-subset data, through the REAL partition kernel
  bodies (``LGBM_TPU_PART_INTERP=kernel``), fused on/off, serial and
  8-shard data-parallel mesh (the mesh cells engage the reduce-scatter
  histogram merge — the owner-masked membership recovery);
* CPU-reference parity: the graduated path agrees with the row_order
  reference host walk on split structure exactly (same bitset member
  booleans by construction) with leaf values to f32 accumulation order;
* categorical edge cases on the TRAINED fast path: negative / unseen /
  rare categories, NaN rows, ``max_cat_threshold``, ``cat_smooth`` /
  ``cat_l2`` — prediction parity against reference CPU trees;
* ServingEngine round-trip: leaf indices from the compiled forest
  engine EXACTLY equal the host walk on a cat-subset-trained booster;
* the ``cat_overwide`` budget defense fires at grow build.
"""
import os
import sys

import numpy as np
import pytest

from conftest import restore_env_knobs as _restore_env
from conftest import save_env_knobs as _save_env

_KNOBS = ("LGBM_TPU_PHYS", "LGBM_TPU_STREAM", "LGBM_TPU_FUSED",
          "LGBM_TPU_PARTITION", "LGBM_TPU_PART_INTERP",
          "LGBM_TPU_HIST_SCATTER")


def _cat_problem(n=1536, n_cats=48, f=8, seed=7, nan_frac=0.0):
    """One high-cardinality categorical column + dense noise; 8 logical
    features so the 8-shard mesh cells satisfy the reduce-scatter
    merge's divisibility and actually exercise the scatter-side
    membership recovery."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, n_cats, size=n)
    good = rng.choice(n_cats, size=n_cats // 3, replace=False)
    dense = rng.normal(size=(n, f - 1)).astype(np.float32)
    if nan_frac:
        dense[rng.random(dense.shape) < nan_frac] = np.nan
    x = np.hstack([c[:, None].astype(np.float32), dense])
    y = (np.isin(c, good).astype(np.float32)
         + 0.4 * (np.nan_to_num(dense[:, 0]) > 0)
         + 0.1 * rng.normal(size=n) > 0.5).astype(np.float32)
    return x, y


def _digest(bst):
    """Exact per-tree digest including the categorical bitsets: any
    membership-word difference (not just split placement) fails."""
    out = []
    for t in bst._models:
        nl = int(t.num_leaves)
        out.append((nl,
                    t.split_feature[:nl - 1].tolist(),
                    t.threshold_bin[:nl - 1].tolist(),
                    np.asarray(t.decision_type[:nl - 1]).tolist(),
                    np.asarray(t.cat_threshold).tobytes(),
                    np.asarray(t.leaf_value[:nl]).tobytes()))
    return out


def _n_multicat_splits(bst):
    """Number of trained splits carrying a multi-category bitset."""
    multi = 0
    for t in bst._models:
        if not t.num_cat:
            continue
        for i in range(int(t.num_leaves) - 1):
            if t.decision_type[i] & 1:
                slot = int(t.threshold[i])
                lo = int(t.cat_boundaries[slot])
                hi = int(t.cat_boundaries[slot + 1])
                bits = sum(bin(int(w)).count("1")
                           for w in t.cat_threshold[lo:hi])
                multi += bits > 1
    return multi


def _fresh_train(env, n=1536, rounds=3, nan_frac=0.0, seed=7, f=8,
                 **params):
    """Train the cat problem in a fresh library generation; returns
    digests + predictions + engaged-path facts."""
    saved = _save_env(_KNOBS)
    for k in _KNOBS:
        os.environ.pop(k, None)
    for k, v in env.items():
        if v:
            os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        x, y = _cat_problem(n=n, f=f, seed=seed, nan_frac=nan_frac)
        p = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
             "min_data_in_leaf": 5, "min_data_per_group": 5,
             "cat_smooth": 2.0, "max_cat_to_onehot": 4, "max_bin": 63}
        p.update(params)
        ds = lgb.Dataset(x, label=y, categorical_feature=[0],
                         params={"max_bin": p["max_bin"],
                                 "min_data_in_bin": 1})
        bst = lgb.train(p, ds, num_boost_round=rounds)
        return {
            "comb_C": getattr(bst._inner.grow, "_C", None),
            "trees": _digest(bst),
            "multicat": _n_multicat_splits(bst),
            "pred": bst.predict(x, raw_score=True),
            "routing": bst._inner.routing_info(),
            "hist_scatter": getattr(bst._inner.grow, "hist_scatter",
                                    None),
            "x": x, "y": y, "bst": bst,
        }
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _kernel_env(partition, fused):
    return {"LGBM_TPU_PHYS": "interpret",
            "LGBM_TPU_PART_INTERP": "kernel",
            "LGBM_TPU_PARTITION": partition,
            "LGBM_TPU_FUSED": fused}


def _assert_byte_identical(a, b):
    assert len(a["trees"]) == len(b["trees"])
    for i, (ta, tb) in enumerate(zip(a["trees"], b["trees"])):
        assert ta[0] == tb[0], f"tree {i}: num_leaves differ"
        assert ta[1] == tb[1], f"tree {i}: split features differ"
        assert ta[2] == tb[2], f"tree {i}: threshold bins differ"
        assert ta[3] == tb[3], f"tree {i}: decision types differ"
        assert ta[4] == tb[4], f"tree {i}: cat bitsets differ"
        assert ta[5] == tb[5], f"tree {i}: leaf values differ bitwise"


def _assert_engaged(run, *, scatter=None):
    r = run["routing"]
    assert r["path"] in ("stream", "physical"), (r["path"], r["reasons"])
    assert run["multicat"] > 0, "no multi-category bitset split engaged"
    if scatter is not None:
        assert run["hist_scatter"] is scatter, run["hist_scatter"]


# ---------------------------------------------------------------------
# bit-parity matrix, real kernel bodies: scheme x fused x learner
# ---------------------------------------------------------------------
# tier-1 keeps a representative diagonal of the matrix; the full
# matrix (marked slow) runs in ci_tier1.sh leg 15 (--cat), which
# drops the 'not slow' filter for exactly this file
@pytest.mark.parametrize("fused,learner", [
    ("1", "serial"),
    ("0", "serial"),
    ("1", "data"),
    pytest.param("0", "data", marks=pytest.mark.slow),
])
def test_cat_partition_scheme_equivalence(fused, learner):
    """permute vs matmul trees BIT-IDENTICAL on cat-subset data through
    the real kernel bodies; the data cells ride the reduce-scatter
    histogram merge (scatter_cat_subset is GONE)."""
    params = ({"tree_learner": "data", "max_bin": 31}
              if learner == "data" else {})
    runs = {s: _fresh_train(_kernel_env(s, fused), **params)
            for s in ("permute", "matmul")}
    for s, run in runs.items():
        _assert_engaged(run, scatter=True if learner == "data" else None)
    _assert_byte_identical(runs["permute"], runs["matmul"])


# ---------------------------------------------------------------------
# CPU-reference parity: graduated path vs row_order host walk
# ---------------------------------------------------------------------
@pytest.mark.parametrize("planes", [1, 2])
def test_cat_physical_matches_row_order_reference(planes):
    """Same bitset member booleans by construction => identical split
    structure; leaf values accumulate in permuted row order (f32
    drift only).  At one comb plane and at two (130 feature columns:
    the value columns sit in the second plane, the categorical split
    column in the first)."""
    kw = dict(rounds=4, nan_frac=0.1, f=8 if planes == 1 else 130)
    ref = _fresh_train({"LGBM_TPU_PHYS": "0"}, **kw)
    phy = _fresh_train(_kernel_env("permute", "1"), **kw)
    assert ref["routing"]["path"] == "row_order"
    assert phy["comb_C"] == 128 * planes
    _assert_engaged(phy)
    assert ref["multicat"] > 0
    assert len(ref["trees"]) == len(phy["trees"])
    for i, (a, b) in enumerate(zip(ref["trees"], phy["trees"])):
        assert a[0] == b[0], f"tree {i}: num_leaves differ"
        assert a[1] == b[1], f"tree {i}: split features differ"
        assert a[2] == b[2], f"tree {i}: threshold bins differ"
        assert a[3] == b[3], f"tree {i}: decision types differ"
        assert a[4] == b[4], f"tree {i}: cat bitsets differ"
        av = np.frombuffer(a[5], np.float64)
        bv = np.frombuffer(b[5], np.float64)
        np.testing.assert_allclose(av, bv, rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(ref["pred"], phy["pred"], rtol=5e-3,
                               atol=1e-3)


# ---------------------------------------------------------------------
# categorical edge cases on the trained fast path (ISSUE 16 sat. 3)
# ---------------------------------------------------------------------
def test_cat_edge_predictions_negative_unseen_nan():
    """Negative, unseen, rare-category and NaN query rows route
    identically through fast-path-trained and reference-trained trees
    (the trees themselves agree structurally, so prediction parity is
    the end-to-end check that bitset encoding round-trips)."""
    ref = _fresh_train({"LGBM_TPU_PHYS": "0"}, rounds=4)
    phy = _fresh_train(_kernel_env("permute", "1"), rounds=4)
    _assert_engaged(phy)
    rng = np.random.default_rng(11)
    xq = phy["x"][:64].copy()
    xq[:16, 0] = -3.0                    # negative category codes
    xq[16:32, 0] = 1000.0                # unseen / out-of-range codes
    xq[32:48, 0] = np.nan                # NaN categorical rows
    xq[48:, 1:] = np.nan                 # NaN dense rows
    pr = ref["bst"].predict(xq, raw_score=True)
    pp = phy["bst"].predict(xq, raw_score=True)
    np.testing.assert_allclose(pr, pp, rtol=5e-3, atol=1e-3)
    assert np.isfinite(pp).all()


def test_cat_knobs_on_fast_path():
    """max_cat_threshold / cat_smooth / cat_l2 reach the device-side
    subset search on the fast path: each knob setting reproduces the
    reference path's trees structurally."""
    knobs = {"max_cat_threshold": 4, "cat_smooth": 25.0, "cat_l2": 30.0}
    ref = _fresh_train({"LGBM_TPU_PHYS": "0"}, rounds=3, **knobs)
    phy = _fresh_train(_kernel_env("permute", "1"), rounds=3, **knobs)
    _assert_engaged(phy)
    assert len(ref["trees"]) == len(phy["trees"])
    for i, (a, b) in enumerate(zip(ref["trees"], phy["trees"])):
        assert a[:5] == b[:5], f"tree {i}: structure differs"
    # max_cat_threshold caps the subset width in BOTH paths
    for run in (ref, phy):
        for t in run["bst"]._models:
            if not t.num_cat:
                continue
            for i in range(int(t.num_leaves) - 1):
                if t.decision_type[i] & 1:
                    slot = int(t.threshold[i])
                    lo = int(t.cat_boundaries[slot])
                    hi = int(t.cat_boundaries[slot + 1])
                    bits = sum(bin(int(w)).count("1")
                               for w in t.cat_threshold[lo:hi])
                    assert bits <= knobs["max_cat_threshold"], bits


# ---------------------------------------------------------------------
# ServingEngine round-trip on a cat-subset-trained booster
# ---------------------------------------------------------------------
def test_serving_engine_roundtrip_cat_fast_path():
    """The compiled forest engine gathers the SAME bitset words the
    partition kernels decoded at train time: leaf indices exactly
    equal the host walk, including edge-category query rows."""
    phy = _fresh_train(_kernel_env("permute", "1"), rounds=4)
    _assert_engaged(phy)
    bst = phy["bst"]
    from lightgbm_tpu.serve import ServingEngine, ServingModel
    eng = ServingEngine(ServingModel.from_booster(bst))
    xq = phy["x"][:128].copy()
    xq[:8, 0] = -1.0
    xq[8:16, 0] = 999.0
    xq[16:24, 0] = np.nan
    leaves = eng.predict_leaves(np.asarray(xq, np.float32))
    host = np.stack([t.predict_leaf(np.asarray(xq, np.float64))
                     for t in bst._models], axis=1)
    np.testing.assert_array_equal(leaves, host)
    scores = eng.predict(np.asarray(xq, np.float32))
    np.testing.assert_allclose(
        scores.ravel(), bst.predict(xq, raw_score=True).ravel(),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------
# the cat_overwide budget defense at grow build
# ---------------------------------------------------------------------
def test_grow_build_rejects_overwide_cat_bitset():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import CAT_BITSET_WORDS
    from lightgbm_tpu.ops.split import SplitHyperParams

    too_wide = 32 * CAT_BITSET_WORDS * 2   # 512 padded bins
    with pytest.raises(ValueError, match="cat_overwide"):
        make_grow_fn(
            SplitHyperParams(min_data_in_leaf=2, use_cat_subset=True),
            num_leaves=8, padded_bins=too_wide,
            physical_bins=jax.ShapeDtypeStruct((4096, 8), jnp.uint16))
