"""EFB bundles on the physical fast path (ISSUES 12 and 36).

Bundled datasets ride the SAME physical / stream / mesh kernels as
unbundled ones, in one of two comb forms (``RouteDecision.efb``):

* ``unbundled`` - the mesh learners, and the grow options the
  bundle-space finder does not cover: the comb ingests the unbundled
  logical layout (``device_data.unbundle_bins`` - per-feature bin
  offsets subtracted on device).  With zero bundling conflicts (the
  shipping ``max_conflict_rate=0.0``) the unbundled ingest is
  bit-identical to the never-bundled bin matrix, so ``enable_bundle``
  does not change a single tree byte there;
* ``bundled`` - the serial learner with the plain finder keeps one comb
  column a bundle (ISSUE 36; ``tests/test_efb_comb.py``).  Its trees
  are the unbundled path's split for split; its leaf values are NOT
  that path's bytes, because the two programs sum in different orders:
  a one-hot split's small child holds the sum of its own rows here and
  the parent's totals minus the default bin's sum there.  ISSUE 36
  asked for byte identity between the two forms; what holds is stated
  below and in ``PERF.md`` (Findings, PR 36), and the bundled comb is
  the one closer to the float64 sums.

Here:

* bit-parity matrix of the unbundling ingest: bundled vs pre-unbundled
  trees BYTE-IDENTICAL across one and two comb planes x serial /
  8-shard mesh, through the REAL partition kernel bodies
  (``LGBM_TPU_PART_INTERP=kernel``); the serial learner takes that
  ingest with ``path_smooth`` on;
* the bundled comb against the pre-unbundled table, one and two planes:
  the same splits in the same order, and tree 0's leaf values of each
  against the float64 sums of its leaves;
* CPU-reference parity: the bundled physical path agrees with the
  bundled row_order reference on a real one-hot dataset (split
  structure exact, leaf values to f32 accumulation order);
* the unbundle primitive itself reproduces the logical bin matrix;
* the ``efb_overwide`` budget defense fires at grow build.
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


def _onehot_problem(n=1024, cats=24, extra=3, seed=5, noisy=False):
    """``noisy``: the label is a draw around the same signal instead of
    a function of the columns, so that no leaf is pure and no gain is
    rounding alone - what two programs that sum in different orders can
    be compared on."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, cats, size=n)
    onehot = np.zeros((n, cats))
    onehot[np.arange(n), c] = 1.0
    dense = rng.normal(size=(n, extra))
    x = np.hstack([onehot, dense]).astype(np.float32)
    if noisy:
        logit = (2.0 * (c % 4 == 0) + 0.5 * np.sin(c) + 0.8 * dense[:, 0]
                 - 1.0)
        y = (logit + rng.logistic(size=n) > 0).astype(np.float32)
    else:
        y = ((c % 4 == 0).astype(np.float32)
             + 0.3 * (dense[:, 0] > 0) > 0.5).astype(np.float32)
    return x, y


def _comb_width(grow):
    """Line width of the grower's comb; None on the row_order path."""
    pieces = getattr(grow, "_pieces", None)
    return pieces.C if pieces is not None else getattr(grow, "_C", None)


def _fresh_train(env, bundle, n=1024, rounds=3, cats=24, noisy=False,
                 **params):
    """Train on the one-hot problem in a fresh library generation and
    return (exact tree digests, raw predictions, engaged facts)."""
    saved = _save_env(_KNOBS)
    for k in _KNOBS:
        os.environ.pop(k, None)
    for k, v in env.items():
        os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        x, y = _onehot_problem(n=n, cats=cats, noisy=noisy)
        p = {"objective": "binary", "num_leaves": 15,
             "min_data_in_leaf": 5, "max_bin": 31, "min_data_in_bin": 1,
             "enable_bundle": bundle, "verbosity": -1}
        p.update(params)
        ds = lgb.Dataset(x, label=y, params=p)
        bst = lgb.train(p, ds, num_boost_round=rounds)
        inner = bst._inner
        trees = [(int(t.num_leaves),
                  t.split_feature[:int(t.num_leaves) - 1].tolist(),
                  t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
                  np.asarray(t.leaf_value[:int(t.num_leaves)]))
                 for t in bst._models]
        return {
            "trees": trees,
            "pred": bst.predict(x, raw_score=True),
            "model": bst.model_to_string(),
            "routing": inner.routing_info(),
            "bundled": inner.dd.bundle is not None,
            "comb_C": _comb_width(inner.grow),
        }
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_same_splits(a, b):
    assert len(a["trees"]) == len(b["trees"])
    for i, (ta, tb) in enumerate(zip(a["trees"], b["trees"])):
        assert ta[0] == tb[0], f"tree {i}: num_leaves differ"
        assert ta[1] == tb[1], f"tree {i}: split features differ"
        assert ta[2] == tb[2], f"tree {i}: threshold bins differ"


def _assert_byte_identical(a, b):
    _assert_same_splits(a, b)
    for i, (ta, tb) in enumerate(zip(a["trees"], b["trees"])):
        assert np.array_equal(ta[3], tb[3]), \
            f"tree {i}: leaf values not byte-identical"
    assert np.array_equal(a["pred"], b["pred"])


# ---------------------------------------------------------------------
# bit-parity matrix of the unbundling ingest: planes x learner, real
# kernel bodies.  140 one-hot columns bundle into a few storage columns
# and UNBUNDLE to a comb line of two 128-lane planes.  The mesh learner
# always takes that ingest; the serial learner takes it for the grow
# options outside the bundle-space finder, of which path_smooth is one.
# ---------------------------------------------------------------------
@pytest.mark.parametrize("learner", ["serial", "data"])
@pytest.mark.parametrize("planes", [1, 2])
def test_bundled_vs_unbundled_byte_identical(planes, learner):
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel"}
    params = ({"tree_learner": learner} if learner != "serial"
              else {"path_smooth": 1.0})
    runs = {f: _fresh_train(env, f, cats=24 if planes == 1 else 140,
                            **params) for f in (True, False)}
    assert runs[True]["bundled"], "EFB did not engage; test is vacuous"
    assert not runs[False]["bundled"]
    assert runs[True]["routing"]["efb"] == "unbundled"
    assert runs[False]["routing"]["efb"] == "none"
    for f in (True, False):
        r = runs[f]["routing"]
        assert r["path"] in ("stream", "physical"), \
            (f, r["path"], r["reasons"])
        assert runs[f]["comb_C"] == 128 * planes, (f, r)
    _assert_byte_identical(runs[True], runs[False])


# ---------------------------------------------------------------------
# the bundled comb (the serial learner, the plain finder) against the
# pre-unbundled table, same kernel bodies: 140 one-hot columns stay a
# few lanes of ONE plane where the unbundled comb takes two.
# ---------------------------------------------------------------------
def _tree0_off_float64(run, x, y):
    """Largest distance of tree 0's leaf values from ``-lr G / H`` of
    the float64 sums over the rows the float64 walk sends to each
    leaf (the benchmark's plain reference)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    import reference
    import reference_mesh
    tree0 = reference.parse_model(run["model"])[0]
    sums = reference_mesh.binary_leaf_sums(tree0, x.astype(np.float64), y)
    return float(np.max(np.abs(tree0.leaf_value
                               - sums.leaf_values(0.1, 0.0))))


@pytest.mark.parametrize("planes", [1, 2])
def test_bundled_comb_grows_the_unbundled_trees(planes):
    """No ``min_gain_to_split``, no sorting of the splits: on labels
    that are a draw (no leaf pure, no gain rounding alone) the two
    forms elect the same split at the same step of every tree.  The
    leaf values are compared with the float64 sums, each form's own:
    the bundled comb gives a one-hot split's small child the sum of its
    own rows and reads 2e-7 here; the unbundled comb gives it the
    parent's totals minus the default bin's sum and reads 6e-4 on a
    leaf of 5 rows (6e-5 at 24 columns).  Byte identity between the two
    would mean reproducing the larger error."""
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel"}
    cats = 24 if planes == 1 else 140
    runs = {f: _fresh_train(env, f, cats=cats, noisy=True)
            for f in (True, False)}
    assert runs[True]["bundled"] and not runs[False]["bundled"]
    assert runs[True]["routing"]["efb"] == "bundled"
    assert runs[False]["routing"]["efb"] == "none"
    for f in (True, False):
        assert runs[f]["routing"]["path"] == "stream", runs[f]["routing"]
    assert runs[True]["comb_C"] == 128
    assert runs[False]["comb_C"] == 128 * planes
    _assert_same_splits(runs[True], runs[False])
    x, y = _onehot_problem(cats=cats, noisy=True)
    assert _tree0_off_float64(runs[True], x, y) < 2e-6
    assert _tree0_off_float64(runs[False], x, y) < 2e-3
    # three trees on: the two forms' scores are as far apart as the
    # unbundled comb's leaves are from theirs
    np.testing.assert_allclose(runs[True]["pred"], runs[False]["pred"],
                               rtol=0, atol=2e-3)


# ---------------------------------------------------------------------
# CPU-reference parity: bundled physical vs bundled row_order
# ---------------------------------------------------------------------
def test_bundled_physical_matches_row_order_reference():
    """The graduated path agrees with the bundled row_order reference
    on a real one-hot dataset.  Cross-PATH comparison: histogram
    accumulation order and the stream kernel's bf16-split gradients
    both differ, so near-tie splits on 2-bin one-hot features may
    flip (the test_efb.py bundled-vs-unbundled tolerance class) —
    predictions must still agree everywhere that matters."""
    phys = _fresh_train({"LGBM_TPU_PHYS": "interpret"}, True,
                        rounds=8)
    ref = _fresh_train({"LGBM_TPU_PHYS": "0"}, True, rounds=8)
    assert phys["routing"]["path"] == "stream"
    assert ref["routing"]["path"] == "row_order"
    assert ref["routing"]["reasons"] == ["phys_env_off"]
    close = np.isclose(phys["pred"], ref["pred"], rtol=1e-3, atol=1e-3)
    assert close.mean() > 0.95, close.mean()
    agree = ((phys["pred"] > 0) == (ref["pred"] > 0)).mean()
    assert agree > 0.98, agree


# ---------------------------------------------------------------------
# the unbundle primitive reproduces the logical bin matrix
# ---------------------------------------------------------------------
def test_unbundle_bins_reproduces_logical_matrix():
    import numpy as np

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset_core import BinnedDataset
    from lightgbm_tpu.ops.device_data import to_device, unbundle_bins

    x, y = _onehot_problem(n=512, cats=12, extra=2)
    cfg = Config.from_params({"max_bin": 31, "min_data_in_bin": 1})
    ds = BinnedDataset.construct(x, cfg, label=y)
    assert ds.bundle_info is not None and ds.bundle_info.any_bundled
    dd = to_device(ds)
    assert dd.bundle is not None
    out = np.asarray(unbundle_bins(dd.bins, dd.bundle))
    assert out.dtype == np.uint8
    assert out.shape == (dd.n_pad, dd.f_log)
    f = ds.num_features
    np.testing.assert_array_equal(
        out[:ds.num_data, :f], np.asarray(ds.bin_matrix, np.uint8),
        err_msg="unbundled ingest differs from the logical bin matrix")
    # padded logical features decode to bin 0 (num_bins 0 -> default 0)
    assert not out[:, f:].any()
    # physical-path geometry facts the routing model prices (ISSUE 12)
    assert ds.bundle_info.num_phys < ds.num_features
    assert dd.phys_f_pad == dd.f_log
    assert dd.phys_padded_bins == dd.padded_bins_log
    assert dd.phys_bins_u8


# ---------------------------------------------------------------------
# the efb_overwide budget defense at grow build
# ---------------------------------------------------------------------
def test_grow_build_rejects_overwide_bundle_expansion():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import MAX_COMB_COLS
    from lightgbm_tpu.ops.split import SplitHyperParams

    f_log = MAX_COMB_COLS + 16     # unbundles past the column budget
    bundle = {
        "feat_phys": np.zeros(f_log, np.int32),
        "feat_offset": np.arange(f_log, dtype=np.int32),
        "feat_default": np.zeros(f_log, np.int32),
        "is_bundled": np.ones(f_log, bool),
        "num_bins_log": np.ones(f_log, np.int32),
    }
    with pytest.raises(ValueError, match="efb_overwide"):
        # (extra_trees: a grow option that takes the unbundling ingest)
        make_grow_fn(
            SplitHyperParams(min_data_in_leaf=2, use_extra_trees=True),
            num_leaves=8,
            padded_bins=256, padded_bins_log=16, bundle=bundle,
            physical_bins=jax.ShapeDtypeStruct((4096, 8), jnp.uint8))
