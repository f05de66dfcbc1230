"""EFB bundles on the physical fast path (ISSUE 12).

The graduation contract: bundled datasets ride the SAME physical /
stream / mesh kernels as unbundled ones, because the comb
ingests the unbundled logical layout (``device_data.unbundle_bins`` —
per-feature bin offsets subtracted on device).  With zero bundling
conflicts (the shipping ``max_conflict_rate=0.0``) the unbundled ingest
is bit-identical to the never-bundled bin matrix, so ``enable_bundle``
must not change a single tree byte anywhere on the fast path:

* bit-parity matrix: bundled vs pre-unbundled trees BYTE-IDENTICAL
  across one and two comb planes x serial/8-shard-mesh, through the REAL
  partition kernel bodies (``LGBM_TPU_PART_INTERP=kernel``);
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


def _onehot_problem(n=1024, cats=24, extra=3, seed=5):
    rng = np.random.default_rng(seed)
    c = rng.integers(0, cats, size=n)
    onehot = np.zeros((n, cats))
    onehot[np.arange(n), c] = 1.0
    dense = rng.normal(size=(n, extra))
    x = np.hstack([onehot, dense]).astype(np.float32)
    y = ((c % 4 == 0).astype(np.float32)
         + 0.3 * (dense[:, 0] > 0) > 0.5).astype(np.float32)
    return x, y


def _comb_width(grow):
    """Line width of the grower's comb; None on the row_order path."""
    pieces = getattr(grow, "_pieces", None)
    return pieces.C if pieces is not None else getattr(grow, "_C", None)


def _fresh_train(env, bundle, n=1024, rounds=3, cats=24, **params):
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
        x, y = _onehot_problem(n=n, cats=cats)
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
            "routing": inner.routing_info(),
            "bundled": inner.dd.bundle is not None,
            "comb_C": _comb_width(inner.grow),
        }
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_byte_identical(a, b):
    assert len(a["trees"]) == len(b["trees"])
    for i, (ta, tb) in enumerate(zip(a["trees"], b["trees"])):
        assert ta[0] == tb[0], f"tree {i}: num_leaves differ"
        assert ta[1] == tb[1], f"tree {i}: split features differ"
        assert ta[2] == tb[2], f"tree {i}: threshold bins differ"
        assert np.array_equal(ta[3], tb[3]), \
            f"tree {i}: leaf values not byte-identical"
    assert np.array_equal(a["pred"], b["pred"])


# ---------------------------------------------------------------------
# bit-parity matrix: planes x learner, real kernel bodies.  140 one-hot
# columns bundle into a few storage columns and UNBUNDLE to a comb line
# of two 128-lane planes.
# ---------------------------------------------------------------------
@pytest.mark.parametrize("learner", ["serial", "data"])
@pytest.mark.parametrize("planes", [1, 2])
def test_bundled_vs_unbundled_byte_identical(planes, learner):
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel"}
    params = {"tree_learner": learner} if learner != "serial" else {}
    runs = {f: _fresh_train(env, f, cats=24 if planes == 1 else 140,
                            **params) for f in (True, False)}
    assert runs[True]["bundled"], "EFB did not engage; test is vacuous"
    assert not runs[False]["bundled"]
    for f in (True, False):
        r = runs[f]["routing"]
        assert r["path"] in ("stream", "physical"), \
            (f, r["path"], r["reasons"])
        assert runs[f]["comb_C"] == 128 * planes, (f, r)
    _assert_byte_identical(runs[True], runs[False])


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
        make_grow_fn(
            SplitHyperParams(min_data_in_leaf=2), num_leaves=8,
            padded_bins=256, padded_bins_log=16, bundle=bundle,
            physical_bins=jax.ShapeDtypeStruct((4096, 8), jnp.uint8))
