"""Score-resident gradient streaming (ops/pallas/stream_grad.py).

On CPU the kernels run their pure-XLA reference implementations via
``LGBM_TPU_PHYS=interpret`` (the same seam test_physical.py uses); the
tests compare streamed training against the gather-refresh physical path
and the plain row_order path.
"""
import os
import sys

import numpy as np
import pytest


def _fresh_train(env_phys, env_stream, objective="binary", n=3000, f=6,
                 rounds=5, weights=None, env_extra=None, expect_c=None,
                 **params):
    os.environ["LGBM_TPU_PHYS"] = env_phys
    os.environ["LGBM_TPU_STREAM"] = env_stream
    _extra_saved = {}
    for k, v in (env_extra or {}).items():
        _extra_saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(3)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        target = (np.nan_to_num(x[:, 0])
                  + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]))
        y = ((target > 0).astype(np.float32) if objective == "binary"
             else target.astype(np.float32))
        p = {"objective": objective, "num_leaves": 15, "verbosity": -1}
        p.update(params)
        ds = lgb.Dataset(x, label=y, weight=weights)
        bst = lgb.train(p, ds, num_boost_round=rounds)
        streaming = bst._inner._stream_grad
        if expect_c is not None:
            assert bst._inner.grow._C == expect_c
        trees = [(int(t.num_leaves),
                  t.split_feature[:int(t.num_leaves) - 1].tolist(),
                  t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
                  np.asarray(t.leaf_value[:int(t.num_leaves)]))
                 for t in bst._models]
        return bst.predict(x), trees, streaming
    finally:
        os.environ.pop("LGBM_TPU_PHYS", None)
        os.environ.pop("LGBM_TPU_STREAM", None)
        for k, v in _extra_saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_trees_close(t_ref, t_str):
    for i, (a, b) in enumerate(zip(t_ref, t_str)):
        assert a[0] == b[0], f"tree {i} num_leaves {a[0]} != {b[0]}"
        assert a[1] == b[1], f"tree {i} split features differ"
        assert a[2] == b[2], f"tree {i} thresholds differ"
        np.testing.assert_allclose(a[3], b[3], rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_stream_matches_gather_refresh(objective):
    p_ref, t_ref, s_ref = _fresh_train("interpret", "0", objective)
    p_str, t_str, s_str = _fresh_train("interpret", "", objective)
    assert not s_ref and s_str, "stream gate did not engage as expected"
    _assert_trees_close(t_ref, t_str)
    np.testing.assert_allclose(p_ref, p_str, rtol=5e-3, atol=1e-3)


def test_stream_weighted_and_unbalance():
    rng = np.random.default_rng(7)
    w = rng.uniform(0.5, 2.0, size=3000).astype(np.float32)
    p_ref, t_ref, s_ref = _fresh_train(
        "interpret", "0", "binary", weights=w, is_unbalance=True)
    p_str, t_str, s_str = _fresh_train(
        "interpret", "", "binary", weights=w, is_unbalance=True)
    assert s_str and not s_ref
    _assert_trees_close(t_ref, t_str)
    np.testing.assert_allclose(p_ref, p_str, rtol=5e-3, atol=1e-3)


def test_stream_gates_off_for_bagging_and_renew():
    _, _, s_bag = _fresh_train("interpret", "", "binary",
                               bagging_fraction=0.7, bagging_freq=1)
    assert not s_bag, "bagging must disable score-resident streaming"
    _, _, s_l1 = _fresh_train("interpret", "", "regression_l1")
    assert not s_l1, "renew objectives must disable streaming"


@pytest.mark.parametrize("n,f,rounds,kw", [
    (6000, 6, 8, {}),
    # a comb of nine planes and one of sixteen (1,100 and
    # 2,000 columns at 63 bins: 1,152 and 2,048 lanes)
    (1500, 1100, 2, {"max_bin": 63, "expect_c": 1152}),
    (1500, 2000, 2, {"max_bin": 63, "expect_c": 2048})],
    ids=["narrow", "nine_planes", "sixteen_planes"])
def test_stream_vs_plain_quality(n, f, rounds, kw):
    # end-to-end sanity at slightly larger scale against the row_order
    # path: identical early trees, close predictions
    p_ref, t_ref, _ = _fresh_train("0", "0", "binary", n=n, f=f,
                                   rounds=rounds, max_bin=kw.get("max_bin",
                                                                 255))
    p_str, t_str, s = _fresh_train("interpret", "", "binary", n=n, f=f,
                                   rounds=rounds, **kw)
    assert s
    _assert_trees_close(t_ref[:4], t_str[:4])
    np.testing.assert_allclose(p_ref, p_str, rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_stream_two_plane_matches_gather_refresh(objective):
    """Streamed training at 130 features - a 256-lane comb line whose
    score, constant and value columns all sit in the second plane -
    grows the trees the gather-refresh physical path grows, both
    through the real scan kernels (LGBM_TPU_PART_INTERP=kernel)."""
    kw = dict(n=1500, f=130, rounds=2, num_leaves=7, expect_c=256,
              env_extra={"LGBM_TPU_PART_INTERP": "kernel"})
    p_ref, t_ref, s_ref = _fresh_train("interpret", "0", objective, **kw)
    p_str, t_str, s_str = _fresh_train("interpret", "", objective, **kw)
    assert not s_ref and s_str, "stream gate did not engage as expected"
    assert any(t[0] > 1 for t in t_str)
    _assert_trees_close(t_ref, t_str)
    np.testing.assert_allclose(p_ref, p_str, rtol=5e-3, atol=1e-3)


@pytest.mark.parametrize("f,C,root", [(144, 256, True),
                                       (2000, 2048, False)])
def test_stream_two_plane_kernels_vs_reference(f, C, root):
    """The REAL stream kernels (init, refresh, fused refresh+root-hist)
    at C = 256 - 144 bin columns, every stream column in the second
    plane - and at C = 2,048 (2,000 bin columns, sixteen planes, the
    block the width gives: 128 rows; no fused root there) run through
    the Pallas interpreter track their XLA references to bf16-rounding
    tolerance on live rows (the kernels round g/h to bf16 — the
    precision every histogram matmul applies on chip anyway; slack
    rows are contractually dead)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas.layout import comb_shape, to_rows
    from lightgbm_tpu.ops.pallas.stream_grad import (
        binary_consts, build_aux, make_init, make_refresh,
        stream_block_rows)
    rng = np.random.default_rng(0)
    n_alloc, n_pad, R = 2048 + 512, 2048, stream_block_rows(C)
    assert R == (512 if C == 256 else 128)
    bins = jnp.asarray(rng.integers(0, 200, size=(n_pad, f))
                       .astype(np.uint8))
    aux = build_aux(
        "binary", jnp.asarray(rng.normal(size=n_pad).astype(np.float32)),
        jnp.asarray((rng.random(n_pad) > 0.1).astype(np.float32)),
        binary_consts(
            jnp.asarray(np.where(rng.random(n_pad) > 0.5, 1.0, -1.0)
                        .astype(np.float32)),
            jnp.asarray(rng.uniform(0.5, 2.0, size=n_pad)
                        .astype(np.float32))))
    kw = dict(kind="binary", sigmoid=1.3, f_real=f, f=f,
              n_alloc=n_alloc, n_pad=n_pad, C=C, R=R)
    comb0 = jnp.zeros(comb_shape(n_alloc, C), jnp.float32)

    def live(comb):
        return np.asarray(to_rows(comb, C))[:n_pad]

    c_ref = make_init(**kw, interpret=True)(comb0, bins, aux)
    c_kern = make_init(**kw, kernel_interpret=True)(comb0, bins, aux)
    assert np.abs(live(c_ref)[:, f:]).sum() > 0
    assert np.abs(live(c_ref) - live(c_kern)).max() < 2e-2

    rkw = dict(kind="binary", sigmoid=1.3, f=f, n_alloc=n_alloc,
               n_pad=n_pad, C=C, R=R)
    lv = jnp.asarray(rng.normal(size=(1, n_pad)).astype(np.float32)
                     * 0.1)
    r_ref = make_refresh(**rkw, interpret=True)(c_ref, lv)
    r_kern = make_refresh(**rkw, kernel_interpret=True)(c_kern, lv)
    assert np.abs(live(r_ref) - live(r_kern)).max() < 2e-2
    if not root:
        return

    _, h_ref = make_refresh(**rkw, interpret=True, root_hist=True,
                            padded_bins=256, root_rpb=256)(c_ref, lv)
    _, h_kern = make_refresh(**rkw, root_hist=True, padded_bins=256,
                             kernel_interpret=True)(c_kern, lv)
    assert np.abs(np.asarray(h_ref)[128:]).sum() > 0
    assert np.abs(np.asarray(h_ref) - np.asarray(h_kern)).max() < 0.15


def test_split_bf16_roundtrip():
    from lightgbm_tpu.ops.pallas.stream_grad import split_bf16_3
    import jax.numpy as jnp
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=4096).astype(np.float32) * 37.5)
    a, b, c = split_bf16_3(x)
    for t in (a, b, c):
        assert np.array_equal(np.asarray(t, np.float32),
                              np.asarray(t.astype(jnp.bfloat16), np.float32))
    err = np.abs(np.asarray(a + b + c - x))
    assert err.max() <= np.abs(np.asarray(x)).max() * 2 ** -22
