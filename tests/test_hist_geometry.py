"""The split of a bin in the one-hot histogram contraction
(``ops/pallas/hist_kernel2.hist_geometry``), and every kernel that embeds
the accumulation, held BITWISE to a numpy ``bincount`` at 32 and 64 bins.

Up to 128 bins a bin splits ``hi * lo_n + lo`` with ``lo_n = b / 8``, so a
group of 16 columns fills the M axis of the MXU (``M == 128``); past 128 the
split is 16 wide, as it always was at 256.  The values here are small
dyadic numbers, exact in bf16, whose f32 sums are exact in any order, so
every kernel must give the oracle's histogram to the bit.  Kernels run
through the Pallas interpreter at small shapes.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import feature_group_size
from lightgbm_tpu.ops.pallas.hist_kernel2 import hist_geometry


@pytest.mark.parametrize("b", [16, 32, 48, 64, 80, 128, 144, 256])
def test_geometry_fills_the_m_axis_and_keeps_the_product(b):
    b_hi, g, m, nn, lo_n = hist_geometry(b, 2)
    assert g == feature_group_size(b)
    assert b % lo_n == 0 and b_hi * lo_n == b
    assert m == g * b_hi and nn == g * lo_n * 2
    # the product's size, the accumulator's bytes and the counted work
    # do not depend on the split
    assert m * nn == g * g * b * 2
    if b <= 128:
        assert (m, b_hi, g) == (128, 8, 16)
    if b == 256:
        assert (b_hi, g, m, nn, lo_n) == (16, 8, 128, 256, 16)


def _dyadic_values(rng, n):
    """(g, h) pairs exact in bf16 whose sums are exact in f32."""
    return np.stack([rng.integers(-16, 17, size=n) / 4.0,
                     rng.integers(0, 9, size=n) / 8.0],
                    axis=1).astype(np.float32)


def _bincount(bins, vals, b):
    """[f, b, 2] histogram of int bins [n, f] and values [n, 2]."""
    f = bins.shape[1]
    out = np.zeros((f, b, 2), np.float32)
    for ch in range(2):
        for j in range(f):
            out[j, :, ch] = np.bincount(bins[:, j].astype(np.int64),
                                        weights=vals[:, ch], minlength=b)
    return out


def _comb(rng, n, f_pad, b, c):
    """A row-major comb of ``c`` lanes: bins [0, f_pad), (g, h) at
    f_pad, f_pad + 1."""
    rows = np.zeros((n, c), np.float32)
    rows[:, :f_pad] = rng.integers(0, b, size=(n, f_pad))
    rows[:, f_pad:f_pad + 2] = _dyadic_values(rng, n)
    return rows


def _comb_hist(b, f_pad, c):
    from lightgbm_tpu.ops.pallas import hist_kernel2 as hk
    from lightgbm_tpu.ops.pallas.layout import LANE, to_planes
    rows = _comb(np.random.default_rng(b + f_pad), 2048 + 512, f_pad, b, c)
    assert (hk.hist_tiles(f_pad, c) > 1) == (c > 2 * LANE)
    start, off, cnt = 77, 3, 1500
    got = hk.build_histogram_comb(
        to_planes(jnp.asarray(rows)), jnp.int32(start), jnp.int32(off),
        jnp.int32(cnt), f_pad=f_pad, size=2048, padded_bins=b,
        rows_per_block=256, interpret=True, planes=c // LANE)
    win = rows[start + off:start + off + cnt]
    return got, _bincount(win[:, :f_pad], win[:, f_pad:f_pad + 2], b)


def _pallas2(b):
    from lightgbm_tpu.ops.pallas.hist_kernel2 import build_histogram_pallas2
    rng = np.random.default_rng(b)
    n, f_pad = 1000, 32
    bins = rng.integers(0, b, size=(n, f_pad)).astype(np.uint8)
    vals = _dyadic_values(rng, n)
    got = build_histogram_pallas2(jnp.asarray(bins), jnp.asarray(vals),
                                  padded_bins=b, rows_per_block=256,
                                  interpret=True)
    return got, _bincount(bins, vals, b)


def _fused_hook(b):
    """The REAL fused scan + histogram kernel: the hook's histogram of
    the left child is the oracle's over the rows the scan put there."""
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    from lightgbm_tpu.ops.pallas.partition_kernel import (SEL_CNT, SEL_S0,
                                                          SEL_SIDE,
                                                          SIDE_LEFT)
    R, size, f_pad, c = 128, 1024, 32, 128
    n = size + 3 * R + 2048
    rows = _comb(np.random.default_rng(b + 1), n, f_pad, b, c)
    s0, cnt = 64, 900
    sel = np.zeros((8,), np.int32)
    sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = s0, cnt, 3, b // 3
    sel[6] = -1                                    # no NaN bin
    sel[SEL_SIDE] = SIDE_LEFT
    rj = jnp.asarray(rows)
    out, _, nleft, h_side = make_fused_split(
        n, c, f_pad=f_pad, padded_bins=b, R=R, size=size,
        fused_kernel_interpret=True)(jnp.asarray(sel), rj,
                                     jnp.zeros_like(rj))
    nleft = int(nleft)
    assert 0 < nleft < cnt
    seg = np.asarray(out)[s0:s0 + nleft]
    assert (seg[:, 3] <= b // 3).all()
    return h_side, _bincount(seg[:, :f_pad], seg[:, f_pad:f_pad + 2], b)


def _stream_root(b):
    """The REAL fused refresh + root pass (l2): the root histogram of
    the gradients it writes, g = score + delta - target, h = validity."""
    from lightgbm_tpu.ops.pallas.layout import comb_layout, comb_shape
    from lightgbm_tpu.ops.pallas.stream_grad import (
        build_aux, l2_consts, make_init, make_refresh, stream_block_rows,
        stream_columns)
    rng = np.random.default_rng(b + 2)
    f, n_pad = 32, 2048
    n_alloc, c = n_pad + 512, comb_layout(32 + stream_columns("l2"))
    R = stream_block_rows(c)
    bins = rng.integers(0, b, size=(n_pad, f)).astype(np.uint8)
    score = rng.integers(-8, 9, size=n_pad) / 4.0
    target = rng.integers(-8, 9, size=n_pad) / 4.0
    live = (rng.random(n_pad) > 0.1).astype(np.float32)
    delta = rng.integers(-4, 5, size=(1, n_pad)) / 4.0
    aux = build_aux("l2", jnp.asarray(score), jnp.asarray(live),
                    l2_consts(jnp.asarray(target, jnp.float32),
                              jnp.ones((n_pad,), jnp.float32)))
    comb = make_init(kind="l2", sigmoid=1.0, f_real=f, f=f,
                     n_alloc=n_alloc, n_pad=n_pad, C=c, R=R,
                     kernel_interpret=True)(
        jnp.zeros(comb_shape(n_alloc, c), jnp.float32), jnp.asarray(bins),
        aux)
    _, hist = make_refresh(kind="l2", sigmoid=1.0, f=f, n_alloc=n_alloc,
                           n_pad=n_pad, C=c, R=R, root_hist=True,
                           padded_bins=b, kernel_interpret=True)(
        comb, jnp.asarray(delta, jnp.float32))
    vals = np.stack([(score + delta[0] - target) * live, live],
                    axis=1).astype(np.float32)
    return hist, _bincount(bins, vals, b)


_KERNELS = {
    "comb": lambda b: _comb_hist(b, 32, 128),
    # 272 columns over three planes: three one-plane tiles
    "comb_tiled": lambda b: _comb_hist(b, 272, 384),
    "pallas2": _pallas2,
    "fused_hook": _fused_hook,
    "stream_root": _stream_root,
}


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
@pytest.mark.parametrize("b", [32, 64])
def test_kernel_histogram_is_the_bincount(kernel, b):
    got, want = _KERNELS[kernel](b)
    assert np.abs(want).sum() > 0
    np.testing.assert_array_equal(np.asarray(got), want)
