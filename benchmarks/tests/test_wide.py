"""The Epsilon cell's generator, plain reference, work functions and
roofline reducer."""
import dataclasses

import numpy as np

from conftest import BENCH  # noqa: F401 - puts benchmarks/ on the path

import epsilon_data
import hist_work
import reference
import reference_wide
import run as bench_run
import trace as bench_trace


def test_generator_is_the_published_shape_and_one_table():
    n, held = 20_000, 3_000
    a = epsilon_data.make_epsilon_like(n + held, seed=2**31 + 11,
                                       table_rows=n)
    b = epsilon_data.make_epsilon_like(n + held, seed=7, table_rows=n)
    x, y, logit = a
    assert x.shape == (n + held, 2000) and x.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    assert not np.isnan(x).any() and 0.48 < y.mean() < 0.52
    assert reference.auc(logit, y) > 0.9            # the oracle's
    # the same training rows whatever the seed, the rest the seed's own
    np.testing.assert_array_equal(a[0][:n], b[0][:n])
    np.testing.assert_array_equal(a[1][:n], b[1][:n])
    assert not np.array_equal(a[2][n:], b[2][n:])
    again = epsilon_data.make_epsilon_like(n + held, seed=7, table_rows=n)
    np.testing.assert_array_equal(again[0], b[0])


def test_root_gains_find_the_planted_column():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4000, 6)).astype(np.float32)
    y = (x[:, 4] > 0.3).astype(np.float32)
    # the reference's own grid, not the program's bins
    grid = reference_wide.quantile_grid(x[:, 4])
    assert len(grid) == reference_wide.GRID_BINS - 1
    assert np.all(np.diff(grid) > 0)
    thr = float(grid[np.searchsorted(grid, 0.3)])
    tree = reference.TextTree(
        num_leaves=2, split_feature=np.array([4]),
        threshold=np.array([thr]), decision_type=np.array([0]),
        left_child=np.array([-1]), right_child=np.array([-2]),
        leaf_value=np.zeros(2), split_gain=np.array([1.0]))
    r = reference_wide.root_gains(x, y, tree, min_data_in_leaf=1)
    assert r.best_column == 4 and r.best_ratio == 1.0
    # a split one grid edge off the planted one: under the best
    off = dataclasses.replace(tree, threshold=np.array(
        [float(grid[np.searchsorted(grid, 0.3) + 3])]))
    assert reference_wide.root_gains(x, y, off,
                                     min_data_in_leaf=1).best_ratio < 0.99
    below = reference_wide.root_gain_of_bf16_sums(x, y, tree)
    assert 0 < abs(below - r.gain_recomputed) / r.gain_recomputed < 1e-2


def test_hist_work_counts_the_contraction_and_two_planes_a_tile():
    assert hist_work.group_geometry(64) == (16, 64, 512)
    assert hist_work.group_geometry(256) == (8, 128, 256)
    # 16 tiles of 8 groups: 2 x 64 x 512 x 128 operations a row
    assert hist_work.hist_flops(1, 16, 64) == 2 * 64 * 512 * 128
    assert hist_work.hist_bytes(1, 16) == 2 * 16 * 512


def test_roofline_share_reads_the_slice_and_the_counters():
    ops = [("%lgbm_hist.10 = f32[128,64,512] custom-call(...)", 0,
            1_000_000), ("%fusion.3 = f32[2] fusion(...)", 1_000_000,
                         1_500_000)]
    sliced = bench_trace.SliceTrace(0, 2_000_000,
                                    {0: bench_trace.DeviceOps(ops)})
    spans = [{"name": "Tree::grow", "ph": "X", "ts": t, "dur": 1,
              "args": {"rows_histogrammed": 1000.0, "hist_tiles": 16}}
             for t in (1, 2, 3)]
    obs = {"spans": spans, "slice": sliced,
           "counters": {"slice_iterations": 2}}
    spec = bench_run.load("layer_metrics", "hist_roofline_share")
    got = bench_run.module("reducers", spec["reducer"]).reduce(
        obs, **spec["args"], device_kind="TPU v5 lite")
    flops = 2 * hist_work.hist_flops(1000.0, 16, 64)
    assert abs(got - 100.0 * flops / 197e12 / 1e-3) < 1e-9
    # a program without the counter: nothing to read
    for e in spans:
        del e["args"]["hist_tiles"]
    assert bench_run.module("reducers", spec["reducer"]).reduce(
        obs, **spec["args"], device_kind="TPU v5 lite") is None
