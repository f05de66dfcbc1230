"""The Expo cell's generator, plain reference and metric files."""
import json
import os

import numpy as np

from conftest import BENCH

import expo_data
import reference
import reference_efb
import run as bench_run


def test_generator_is_the_published_shape_and_a_function_of_the_seed():
    seed = 2**31 + 11
    x, y, logit = expo_data.make_expo_like(300_000, seed=seed)
    assert x.shape == (300_000, 700) and x.dtype == np.float32
    assert np.all(np.diff(x.indptr) == expo_data.STORED_PER_ROW)
    hot = x[:, :expo_data.N_ONEHOT]
    assert np.all(hot.data == 1.0)
    # exactly one level a field a row, none over EFB's 0.2 share
    offs = list(expo_data.field_offsets()) + [expo_data.N_ONEHOT]
    share = np.asarray(hot.sum(0)).ravel() / x.shape[0]
    for lo, hi in zip(offs[:-1], offs[1:]):
        assert np.all(np.asarray(hot[:, lo:hi].sum(1)).ravel() == 1.0)
    assert share.max() < 0.2 and share.min() > 0
    assert 0.25 < y.mean() < 0.4
    assert reference.auc(logit, y) > 0.68        # there is a signal
    x2, y2, _ = expo_data.make_expo_like(300_000, seed=seed)
    assert (x != x2).nnz == 0 and np.array_equal(y, y2)
    # a longer draw starts with the same rows; another seed does not
    x3, y3, _ = expo_data.make_expo_like(300_005, seed=seed)
    assert (x3[:300_000] != x).nnz == 0 and np.array_equal(y3[:300_000], y)
    assert not np.array_equal(
        expo_data.make_expo_like(1000, seed=seed + 1)[1], y[:1000])


def test_the_training_table_is_one_table_and_the_rest_is_the_seeds():
    n, held = 2**18 + 1000, 5000
    a = expo_data.make_expo_like(n + held, seed=2**31 + 11, table_rows=n)
    b = expo_data.make_expo_like(n + held, seed=7, table_rows=n)
    for x, _, _ in (a, b):
        assert np.all(np.diff(x.indptr) == expo_data.STORED_PER_ROW)
        assert np.all(np.diff(x.indices.reshape(-1, 8), axis=1) > 0)
    # the same training rows whatever the seed ...
    assert (a[0][:n] != b[0][:n]).nnz == 0
    np.testing.assert_array_equal(a[1][:n], b[1][:n])
    np.testing.assert_array_equal(a[2][:n], b[2][:n])
    # ... the rows after them the seed's own, and no part of the table
    assert not np.array_equal(a[2][n:], b[2][n:])
    assert not np.array_equal(a[2][n:], a[2][:held])
    again = expo_data.make_expo_like(n + held, seed=7, table_rows=n)
    assert (again[0] != b[0]).nnz == 0
    # a longer table starts with the same rows
    c = expo_data.make_expo_like(n + 10, seed=7, table_rows=n + 10)
    assert (c[0][:n] != b[0][:n]).nnz == 0
    np.testing.assert_array_equal(c[1][:n], b[1][:n])


def test_walk_and_root_gains_against_dense_numpy():
    x, y, _ = expo_data.make_expo_like(20_000, seed=5)
    cols = reference_efb.Columns(x)
    dense = np.asarray(x.todense(), np.float64)
    # a hand-made tree: hot column 3 | numeric column 698 | hot column 40
    tree = reference.TextTree(
        num_leaves=4, split_feature=np.array([3, 698, 40]),
        threshold=np.array([0.5, 9.25, 1e-35]),
        decision_type=np.zeros(3, np.int64),
        left_child=np.array([1, -1, -3]), right_child=np.array([2, -2, -4]),
        leaf_value=np.array([0.1, -0.2, 0.3, -0.4]),
        split_gain=np.array([1.0, 1.0, 1.0]))
    want = reference.walk([tree], dense)
    np.testing.assert_array_equal(reference_efb.walk([tree], cols), want)
    leaf = reference_efb.leaf_of_rows(tree, cols)
    np.testing.assert_array_equal(tree.leaf_value[leaf], want)
    # the root gain of a one-hot column is reference.root_split's
    got = reference_efb.root_gains(cols, y, tree)
    ref = reference.root_split(dense, y, tree)
    np.testing.assert_allclose(got.gain_recomputed, ref.gain_recomputed,
                               rtol=1e-12)
    assert got.root_column == 3 and got.gain_best >= got.gain_recomputed


def test_the_cell_lists_its_metrics_and_the_benchmark_names_them():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = bench_run.load("workloads", "expo-train-10m")
    conf = bench_run.load("configs", cell["config"])
    assert conf["expect_route"]["efb"] == "bundled" and conf["reduced"] == []
    assert conf["n_features"] == expo_data.N_FEATURES
    named = {m["name"]: m for m in spec["per_layer"]}
    for name in ("scan_member_share", "member_scan_ns_per_row",
                 "hist_ms_per_iter"):
        assert bench_run.load("layer_metrics", name)["cells"] == [
            "expo-train-10m"]
        assert named[name]["workloads"] == ["expo-train-10m"]
    # the price of the membership decode is read like higgs's scan
    assert bench_run.load("layer_metrics", "member_scan_ns_per_row")[
        "args"] == bench_run.load("layer_metrics",
                                  "split_scan_ns_per_row")["args"]


def test_count_clock_times_a_fixed_number_of_iterations():
    """The window opens after the warm-up and closes after exactly
    ``iterations`` more, however fast they ran; nothing inside it waits
    for the device but the pull that closes it."""
    import types

    import numpy as np
    import pytest

    from kinds.train_sparse import CountClock

    class Stop(Exception):
        def __init__(self, *args):
            pass

    lgb = types.SimpleNamespace(
        callback=types.SimpleNamespace(EarlyStopException=Stop))
    compiles = types.SimpleNamespace(built=7)
    model = types.SimpleNamespace(
        model_to_string=lambda: "",
        _inner=types.SimpleNamespace(train_score=np.zeros(3)))
    clock = CountClock(lgb, {"warmup_iters": 3}, 40.0, compiles, None,
                       None, iterations=5)
    for it in range(7):
        clock(types.SimpleNamespace(iteration=it, model=model))
        assert clock.t_close is None
    assert clock.iters_at_open == 3 and len(clock.score_sums) == 1
    with pytest.raises(Stop):
        clock(types.SimpleNamespace(iteration=7, model=model))
    assert clock.iters_at_close - clock.iters_at_open == 5
    assert len(clock.score_sums) == 2 and clock.compiles_in_window == 0
