"""The data-parallel cell's yardstick: ``reference_mesh.py`` against a
brute-force loop, the two mesh reducers on a slice made by hand, and a
rehearsal of the cell on four forced host devices."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import reference
import reference_mesh
import run as bench_run
import trace as bench_trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "higgs-data4-train-21m"


def small_tree():
    """Three splits, four leaves: x0 <= 0 ? (x1 <= -0.5 ? L0 : L2)
    : (x2 <= 0.25 ? L1 : L3)."""
    return reference.TextTree(
        num_leaves=4, split_feature=np.array([0, 1, 2]),
        threshold=np.array([0.0, -0.5, 0.25]),
        decision_type=np.zeros(3, np.int64),
        left_child=np.array([1, -1, -2]), right_child=np.array([2, -3, -4]),
        leaf_value=np.zeros(4), split_gain=np.ones(3))


def test_leaf_sums_against_a_brute_force_loop():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(500, 3)).astype(np.float32)
    x[7, 0] = 0.0                    # on a threshold: goes left
    y = (rng.random(500) < 0.4).astype(np.float32)
    t = small_tree()
    ref = reference_mesh.binary_leaf_sums(t, x, y)
    p = float(np.mean(y.astype(np.float64)))
    count, g, h = [0] * 4, [0.0] * 4, [0.0] * 4
    for i in range(500):
        node = 0
        while node >= 0:
            left = float(x[i, t.split_feature[node]]) <= t.threshold[node]
            node = int(t.left_child[node] if left else t.right_child[node])
        leaf = -node - 1
        assert ref.leaf[i] == leaf
        count[leaf] += 1
        g[leaf] += p - float(y[i])
        h[leaf] += p * (1.0 - p)
    assert ref.leaf[7] in (0, 2)
    assert ref.count.tolist() == count and sum(count) == 500
    sum_g, sum_h = ref.sums()
    np.testing.assert_allclose(sum_g, g, rtol=0, atol=1e-9)
    np.testing.assert_allclose(sum_h, h, rtol=0, atol=1e-9)
    want = [-0.1 * g[k] / h[k] + np.log(p / (1 - p)) for k in range(4)]
    np.testing.assert_allclose(ref.leaf_values(0.1), want, atol=1e-12)


def test_bf16_operands_and_a_bf16_merge_are_further_off_in_that_order():
    assert reference_mesh.to_bf16(0.4985).item() == 0.498046875
    assert reference_mesh.to_bf16(-0.5015).item() == -0.5
    assert reference_mesh.to_bf16(np.float32(1.00390625)).item() == 1.0
    rng = np.random.default_rng(5)
    x = rng.normal(size=(40_000, 3)).astype(np.float32)
    y = (rng.random(40_000) < 1 / (1 + np.exp(-2 * x[:, 0] + x[:, 1]))
         ).astype(np.float32)
    ref = reference_mesh.binary_leaf_sums(small_tree(), x, y)
    plain = ref.leaf_values(0.1)
    stated = ref.leaf_values(0.1, operands="bf16")
    below, below_count = ref.merged_in_bf16(0.1, shards=4)
    assert below_count.sum() != 40_000 or np.any(below_count != ref.count)
    # the operands' rounding is the same on every row, so it shifts
    # every leaf alike; a merge in bf16 rounds each shard's sums apart
    assert 1e-5 < np.max(np.abs(stated - plain)) < 1e-3
    assert np.max(np.abs(below - stated)) > 1e-5
    assert np.ptp(stated - plain) < np.ptp(below - stated)


def test_leaf_count_line_of_the_model_text():
    text = ("tree\nversion=v4\n\nTree=0\nnum_leaves=3\nleaf_value=1 2 3\n"
            "leaf_count=5 7 9\n\nTree=1\nnum_leaves=2\nleaf_count=1 2\n"
            "\nend of trees\n")
    assert reference_mesh.tree0_leaf_counts(text).tolist() == [5, 7, 9]


def test_root_histogram_is_a_count_and_a_label_sum():
    bins = np.array([0, 1, 1, 2, 2, 2])
    y = np.array([1, 0, 1, 0, 0, 1], np.float32)
    h = reference_mesh.root_histogram(bins, y, 4)
    np.testing.assert_allclose(h[:, 1], 0.25 * np.array([1, 2, 3, 0]))
    np.testing.assert_allclose(h[:, 0], [0.5 - 1, 1.0 - 1, 1.5 - 1, 0.0])


# ---- the reducers, on a slice of two device planes made by hand ------
def metric(name, obs):
    spec = bench_run.load("layer_metrics", name)
    return bench_run.module("reducers", spec["reducer"]).reduce(
        obs, **spec["args"])


def hand_made_slice():
    """Device 0: a 1000 ns ``while`` that holds a 600 ns scan, a 100 ns
    reduce-scatter and a 50 ns all-reduce, then a 200 ns all-gather on
    its own.  Device 1 is busy elsewhere and must not be read."""
    ops0 = [("%while.3 = (s32[]) while(...)", 0, 1000),
            ("%lgbm_split_scan.8 = f32[8] custom-call(...)", 100, 700),
            ("%reduce-scatter.2 = f32[8,256,2] reduce-scatter(...)",
             700, 800),
            ("%all-reduce.5 = s32[] all-reduce(...)", 800, 850),
            ("%all-gather.1 = f32[32] all-gather(...)", 1200, 1400),
            # reads a collective's result, is none: device_op_share's
            # match on the whole text counts it, the per-split one not
            ("%fusion.3 = f32[8] fusion(f32[8] %collective-permute-done.2)",
             1400, 1430),
            ("%pmax.24 = (f32[2], f32[2]) all-reduce(f32[2] %x, f32[2] %y)",
             1500, 1520)]
    ops1 = [("%all-reduce.5 = s32[] all-reduce(...)", 0, 2000)]
    return bench_trace.SliceTrace(
        0, 2000, {0: bench_trace.DeviceOps(ops0),
                  1: bench_trace.DeviceOps(ops1)})


def grow_span(ts, splits, shard_rows=None):
    args = {"splits": float(splits), "rows_partitioned": 1e6}
    if shard_rows is not None:
        args.update(shard_rows_partitioned=shard_rows,
                    shard_rows_max=max(shard_rows))
    return {"name": "Tree::grow", "ts": ts, "dur": 10, "tid": 1, "ph": "X",
            "args": args}


def test_collective_metrics_read_device_0s_self_time():
    spans = [grow_span(0, 4), grow_span(100, 3), grow_span(200, 254)]
    obs = {"spans": spans, "slice": hand_made_slice(),
           "counters": {"slice_iterations": 2}}
    # 100 + 50 + 200 + 20 ns of collectives (and the 30 ns fusion that
    # reads one) in 1250 ns busy
    assert metric("collective.train", obs) == pytest.approx(100 * 400 / 1250)
    # ... over the 4 + 3 splits of the slice's two trees, in us
    assert metric("collective_us_per_split", obs) == pytest.approx(
        370e-9 * 1e6 / 7)
    # the slice holds more iterations than the window has spans for
    obs["counters"]["slice_iterations"] = 4
    assert metric("collective_us_per_split", obs) is None


def test_collective_metrics_on_one_chip_find_nothing():
    sliced = bench_trace.SliceTrace(0, 1000, {0: bench_trace.DeviceOps(
        [("%lgbm_split_scan.8 = f32[8] custom-call(...)", 0, 900)])})
    obs = {"spans": [grow_span(0, 4)], "slice": sliced,
           "counters": {"slice_iterations": 1}}
    assert metric("collective_us_per_split", obs) is None
    assert metric("collective.train", obs) == 0.0
    obs["slice"] = None
    assert metric("collective_us_per_split", obs) is None


def test_shard_scan_skew_is_the_fullest_shard_over_the_mean():
    spans = [grow_span(0, 4, [100.0, 100.0, 100.0, 100.0]),
             grow_span(100, 3, [130.0, 90.0, 90.0, 90.0])]
    obs = {"spans": spans, "slice": None, "counters": {}}
    # (100 + 130) / (100 + 100) - 1
    assert metric("shard_scan_skew", obs) == pytest.approx(15.0)
    assert metric("shard_scan_skew", {**obs, "spans": spans[:1]}) == 0.0
    # a program that does not count by shard: left out, not an error
    obs["spans"] = [grow_span(0, 4)]
    assert metric("shard_scan_skew", obs) is None


def test_the_cell_lists_the_mesh_metrics_and_the_benchmark_names_them():
    cell = bench_run.load("workloads", CELL)
    config = bench_run.load("configs", cell["config"])
    assert cell["kind"] == "train_mesh" and cell["chips"] == 4
    assert config["expect_route"]["hist_merge"] == "scatter"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mesh = [m for m in spec["per_layer"] if m["layer"] == "mesh"]
    assert sorted(m["name"] for m in mesh) == [
        "collective.train", "collective_us_per_split", "shard_scan_skew"]
    for m in mesh:
        assert m["workloads"] == [CELL] and m["name"] in cell["per_layer"]


def test_the_cell_rehearses_on_four_forced_host_devices():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11),
         "--seconds", "3", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 3, out.stderr[-2000:]
    notes = {}
    for line in out.stdout.splitlines():
        if line.startswith('{"note"'):
            n = json.loads(line)
            notes[n["note"]] = n
    assert notes["route"]["learner"] == "data"
    assert notes["route"]["n_shards"] == 4
    assert notes["route"]["hist_merge"] == "scatter"
    leaves = notes["tree0_leaves"]
    assert leaves["leaf_count_max_abs_diff"] == 0
    assert leaves["rows_in_another_leaf"] == 0
    assert leaves["leaf_count_sum"] == leaves["rows"]
    assert "It would have read" in out.stderr
    assert "shard_scan_skew" in out.stderr
    assert "grow_ms_per_iter" in out.stderr
