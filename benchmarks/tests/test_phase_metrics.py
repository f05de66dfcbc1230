"""The per-layer metrics of PR 38 on slices made by hand: device time by
the phase the program wrote an op under (``device_phase_self_per`` over
the tracer's ``Program::ops`` tables) and idle time by the host span
beneath it (``idle_by_host_span``)."""
import json
import os

import pytest

import run as bench_run
import trace as bench_trace

PHASES = ("partition", "hist", "find", "glue", "leafrows", "root",
          "refresh", "merge", "score", "gradients")
CELLS = ["higgs-train-10m", "msltr-train-2m", "higgs-data4-train-21m",
         "expo-train-10m"]
NEW = tuple(f"phase_{p}_ms_per_iter" for p in PHASES) + (
    "phase_unnamed_share", "idle_tracer_ms_per_iter",
    "idle_host_ms_per_iter")
MS = 1_000_000                      # ns


def metric(name, obs):
    spec = bench_run.load("layer_metrics", name)
    return bench_run.module("reducers", spec["reducer"]).reduce(
        obs, **spec["args"])


def table(program, **ops):
    return {"name": "Program::ops", "ph": "X", "ts": 0.0, "dur": 0.0,
            "tid": 1, "args": {"program": program, "ops": ops}}


def op(name, shape, kind, at, ms):
    text = f"%{name} = {shape} {kind}(f32[8]{{0:T(128)}} %p), calls=%c"
    return (text, at * MS, (at + ms) * MS)


def sliced(ops, host=(), end=None):
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    end = end or max(o[2] for o in ops)
    return bench_trace.SliceTrace(
        0, end, {0: bench_trace.DeviceOps(ops)},
        [(bench_trace.SLICE_NAME, 0, end)] + list(host))


GROW = table(
    "grow",
    partition=["lgbm_split_scan.1 (f32[64,128], s32[1])",
               "fusion.5 s32[16]"],
    hist=["lgbm_hist.2 f32[4,128,256]"],
    glue=["while.11 (s32[], f32[255,10])"],
    leafrows=["fusion.9 s32[1024]", "sort (s32[1024], s32[1024])"],
    **{"": ["copy.3 f32[255,10]"]})
SCORE = table("score", score=["fusion.9 f32[1024]"],
              leafrows=["fusion.7 f32[8]"],
              **{"": ["fusion.5 s32[16]"]})


def slice_of_two_iterations():
    """One chip, 100 ms, every ms of it busy: a ``while`` of 60 ms with
    a scan, a fusion and a histogram inside, then the hand-off."""
    return sliced([
        op("while.11", "(s32[]{:T(128)}, f32[255,10]{1,0:T(8,128)})",
           "while", 0, 60),
        op("lgbm_split_scan.1",
           "(f32[64,128]{1,0:T(8,128)}, s32[1]{0:T(128)S(6)})",
           "custom-call", 2, 30),
        op("fusion.5", "s32[16]{0:T(128)}", "fusion", 32, 3),
        op("lgbm_hist.2", "f32[4,128,256]{2,1,0:T(8,128)}",
           "custom-call", 36, 20),
        op("copy.3", "f32[255,10]{1,0:T(8,128)}", "copy", 56, 1),
        op("fusion.9", "s32[1024]{0:T(1024)}", "fusion", 60, 10),
        op("sort", "(s32[1024]{0:T(1024)}, s32[1024]{0:T(1024)})",
           "sort", 70, 14),
        op("fusion.9", "f32[1024]{0:T(1024)S(1)}", "fusion", 84, 12),
        op("fusion.7", "f32[8]{0:T(128)}", "fusion", 96, 1),
        op("dynamic-slice.1", "f32[1024]{0:T(1024)}", "dynamic-slice",
           97, 3),
    ])


@pytest.mark.parametrize("name", NEW)
def test_a_metric_is_its_own_file_and_benchmark_json_lists_its_cells(name):
    spec = bench_run.load("layer_metrics", name)
    assert spec["name"] == name and spec["moves"] == "train_iters_per_s"
    assert spec["source"] == "device_trace" and spec["better"] == "lower"
    assert set(spec["cells"]) <= set(CELLS)
    for cell in spec["cells"]:
        assert name not in bench_run.load("workloads", cell)["per_layer"]
    with open(os.path.join(bench_run.REPO, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == spec["cells"]
    assert {k: entry[0][k] for k in ("unit", "layer", "source", "moves")} \
        == {k: spec[k] for k in ("unit", "layer", "source", "moves")}


def test_busy_time_is_split_over_the_phases_and_the_unnamed_share():
    obs = {"spans": [GROW, SCORE], "slice": slice_of_two_iterations(),
           "counters": {"slice_iterations": 2}}
    got = {p: metric(f"phase_{p}_ms_per_iter", obs) for p in PHASES}
    # the while keeps what its body's ops leave of it: 60 - 54
    assert got == {"partition": pytest.approx(15.0),
                   "hist": pytest.approx(10.0), "glue": pytest.approx(3.0),
                   # ``fusion.9`` is two ops: its shape tells them apart
                   "leafrows": pytest.approx((10 + 14 + 1) / 2),
                   "score": pytest.approx(6.0),
                   "find": None, "root": None, "refresh": None,
                   "merge": None, "gradients": None}
    # unnamed: under "" in its table (copy.3), ambiguous (fusion.5 is
    # partition's in one program and nobody's in the other), and in no
    # table at all (an eager program's dynamic-slice)
    unnamed = metric("phase_unnamed_share", obs)
    assert unnamed == pytest.approx(100.0 * (1 + 3 + 3) / 100)
    busy_ms = obs["slice"].devices[0].busy_ns() / MS
    named = sum(v for v in got.values() if v is not None) * 2
    assert named + unnamed / 100.0 * busy_ms == pytest.approx(busy_ms)


def test_an_ambiguous_name_lands_in_the_unnamed_share():
    only = sliced([op("fusion.5", "s32[16]{0:T(128)}", "fusion", 0, 4)])
    obs = {"spans": [GROW], "slice": only,
           "counters": {"slice_iterations": 1}}
    assert metric("phase_partition_ms_per_iter", obs) == pytest.approx(4.0)
    assert metric("phase_unnamed_share", obs) == 0.0
    obs["spans"] = [GROW, SCORE]
    assert metric("phase_partition_ms_per_iter", obs) is None
    assert metric("phase_unnamed_share", obs) == pytest.approx(100.0)
    # two captures describe a program twice: that is no disagreement
    obs["spans"] = [GROW, GROW]
    assert metric("phase_partition_ms_per_iter", obs) == pytest.approx(4.0)


@pytest.mark.parametrize("name", NEW[:11])
def test_a_program_without_tables_gives_no_reading(name):
    """The parent commit: no ``Program::ops`` in its spans.  The metric
    is left out, nothing raises; and so without a slice."""
    obs = {"spans": [], "slice": slice_of_two_iterations(),
           "counters": {"slice_iterations": 2}}
    assert metric(name, obs) is None
    assert metric(name, {**obs, "spans": [GROW], "slice": None}) is None


def test_a_gap_under_three_spans_splits_three_ways():
    """10 ms of idle from the end of one program to the start of the
    next, under BeforeTrain (3), HbmCensus (5) and GradSlice (2); its
    middle is HbmCensus's, which is all ``trace.breakdown`` says."""
    host = [("obs::GBDT::TrainOneIter", 18 * MS, 60 * MS),
            ("obs::BeforeTrain", 20 * MS, 23 * MS),
            ("obs::HbmCensus", 23 * MS, 28 * MS),
            ("obs::GradSlice", 28 * MS, 31 * MS),
            ("obs::Tree::grow", 31 * MS, 58 * MS),
            ("obs::WorkCounters", 50 * MS, 56 * MS)]
    s = sliced([op("fusion.1", "f32[8]{0}", "fusion", 0, 20),
                op("while.11", "(s32[])", "while", 30, 22)],
               host, end=60 * MS)
    assert [(e - b) // MS for b, e in s.idle_gaps(0)] == [10, 8]
    assert s.breakdown()["idle_gaps"][0] == ["obs::HbmCensus", 0.01]
    idle = bench_run.module("reducers", "idle_by_host_span")
    assert idle.idle_ns_by_span(s) == {
        "obs::BeforeTrain": 3 * MS, "obs::HbmCensus": 5 * MS,
        "obs::GradSlice": 2 * MS, "obs::WorkCounters": 4 * MS,
        "obs::Tree::grow": 2 * MS, "obs::GBDT::TrainOneIter": 2 * MS}
    obs = {"spans": [], "slice": s, "counters": {"slice_iterations": 2}}
    tracer = metric("idle_tracer_ms_per_iter", obs)
    host_ms = metric("idle_host_ms_per_iter", obs)
    assert tracer == pytest.approx((5 + 4) / 2)
    assert host_ms == pytest.approx((3 + 2 + 2 + 2) / 2)
    # the two are the chip's idle time, which device_idle.train reads
    share = metric("device_idle.train", obs)
    assert (tracer + host_ms) * 2 == pytest.approx(
        share / 100.0 * s.window_s * 1e3)


def test_idle_outside_every_span_is_the_hosts():
    s = sliced([op("fusion.1", "f32[8]{0}", "fusion", 5, 10)],
               [("obs::HbmCensus", 8 * MS, 12 * MS)], end=20 * MS)
    obs = {"spans": [], "slice": s, "counters": {"slice_iterations": 1}}
    assert metric("idle_tracer_ms_per_iter", obs) == 0.0
    assert metric("idle_host_ms_per_iter", obs) == pytest.approx(10.0)
    assert metric("idle_host_ms_per_iter", {**obs, "counters": {}}) is None
