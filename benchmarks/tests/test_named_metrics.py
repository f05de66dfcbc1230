"""The per-layer metrics that read what the program names: ``::wait``
spans, ``jax::*`` build events, the work counters on ``Tree::grow`` and
the kernels' fixed names in the device trace.  First on spans and
slices made by hand, then on the recorded ones (``test_recorded_*``)."""
import pytest

import run as bench_run
import trace as bench_trace

NEW = ("split_scan_ms_per_iter", "split_scan_ns_per_row",
       "host_ms_per_iter", "iter_ms_worst", "build_ms_in_window")


def metric(name, obs):
    spec = bench_run.load("layer_metrics", name)
    return bench_run.module("reducers", spec["reducer"]).reduce(
        obs, **spec["args"])


def span(name, ts, dur, tid=1, **args):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "ph": "X",
            "args": args}


def iteration(t, grow_wait, rows, stall=0, build=None):
    """One traced iteration from ``t`` (us): 5 ms of the host's own, the
    rest waits; ``stall`` us more inside ``UpdateScore::wait``."""
    grow = grow_wait + 2_000
    spans = [
        span("Train::iteration", t, grow + 100_000 + stall + 3_000),
        span("Tree::grow", t + 1_000, grow, splits=254.0,
             rows_partitioned=float(rows), rows_histogrammed=rows / 3.0,
             fused_splits=254.0),
        span("Tree::grow::wait", t + 1_500, grow_wait),
        span("UpdateScore", t + 1_000 + grow, 100_000 + stall),
        span("UpdateScore::wait", t + 2_000 + grow, 99_000 + stall),
        span("Callbacks", t + grow + 101_000 + stall, 1_000),
    ]
    if build is not None:
        spans.append(span("jax::" + build, t + 1_200, 200))
    return spans


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_offered_to_the_cell_by_their_own_files(name):
    spec = bench_run.load("layer_metrics", name)
    assert spec["name"] == name and spec["cells"] == ["higgs-train-10m"]
    assert spec["moves"] == "train_iters_per_s"
    # ... and not by an edit to the cell's own file
    cell = bench_run.load("workloads", "higgs-train-10m")
    assert name not in cell["per_layer"]


def test_span_metrics_on_a_window_with_one_stalled_iteration():
    spans = []
    for i, stall in enumerate((0, 4_800_000, 0, 0, 0)):
        spans += iteration(i * 10_000_000, 2_300_000, 90e6, stall=stall)
    # what a kind also hands over: the checks after the window build
    spans += [span("jax::backend_compile", 60_000_000, 130_000)]
    obs = {"spans": spans, "slice": None, "counters": {}}
    assert metric("host_ms_per_iter", obs) == pytest.approx(6.0)
    # the longest iteration, outside its callbacks: the one that closes
    # the slice stops the profiler there, which is not the program's
    obs["spans"][-2]["dur"] += 1_175_000          # the last Callbacks
    obs["spans"][-7]["dur"] += 1_175_000          # and its iteration
    assert metric("iter_ms_worst", obs) == pytest.approx(
        (2_302_000 + 100_000 + 4_800_000 + 3_000 - 1_000) / 1e3)
    assert metric("build_ms_in_window", obs) == 0.0
    # a load inside an iteration is a reading; nested events count once
    obs["spans"] += [span("jax::backend_compile", 20_001_200, 300),
                     span("jax::cache_load", 20_001_250, 100)]
    assert metric("build_ms_in_window", obs) == pytest.approx(0.3)


@pytest.mark.parametrize("name", ["host_ms_per_iter",
                                  "build_ms_in_window"])
def test_a_program_without_wait_spans_gives_no_reading(name):
    """The parent commit's spans: barriers inside their parents, no
    ``jax::*`` events.  The metric is left out, nothing raises."""
    spans = [span("Train::iteration", 0, 2_400_000),
             span("Tree::grow", 1_000, 2_300_000)]
    assert metric(name, {"spans": spans, "slice": None,
                         "counters": {}}) is None
    assert metric(name, {"spans": [], "slice": None,
                         "counters": {}}) is None


def _slice(*ops):
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return bench_trace.SliceTrace(0, 5_000_000_000,
                                  {0: bench_trace.DeviceOps(ops)}, [])


def test_split_scan_metrics_read_the_named_kernel_and_the_span_arg():
    scan = ("%lgbm_split_scan.23 = (f32[1024,128]{1,0}, s32[2]{0}) "
            "custom-call(s32[16]{0} %sel), custom_call_target="
            "\"tpu_custom_call\"")
    sliced = _slice(
        ("%while.7 = (s32[]) while(%tuple)", 0, 4_000_000_000),
        (scan, 100, 1_800_000_100), (scan, 2_000_000_000, 3_800_000_000),
        ("%lgbm_split_scan_tail.2 = f32[8] fusion(%x)", 3_900_000_000,
         3_900_000_500),
        ("%lgbm_copyback.25 = f32[1024,128]{1,0} custom-call(%a)",
         1_800_000_200, 1_900_000_000))
    spans = iteration(0, 2_300_000, 90e6) + iteration(3e6, 2_300_000, 110e6) \
        + iteration(6e6, 2_300_000, 500e6)
    obs = {"spans": spans, "slice": sliced,
           "counters": {"slice_iterations": 2, "iterations": 16}}
    assert metric("split_scan_ms_per_iter", obs) == pytest.approx(1800.0)
    # 3.6 s over the first two trees' 200M row visits
    assert metric("split_scan_ns_per_row", obs) == pytest.approx(18.0)
    # fewer Tree::grow spans than the slice has iterations, or spans
    # that carry no counter (the parent): no reading
    obs["counters"]["slice_iterations"] = 4
    assert metric("split_scan_ns_per_row", obs) is None
    for e in spans:
        e["args"].pop("rows_partitioned", None)
    obs["counters"]["slice_iterations"] = 2
    assert metric("split_scan_ns_per_row", obs) is None


@pytest.mark.parametrize("name", ["split_scan_ms_per_iter",
                                  "split_scan_ns_per_row"])
def test_an_unnamed_kernel_gives_no_reading(name):
    """The parent's trace calls the scan ``%body.23``."""
    sliced = _slice(("%body.23 = (f32[1024,128]{1,0}) custom-call(%s)",
                     0, 1_800_000_000))
    obs = {"spans": iteration(0, 2_300_000, 90e6), "slice": sliced,
           "counters": {"slice_iterations": 1}}
    assert metric(name, obs) is None
    assert metric(name, dict(obs, slice=None)) is None


# ---------------------------------------------------------------------
# recorded: PR 27's first traced chip run of higgs-train-10m (my chip
# run, call 1, seed 2147483749, TPU v5 lite, 40 s).  The slice (three
# iterations) is cut as PR 25 cut its own: the chip's ``XLA Ops`` line
# and the mirrored host spans, without stats, gzipped; the span file
# holds the window's X events, the checks after the window too.
# ---------------------------------------------------------------------
import gzip                                           # noqa: E402
import json                                           # noqa: E402
import os                                             # noqa: E402
import shutil                                         # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    dst = tmp_path_factory.mktemp("trace") / "named-slice.xplane.pb"
    with gzip.open(os.path.join(DATA, "named-slice.xplane.pb.gz"),
                   "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with gzip.open(os.path.join(DATA, "named-spans.jsonl.gz"), "rt") as f:
        events = [json.loads(line) for line in f if line.strip()]
    opened = [e["ts"] for e in events if e["name"] == "bench::window_open"]
    spans = [e for e in events
             if e.get("ph") == "X" and e["ts"] >= opened[0]]
    return {"slice": bench_trace.read_slice(str(dst)), "spans": spans,
            "counters": {"iterations": 17, "slice_iterations": 3}}


# what that run's result line read
PRINTED = {
    "split_scan_ms_per_iter": 1812.2419086666666,
    "split_scan_ns_per_row": 18.92465317631328,
    "host_ms_per_iter": 16.21678800000856,
    "build_ms_in_window": 0.0,
    "grow_ms_per_iter": 2332.789512000005,
    "loop_ms_per_iter": 97.75446299998696,
    "device_idle.train": 0.7481154054242212,
}


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_recorded_run_reduces_to_what_it_printed(name, recorded):
    assert metric(name, recorded) == pytest.approx(PRINTED[name],
                                                   rel=1e-9), name


def test_recorded_worst_iteration_is_the_one_that_stops_the_profiler(
        recorded):
    """That run printed ``iter_ms_worst`` 3570.2 under the metric's
    first definition, the longest ``Train::iteration``: the iteration
    that closes the slice, whose ``Callbacks`` span holds the
    benchmark's ``stop_trace`` (1175.5 ms).  Outside the callbacks,
    which is what the metric's file asks for since, the window's
    longest iteration is 2470.1 ms, against a median of 2432.4."""
    red = bench_run.module("reducers", "span_own").reduce
    assert red(recorded, span="Train::iteration", stat="max") == \
        pytest.approx(3570.215672999993, rel=1e-9)
    assert red(recorded, span="Callbacks", stat="max") == pytest.approx(
        1175.468, abs=1e-3)
    assert metric("iter_ms_worst", recorded) == pytest.approx(
        2470.1, abs=0.05)


def test_recorded_slice_names_its_kernels_and_its_gaps(recorded):
    """The top ops carry the program's names (no ``%body.NN`` left), the
    scan metric agrees with the breakdown's own line, and every idle
    gap above 1 ms is named by a span of ``obs/tracer.py``'s tree - the
    slice's first gap by the bare ``GBDT::TrainOneIter``, which is what
    led to the ``GradSlice`` span (the run predates it)."""
    b = recorded["slice"].breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith("%lgbm_split_scan.7 custom-call")
    assert any(n.startswith("%lgbm_copyback.7 custom-call") for n in names)
    assert any(n.startswith("%lgbm_refresh.1 custom-call") for n in names)
    assert not any(n.startswith("%body") for n in names)
    assert b["device_ops"][0][1] == pytest.approx(5.436725726, rel=1e-9)
    assert b["device_ops"][0][1] / 3 * 1e3 == pytest.approx(
        PRINTED["split_scan_ms_per_iter"], rel=1e-9)
    gaps = [(n, s) for n, s in b["idle_gaps"] if s > 1e-3]
    assert [n for n, _ in gaps] == [
        "obs::BeforeTrain", "obs::HbmCensus", "obs::GBDT::TrainOneIter",
        "obs::Tree::grow::wait", "obs::Tree::grow",
        "obs::Tree::grow::wait", "obs::UpdateScore::set",
        "obs::UpdateScore::set", "obs::Callbacks",
        "obs::UpdateScore::wait"]
    assert gaps[0][1] == pytest.approx(0.01167762, rel=1e-9)
    # the rows the three trees' scans visited, off the spans
    rows = [e["args"]["rows_partitioned"] for e in recorded["spans"]
            if e["name"] == "Tree::grow"][:3]
    assert sum(rows) == pytest.approx(5.436725726 / 18.92465317631328e-9,
                                      rel=1e-9)
