"""The reduction from trace and spans to per-layer metrics."""
import gzip
import json
import os
import shutil

import pytest

import run as bench_run
import trace as bench_trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ops(*events):
    return bench_trace.DeviceOps(sorted(events, key=lambda o: (o[1], -o[2])))


def test_busy_is_the_union_and_self_time_leaves_out_the_nested():
    d = ops(("while", 0, 100), ("body", 10, 40), ("body", 50, 90),
            ("fusion", 60, 70), ("tail", 150, 200))
    assert d.busy_intervals() == [(0, 100), (150, 200)]
    assert d.busy_ns() == 150
    assert d.self_ns_by_name() == {"while": 30, "body": 60, "fusion": 10,
                                   "tail": 50}


def test_idle_gaps_are_named_by_the_host_span_open_then():
    s = bench_trace.SliceTrace(
        0, 300, {0: ops(("a", 0, 100), ("b", 150, 200))},
        [("bench::slice", 0, 300), ("obs::Train::iteration", 90, 210),
         ("obs::UpdateScore", 120, 140)])
    assert s.idle_gaps() == [(100, 150), (200, 300)]
    b = s.breakdown()
    assert b["idle_gaps"] == [["outside any host span", 1e-7],
                              ["obs::UpdateScore", 5e-8]]
    idle = bench_run.module("reducers", "device_idle").reduce(
        {"slice": s, "spans": [], "counters": {}})
    assert idle == pytest.approx(50.0)
    share = bench_run.module("reducers", "device_op_share").reduce(
        {"slice": s}, patterns=["b"])
    assert share == pytest.approx(100.0 / 3)


def test_short_op_name():
    assert bench_trace.short_op_name(
        "%fusion.6 = f32[10]{0} fusion(f32[255]{0} %x), kind=kCustom"
    ) == "%fusion.6 fusion"
    name = bench_trace.short_op_name(
        "%body.23 = (f32[8,128]{1,0}, s32[2]{0}) custom-call(s32[] %a), "
        "custom_call_target=\"tpu_custom_call\"")
    assert name.startswith("%body.23 custom-call (f32[8,128]")


def test_span_self_median():
    def span(name, ts, dur, **args):
        return {"name": name, "ts": ts, "dur": dur, "tid": 1, "ph": "X",
                "args": args}
    spans = []
    for i, grow in enumerate((200_000, 220_000, 900_000)):
        t = i * 2_000_000
        spans += [span("Train::iteration", t, grow + 30_000),
                  span("Tree::grow", t + 10_000, grow),
                  span("ConstructHistogram", t + 11_000, 5_000,
                       sample="root")]
    red = bench_run.module("reducers", "span_self_median").reduce
    obs = {"spans": spans}
    assert red(obs, span="Tree::grow",
               minus=["ConstructHistogram"]) == pytest.approx(215.0)
    assert red(obs, span="Train::iteration",
               minus=["Tree::grow"]) == pytest.approx(30.0)
    assert red(obs, span="Eval") is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The traced slice (three iterations) of this PR's first traced chip
    run of ``higgs-train-10m`` (seed 77, TPU v5 lite), cut to what
    ``read_slice`` reads - the chip's ``XLA Ops`` line and the mirrored
    host spans, without their stats - and gzipped."""
    src = os.path.join(DATA, "train-slice.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("trace") / "train-slice.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return bench_trace.read_slice(str(dst))


def test_recorded_trace_reduces_to_what_that_run_printed(recorded):
    assert sorted(recorded.devices) == [0]
    assert recorded.window_s == pytest.approx(7.228558929, rel=1e-9)
    assert recorded.busy_s() == pytest.approx(7.082874122, rel=1e-9)
    idle = bench_run.module("reducers", "device_idle").reduce(
        {"slice": recorded, "spans": [], "counters": {}})
    assert idle == pytest.approx(2.015405953398719, rel=1e-9)
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    name, seconds = b["device_ops"][0]
    assert name.startswith("%body.23 custom-call (f32[10505216,128]")
    assert seconds == pytest.approx(5.437357605, rel=1e-9)
    assert b["idle_gaps"][0] == ["obs::Tree::grow",
                                 pytest.approx(0.100266434, rel=1e-9)]
    # no collective ran on one chip
    share = bench_run.module("reducers", "device_op_share").reduce(
        {"slice": recorded}, patterns=["all-reduce", "reduce-scatter"])
    assert share == 0.0


def test_recorded_spans_reduce_to_what_that_run_printed():
    """The same run's span file through ``kinds/train._window_spans``'s
    rule (spans that began after ``bench::window_open``) and the two
    span metrics' own files."""
    with gzip.open(os.path.join(DATA, "train-spans.jsonl.gz"), "rt") as f:
        events = [json.loads(line) for line in f if line.strip()]
    opened = [e["ts"] for e in events if e["name"] == "bench::window_open"]
    spans = [e for e in events if e.get("ph") == "X" and e["ts"] >= opened[0]]
    assert sum(e["name"] == "Tree::grow" for e in spans) == 16
    want = {"grow_ms_per_iter": 2323.1306039999918,
            "loop_ms_per_iter": 98.01681399999023}
    for name, value in want.items():
        spec = bench_run.load("layer_metrics", name)
        got = bench_run.module("reducers", spec["reducer"]).reduce(
            {"spans": spans}, **spec["args"])
        assert got == pytest.approx(value, rel=1e-9), name
