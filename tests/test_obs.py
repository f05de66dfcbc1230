"""Observability subsystem: phase tracer, device counters, harness.

Covers the ISSUE-2 acceptance contract: span nesting + JSON schema
round-trip, enable/disable semantics, counter exactness against a
deterministic tree, and — the critical one, since ISSUE 27 in both
directions — that the tracer changes no program: on before the booster
is built, on after it, or never, the grow program lowers to the same
text; barriers are ``<name>::wait`` children, JAX's builds are
``jax::*`` events, and the work counters come from the finished tree.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import COUNTER_NAMES, counters, tracer
from lightgbm_tpu.obs.report import (counter_totals, load_events,
                                     phase_summary)
from lightgbm_tpu.obs.tracer import Tracer


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Each test starts and ends with the global tracer off and empty
    (reset_run also clears events, the run ledger and warn-once sets)."""
    from lightgbm_tpu.obs import reset_run
    tracer.disable()
    tracer.close()
    tracer.reset()
    reset_run()
    yield
    tracer.disable()
    tracer.close()
    tracer.reset()
    reset_run()


def _make_problem(n=1200, f=6, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] - 0.7 * x[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(
        np.float32)
    return x, y


# ---------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------
def test_span_nesting_and_schema_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = Tracer()
    t.enable(path)
    with t.span("outer", tag="a"):
        with t.span("inner") as h:
            h.set(rows=7)
        with t.span("inner"):
            pass
    t.close()

    events, meta = load_events(path)   # every line must parse
    assert meta["schema"] == "lightgbm_tpu/trace/v1"
    spans = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["inner", "inner", "outer"]
    outer = spans[-1]
    for inner in spans[:2]:
        # children nest inside the parent's window, carry depth+parent
        assert inner["args"]["depth"] == 1
        assert inner["args"]["parent"] == "outer"
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert spans[0]["args"]["rows"] == 7
    assert outer["args"]["depth"] == 0
    # chrome-trace required keys on every span event
    for e in spans:
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    # file summary agrees with the in-memory accumulators
    fs = phase_summary(events)
    ms = t.summary()
    assert set(fs) == set(ms)
    for name in fs:
        assert fs[name]["count"] == ms[name]["count"]
        assert fs[name]["total_s"] == pytest.approx(
            ms[name]["total_s"], rel=1e-6, abs=1e-9)


def test_enable_disable_and_counter_events(tmp_path):
    t = Tracer()
    with t.span("off"):
        pass
    t.count("n", 1.0)
    assert t.events == [] and t.summary() == {}
    path = str(tmp_path / "c.jsonl")
    t.enable(path)
    with t.span("on"):
        t.count("n", 2.0)
        t.count("n", 3.0)
    t.disable()
    with t.span("off-again"):
        pass
    t.close()
    events, _ = load_events(path)
    assert counter_totals(events) == {"n": 5.0}
    assert t.counter_totals() == {"n": 5.0}
    assert [e["name"] for e in events if e["ph"] == "X"] == ["on"]


def test_tracer_enable_from_env(tmp_path, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("LGBM_TPU_TRACE", path)
    t = Tracer()   # fresh instance reads the env lazily
    assert t.enabled
    with t.span("via-env"):
        pass
    t.close()
    events, meta = load_events(path)
    assert meta["schema"] and [e["name"] for e in events] == ["via-env"]


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what the
    tracer mirrors, in order, without a profiler session."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))


@pytest.fixture
def mirror_log(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    return _FakeAnnotation.log


@pytest.mark.parametrize("annotating", [False, True])
@pytest.mark.parametrize("how", ["block_on", "wait"])
def test_barrier_is_a_nested_wait_span(how, annotating, mirror_log):
    """A barrier is the child span ``<name>::wait`` (deferred to span
    exit by ``block_on``, at once by ``wait``), nested inside its
    parent, whose duration still covers it; both are mirrored as
    ``obs::<name>`` only while annotating."""
    import jax.numpy as jnp
    t = Tracer()
    t.enable(None)
    t.annotate(annotating)
    value = jnp.ones((8,), jnp.float32)
    with t.span("outer"):
        with t.span("Phase", kidx=0) as h:
            getattr(h, how)(value)
            h.set(after_barrier=how == "wait")
    spans = [e for e in t.events
             if e["ph"] == "X" and not e["name"].startswith("jax::")]
    assert [e["name"] for e in spans] == ["Phase::wait", "Phase", "outer"]
    wait, phase, _ = spans
    assert wait["args"]["parent"] == "Phase" and wait["args"]["depth"] == 2
    assert phase["args"]["parent"] == "outer"
    assert phase["args"]["after_barrier"] == (how == "wait")
    assert wait["ts"] >= phase["ts"]
    assert wait["ts"] + wait["dur"] <= phase["ts"] + phase["dur"] + 1
    entered = [n for kind, n in mirror_log if kind == "enter"]
    assert entered == (["obs::outer", "obs::Phase", "obs::Phase::wait"]
                       if annotating else [])
    assert len(mirror_log) == 2 * len(entered)    # every one exited


def test_annotate_mirrors_the_spans_open_around_a_capture(mirror_log):
    """A capture started and stopped from inside a callback: the spans
    already open are mirrored from ``annotate(True)`` on and closed on
    the mirror at ``annotate(False)`` (innermost first), because the
    profiler keeps only annotations that begin and end while it runs;
    their own exit then mirrors nothing twice."""
    t = Tracer()
    t.enable(None)
    with t.span("Train::iteration"):
        with t.span("Callbacks"):
            t.annotate(True)
        with t.span("inside"):
            pass
        with t.span("Callbacks"):
            t.annotate(False)
    assert mirror_log == [
        ("enter", "obs::Train::iteration"), ("enter", "obs::Callbacks"),
        ("exit", "obs::Callbacks"),
        ("enter", "obs::inside"), ("exit", "obs::inside"),
        ("enter", "obs::Callbacks"),
        ("exit", "obs::Callbacks"), ("exit", "obs::Train::iteration")]
    assert [e["name"] for e in t.events] == [
        "Callbacks", "inside", "Callbacks", "Train::iteration"]


@pytest.mark.parametrize("built", [True, False])
def test_a_build_is_a_jax_event_inside_the_open_span(built):
    """A jit built inside an open span yields exactly one
    ``jax::backend_compile`` (JAX reports a cache fetch under that name
    too, with a ``jax::cache_load`` inside it) whose parent is that
    span, lasting the seconds JAX reported and ending inside the span;
    a call that builds nothing yields no ``jax::*`` event at all."""
    import jax
    fn = jax.jit(lambda v: v * 3.0 + 1.0)
    arg = np.arange(24, dtype=np.float32).reshape(4, 6)
    if not built:
        fn(arg).block_until_ready()          # built outside the span
    tracer.enable(None)
    with tracer.span("UpdateScore::tail") as h:
        h.block_on(fn(arg))
    events = tracer.events
    jax_events = [e for e in events if e["name"].startswith("jax::")]
    if not built:
        assert jax_events == []
        return
    compiles = [e for e in jax_events
                if e["name"] == "jax::backend_compile"]
    assert len(compiles) == 1
    span = next(e for e in events if e["name"] == "UpdateScore::tail")
    assert {"jax::trace", "jax::backend_compile"} <= {
        e["name"] for e in jax_events}
    for e in jax_events:
        assert e["ph"] == "X" and e["dur"] > 0
        assert e["args"]["parent"] == "UpdateScore::tail"
        assert e["ts"] + e["dur"] <= span["ts"] + span["dur"] + 1
    # a disabled tracer hears nothing
    tracer.disable()
    jax.jit(lambda v: v - 7.0)(arg).block_until_ready()
    assert len(tracer.events) == len(events)


# ---------------------------------------------------------------------
# work counters
# ---------------------------------------------------------------------
class _EnableAfter:
    """A callback that turns the tracer on after iteration ``at`` —
    the benchmark's way: the booster is long built by then."""

    def __init__(self, at):
        self.at = at

    def __call__(self, env):
        if env.iteration == self.at:
            tracer.enable(None)


@pytest.mark.parametrize("enabled_after", [None, 0])
def test_counters_match_tree_structure(tmp_path, enabled_after):
    """The host-derived counters must reproduce the trained model's
    actual tree structure: splits == num_leaves-1 summed, rows
    partitioned == the internal_count sum — with the tracer live
    before the booster is built, and enabled after its first
    iteration (the trees from then on)."""
    cbs = []
    if enabled_after is None:
        tracer.enable(str(tmp_path / "ctr.jsonl"))
    else:
        cbs = [_EnableAfter(enabled_after)]
    x, y = _make_problem()
    ds = lgb.Dataset(x, label=y, params={"max_bin": 63})
    bst = lgb.train({"objective": "binary", "num_leaves": 8,
                     "min_data_in_leaf": 20, "verbosity": -1,
                     "max_bin": 63}, ds, num_boost_round=3,
                    callbacks=cbs)
    bst._inner._flush_pending()
    models = bst._inner.models
    if enabled_after is not None:
        models = models[enabled_after + 1:]
        assert len(models) == 2
    splits_model = sum(int(t.num_leaves) - 1 for t in models)
    rows_model = sum(int(t.internal_count.sum()) for t in models
                    if t.num_leaves > 1)
    assert splits_model > 0
    tot = counters.totals()
    assert tot["splits"] == splits_model
    assert tot["rows_partitioned"] == pytest.approx(rows_model, abs=0.5)
    # the subtraction trick histograms at most half the partitioned rows
    # beyond the per-tree root pass
    assert 0 < tot["rows_histogrammed"] <= tot["rows_partitioned"] + 1
    # per-tree records line up with per-tree structure
    assert len(counters.per_tree) == len(models)
    for rec, t in zip(counters.per_tree, models):
        assert rec["splits"] == int(t.num_leaves) - 1
    assert set(rec) == set(COUNTER_NAMES)
    # ... and ride the Tree::grow spans as args, which is how a reducer
    # that keeps only X events reads them
    grows = [e for e in tracer.events if e["name"] == "Tree::grow"]
    assert len(grows) == len(models)
    for e, rec in zip(grows, counters.per_tree):
        assert {k: e["args"][k] for k in COUNTER_NAMES} == rec


def test_tracing_off_changes_nothing():
    """With the tracer off: training emits no events and records no
    counters, and the grow program is the one a live tracer gets too
    (no counter state, no extra outputs either way).

    Since ISSUE 7 the jaxpr-identity pins themselves live in the
    static analyzer's purity-pin REGISTRY (one source of truth for
    "knob off => identical program"; the analyzer CLI and ci_tier1.sh
    leg 6 run the same invariants) — this test drives that registry
    and keeps the behavioural end-to-end half."""
    import jax.numpy as jnp

    from lightgbm_tpu.analysis import registry
    from lightgbm_tpu.analysis.passes import purity
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams

    registry.collect()
    # the registered pins: a build under a live tracer == the default
    # build, and the obs tracer/ledger/reset lifecycle (ISSUE-5 hooks)
    # leaks nothing
    for pin in ("grow-tracer-live", "grow-obs-lifecycle"):
        findings = purity.check_pin(pin, registry.PURITY_PINS[pin])
        assert findings == [], \
            f"purity pin {pin} diverged: " \
            f"{[f.message for f in findings]}"

    # the grow program returns (tree, leaf_id) only, on real data
    hp = SplitHyperParams(min_data_in_leaf=2)
    n, f, B = 128, 8, 32
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.integers(0, 31, (n, f)).astype(np.uint8)),
            jnp.asarray(rng.normal(size=n).astype(np.float32)),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((f,), jnp.float32), jnp.full((f,), 31, jnp.int32),
            jnp.zeros((f,), bool), jnp.zeros((f,), bool), jnp.int32(0))
    grow_default = make_grow_fn(hp, num_leaves=8, padded_bins=B)
    assert len(grow_default(*args)) == 2   # (tree, leaf_id) only

    # end-to-end: an untraced booster records nothing
    assert not tracer.enabled
    x, yv = _make_problem(n=400)
    ds = lgb.Dataset(x, label=yv, params={"max_bin": 63})
    bst = lgb.train({"objective": "binary", "num_leaves": 6,
                     "verbosity": -1, "max_bin": 63}, ds,
                    num_boost_round=2)
    assert counters.totals()["splits"] == 0
    assert tracer.events == []


@pytest.mark.parametrize("entry", ["grow_serial", "grow_physical",
                                   "grow_stream"])
def test_grow_program_is_the_same_whenever_the_tracer_comes_on(entry):
    """Turning the tracer on changes no compiled program: the grow
    program (row-order, physical, and the stream route ``higgs`` takes)
    lowers to the same text with the tracer enabled before the program
    is built, after it, and never."""
    import jax

    from lightgbm_tpu.analysis import registry
    builder = registry.collect()[entry].builder

    def lowered(when):
        if when == "before":
            tracer.enable(None)
        fn, args = builder()
        if when == "after":
            tracer.enable(None)
        try:
            with tracer.span("Tree::grow"):
                return jax.jit(fn).lower(*args).as_text()
        finally:
            tracer.disable()

    never = lowered("never")
    assert "func.func" in never
    assert lowered("before") == never
    assert lowered("after") == never


def test_counters_from_tree_by_hand():
    """The numbers on a tree small enough to check by eye: root (100
    rows) splits 60 | 40, then the 60 splits 45 | 15.  Leaves are
    encoded ~leaf; padding past ``num_leaves`` is ignored.  The last
    six are the grow program's own count (``TreeArrays.side_miss``: four
    of them, six under the bundled comb), handed through."""
    from lightgbm_tpu.obs import counters_from_tree
    tree = dict(left_child=[1, -1, 7, 7], right_child=[-2, -3, 7, 7],
                internal_count=[100.0, 60.0, 999.0, 999.0],
                leaf_count=[45.0, 40.0, 15.0, 999.0])
    got = counters_from_tree(3, **tree, fused=True)
    # rows_histogrammed: the root pass + min(60, 40) + min(45, 15)
    assert got.tolist() == [2.0, 160.0, 100.0 + 40.0 + 15.0, 2.0, 0.0,
                            0.0, 0.0, 0.0, 0.0, 0.0, 0.0]
    # the hook ran at the second split only (its parent's 60 rows are
    # under the crossover), and its record named the 45: the 15 were
    # read again
    got = counters_from_tree(3, **tree, side_miss=np.array([1, 15, 1, 60]),
                             fused=True)
    assert got.tolist()[4:-1] == [1.0, 15.0, 1.0, 60.0, 0.0, 0.0]
    assert len(got) == len(COUNTER_NAMES)
    # under the bundled comb the program counts six: the last two are
    # the splits decided by a membership set and their parent rows
    got = counters_from_tree(
        3, **tree, side_miss=np.array([0, 0, 0, 0, 1, 100]), fused=True)
    assert got.tolist()[4:-1] == [0.0, 0.0, 0.0, 0.0, 1.0, 100.0]
    stump = counters_from_tree(1, [0], [0], [0.0], [77.0], fused=False,
                               scan_block_rows=32)
    assert stump.tolist() == [0.0, 0.0, 77.0] + [0.0] * 8


@pytest.mark.parametrize("block,shards,steps", [
    (0, 1, 0.0),        # no physical route: no scan, no steps
    (32, 1, 4 + 2),     # ceil(100 / 32) + ceil(60 / 32)
    (64, 1, 2 + 1),
    (128, 1, 1 + 1),    # a parent under a block still takes a step
    (16, 4, 4 * 2 + 4 * 1),     # 4 x ceil(25 / 16) + 4 x ceil(15 / 16)
])
def test_scan_steps_by_hand(block, shards, steps):
    """``scan_steps`` of the same tree: the grid steps of the partition
    scan, ``ceil(parent rows / R)`` a split - on a mesh ``shards x
    ceil(parent rows / shards / R)``, from the global counts - so that
    ``rows_partitioned`` over it is the rows a step really moved."""
    from lightgbm_tpu.obs import counters_from_tree
    got = counters_from_tree(
        3, left_child=[1, -1, 7, 7], right_child=[-2, -3, 7, 7],
        internal_count=[100.0, 60.0, 999.0, 999.0],
        leaf_count=[45.0, 40.0, 15.0, 999.0], fused=True,
        scan_block_rows=block, shards=shards)
    d = dict(zip(COUNTER_NAMES, got.tolist()))
    assert d["scan_steps"] == steps
    assert d["rows_partitioned"] == 160.0


# ---------------------------------------------------------------------
# trace phases end-to-end + TraceCallback
# ---------------------------------------------------------------------
def test_training_trace_has_nested_grow_phases(tmp_path):
    path = str(tmp_path / "train.jsonl")
    tracer.enable(path)
    x, y = _make_problem(n=800)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 63})
    cb = lgb.TraceCallback(logger=False)
    lgb.train({"objective": "binary", "num_leaves": 6, "verbosity": -1,
               "max_bin": 63, "metric": "binary_logloss"}, ds,
              num_boost_round=3, callbacks=[cb])
    tracer.close()
    events, _ = load_events(path)
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    parents = {
        "Train::iteration": None, "GBDT::TrainOneIter": "Train::iteration",
        "BeforeTrain": "GBDT::TrainOneIter", "Boosting": "BeforeTrain",
        "Boosting::wait": "Boosting",
        "GradSlice": "GBDT::TrainOneIter",
        "Tree::grow::wait": "Tree::grow", "WorkCounters": "Tree::grow",
        "UpdateScore": "GBDT::TrainOneIter",
        "UpdateScore::tail": "UpdateScore",
        "UpdateScore::set": "UpdateScore",
        "UpdateScore::wait": "UpdateScore",
        "Eval": "Train::iteration", "Callbacks": "Train::iteration"}
    for name, parent in parents.items():
        assert name in spans, f"missing span {name}"
        assert spans[name]["args"].get("parent") == parent, name
    # the censuses of a dispatched program run under it, between the
    # dispatch and its barrier (ISSUE 38): the device does not wait
    census = {e["args"]["phase"]: e for e in events
              if e["ph"] == "X" and e["name"] == "HbmCensus"}
    assert {p: e["args"]["parent"] for p, e in census.items()} == {
        "BeforeTrain": "GBDT::TrainOneIter", "Tree::grow": "Tree::grow",
        "UpdateScore": "UpdateScore"}
    for phase, wait in (("Tree::grow", "Tree::grow::wait"),
                        ("UpdateScore", "UpdateScore::wait")):
        assert census[phase]["ts"] + census[phase]["dur"] \
            <= spans[wait]["ts"]
    # the root-scale sampled probes went with their producer, the
    # legacy timer's twin of ``Tree::grow`` with the timer
    for name in ("ConstructHistogram", "FindBestSplits", "Split",
                 "GBDT::grow"):
        assert name not in spans
    assert spans["BeforeTrain"]["args"]["parent"] == "GBDT::TrainOneIter"
    # TraceCallback history carries the counter telemetry
    assert len(cb.history) == 3
    assert cb.history[-1]["counters"]["splits"] > 0
    # per-tree counter events landed in the file too
    assert counter_totals(events)["splits"] == \
        counters.totals()["splits"] > 0


def test_trace_callback_standalone():
    """TraceCallback without a pre-enabled tracer still produces
    per-iteration records (it enables in-memory tracing itself)."""
    x, y = _make_problem(n=500)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 63})
    cb = lgb.TraceCallback(logger=False)
    lgb.train({"objective": "binary", "num_leaves": 5, "verbosity": -1,
               "max_bin": 63}, ds, num_boost_round=2, callbacks=[cb])
    assert len(cb.history) == 2
    assert cb.history[1]["iter_wall_s"] is not None
    assert cb.history[1]["trees"] == 2


def test_hbm_live_bytes_counts_buffers():
    import jax.numpy as jnp

    from lightgbm_tpu.obs import hbm_live_bytes
    base = hbm_live_bytes()
    keep = jnp.ones((1024, 256), jnp.float32) * 2.0
    keep.block_until_ready()
    assert hbm_live_bytes() >= base + keep.nbytes
    del keep
