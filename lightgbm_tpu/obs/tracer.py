"""Phase tracer: nested wall-clock spans with device barriers.

The reference's ``Common::Timer`` / ``FunctionTimer``
(utils/common.h:973) as a structured trace: nested spans, JSON-lines
output that doubles as Chrome-trace events, per-phase accumulators,
counter channels, and the phase of the algorithm every device op
serves.

**The rule: turning the tracer on, at any moment, changes no compiled
program and dispatches no extra one; it only adds names.**  It may be
enabled before the booster is built (``LGBM_TPU_TRACE=/path.jsonl``,
read at first use) or after (``tracer.enable(path)``, at any
iteration): the grow program and every other program are the same
either way (``tests/test_obs.py`` holds the lowered text equal, the
``grow-tracer-live`` purity pin the jaxpr).  Disabled (the default)
every ``span`` entry is a single attribute check.

Output format: one JSON object per line.  The first line is a metadata
record carrying the schema version; every span line is a valid Chrome
"complete" event (``ph: "X"``, microsecond ``ts``/``dur``), so
``python -m lightgbm_tpu.obs report --chrome out.json`` only has to
wrap the lines in an array for chrome://tracing / Perfetto.

Set-up, once a booster (both only in a trace enabled before the
booster is built)::

    Dataset::bundle                     io/dataset_core.py: find_bundles
                                        over the sampled rows; args
                                        features_bundled, bundles,
                                        conflict_rows
    Train::layout                       models/gbdt.py, closing the device
                                        layout and the route decision;
                                        args phys_cols,
                                        logical_features, bundles,
                                        comb_cols, comb_line_bytes,
                                        comb_planes, hist_tiles,
                                        hist_lo_n
                                        (GBDT.layout_info())

The span tree of one boosting iteration (serial learner, fast path;
the names the per-layer metrics of ``benchmarks/`` are keyed on)::

    Train::iteration                    engine.py, one per iteration
      GBDT::TrainOneIter
        BeforeTrain                     bagging, boost-from-average
          Boosting                      gradient pass (not in stream mode);
                                        args: the objective's span_args()
                                        (ranking: queries, buckets,
                                        pairs_visited, pair_slots)
            Boosting::wait
        HbmCensus                       live-array census (obs mem);
                                        arg phase, here BeforeTrain
        GradSlice                       eager grad[k], hess[k]
        Tree::grow                      args: the work counters
                                        (obs/counters.py COUNTER_NAMES;
                                        under the bundled comb
                                        member_splits and rows_member
                                        are counted by the grow program)
                                        and scan_block_rows, the rows a
                                        grid step of the partition scan
                                        moves (scan_steps counts them);
                                        comb_planes, hist_tiles (tiles a
                                        comb histogram sweeps),
                                        hist_block_rows and hist_lo_n
                                        (the split of a bin:
                                        bin = hi * lo_n + lo), from the
                                        built program's shapes;
                                        tree_depth, the finished tree's
                                        deepest leaf (the walk steps a
                                        row of it needs), from its child
                                        arrays (``depth`` is every
                                        span's nesting depth)
          HbmCensus                     phase Tree::grow: under the
                                        running program, so the device
                                        does not wait for the walk
          Tree::grow::wait              the device runs the grow program
          WorkCounters                  pull of the tree's small arrays
        UpdateScore
          UpdateScore::tail             dispatch of the score/valid tail;
                                        with valid sets args valid_sets,
                                        valid_rows (rows replayed),
                                        replay_matmul_rows (of them, the
                                        rows the decision-matrix route
                                        replayed: u8 bins) and
                                        replay_steps (node decisions a
                                        row: the tree's inner nodes),
                                        summed over the sets
          UpdateScore::set              eager slice + .at[].set (on the
                                        unpaged stream route the valid
                                        sets' alone: the train score is
                                        not kept every tree)
          HbmCensus                     phase UpdateScore, likewise
          UpdateScore::wait             on the tail's outputs
        StallProbe                      every 8th iteration
        FlushPending                    every 32nd iteration
      Eval                              when a metric is due: one data
                                        set's metrics a call (args
                                        datasets, metrics, rows)
        Eval::wait                      the metric programs' scalars
      Callbacks                         cbs_after (a benchmark's pulls)
        TrainScore::materialise         wherever ``GBDT.train_score`` is
                                        read on the unpaged stream route
                                        with trees un-pulled (arg
                                        trees_behind): one run of the
                                        grower's ``pull_score``; also an
                                        obs event of the same name
          TrainScore::materialise::wait

Device work is asynchronous under JAX: a span that covers a dispatch
measures only the enqueue unless it blocks.  ``span(...)`` yields a
handle; ``handle.block_on(x)`` makes span exit run
``jax.block_until_ready(x)`` before the clock stops, and
``handle.wait(x)`` runs the same barrier at once.  Either way the
barrier is recorded as a child span ``<name>::wait``, so a parent's
time outside its ``::wait`` child is its own: dispatch and host work.
The parent's duration is what it always was.

Build events: while enabled, the tracer listens to JAX's monitoring
durations and records each as an ``X`` event that ends at the
callback and lasts the reported seconds, with ``parent`` the span open
on that thread: ``jax::trace`` (a jaxpr was traced), ``jax::lower``
(lowered to MLIR), ``jax::backend_compile`` (compiled, or fetched:
JAX reports the fetch under this name too) and ``jax::cache_load``
(read from the persistent cache, inside the former).  A stall that is
a load or a retrace says so, inside the span it happened in.

Work counters are derived on the host from the finished tree
(``obs/counters.counters_from_tree``; four of them - six under the
bundled comb - are counted by the grow program, traced or not, and come
with the tree) after the
``Tree::grow`` barrier, and set as args of that span.

Phases: the busy time of a capture, named by the program.  Every op
of the three programs an iteration dispatches is traced under exactly
one ``jax.named_scope("lgbm.<phase>")`` - always, traced or not: a
scope is metadata of the ops under it, and the compiled program with
its ``metadata={...}`` stripped is the same text without the scopes
(``tests/test_chip_compile.py``).  The names mirror the reference's
``FunctionTimer`` ones (serial_tree_learner.cpp: BeforeTrain,
ConstructHistograms, FindBestSplits, Split)::

    lgbm.root        ops/grow.py: the per-tree start.  Off the stream
                     route g / h / w gathered by row id and written
                     into the comb, the bf16 rounding, the root sums,
                     the root histogram, the root's finder call; on it
                     the little that is left of those
    lgbm.hist        the smaller child's histogram: lgbm_hist, the
                     scan hook's accumulator taken apart, the pool's
                     reads and writes, the subtraction
    lgbm.merge       the mesh learners' collectives, wherever they are
                     written (the root's too): psum_scatter / psum of
                     histograms and row counts, sync_best's election
    lgbm.find        the finder over the two children: lgbm_apply_find
                     or the XLA tail, find_best_split_segments
    lgbm.partition   lgbm_split_scan (the fused scan) or
                     lgbm_partition_scan, lgbm_copyback, what they are told
                     (the descriptor, bundled_split_members), the
                     segment table's writes
    lgbm.glue        what a split does besides: leaf election, state
                     and tree-array writes, the counters the program
                     keeps, the ``while`` itself; and the tree arrays
                     taken out of the state after the last split
    lgbm.leafrows    end of tree: per-position leaf (and value) from
                     the segment table, the row-id decode, the
                     un-permute to row order (scatter and its sort)
    lgbm.refresh     stream route: lgbm_refresh (scores, gradients, the
                     next tree's root histogram)
    lgbm.score       models/gbdt.py: the jitted score tail (train
                     score, the replay replica)
    lgbm.valid       inside the score tail, each valid set's replay of
                     the new tree (ops/predict.predict_leaf_bins: the
                     tree's path matrix, built once a tail, and a
                     block of rows a trip, its node columns and leaves
                     by two matmuls; the lock-step walk on i32 bins),
                     the leaf-table lookup and the add.  A tail
                     without valid sets has no op here
    lgbm.gradients   models/gbdt.py: the objective's gradient program
                     (not on the stream route, which has none)
    lgbm.eval        metric/metrics.py: a metric's device program (the
                     AUC's sort and segment sums, NDCG, the multiclass
                     logloss and error)

``phased`` / ``next_phase`` cut a long function at its seams, ``phase``
scopes a block or decorates a function (below).  An instruction the
compiler makes of several ops (a fusion) carries its root's scope; the
ops of a library's own nested ``jit`` (``jnp.cumsum``) carry the scope
of the call.

``Program::ops``: a capture's ``XLA Ops`` line names an event by its
HLO instruction (``%fusion.9 = f32[32,256]{...} fusion(...)``), which
the compiler numbers anew with every change; the phase is in the
instruction's ``metadata``, which the capture does not print.  So the
first iteration the tracer is live, each dispatch site hands
``tracer.program(name, jitted, *args)`` its program (``grow``: the
growers in ops/grow.py and parallel/data_parallel.py; ``pull_score``:
ops/grow.py, its ops ``leafrows``'s; ``score`` and
``gradients``: models/gbdt.py; ``eval:<data set>:<metric>``: each
metric's device program, models/gbdt.py ``eval``), which keeps ``{phase: [op_key, ...]}``
parsed from the compiled module's text (``program_ops``; ``""`` holds
the instructions under no phase) - under a span ``Program::table``
(arg ``program``); nothing is built for it: handed the dispatch's own
arguments, ``lower`` and ``compile`` find its trace and its executable
in JAX's caches -, and ``annotate(True)``
writes one zero-length ``X`` event ``Program::ops`` (args ``program``,
``ops``) for each into the capture's span file.  ``op_key`` is the
instruction name and the result's shape without layouts,
``fusion.9 f32[32,256]``.
A reader books an op whose key two programs put under different phases
as ambiguous; the eager one-op programs of an iteration (``GradSlice``,
``UpdateScore::set``) are in no table.

Xplane correlation: while ``tracer.annotate(True)`` — a profiler
capture is live — every span additionally enters a
``jax.profiler.TraceAnnotation("obs::<name>")``, so the capture's host
plane carries the span names on the device's clock.  Spans already
open on the calling thread when annotation is switched on are mirrored
from that moment, and spans still open when it is switched off are
closed on the mirror at that moment, so the edges of a capture taken
from inside a callback are named too.
"""
from __future__ import annotations

import atexit
import contextlib
import functools
import json
import os
import re
import threading
import time
import weakref
from typing import Dict, List, Optional

TRACE_SCHEMA = "lightgbm_tpu/trace/v1"
TRACE_ENV = "LGBM_TPU_TRACE"
WAIT_SUFFIX = "::wait"

# JAX's monitoring durations, by the names the installed JAX gives
# them (jax/_src/dispatch.py, jax/_src/compiler.py), and the build
# event each is recorded as
BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax::trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax::lower",
    "/jax/core/compile/backend_compile_duration": "jax::backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax::cache_load",
}
# JAX has no public way to take a listener back, so one module-level
# listener is registered at the first enable() and hands each duration
# to the tracers that are enabled at that moment
_LISTENING: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_registered = False

# The phases of the algorithm a device op can serve (the docstring's
# table): every op of the grow, score and gradient programs is traced
# under exactly one ``lgbm.<phase>`` of ``jax.named_scope``, which the
# compiler carries into each instruction's ``metadata={op_name=...}``.
PHASES = ("root", "hist", "merge", "find", "partition", "glue",
          "leafrows", "refresh", "score", "valid", "gradients", "eval")
PHASE_PREFIX = "lgbm."
_phase_local = threading.local()


def _phase_scope(name: str):
    if name not in PHASES:
        raise ValueError(f"no phase {name!r}: one of {PHASES}")
    import jax
    return jax.named_scope(PHASE_PREFIX + name)


def _jaxpr_level():
    """What tells one jaxpr being traced from the next: JAX starts
    each (a jitted function, a loop body, a branch) with an empty name
    stack, so a phase held open in one is not open in the other."""
    import jax
    return jax.core.get_opaque_trace_state()


class _PhaseCursor:
    """The one phase open in a ``phased`` function, and the scope that
    holds it open."""

    __slots__ = ("name", "level", "_open")

    def __init__(self) -> None:
        self.name = None
        self.level = _jaxpr_level()
        self._open = contextlib.ExitStack()

    def switch(self, name) -> None:
        self._open.close()
        self.name = name
        if name is not None:
            self._open.enter_context(_phase_scope(name))


def _cursor():
    """The cursor of the jaxpr being traced, or None."""
    cursor = getattr(_phase_local, "cursor", None)
    if cursor is not None and cursor.level == _jaxpr_level():
        return cursor
    return None


def phased(fn):
    """Decorator of a function that passes through several phases:
    inside it ``next_phase(name)`` closes the phase open before and
    holds ``lgbm.<name>`` open until the next seam or the function's
    end, so a long function is cut at its seams by one line each and
    is not re-indented.  A function traced as its own jaxpr (a jitted
    function, a loop body, a branch) that has seams is decorated
    itself; called in line from another ``phased`` function, it moves
    that one's cursor and hands it back as it found it."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        outer = getattr(_phase_local, "cursor", None)
        cursor = _cursor()          # called in line: the caller's
        before = None if cursor is None else cursor.name
        if cursor is None:
            cursor = _phase_local.cursor = _PhaseCursor()
        try:
            return fn(*args, **kwargs)
        finally:
            cursor.switch(before)
            _phase_local.cursor = outer
    return wrapper


def next_phase(name: str) -> None:
    """A seam of a ``phased`` function: from here on the ops are
    ``lgbm.<name>``'s."""
    cursor = _cursor()
    if cursor is None:
        raise RuntimeError(f"next_phase({name!r}) outside a phased "
                           "function of the jaxpr being traced")
    cursor.switch(name)


@contextlib.contextmanager
def phase(name: str):
    """``lgbm.<name>`` for a block, or as a decorator for a function.
    Always on: a scope is metadata of the ops traced under it, not an
    instruction.  Inside a ``phased`` function the phase open there is
    set aside for the block (an op is under one phase, never two) and
    taken up again after it."""
    cursor = _cursor()
    if cursor is None:
        with _phase_scope(name):
            yield
        return
    before = cursor.name
    cursor.switch(name)
    try:
        yield
    finally:
        cursor.switch(before)


# What never runs as an op of its own on the device, and so never
# shows on a capture's ``XLA Ops`` line.
_NOT_AN_OP = frozenset({"parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast"})
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) \(.*\) -> .* \{$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.-]+) = (.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
# a layout, or the ``/*index=5*/`` a long tuple is printed with
_HLO_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
_HLO_CALLED = re.compile(
    r"(?:condition|body|true_computation|false_computation)=%([\w.-]+)"
    r"|branch_computations=\{([^}]*)\}")
_HLO_APPLIED = re.compile(r"to_apply=%([\w.-]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OP_NAME_PHASE = re.compile(re.escape(PHASE_PREFIX) + r"([a-z]+)(?:/|$)")


def op_key(name: str, shape: str) -> str:
    """How ``Program::ops`` names an instruction: its name and its
    result's shape without the layouts (the capture prints a tiled
    layout, the compiled text need not), ``fusion.9 f32[32,256]``."""
    return name.lstrip("%") + " " + _HLO_LAYOUT.sub("", shape).strip()


def program_ops(hlo_text: str) -> Dict[str, List[str]]:
    """``{phase: [op_key, ...]}`` of a compiled module's text: the
    instructions a capture's ``XLA Ops`` line can show - the entry
    computation's, and those of the ``while`` bodies and conditions,
    branches and calls reached from it, not the insides of fused
    computations - each under the innermost ``lgbm.<phase>`` of its
    ``metadata={op_name=...}`` (a fusion carries its root's), or under
    ``""`` where it has none: an op of a nested library ``jit``, or of
    code no phase was written for."""
    computations: Dict[str, List[tuple]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(1), [])
            if line.startswith("ENTRY "):
                entry = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line) if current is not None else None
        if m:
            current.append((m.group(1), m.group(2)))
    ops: Dict[str, List[str]] = {}
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in computations:
            continue
        seen.add(comp)
        for name, rest in computations[comp]:
            m = _HLO_OPCODE.search(rest)
            if m is None or m.group(1) in _NOT_AN_OP:
                continue
            for one, many in _HLO_CALLED.findall(rest):
                todo += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
            if m.group(1) == "call":
                todo += _HLO_APPLIED.findall(rest)
            named = _HLO_OP_NAME.search(rest)
            phases = _OP_NAME_PHASE.findall(named.group(1)) if named else []
            phase_ = phases[-1] if phases and phases[-1] in PHASES else ""
            ops.setdefault(phase_, []).append(
                op_key(name, rest[:m.start()]))
    return ops


def _on_jax_duration(event: str, secs: float, **kwargs) -> None:
    name = BUILD_EVENTS.get(event)
    if name is None:
        return
    for t in list(_LISTENING):
        if t._enabled:
            t._build_event(name, secs, kwargs)


class _SpanHandle:
    """Mutable handle yielded by ``Tracer.span``: lets the body attach
    late args and a device value to barrier on at exit."""

    __slots__ = ("args", "_block", "_tracer", "_name")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.args = args
        self._block = None
        self._tracer = tracer
        self._name = name

    def block_on(self, value) -> None:
        self._block = value

    def wait(self, value) -> None:
        """The barrier ``block_on`` defers to span exit, run now (as
        the same ``<name>::wait`` child), for a body that has host work
        to do after the device is done."""
        self._tracer._barrier(self._name, value)

    def set(self, **kwargs) -> None:
        self.args.update(kwargs)


class _NoopHandle:
    """Shared handle for disabled spans: every method is a no-op (in
    particular ``block_on`` must not retain the device value)."""

    __slots__ = ()
    args: dict = {}

    def block_on(self, value) -> None:
        pass

    def wait(self, value) -> None:
        pass

    def set(self, **kwargs) -> None:
        pass


_NOOP_HANDLE = _NoopHandle()


class Tracer:
    """Nested-span wall-clock tracer with JSON-lines / Chrome output."""

    def __init__(self) -> None:
        self._enabled = False
        self._path: Optional[str] = None
        self._file = None
        self._events: List[dict] = []       # in-memory copy (summary/tests)
        self._acc: Dict[str, List[float]] = {}   # name -> [total_s, count]
        self._counters: Dict[str, float] = {}
        self._local = threading.local()
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._env_checked = False
        self._annotate = False
        self._programs: Dict[str, tuple] = {}   # name -> (jitted, ops)
        self._max_events = int(os.environ.get("LGBM_TPU_TRACE_MAX_EVENTS",
                                              "200000"))

    # -- enable / disable ------------------------------------------------
    @property
    def enabled(self) -> bool:
        if not self._env_checked:
            self._env_checked = True
            path = os.environ.get(TRACE_ENV, "")
            if path:
                self.enable(path)
        return self._enabled

    def enable(self, path: Optional[str] = None) -> None:
        """Turn tracing on.  ``path=None`` collects in memory only
        (summary / counters still work; nothing is written)."""
        self._env_checked = True
        self._enabled = True
        self._listen()
        if path and path != self._path:
            self._close_file()
            self._path = path
            self._file = open(path, "w", buffering=1)
            self._file.write(json.dumps({
                "schema": TRACE_SCHEMA, "ph": "M", "name": "trace_start",
                "pid": os.getpid(),
                "args": {"unix_time": time.time()}}) + "\n")
            atexit.register(self.close)

    def disable(self) -> None:
        self._env_checked = True
        self._enabled = False

    def _listen(self) -> None:
        """Hear JAX's build durations from now on (``_on_jax_duration``
        drops them while this tracer is disabled)."""
        global _registered
        _LISTENING.add(self)
        if not _registered:
            try:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    _on_jax_duration)
                _registered = True
            except Exception:   # no jax: spans and counters still work
                pass

    def annotate(self, on: bool) -> None:
        """Toggle ``jax.profiler.TraceAnnotation`` emission around
        spans — on only while an xplane capture is active, so that the
        capture's host plane carries the span names on the device's
        clock.  Called from inside open spans (a callback that starts
        or stops a capture), the calling thread's open spans are
        mirrored from, respectively up to, this moment: the profiler
        keeps only annotations that begin and end while it runs."""
        on = bool(on)
        if on == self._annotate:
            return
        self._annotate = on
        stack = self._stack()
        if on:
            for name in self._programs:
                self._record_program(name)
            for entry in stack:
                if entry[1] is None:
                    entry[1] = self._mirror(entry[0])
        else:
            for entry in reversed(stack):
                self._unmirror(entry)

    @property
    def annotating(self) -> bool:
        return self._annotate

    # -- what a capture's instruction names mean --------------------------
    def program(self, name: str, jitted, *args) -> None:
        """Keep, for the jitted function an iteration has just
        dispatched as ``name`` (``grow``, ``pull_score``, ``score``,
        ``gradients``, ``eval:<data set>:<metric>``),
        the phase of each instruction of its compiled module
        (``program_ops``), to be written into every capture as a
        ``Program::ops`` event.  Once a function, and nothing is
        built for it: called after the dispatch with the very
        arguments it was given (a donated buffer, deleted by now, is
        asked only its shape and placement), ``lower`` finds the
        dispatch's trace and ``compile`` its executable in JAX's own
        caches; arguments described afresh (a ``ShapeDtypeStruct``
        with the sharding an uncommitted array merely has) lower to
        another module and compile it anew, 17-80 s on the chip (PR
        38).  Off, a single attribute check."""
        if not self.enabled:
            return
        known = self._programs.get(name)
        if known is not None and known[0]() is jitted:
            return
        # a span of its own: should this ever build (a ``jax::lower``
        # or ``jax::backend_compile`` event), it says so as the parent
        with self.span("Program::table", program=name):
            try:
                ops = program_ops(
                    jitted.lower(*args).compile().as_text())
            except Exception as e:      # a table is never worth a run
                ops = {}
                self.instant("Program::ops::failed", program=name,
                             error=f"{type(e).__name__}: {e}"[:400])
        self._programs[name] = (weakref.ref(jitted), ops)
        if self._annotate:
            self._record_program(name)

    def _record_program(self, name: str) -> None:
        stack = self._stack()
        self._record("Program::ops", time.perf_counter(), 0.0,
                     stack[-1][0] if stack else None, len(stack),
                     {"program": name, "ops": self._programs[name][1]})

    def close(self) -> None:
        self._close_file()

    def _close_file(self) -> None:
        # under the lock: _record/count/instant check-then-write the
        # file handle while holding it, so close must be excluded or a
        # concurrent span exit writes to a closed file
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
                self._path = None

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._acc.clear()
            self._counters.clear()
            self._programs.clear()
            self._t0 = time.perf_counter()

    # -- spans -----------------------------------------------------------
    def _stack(self) -> list:
        """This thread's open spans, outermost first: ``[name, mirror]``
        with ``mirror`` the live TraceAnnotation or None."""
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @staticmethod
    def _mirror(name: str):
        """Enter ``obs::<name>`` on the capture's host plane."""
        try:
            import jax.profiler
            annotation = jax.profiler.TraceAnnotation("obs::" + name)
            annotation.__enter__()
            return annotation
        except Exception:   # no live profiler session / old jax
            return None

    @staticmethod
    def _unmirror(entry: list) -> None:
        if entry[1] is not None:
            try:
                entry[1].__exit__(None, None, None)
            except Exception:
                pass
            entry[1] = None

    def _barrier(self, name: str, value) -> None:
        """``jax.block_until_ready(value)`` as the child span
        ``<name>::wait``: what the host spent waiting for the device
        (or sat in the runtime), apart from its own work."""
        with self.span(name + WAIT_SUFFIX):
            import jax
            jax.block_until_ready(value)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Context manager timing a named span.  Nesting is tracked per
        thread; the yielded handle takes late args and an optional
        device value to block on before the clock stops."""
        if not self.enabled:
            yield _NOOP_HANDLE
            return
        stack = self._stack()
        handle = _SpanHandle(self, name, dict(args))
        parent = stack[-1][0] if stack else None
        # the mirror is entered before the clock starts and exited
        # after the device barrier so the annotated window covers what
        # the span wall covers
        entry = [name, self._mirror(name) if self._annotate else None]
        stack.append(entry)
        start = time.perf_counter()
        try:
            yield handle
        finally:
            try:
                if handle._block is not None:
                    self._barrier(name, handle._block)
            finally:
                # the span must unwind and record even when the barrier
                # surfaces a device error — a stale stack entry would
                # corrupt every later span's parent/depth in this thread
                dur = time.perf_counter() - start
                stack.pop()
                self._unmirror(entry)
                self._record(name, start, dur, parent, len(stack),
                             handle.args)

    def _build_event(self, name: str, secs: float, kwargs: dict) -> None:
        """One of JAX's build durations as a complete event that ends
        now, inside the span open on this thread."""
        stack = self._stack()
        end = time.perf_counter()
        args = {k: v for k, v in kwargs.items()
                if isinstance(v, (str, int, float))}
        self._record(name, end - secs, secs,
                     stack[-1][0] if stack else None, len(stack), args)

    def _record(self, name, start, dur, parent, depth, args) -> None:
        with self._lock:
            acc = self._acc.setdefault(name, [0.0, 0])
            acc[0] += dur
            acc[1] += 1
            ev = {
                "name": name, "cat": "lgbm_tpu", "ph": "X",
                "ts": (start - self._t0) * 1e6, "dur": dur * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": dict(args, depth=depth,
                             **({"parent": parent} if parent else {})),
            }
            if len(self._events) < self._max_events:
                self._events.append(ev)
            if self._file is not None:
                self._file.write(json.dumps(ev) + "\n")

    # -- counters --------------------------------------------------------
    def count(self, name: str, value: float, **args) -> None:
        """Accumulate a named counter and emit a Chrome 'C' event."""
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value
            ev = {
                "name": name, "cat": "lgbm_tpu", "ph": "C",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": dict(args, value=value,
                             total=self._counters[name]),
            }
            if len(self._events) < self._max_events:
                self._events.append(ev)
            if self._file is not None:
                self._file.write(json.dumps(ev) + "\n")

    def instant(self, name: str, **args) -> None:
        """Emit an instant ('i') marker event."""
        if not self.enabled:
            return
        with self._lock:
            ev = {
                "name": name, "cat": "lgbm_tpu", "ph": "i", "s": "t",
                "ts": (time.perf_counter() - self._t0) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": dict(args),
            }
            if len(self._events) < self._max_events:
                self._events.append(ev)
            if self._file is not None:
                self._file.write(json.dumps(ev) + "\n")

    # -- introspection ---------------------------------------------------
    @property
    def events(self) -> List[dict]:
        return list(self._events)

    def summary(self) -> Dict[str, dict]:
        """Per-phase accumulators: {name: {total_s, count, mean_s}}."""
        with self._lock:
            return {
                name: {"total_s": acc[0], "count": acc[1],
                       "mean_s": acc[0] / max(acc[1], 1)}
                for name, acc in sorted(
                    self._acc.items(), key=lambda kv: -kv[1][0])}

    def counter_totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def report(self) -> str:
        lines = ["LightGBM-TPU trace summary:"]
        for name, s in self.summary().items():
            lines.append(f"  {name}: {s['total_s']:.4f}s over "
                         f"{s['count']} calls")
        for name, v in sorted(self.counter_totals().items()):
            lines.append(f"  counter {name}: {v:g}")
        return "\n".join(lines)


tracer = Tracer()
