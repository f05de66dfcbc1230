"""Phase tracer: nested wall-clock spans with device barriers.

Generalizes ``utils/timer.py`` (the reference ``Common::Timer`` /
``FunctionTimer`` analog, utils/common.h:973) from flat named
accumulators into a structured trace: nested spans, JSON-lines output
that doubles as Chrome-trace events, per-phase accumulators, and
counter channels.

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
                                        comb_cols, comb_line_bytes
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
        HbmCensus                       live-array census (obs mem)
        GradSlice                       eager grad[k], hess[k]
        GBDT::grow                      utils/timer.py's twin of the next
          Tree::grow                    args: the work counters
                                        (obs/counters.py COUNTER_NAMES;
                                        under the bundled comb
                                        member_splits and rows_member
                                        are counted by the grow program)
                                        and scan_block_rows, the rows a
                                        grid step of the partition scan
                                        moves (scan_steps counts them)
            Tree::grow::wait            the device runs the grow program
            WorkCounters                pull of the tree's small arrays
        HbmCensus
        UpdateScore
          UpdateScore::tail             dispatch of the score/valid tail
          UpdateScore::set              eager slice + .at[].set
          UpdateScore::wait
        HbmCensus
        StallProbe                      every 8th iteration
        FlushPending                    every 32nd iteration
      Eval                              when a metric is due
      Callbacks                         cbs_after (a benchmark's pulls)

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
import json
import os
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
            for entry in stack:
                if entry[1] is None:
                    entry[1] = self._mirror(entry[0])
        else:
            for entry in reversed(stack):
                self._unmirror(entry)

    @property
    def annotating(self) -> bool:
        return self._annotate

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
