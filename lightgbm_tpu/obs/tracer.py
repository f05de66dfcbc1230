"""Phase tracer: nested wall-clock spans with device barriers.

Generalizes ``utils/timer.py`` (the reference ``Common::Timer`` /
``FunctionTimer`` analog, utils/common.h:973) from flat named
accumulators into a structured trace: nested spans, JSON-lines output
that doubles as Chrome-trace events, per-phase accumulators, and
counter channels.  Phase names mirror the reference hot path
(BeforeTrain / ConstructHistogram / FindBestSplits / Split,
serial_tree_learner.cpp) so traces are comparable across ports.

Enable with ``LGBM_TPU_TRACE=/path/to/trace.jsonl`` (read at first
use), or programmatically via ``tracer.enable(path)``.  Disabled (the
default) every ``span`` entry is a single attribute check — the hot
path pays nothing and the booster compiles the exact same HLO (see
tests/test_obs.py::test_tracing_off_changes_nothing).

Output format: one JSON object per line.  The first line is a metadata
record carrying the schema version; every span line is a valid Chrome
"complete" event (``ph: "X"``, microsecond ``ts``/``dur``), so
``python -m lightgbm_tpu.obs report --chrome out.json`` only has to
wrap the lines in an array for chrome://tracing / Perfetto.

Device work is asynchronous under JAX: a span that covers a dispatch
measures only the enqueue unless it blocks.  ``span(...)`` yields a
handle; call ``handle.block_on(x)`` to make span exit run
``jax.block_until_ready(x)`` before the clock stops (the host-pull
barrier the profiling tools use lives one level up, in
``tools/profile_lib.py``).

Xplane correlation (ISSUE 6): while an xplane capture is active —
``tools/profile_lib.xplane_capture`` (and ``bench.py`` under
``LGBM_TPU_XPLANE``) toggles ``tracer.annotate(True)`` — every span
additionally enters a ``jax.profiler.TraceAnnotation("obs::<name>")``,
so the capture's host plane carries the obs phase names and
``python -m lightgbm_tpu.obs attr`` (obs/xattr.py) can join device
kernels back to phases.  Off by default: with no capture active the
span fast path is byte-for-byte the PR-2 one and the counters=False
grow jaxpr pin is untouched.
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

TRACE_SCHEMA = "lightgbm_tpu/trace/v1"
TRACE_ENV = "LGBM_TPU_TRACE"


class _SpanHandle:
    """Mutable handle yielded by ``Tracer.span``: lets the body attach
    late args and a device value to barrier on at exit."""

    __slots__ = ("args", "_block")

    def __init__(self, args: dict):
        self.args = args
        self._block = None

    def block_on(self, value) -> None:
        self._block = value

    def set(self, **kwargs) -> None:
        self.args.update(kwargs)


class _NoopHandle:
    """Shared handle for disabled spans: every method is a no-op (in
    particular ``block_on`` must not retain the device value)."""

    __slots__ = ()
    args: dict = {}

    def block_on(self, value) -> None:
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

    def annotate(self, on: bool) -> None:
        """Toggle ``jax.profiler.TraceAnnotation`` emission around
        spans — on only while an xplane capture is active
        (``profile_lib.xplane_capture`` flips it), so device events can
        be joined back to obs phases by ``obs attr``."""
        self._annotate = bool(on)

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
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Context manager timing a named span.  Nesting is tracked per
        thread; the yielded handle takes late args and an optional
        device value to block on before the clock stops."""
        if not self.enabled:
            yield _NOOP_HANDLE
            return
        stack = self._stack()
        handle = _SpanHandle(dict(args))
        parent = stack[-1] if stack else None
        annotation = None
        if self._annotate:
            # mirror the span as a TraceMe region on the capture's host
            # plane; entered before the clock starts and exited after
            # the device barrier so the annotated window covers what
            # the span wall covers
            try:
                import jax.profiler
                annotation = jax.profiler.TraceAnnotation("obs::" + name)
                annotation.__enter__()
            except Exception:   # no live profiler session / old jax
                annotation = None
        stack.append(name)
        start = time.perf_counter()
        try:
            yield handle
        finally:
            try:
                if handle._block is not None:
                    import jax
                    jax.block_until_ready(handle._block)
            finally:
                # the span must unwind and record even when the barrier
                # surfaces a device error — a stale stack entry would
                # corrupt every later span's parent/depth in this thread
                dur = time.perf_counter() - start
                stack.pop()
                if annotation is not None:
                    try:
                        annotation.__exit__(None, None, None)
                    except Exception:
                        pass
                self._record(name, start, dur, parent, len(stack),
                             handle.args)

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
