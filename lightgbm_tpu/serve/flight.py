"""Serving flight recorder: continuous in-process telemetry for the
inference path (ISSUE 17 tentpole).

Training has five flight recorders; serving had none — latency was a
post-hoc host sample list in ``bench.py`` and queue depth, padding
waste and retraces had no live signal.  This module is the serving
counterpart, built the way "millions of users" deployments expect:

* **log-bucketed latency histograms** — fixed-size (``HIST_BUCKETS``
  bins, ``HIST_GROWTH`` geometric growth from ``HIST_ORIGIN_S``),
  mergeable by bin-wise addition, with p50/p99/p999 DERIVED from the
  bucket counts — never a sample list, so memory is O(1) per dispatch
  bucket regardless of traffic volume and two windows merge exactly;
* **rolling time-window ring** — observations aggregate into the
  current window (``LGBM_TPU_SERVE_METRICS_WINDOW_S`` seconds); closed
  windows rotate into a bounded ring and, when
  ``LGBM_TPU_SERVE_METRICS`` names a directory, emit as JSONL records
  (schema ``lightgbm_tpu/servemetrics/v1``) through an ATOMIC
  tmp+rename rewrite so readers never see a torn file;
* **digest segmentation** — every window is tagged with the
  ServingModel content digest it observed; a hot-swap (new digest)
  closes the window immediately, so a rebuilt engine NEVER merges its
  stream into the previous model's (the ``obs serve`` reader and the
  perf gate treat digest boundaries as incomparable, like routing
  digests);
* **queue depth / occupancy sampling**, **padding-waste bytes**
  (padded minus true rows, priced via
  ``obs.costmodel.serving_traversal_bytes``), **retrace-after-warmup**
  and **error-class events**.

Purity discipline (the ``grow-tracer-live`` pattern): the recorder
lives entirely on the host side of the dispatch — nothing it does is
visible to jit, so metrics on/off compiles the IDENTICAL serving
program (the jitted entry is cached per (n_steps, digest) and shared);
with metrics off the engine's hot path pays exactly one ``is None``
branch per dispatch and allocates nothing recorder-related.  Pinned by
``tests/test_serve.py``.
"""
from __future__ import annotations

import json
import math
import os
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional

SERVEMETRICS_SCHEMA = "lightgbm_tpu/servemetrics/v1"

# ---------------------------------------------------------------------
# log-bucketed histogram: bin 0 is [0, ORIGIN); bin i>=1 covers
# [ORIGIN*G^(i-1), ORIGIN*G^i); the LAST bin absorbs overflow.  With
# G = 2^0.25 (~19% per bin) and 96 bins the range is 1 µs .. ~16.7 s —
# percentiles derived from counts land within one bin (<= ~19% rel
# error) of the exact sample percentile, inside the perf gate's 25%
# wall tolerance (the bench parity contract).
# ---------------------------------------------------------------------
HIST_ORIGIN_S = 1e-6
HIST_GROWTH = 2.0 ** 0.25
HIST_BUCKETS = 96
_LOG_GROWTH = math.log(HIST_GROWTH)


def bucket_index(seconds: float) -> int:
    """The histogram bin a latency falls in (clamped; never raises)."""
    if seconds < HIST_ORIGIN_S:
        return 0
    i = int(math.log(max(seconds, HIST_ORIGIN_S) / HIST_ORIGIN_S)
            / _LOG_GROWTH) + 1
    return min(max(i, 1), HIST_BUCKETS - 1)


def bucket_value_s(i: int) -> float:
    """The representative latency of bin ``i`` (geometric midpoint;
    the overflow bin reports its lower edge)."""
    if i <= 0:
        return HIST_ORIGIN_S / 2.0
    if i >= HIST_BUCKETS - 1:
        return HIST_ORIGIN_S * HIST_GROWTH ** (HIST_BUCKETS - 2)
    return HIST_ORIGIN_S * HIST_GROWTH ** (i - 0.5)


def percentile_from_counts(counts: List[int], q: float) -> float:
    """The q-th percentile (0..100) derived from bin counts alone —
    the mergeable-histogram contract: never a sample list.  Returns
    0.0 for an empty histogram."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    target = max(q, 0.0) / 100.0 * total
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if cum >= target and c:
            return bucket_value_s(i)
    for i in range(len(counts) - 1, -1, -1):   # pragma: no cover
        if counts[i]:
            return bucket_value_s(i)
    return 0.0


class LatencyHistogram:
    """Fixed-size mergeable latency histogram (one per dispatch
    bucket per window)."""

    __slots__ = ("counts", "count")

    def __init__(self, counts: Optional[List[int]] = None):
        self.counts = list(counts) if counts else [0] * HIST_BUCKETS
        if len(self.counts) != HIST_BUCKETS:
            self.counts = (self.counts + [0] * HIST_BUCKETS)[
                :HIST_BUCKETS]
        self.count = sum(self.counts)

    def add(self, seconds: float) -> None:
        self.counts[bucket_index(seconds)] += 1
        self.count += 1

    def merge(self, other: "LatencyHistogram") -> None:
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count

    def percentile_s(self, q: float) -> float:
        return percentile_from_counts(self.counts, q)

    def to_sparse(self) -> Dict[str, int]:
        """JSON-able {bin_index: count} with zero bins elided (the
        window-record wire form; keys are strings per JSON)."""
        return {str(i): c for i, c in enumerate(self.counts) if c}

    @classmethod
    def from_sparse(cls, sparse: Dict[str, Any]) -> "LatencyHistogram":
        h = cls()
        for k, c in (sparse or {}).items():
            i = int(k)
            if 0 <= i < HIST_BUCKETS:
                h.counts[i] += int(c)
        h.count = sum(h.counts)
        return h


class _Window:
    """One open aggregation window: every field is O(1) per
    observation (bin increments and scalar adds)."""

    __slots__ = ("digest", "start", "end", "seq", "dispatches",
                 "rows_true", "rows_padded", "padding_waste_bytes",
                 "dispatch_bytes", "hist", "queue_samples",
                 "queue_depth_sum", "queue_depth_max", "queue_depth_cap",
                 "events")

    def __init__(self, digest: str, start: float, seq: int):
        self.digest = digest
        self.start = start
        self.end = start
        self.seq = seq
        self.dispatches = 0
        self.rows_true = 0
        self.rows_padded = 0
        self.padding_waste_bytes = 0
        self.dispatch_bytes = 0
        self.hist: Dict[int, LatencyHistogram] = {}
        self.queue_samples = 0
        self.queue_depth_sum = 0
        self.queue_depth_max = 0
        self.queue_depth_cap = 0
        self.events: Dict[str, int] = {}

    def to_record(self) -> Dict[str, Any]:
        return {
            "schema": SERVEMETRICS_SCHEMA,
            "digest": self.digest,
            "seq": self.seq,
            "window_start": round(self.start, 6),
            "window_end": round(self.end, 6),
            "dispatches": self.dispatches,
            "rows_true": self.rows_true,
            "rows_padded": self.rows_padded,
            "padding_waste_bytes": self.padding_waste_bytes,
            "dispatch_bytes": self.dispatch_bytes,
            "latency": {
                "unit": "s",
                "origin_s": HIST_ORIGIN_S,
                "growth": round(HIST_GROWTH, 6),
                "bins": HIST_BUCKETS,
                "buckets": {str(b): h.to_sparse()
                            for b, h in sorted(self.hist.items())},
            },
            "queue": {
                "samples": self.queue_samples,
                "depth_sum": self.queue_depth_sum,
                "depth_max": self.queue_depth_max,
                "depth_cap": self.queue_depth_cap,
            },
            "events": dict(sorted(self.events.items())),
        }


class ServingFlightRecorder:
    """Lock-light process-wide aggregation point for the serving hot
    path.  Every public method is one short critical section of scalar
    updates; nothing here touches jax, so the recorder can NEVER cause
    a retrace (the ``stats()["programs"]`` pin)."""

    def __init__(self, *, emit_dir: str = "", window_s: float = 60.0,
                 clock: Optional[Callable[[], float]] = None,
                 ring: int = 128):
        import time
        self._lock = threading.Lock()
        self._clock = clock or time.time
        self.window_s = max(float(window_s), 1e-3)
        self.emit_dir = emit_dir
        self._emit_path = (os.path.join(
            emit_dir, f"servemetrics-{os.getpid()}.jsonl")
            if emit_dir else "")
        self._ring: deque = deque(maxlen=max(int(ring), 1))
        self._win: Optional[_Window] = None
        self._seq = 0
        self.windows_emitted = 0

    # -- window lifecycle ----------------------------------------------
    def _window(self, digest: str, now: float) -> _Window:
        """The open window for ``digest``; a digest change (hot swap)
        or an elapsed cadence closes the current one FIRST — segments
        never merge across a swap boundary."""
        w = self._win
        if (w is None or w.digest != digest
                or now - w.start >= self.window_s):
            if w is not None and w.dispatches + w.queue_samples \
                    + sum(w.events.values()) > 0:
                self._close(w, now)
            w = _Window(digest, now, self._seq)
            self._seq += 1
            self._win = w
        return w

    def _close(self, w: _Window, now: float) -> None:
        w.end = now
        self._ring.append(w.to_record())
        self.windows_emitted += 1
        if self._emit_path:
            self._emit()
        # live pulse (ISSUE 20): one serving heartbeat per closed
        # window — digest + derived p99 ride the stream so the
        # watchdog sees a hot-swap and an SLO breach without reading
        # the window files.  Knob-gated: LGBM_TPU_PULSE=off allocates
        # nothing and this is a single `is None` branch per window.
        from ..obs import pulse as pulse_mod
        em = pulse_mod.emitter("serving")
        if em is not None:
            merged = LatencyHistogram()
            for h in w.hist.values():
                merged.merge(h)
            em.beat("serve::window", force=True, serving={
                "digest": w.digest,
                "p99_ms": round(merged.percentile_s(99.0) * 1e3, 3),
                "dispatches": w.dispatches})

    def _emit(self) -> None:
        """Atomic rotation: the bounded ring is rewritten whole through
        a tmp file + ``os.replace``, so a reader (or a crash) never
        observes a torn JSONL line."""
        tmp = self._emit_path + ".tmp"
        with open(tmp, "w") as f:
            for rec in self._ring:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
        os.replace(tmp, self._emit_path)

    def flush(self) -> None:
        """Close and emit the open window (end of a bench run, an
        engine teardown, a test boundary)."""
        with self._lock:
            w = self._win
            if w is not None and w.dispatches + w.queue_samples \
                    + sum(w.events.values()) > 0:
                self._close(w, self._clock())
            self._win = None

    def snapshot(self) -> List[Dict[str, Any]]:
        """Closed windows plus the open one (read-only copies)."""
        with self._lock:
            out = list(self._ring)
            if self._win is not None and self._win.dispatches:
                live = self._win.to_record()
                live["window_end"] = round(self._clock(), 6)
                out.append(live)
        return out

    # -- observation points (the engine/queue hooks) -------------------
    def on_dispatch(self, digest: str, bucket: int, n_rows: int, *,
                    novel: bool, warm: bool,
                    geom: Dict[str, int]) -> None:
        """One bucketed dispatch: rows, padding waste priced via the
        cost model, and the compile / retrace-after-warmup events.
        ``geom`` selects the pricing contract: with ``kernel: True``
        (the ISSUE-18 VMEM-resident traversal) the remaining keys are
        ``costmodel.serving_kernel_bytes`` kwargs — the forest term is
        per-DISPATCH, not per-row, so waste is the marginal
        price(bucket) - price(true rows), which reduces to the old
        price(bucket - rows) on the row-linear gather contract."""
        from ..obs.costmodel import (serving_kernel_bytes,
                                     serving_traversal_bytes)
        g = dict(geom)
        if g.pop("kernel", False):
            def price(rows):
                return serving_kernel_bytes(rows, **g)
        else:
            def price(rows):
                return serving_traversal_bytes(rows, **g)
        total = price(bucket)
        waste = total - price(n_rows) if bucket > n_rows else 0
        with self._lock:
            w = self._window(digest, self._clock())
            w.dispatches += 1
            w.rows_true += n_rows
            w.rows_padded += bucket
            w.padding_waste_bytes += waste
            w.dispatch_bytes += total
            if novel:
                w.events["serve_compile"] = \
                    w.events.get("serve_compile", 0) + 1
                if warm:
                    w.events["serve_retrace_after_warmup"] = \
                        w.events.get("serve_retrace_after_warmup", 0) + 1

    def observe_latency(self, digest: str, bucket: int,
                        seconds: float) -> None:
        """One submit->completion delta from the ServingQueue (the
        single source of latency truth since ISSUE 17 satellite 1)."""
        with self._lock:
            w = self._window(digest, self._clock())
            h = w.hist.get(bucket)
            if h is None:
                h = w.hist[bucket] = LatencyHistogram()
            h.add(seconds)

    def sample_queue_depth(self, digest: str, depth: int,
                           cap: int) -> None:
        """Queue occupancy at submit entry — sampled BEFORE the
        full-queue block, so saturation shows depth == cap."""
        with self._lock:
            w = self._window(digest, self._clock())
            w.queue_samples += 1
            w.queue_depth_sum += depth
            if depth > w.queue_depth_max:
                w.queue_depth_max = depth
            w.queue_depth_cap = max(w.queue_depth_cap, cap)

    def record_event(self, digest: str, name: str) -> None:
        """Error-class / lifecycle event (``serve_error_*``)."""
        with self._lock:
            w = self._window(digest, self._clock())
            w.events[name] = w.events.get(name, 0) + 1


# ---------------------------------------------------------------------
# knob-gated process recorder
# ---------------------------------------------------------------------
_RECORDER: Optional[ServingFlightRecorder] = None
_RECORDER_KEY: Optional[tuple] = None
_MEM_MODES = ("1", "on", "mem")


def engine_recorder() -> Optional[ServingFlightRecorder]:
    """The process recorder per ``LGBM_TPU_SERVE_METRICS``, or None
    when metrics are off.  Engines capture the result ONCE at
    construction, so the steady-state dispatch pays a single ``is
    None`` branch; the knob is re-read here so tests (and hot config
    reloads) can flip it between engine builds."""
    global _RECORDER, _RECORDER_KEY
    from ..config import env_knob
    from ..utils.log import LightGBMError
    mode = env_knob("LGBM_TPU_SERVE_METRICS")
    if mode in ("off", "0", ""):
        return None
    try:
        window_s = float(env_knob("LGBM_TPU_SERVE_METRICS_WINDOW_S"))
    except ValueError:
        raise LightGBMError(
            "LGBM_TPU_SERVE_METRICS_WINDOW_S must be a number of "
            "seconds")
    key = (mode, window_s)
    if _RECORDER is None or _RECORDER_KEY != key:
        emit_dir = "" if mode in _MEM_MODES else mode
        if emit_dir:
            os.makedirs(emit_dir, exist_ok=True)
        _RECORDER = ServingFlightRecorder(emit_dir=emit_dir,
                                          window_s=window_s)
        _RECORDER_KEY = key
    return _RECORDER


def _reset() -> None:
    """Drop the process recorder (test isolation)."""
    global _RECORDER, _RECORDER_KEY
    _RECORDER = None
    _RECORDER_KEY = None
