"""ServingEngine: bucketed jit dispatch + donated score buffers +
double-buffered async queue (ISSUE 14).

Shape discipline is the whole point: batch sizes round UP to
power-of-two row buckets between the ``LGBM_TPU_SERVE_BUCKETS``
floor and cap, so a production traffic mix of novel batch sizes
compiles exactly ``len(buckets)`` programs and then never retraces
(the PR-10 ROUTING_RETRACE same-bucket contract — ``stats()`` exposes
the live program count so benches and CI can pin it).  Each bucket
rotates a small pool of ``[bucket, K]`` score buffers through jit
donation: the dispatch writes its sums into the donated buffer's
memory and the consumed output array goes back into the pool, so
steady-state serving allocates nothing per call (the PR-9 audit keeps
the aliasing honest on the registered ``serve_forest`` entrypoint).
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.log import LightGBMError
from . import flight
from .model import ServingModel


def bucket_policy() -> Tuple[int, int]:
    """(floor, cap) row buckets from ``LGBM_TPU_SERVE_BUCKETS``."""
    from ..config import env_knob
    spec = env_knob("LGBM_TPU_SERVE_BUCKETS")
    try:
        lo_s, hi_s = spec.split(":")
        lo, hi = int(lo_s), int(hi_s)
        if lo < 1 or hi < lo:
            raise ValueError
    except ValueError:
        raise LightGBMError(
            f"LGBM_TPU_SERVE_BUCKETS must be FLOOR:CAP (got {spec!r})")
    return lo, hi


def _next_pow2(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0)


def bucket_for(n: int, lo: int, hi: int) -> int:
    """The power-of-two row bucket a batch of ``n`` rows pads into
    (clamped to [lo, hi]; batches above ``hi`` chunk).  Module-level so
    the analyzer's serving-forest-bucket retrace pin evaluates the SAME
    policy the engine dispatches with."""
    return min(max(_next_pow2(max(n, 1)), lo), hi)


class _Pending:
    """One in-flight bucketed dispatch (jax dispatch is async: the
    device array exists immediately, the values land later).
    ``t_sub`` is the host submit timestamp the ServingQueue stamps so
    its completion handler records the submit->drain latency at the
    source (ISSUE 17 satellite: the bench no longer keeps its own
    sample list)."""

    __slots__ = ("out", "n", "bucket", "t_sub")

    def __init__(self, out, n: int, bucket: int):
        self.out = out
        self.n = n
        self.bucket = bucket
        self.t_sub: Optional[float] = None


class ServingEngine:
    """Compiled bulk + small-batch scoring over one ServingModel."""

    def __init__(self, model: ServingModel, *,
                 bucket_min: Optional[int] = None,
                 bucket_max: Optional[int] = None):
        self.model = model
        lo, hi = bucket_policy()
        self.bucket_min = int(bucket_min or lo)
        self.bucket_max = int(bucket_max or hi)
        if self.bucket_max < self.bucket_min:
            raise LightGBMError("serving bucket cap below floor")
        # ISSUE 18: which compiled program serves — "" = XLA gather
        # walk, "compiled"/"interpret" = the VMEM-resident Pallas
        # traversal (decided by the predict_decide serve_kernel rules
        # over the stacked forest's actual VMEM fit)
        self.kernel_mode = _kernel_mode(model)
        self._fn, self._leaf_fn = _jitted_entries(
            model.n_steps, model.digest, self.kernel_mode)
        self._pool: Dict[int, List] = {}
        self._buckets: set = set()
        self.dispatches = 0
        self.rows_true = 0
        self.rows_padded = 0
        self.retraces_after_warmup = 0
        self._warm = False
        # flight-recorder binding (ISSUE 17): captured ONCE here so the
        # dispatch hot path pays exactly one `is None` branch when
        # LGBM_TPU_SERVE_METRICS is off; the recorder is pure host-side
        # aggregation, so the jitted program is identical either way
        # (the shared _jitted_entries cache is the byte-identity proof)
        self._flight = flight.engine_recorder()
        if self.kernel_mode:
            # kernel pricing contract: forest bytes once + row bytes
            # once (costmodel.serving_kernel_bytes), keyed off the
            # INNER feature count the [n, F] bins matrix carries
            import numpy as _np
            self._flight_geom = dict(
                model.kernel_geometry(), kernel=True,
                features=int(_np.asarray(
                    model.forest.used_cols).shape[0]),
                num_class=model.num_class)
        else:
            self._flight_geom = {
                "trees": model.n_trees, "levels": model.n_steps,
                "features": model.n_orig_features,
                "num_class": model.num_class,
            }

    # ------------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        return bucket_for(n, self.bucket_min, self.bucket_max)

    def mark_warm(self) -> None:
        """Declare warmup complete: every bucket that compiles past
        this point counts as a retrace-after-warmup (the same-bucket
        contract) in ``stats()`` and in the flight recorder's event
        stream."""
        self._warm = True

    def _note_error(self, code: str) -> None:
        """Error-class event on the raise paths (off the dispatch
        hot path; a no-op when metrics are off)."""
        if self._flight is not None:
            self._flight.record_event(self.model.digest,
                                      "serve_error_" + code)

    def stats(self) -> dict:
        """Program-cache facts the retrace pin reads: ``programs`` is
        the live jit cache size, which must equal ``len(buckets)`` after
        warmup and never grow mid-serving."""
        return {
            "buckets": sorted(self._buckets),
            "programs": int(self._fn._cache_size()),
            "dispatches": self.dispatches,
            "rows_true": self.rows_true,
            "rows_padded": self.rows_padded,
            "retraces_after_warmup": self.retraces_after_warmup,
            "digest": self.model.digest,
            "kernel": self.kernel_mode,
        }

    # ------------------------------------------------------------------
    def _pad(self, chunk: np.ndarray, bucket: int) -> np.ndarray:
        # width check up front: the jitted gather over used_cols CLAMPS
        # out-of-range column indices, so a wrong-width matrix would
        # score silently wrong (the host walk raises) — and each novel
        # width would trace a fresh program, breaking the retrace pin
        if chunk.shape[1] != self.model.n_orig_features:
            self._note_error("input_width")
            raise LightGBMError(
                f"predict input has {chunk.shape[1]} features but the "
                f"compiled model (digest {self.model.digest}) was "
                f"trained on {self.model.n_orig_features}")
        if chunk.shape[0] == bucket:
            return np.ascontiguousarray(chunk, np.float32)
        out = np.zeros((bucket, chunk.shape[1]), np.float32)
        out[:chunk.shape[0]] = chunk
        return out

    def dispatch(self, chunk: np.ndarray) -> _Pending:
        """Submit one bucketed dispatch (rows <= bucket cap); returns
        immediately — jax queues the device work async."""
        import jax.numpy as jnp

        n = chunk.shape[0]
        bucket = self.bucket_for(n)
        if n > bucket:
            self._note_error("bucket_cap")
            raise LightGBMError(
                f"dispatch of {n} rows exceeds the bucket cap "
                f"{self.bucket_max}; chunk through predict()")
        raw = jnp.asarray(self._pad(chunk, bucket))
        pool = self._pool.setdefault(bucket, [])
        buf = pool.pop() if pool else jnp.zeros(
            (bucket, self.model.num_class), jnp.float32)
        out = self._fn(self.model.forest, raw, jnp.int32(n), buf)
        novel = bucket not in self._buckets
        if novel:
            self._buckets.add(bucket)
            if self._warm:
                self.retraces_after_warmup += 1
        self.dispatches += 1
        self.rows_true += n
        self.rows_padded += bucket
        if self._flight is not None:
            self._flight.on_dispatch(self.model.digest, bucket, n,
                                     novel=novel, warm=self._warm,
                                     geom=self._flight_geom)
        return _Pending(out, n, bucket)

    def collect(self, p: _Pending) -> np.ndarray:
        """Block on one pending dispatch; the consumed output array
        returns to its bucket's pool as the next donation target."""
        host = np.asarray(p.out[:p.n])
        self._pool.setdefault(p.bucket, []).append(p.out)
        p.out = None
        return host

    # ------------------------------------------------------------------
    def predict(self, X: np.ndarray, *,
                queue_depth: Optional[int] = None) -> np.ndarray:
        """Bulk scoring: [n, F] raw f32 rows -> [n, K] raw scores.
        Chunks of the bucket cap are pipelined ``queue_depth`` deep
        (dispatch chunk t+1 while t is in flight)."""
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        n = X.shape[0]
        k = self.model.num_class
        if n == 0:
            return np.zeros((0, k), np.float32)
        depth = queue_depth or _queue_depth_knob()
        out = np.empty((n, k), np.float32)
        pending: deque = deque()
        for start in range(0, n, self.bucket_max):
            pending.append(
                (start, self.dispatch(X[start:start + self.bucket_max])))
            while len(pending) > depth:
                s, p = pending.popleft()
                out[s:s + p.n] = self.collect(p)
        while pending:
            s, p = pending.popleft()
            out[s:s + p.n] = self.collect(p)
        return out

    def predict_leaves(self, X: np.ndarray) -> np.ndarray:
        """[n, F] raw rows -> [n, T] leaf indices (the exactness side
        of the parity suite; not donated — diagnostics only)."""
        import jax.numpy as jnp

        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        n = X.shape[0]
        if n == 0:
            return np.zeros((0, self.model.n_trees), np.int32)
        outs = []
        for start in range(0, n, self.bucket_max):
            chunk = X[start:start + self.bucket_max]
            bucket = self.bucket_for(chunk.shape[0])
            raw = jnp.asarray(self._pad(chunk, bucket))
            leaf = self._leaf_fn(self.model.forest, raw,
                                 jnp.int32(chunk.shape[0]))
            outs.append(np.asarray(leaf[:chunk.shape[0]]))
        return np.concatenate(outs, axis=0)


def _queue_depth_knob() -> int:
    from ..config import env_knob
    try:
        depth = int(env_knob("LGBM_TPU_SERVE_QUEUE"))
    except ValueError:
        raise LightGBMError("LGBM_TPU_SERVE_QUEUE must be an integer")
    return max(depth, 1)


def _kernel_mode(model: ServingModel) -> str:
    """'' (XLA gather walk) | "compiled" | "interpret" — the serving
    program for one stacked model, decided by the SAME predict_decide
    serve_kernel rules the golden matrix audits.  The loud
    ``serve_forest_overwide`` fallback reports from here so direct
    ``ServingEngine`` users (bypassing ``Booster.predict``) still get
    the structured event + warn-once line."""
    import jax

    from ..config import env_knob
    from ..ops import routing
    d = routing.predict_decide(routing.PredictInputs(
        backend=jax.default_backend(), serve_env="1",
        serve_kernel_env=routing.predict_kernel_env_snapshot(),
        forest_overwide=not model.kernel_fit))
    routing.report_predict_fallbacks(d)
    if not d.kernel:
        return ""
    return ("interpret"
            if env_knob("LGBM_TPU_SERVE_INTERP") == "kernel"
            else "compiled")


# jit wrappers are cached per (n_steps, digest, kernel mode) so every
# engine over the SAME compiled model shares one trace cache entry per
# bucket (a rebuilt engine — e.g. after the booster cache evicts, or a
# serving hot-swap back to a previous digest — reuses the compiled
# programs instead of retracing every bucket); distinct digests get
# distinct wrappers so stats()["programs"] counts only this model's
# programs
@functools.lru_cache(maxsize=64)
def _jitted_entries(n_steps: int, digest: str, kernel: str = ""):
    import jax
    del digest   # cache key only: separates program counts per model
    if kernel:
        interp = kernel == "interpret"
        return (
            jax.jit(functools.partial(_scores_entry_kernel,
                                      n_steps=n_steps,
                                      interpret=interp),
                    donate_argnums=(3,)),
            jax.jit(functools.partial(_leaves_entry_kernel,
                                      n_steps=n_steps,
                                      interpret=interp)),
        )
    return (
        jax.jit(functools.partial(_scores_entry, n_steps=n_steps),
                donate_argnums=(3,)),
        jax.jit(functools.partial(_leaves_entry, n_steps=n_steps)),
    )


def _scores_entry(forest, raw, n_real, buf, *, n_steps):
    from ..ops.predict import forest_scores
    return forest_scores(forest, raw, n_real, buf, n_steps=n_steps)


def _leaves_entry(forest, raw, n_real, *, n_steps):
    from ..ops.predict import forest_leaves
    return forest_leaves(forest, raw, n_real, n_steps=n_steps)


def _kernel_bins(forest, raw):
    """The kernel's single [n, F] i32 input matrix over the INNER
    (used) columns — quantized bins on numerical columns,
    int-truncated raw values on categorical ones."""
    from ..ops.predict import quantize_rows_kernel
    return quantize_rows_kernel(forest, raw[:, forest.used_cols])


def _kernel_traverse(forest, n: int, *, n_steps, interpret, num_class,
                     leaves=False):
    """Build the Pallas traversal for one (bucket, forest) cell; all
    geometry is static from the traced operand shapes, so the bucket
    stays the only shape the program sees (the retrace contract)."""
    from ..ops.pallas.serve_kernel import make_serve_traverse
    t, ni = (int(s) for s in forest.split_feature.shape)
    return make_serve_traverse(
        n=int(n), trees=t, ni_pad=ni,
        nl_pad=int(forest.leaf_value.shape[1]),
        cat_words_w=int(forest.cat_words.shape[1]) // max(ni, 1),
        n_feat=int(forest.used_cols.shape[0]),
        num_class=int(num_class), n_steps=int(n_steps),
        leaf_dtype=forest.leaf_value.dtype, leaves=leaves,
        interpret=interpret)


def _scores_entry_kernel(forest, raw, n_real, buf, *, n_steps,
                         interpret):
    import jax.numpy as jnp

    from ..ops.pallas.serve_kernel import forest_kernel_args
    fn = _kernel_traverse(forest, buf.shape[0], n_steps=n_steps,
                          interpret=interpret, num_class=buf.shape[1])
    nr = jnp.reshape(n_real, (1,)).astype(jnp.int32)
    return fn(*forest_kernel_args(forest), _kernel_bins(forest, raw),
              nr, buf)


def _leaves_entry_kernel(forest, raw, n_real, *, n_steps, interpret):
    import jax.numpy as jnp

    from ..ops.pallas.serve_kernel import forest_kernel_args
    fn = _kernel_traverse(forest, raw.shape[0], n_steps=n_steps,
                          interpret=interpret, num_class=1,
                          leaves=True)
    nr = jnp.reshape(n_real, (1,)).astype(jnp.int32)
    return fn(*forest_kernel_args(forest, leaves=True),
              _kernel_bins(forest, raw), nr)


class ServingQueue:
    """Double-buffered async dispatch for the small-batch latency path:
    ``submit`` returns immediately until ``depth`` batches are in
    flight (batch t+1 is on the device before t's scores are pulled),
    ``result`` blocks on the OLDEST in-flight batch.

    Since ISSUE 17 the submit->completion latency is measured HERE,
    once, at the source: ``submit`` stamps the pending's host clock,
    the completion handler records the delta into a per-bucket
    log-bucketed histogram (``latency_percentiles`` is what the bench
    reports as p50/p99/p999) and forwards it to the serving flight
    recorder when ``LGBM_TPU_SERVE_METRICS`` is live."""

    def __init__(self, engine: ServingEngine,
                 depth: Optional[int] = None):
        self.engine = engine
        self.depth = int(depth or _queue_depth_knob())
        self._inflight: deque = deque()
        self._results: deque = deque()
        self._submitted = 0
        self._lat: Dict[int, flight.LatencyHistogram] = {}
        self._flight = engine._flight

    def submit(self, X: np.ndarray) -> int:
        """Queue one small batch; returns its ticket (the 0-based
        submission index — ``result()`` hands batches back in this
        order).  Blocks only when the queue is already ``depth``
        deep."""
        if self._flight is not None:
            # occupancy BEFORE the full-queue block: saturation is
            # visible as depth == cap in the window record
            self._flight.sample_queue_depth(
                self.engine.model.digest, len(self._inflight),
                self.depth)
        while len(self._inflight) >= self.depth:
            # make room by completing the oldest (the double-buffer
            # steady state: one finishing, depth-1 in flight)
            self._results.append(self._complete())
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        t0 = time.perf_counter()
        p = self.engine.dispatch(X)
        p.t_sub = t0
        self._inflight.append(p)
        ticket = self._submitted
        self._submitted += 1
        return ticket

    def _complete(self) -> np.ndarray:
        p = self._inflight.popleft()
        bucket, t0 = p.bucket, p.t_sub
        host = self.engine.collect(p)
        if t0 is not None:
            dt = time.perf_counter() - t0
            h = self._lat.get(bucket)
            if h is None:
                h = self._lat[bucket] = flight.LatencyHistogram()
            h.add(dt)
            if self._flight is not None:
                self._flight.observe_latency(
                    self.engine.model.digest, bucket, dt)
        return host

    def latency_snapshot(self) -> Dict[int, List[int]]:
        """Per-bucket histogram bin counts (copies) of every
        submit->completion delta this queue has drained."""
        return {b: list(h.counts) for b, h in sorted(self._lat.items())}

    def latency_percentiles(self, qs=(50.0, 99.0, 99.9)) -> dict:
        """Percentiles in MILLISECONDS derived from the merged
        per-bucket histograms (never a sample list), plus the drained
        count — the bench's serving-block latency source."""
        merged = flight.LatencyHistogram()
        for h in self._lat.values():
            merged.merge(h)
        out = {"p" + format(q, "g").replace(".", "") + "_ms":
               round(merged.percentile_s(q) * 1e3, 4) for q in qs}
        out["count"] = merged.count
        return out

    def result(self) -> np.ndarray:
        """Scores of the oldest submitted batch (FIFO)."""
        if self._results:
            return self._results.popleft()
        if not self._inflight:
            raise LightGBMError("ServingQueue.result() with nothing "
                                "in flight")
        return self._complete()

    def drain(self) -> List[np.ndarray]:
        out = []
        while self._results:
            out.append(self._results.popleft())
        while self._inflight:
            out.append(self._complete())
        return out
