"""Named-phase timers.

Reference analog: Common::Timer / FunctionTimer (utils/common.h:973-1057),
which accumulate per-phase wall time and dump at exit when built with
-DUSE_TIMETAG.  Here timing is always available (enable with
``global_timer.enable()``) and phase names mirror the reference hot path
(BeforeTrain / Boosting / GBDT::grow) so traces are comparable.  Device work is asynchronous under JAX; callers that want accurate
device timings should pass ``block=True`` which calls
``jax.block_until_ready`` on the result of the timed region.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict

# bound at import time so each library generation (module purges in
# tests/test_fused.py / tools/tpu_smoke.py) mirrors into ITS tracer
from ..obs.tracer import tracer as _obs_tracer


class Timer:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = collections.defaultdict(float)
        self._count: Dict[str, int] = collections.defaultdict(int)
        self._enabled = False

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        self._acc.clear()
        self._count.clear()

    @contextlib.contextmanager
    def time(self, name: str):
        # the structured tracer (lightgbm_tpu.obs) generalizes this
        # class; when IT is enabled, mirror the region as a span so the
        # legacy call sites land in the JSONL/Chrome trace too
        _tracer = _obs_tracer
        if not self._enabled and not _tracer.enabled:
            yield
            return
        start = time.perf_counter()
        try:
            if _tracer.enabled:
                with _tracer.span(name):
                    yield
            else:
                yield
        finally:
            if self._enabled:
                self._acc[name] += time.perf_counter() - start
                self._count[name] += 1

    def summary(self) -> Dict[str, float]:
        return dict(self._acc)

    def report(self) -> str:
        lines = ["LightGBM-TPU timer summary:"]
        for name in sorted(self._acc, key=self._acc.get, reverse=True):
            lines.append(
                f"  {name}: {self._acc[name]:.4f}s over {self._count[name]} calls"
            )
        return "\n".join(lines)


global_timer = Timer()
