"""Unified profiling harness for the TPU tools (and ``bench.py``).

Absorbs the boilerplate every ``tools/profile_*.py`` script used to
copy-paste (the ``tools/_timing.py`` helpers fold in here):

* ``pull``          — execution barrier: host-pull a scalar (the
                      round-3b methodology in docs/PERF_NOTES.md).
* ``bench_call``    — eager re-dispatch loop, one warmup, mean secs.
* ``bench_selffeed``— eager loop feeding each call's output back in
                      (donation-friendly self-chaining).
* ``bench_chain``   — the IN-JIT ``fori_loop`` chain with a result
                      accumulator that depends on the kernel's writes
                      and a host value pull as the barrier — the
                      pattern every partition/fused microbench uses so
                      the ~20-50 ms dispatch floor can't pollute
                      per-step numbers (keep ``reps`` >= 1000 on-chip).
* ``median_of_k``   — median-of-k wall times for noisy host-level runs.
* ``xplane_capture``— optional ``jax.profiler`` trace capture around a
                      block (kernel-level attribution of the fused
                      grow loop; view in xprof / tensorboard).
* ``bench_record`` / ``write_bench_record`` — schema-versioned BENCH
  JSON records (``BENCH_SCHEMA``) so the perf trajectory is
  machine-comparable across PRs; read them back with
  ``python -m lightgbm_tpu.obs report --bench``.

Import from a tools script as ``from profile_lib import bench_chain``
(scripts sys.path-insert their own directory) or as
``tools.profile_lib`` from the repo root.
"""
from __future__ import annotations

import contextlib
import datetime
import json
import os
import sys
import time
from typing import Callable, Optional, Sequence

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import jax
import jax.numpy as jnp

# v3 (ISSUE 5): records carry a hostname-free provenance block (git
# SHA, jax/jaxlib versions, backend/device kind) and — when traced —
# the embedded run-ledger trajectory.  obs/report.py and obs/regress.py
# read v2 records too (they just lack those blocks).  The schema id is
# defined once, in obs/report.py.
from lightgbm_tpu.obs.report import BENCH_SCHEMA_V3 as BENCH_SCHEMA


def pull(out) -> float:
    """Execution barrier: host-pull one scalar."""
    jax.block_until_ready(out)
    x = out
    while isinstance(x, (tuple, list)):
        x = x[0]
    return float(jnp.sum(x))


def bench_call(fn: Callable, *args, reps: int = 10,
               chain: bool = False) -> float:
    """Average seconds per call of ``fn(*args)`` after one warmup.

    ``chain=True`` feeds each call's output back in as the (single)
    argument — for loop-carried-state experiments.
    """
    out = fn(*args)
    pull(out)
    t0 = time.perf_counter()
    if chain:
        for _ in range(reps):
            out = fn(out)
    else:
        for _ in range(reps):
            out = fn(*args)
    pull(out)
    return (time.perf_counter() - t0) / reps


def bench_selffeed(fn: Callable, x0, reps: int = 100) -> float:
    """Average secs/call of ``y = fn(y)`` starting from ``fn(x0)``
    (the kernel-microbench eager chain: output aliases input)."""
    y = fn(x0)
    pull(y)
    t0 = time.perf_counter()
    for _ in range(reps):
        y = fn(y)
    pull(y)
    return (time.perf_counter() - t0) / reps


def bench_chain(step: Callable, *carry, reps: int,
                acc_init=None, donate: Sequence[int] = (0, 1)):
    """Seconds per step of an IN-JIT chained loop.

    ``step(*carry) -> (*carry', delta)`` runs ``reps`` times inside one
    jitted ``lax.fori_loop`` whose accumulator adds each ``delta`` (so
    XLA cannot dead-code the chain), with ``carry`` buffers donated.
    The function is called twice — once to compile+warm, once timed —
    and both runs barrier with a HOST VALUE PULL of the accumulator.

    Returns ``(secs_per_step, final_carry)``.
    """
    acc0 = jnp.float32(0) if acc_init is None else acc_init

    def many(*c):
        def body(_, st):
            *cc, acc = st
            out = step(*cc)
            *cc2, d = out
            return (*cc2, acc + d.astype(acc.dtype))
        return jax.lax.fori_loop(0, reps, body, (*c, acc0))

    f = jax.jit(many, donate_argnums=tuple(donate))
    out = f(*carry)
    float(out[-1])              # host pull = real barrier
    t0 = time.perf_counter()
    out = f(*out[:-1])
    float(out[-1])
    dt = (time.perf_counter() - t0) / reps
    return dt, out[:-1]


def median_of_k(fn: Callable, *args, k: int = 5, warmup: int = 1) -> float:
    """Median wall seconds of ``fn(*args)`` over ``k`` barriered runs."""
    for _ in range(warmup):
        pull(fn(*args))
    times = []
    for _ in range(k):
        t0 = time.perf_counter()
        pull(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


@contextlib.contextmanager
def xplane_capture(path: Optional[str] = None):
    """Capture a ``jax.profiler`` trace (xplane) around the block when
    ``path`` (or the LGBM_TPU_XPLANE env var) is set; no-op otherwise.

    While the capture is live the obs tracer emits
    ``jax.profiler.TraceAnnotation("obs::<phase>")`` around every span,
    so the capture's host plane carries the obs phase names.  Decode
    the result in-repo with ``python -m lightgbm_tpu.obs attr <path>``
    (per-kernel device time, cost-model bytes join) — xprof /
    tensorboard still read the same files."""
    path = path or os.environ.get("LGBM_TPU_XPLANE", "")
    if not path:
        yield
        return
    from lightgbm_tpu.obs import tracer as _obs_tracer
    jax.profiler.start_trace(path)
    _obs_tracer.annotate(True)
    try:
        yield
    finally:
        _obs_tracer.annotate(False)
        jax.profiler.stop_trace()
        print(f"[profile_lib] xplane trace -> {path} "
              "(decode: python -m lightgbm_tpu.obs attr)",
              file=sys.stderr)


def bench_record(metric: str, value: float, unit: str, **extra) -> dict:
    """Schema-versioned benchmark record (BENCH_r*.json point) with the
    bench/v3 provenance header — every artifact answers "what code, on
    what stack, on what device" by itself (and the diff gate refuses to
    compare records whose engaged knob sets differ)."""
    from lightgbm_tpu.obs.metrics import provenance
    rec = {
        "schema": BENCH_SCHEMA,
        "metric": metric,
        "value": value,
        "unit": unit,
        "backend": jax.default_backend(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "provenance": provenance(),
    }
    rec.update(extra)
    return rec


def write_bench_record(path: str, rec: dict) -> None:
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
        f.write("\n")
