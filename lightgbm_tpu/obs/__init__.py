"""Structured training telemetry: phase tracer, device counters, run
ledger, cost model, perf-regression gate.

Five pieces (see ``docs/PERF_NOTES.md`` and the README observability
section):

* ``tracer`` — nested wall-clock spans with device barriers, JSON-lines
  / Chrome-trace output.  Enable with ``LGBM_TPU_TRACE=/path.jsonl`` or
  ``tracer.enable(path)``, before or after the booster is built: the
  compiled programs are the same either way.  Barriers are child spans
  ``<name>::wait``; JAX's builds are ``jax::*`` events.
* ``counters`` — per-tree work counters (splits, rows partitioned,
  rows histogrammed, fused-kernel engagements) derived on the host
  from the finished tree while tracing, plus ``hbm_live_bytes``
  watermark sampling.
* ``ledger`` (``obs/metrics.py``) — the per-iteration time-series
  registry: phase-wall deltas, counter deltas, eval history, HBM
  watermark and mesh-collective records, embedded in ``bench/v3``
  artifacts with a ``provenance()`` header (git SHA, jax version,
  device kind).
* ``costmodel`` — scheme-aware per-phase HBM-bytes / FLOPs
  predictions for the hist / partition / fused / stream kernels,
  joined with measured walls by ``obs report --roofline``.
* ``python -m lightgbm_tpu.obs report`` / ``... diff`` — summarize
  traces and schema-versioned BENCH records; diff two records as a
  noise-aware regression gate (``obs/regress.py``,
  ``tools/perf_gate.py``) — per-kernel device times included.
* ``xattr`` (``python -m lightgbm_tpu.obs attr``) — device-time kernel
  attribution: a dependency-free xplane ``.pb`` decoder, a Mosaic/XLA
  kernel classifier onto the cost-model entries, and the phase<->kernel
  join (achieved GB/s per kernel, per-phase dispatch overhead, mesh
  straggler skew); captures embed in bench records as the ``device``
  block.  The tracer mirrors spans as ``jax.profiler.TraceAnnotation``
  while a capture is active (``tracer.annotate``).
* ``doctor`` (``python -m lightgbm_tpu.obs doctor``) — layered
  environment preflight for chip runs (backend, libtpu/PJRT, the
  BENCH_r03 ``TPU_WORKER_HOSTNAMES`` env class, topology, HBM/VMEM vs
  the costmodel tables, capture smoke, disk headroom); ``bench.py``
  preflights through it and ``tools/chip_run.py`` gates on it.
* ``trend`` (``python -m lightgbm_tpu.obs trend``) — the BENCH_r*
  trajectory as a routing-digest-aware table with drift flags.
* ``findings`` — the shared finding schema + 0/1/2 exit-code contract
  every obs subcommand renders and exits through.

Everything here is import-light (no jax at import time) so the
no-trace hot path pays nothing.  ``reset_run()`` restarts the per-run
state (counters, events, ledger, warn-once caches) and is called
between ``lgb.train`` runs.
"""
from .counters import (COUNTER_NAMES, CounterStore, EventCounter,
                       counters, counters_from_tree, counters_to_dict,
                       events,
                       hbm_high_water_bytes, hbm_live_bytes, on_reset)
from .counters import reset_all as reset_run
from .metrics import (LEDGER_SCHEMA, MULTICHIP_SCHEMA, RunLedger,
                      ledger, provenance)
from .tracer import TRACE_ENV, TRACE_SCHEMA, Tracer, tracer

__all__ = [
    "tracer", "Tracer", "TRACE_ENV", "TRACE_SCHEMA",
    "counters", "CounterStore", "COUNTER_NAMES", "counters_to_dict",
    "counters_from_tree",
    "events", "EventCounter", "hbm_live_bytes", "hbm_high_water_bytes",
    "ledger", "RunLedger", "LEDGER_SCHEMA", "MULTICHIP_SCHEMA",
    "provenance",
    "on_reset", "reset_run",
]
