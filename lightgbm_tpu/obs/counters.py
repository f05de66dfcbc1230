"""Training work counters and live-buffer watermarks.

Five work counters of a tree are functions of the finished tree, so
while tracing the booster derives them on the host
(``counters_from_tree``) from one pull of the tree's small arrays after
the ``Tree::grow`` barrier — no second grow program, no extra
dispatch, and the same whether the tracer was enabled before the
booster was built or after.  Six more are not (whether the fused
scan's histogram hook ran at a split, which child it was told, and
whether the split was decided by a membership set, is forgotten once
the split is done): the grow program counts them in its
state, always, traced or not, and they ride the same pull as
``TreeArrays.side_miss``.  Counter semantics:

  splits            — splits taken (== num_leaves - 1 of the tree)
  rows_partitioned  — in-bag rows moved by the physical/logical
                      partition, summed over splits; equals the sum of
                      the tree's ``internal_count`` exactly (i32
                      accumulation: exact below 2^31 rows per tree)
  rows_histogrammed — in-bag rows streamed through histogram
                      construction: the root pass plus the smaller
                      child of every split (the subtraction trick,
                      serial_tree_learner.cpp:287-327)
  fused_splits      — splits executed by the fused partition+histogram
                      Pallas kernel (LGBM_TPU_FUSED path): ``splits``
                      on that route on a TPU, 0 on the unfused /
                      non-physical / interpreted paths
  side_miss_splits  — fused physical route: splits at which the
                      scan's histogram hook ran and the child the
                      finder's record called smaller (its
                      hessian-derived left count, ops/grow.py
                      ``pred_left``) was not the smaller one by the
                      exact counts, so the scan's one-sided histogram
                      was of the wrong child and the smaller one was
                      histogrammed again from the comb.  Counted off
                      the chip too, where the reference path takes
                      the comb-direct histogram at every split; 0 on
                      the other routes; never more than
                      ``hook_splits``
  rows_rehistogrammed — the rows of those smaller children (global
                      under the mesh learners): over
                      ``rows_partitioned`` it is the share of the
                      scan's row visits the estimate cost a second
                      read for (benchmarks: ``scan_side_miss``)
  hook_splits       — fused physical route: splits at which the hook
                      ran, i.e. whose parent held no more rows (a
                      shard, under the mesh learners) than
                      ``fused_split.hook_crossover_rows`` says the
                      hook is worth; at the others the scan was told
                      no child and the smaller one was histogrammed
                      from the comb.  Counted off the chip too; 0 on
                      the other routes
  rows_hooked       — the parent rows of those splits (global under
                      the mesh learners): over ``rows_partitioned``
                      it is the share of the scan's row visits that
                      went through the hook (benchmarks:
                      ``scan_rows_hooked``)

  member_splits     — bundled comb (ISSUE 36): splits whose go-left
                      bit was a membership test of a bundle column's
                      bins, i.e. splits on a bundled sub-feature; 0 on
                      every other route
  rows_member       — the parent rows of those splits: over
                      ``rows_partitioned`` the share of the scan's row
                      visits decided by a membership set (benchmarks:
                      ``scan_member_share``)
  scan_steps        — physical routes: grid steps of the partition scan,
                      the sum over the tree's splits of ``ceil(parent
                      rows / R)`` at the ``R`` rows a step moves
                      (``partition_kernel2.scan_block_rows``; the
                      ``Tree::grow`` span carries it as
                      ``scan_block_rows``); under the mesh learners of
                      ``shards x ceil(parent rows / shards / R)``, from
                      the replicated record's global counts, as if the
                      rows lay evenly.  ``rows_partitioned`` over it is
                      the rows a step really moved (benchmarks:
                      ``scan_rows_per_step``): what a larger step wastes
                      on a parent's last partial block shows there.  0
                      off the physical routes

Plus HBM watermark sampling: ``hbm_live_bytes`` is the cheap
``jax.live_arrays`` census of live device buffers (catches leaks and
order-of-magnitude regressions from the host), and
``hbm_high_water_bytes`` is its allocator-side companion — the
runtime's ``peak_bytes_in_use`` when the backend reports it, else a
``jax.profiler.device_memory_profile`` census decoded in-repo.  The
run ledger samples both per iteration.

Lifecycle (ISSUE 5): the process-global ``counters`` / ``events``
stores are lock-guarded so concurrent recording never corrupts the
structures, and reset between ``lgb.train`` calls via ``reset_all()``
(called at the top of ``engine.train``), which ALSO clears every
warn-once set registered through ``on_reset`` — so a second training
run re-reports the psum / routing fallbacks its own configuration
triggers instead of inheriting the first run's suppression.  Note the
stores are still ONE per process: two ``lgb.train`` calls running
concurrently in different threads share (and reset) the same state,
so attribute per-run telemetry only when runs are sequential.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

import numpy as np

COUNTER_NAMES = ("splits", "rows_partitioned", "rows_histogrammed",
                 "fused_splits", "side_miss_splits", "rows_rehistogrammed",
                 "hook_splits", "rows_hooked", "member_splits",
                 "rows_member", "scan_steps")


def counters_to_dict(vec) -> Dict[str, float]:
    """Name a raw counter vector (``COUNTER_NAMES`` order)."""
    a = np.asarray(vec, np.float64).reshape(-1)
    return {name: float(a[i]) for i, name in enumerate(COUNTER_NAMES)}


def counters_from_tree(num_leaves, left_child, right_child,
                       internal_count, leaf_count,
                       side_miss=(0, 0, 0, 0), *,
                       fused: bool, scan_block_rows: int = 0,
                       shards: int = 1) -> np.ndarray:
    """The counter vector (``COUNTER_NAMES`` order) of one finished
    tree, from its host arrays; ``side_miss`` is the four (six under
    the bundled comb) the grow program counted
    (``TreeArrays.side_miss``); ``scan_block_rows`` the rows a grid
    step of the partition scan moves (0: no scan) on each of
    ``shards`` row shards.  Counts are integral
    f32 below 2^24 each; sums run in float64, exact far beyond the
    ~n*log2(L) a tree can reach (84M at Higgs 10.5M)."""
    splits = int(num_leaves) - 1
    leaf_c = np.asarray(leaf_count, np.float64)
    miss = [float(v) for v in np.asarray(side_miss).reshape(-1)]
    miss += [0.0] * (6 - len(miss))     # off the bundled comb: [4]
    if splits <= 0:
        # a stump: the root pass is all the work there was
        return np.array([0.0, 0.0, leaf_c[0], 0.0] + miss + [0.0])
    int_c = np.asarray(internal_count, np.float64)[:splits]

    def child_count(child):
        # leaves are encoded ~leaf and read leaf_count, inner nodes
        # read internal_count
        c = np.asarray(child, np.int64)[:splits]
        return np.where(c < 0, leaf_c[np.clip(-c - 1, 0, len(leaf_c) - 1)],
                        int_c[np.clip(c, 0, splits - 1)])

    smaller = np.minimum(child_count(left_child), child_count(right_child))
    steps = 0.0
    if scan_block_rows:
        steps = shards * np.ceil(int_c / (shards * scan_block_rows)).sum()
    # node 0 is the root: its count is the root pass
    return np.array([splits, int_c.sum(), int_c[0] + smaller.sum(),
                     splits if fused else 0] + miss + [steps], np.float64)


def tree_depth(num_leaves, left_child, right_child) -> int:
    """Internal nodes on the finished tree's longest root-to-leaf path:
    the node decisions a row of its deepest leaf needs (the bin-space
    replay, ``ops/predict.predict_leaf_bins``, makes one at every inner
    node); 0 for a stump.  Children are
    encoded ``~leaf`` and inner nodes by index, from the host arrays."""
    splits = int(num_leaves) - 1
    if splits <= 0:
        return 0
    children = np.stack([np.asarray(left_child, np.int64)[:splits],
                         np.asarray(right_child, np.int64)[:splits]], 1)
    deepest, level, nodes = 0, 1, np.array([0])
    while len(nodes):
        deepest = level
        nxt = children[nodes].reshape(-1)
        nodes, level = nxt[nxt >= 0], level + 1
    return deepest


class CounterStore:
    """Per-tree counter history + totals (host side, thread-safe)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._per_tree: List[Dict[str, float]] = []

    def record(self, vec) -> Dict[str, float]:
        d = counters_to_dict(vec)
        with self._lock:
            self._per_tree.append(d)
        return d

    def reset(self) -> None:
        with self._lock:
            self._per_tree.clear()

    @property
    def per_tree(self) -> List[Dict[str, float]]:
        with self._lock:
            return list(self._per_tree)

    def totals(self) -> Dict[str, float]:
        out = {name: 0.0 for name in COUNTER_NAMES}
        with self._lock:
            for d in self._per_tree:
                for name in COUNTER_NAMES:
                    out[name] += d.get(name, 0.0)
        return out


counters = CounterStore()


class EventCounter:
    """Host-side named occurrence counts for structural events that the
    device counter vector cannot carry (e.g. the hist_scatter psum
    fallback engaging at trace time).  Cheap, always on, thread-safe —
    recording is a locked dict increment; consumers (bench.py --json,
    obs report) attach ``totals()`` to their artifacts when
    non-empty."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}

    def record(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + n

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()

    def totals(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


events = EventCounter()


# -- run lifecycle ----------------------------------------------------
# warn-once caches elsewhere in the library (grow.py's psum fallback
# shape set, routing.py's fallback sets) register a clear-callback here
# so one reset call restarts the whole observability state between
# training runs
_RESET_HOOKS: List[Callable[[], None]] = []
_RESET_LOCK = threading.Lock()


def on_reset(fn: Callable[[], None]) -> Callable[[], None]:
    """Register a callable to run on ``reset_all()`` (idempotent —
    re-registration of the same function is a no-op); returns it."""
    with _RESET_LOCK:
        if fn not in _RESET_HOOKS:
            _RESET_HOOKS.append(fn)
    return fn


def reset_all() -> None:
    """Reset the per-run observability state: counter history, event
    totals, and every registered reset hook (the run ledger registers
    its reset here at import, as do grow.py's warn-once caches — all
    within ONE library generation, so a purge/reimport cannot cross
    stores).  Called between ``lgb.train`` runs (engine.train); does
    NOT touch the tracer — trace files span whatever window the user
    enabled."""
    counters.reset()
    events.reset()
    with _RESET_LOCK:
        hooks = list(_RESET_HOOKS)
    for fn in hooks:
        fn()


def hbm_live_bytes(platform: Optional[str] = None) -> int:
    """Total bytes of live jax arrays (all platforms, or one).

    This is the host-side census: cheap, always available, an UPPER
    bound on what the arrays pin but blind to allocator fragmentation
    and transient scratch.  The allocator's own view lives in
    ``hbm_high_water_bytes``."""
    import jax
    total = 0
    for a in jax.live_arrays(platform):
        try:
            total += int(a.nbytes)
        except Exception:  # deleted/donated buffers race the census
            pass
    return total


# probe-once cache: None = unprobed, True/False = whether
# memory_stats() reports peak_bytes_in_use on this backend
_MEMSTATS_HAS_PEAK: List[bool] = []
# running max of the pprof-census fallback (reset per training run via
# on_reset below) — makes the fallback an actual high-water mark of
# allocator-side censuses instead of a point-in-time reading
_PPROF_HIGH_WATER: List[int] = [0]


def _reset_pprof_high_water() -> None:
    _PPROF_HIGH_WATER[0] = 0


def hbm_high_water_bytes() -> Optional[int]:
    """Allocator high-water mark, when the runtime reports one.

    Preferred source: ``device.memory_stats()['peak_bytes_in_use']``
    (TPU/GPU runtimes) — the true allocator peak, including scratch the
    live-array census never sees; the max across local devices is the
    per-chip watermark that decides whether a shape fits HBM.  Fallback
    when memory_stats has no peak (probed once per process):
    ``jax.profiler.device_memory_profile()`` decoded by the in-repo
    pprof reader (``obs/xattr.py``), tracked as a RUNNING MAX across
    calls within a run — an allocator-side high-water of sampled
    censuses (it can miss transient spikes between samples, and
    measures the allocator's view, so it may sit below the
    ``hbm_live_bytes`` host census).  The fallback serializes the heap
    profile per call — callers only sample it per-iteration while
    tracing, where walls are already not the metric of record.
    Returns ``None`` when neither source exists, so callers can
    distinguish "zero bytes" from "no profiler"."""
    import jax
    if not _MEMSTATS_HAS_PEAK or _MEMSTATS_HAS_PEAK[0]:
        peaks = []
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats and stats.get("peak_bytes_in_use") is not None:
                peaks.append(int(stats["peak_bytes_in_use"]))
        if not _MEMSTATS_HAS_PEAK:
            _MEMSTATS_HAS_PEAK.append(bool(peaks))
        if peaks:
            return max(peaks)
    try:
        from .xattr import parse_pprof_space_bytes
        prof = jax.profiler.device_memory_profile()
        if not prof:
            return None
        _PPROF_HIGH_WATER[0] = max(_PPROF_HIGH_WATER[0],
                                   int(parse_pprof_space_bytes(prof)))
        return _PPROF_HIGH_WATER[0]
    except Exception:
        return None


# the fallback's running max is per-RUN state: restart it with the
# counters/events/ledger on reset_all()
on_reset(_reset_pprof_high_water)
