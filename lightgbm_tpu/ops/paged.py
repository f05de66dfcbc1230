"""Paged comb: larger-than-HBM training (ISSUE 15, ROADMAP item 5).

The physical fast path keeps the whole ``[n_alloc, C]`` comb matrix
HBM-resident: 10.5M rows already peaks at 10.2 GB of a 15.75 GB chip,
so the 100M+-row production shapes cannot train at all.  This module
makes the comb a PAGED abstraction — fixed-size pages whose home is
host memory, streamed through ping-pong HBM page buffers with the
page ``p+1`` transfer issued while page ``p`` computes:

* :func:`double_buffer_schedule` emits the typed DMA/compute event
  list for one page sweep (prefetch depth 1, two rotating buffers,
  optional write-back interleave for the refresh sweep that flushes
  tree ``t-1``'s refreshed pages while tree ``t``'s pages prefetch —
  the first async-pipelining step of ROADMAP item 5);
* :func:`validate_schedule` is the audit the analyzer's dma-race pass
  runs over every registered schedule (and over the ``bad_page``
  red-team fixture, which must fail): no compute may read an
  in-flight page, every page lands exactly once, and the overlap
  property (next transfer issued before this page computes) is
  checked, all off-chip;
* :class:`PageStore` holds the comb as host-resident numpy pages plus
  the two device page buffers, and assembles/flushes the grow-time
  window by executing the schedule.

Geometry comes from ``obs/costmodel.page_schedule`` (the PR-9
planner): pages are ``rows_per_page`` logical rows (a multiple of the
partition block R; the planner's own are whole blocks of the largest,
grow.PHYS_ROW_PAD) plus the PHYS_ROW_SLACK tail each page buffer
carries for kernel DMA tails, so the partition / hist / stream /
fused kernels — already dynamic-grid scans over row blocks — extend
their grid over pages instead of being rewritten.

Off-TPU emulation note (same contract as ``LGBM_TPU_PHYS=interpret``):
on this CPU container the per-tree window is fully materialised from
the pages before the grow program runs — pages round-trip bit-exactly
through the schedule, so paged and unpaged training produce
byte-identical trees BY CONSTRUCTION, which is the acceptance
contract tests/test_paged.py pins.  On chip the same schedule streams
the per-level partition sweeps page by page (the DMA accounting
``page_schedule`` prices: every page read+written once per level plus
once for the fused refresh+root pass); the resident set is then the
three page buffers + fixed arenas the hbm-budget pass validates — not
the full comb.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# schedule event kinds: (kind, page, buf)
DMA_IN = "dma_in"          # start host->HBM transfer of page into buf
DMA_WAIT = "dma_wait"      # wait for the transfer of page into buf
COMPUTE = "compute"        # kernels consume page (resident in buf)
DMA_OUT = "dma_out"        # start HBM->host write-back of page from buf
DMA_OUT_WAIT = "dma_out_wait"  # wait for the write-back of page from
                               # buf (required before REFILLING buf —
                               # the inbound fill would overwrite the
                               # bytes the outbound engine still reads)

Event = Tuple[str, int, int]


def double_buffer_schedule(n_pages: int, *,
                           writeback: bool = False) -> List[Event]:
    """The ping-pong page schedule: page ``p`` computes out of buffer
    ``p % 2`` while page ``p+1``'s inbound transfer fills the other
    buffer.  With ``writeback`` the sweep also flushes each computed
    page back to host (the refresh sweep: tree t-1's refreshed pages
    stream out while tree t's stream in) — each buffer's outbound
    transfer is WAITED before the buffer refills (an inbound fill over
    an in-flight write-back would corrupt the host copy; the audit's
    ``PAGE_WRITEBACK_RACE`` rule), so the write-back overlaps the
    other buffer's compute window, not its own refill."""
    n_pages = int(n_pages)
    if n_pages <= 0:
        raise ValueError(f"n_pages must be positive, got {n_pages}")
    ev: List[Event] = [(DMA_IN, 0, 0)]
    out_inflight = {}          # buf -> page whose write-back is open
    for p in range(n_pages):
        buf = p % 2
        ev.append((DMA_WAIT, p, buf))
        if p + 1 < n_pages:
            nbuf = (p + 1) % 2
            if nbuf in out_inflight:
                # drain the buffer's previous write-back before the
                # inbound fill reuses it
                ev.append((DMA_OUT_WAIT, out_inflight.pop(nbuf), nbuf))
            # the overlap: page p+1's transfer is IN FLIGHT while page
            # p computes (into the other buffer, so no race)
            ev.append((DMA_IN, p + 1, nbuf))
        ev.append((COMPUTE, p, buf))
        if writeback:
            ev.append((DMA_OUT, p, buf))
            out_inflight[buf] = p
    for buf in sorted(out_inflight):
        ev.append((DMA_OUT_WAIT, out_inflight[buf], buf))
    return ev


def validate_schedule(events: List[Event], n_pages: int,
                      n_bufs: int = 2) -> List[str]:
    """Audit one page schedule; returns violation strings (empty =
    clean).  The rules mirror the kernel-level dma-race pass one level
    up, at page granularity:

    * ``PAGE_COMPUTE_NO_WAIT``  compute consumes a page whose inbound
      transfer was never waited — the kernels read a buffer the DMA
      engine is still filling (the red-team fixture's seeded bug);
    * ``PAGE_READ_INFLIGHT``    a transfer into a buffer was started
      and not yet waited when a compute reads that buffer — the
      double-buffer rotation collapsed onto one buffer;
    * ``PAGE_WAIT_NEVER_STARTED``  a wait with no matching start;
    * ``PAGE_WRITEBACK_STALE``  a page's write-back names a buffer
      that no longer holds it;
    * ``PAGE_WRITEBACK_RACE``  an inbound fill starts into a buffer
      whose write-back is still in flight — the fill overwrites the
      bytes the outbound engine is reading and corrupts the host copy;
    * ``PAGE_WRITEBACK_UNDRAINED``  a write-back never waited by the
      sweep's end — the host copy is not guaranteed complete when the
      next sweep (or the checkpoint layer) reads the pages;
    * ``PAGE_MISSING`` / ``PAGE_DUP``  every page must compute exactly
      once per sweep;
    * ``PAGE_NO_OVERLAP``  (only when more than one page exists) no
      inbound transfer was in flight during any compute — the
      schedule serialises DMA after compute and the ~29 s/tree of
      host DMA lands on the critical path.
    """
    out: List[str] = []
    inflight: Dict[int, Optional[int]] = {b: None for b in range(n_bufs)}
    resident: Dict[int, Optional[int]] = {b: None for b in range(n_bufs)}
    out_open: Dict[int, Optional[int]] = {b: None for b in range(n_bufs)}
    computed: List[int] = []
    saw_overlap = False
    for kind, page, buf in events:
        if buf not in inflight:
            out.append(f"PAGE_BAD_BUF: event {(kind, page, buf)} names "
                       f"buffer {buf} outside the {n_bufs}-buffer "
                       f"ping-pong set")
            continue
        if kind == DMA_IN:
            if out_open[buf] is not None:
                out.append(
                    f"PAGE_WRITEBACK_RACE: inbound fill of page {page} "
                    f"starts into buffer {buf} while the write-back of "
                    f"page {out_open[buf]} from it is still in flight")
            inflight[buf] = page
        elif kind == DMA_WAIT:
            if inflight[buf] != page:
                out.append(
                    f"PAGE_WAIT_NEVER_STARTED: wait for page {page} on "
                    f"buffer {buf} but the in-flight transfer there is "
                    f"{inflight[buf]}")
            else:
                resident[buf] = page
                inflight[buf] = None
        elif kind == COMPUTE:
            if any(p is not None for p in inflight.values()):
                saw_overlap = True
            if inflight[buf] is not None:
                out.append(
                    f"PAGE_READ_INFLIGHT: compute on page {page} reads "
                    f"buffer {buf} while the transfer of page "
                    f"{inflight[buf]} into it is still in flight")
            if resident[buf] != page:
                out.append(
                    f"PAGE_COMPUTE_NO_WAIT: compute consumes page "
                    f"{page} from buffer {buf} but the waited-for "
                    f"resident page there is {resident[buf]}")
            computed.append(page)
        elif kind == DMA_OUT:
            if resident[buf] != page:
                out.append(
                    f"PAGE_WRITEBACK_STALE: write-back of page {page} "
                    f"from buffer {buf} but the resident page there is "
                    f"{resident[buf]}")
            out_open[buf] = page
        elif kind == DMA_OUT_WAIT:
            if out_open[buf] != page:
                out.append(
                    f"PAGE_WAIT_NEVER_STARTED: wait for the write-back "
                    f"of page {page} from buffer {buf} but the open "
                    f"write-back there is {out_open[buf]}")
            else:
                out_open[buf] = None
        else:
            out.append(f"PAGE_BAD_EVENT: unknown kind {kind!r}")
    for buf, page in sorted(out_open.items()):
        if page is not None:
            out.append(
                f"PAGE_WRITEBACK_UNDRAINED: the write-back of page "
                f"{page} from buffer {buf} is never waited — the host "
                f"copy is not guaranteed complete at sweep end")
    for p in range(int(n_pages)):
        c = computed.count(p)
        if c == 0:
            out.append(f"PAGE_MISSING: page {p} never computes")
        elif c > 1:
            out.append(f"PAGE_DUP: page {p} computes {c}x in one sweep")
    if int(n_pages) > 1 and not saw_overlap and not out:
        out.append(
            "PAGE_NO_OVERLAP: no inbound transfer was in flight during "
            "any compute — the schedule serialises host DMA after "
            "compute instead of overlapping it")
    return out


def plan_pages(*, rows: int, f_pad: int, padded_bins: int,
               num_leaves: int, stream: bool = True,
               fused: bool = True, stream_kind: str = "binary",
               num_class: int = 1,
               rows_per_page: Optional[int] = None,
               force: bool = False,
               limit_bytes: Optional[int] = None) -> Dict:
    """The engaged page plan: ``costmodel.page_schedule`` over the
    engaged geometry — including ``stream_kind``, whose per-objective
    constant columns decide the comb line width near the lane
    boundary — honoring the ``LGBM_TPU_PAGE_ROWS`` override
    (``rows_per_page``) and the forced-paged mode (``force`` — the
    ``LGBM_TPU_PAGED=1`` tiny-budget CI shape, which pages even when
    the footprint fits the budget)."""
    from ..obs.costmodel import page_schedule
    plan = page_schedule(
        rows=rows, f_pad=f_pad, padded_bins=padded_bins,
        num_leaves=num_leaves, stream=stream, fused=fused,
        stream_kind=stream_kind, num_class=max(int(num_class), 1),
        rows_per_page=rows_per_page, limit_bytes=limit_bytes,
        force=force)
    if not plan.get("paged"):
        raise ValueError(
            "plan_pages called for a shape the planner keeps unpaged "
            f"(peak {plan.get('unpaged_peak_bytes')} <= limit "
            f"{plan.get('limit_bytes')}); routing should not have "
            "engaged the paged path")
    if not plan.get("fits", False):
        raise ValueError(
            f"page plan does not fit the HBM budget: {plan}")
    return plan


class PageStore:
    """The paged comb: host-resident numpy pages + two device page
    buffers, with the grow-time window assembled and flushed by
    executing the double-buffered schedule.

    Page ``p`` owns rows ``[p * rows_per_page, (p + 1) *
    rows_per_page)`` of the comb's ``n_alloc``-row line space; every
    page buffer is allocated at the planner's fixed page size
    (``rows_per_page + slack`` rows — the slack tail is the kernels'
    DMA-tail region, carried per page so the last page also round-
    trips the window's slack lines bit-exactly).  ``fetch_window`` /
    ``flush_window`` execute the inbound / write-back schedules; the
    per-page window update and extract are REAL jitted programs whose
    buffer shapes tests/test_mem.py equality-checks against the
    planner's page geometry."""

    def __init__(self, *, n_alloc: int, C: int, rows_per_page: int,
                 dtype=None):
        import jax.numpy as jnp
        from .grow import PHYS_ROW_SLACK
        self.n_alloc = int(n_alloc)          # rows incl. slack
        self.C = int(C)
        self.rows_per_page = int(rows_per_page)
        self.dtype = dtype if dtype is not None else jnp.float32
        self.slack = int(PHYS_ROW_SLACK)
        n_local = self.n_alloc - self.slack
        self.n_pages = -(-n_local // self.rows_per_page)
        # fixed page-buffer size: owned rows + the kernels' DMA-tail
        # slack (never larger than the window itself — the one-page
        # degenerate case of a forced tiny-budget run)
        self.page_lines = min(self.rows_per_page + self.slack,
                              self.n_alloc)
        self._pages: List[Optional[np.ndarray]] = [None] * self.n_pages
        self.stats = {"fetch_s": 0.0, "flush_s": 0.0, "cycles": 0,
                      "dma_bytes": 0}
        self._jit_update = None
        self._jit_extract = None

    # -- per-page device programs (the "paged jaxprs" test_mem pins) --
    def _update_fn(self):
        """window, page_buf, line0 -> window with the page's lines
        landed (donated window: the assembly rotates one buffer)."""
        import jax
        import jax.numpy as jnp
        if self._jit_update is None:
            from .pallas.layout import comb_operand
            C = self.C

            def upd(window, page_buf, line0, valid_lines):
                # land only the page's VALID lines: a mid-window page
                # must not smear its slack tail over its neighbor.  The
                # window is plane-major (layout.py): a page is the same
                # line range of every plane
                view = comb_operand(window, C)
                at = (0,) * (view.ndim - 2) + (line0, 0)
                lines = jnp.arange(page_buf.shape[-2])[:, None]
                cur = jax.lax.dynamic_slice(view, at, page_buf.shape)
                mixed = jnp.where(lines < valid_lines, page_buf, cur)
                return jax.lax.dynamic_update_slice(
                    view, mixed, at).reshape(window.shape)

            self._jit_update = jax.jit(upd, donate_argnums=(0,))
        return self._jit_update

    def _extract_fn(self):
        """window, line0 -> one page buffer (the write-back slice)."""
        import jax
        if self._jit_extract is None:
            from .pallas.layout import comb_operand, comb_operand_shape
            C = self.C
            page = comb_operand_shape(self.page_lines, C)

            def ext(window, line0):
                view = comb_operand(window, C)
                return jax.lax.dynamic_slice(
                    view, (0,) * (view.ndim - 2) + (line0, 0), page)

            self._jit_extract = jax.jit(ext)
        return self._jit_extract

    def _line0(self, p: int) -> int:
        # clamp so the last page's full-size buffer stays in range (its
        # tail overlaps the previous page's rows; valid_lines masks the
        # overlap out on update, and flush writes it back verbatim)
        return min(p * self.rows_per_page,
                   self.n_alloc - self.page_lines)

    def _valid_lines(self, p: int) -> int:
        return self.n_alloc - self._line0(p) if p == self.n_pages - 1 \
            else self.rows_per_page

    # -- schedule execution ------------------------------------------
    def flush_window(self, window) -> None:
        """Write the window back to host pages (one DMA_OUT-only sweep;
        interleaved with the next fetch on chip — here the host mirror
        IS the destination, so the extract + host pull is the
        transfer)."""
        t0 = time.perf_counter()
        ext = self._extract_fn()
        for p in range(self.n_pages):
            page = ext(window, self._line0(p))
            self._pages[p] = np.asarray(page)
            self.stats["dma_bytes"] += self._pages[p].nbytes
        self.stats["flush_s"] += time.perf_counter() - t0

    def fetch_window(self):
        """Assemble the grow-time window by executing the double-
        buffered inbound schedule: ``DMA_IN`` stages the host page into
        the ping-pong device buffer, ``COMPUTE`` lands the resident
        buffer's lines into the window (on chip: the kernels' page
        sweep consumes the buffer here)."""
        import jax
        import jax.numpy as jnp
        if any(p is None for p in self._pages):
            raise RuntimeError("fetch_window before pages were built "
                               "(flush_window installs them)")
        t0 = time.perf_counter()
        sched = double_buffer_schedule(self.n_pages)
        bad = validate_schedule(sched, self.n_pages)
        if bad:
            raise RuntimeError(f"page schedule failed its own audit: "
                               f"{bad}")
        from .pallas.layout import comb_shape
        window = jnp.zeros(comb_shape(self.n_alloc, self.C), self.dtype)
        upd = self._update_fn()
        bufs: List = [None, None]
        for kind, p, b in sched:
            if kind == DMA_IN:
                # the host->HBM staging transfer (async on chip; jax
                # dispatches it ahead of the consuming compute here)
                bufs[b] = jax.device_put(self._pages[p])
                self.stats["dma_bytes"] += self._pages[p].nbytes
            elif kind == COMPUTE:
                window = upd(window, bufs[b], self._line0(p),
                             self._valid_lines(p))
        self.stats["fetch_s"] += time.perf_counter() - t0
        self.stats["cycles"] += 1
        return window

    def drop(self) -> None:
        """Forget every page (checkpoint re-anchor: the next window is
        rebuilt from bins + scores in initial row order, so the
        per-page permutations reset with it)."""
        self._pages = [None] * self.n_pages

    @property
    def built(self) -> bool:
        return all(p is not None for p in self._pages)

    def geometry(self) -> Dict:
        """The engaged geometry (tests equality-check this against
        ``costmodel.page_schedule``'s plan)."""
        return {
            "n_pages": self.n_pages,
            "rows_per_page": self.rows_per_page,
            "page_lines": self.page_lines,
            "page_bytes": self.page_lines * self.C
            * np.dtype(self.dtype).itemsize,
            "C": self.C,
        }
