"""Pallas TPU kernel: single-scan two-sided in-place row partition.

A split compacts the parent's contiguous row range of the comb into
left|right.  The design this replaced read the parent's rows TWICE (one
scan keeping left, one keeping right), compacted through carry windows
so every DMA write held only valid rows, and then copied the whole
partitioned range back from scratch - 3 full DMA passes, two [2R, R]
compaction matmuls per block, and inline DMA waits everywhere.

This kernel does ONE scan with OVERLAPPING full-R writes and a SINGLE
[R, R] compaction matmul per block (row order within a leaf segment is
semantically irrelevant, so the right side is packed in REVERSE):

  phase 0 (scan; 1-block read-ahead; deferred write waits):
    Per block, compute go-left bits once and pack BOTH sides into ONE
    R-row buffer with a single [R, R] one-hot matmul: left rows at
    slots [0, nl) ascending, right rows at slots [R - nr, R)
    DESCENDING (slot R-1-posR).  nl + nr <= R, so the two never
    collide.  The packed buffer is then written twice:
      * to ``rows`` at the ascending left cursor (cursor += nl): valid
        left rows at the front, garbage behind, overwritten by the next
        left write.  Safety: the write end never passes the end of the
        current block (kept <= rows seen), and reads run exactly one
        block ahead — in-flight reads and in-place writes never
        overlap.  Same-side writes overlap each other, so each write
        waits the previous same-side write before issuing (one block of
        compute hides the latency; packed buffers ping-pong).
      * to ``scratch`` at the DESCENDING right cursor ([cur_r - R,
        cur_r), cursor -= nr): valid right rows at the TOP, garbage
        below, overwritten by the next (lower) right write.  The right
        zone grows downward from T = s0 + (nb_live + 1)*R (the +R
        headroom keeps every full-R write >= s0).
    The LAST live block skips the left write; its left rows are instead
    packed DIRECTLY below its right rows (slot offset R - nr - nl), so
    the single scratch write leaves the left tail + the whole right
    zone CONTIGUOUS in scratch at [T - m, T), m = tl + nright.
  phase 1 (copyback): direct HBM->HBM DMAs move that span to
    rows[s0 + nleft - tl, s0 + par_cnt); the tail block read-merges
    rows' own content beyond the range (neighbour leaves keep their
    rows).  Left in-place garbage is provably confined to
    [s0 + nleft - tl, s0 + cnt) — exactly the copyback span.

DMA traffic per split: read cnt + write ~2*cnt (both destinations) +
copy ~nright twice; the compaction matmul work HALVES vs the previous
two-sided [2R, R] scheme and only 4 [R, C] VMEM buffers ride the
kernel (was 6).  Layout/contract: identical to partition_kernel.py
(see its module docstring) — [n, C] f32 rows with C % 128 == 0,
bf16-exact column values, sel i32[8], par_cnt == 0 dead calls
supported — EXCEPT that right-segment rows land in reverse order
(partitions are multiset-preserving, not stable).  Right-zone scratch
writes stay within [s0, s0 + cnt + 2R) (see layout.COMB_ROW_SLACK).

Round 6 (ISSUE 3): the per-block compaction is now a PLUGGABLE
``pack_impl`` hook on ``_scan_kernel`` — the matmul packing below is
the ``LGBM_TPU_PARTITION=matmul`` bisection scheme, while the default
``permute`` packing (partition_kernel3.py) computes destinations with
prefix sums and moves rows with O(log R) butterfly routing, producing a
bit-identical packed layout.  The schedule, cursor math and copyback
in this file serve both schemes unchanged.

Grid-step economics.  A step of this scan costs ``F + R x c`` on the
v5e with F = 1.40 us - the step itself, the issue and wait of its six
DMA descriptors, its three SMEM cursors: no row's work, and in no VLIW
bundle (tools/bundle_census.py reads twice the bundles for twice the
rows, within 2%) - and c = 3.15 ns a row, ~2.1 of it the butterfly
compaction and ~1.0 the copy-back (tools/profile_fused.py, VARS=scan at
R = 512 / 1,024 / 2,048: 5.92 / 4.39 / 3.91 ns a parent row; PERF.md,
Findings, PR 37 holds the table and the chip call).  Hence: (a) the
scan is a single 1-D grid (no second phase full of skipped-but-billed
steps); (b) the copyback runs as a SEPARATE pallas_call whose dynamic
grid is sized exactly from the scan's (nleft, m) outputs, with large
blocks (pure DMA); (c) R is the largest power of two whose stack fits
the default scoped VMEM (``scan_block_rows`` below: 2,048 rows at one
plane, 1,024 at two) - under the permute compaction, whose rounds are
O(log R) a row.  The matmul compaction is O(R) a row and stays at 512,
the knee of the only sweep it had (512 / 768 / 1,024 / 1,536 rows: 10.8
/ 11.4 / 11.9 / 12.9 ns a row at 1M rows, docs/PERF_NOTES.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (COMB_ROW_SLACK, COPYBACK_ROWS, SCAN_ROWS_MAX,
                     SCAN_ROWS_MIN, SCAN_ROWS_WIDE, SCOPED_VMEM_LIMIT,
                     check_lane_width, comb_shape, fit_rows, hbm_copies,
                     plane_copies)
from .partition_kernel import _HBM, SEL_S0, SEL_CNT, SEL_FEAT, \
    _go_left, make_reference_partition

# cursor SMEM i32[8] slots
_CUR_L, _CUR_TL, _CUR_R = 0, 1, 2


# Both scans build under layout.SCOPED_VMEM_LIMIT.  The hook's
# [ngroups, M, N] accumulator is an OUTPUT block and not on that stack:
# 1,024 rows x 384 lanes builds under the limit at 12.04 MiB of stack
# beside 2 x 4.25 MiB of accumulator, 512 x 896 at 14.59 beside 2 x
# 13.75.
#
# Lines of the comb ([C] f32) and further bytes a row of the block that
# the scan's stack is priced at.  An upper envelope of what the TPU
# compiler itself reports, read off-chip for the described v5e by
# lowering the limit until it refuses (ISSUE 37; PERF.md, Findings, PR
# 37: the table).  The UNFUSED permute scan's stack is its seven [R, C]
# buffers to the byte - the schedule's four and the compaction's three
# scoped ones (partition_kernel3._pack_permute): 6.98 MiB at 2,048 rows
# x 128 lanes, 14.01 at 2,048 x 256, 7.01 at 1,024 x 256 - and the
# matmul compaction's is under it.  The hook adds 1.5 to 2.8 KiB a row
# at 4 to 50 feature groups and 4.7 at 110 (its one-hot operands, the
# block's bf16 copy) - MiB of stack at rows x lanes (groups): 2.97 at
# 512 x 128 (4), 6.05 at 1,024 x 128, 12.42 at 2,048 x 128, 4.58 at 512
# x 256 (18), 9.04 at 1,024 x 256, 18.39 at 2,048 x 256 (refused), 6.55
# at 512 x 384 (40), 12.04 at 1,024 x 384 (34), 8.40 at 512 x 512 (50),
# 14.59 at 512 x 896 (110).  An eighth line and 3 KiB a row lie 6 to
# 25% over every one of them.
_SCAN_LINES, _SCAN_ROW_BYTES = 8, 3 * 1024


def scan_vmem_bytes(R: int, C: int) -> int:
    """The scoped VMEM a grid step of ``R`` rows on a comb of ``C``
    lanes is priced at (see ``_SCAN_LINES``): f32 lines whatever the
    comb's dtype - the compaction's words are 32-bit, and bf16 storage
    is refused by Mosaic today (ops/grow.py)."""
    return R * (_SCAN_LINES * C * 4 + _SCAN_ROW_BYTES)


def scan_block_rows(C: int, *, scheme: str = "permute",
                    vmem_limit: int = SCOPED_VMEM_LIMIT) -> int:
    """Rows one grid step of the single-scan partition moves on a comb
    of ``C`` lanes, with or without the histogram hook: the largest
    power of two in [SCAN_ROWS_MIN, SCAN_ROWS_MAX] whose price fits
    ``vmem_limit`` - and below SCAN_ROWS_MIN, down to SCAN_ROWS_WIDE,
    on a comb past seven planes, where not even 512 rows fit (128
    rows at sixteen; the unfused scan's stack there reads 7.28 MiB
    at 128 x 2,048, 14.54 at 256 and 28.75 at 512, the compiler's own
    report, under prices of 8.4 / 16.8 / 33.5).

    Why the largest: a step costs ``F + R x c`` and F is no row's work
    - the step itself, its descriptors' issue and wait, its cursors -
    so a row's share of it halves with every doubling, while c, the
    O(log R) butterfly, does not grow (tools/profile_fused.py on the
    v5e, VARS=scan over R = 512 / 1,024 / 2,048 at both widths;
    PERF.md, Findings, PR 37: the table, F and c).  One algorithm that
    wants another parameter at another width: 2,048 rows at one plane,
    1,024 at two and at three.  The hook's accumulator is no input: it
    is not on the scan's stack (``SCOPED_VMEM_LIMIT``), so the fused scan
    and the pair behind LGBM_TPU_FUSED=0 take the same block and leave
    a leaf's rows in the same order.  Every shard of a mesh builds the
    same kernel from the same shapes, so R is equal on all of them.

    The one-hot MATMUL compaction stays at SCAN_ROWS_MIN: its [R, R]
    contraction is O(R) a row, and the only sweep it ever had (512 /
    768 / 1,024 / 1,536 rows: 10.8 / 11.4 / 11.9 / 12.9 ns a row,
    docs/PERF_NOTES.md) lost by every step up."""
    if scheme == "matmul":
        return SCAN_ROWS_MIN
    return fit_rows(lambda r: scan_vmem_bytes(r, C), SCAN_ROWS_MAX,
                    SCAN_ROWS_WIDE, vmem_limit)


def copyback_vmem_bytes(CB: int, C: int) -> int:
    """The scoped VMEM the copy-back is priced at for ``CB`` rows a
    step on a comb of ``C`` lanes: its two [CB, C] f32 tail buffers and
    512 B a row.  The compiler's report: 2.72 MiB at 2,048 x 128, 4.81
    at 2,048 x 256, 4.08 at 256 x 2,048, 8.14 at 512 x 2,048."""
    return CB * (2 * C * 4 + 512)


def copyback_block_rows(C: int) -> int:
    """Rows a step of the copy-back moves on a comb of ``C`` lanes:
    the largest power of two up to COPYBACK_ROWS whose price fits the
    scoped VMEM (2,048 up to seven planes, 512 at sixteen).  The full
    blocks are HBM -> HBM DMAs; only the tail block stages in VMEM, so
    the block only bounds the descriptors a step."""
    return fit_rows(lambda r: copyback_vmem_bytes(r, C), COPYBACK_ROWS, 8)


def _pack_matmul(x, sel_ref, cnt, blk, is_last, out_ref, *, R: int,
                 C: int):
    """One-hot-matmul block compaction (the original single-scan
    scheme): left rows ascending at [loff, loff + nl), right rows
    REVERSED at [R - nr, R), via one [R, R] one-hot contraction.
    Writes the packed [R, C] block to ``out_ref`` and returns ``(nl,
    nr, None)``: its go-left bits are lane-oriented, so the scan's
    ``block_cb`` computes its own.

    This is the ``LGBM_TPU_PARTITION=matmul`` packing; the default
    permutation packing (same output layout, O(log R) butterfly routing
    instead of the O(R)-per-row matmul) lives in
    partition_kernel3._pack_permute.  Both produce IDENTICAL packed
    buffers bit-for-bit for bf16-exact columns — the permute scheme
    additionally preserves arbitrary f32 columns exactly (it moves
    rows with selects, never through the MXU)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    e_col = (lane == sel_ref[SEL_FEAT]).astype(jnp.float32)
    col = jax.lax.dot_general(
        e_col, x.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [1, R]
    pos_r = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    valid = pos_r < (cnt - blk * R)
    gleft = _go_left(col, sel_ref) & valid
    gright = jnp.logical_xor(gleft, valid)           # ~gleft&valid
    # stable intra-block positions, both sides in one [2, R]
    r_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    striu = (r_i < c_i).astype(jnp.bfloat16)
    klf = gleft.astype(jnp.float32)
    krf = gright.astype(jnp.float32)
    kb = jnp.concatenate([klf, krf], axis=0).astype(jnp.bfloat16)
    pos2 = jax.lax.dot_general(
        kb, striu, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [2, R]
    nl = jnp.sum(klf).astype(jnp.int32)
    nr = jnp.sum(krf).astype(jnp.int32)
    # ONE packed buffer: left rows ascending at loff, right rows
    # DESCENDING from slot R-1 (slots [R - nr, R); segment row
    # order is irrelevant).  Last block: left rows sit directly
    # below the right rows (loff = R - nr - nl) so the single
    # scratch write leaves left tail + right zone contiguous.
    loff = jnp.where(is_last, R - nr - nl, 0)
    dstl = pos2[0:1].astype(jnp.int32) + loff
    dstr = (R - 1) - pos2[1:2].astype(jnp.int32)
    dst = jnp.where(gleft, dstl,
                    jnp.where(gright, dstr, -1))     # [1, R]
    slot = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    PT = (slot == dst).astype(x.dtype)               # [R, R]
    packed = jax.lax.dot_general(
        PT, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [R, C]
    out_ref[:] = packed.astype(x.dtype)
    return nl, nr, None


def _scan_kernel(sel_ref, rows_in, scratch_in,
                 rows_ref, scratch_ref, out_ref,
                 vx, pk, cursor,
                 sem_r, sem_wl, sem_wr,
                 *, R: int, C: int, n: int, init_cb=None, block_cb=None,
                 pack_impl=None):
    """Single-phase scan.  out_ref SMEM i32[2]: [0] nleft, [1] m (rows
    to copy back: left tail + right zone).

    rows / scratch are plane-major (layout.py): ``n`` rows a plane, and
    every transfer of an [R, C] block is one row DMA a plane on one
    semaphore (``plane_copies``), all started, then all waited.

    ``vx`` / ``pk`` are the read and the packed blocks, [2, R, C] each:
    block ``blk`` works in slot ``blk % 2`` and reads ahead into the
    other, the slot a traced index, so the step's body - the compaction
    with its unrolled routing, the hook - is traced, lowered and
    compiled ONCE, not once a parity (ISSUE 37: trace + lower is paid
    by every process, the persistent cache keying on the lowered
    module; halving it is what pays for a larger block's unrolling).

    ``init_cb()`` / ``block_cb(x, blk, cnt, side)`` are OPTIONAL
    trace-time hooks for
    kernels that extend the scan with extra per-block VMEM compute
    (fused_split.py accumulates child histograms from the resident
    block): init_cb runs in the blk == 0 init, block_cb runs on each
    live block's [R, C] rows right after the compaction, before
    the write waits; ``side`` is what the compaction hands on (below).
    Hooks must not touch the DMA/cursor state — the
    schedule's safety argument above assumes this body is the only
    writer.

    ``pack_impl(x, sel_ref, cnt, blk, is_last, out_ref) -> (nl, nr,
    side)`` swaps the per-block compaction implementation (default: the
    one-hot matmul above; partition_kernel3 plugs the butterfly-routing
    permutation in).  Every implementation must write the SAME packed
    layout to ``out_ref`` — left rows ascending at [loff, loff + nl),
    right rows reversed at [R - nr, R) — so the block schedule, cursor
    math and copyback stay scheme-independent and have exactly one home
    here.
    ``side`` is ``None`` or the block's ``(go_left, go_right)`` bits as
    [R, C] lane-replicated f32 0/1 arrays (invalid rows 0 in both), so
    ``block_cb`` need not extract the split column a second time."""
    blk = pl.program_id(0)
    s0 = sel_ref[SEL_S0]
    cnt = sel_ref[SEL_CNT]
    nb_live = (cnt + R - 1) // R

    def _read(start, buf, sem):
        return plane_copies(rows_in, start, R, buf, sem, n=n, C=C)

    def _wait_write(sem):
        # a write's descriptors again, for their sizes only
        for cp in plane_copies(rows_ref, 0, R, pk.at[0], sem, n=n, C=C,
                               to_hbm=True):
            cp.wait()

    @pl.when(blk == 0)
    def _init0():
        cursor[_CUR_L] = s0
        cursor[_CUR_TL] = 0
        # right zone grows DOWN from T; the +R headroom keeps every
        # full-R descending write >= s0 even when almost all rows go
        # right with an unaligned cnt (write start is provably
        # >= T - nright - R >= s0 since nright <= nb_live * R)
        cursor[_CUR_R] = s0 + (nb_live + 1) * R
        # dead call (par_cnt == 0): no other write runs — answer here
        out_ref[0] = 0
        out_ref[1] = 0
        if init_cb is not None:
            init_cb()

    @pl.when(blk < nb_live)
    def _scan():
        start = s0 + blk * R
        is_last = blk == nb_live - 1

        @pl.when(blk == 0)
        def _prime():
            for cp in _read(start, vx.at[0], sem_r.at[0]):
                cp.start()

        slot = jax.lax.rem(blk, 2)
        vx_cur, pk_cur = vx.at[slot], pk.at[slot]

        for cp in _read(start, vx_cur, sem_r.at[slot]):
            cp.wait()

        @pl.when(blk + 1 < nb_live)
        def _ra():
            for cpn in _read(start + R, vx.at[1 - slot],
                             sem_r.at[1 - slot]):
                cpn.start()

        x = vx_cur[...]
        pack = pack_impl or functools.partial(_pack_matmul, R=R, C=C)
        nl, nr, side = pack(x, sel_ref, cnt, blk, is_last, pk_cur)

        if block_cb is not None:
            block_cb(x, blk, cnt, side)

        # overlapping same-side writes must issue in order: wait the
        # previous same-side write first (its latency hid behind this
        # block's compute, so the wait is normally already satisfied;
        # packed buffers ping-pong with the slot)
        @pl.when(blk > 0)
        def _wl_wait():
            _wait_write(sem_wl)

        @pl.when(jnp.logical_not(is_last))
        def _wl_go():
            for cpo in plane_copies(rows_ref, cursor[_CUR_L], R, pk_cur,
                                    sem_wl, n=n, C=C, to_hbm=True):
                cpo.start()
            cursor[_CUR_L] = cursor[_CUR_L] + nl

        @pl.when(is_last)
        def _wl_last():
            cursor[_CUR_TL] = nl

        @pl.when(blk > 0)
        def _wr_wait():
            _wait_write(sem_wr)

        for cpr in plane_copies(scratch_ref, cursor[_CUR_R] - R, R,
                                pk_cur, sem_wr, n=n, C=C, to_hbm=True):
            cpr.start()
        cursor[_CUR_R] = cursor[_CUR_R] - nr

    # ---- scan end: drain the outstanding scratch write, emit results ----
    # (the last left write was already waited by the final block's
    # _wl_wait; the final block issues no left write of its own)
    @pl.when((blk == nb_live - 1) & (nb_live > 0))
    def _fin():
        _wait_write(sem_wr)                      # last scratch write
        tl = cursor[_CUR_TL]
        nleft = cursor[_CUR_L] - s0 + tl
        out_ref[0] = nleft
        out_ref[1] = tl + (s0 + (nb_live + 1) * R - cursor[_CUR_R])


def _copyback_kernel(sel_ref, scratch_in, rows_in, rows_ref,
                     va, vb, sem,
                     *, R: int, CB: int, C: int, n: int):
    """Move the contiguous span scratch[src0, src0+m) to
    rows[dst0, dst0+m); the tail block read-merges rows' own content
    beyond the span.  sel: [src0, dst0, m].  Plane-major like the scan:
    every move is one DMA a plane."""
    blk = pl.program_id(0)
    src0, dst0, m = sel_ref[0], sel_ref[1], sel_ref[2]

    def _run(copies):
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()

    @pl.when(blk * CB < m)
    def _go():
        last = (blk + 1) * CB >= m

        @pl.when(jnp.logical_not(last))
        def _full():
            _run(hbm_copies(scratch_in, src0 + blk * CB, rows_ref,
                            dst0 + blk * CB, CB, sem, n=n, C=C))

        @pl.when(last)
        def _tail():
            _run(plane_copies(scratch_in, src0 + blk * CB, CB, va, sem,
                              n=n, C=C))
            _run(plane_copies(rows_in, dst0 + blk * CB, CB, vb, sem,
                              n=n, C=C))
            rid = jax.lax.broadcasted_iota(jnp.int32, (CB, C), 0)
            live = rid < (m - blk * CB)
            va[:] = jnp.where(live, va[:], vb[:])
            _run(plane_copies(rows_ref, dst0 + blk * CB, CB, va, sem,
                              n=n, C=C, to_hbm=True))


def copyback_call(sel, rows1, scratch1, nleft, m, *, R: int,
                  cb_block: int, n: int, C: int, dtype,
                  interpret: bool = False):
    """Shared tail of the single-scan partition: derive the contiguous
    scratch span from the scan's (nleft, m) outputs and run the copyback
    pallas_call.  The span math encodes the scan's headroom invariant
    (T = s0 + (ceil(cnt/R) + 1)*R, left tail tl = m - (cnt - nleft)) —
    fused_split._call reuses this so the invariant has exactly one home.

    m = tl + nright with nright = cnt - nleft; the scan left the span
    contiguous at [T - m, T)."""
    cb_kern = functools.partial(_copyback_kernel, R=R, CB=cb_block, C=C,
                                n=n)
    cnt = sel[SEL_CNT]
    tl = m - (cnt - nleft)
    T = sel[SEL_S0] + (jnp.maximum(-(-cnt // R), 0) + 1) * R
    sel_cb = jnp.stack(
        [T - m, sel[SEL_S0] + nleft - tl, m]).astype(jnp.int32)
    nb_cb = jnp.maximum(-(-m // cb_block), 1)
    return pl.pallas_call(
        cb_kern,
        name="lgbm_copyback",
        grid=(nb_cb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=_HBM),
                  pl.BlockSpec(memory_space=_HBM)],
        out_specs=pl.BlockSpec(memory_space=_HBM),
        out_shape=jax.ShapeDtypeStruct(comb_shape(n, C), dtype),
        scratch_shapes=[pltpu.VMEM((cb_block, C), dtype),
                        pltpu.VMEM((cb_block, C), dtype),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(sel_cb, scratch1, rows1)


def make_partition_ss(n: int, C: int, *, R: int = SCAN_ROWS_MIN,
                      size: int = 0,
                      dtype=jnp.float32, interpret: bool = False,
                      dynamic: bool = False,
                      cb_block: int = 0,
                      pack_impl=None, interpret_kernel: bool = False):
    """Single-scan partition: ``partition(sel, rows, scratch[,
    grid_blocks]) -> (rows', scratch', nleft)``, the contract of
    partition_kernel.make_reference_partition (the copyback sub-call is
    hidden inside the returned function).  The interpret path IS that
    XLA reference, which is STABLE — the compiled kernel packs
    right-segment rows in reverse, so the two agree on segment
    membership/counts but NOT on row order within the right segment.
    Nothing downstream may depend on intra-segment order.

    ``interpret_kernel=True`` (with ``interpret=True``) instead runs
    the REAL scan + copyback kernels through the Pallas interpreter —
    same block schedule, manual DMAs, SMEM cursors and packed row
    ORDER as the compiled kernel (the interpreter honours the aliased
    manual-DMA semantics; verified by tests/test_partition_perm.py).
    Static grids only (``dynamic`` must be False) — the off-TPU grow
    path's static bucket classes are exactly that shape.

    ``pack_impl`` swaps the per-block compaction (see _scan_kernel);
    partition_kernel3.make_partition_perm passes the butterfly-routing
    permutation packing through here so the schedule has one home.
    ``cb_block`` 0 takes the copy-back's block from the width
    (``copyback_block_rows``)."""
    check_lane_width(C, dtype)
    if interpret and not interpret_kernel:
        return make_reference_partition(n, C, dtype=dtype,
                                        dynamic=dynamic)
    if interpret_kernel and dynamic:
        raise ValueError(
            "interpret_kernel supports static grids only (the Pallas "
            "interpreter cannot run a traced grid bound)")
    nblocks = max((size + R - 1) // R, 1)
    cb_block = cb_block or copyback_block_rows(C)
    kern = functools.partial(_scan_kernel, R=R, C=C, n=n,
                             pack_impl=pack_impl)

    def _call(sel, rows, scratch, grid_blocks):
        rows1, scratch1, res = pl.pallas_call(
            kern,
            name="lgbm_partition_scan",
            grid=(grid_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=_HBM),
                      pl.BlockSpec(memory_space=_HBM)],
            out_specs=[pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[jax.ShapeDtypeStruct(comb_shape(n, C), dtype),
                       jax.ShapeDtypeStruct(comb_shape(n, C), dtype),
                       jax.ShapeDtypeStruct((2,), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((2, R, C), dtype),
                            pltpu.VMEM((2, R, C), dtype),
                            pltpu.SMEM((8,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            input_output_aliases={1: 0, 2: 1},
            interpret=interpret_kernel,
        )(sel, rows, scratch)
        nleft, m = res[0], res[1]
        rows2 = copyback_call(sel, rows1, scratch1, nleft, m, R=R,
                              cb_block=cb_block, n=n, C=C, dtype=dtype,
                              interpret=interpret_kernel)
        return rows2, scratch1, nleft

    if dynamic:
        def partition(sel, rows, scratch, grid_blocks):
            return _call(sel, rows, scratch, grid_blocks)
    else:
        def partition(sel, rows, scratch):
            return _call(sel, rows, scratch, nblocks)

    return partition


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import partition_args, register_kernel


@register_kernel("partition_ss_matmul", kind="partition",
                 note="single-scan kernel, one-hot matmul packing "
                      "(LGBM_TPU_PARTITION=matmul)")
def _analysis_partition_ss():
    n, C = 2048 + COMB_ROW_SLACK, 128
    return (make_partition_ss(
        n, C, R=scan_block_rows(C, scheme="matmul"), size=2048),
        partition_args(n, C))


@register_kernel("partition_ss_matmul_cat", kind="partition",
                 note="single-scan matmul kernel, cat-subset bitset sel "
                      "(ISSUE 16)")
def _analysis_partition_ss_cat():
    from .layout import CAT_BITSET_WORDS
    n, C = 2048 + COMB_ROW_SLACK, 128
    return (make_partition_ss(
        n, C, R=scan_block_rows(C, scheme="matmul"), size=2048),
        partition_args(n, C, sel_words=CAT_BITSET_WORDS))
