"""Pallas TPU kernel: fused single-scan partition + one child's histogram.

Per-split the unfused pipeline is TWO pallas_call entries (partition
scan, smaller-child comb-direct histogram) plus the copyback — ~8-10
Mosaic grid steps and a ~120 us fixed floor at small leaves, and the
histogram pass RE-READS from HBM the exact rows the partition scan just
streamed through VMEM (~32 ms per M rows of the ~165 ms/M marginal cost
at 10.5M rows; docs/PERF_NOTES.md "Next levers" #3).

This kernel runs the single-scan two-sided compaction UNCHANGED — same
block schedule, same overlapping garbage-tail writes, same copyback
sub-call, with the per-block packing selected through _scan_kernel's
``pack_impl`` hook (permute butterfly routing by default, the one-hot
matmul under LGBM_TPU_PARTITION=matmul; bit-identical packed layouts
either way) — and, WHERE THE CALLER ASKS FOR IT, additionally
accumulates ONE child's 2-channel (grad, hess) histogram in VMEM from
the row block already resident for the compaction (the hook):

  * ``sel[SEL_SIDE]`` names the child (``SIDE_LEFT`` / ``SIDE_RIGHT``)
    or none (``SIDE_NONE``): a traced scalar in SMEM, so one compiled
    kernel serves all three.  With no child the hook's body sits
    behind a ``pl.when`` and costs a scalar branch a block; the
    accumulator is still zeroed at block 0, so the call returns zeros;
  * the block's values are masked with that child's go-left /
    go-right bits as the permute compaction hands them over
    (row-oriented and lane-replicated, from its one MXU transpose - a
    by-product of the contraction that carries the routing word, so it
    is not gated); under the matmul compaction, whose bits are
    lane-oriented, the split column is extracted a second time in ROW
    orientation ([R, 1] matvec) and the bits recomputed, inside the
    hook;
  * hist_kernel2._hist_accumulate — the comb-direct kernel's own
    nibble one-hot contraction, the one accumulate body there is —
    adds them into one [ngroups, M, N] VMEM block (constant index map
    -> resident across the dynamic grid);
  * ``hook_histogram`` extracts the same-feature diagonal blocks
    (hist_kernel2._diag_extract): the wrapper calls it, or with
    ``raw_hist=True`` the caller does, in the branch that reads it.

Which child, and whether any: the caller wants the SMALLER one (the
sibling is parent minus child).  The hook masks and contracts EVERY row
of the parent to get the histogram of a child that holds at most half
of them, where the comb-direct kernel reads only the child's now
contiguous rows, at a fixed cost of one more kernel launch: past a
parent size that depends on the histogram's geometry the second way is
the cheaper one (``hook_crossover_rows``: measured on the chip at the
two widths the benchmark runs; PERF.md, Findings, PR 35).  So
ops/grow.py names no child at parents past that size and histograms
the exactly smaller child from the comb; below it names the side that
the finder's best-split record says is smaller (ops/split.py
``derived_counts``: the reference's estimate from the hessian sums,
known before the scan is dispatched, where the exact counts are known
only when it finishes - under the mesh learners after a psum), and on
a split where the estimate named the wrong one - a split close to
even - it takes the comb-direct histogram too.  Accumulating BOTH
children and throwing one away is not free on the v5e: there is no DMA
shadow for it to ride under.  At 512 rows a step (PRs 28 and 30) a
step moved ~1.5 KB a row, 0.94 us at 819 GB/s, and took 5.0 us with
the hook (9.8 ns a row visit; 5.9 us with both sides): the scan is
bound by what it computes in VMEM, and a side's contraction is ~1.2k
of such a step's VLIW bundles (PERF.md, Findings, PR 28 and PR 30;
the rows a step moves now come from ``scan_block_rows``, PR 37).

Layout/contract: identical to partition_kernel2.make_partition_ss, plus
``f_pad`` value/bin column conventions from hist_kernel2's comb-direct
kernel (bins at cols [0, f_pad), (g*w, h*w) at [f_pad, f_pad+2)).
On the CPU trained trees are bit-identical to the unfused path: the
interpret builder COMPOSES the reference implementations (the XLA
reference partition + comb-direct histogram of the named child's
range) so off-TPU tests exercise the fused orchestration with exactly
the unfused arithmetic.  On the chip the hook sums a child's rows in
the parent's scan blocks (``scan_block_rows``: 1,024-2,048 rows) and
the comb-direct kernel in the child's own 2048-row ones: same bf16
operands, same f32 accumulation, other groupings, so last bits can
differ between the two - as they can between two block sizes of the
scan, which order a leaf's rows differently.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hist_kernel2 import _diag_extract, _hist_accumulate, \
    build_histogram_comb, hist_geometry
from .partition_kernel import _HBM, SEL_S0, SEL_CNT, SEL_FEAT, SEL_SIDE, \
    SIDE_LEFT, SIDE_NONE, _go_left, make_reference_partition
from .layout import COMB_ROW_SLACK, HIST_COMB_ROWS, SCAN_ROWS_MIN, \
    SCOPED_VMEM_LIMIT
from .partition_kernel2 import _scan_kernel, \
    copyback_block_rows, copyback_call, scan_block_rows, scan_vmem_bytes

_CHANNELS = 2       # (grad, hess) — the 2-channel histogram layout

def hook_acc_bytes(f_pad: int, b: int) -> int:
    """Bytes of the hook's resident [ngroups, M, N] f32 accumulator for
    ``f_pad`` columns of ``b`` padded bins; 0 where the geometry has no
    whole number of feature groups (no hook can be built)."""
    _, g, m, nn, lo_n = hist_geometry(b, _CHANNELS)
    if b % lo_n != 0 or f_pad % g != 0:
        return 0
    return (f_pad // g) * m * nn * 4


def fused_supported(f_pad: int, b: int, C: int) -> bool:
    """Whether the fused kernel can be built for ``f_pad`` columns of
    ``b`` padded bins on a comb of ``C`` lanes: the hook's geometry has
    whole feature groups (hist_kernel2's constraint) and the scan's
    smallest block fits the scoped VMEM at ``scan_block_rows``'s own
    price (grow falls back to the separate partition + histogram pair
    where not).  The accumulator and its output buffer are not on that
    stack (layout.SCOPED_VMEM_LIMIT); at the widest comb the
    price admits, 896 lanes, they are 2 x 13.9 MiB of the chip's 128."""
    return bool(hook_acc_bytes(f_pad, b)) and \
        scan_vmem_bytes(SCAN_ROWS_MIN, C) <= SCOPED_VMEM_LIMIT


# Per-split cost of the two ways to the smaller child's histogram, as
# tools/profile_fused.py reads them on the v5e at an even split (the
# child is half the parent: the comb-direct way's worst case), fitted
# as fixed + rows x slope over leaves of 1k to 1M rows AT THE BLOCK
# ``scan_block_rows`` gives each width, so the rows a step moves ride
# on ``ngroups`` in the two measurements as the planes do (PERF.md,
# Findings, PR 37: the table and the chip call it came from; PR 35 read
# 10.57 / 2.815 and 39.20 / 9.135 at 512 rows a step).
# ngroups -> (us a split, ns a parent row) by which the comb-direct
# way's fixed cost exceeds the hook's, and the hook's row cost the
# comb-direct way's.
_CROSSOVER_MEASURED = {
    4: (8.05, 1.974),       # 32 columns, one plane (``higgs``), 2,048
                            # rows a step: 4,078 rows
    18: (48.43, 8.858),     # 144 columns, two planes (``msltr``), 1,024
                            # rows a step: 5,468
}
HOOK_ALWAYS = (1 << 31) - 1     # no i32 row count is past it


def hook_crossover_rows(ngroups: int) -> int:
    """The parent row count (a shard, under the mesh learners) PAST
    which the scan's histogram hook is the dearer way to the smaller
    child's histogram, for a histogram of ``ngroups`` feature groups;
    ``HOOK_ALWAYS`` where the hook wins at every size.

    One algorithm, another parameter at another width: the hook's cost
    is fixed + parent rows x its contraction, the comb-direct kernel's
    one more launch (and its own extraction) + child rows x the same
    contraction in larger blocks, and both grow with ``ngroups``.  The
    gaps are measured at two geometries and taken linear in ``ngroups``
    BETWEEN them; outside them the nearer measurement's pair serves as
    it is (a line through two points reads 0 rows at one group, which
    nothing measured; ``expo``'s 2 groups take ``higgs``'s 4,078 rows).
    The comb's planes and the scan's block ride on ``ngroups`` in the
    two measurements - 1 plane and 2,048 rows at 4 groups, 2 and 1,024
    at 18 - and are not separated."""
    (g0, (f0, r0)), (g1, (f1, r1)) = sorted(_CROSSOVER_MEASURED.items())
    t = min(max((ngroups - g0) / (g1 - g0), 0.0), 1.0)
    fixed_us = f0 + t * (f1 - f0)
    row_ns = r0 + t * (r1 - r0)
    if row_ns <= 0.0:
        return HOOK_ALWAYS
    return int(min(max(fixed_us, 0.0) * 1e3 / row_ns, HOOK_ALWAYS))


def hook_histogram(acc, f_pad: int, padded_bins: int):
    """The [f_pad, padded_bins, 2] histogram out of the scan's
    [ngroups, M, N] accumulator (50.6 us a split at 18 groups: called
    where the histogram is read, not at every split)."""
    b_hi, g, _, _, lo_n = hist_geometry(padded_bins, _CHANNELS)
    return _diag_extract(acc, f_pad // g, g, b_hi, _CHANNELS, lo_n,
                         f_pad, padded_bins)


def _side_flag(sel_ref, go_left, go_right):
    """The f32 0/1 row mask of the child ``sel[SEL_SIDE]`` names, from
    the two sides' masks (same shape, f32)."""
    return jnp.where(sel_ref[SEL_SIDE] == SIDE_LEFT, go_left, go_right)


def _fused_scan_kernel(sel_ref, rows_in, scratch_in,
                       rows_ref, scratch_ref, out_ref, hist_ref,
                       vx, pk, cursor,
                       sem_r, sem_wl, sem_wr,
                       *, R: int, C: int, n: int, f_pad: int, b_hi: int,
                       g: int, lo_n: int, ngroups: int, pack_impl=None):
    """partition_kernel2._scan_kernel + per-block histogram
    accumulation of the named child, injected through the scan's
    trace-time hooks so the compaction/DMA schedule (and its safety
    argument) has exactly one home.  The hooks are pure VMEM compute —
    no DMA/cursor state."""

    def _hist_init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    def _hist_block(x, blk, cnt, side):
        # ---- one child's histogram accumulation (the fusion) ----
        # nothing of it runs where the caller named no child
        @pl.when(sel_ref[SEL_SIDE] != SIDE_NONE)
        def _hook():
            # Mosaic has no direct bf16 -> i32 cast; hop through f32
            bins_i = x[:, :f_pad].astype(jnp.float32).astype(jnp.int32)
            v = x[:, f_pad:f_pad + _CHANNELS].astype(jnp.float32)
            if side is not None:
                # the compaction's own go-left / go-right bits, already
                # row-oriented and lane-replicated: take the value lanes
                flag_l, flag_r = side
                glf = flag_l[:, f_pad:f_pad + _CHANNELS]
                grf = flag_r[:, f_pad:f_pad + _CHANNELS]
            else:
                # the matmul compaction's bits are lane-oriented ([1,
                # R]); a [1, R] -> [R, 1] relayout is a Mosaic
                # transpose, a second exact matvec against the same
                # one-hot column is ~R*C MACs, noise next to its [R, R]
                # contraction
                e_colv = (jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
                          == sel_ref[SEL_FEAT]).astype(jnp.float32)
                col2 = jax.lax.dot_general(
                    x.astype(jnp.float32), e_colv,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [R, 1]
                pos_c = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
                valid2 = pos_c < (cnt - blk * R)
                gl2 = _go_left(col2, sel_ref) & valid2
                glf = gl2.astype(jnp.float32)
                grf = jnp.logical_xor(gl2, valid2).astype(jnp.float32)
            _hist_accumulate(bins_i, v * _side_flag(sel_ref, glf, grf),
                             hist_ref, b_hi=b_hi, g=g, c=_CHANNELS,
                             lo_n=lo_n, ngroups=ngroups)

    _scan_kernel(sel_ref, rows_in, scratch_in,
                 rows_ref, scratch_ref, out_ref,
                 vx, pk, cursor,
                 sem_r, sem_wl, sem_wr,
                 R=R, C=C, n=n, init_cb=_hist_init, block_cb=_hist_block,
                 pack_impl=pack_impl)


def make_fused_split(n: int, C: int, *, f_pad: int, padded_bins: int,
                     R: int = SCAN_ROWS_MIN, size: int = 0,
                     dtype=jnp.float32,
                     interpret: bool = False, dynamic: bool = False,
                     cb_block: int = 0,
                     hist_rpb: int = HIST_COMB_ROWS,
                     scan: str = "permute",
                     interpret_kernel: bool = False,
                     fused_kernel_interpret: bool = False,
                     raw_hist: bool = False):
    """Build ``fused(sel, rows, scratch[, grid_blocks]) -> (rows, scratch,
    nleft, h_side)`` — the single-scan partition contract of
    partition_kernel2.make_partition_ss extended with ONE child's
    [f_pad, padded_bins, 2] f32 histogram, accumulated during the scan:
    the left child's where ``sel[SEL_SIDE]`` is ``SIDE_LEFT``, the
    right's at ``SIDE_RIGHT``, all zeros at ``SIDE_NONE`` (the hook is
    skipped; rows, scratch and nleft do not depend on the side).
    ``raw_hist=True`` (compiled kernel only) hands ``h_side`` back as
    the kernel's [ngroups, M, N] accumulator, for a caller that calls
    ``hook_histogram`` only in the branch that reads it.

    ``scan`` selects the per-block compaction plugged into the shared
    schedule: ``"permute"`` (partition_kernel3's butterfly routing — the
    LGBM_TPU_PARTITION default) or ``"matmul"`` (the one-hot
    contraction).  Both produce bit-identical packed layouts, so the
    histogram hook and everything downstream are scheme-blind.

    The interpret path COMPOSES the reference pieces (partition
    emulation, then the comb-direct histogram of the named child's
    contiguous range) so the fused orchestration can be tested off-TPU
    with arithmetic identical to the unfused path's; with
    ``interpret_kernel=True`` the partition piece is the REAL scan +
    copyback run through the Pallas interpreter (compiled row order),
    letting CPU tests pin the cross-scheme identity at kernel depth.
    ``fused_kernel_interpret=True`` instead builds the REAL fused
    scan+histogram kernel and runs it through the Pallas
    interpreter (static grids only) — the off-chip pin for the kernel
    body itself, hook included."""
    from .layout import check_lane_width, comb_planes, comb_shape
    check_lane_width(C, dtype)
    if scan not in ("matmul", "permute"):
        raise ValueError(f"unknown scan scheme {scan!r}")
    b = int(padded_bins)
    b_hi, g, m, nn, lo_n = hist_geometry(b, _CHANNELS)
    assert f_pad % g == 0, (f_pad, g)
    ngroups = f_pad // g
    if scan == "permute":
        # shared validated hook (power-of-two R precondition lives in
        # exactly one place; the XOR-reversal rounds are only a
        # permutation for pow2 R)
        from .partition_kernel3 import perm_pack_impl
        _pack = perm_pack_impl(R, C)
    else:
        _pack = None
    if fused_kernel_interpret and dynamic:
        raise ValueError(
            "fused_kernel_interpret supports static grids only (the "
            "Pallas interpreter cannot run a traced grid bound)")
    if interpret and not fused_kernel_interpret:
        assert not raw_hist, "the composition has no raw accumulator"
        if interpret_kernel:
            if scan == "permute":
                from .partition_kernel3 import make_partition_perm
                part = make_partition_perm(
                    n, C, R=R, size=size, dtype=dtype, interpret=True,
                    dynamic=dynamic, interpret_kernel=True)
            else:
                from .partition_kernel2 import make_partition_ss
                part = make_partition_ss(
                    n, C, R=R, size=size, dtype=dtype, interpret=True,
                    dynamic=dynamic, interpret_kernel=True)
        else:
            part = make_reference_partition(n, C, dtype=dtype,
                                            dynamic=dynamic)
        # the compiled path sizes its grids dynamically and ignores
        # ``size``; the interpret reference needs the real static bound
        # (build_histogram_comb scans at most ceil(size/rpb)+1 blocks,
        # so size=0 would silently truncate the histograms)
        assert size > 0, "interpret mode needs the static size bound"
        h_size = size

        def _hist_side(rows1, start, count):
            return build_histogram_comb(
                rows1, start, jnp.int32(0), count, f_pad=f_pad,
                size=h_size, padded_bins=b, rows_per_block=hist_rpb,
                interpret=True, planes=comb_planes(C))

        def _fused_i(sel, rows, scratch, *gb):
            rows1, scratch1, nleft = part(sel, rows, scratch, *gb)
            left = sel[SEL_SIDE] == SIDE_LEFT
            h_side = _hist_side(
                rows1, sel[SEL_S0] + jnp.where(left, 0, nleft),
                jnp.where(sel[SEL_SIDE] == SIDE_NONE, 0,
                          jnp.where(left, nleft, sel[SEL_CNT] - nleft)))
            return rows1, scratch1, nleft, h_side

        if dynamic:
            def fused(sel, rows, scratch, grid_blocks):
                return _fused_i(sel, rows, scratch, grid_blocks)
        else:
            def fused(sel, rows, scratch):
                return _fused_i(sel, rows, scratch)
        return fused

    nblocks = max((size + R - 1) // R, 1)
    cb_block = cb_block or copyback_block_rows(C)
    kern = functools.partial(_fused_scan_kernel, R=R, C=C, n=n,
                             f_pad=f_pad, b_hi=b_hi, g=g, lo_n=lo_n,
                             ngroups=ngroups, pack_impl=_pack)

    def _call(sel, rows, scratch, grid_blocks):
        rows1, scratch1, res, hist = pl.pallas_call(
            kern,
            name="lgbm_split_scan",
            grid=(grid_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=_HBM),
                      pl.BlockSpec(memory_space=_HBM)],
            out_specs=[pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=pltpu.SMEM),
                       pl.BlockSpec((ngroups, m, nn),
                                    lambda i: (0, 0, 0),
                                    memory_space=pltpu.VMEM)],
            out_shape=[jax.ShapeDtypeStruct(comb_shape(n, C), dtype),
                       jax.ShapeDtypeStruct(comb_shape(n, C), dtype),
                       jax.ShapeDtypeStruct((2,), jnp.int32),
                       jax.ShapeDtypeStruct((ngroups, m, nn),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((2, R, C), dtype),
                            pltpu.VMEM((2, R, C), dtype),
                            pltpu.SMEM((8,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            input_output_aliases={1: 0, 2: 1},
            interpret=fused_kernel_interpret,
        )(sel, rows, scratch)
        nleft, mm = res[0], res[1]
        rows2 = copyback_call(sel, rows1, scratch1, nleft, mm, R=R,
                              cb_block=cb_block, n=n, C=C, dtype=dtype,
                              interpret=fused_kernel_interpret)
        return rows2, scratch1, nleft, (
            hist if raw_hist else hook_histogram(hist, f_pad, b))

    if dynamic:
        def fused(sel, rows, scratch, grid_blocks):
            return _call(sel, rows, scratch, grid_blocks)
    else:
        def fused(sel, rows, scratch):
            return _call(sel, rows, scratch, nblocks)

    return fused


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import partition_args, register_kernel


@register_kernel("fused_split", kind="fused",
                 note="fused partition+child-histogram scan "
                      "(LGBM_TPU_FUSED default path)")
def _analysis_fused():
    n, C, f, b = 2048 + COMB_ROW_SLACK, 128, 16, 32
    fn = make_fused_split(
        n, C, f_pad=f, padded_bins=b, size=2048,
        R=scan_block_rows(C))
    return fn, partition_args(n, C)


@register_kernel("fused_split_cat", kind="fused",
                 note="fused scan, cat-subset bitset sel (ISSUE 16)")
def _analysis_fused_cat():
    from .layout import CAT_BITSET_WORDS
    n, C, f, b = 2048 + COMB_ROW_SLACK, 128, 16, 32
    fn = make_fused_split(
        n, C, f_pad=f, padded_bins=b, size=2048,
        R=scan_block_rows(C))
    return fn, partition_args(n, C, sel_words=CAT_BITSET_WORDS)
