"""Pallas TPU kernel: in-place physical row partition (stable, streaming).

Reference analog: CUDADataPartition::Split (cuda_data_partition.cu:288-907
— go-left bit vector, block prefix sums, SplitInnerKernel scatter).  The
round-1 design kept a ``row_order`` index permutation and GATHERED the
parent's rows on every split; on TPU gathers/scatters are per-INDEX DMA
priced (~13/17 ns per row) which made the partition+gather ~23 ns per
row-visit — two orders of magnitude above streaming bandwidth.  This
kernel instead moves the rows THEMSELVES: the row universe is a
``[n, C]`` matrix (bins, per-row values, encoded row index as columns),
and a split compacts the parent's contiguous range into left|right with
sequential full-block DMAs (bandwidth-bound) and MXU one-hot permutation
matmuls (compaction = a [R, 2R] 0/1 matrix applied to the block).

Layout contract (built by the caller):
  * rows [n, C] f32 with C a multiple of 128 (DMA minor-dim tiling) and n
    a caller-guaranteed bound such that s0 + ceil(cnt/R)*R <= n;
  * column VALUES must be exact under bf16 multiplication by a 0/1
    one-hot: Mosaic runs the compaction matmuls at bf16 operand
    precision, so bin ids must be <= 255 (uint8-bin datasets; uint16
    keeps the index-gather path) and f32 value columns (g*w, h*w) are
    bf16-ROUNDED on every move — benign downstream because the histogram
    kernel multiplies values at bf16 anyway, but callers must not store
    columns whose exactness above bf16 matters (row-id bytes are split
    into <= 255-valued columns for this reason).

Algorithm (one kernel, grid = (3, nblocks), sequential on TPU):
  phase 0 (left):  stream parent blocks; per block compute go-left bits,
      compact the kept rows via a one-hot matmul into a carry window
      (vtail holds <R pending rows so every DMA write is a FULL R rows),
      flush full blocks to scratch at the ascending left cursor.  Each
      full-R write's garbage tail is overwritten by the next write; the
      final left write's garbage lands in the right zone and is
      overwritten by phase 1 (which runs entirely after phase 0).
  phase 1 (right): same for go-right rows, cursor starting at s0+nleft;
      the final write's garbage tail lands beyond s0+par_cnt, harmless
      because phase 2 never reads past the range.
  phase 2 (copyback): stream scratch[s0 : s0+par_cnt] back into rows
      with full-R HBM->HBM DMAs; the tail block is a read-merge-write
      (read rows' own content beyond the range, merge, write full R) so
      neighbouring leaves' rows are preserved.

In-place safety: rows/scratch are HBM aliased in+out refs written ONLY
via manual DMAs (no BlockSpec-managed write-back, so the uninitialised
VMEM write-back hazard that bit apply_find does not apply — verified by
`tools/profile_legacy.py hbm_alias` on-device; the donation side of
the aliasing contract is proven off-chip by the analyzer's hbm-budget
pass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the unblocked HBM memory space of manually-DMA'd refs
_HBM = pltpu.HBM

# sel layout (SMEM i32[8]): s0, par_cnt, feat_col, sbin, default_left,
# is_cat, nan_bin (== num_bins-1 if feature has a NaN bin else -1), and
# the child the fused scan's hook histograms (fused_split.py: > 0 the
# left one, else the right; the plain partition scans do not read it)
(SEL_S0, SEL_CNT, SEL_FEAT, SEL_SBIN, SEL_DL, SEL_CAT, SEL_NANB,
 SEL_SIDE) = range(8)
# bitset extension (ISSUE 16): a caller may append ceil(padded_bins/32)
# i32 membership words after the 8 descriptor slots — sel becomes
# i32[8 + W] and a categorical split's go-left bit is bit (bin % 32) of
# word (bin // 32), the same bin-indexed encoding ops/predict.py packs
# for serving.  Kernels detect the mode from sel's static shape, so the
# 8-slot program is bit-identical to the pre-bitset build.
SEL_MEMBER = 8


def _member_bit(v, words, read_word):
    """Bitset membership test for i32 bin ids ``v``.

    ``read_word(k)`` returns membership word k (scalar i32, broadcast
    against v).  The word select is an unrolled static chain — W is a
    handful of words (8 at the 256-bin budget) and scalar-SMEM gather is
    not a Mosaic vector op.  Arithmetic shift + mask extracts bit
    (v % 32) exactly for any i32 word including bit 31 set."""
    word = jnp.zeros_like(v)
    for k in range(words):
        word = jnp.where((v >> 5) == k, read_word(k), word)
    return ((word >> (v & 31)) & 1) > 0


def _go_left(col, sel_ref):
    """Go-left predicate on the extracted split column (f32 [R, 1]).

    Mirrors ops/grow.py's bucket predicate: categorical membership
    (bitset words when sel carries them, else one-hot col == sbin),
    numerical (col <= sbin) with NaN-bin rows routed by default_left."""
    sbin = sel_ref[SEL_SBIN].astype(jnp.float32)
    nanb = sel_ref[SEL_NANB]
    at_nan = (nanb >= 0) & (col == nanb.astype(jnp.float32))
    num_left = ((col <= sbin) & ~at_nan) | (at_nan & (sel_ref[SEL_DL] > 0))
    if sel_ref.shape[0] > SEL_MEMBER:
        # bitset mode covers one-hot uniformly (the builder packs the
        # single winning bin); words are zeroed for numerical splits
        cat_left = _member_bit(
            col.astype(jnp.int32), sel_ref.shape[0] - SEL_MEMBER,
            lambda k: sel_ref[SEL_MEMBER + k])
    else:
        cat_left = col == sbin
    # and/or instead of a bool select (i1-vector arith.select doesn't
    # legalize in Mosaic)
    is_cat = sel_ref[SEL_CAT] > 0
    return (cat_left & is_cat) | (num_left & ~is_cat)


def _partition_kernel(sel_ref, rows_in, scratch_in,
                      rows_ref, scratch_ref, nsplit_ref,
                      vx, vtail, cursor, sem,
                      *, R: int, C: int):
    """One grid step of the 3-phase partition.

    cursor (SMEM i32[4]): [0] current phase's write cursor, [1] nleft
    (set at phase-0 end), [2] pending row count in vtail.
    """
    phase = pl.program_id(0)
    blk = pl.program_id(1)
    s0 = sel_ref[SEL_S0]
    cnt = sel_ref[SEL_CNT]
    nb_live = (cnt + R - 1) // R

    @pl.when((phase == 0) & (blk == 0))
    def _init0():
        cursor[0] = s0
        cursor[1] = 0
        cursor[2] = 0
        # nsplit is SMEM output (not zero-initialised): when par_cnt == 0
        # nb_live == 0 so the phase-1 flush below never runs — write the
        # answer here so a dead call returns 0, not garbage.
        nsplit_ref[0] = 0

    # ---- phases 0/1: stream + compact + full-R flushes ----
    # All intermediates are LANE-oriented ([1, R] vectors, [2R, R] one-hot
    # with the contraction dim on lanes/sublanes in natural MXU layout) —
    # a first sublane-oriented version forced Mosaic relayouts/transposes
    # that cost ~19 us per block, 10x the math itself.
    @pl.when((phase < 2) & (blk < nb_live))
    def _scan():
        start = s0 + blk * R
        cp = pltpu.make_async_copy(rows_in.at[pl.ds(start, R)], vx, sem)
        cp.start()
        cp.wait()
        x = vx[:]
        # split-column extraction, transposed: one-hot [1, C] against
        # rows' lanes -> col values along LANES [1, R] (A.B^T matmul;
        # exact — single nonzero product per output)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        e_col = (lane == sel_ref[SEL_FEAT]).astype(jnp.float32)
        col = jax.lax.dot_general(
            e_col, x.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [1, R]
        pos_r = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        valid = pos_r < (cnt - blk * R)
        keep = _go_left(col, sel_ref)
        # phase 1 keeps the complement; i1-vector select doesn't legalize
        # in Mosaic, xor does
        keep = jnp.logical_xor(keep, phase > 0) & valid
        kf = keep.astype(jnp.float32)                    # [1, R]
        # stable intra-block positions: exclusive prefix sum of the keep
        # bits along lanes via a strict-upper-tril matmul (0/1 bf16
        # products exact, f32 accumulation)
        r_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
        striu = (r_i < c_i).astype(jnp.bfloat16)
        pos = jax.lax.dot_general(
            kf.astype(jnp.bfloat16), striu,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [1, R]
        nk = jnp.sum(kf).astype(jnp.int32)
        t = cursor[2]
        dst = jnp.where(keep, pos.astype(jnp.int32) + t, -1)   # [1, R]
        # one-hot compaction into the [2R] tail+block window:
        # PT[j, r] = (row r lands in slot j); then PT @ x compacts
        slot = jax.lax.broadcasted_iota(jnp.int32, (2 * R, 1), 0)
        PT = (slot == dst).astype(x.dtype)               # [2R, R]
        packed = jax.lax.dot_general(
            PT, x, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [2R, C]
        rid2 = jax.lax.broadcasted_iota(jnp.int32, (2 * R, C), 0)
        old_tail = jnp.concatenate(
            [vtail[:], jnp.zeros_like(vtail)], axis=0).astype(jnp.float32)
        win = jnp.where(rid2 < t, old_tail, packed)      # [2R, C] f32
        total = t + nk

        @pl.when(total >= R)
        def _emit():
            vtail[:] = win[:R].astype(x.dtype)
            cpo = pltpu.make_async_copy(
                vtail, scratch_ref.at[pl.ds(cursor[0], R)], sem)
            cpo.start()
            cpo.wait()
            cursor[0] = cursor[0] + R

        vtail[:] = jnp.where(total >= R, win[R:], win[:R]).astype(x.dtype)
        cursor[2] = jnp.where(total >= R, total - R, total)

    # ---- phase end: flush the pending tail as a full-R write ----
    @pl.when((phase < 2) & (blk == nb_live - 1))
    def _flush():
        t = cursor[2]

        @pl.when(t > 0)
        def _go():
            # phase 0: garbage tail lands in the right zone, overwritten
            # by phase 1.  phase 1: garbage lands beyond the range,
            # never read back.
            cpo = pltpu.make_async_copy(
                vtail, scratch_ref.at[pl.ds(cursor[0], R)], sem)
            cpo.start()
            cpo.wait()

        @pl.when(phase == 0)
        def _fin0():
            cursor[1] = cursor[0] - s0 + t
            cursor[0] = s0 + cursor[1]
            cursor[2] = 0

        @pl.when(phase == 1)
        def _fin1():
            nsplit_ref[0] = cursor[1]

    # ---- phase 2: copy the partitioned range back into rows ----
    @pl.when((phase == 2) & (blk < nb_live))
    def _copyback():
        start = s0 + blk * R
        last = blk == nb_live - 1

        @pl.when(jnp.logical_not(last))
        def _full():
            cp = pltpu.make_async_copy(
                scratch_in.at[pl.ds(start, R)],
                rows_ref.at[pl.ds(start, R)], sem)
            cp.start()
            cp.wait()

        @pl.when(last)
        def _tail():
            cp = pltpu.make_async_copy(
                scratch_in.at[pl.ds(start, R)], vx, sem)
            cp.start()
            cp.wait()
            cpi = pltpu.make_async_copy(
                rows_in.at[pl.ds(start, R)], vtail, sem)
            cpi.start()
            cpi.wait()
            rid = jax.lax.broadcasted_iota(jnp.int32, (R, C), 0)
            live = rid < (cnt - blk * R)
            vx[:] = jnp.where(live, vx[:], vtail[:])
            cpo = pltpu.make_async_copy(
                vx, rows_ref.at[pl.ds(start, R)], sem)
            cpo.start()
            cpo.wait()


def make_partition(n: int, C: int, *, R: int = 1024, size: int = 0,
                   dtype=jnp.float32, interpret: bool = False,
                   dynamic: bool = False):
    """Build ``partition(sel, rows, scratch) -> (rows', scratch',
    nleft)`` — or, with ``dynamic=True``, ``partition(sel, rows,
    scratch, nblocks)`` where ``nblocks`` is a TRACED grid bound
    (Mosaic dynamic grid; must equal max(ceil(par_cnt / R), 1)).

    The dynamic form exists to kill the per-split ``lax.switch`` over
    static bucket sizes: XLA cannot alias a pallas in-place output
    through a conditional and inserts a FULL copy of the row matrix per
    branch per split (measured 5.4 GB/split at 10.5M rows).  One
    dynamically-bounded kernel needs no conditional at all.

    ``size`` (static form) is the bucket class (max parent rows); the
    grid covers ceil(size / R) blocks.  rows/scratch are [n, C] HBM
    buffers aliased in/out (scratch content is don't-care between
    calls); sel is the i32[8] split descriptor.  Caller guarantees
    0 <= par_cnt <= size and s0 + ceil(par_cnt/R)*R <= n; par_cnt == 0
    is a supported dead call (rows untouched, nleft == 0 — used when a
    tree finishes early)."""
    from .layout import (LANE, check_lane_width, comb_planes,
                         plane_view)
    check_lane_width(C, dtype)
    nblocks = max((size + R - 1) // R, 1)
    kern = functools.partial(_partition_kernel, R=R, C=C)

    if interpret:
        # Pure-XLA reference implementation (CPU tests / off-TPU): the
        # Mosaic interpreter does not reproduce the aliased-manual-DMA
        # semantics (unwritten regions of the aliased outputs come back
        # zeroed), so emulate the kernel's contract directly.
        def partition(sel, rows, scratch):
            # rows is the plane-major comb (layout.py): work on its
            # [planes, n, 128] view
            rows3 = plane_view(rows, C)
            s0, cnt = sel[0], sel[1]
            pos = jnp.arange(n, dtype=jnp.int32)
            in_rng = (pos >= s0) & (pos < s0 + cnt)
            col = jnp.take(
                jnp.take(rows3, sel[SEL_FEAT] // LANE, axis=0),
                sel[SEL_FEAT] % LANE, axis=1).astype(jnp.float32)
            sbin = sel[SEL_SBIN].astype(jnp.float32)
            nanb = sel[SEL_NANB]
            at_nan = (nanb >= 0) & (col == nanb.astype(jnp.float32))
            num_left = (((col <= sbin) & ~at_nan)
                        | (at_nan & (sel[SEL_DL] > 0)))
            if sel.shape[0] > SEL_MEMBER:
                ci = col.astype(jnp.int32)
                word = jnp.take(sel[SEL_MEMBER:], ci >> 5)
                cat_go = ((word >> (ci & 31)) & 1) > 0
            else:
                cat_go = col == sbin
            glb = jnp.where(sel[SEL_CAT] > 0, cat_go, num_left)
            gl = in_rng & glb
            gr = in_rng & ~glb
            nleft = jnp.sum(gl.astype(jnp.int32))
            dst = jnp.where(
                gl, s0 + jnp.cumsum(gl.astype(jnp.int32)) - 1,
                jnp.where(gr,
                          s0 + nleft + jnp.cumsum(gr.astype(jnp.int32))
                          - 1, pos))
            rows_new = jnp.zeros_like(rows3).at[:, dst].set(rows3)
            return rows_new.reshape(rows.shape), scratch, nleft

        if dynamic:
            return lambda sel, rows, scratch, grid_blocks: partition(
                sel, rows, scratch)
        return partition

    if comb_planes(C) > 1:
        raise ValueError(
            f"the 3-phase bisection kernel (LGBM_TPU_PART=3ph) moves "
            f"[R, {C}] blocks of a row-major comb and was not ported "
            f"to the plane-major layout of lines wider than {LANE} "
            f"lanes; unset LGBM_TPU_PART")

    def _call(sel, rows, scratch, grid_blocks):
        rows_out, scratch_out, nsplit = pl.pallas_call(
            kern,
            grid=(3, grid_blocks),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=_HBM),
                      pl.BlockSpec(memory_space=_HBM)],
            out_specs=[pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[jax.ShapeDtypeStruct((n, C), dtype),
                       jax.ShapeDtypeStruct((n, C), dtype),
                       jax.ShapeDtypeStruct((1,), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((R, C), dtype),
                            pltpu.VMEM((R, C), dtype),
                            pltpu.SMEM((4,), jnp.int32),
                            pltpu.SemaphoreType.DMA],
            input_output_aliases={1: 0, 2: 1},
            interpret=interpret,
        )(sel, rows, scratch)
        return rows_out, scratch_out, nsplit[0]

    if dynamic:
        def partition(sel, rows, scratch, grid_blocks):
            return _call(sel, rows, scratch, grid_blocks)
    else:
        def partition(sel, rows, scratch):
            return _call(sel, rows, scratch, nblocks)

    return partition


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import partition_args, register_kernel


@register_kernel("partition_3ph", kind="partition",
                 note="3-phase bisection kernel (LGBM_TPU_PART=3ph)")
def _analysis_partition_3ph():
    n, C = 7168, 128
    return (make_partition(n, C, R=512, size=2048),
            partition_args(n, C))


@register_kernel("partition_3ph_cat", kind="partition",
                 note="3-phase kernel, cat-subset bitset sel (ISSUE 16)")
def _analysis_partition_3ph_cat():
    from .layout import CAT_BITSET_WORDS
    n, C = 7168, 128
    return (make_partition(n, C, R=512, size=2048),
            partition_args(n, C, sel_words=CAT_BITSET_WORDS))
