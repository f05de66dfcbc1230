"""The split selection record every partition scan reads, and the
pure-XLA reference partition every kernel is compared with.

Reference analog: CUDADataPartition::Split (cuda_data_partition.cu:288-907
- go-left bit vector, block prefix sums, SplitInnerKernel scatter).  The
round-1 design kept a ``row_order`` index permutation and GATHERED the
parent's rows on every split; on TPU gathers/scatters are per-INDEX DMA
priced (~13/17 ns per row).  The physical path instead moves the rows
THEMSELVES: the row universe is the comb (ops/pallas/layout.py: bins,
per-row values and the encoded row index as columns), and a split
compacts the parent's contiguous range into left|right.  The kernels
that do it on the chip are partition_kernel2.py (the scan schedule and
the matmul compaction), partition_kernel3.py (the permute compaction)
and fused_split.py (the scan with one child's histogram).  This module
holds what all of them share:

  * the ``sel`` record (``SEL_*``): the i32 split descriptor a scan
    takes in SMEM, with its optional categorical bitset words, and the
    go-left predicate on the extracted split column (``_go_left``,
    ``_member_bit``) that runs inside every scan;
  * ``make_reference_partition``: the same contract in plain XLA - a
    STABLE partition of rows [s0, s0 + cnt) by that predicate.  Every
    off-chip run partitions with it (the Mosaic interpreter is the
    opt-in ``LGBM_TPU_PART_INTERP=kernel``), and the kernel tests
    compare segment membership and counts with it.

Layout contract of the comb a scan moves (built by the caller):
  * plane-major [C // 128 * n, 128] f32 with C a multiple of 128 (DMA
    minor-dim tiling) and n a caller-guaranteed bound such that
    s0 + ceil(cnt/R)*R <= n;
  * column VALUES must be exact under bf16 multiplication by a 0/1
    one-hot where the matmul compaction or the histogram contraction
    reads them: bin ids <= 255 (uint8-bin datasets; wider bins keep the
    index-gather path), row-id bytes split into <= 255-valued columns.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

# the unblocked HBM memory space of manually-DMA'd refs
_HBM = pltpu.HBM

# sel layout (SMEM i32[8]): s0, par_cnt, feat_col, sbin, default_left,
# is_cat, nan_bin (== num_bins-1 if feature has a NaN bin else -1), and
# the child the fused scan's hook histograms (fused_split.py: SIDE_LEFT,
# SIDE_RIGHT, or SIDE_NONE - the hook is skipped; the plain partition
# scans do not read it)
(SEL_S0, SEL_CNT, SEL_FEAT, SEL_SBIN, SEL_DL, SEL_CAT, SEL_NANB,
 SEL_SIDE) = range(8)
SIDE_RIGHT, SIDE_LEFT, SIDE_NONE = 0, 1, -1
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


def make_reference_partition(n: int, C: int, *, dtype=jnp.float32,
                             dynamic: bool = False):
    """Build ``partition(sel, rows, scratch) -> (rows', scratch,
    nleft)`` in plain XLA - or, with ``dynamic=True``,
    ``partition(sel, rows, scratch, nblocks)``, the signature of the
    compiled scans' traced-grid form (``nblocks`` is not read).

    rows is the plane-major comb of ``n`` lines of ``C`` lanes, sel the
    i32[8 (+ W)] split descriptor.  Rows [s0, s0 + cnt) are compacted
    into left|right, each side in its original order; every other row
    stays; scratch comes back untouched.  cnt == 0 is a supported dead
    call (rows untouched, nleft == 0 - used when a tree finishes
    early)."""
    from .layout import LANE, check_lane_width, plane_view
    check_lane_width(C, dtype)

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
