"""Physical comb-matrix layout contract shared by every Pallas kernel.

Single source of truth for the lane-granularity rules that the round-3
snapshot regression (BENCH_r03.json) violated: the end-of-round commit
stored comb rows at 64-lane granularity, but Mosaic tiles f32 HBM
memrefs (1, 128) — a [n, 64] array is physically lane-padded to 128, so
every dynamic row DMA in the partition kernel became a 64-wide slice of
a 128-wide memref and the chip failed to compile ("Slice shape along
dimension 1 must be aligned to tiling (128), but is 64";
docs/PERF_NOTES.md lever #4 post-mortem).  The CPU suite could not see
it because the 64-lane branch was TPU-only.  Every kernel builder that
DMA-slices comb rows now validates its width HERE, and
tests/test_partition_perm.py::TestLaneContract pins the rule off-chip.

Also the one place that says how a logical row lies in the comb
(``comb_layout`` gives the line width; ops/grow.py, obs/costmodel.py
and every kernel builder take it from here): ONE logical row a line of
C lanes, C the column count rounded up to a multiple of 128.  In HBM
the comb is stored PLANE-MAJOR (ISSUE 29): plane p holds lanes [128 p,
128 p + 128) of every row as an [n, 128] matrix, and the C // 128
planes lie one after the other in ONE [C // 128 * n, 128] array
(``to_planes`` / ``to_rows``).  A [n, 256] f32 array is tiled (8, 128)
in HBM, so a row DMA at an arbitrary row offset - every segment start
of the partition scan - is refused by Mosaic ("tile index in dimension
0 is divisible by the tiling (8)"); a [n, 128] matrix is linear in
memory and takes any row offset.  With one plane (C = 128) the
plane-major array IS the [n, 128] row matrix.  In VMEM a block stays
[R, C]: the kernels move it as C // 128 row DMAs, one a plane, into and
out of its 128-lane column tiles (``plane_copies``), or take it through
a [C // 128, R, 128] block of the free 3-D view (``comb_block_spec`` /
``load_rows`` / ``store_rows``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE = 128          # TPU minor-dim tile: every HBM row DMA moves
                    # multiples of this many lanes

# Physical comb width budget, and the width every comb kernel is
# built to stage.  The comb-direct kernels stream [R, C] blocks through
# VMEM, so C is bounded by the staging budget, not the lane contract.
# Every kernel takes its block from a price of its width that is fitted
# to the compiler's own report and fits the default scoped VMEM at 16
# planes: the scan 128 rows (SCAN_ROWS_WIDE), the copy-back 512, the
# init / refresh 128, and the histogram sweeps one tile a plane
# (hist_kernel2.hist_tiles).  A wider layout - a dense table of more
# than ~2,030 columns, or an EFB dataset whose bundles unbundle past
# the budget - falls back to the row_order path by the routing model's
# ``comb_overwide`` / ``efb_overwide`` rules instead of dying in
# Mosaic's VMEM allocator on chip.
MAX_COMB_COLS = 16 * LANE

# Rows a grid step of the single-scan partition moves (ISSUE 37).  The
# scan takes the largest power of two in [SCAN_ROWS_MIN, SCAN_ROWS_MAX]
# whose buffers fit its scoped VMEM (partition_kernel2.scan_block_rows:
# it rides on the comb's width and the histogram hook's accumulator,
# both known only once the device layout is final), so what has to be
# sized BEFORE the layout exists is sized for the largest block: rows
# pad to a whole number of SCAN_ROWS_MAX blocks (a shard's rows, under
# the mesh learners; grow.PHYS_ROW_PAD), and every smaller power of two
# divides them.  The upper end is the permute
# compaction's: both sides' log2(R)-bit routing words share one 23-bit
# word (partition_kernel3._BIAS).
SCAN_ROWS_MIN = 512
# ... and on a comb past seven planes, where 512 rows do not fit the
# scoped VMEM, down to this (partition_kernel2.scan_block_rows)
SCAN_ROWS_WIDE = 128
SCAN_ROWS_MAX = 2048
COPYBACK_ROWS = 2048    # rows a step of the copy-back moves (pure DMA)
HIST_COMB_ROWS = 2048   # rows a step of the comb-direct histogram reads

# The scoped VMEM of a kernel that asks Mosaic for nothing, as every
# comb kernel does (the v5e has 128 MiB): what a kernel's stack - its
# pipelined blocks, scratch shapes, run_scoped buffers, the compiler's
# own temporaries - has to fit.  Each kernel's price of a block is
# fitted to the compiler's own report (partition_kernel2
# ``scan_vmem_bytes`` / ``copyback_vmem_bytes``, stream_grad
# ``stream_vmem_bytes``, hist_kernel2 ``hist_vmem_bytes``).
SCOPED_VMEM_LIMIT = 16 * 1024 * 1024


def fit_rows(price, top: int, floor: int,
             limit: int = SCOPED_VMEM_LIMIT) -> int:
    """The largest power of two in [``floor``, ``top``] rows a grid
    step whose ``price(rows)`` fits ``limit``; ``floor`` where none
    does (the caller's price says so: ``routing.comb_stageable``)."""
    rows = top
    while rows > floor and price(rows) > limit:
        rows //= 2
    return rows

# Lines the comb and its scratch carry past the padded rows, ``n_alloc
# - n_pad``, callers gating on the 2^24 row-id limit must subtract this
# (gbdt's use_phys decision).  Every kernel that runs past the last
# row's block does so in ONE of the two arrays, so the slack is the
# largest of the tails, not their sum (ROADMAP C12):
#   * the SCRATCH: the scan's right zone grows down from T = s0 +
#     (ceil(cnt / R) + 1) * R < s0 + cnt + 2 R (partition_kernel2's
#     docstring: the + R headroom keeps every full-R write >= s0), and
#     the copy-back reads the span [T - m, T) in whole COPYBACK_ROWS
#     blocks, so its tail block reads up to T + COPYBACK_ROWS - 1:
#     2 R + COPYBACK_ROWS lines past s0 + cnt <= n_pad;
#   * the COMB: the scan reads and writes whole R-row blocks of [s0,
#     s0 + cnt): under R lines past; the copy-back's tail block
#     read-merges COPYBACK_ROWS lines from its start inside the span:
#     under COPYBACK_ROWS; the comb-direct histogram reads
#     ceil(count / HIST_COMB_ROWS) + 1 whole blocks from the block its
#     range starts in, under 2 HIST_COMB_ROWS past (and clamps its
#     window to the array's whole blocks: hist_kernel2._comb_hist_call).
# At the largest R the scratch's tail is the longest: 6,144 lines.  A
# constant, so n_alloc - every compiled program's shape - does not
# depend on which R the scan took.
COMB_ROW_SLACK = max(2 * SCAN_ROWS_MAX + COPYBACK_ROWS,
                     2 * HIST_COMB_ROWS)

# Categorical bitset budget (ISSUE 16, the cat-subset graduation).  A
# sorted-subset categorical split ships its membership as ceil(B/32)
# i32 words appended to the 8-slot SMEM split descriptor (sel becomes
# i32[8 + W]; partition_kernel.SEL_MEMBER).  The in-kernel word select
# is an unrolled static chain over W scalar SMEM reads per row block,
# so W is budgeted, not unbounded: 8 words covers every u8-bin dataset
# (padded_bins <= 256) at ~zero SMEM/decode cost, and anything wider
# (u16 bins would need 2048 words) must fall back to the row_order
# path via the routing model's ``cat_overwide`` rule instead of
# compiling a 2048-branch select chain.
CAT_BITSET_WORDS = 8


# Serving-forest VMEM residency budget (ISSUE 18, the VMEM-resident
# traversal kernel).  The serve kernel DMAs the ENTIRE stacked forest
# — five [T, ni_pad] i32 node arrays (split_feature, threshold_bin,
# left/right pointers, packed node-meta word), the flat cat bitset
# words + per-node bit counts when the forest has categorical splits,
# and the [T, nl_pad] leaf table — into VMEM scratch once per
# dispatch, then keeps it resident across every traversal level.  The
# cap bounds that resident slice to a small fraction of the usable
# VMEM budget (obs/costmodel.vmem_limit_bytes, 96 MiB on v5e) so the
# double-buffered row tiles always have room to pipeline: 4 MiB
# covers the "~2 MB-class" small production forests the round-17
# headroom list targeted (255 leaves x 500 trees ~ 2.5 MiB of padded
# i32 fields) with slack for the leaf table, and anything wider must
# fall back to the XLA gather walk via the routing model's
# ``serve_forest_overwide`` rule instead of dying in Mosaic's VMEM
# allocator on chip.
SERVE_FOREST_VMEM_CAP = 4 << 20


def serve_forest_vmem_bytes(trees: int, ni_pad: int, nl_pad: int, *,
                            cat_words_w: int = 0,
                            leaf_itemsize: int = 4) -> int:
    """Resident VMEM bytes of one stacked forest under the serve
    kernel's layout: the node arrays it DMAs once per dispatch.  The
    SAME accounting backs :func:`serve_forest_fit` (the engagement
    predicate), ``obs/costmodel.serving_kernel_bytes`` (the priced
    HBM contract — the forest moves HBM->VMEM exactly once) and the
    analyzer's registered ``serve_traverse`` scratch shapes, so the
    matrix, the cost model and the runtime can never disagree about
    which forests fit."""
    t, ni, nl = int(trees), int(ni_pad), int(nl_pad)
    w = int(cat_words_w)
    # sf, tb, lc, rc, node_meta: five i32 node words per padded node
    out = t * ni * 5 * 4
    if w > 0:
        out += t * ni * w * 4     # flat cat bitset words
        out += t * ni * 4        # cat_nbits
    out += t * nl * int(leaf_itemsize)
    return out


def serve_forest_fit(trees: int, ni_pad: int, nl_pad: int, *,
                     cat_words_w: int = 0,
                     leaf_itemsize: int = 4) -> bool:
    """Whether a stacked forest fits the serve kernel's VMEM residency
    cap — the shape fact behind the ``serve_forest_overwide`` routing
    rule (ops/routing.py), shared with ``serve/engine.py``'s dispatch
    choice so the matrix and the runtime can never disagree about
    which forests traverse VMEM-resident.  Expects the PADDED
    geometry (``ni_pad`` / ``nl_pad`` are 128-lane multiples since
    the ISSUE-18 restack; ``serve/model.py`` is the one producer)."""
    if trees <= 0 or ni_pad <= 0 or nl_pad <= 0:
        return False
    if ni_pad % LANE or nl_pad % LANE:
        return False
    return serve_forest_vmem_bytes(
        trees, ni_pad, nl_pad, cat_words_w=cat_words_w,
        leaf_itemsize=leaf_itemsize) <= SERVE_FOREST_VMEM_CAP


def cat_bitset_fit(padded_bins: int) -> bool:
    """Whether a categorical membership bitset over ``padded_bins``
    bins fits the sel-word budget — the shape fact behind the
    ``cat_overwide`` routing rule (ops/routing.py), shared with the
    grow-build defense in ops/grow.py so the matrix and the runtime
    can never disagree about which bin widths fit."""
    return 0 < int(padded_bins) <= 32 * CAT_BITSET_WORDS


def comb_cols_fit(n_cols: int) -> bool:
    """Whether ``n_cols`` logical comb columns (features + value/rid/
    stream extras) fit the lane/VMEM column budget — the shape fact
    behind the ``comb_overwide`` and ``efb_overwide`` routing rules
    (ops/routing.py), shared with the grow-build defense in ops/grow.py
    so the matrix and the runtime can never disagree about which
    layouts fit."""
    return 0 < int(n_cols) <= MAX_COMB_COLS


def check_lane_width(C: int, dtype=jnp.float32) -> int:
    """Validate a kernel's comb line width against the DMA tiling
    contract; returns C.  Raises ValueError for the BENCH_r03 class of
    regression (any width that is not a multiple of the 128-lane tile
    — Mosaic would lane-pad the memref and every dynamic row slice
    would fail the "aligned to tiling (128)" check on-chip).
    ``dtype`` is accepted so stricter per-dtype rules (e.g. bf16's
    (8,128)x2 sublane tiling, should Mosaic ever admit dynamic row
    offsets there) can slot in without touching the call sites."""
    if C <= 0 or C % LANE != 0:
        raise ValueError(
            f"comb line width {C} violates the {LANE}-lane DMA tiling "
            f"contract (Mosaic lane-pads the memref and dynamic row "
            f"slices fail 'aligned to tiling ({LANE})' at compile "
            f"time — the BENCH_r03 regression); pad the column count "
            f"to a multiple of {LANE}")
    return C


def comb_planes(C: int) -> int:
    """128-lane planes of a comb line of ``C`` lanes."""
    return check_lane_width(C) // LANE


def comb_shape(n: int, C: int):
    """HBM shape of the plane-major comb of ``n`` lines of ``C`` lanes."""
    return comb_planes(C) * int(n), LANE


def to_planes(x):
    """[n, C] rows -> the plane-major [C // 128 * n, 128] comb (the
    identity at C = 128; a transposing copy above it, so a program
    that runs at a real size builds its planes directly)."""
    n, C = x.shape
    P = comb_planes(C)
    if P == 1:
        return x
    return x.reshape(n, P, LANE).swapaxes(0, 1).reshape(P * n, LANE)


def to_rows(y, C: int):
    """The inverse of :func:`to_planes`: the [n, C] row view the
    off-chip reference paths slice."""
    P = comb_planes(C)
    if P == 1:
        return y
    n = y.shape[0] // P
    return y.reshape(P, n, LANE).swapaxes(0, 1).reshape(n, C)


def plane_view(y, C: int):
    """The plane-major comb as [C // 128, n, 128]: a split of the major
    dimension, free in XLA."""
    P = comb_planes(C)
    return y.reshape(P, y.shape[0] // P, LANE)


def set_cols(y, vals, col0: int, C: int):
    """The plane-major comb with logical columns [col0, col0 + k) of
    every row replaced by ``vals`` [n, k]: one update a plane touched."""
    n = y.shape[0] // comb_planes(C)
    k, done = vals.shape[1], 0
    while done < k:
        p, lane = divmod(col0 + done, LANE)
        w = min(k - done, LANE - lane)
        y = jax.lax.dynamic_update_slice(
            y, vals[:, done:done + w].astype(y.dtype),
            (jnp.int32(p * n), jnp.int32(lane)))
        done += w
    return y


def plane_copies(hbm_ref, start, rows: int, vmem_ref, sem, *, n: int,
                 C: int, to_hbm: bool = False):
    """The row DMAs that move comb rows [start, start + rows) between
    the plane-major HBM array ``hbm_ref`` (``n`` rows a plane) and the
    [rows, C] VMEM block ``vmem_ref``: one descriptor a plane, all on
    ``sem``.  Start every one, wait every one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    P = comb_planes(C)
    out = []
    for p in range(P):
        h = hbm_ref.at[pl.ds(p * n + start, rows)]
        v = vmem_ref if P == 1 else vmem_ref.at[:, pl.ds(p * LANE, LANE)]
        out.append(pltpu.make_async_copy(v, h, sem) if to_hbm
                   else pltpu.make_async_copy(h, v, sem))
    return out


def hbm_copies(src_ref, src_start, dst_ref, dst_start, rows: int, sem, *,
               n: int, C: int):
    """HBM -> HBM: rows [src_start, + rows) of one plane-major comb to
    rows [dst_start, + rows) of another, one descriptor a plane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.make_async_copy(
        src_ref.at[pl.ds(p * n + src_start, rows)],
        dst_ref.at[pl.ds(p * n + dst_start, rows)], sem)
        for p in range(comb_planes(C))]


def comb_block_spec(rows: int, C: int, index, **kw):
    """BlockSpec of a [rows, C] block of comb rows for a kernel that
    takes the comb through :func:`comb_operand`; ``index(*grid ids)``
    gives the row-block index."""
    from jax.experimental import pallas as pl
    if comb_planes(C) == 1:
        return pl.BlockSpec((rows, C), lambda *a: (index(*a), 0), **kw)
    return pl.BlockSpec((comb_planes(C), rows, LANE),
                        lambda *a: (0, index(*a), 0), **kw)


def comb_operand(y, C: int):
    """The comb as a BlockSpec kernel takes it (and hands it back):
    itself with one plane, the 3-D plane view above."""
    return y if comb_planes(C) == 1 else plane_view(y, C)


def comb_operand_shape(n: int, C: int):
    """Shape of :func:`comb_operand` for ``n`` lines of ``C`` lanes."""
    P = comb_planes(C)
    return (int(n), C) if P == 1 else (P, int(n), LANE)


def load_rows(ref):
    """A :func:`comb_block_spec` block as its [rows, C] value (placing
    128-lane tiles side by side moves no data)."""
    if len(ref.shape) == 2:
        return ref[...]
    return jnp.concatenate([ref[p] for p in range(ref.shape[0])], axis=1)


def store_rows(ref, x):
    """Write a [rows, C] value back into a comb block."""
    if len(ref.shape) == 2:
        ref[...] = x
        return
    for p in range(ref.shape[0]):
        ref[p] = x[:, p * LANE:(p + 1) * LANE]


def comb_layout(n_cols: int, dtype=jnp.float32) -> int:
    """Line width C of a comb with ``n_cols`` logical columns: the
    column count rounded up to whole 128-lane planes."""
    C = LANE * ((max(int(n_cols), 1) + LANE - 1) // LANE)
    return check_lane_width(C, dtype)
