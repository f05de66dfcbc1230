"""Pallas TPU histogram kernel, v2 — matmul-expanded one-hots.

The TPU re-design of the reference's hottest kernel
(``CUDAConstructHistogramDenseKernel``,
src/treelearner/cuda/cuda_histogram_constructor.cu:18-68; CUDA uses
shared-memory atomicAdd per (feature, bin)).  TPUs have no scatter-atomics,
so the histogram is a split-bin one-hot contraction on the MXU
(see ops/histogram.py for the math; here ``bin = hi * lo_n + lo`` with the
split ``hist_geometry`` takes from the bin count, so a group of columns
fills the M axis at every bin count up to 128).  v2 fixes the two things
that made both the pure-XLA formulation and the v1 kernel
bandwidth/VPU-bound:

1. **One-hot construction via constant matmuls.**  Expanding ``hi[r, g]`` to
   its ``b_hi``-lane span (and ``lo``/values to their ``lo_n * C``-lane
   spans) with
   reshape/concat causes TPU relayouts — sublane shuffles that dominated v1.
   Instead the lane-broadcast is itself a matmul with a tiny constant 0/1
   matrix (``[G, M]`` / ``[C, N]``), so the MXU does the replication and the
   VPU only does two compares and a select per element.

2. **No per-block diagonal extraction.**  The kernel accumulates the raw
   ``[M, N]`` group products in VMEM across all row blocks; the diagonal
   (same-feature) blocks are sliced out ONCE at the end by XLA on a
   [ngroups, M, N] array — O(F*B) instead of O(F*B) *per block*.

Matmuls run in bf16 (one-hots are exact in bf16; values round to bf16 —
the same value precision the XLA path gets from the TPU's default matmul
precision).  Accumulation is f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..histogram import feature_group_size
from .layout import HIST_COMB_ROWS, load_rows


def hist_geometry(b: int, channels: int = 2):
    """(b_hi, g, m, nn, lo_n) of the [ngroups, M, N] one-hot
    accumulator layout for padded_bins ``b`` (a multiple of 16) — the
    single source of truth for every kernel that embeds this
    accumulation (hist_kernel2 itself, fused_split's scan hook,
    stream_grad's fused refresh+root pass).

    A bin splits as ``bin = hi * lo_n + lo``.  Up to 128 bins
    ``lo_n = b / 8``, so ``b_hi = 8`` and the group's ``g = 16`` columns
    fill the M axis (``g * b_hi == 128``, one whole MXU pass); past 128
    ``lo_n = 16``.  ``M x N = g^2 x b x channels`` whatever the split:
    only the place of an entry in the product changes."""
    lo_n = b // 8 if b <= 128 else 16
    b_hi = b // lo_n
    g = feature_group_size(b)
    return b_hi, g, g * b_hi, g * lo_n * channels, lo_n


def onehot_consts(b_hi, g, c, lo_n):
    """(e_hi, e_lo, e_v, lane_hi, lane_lo) — the constant 0/1 broadcast
    matrices and lane indices of the nibble one-hot contraction.  Built
    from iotas so kernels capture no array constants (pallas
    requirement); Mosaic hoists them out of the grid loop.  Single
    source of truth: the fused/unfused bit-identity contract depends on
    every kernel embedding this accumulation (_hist_accumulate's
    callers here, in fused_split and in stream_grad) using
    byte-identical constants."""
    m = g * b_hi
    n_cols = g * lo_n * c
    col_m = jax.lax.broadcasted_iota(jnp.int32, (g, m), 1)
    row_g = jax.lax.broadcasted_iota(jnp.int32, (g, m), 0)
    e_hi = (col_m // b_hi == row_g).astype(jnp.float32)       # [G, M]
    col_n = jax.lax.broadcasted_iota(jnp.int32, (g, n_cols), 1)
    row_gn = jax.lax.broadcasted_iota(jnp.int32, (g, n_cols), 0)
    e_lo = (col_n // (lo_n * c) == row_gn).astype(jnp.float32)  # [G, N]
    col_c = jax.lax.broadcasted_iota(jnp.int32, (c, n_cols), 1)
    row_c = jax.lax.broadcasted_iota(jnp.int32, (c, n_cols), 0)
    e_v = ((col_c // lo_n) % c == row_c).astype(jnp.float32)    # [C, N]
    lane_hi = (jax.lax.broadcasted_iota(jnp.int32, (1, m), 1) % b_hi
               ).astype(jnp.float32)
    lane_lo = (jax.lax.broadcasted_iota(jnp.int32, (1, n_cols), 1) % lo_n
               ).astype(jnp.float32)
    return e_hi, e_lo, e_v, lane_hi, lane_lo


def _hist_accumulate(b, v, out_ref, *, b_hi, g, c, lo_n, ngroups):
    """Shared accumulation body: one-hot (hi, lo) contraction of a
    block's bins [R, F] (i32) and values [R, C] (f32) into out_ref
    [ngroups, M, N], at the split ``hist_geometry`` gives."""
    e_hi, e_lo, e_v, lane_hi, lane_lo = onehot_consts(b_hi, g, c, lo_n)

    hi = b // lo_n
    lo = b - hi * lo_n

    # channel expansion shared by all groups: [R, N] f32
    v_tile = jax.lax.dot_general(
        v, e_v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    for grp in range(ngroups):
        f0 = grp * g
        hi_g = hi[:, f0:f0 + g].astype(jnp.float32)   # [R, G]
        lo_g = lo[:, f0:f0 + g].astype(jnp.float32)
        # lane broadcasts via constant matmuls (MXU, no relayout)
        hi_rep = jax.lax.dot_general(
            hi_g, e_hi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, M]
        lo_rep = jax.lax.dot_general(
            lo_g, e_lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [R, N]
        oh_hi = (hi_rep == lane_hi).astype(jnp.bfloat16)
        lo_v = jnp.where(lo_rep == lane_lo, v_tile, 0.0
                         ).astype(jnp.bfloat16)
        prod = jax.lax.dot_general(
            oh_hi, lo_v, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [M, N]
        out_ref[grp] += prod


def _hist2_kernel(bins_ref, vals_ref, out_ref, *, b_hi, g, c, lo_n, ngroups):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    _hist_accumulate(bins_ref[:].astype(jnp.int32), vals_ref[:], out_ref,
                     b_hi=b_hi, g=g, c=c, lo_n=lo_n, ngroups=ngroups)


def _hist2_comb_kernel(sel_ref, comb_ref, out_ref, *, b_hi, g, c, lo_n,
                       ngroups, f_pad, rpb):
    """Comb-direct variant: the block arrives as a [R, C] slice of the
    physical row matrix (bins cols [0:f_pad], value cols
    [f_pad:f_pad+c] — (g, h) pairs since the count-channel removal);
    rows outside the [off, off+count) window are masked.
    sel = (start_block, off, count)."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    rows = load_rows(comb_ref)                  # [R, C] f32/bf16
    # Mosaic has no direct bf16 -> i32 cast; hop through f32
    b = rows[:, :f_pad].astype(jnp.float32).astype(jnp.int32)
    off, cnt = sel_ref[1], sel_ref[2]
    pos = (pl.program_id(0) * rpb
           + jax.lax.broadcasted_iota(jnp.int32, (rpb, 1), 0))
    live = ((pos >= off) & (pos < off + cnt)).astype(jnp.float32)
    v = rows[:, f_pad:f_pad + c].astype(jnp.float32) * live  # [R, c]
    _hist_accumulate(b, v, out_ref, b_hi=b_hi, g=g, c=c, lo_n=lo_n,
                     ngroups=ngroups)


def _hist2_tile_kernel(sel_ref, bins_ref, vals_ref, out_ref, *, b_hi, g, c,
                       lo_n, ngroups, vcol, rpb):
    """One tile of the comb-direct histogram: grid (tile, row block).
    ``bins_ref`` is the tile's own plane of the block, [R, 128] (bin
    columns [128 t, 128 t + 128)), ``vals_ref`` the plane the value
    columns lie in (lanes [vcol, vcol + c)); the tile's [ngroups, M, N]
    accumulator is resident across the row blocks.  The last tile's
    lanes past the bin columns are accumulated too, into groups the
    caller drops."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    b = bins_ref[...].astype(jnp.float32).astype(jnp.int32)
    off, cnt = sel_ref[1], sel_ref[2]
    pos = (pl.program_id(1) * rpb
           + jax.lax.broadcasted_iota(jnp.int32, (rpb, 1), 0))
    live = ((pos >= off) & (pos < off + cnt)).astype(jnp.float32)
    v = vals_ref[:, vcol:vcol + c].astype(jnp.float32) * live
    _hist_accumulate(b, v, out_ref, b_hi=b_hi, g=g, c=c, lo_n=lo_n,
                     ngroups=ngroups)


def _diag_extract(out, ngroups, g, b_hi, c, lo_n, f_pad, b):
    """Diagonal (same-feature) block extraction shared by both kernels."""
    out = out.reshape(ngroups, g, b_hi, g, c, lo_n)
    diag = jnp.diagonal(out, axis1=1, axis2=3)
    diag = jnp.moveaxis(diag, -1, 1)
    hist = jnp.transpose(diag, (0, 1, 2, 4, 3))
    return hist.reshape(f_pad, b, c)


def hist_vmem_bytes(rpb: int, lanes: int) -> int:
    """Scoped VMEM of a comb histogram that reads ``lanes`` f32 lanes of
    ``rpb`` rows a grid step (the whole line untiled; two planes a
    tile: its own and the values').  The pipelined input block, double
    buffered, to the byte: the compiler's own report (a 1 MiB limit)
    reads 2.00 MiB at 2,048 rows x 128 lanes and 4.00 at 2,048 x 256 -
    the resident [ngroups, M, N] accumulator is an output block beside
    it, and the one-hot operands live in the kernel's internal scratch."""
    return 2 * rpb * lanes * 4


def hist_tiles(f_pad: int, C: int) -> int:
    """Tiles a comb histogram of ``f_pad`` columns sweeps on a comb of
    ``C`` lanes: 1 - the whole line a row block, every group in one
    resident accumulator - up to two planes; past them one a plane of
    bin columns, 128 // g groups each.  The group loop of
    ``_hist_accumulate`` is unrolled and the compile grows faster than
    its groups (16 groups 20 s, 64 more than 200 s off the chip for the
    described v5e), and the untiled block and accumulator grow with the
    line: a tile bounds both by one plane."""
    from .layout import LANE
    return 1 if C <= 2 * LANE else -(-f_pad // LANE)


def hist_block_rows(C: int) -> int:
    """Rows a grid step of the comb histogram reads on a comb of ``C``
    lanes: the largest power of two up to ``HIST_COMB_ROWS`` whose block
    fits the scoped VMEM (2,048 at every width: a block reads two planes
    at most)."""
    from .layout import LANE, fit_rows
    lanes = min(C, 2 * LANE)
    return fit_rows(lambda r: hist_vmem_bytes(r, lanes), HIST_COMB_ROWS, 8)


def _comb_hist_call(comb, start, off, count, nblocks, *, f_pad, b, rpb,
                    interpret, channels=2, planes=1):
    """Shared tail of the comb-direct histogram: start-block clamp (both
    ways — a garbage-negative start from a dead partition call must not
    become an OOB DMA), scalar-prefetch grid, diagonal extraction.
    ``nblocks`` may be a python int (static grid) or a traced scalar
    (Mosaic dynamic grid).  ``rpb`` counts rows per block.  Past
    ``hist_tiles`` == 1 the grid is (tile, row block): each tile reads
    its own plane and the values' and keeps its own groups resident."""
    from .layout import (LANE, check_lane_width, comb_block_spec,
                         comb_operand)
    # the comb is plane-major (layout.py): ``planes`` x [n_rows, 128]
    n_rows, C = comb.shape[0] // planes, planes * LANE
    check_lane_width(comb.shape[1], comb.dtype)
    c = channels
    b_hi, g, m, nn, lo_n = hist_geometry(b, c)
    assert f_pad % g == 0, (f_pad, g)
    ngroups = f_pad // g
    start_blk = start // rpb
    off_total = off + (start - start_blk * rpb)
    max_blk = jnp.maximum(n_rows // rpb - nblocks, 0)
    start_blk_c = jnp.clip(start_blk, 0, max_blk)
    off_total = off_total + (start_blk - start_blk_c) * rpb
    sel = jnp.stack([start_blk_c, off_total, count]).astype(jnp.int32)

    tiles = hist_tiles(f_pad, C)
    if tiles > 1:
        gpt = LANE // g
        kern = functools.partial(
            _hist2_tile_kernel, b_hi=b_hi, g=g, c=c, lo_n=lo_n,
            ngroups=gpt, vcol=f_pad % LANE, rpb=rpb)
        vplane = f_pad // LANE
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles, nblocks),
            in_specs=[
                pl.BlockSpec((None, rpb, LANE),
                             lambda t, i, s: (t, s[0] + i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((None, rpb, LANE),
                             lambda t, i, s: (vplane, s[0] + i, 0),
                             memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((gpt, m, nn), lambda t, i, s: (t, 0, 0),
                                   memory_space=pltpu.VMEM),
        )
        view = comb_operand(comb, C)
        out = pl.pallas_call(
            kern,
            name="lgbm_hist",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((tiles * gpt, m, nn),
                                           jnp.float32),
            interpret=interpret,
        )(sel, view, view)
        return _diag_extract(out, tiles * gpt, g, b_hi, c, lo_n,
                             tiles * LANE, b)[:f_pad]

    kern = functools.partial(
        _hist2_comb_kernel, b_hi=b_hi, g=g, c=c, lo_n=lo_n,
        ngroups=ngroups, f_pad=f_pad, rpb=rpb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[comb_block_spec(rpb, C, lambda i, s: s[0] + i,
                                  memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((ngroups, m, nn), lambda i, s: (0, 0, 0),
                               memory_space=pltpu.VMEM),
    )
    out = pl.pallas_call(
        kern,
        name="lgbm_hist",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ngroups, m, nn), jnp.float32),
        interpret=interpret,
    )(sel, comb_operand(comb, C))
    return _diag_extract(out, ngroups, g, b_hi, c, lo_n, f_pad, b)


def _comb_rpb(rows_per_block: int, cap: int) -> int:
    """Rows per block, honouring Mosaic's 8-sublane rule."""
    rpb = min(rows_per_block, max(cap, 8))
    return max((rpb // 8) * 8, 8)


@functools.partial(jax.jit, static_argnames=(
    "f_pad", "padded_bins", "rows_per_block", "interpret", "planes"))
def build_histogram_comb_dyn(
    comb: jnp.ndarray,       # plane-major [planes * n_alloc, 128]
    start: jnp.ndarray,      # i32 scalar: first row of the parent range
    off: jnp.ndarray,        # i32 scalar: valid rows begin at start+off...
    count: jnp.ndarray,      # ...and span count rows
    *,
    f_pad: int,
    padded_bins: int,
    rows_per_block: int = 2048,
    interpret: bool = False,
    planes: int = 1,
) -> jnp.ndarray:
    """Dynamic-grid variant of build_histogram_comb: the block count is a
    TRACED value (ceil(count / rows_per_block) + 1 alignment block), so
    one kernel instance serves every parent size — no ``lax.switch``
    over static bucket classes (XLA copies the whole aliased row matrix
    per branch per split otherwise) and no masked overhang blocks
    (static classes run up to 2x the parent rows)."""
    n_rows = comb.shape[0] // planes
    rpb = _comb_rpb(rows_per_block, n_rows)
    nblocks = jnp.maximum(-(-count // rpb) + 1, 1)
    return _comb_hist_call(comb, start, off, count, nblocks,
                           f_pad=f_pad, b=int(padded_bins), rpb=rpb,
                           interpret=interpret, planes=planes)


@functools.partial(jax.jit, static_argnames=(
    "f_pad", "size", "padded_bins", "rows_per_block", "interpret",
    "planes"))
def build_histogram_comb(
    comb: jnp.ndarray,       # plane-major [planes * n_alloc, 128]
    start: jnp.ndarray,      # i32 scalar: first row of the parent range
    off: jnp.ndarray,        # i32 scalar: valid rows begin at start+off...
    count: jnp.ndarray,      # ...and span count rows
    *,
    f_pad: int,
    size: int,               # static bucket class (max off + count)
    padded_bins: int,
    rows_per_block: int = 2048,
    interpret: bool = False,
    planes: int = 1,
) -> jnp.ndarray:
    """Histogram of comb rows [start+off, start+off+count) WITHOUT
    materialising any sliced copy: the kernel reads [R, C] blocks of the
    row matrix directly (dynamic block offset via scalar prefetch) and
    slices bins/value lanes in VMEM.  The bucket path previously paid
    three lane-padded slice copies (512 B/row each) per split."""
    n_rows = comb.shape[0] // planes
    rpb = _comb_rpb(rows_per_block, size)
    # block-align the dynamic start: one extra block covers the head
    # misalignment, the off/count window masks the rest
    nblocks = -(-size // rpb) + 1
    if n_rows < nblocks * rpb:
        raise ValueError(
            f"comb needs >= {nblocks * rpb} rows for bucket "
            f"size {size} at rows_per_block {rpb} (got "
            f"{n_rows}); pad the row matrix")
    return _comb_hist_call(comb, start, off, count, nblocks,
                           f_pad=f_pad, b=int(padded_bins), rpb=rpb,
                           interpret=interpret, planes=planes)


@functools.partial(jax.jit, static_argnames=("padded_bins", "rows_per_block",
                                             "interpret"))
def build_histogram_pallas2(
    bins: jnp.ndarray,       # [n, F_pad] uint8/int32, values < padded_bins
    values: jnp.ndarray,     # [n, C] f32 (grad, hess, count), pre-masked
    *,
    padded_bins: int,
    rows_per_block: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns hist [F_pad, padded_bins, C] f32."""
    n, f_pad = bins.shape
    c = values.shape[1]
    b = int(padded_bins)
    b_hi, g, m, nn, lo_n = hist_geometry(b, c)
    assert f_pad % g == 0, (f_pad, g)
    ngroups = f_pad // g

    rpb = min(rows_per_block, max(n, 8))
    nblocks = -(-n // rpb)
    n_padded = nblocks * rpb
    if n_padded != n:
        # padded rows carry 0 in every value channel -> contribute nothing
        bins = jnp.pad(bins, ((0, n_padded - n), (0, 0)))
        values = jnp.pad(values, ((0, n_padded - n), (0, 0)))

    kern = functools.partial(_hist2_kernel, b_hi=b_hi, g=g, c=c, lo_n=lo_n,
                             ngroups=ngroups)
    out = pl.pallas_call(
        kern,
        name="lgbm_hist",
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rpb, f_pad), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((rpb, c), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ngroups, m, nn), lambda i: (0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((ngroups, m, nn), jnp.float32),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * n_padded * ngroups * m * nn,
            bytes_accessed=n_padded * f_pad * bins.dtype.itemsize
            + n_padded * c * 4 + ngroups * m * nn * 4,
            transcendentals=0,
        ),
    )(bins, values)
    return _diag_extract(out, ngroups, g, b_hi, c, lo_n, f_pad, b)


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import register_kernel, sds


@register_kernel("hist_pallas2", kind="hist",
                 note="v2 matmul-expanded one-hot histogram")
def _analysis_hist2():
    n, f, b = 4096, 16, 32
    def fn(bins, values):
        return build_histogram_pallas2(bins, values, padded_bins=b)
    return fn, (sds((n, f), jnp.uint8), sds((n, 2), jnp.float32))


@register_kernel("hist_comb", kind="hist",
                 note="comb-direct histogram (physical mode)")
def _analysis_hist_comb():
    n, C, f, b = 7168, 128, 16, 32
    def fn(comb, start, off, count):
        return build_histogram_comb(comb, start, off, count, f_pad=f,
                                    size=2048, padded_bins=b)
    return fn, (sds((n, C), jnp.float32), sds((), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32))
