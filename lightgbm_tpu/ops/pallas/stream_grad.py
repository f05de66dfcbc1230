"""Pallas TPU kernels: score-resident gradient streaming (physical mode).

Reference analog: the cuda_exp boosting loop keeps scores and gradients
device-resident and recomputes gradients in place each iteration
(src/boosting/cuda/cuda_score_updater.cpp + objective/cuda/ GetGradients
kernels).  The TPU physical-partition mode goes further: scores, labels
and per-row objective constants ride as COLUMNS of the permuted
``[n_alloc, C]`` row matrix, so the per-tree gradient refresh is one
streaming in-place pass over the matrix — no per-index gather by row id
(~13 ns/index), and none of the ``[n, k<128]`` f32 temporaries that
lane-pad to 512 B/row and OOM the 10.5M-row dataset.

Column layout (appended after the row-id bytes; every value bf16-exact
so the partition kernel's bf16-precision compaction matmuls preserve it
bit-for-bit):

  [0 : f]          bins (uint8 values in f32)
  [f+0 .. f+2]     g*w, h*w, w       (refreshed per tree; w = validity)
  [f+3 .. f+5]     row-id bytes (hi, mid, lo)
  [f+6 .. f+8]     score as 3 bf16-exact f32 terms (hi, mid, lo —
                   ~24 mantissa bits total; (hi + mid) + lo IS the f32
                   that was split).  On the unpaged stream route this
                   is the booster's train score OF RECORD between
                   pulls: the refresh adds every tree's shrunk leaf
                   output here, by position, and the row-order copy
                   (``GBDT.train_score``) is made from these columns
                   and the row-id bytes beside them when somebody
                   reads it (``ops/grow.py`` ``pull_score``)
  [f+9 .. ]        objective constants:
                     binary: sign (±1), lw_hi, lw_mid, lw_lo
                             (label_weight = scale_pos_weight x sample
                             weight, bf16x3)
                     l2:     t_hi, t_mid, t_lo, w_hi, w_mid, w_lo
                             (target bf16x3, sample weight bf16x3)

Gradient formulas mirror objective/binary.py (binary_objective.hpp:76)
and objective/regression.py (regression_objective.hpp:117):

  binary: z = sign * sigmoid * score; abs_r = sigmoid / (1 + exp(z))
          g = -sign * abs_r * lw;  h = abs_r * (sigmoid - abs_r) * lw
  l2:     g = (score - target) * w;  h = w

Both kernels write FULL blocks of BlockSpec-aliased outputs, so the
uninitialised-VMEM write-back hazard (see apply_find) does not apply;
uncovered blocks (the slack rows past n_pad) keep the aliased input's
HBM content untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import (comb_block_spec, comb_operand,
                     comb_operand_shape, load_rows, store_rows, to_planes,
                     to_rows)

# column offsets relative to f (the bin column count)
COL_G, COL_H, COL_CNT = 0, 1, 2
COL_RID = 3            # 3 columns
COL_SC = 6             # 3 columns
COL_CONSTS = 9         # objective constants start here

N_CONSTS = {"binary": 4, "l2": 6}

# Rows a grid step of the init / refresh kernels takes (BlockSpec-
# pipelined passes over [0, n_pad), their own block; every power of two
# up to it divides the scan's), and the scoped VMEM both are priced at:
# eight f32 comb lines and 1 KiB a row, an envelope of the compiler's own
# report for the described v5e - the refresh 4.09 / 8.06 / 16.37 MiB at
# 128 / 256 / 512 rows x 2,048 lanes, the init 7.38 / 14.73 / 29.81
# (its [R, f] bins beside the line it builds).  512 rows up to seven
# planes, 128 at sixteen.
STREAM_ROWS = 512


def stream_vmem_bytes(R: int, C: int) -> int:
    """The scoped VMEM an init or refresh step of ``R`` rows on a comb
    of ``C`` lanes is priced at."""
    return R * (8 * C * 4 + 1024)


def stream_block_rows(C: int) -> int:
    """Rows a grid step of the init / refresh kernels takes on a comb of
    ``C`` lanes: the largest power of two up to ``STREAM_ROWS`` whose
    price fits the scoped VMEM."""
    from .layout import fit_rows
    return fit_rows(lambda r: stream_vmem_bytes(r, C), STREAM_ROWS, 8)


def stream_columns(kind: str) -> int:
    """Total non-bin columns the streaming layout needs."""
    return COL_CONSTS + N_CONSTS[kind]


def _round_bf16(x, mosaic: bool):
    """Round f32 to bf16 precision, for real.  In XLA an
    astype(bf16).astype(f32) round-trip is ELIDED by the
    excess-precision pass inside fusions (verified on-device), so use
    lax.reduce_precision there; Mosaic honours casts literally but has
    no reduce_precision lowering, so kernels keep the cast chain."""
    if mosaic:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.reduce_precision(x, 8, 7)


def split_bf16_3(x: jnp.ndarray, mosaic: bool = False):
    """f32 -> 3 bf16-exact f32 terms whose sum is f32-faithful (~24
    mantissa bits).  Each term survives a bf16-precision matmul by a 0/1
    matrix exactly (the apply_find bf16x3 trick applied to storage)."""
    a = _round_bf16(x, mosaic)
    r = x - a
    b = _round_bf16(r, mosaic)
    c = _round_bf16(r - b, mosaic)
    return a, b, c


def build_aux(kind: str, score, cnt, consts):
    """Stack the init-kernel aux input [K_aux, n_pad] f32: row 0 score,
    row 1 validity/count, rows 2.. objective constants (pre-split)."""
    rows = [score, cnt] + list(consts)
    assert len(rows) == 2 + N_CONSTS[kind]
    return jnp.stack([r.astype(jnp.float32) for r in rows], axis=0)


def binary_consts(sign, label_weight):
    """Per-row constant rows for the binary objective (pre-padded [n])."""
    return (sign,) + split_bf16_3(label_weight)


def l2_consts(target, weight):
    """Per-row constant rows for the l2 objective (pre-padded [n])."""
    return split_bf16_3(target) + split_bf16_3(weight)


def _grad_core(kind: str, sigmoid: float, s, cnt, consts):
    """(g, h) from score + per-row constants; all [1, R] f32 lanes."""
    if kind == "binary":
        sign = consts[0]
        lw = consts[1] + consts[2] + consts[3]
        z = sign * (sigmoid * s)
        abs_r = sigmoid / (1.0 + jnp.exp(z))
        g = -sign * abs_r * lw
        h = abs_r * (sigmoid - abs_r) * lw
    elif kind == "l2":
        t = consts[0] + consts[1] + consts[2]
        w = consts[3] + consts[4] + consts[5]
        g = (s - t) * w
        h = w
    else:  # pragma: no cover - gated by stream_supported
        raise ValueError(kind)
    return g * cnt, h * cnt


def _transpose_lanes(rows, *, R: int):
    """Exact MXU transpose of lane-oriented [1, R] rows into one
    sublane-oriented [R, K] block — a direct [1, R] -> [R, 1] relayout
    is a Mosaic sublane shuffle (~10x, see perf notes)."""
    W = jnp.concatenate(rows, axis=0)                    # [K, R]
    r_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    eye = (r_i == c_i).astype(jnp.float32)
    return jax.lax.dot_general(                          # [R, K]
        eye, W, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _writeback(x, rows, dst_cols, *, R: int, C: int):
    """x [R, C] with columns dst_cols replaced by rows [K, R] (each row
    bf16-exact), via exact MXU transpose + placement matmuls — writing a
    lane-oriented [1, R] value into a column would otherwise force a
    sublane relayout (~10x, see perf notes)."""
    K = len(dst_cols)
    Wt = _transpose_lanes(rows, R=R)                     # [R, K]
    sub = jax.lax.broadcasted_iota(jnp.int32, (K, C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, C), 1)
    tgt = sum(jnp.where(sub == i, c, 0) for i, c in enumerate(dst_cols))
    P = (lane == tgt).astype(jnp.float32)                # [K, C]
    delta = jax.lax.dot_general(                         # [R, C]
        Wt, P, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    keep = jnp.ones((1, C), jnp.float32)
    for c in dst_cols:
        keep = keep * (lane1 != c).astype(jnp.float32)
    return x * keep + delta


def _extract(x, src_cols, *, C: int):
    """Columns src_cols of x [R, C] as [K, R] f32 lanes (exact: the
    extracted columns are bf16-exact by layout contract)."""
    K = len(src_cols)
    sub = jax.lax.broadcasted_iota(jnp.int32, (K, C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, C), 1)
    tgt = sum(jnp.where(sub == i, c, 0) for i, c in enumerate(src_cols))
    E = (lane == tgt).astype(jnp.float32)                # [K, C]
    return jax.lax.dot_general(                          # [K, R]
        E, x.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _refresh_kernel(lv_ref, comb_in, comb_ref, *, kind: str, sigmoid: float,
                    f: int, R: int, C: int, nc: int):
    x = load_rows(comb_in).astype(jnp.float32)           # [R, C]
    cols = ([f + COL_SC, f + COL_SC + 1, f + COL_SC + 2, f + COL_CNT]
            + [f + COL_CONSTS + i for i in range(nc)])
    V = _extract(x, cols, C=C)
    s = V[0:1] + V[1:2] + V[2:3] + lv_ref[:]
    cnt = V[3:4]
    consts = [V[4 + i:5 + i] for i in range(nc)]
    g, h = _grad_core(kind, sigmoid, s, cnt, consts)
    sh, sm, sl = split_bf16_3(s, mosaic=True)
    g = g.astype(jnp.bfloat16).astype(jnp.float32)
    h = h.astype(jnp.bfloat16).astype(jnp.float32)
    store_rows(comb_ref, _writeback(
        x, [g, h, sh, sm, sl],
        [f + COL_G, f + COL_H, f + COL_SC, f + COL_SC + 1, f + COL_SC + 2],
        R=R, C=C).astype(comb_ref.dtype))
    return x, g, h


def _refresh_hist_kernel(lv_ref, comb_in, comb_ref, hist_ref, *,
                         kind: str, sigmoid: float, f: int, R: int,
                         C: int, nc: int, b_hi: int, hg: int, lo_n: int,
                         ngroups: int):
    """Refresh + NEXT tree's root histogram in one pass (lever #5): the
    block is already resident for the score/gradient rewrite, so its
    (bins, fresh g/h) contribution to the root histogram is accumulated
    here instead of re-reading the whole comb matrix in a separate
    kernel one call later.  The refresh grid covers exactly the rows
    [0, n_pad) the root histogram wants; slack rows never enter."""
    from .hist_kernel2 import _hist_accumulate
    x, g, h = _refresh_kernel(lv_ref, comb_in, comb_ref, kind=kind,
                              sigmoid=sigmoid, f=f, R=R, C=C, nc=nc)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    v = _transpose_lanes([g, h], R=R)                    # [R, 2]
    bins_i = x[:, :f].astype(jnp.int32)
    _hist_accumulate(bins_i, v, hist_ref, b_hi=b_hi, g=hg, c=2,
                     lo_n=lo_n, ngroups=ngroups)


def _init_kernel(bins_ref, aux_ref, comb_in, comb_ref, *, kind: str,
                 sigmoid: float, f_real: int, f: int, R: int, C: int,
                 nc: int):
    del comb_in  # aliased for the untouched slack rows only
    # Mosaic has no direct u8 -> f32 cast; hop through i32
    binsf = bins_ref[:].astype(jnp.int32).astype(jnp.float32)  # [R, f_real]
    sub_b = jax.lax.broadcasted_iota(jnp.int32, (f_real, C), 0)
    lane_b = jax.lax.broadcasted_iota(jnp.int32, (f_real, C), 1)
    Pb = (lane_b == sub_b).astype(jnp.float32)           # [f_real, C]
    base = jax.lax.dot_general(                          # [R, C]
        binsf, Pb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # row ids from the global position (identity permutation at init)
    pos = (pl.program_id(0) * R
           + jax.lax.broadcasted_iota(jnp.int32, (R, C), 0))
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    rid_hi = (pos // 65536).astype(jnp.float32)
    rid_mid = ((pos // 256) % 256).astype(jnp.float32)
    rid_lo = (pos % 256).astype(jnp.float32)
    base = base + jnp.where(lane == f + COL_RID, rid_hi, 0.0)
    base = base + jnp.where(lane == f + COL_RID + 1, rid_mid, 0.0)
    base = base + jnp.where(lane == f + COL_RID + 2, rid_lo, 0.0)

    s = aux_ref[0:1]
    cnt = aux_ref[1:2]
    consts = [aux_ref[2 + i:3 + i] for i in range(nc)]
    g, h = _grad_core(kind, sigmoid, s, cnt, consts)
    sh, sm, sl = split_bf16_3(s, mosaic=True)
    g = g.astype(jnp.bfloat16).astype(jnp.float32)
    h = h.astype(jnp.bfloat16).astype(jnp.float32)
    store_rows(comb_ref, _writeback(
        base, [g, h, cnt, sh, sm, sl] + consts,
        [f + COL_G, f + COL_H, f + COL_CNT,
         f + COL_SC, f + COL_SC + 1, f + COL_SC + 2]
        + [f + COL_CONSTS + i for i in range(nc)],
        R=R, C=C).astype(comb_ref.dtype))


def _xla_refresh(comb, lv2d, *, kind, sigmoid, f, n_pad, C, nc,
                 round_bf16):
    """Off-TPU reference implementation (exact f32; the interpret path
    skips bf16 rounding of g/h the same way the non-streaming CPU path
    does — on TPU the histogram matmuls round values to bf16 anyway)."""
    n_alloc = comb.shape[0]
    lv = jnp.pad(lv2d.reshape(-1), (0, n_alloc - n_pad))
    sc = comb[:, f + COL_SC] + comb[:, f + COL_SC + 1] + comb[:, f + COL_SC + 2]
    s = sc + lv
    cnt = comb[:, f + COL_CNT]
    consts = [comb[:, f + COL_CONSTS + i] for i in range(nc)]
    g, h = _grad_core(kind, sigmoid, s, cnt, consts)
    if round_bf16:
        g = _round_bf16(g, mosaic=False)
        h = _round_bf16(h, mosaic=False)
    sh, sm, sl = split_bf16_3(s)
    live = jnp.arange(n_alloc) < n_pad
    def put(c, col, v):
        return c.at[:, col].set(jnp.where(live, v, c[:, col]))
    comb = put(comb, f + COL_G, g)
    comb = put(comb, f + COL_H, h)
    comb = put(comb, f + COL_SC, sh)
    comb = put(comb, f + COL_SC + 1, sm)
    comb = put(comb, f + COL_SC + 2, sl)
    return comb


def _xla_refresh_hist(comb, lv2d, *, kind, sigmoid, f, n_pad, C, nc,
                      round_bf16, padded_bins, rows_per_block):
    """Reference fused refresh+root-hist: the refresh, then EXACTLY the
    computation grow's interpret stream-root branch runs on the carried
    comb — bins/value column slices, position mask, build_histogram —
    so carrying the returned histogram into the next tree is
    bit-identical to recomputing it there."""
    from ..histogram import build_histogram
    comb = _xla_refresh(comb, lv2d, kind=kind, sigmoid=sigmoid, f=f,
                        n_pad=n_pad, C=C, nc=nc, round_bf16=round_bf16)
    n_alloc = comb.shape[0]
    pos_al = jnp.arange(n_alloc, dtype=jnp.int32)
    gvals = (jax.lax.slice(comb, (0, f), (n_alloc, f + 3))
             * (pos_al < n_pad).astype(jnp.float32)[:, None])
    bins_c = jax.lax.slice(comb, (0, 0), (n_alloc, f))
    hist = build_histogram(bins_c, gvals[:, :2], padded_bins=padded_bins,
                           rows_per_block=rows_per_block)
    return comb, hist


def make_refresh(*, kind: str, sigmoid: float, f: int, n_alloc: int,
                 n_pad: int, C: int, R: int = 512,
                 interpret: bool = False, dtype=jnp.float32,
                 root_hist: bool = False, padded_bins: int = 0,
                 root_rpb: int = 16384,
                 kernel_interpret: bool = False):
    """Build ``refresh(comb, lv) -> comb`` (in-place over rows
    [0, n_pad); slack rows untouched).  ``lv`` is [1, n_pad] f32: the
    per-POSITION score delta (shrinkage * leaf output of the leaf
    owning that position under the CURRENT partition).  The leading
    1-dim keeps the BlockSpec legal — blocks advance along dim 1
    ((1, R) at index (0, i)); do NOT pass a [n_pad // R, R] reshape.

    With ``root_hist=True`` the returned function is ``refresh(comb, lv)
    -> (comb, hist [f, padded_bins, 2])``: the NEXT tree's root
    histogram is accumulated from the freshly-written (bins, g, h)
    blocks while they are VMEM-resident, saving the full comb read the
    standalone root-histogram kernel would pay one call later.

    ``kernel_interpret=True`` builds the REAL Mosaic kernels but runs
    them through the Pallas interpreter (the test seam the partition
    kernels expose as LGBM_TPU_PART_INTERP=kernel) — off-TPU tests pin
    the kernel bodies against the XLA references."""
    from .layout import check_lane_width
    check_lane_width(C, dtype)
    nc = N_CONSTS[kind]
    assert n_pad % R == 0
    nblocks = n_pad // R
    if interpret and not kernel_interpret:
        if root_hist:
            ref_h = jax.jit(functools.partial(
                _xla_refresh_hist, kind=kind, sigmoid=sigmoid, f=f,
                n_pad=n_pad, C=C, nc=nc, round_bf16=False,
                padded_bins=int(padded_bins), rows_per_block=root_rpb))

            def refresh_h1(comb, lv2d):
                comb_l, hist = ref_h(to_rows(comb, C), lv2d)
                return to_planes(comb_l), hist

            return jax.jit(refresh_h1)
        ref = jax.jit(functools.partial(
            _xla_refresh, kind=kind, sigmoid=sigmoid, f=f, n_pad=n_pad,
            C=C, nc=nc, round_bf16=False))

        def refresh1(comb, lv2d):
            return to_planes(ref(to_rows(comb, C), lv2d))

        return jax.jit(refresh1)

    if root_hist:
        from .hist_kernel2 import _diag_extract, hist_geometry
        b = int(padded_bins)
        b_hi, hg, m, nn, lo_n = hist_geometry(b, 2)
        assert f % hg == 0, (f, hg)
        ngroups = f // hg
        kern_h = functools.partial(
            _refresh_hist_kernel, kind=kind, sigmoid=sigmoid, f=f, R=R,
            C=C, nc=nc, b_hi=b_hi, hg=hg, lo_n=lo_n, ngroups=ngroups)

        @jax.jit
        def refresh_h(comb, lv2d):
            comb_r, out = pl.pallas_call(
                kern_h,
                name="lgbm_refresh",
                grid=(nblocks,),
                in_specs=[
                    pl.BlockSpec((1, R), lambda i: (0, i),
                                 memory_space=pltpu.VMEM),
                    comb_block_spec(R, C, lambda i: i,
                                    memory_space=pltpu.VMEM),
                ],
                out_specs=[
                    comb_block_spec(R, C, lambda i: i,
                                    memory_space=pltpu.VMEM),
                    pl.BlockSpec((ngroups, m, nn), lambda i: (0, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct(comb_operand_shape(n_alloc, C),
                                         dtype),
                    jax.ShapeDtypeStruct((ngroups, m, nn), jnp.float32),
                ],
                input_output_aliases={1: 0},
                cost_estimate=pl.CostEstimate(
                    flops=2 * n_pad * (C * (R + 16)
                                       + ngroups * m * nn // R),
                    bytes_accessed=2 * n_pad * C * 4
                    + ngroups * m * nn * 4,
                    transcendentals=n_pad,
                ),
                interpret=kernel_interpret,
            )(lv2d, comb_operand(comb, C))
            return (comb_r.reshape(comb.shape),
                    _diag_extract(out, ngroups, hg, b_hi, 2, lo_n, f, b))

        return refresh_h

    # pallas_call kernels must return None; the core's return value
    # exists for the fused root-hist variant only
    def kern(*refs):
        _refresh_kernel(*refs, kind=kind, sigmoid=sigmoid, f=f, R=R,
                        C=C, nc=nc)

    @jax.jit
    def refresh(comb, lv2d):
        return pl.pallas_call(
            kern,
            name="lgbm_refresh",
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((1, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                comb_block_spec(R, C, lambda i: i,
                                memory_space=pltpu.VMEM),
            ],
            out_specs=comb_block_spec(R, C, lambda i: i,
                                      memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                comb_operand_shape(n_alloc, C), dtype),
            input_output_aliases={1: 0},
            cost_estimate=pl.CostEstimate(
                flops=2 * n_pad * C * (R + 16),
                bytes_accessed=2 * n_pad * C * 4,
                transcendentals=n_pad,
            ),
            interpret=kernel_interpret,
        )(lv2d, comb_operand(comb, C)).reshape(comb.shape)

    return refresh


def _xla_init(comb0, bins, aux, *, kind, sigmoid, f, n_pad, C, nc,
              round_bf16):
    n_alloc = comb0.shape[0]
    binsf = bins.astype(jnp.float32)
    comb = jax.lax.dynamic_update_slice(
        comb0, binsf, (jnp.int32(0), jnp.int32(0)))
    rid = jnp.arange(n_alloc, dtype=jnp.int32)
    comb = comb.at[:, f + COL_RID].set((rid // 65536).astype(jnp.float32))
    comb = comb.at[:, f + COL_RID + 1].set(
        ((rid // 256) % 256).astype(jnp.float32))
    comb = comb.at[:, f + COL_RID + 2].set((rid % 256).astype(jnp.float32))
    live = jnp.arange(n_alloc) < n_pad
    def putrow(c, col, v):
        vp = jnp.pad(v, (0, n_alloc - n_pad))
        return c.at[:, col].set(jnp.where(live, vp, c[:, col]))
    s, cnt = aux[0], aux[1]
    consts = [aux[2 + i] for i in range(nc)]
    g, h = _grad_core(kind, sigmoid, s, cnt, consts)
    if round_bf16:
        g = _round_bf16(g, mosaic=False)
        h = _round_bf16(h, mosaic=False)
    sh, sm, sl = split_bf16_3(s)
    for col, v in zip(
            [f + COL_G, f + COL_H, f + COL_CNT,
             f + COL_SC, f + COL_SC + 1, f + COL_SC + 2]
            + [f + COL_CONSTS + i for i in range(nc)],
            [g, h, cnt, sh, sm, sl] + consts):
        comb = putrow(comb, col, v)
    return comb


def make_init(*, kind: str, sigmoid: float, f_real: int, f: int,
              n_alloc: int, n_pad: int, C: int, R: int = 512,
              interpret: bool = False, dtype=jnp.float32,
              kernel_interpret: bool = False):
    """Build ``init(comb0, bins, aux) -> comb``: populate the streaming
    row matrix from the [n_pad, f_real] uint8 bin matrix and the
    [2 + n_consts, n_pad] aux rows (score, validity, objective consts).
    ``comb0`` must be the zero comb of ``n_alloc`` lines of ``C`` lanes
    (its slack rows pass through)."""
    from .layout import check_lane_width
    check_lane_width(C, dtype)
    nc = N_CONSTS[kind]
    assert n_pad % R == 0
    nblocks = n_pad // R
    if interpret and not kernel_interpret:
        ini = jax.jit(functools.partial(
            _xla_init, kind=kind, sigmoid=sigmoid, f=f, n_pad=n_pad,
            C=C, nc=nc, round_bf16=False))

        def init1(comb0, bins, aux):
            return to_planes(ini(to_rows(comb0, C), bins, aux))

        return jax.jit(init1)

    k_aux = 2 + nc
    kern = functools.partial(_init_kernel, kind=kind, sigmoid=sigmoid,
                             f_real=f_real, f=f, R=R, C=C, nc=nc)

    @jax.jit
    def init(comb0, bins, aux):
        return pl.pallas_call(
            kern,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((R, f_real), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k_aux, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                comb_block_spec(R, C, lambda i: i,
                                memory_space=pltpu.VMEM),
            ],
            out_specs=comb_block_spec(R, C, lambda i: i,
                                      memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct(
                comb_operand_shape(n_alloc, C), dtype),
            input_output_aliases={2: 0},
            cost_estimate=pl.CostEstimate(
                flops=2 * n_pad * C * (R + f_real + 16),
                bytes_accessed=n_pad * (f_real + 2 * C * 4),
                transcendentals=n_pad,
            ),
            interpret=kernel_interpret,
        )(bins, aux, comb_operand(comb0, C)).reshape(comb0.shape)

    return init


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import register_kernel, sds


def _stream_shapes():
    # f=16 features, l2 objective (6 consts), 4096 padded rows + slack
    return dict(f=16, n_alloc=7168, n_pad=4096, C=128, R=512)


@register_kernel("stream_init", kind="stream",
                 note="comb init from bins + aux rows")
def _analysis_stream_init():
    s = _stream_shapes()
    fn = make_init(kind="l2", sigmoid=1.0, f_real=s["f"], **s)
    k_aux = 2 + N_CONSTS["l2"]
    return fn, (sds((s["n_alloc"], s["C"]), jnp.float32),
                sds((s["n_pad"], s["f"]), jnp.uint8),
                sds((k_aux, s["n_pad"]), jnp.float32))


@register_kernel("stream_refresh", kind="stream",
                 note="per-tree score/gradient refresh")
def _analysis_stream_refresh():
    s = _stream_shapes()
    fn = make_refresh(kind="l2", sigmoid=1.0, **s)
    return fn, (sds((s["n_alloc"], s["C"]), jnp.float32),
                sds((1, s["n_pad"]), jnp.float32))


@register_kernel("stream_refresh_root", kind="stream",
                 note="fused refresh + next root histogram carry")
def _analysis_stream_refresh_root():
    s = _stream_shapes()
    fn = make_refresh(kind="l2", sigmoid=1.0, root_hist=True,
                      padded_bins=32, **s)
    return fn, (sds((s["n_alloc"], s["C"]), jnp.float32),
                sds((1, s["n_pad"]), jnp.float32))
