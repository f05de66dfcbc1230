"""Pallas TPU kernel: compute-light permutation packing for the
single-scan partition (+ the pack=2 half-width comb variant).

The single-scan kernel's block schedule (partition_kernel2.py: one
read of the parent, overlapping garbage-tail writes behind a 1-block
read-ahead, exactly-sized copyback) left ONE compute-bound stage: the
per-block compaction ran as an [R, R] one-hot matmul - R*C MACs PER
ROW (R=512, C=128: 65k).  XGBoost's GPU partition computes row
destinations with warp prefix sums and moves rows by address, never
through a dense permutation matrix - this module is that idea in
Mosaic terms.  What decides its cost on the chip is the shape of the
arrays it works on: a vreg is [8, 128], so a [R, 1] column of per-row
state is R / 8 vregs - as many as the whole [R, 128] block - and the
v5e has 64 of them, so every such column is also spilled and
reloaded.  A [K, R] array (K <= 8) is R / 128 vregs.  Hence (pack=1,
ISSUE 28; 6.2k -> 1.5k VLIW bundles a block in the unfused scan,
11.0k -> 6.1k in the fused one, PERF.md section 5):

* everything that is ONE NUMBER A ROW lives in LANE orientation: the
  split column (one exact [1, C] x [R, C]^T matvec), the go-left
  bits, both sides' prefix positions (a lane Hillis-Steele scan on one
  [2, R] array), the destinations, and all log2(R) rounds of the
  routing's bookkeeping (_route_words);
* rows move through an LSB-first BUTTERFLY: in round k the row in
  slot j goes to slot j ^ k iff bit k of (j XOR its destination) is
  set.  For a side's kept rows - consecutive destinations in row
  order, ascending or DESCENDING - the routing is collision-free
  (_route_words states the argument; tests/test_partition_perm.py
  fuzzes it against a numpy oracle), so the right side goes straight
  to the matmul scheme's reversed order and the last block's left
  tail straight to its offset: no reversal pass, no dynamic rotate.
  O(log R) selects per row replace the O(R) MAC column of the one-hot
  matmul;
* the finished routing word crosses to ROW orientation ONCE, lane-
  replicated, through one small exact MXU contraction
  (_rows_from_lanes), together with the go-left / go-right bits the
  fused scan's histogram hook needs (it no longer extracts the split
  column a second time);
* on the [R, C] block the three in-vreg rounds are one sublane gather
  a vreg, and the rounds across vregs run three at a time on eight
  vregs held in registers (_route_rows): a vreg is loaded and stored
  twice a side, not once a round.

permute and matmul kernels produce BIT-IDENTICAL row layouts (not
just equal multisets) and compiled trees match byte-for-byte across
``LGBM_TPU_PARTITION=permute|matmul`` (the tpu_smoke identity gate).

Because rows move through selects and gathers - never through the MXU
- the permutation packing preserves ARBITRARY f32 column values
exactly; the matmul scheme's "columns must be bf16-exact" constraint
now binds only the histogram kernels.  dtype-agnostic in the
interpreter (the HBM-side (8,128)x2 bf16 tiling restriction on dynamic
row offsets still gates ``LGBM_TPU_COMB_DT=bf16``; see ops/grow.py).

The block schedule itself is NOT duplicated: ``_pack_permute`` plugs
into partition_kernel2's ``_scan_kernel`` through its ``pack_impl``
hook, so the DMA/cursor safety argument keeps exactly one home.

``pack=2`` (two logical rows per 128-lane line — ops/pallas/layout.py
``comb_layout``) has its own scan + copyback kernels at the bottom of
this file, still in the older ROW-oriented form (sublane prefix scan,
rotate routing, XOR-exchange reversal: _prefix_rows, _compact_logical,
_reverse_rows; refused by Mosaic on the chip, ROADMAP A12): the
routing runs in the LOGICAL row domain (an extra
bit-0 round exchanges lane halves), every physical memref stays
128-wide f32, and partition DMA bytes per logical row HALVE.  Cursor
parity is absorbed by one dynamic logical roll of the packed buffer
per write plus a one-line VMEM carry that re-merges the half-line the
previous write left at the boundary.  Since ISSUE 4 this is the
TRAINED path behind ``LGBM_TPU_COMB_PACK=2``: ops/grow.py wires every
comb consumer (comb-direct + fused histograms via hist_kernel2 /
fused_split, stream init/refresh via stream_grad, rid/value plumbing)
to the packed layout, with pack=1 the default until chip numbers land.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import LANE, PACK_W, check_lane_width
from .partition_kernel import _HBM, SEL_S0, SEL_CNT, SEL_FEAT, _go_left
from .partition_kernel2 import _CUR_L, _CUR_TL, _CUR_R, \
    make_partition_ss


def _row_iota(R: int):
    return jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)


def _prefix_rows(v, *, R: int):
    """(pack=2 only.)  Inclusive prefix sum along sublanes of a [R, 1]
    f32 vector: log2(R) Hillis-Steele rounds of static roll + masked
    add (wrapped lanes zeroed).  Exact for 0/1 flags (integer sums < 2^24)."""
    row = _row_iota(R)
    p = v
    k = 1
    while k < R:
        p = p + jnp.where(row >= k, pltpu.roll(p, k, 0), 0.0)
        k *= 2
    return p


def _reverse_rows(y, *, R: int):
    """(pack=2 only.)  Full sublane reversal (slot j -> R - 1 - j) as
    log2(R) constant index-XOR exchange rounds: y'[j] = y[j ^ 2^k]
    composes to the full bit complement."""
    row = _row_iota(R)
    k = 1
    while k < R:
        lo = pltpu.roll(y, R - k, 0)       # y[j + k]
        hi = pltpu.roll(y, k, 0)           # y[j - k]
        y = jnp.where(jnp.bitwise_and(row, k) > 0, hi, lo)
        k *= 2
    return y


def _lane_iota(R: int):
    return jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)


def _prefix_lanes(v, *, R: int):
    """Inclusive prefix sum along LANES of a [K, R] f32 array (K rows
    scanned at once): log2(R) Hillis-Steele rounds of static lane roll
    + masked add.  Exact for 0/1 flags.  A [K, R] array with K <= 8 is
    R / 128 vregs, so a round costs what ONE row of the block costs in
    row orientation."""
    lane = _lane_iota(R)
    p = v
    k = 1
    while k < R:
        p = p + jnp.where(lane >= k, pltpu.roll(p, k, 1), 0.0)
        k *= 2
    return p


def _xchg_lanes(a, k: int, *, R: int):
    """a[:, j ^ k] for a [K, R] array (k a power of two < R)."""
    hi = jnp.bitwise_and(_lane_iota(R), k) > 0
    return jnp.where(hi, pltpu.roll(a, k, 1), pltpu.roll(a, R - k, 1))


_SUB = 8          # rows of one 32-bit vreg: they move together


def _vreg(v: int):
    """Rows of vreg ``v`` of a [R, C] block."""
    return slice(v * _SUB, (v + 1) * _SUB)


def _xchg_rows(y, k: int):
    """y[j ^ k] for a [n, C] array, k a power of two and a whole
    number of vregs: a static renaming of vregs (aligned slices), no
    data moves."""
    return jnp.concatenate(
        [y[(i ^ 1) * k:((i ^ 1) + 1) * k] for i in range(y.shape[0] // k)],
        axis=0)


def _route_words(flags, dst, *, R: int):
    """The bookkeeping of the bit-serial routing, LANE-dense.

    ``flags`` [K, R] bool marks, per side (one sublane each), the rows
    that side keeps; ``dst`` [K, R] i32 their destination slots, which
    within a side are consecutive in row order (ascending or
    descending).  Rows move through an LSB-first butterfly: in round k
    the row in slot j goes to slot j ^ k iff bit k of ``j ^ dst`` is
    set.  After round k a kept row sits in the slot made of its source
    index's bits above k and its destination's bits up to k; two kept
    rows of one side that share the upper bits are fewer than 2k rows
    apart, so their destinations differ by less than 2k and not by 0,
    and the slots differ: no collision, in either direction of travel
    (tests/test_partition_perm.py fuzzes it against a numpy oracle).
    A slot whose row left without a successor keeps a stale copy; its
    state is zeroed so the copy never moves again.

    Returns the [K, R] i32 routing word of every slot j.  Bits 0-2:
    the sublane, within j's own vreg, of the row that slot j holds
    after the three in-vreg rounds (k = 1, 2, 4) - those rounds are one
    sublane gather on the block.  Bit b >= 3: "in round k = 2**b slot j
    takes the row of slot j ^ k".  The whole of it - 9 rounds at R =
    512 - runs on R / 128 vregs; the [R, C] block sees only the
    finished word."""
    lane = _lane_iota(R)
    rel = jnp.where(flags, jnp.bitwise_xor(lane, dst), 0)
    word = jnp.broadcast_to(jnp.bitwise_and(lane, _SUB - 1), rel.shape)
    k = 1
    while k < R:
        relx = _xchg_lanes(rel, k, R=R)
        bit = jnp.bitwise_and(relx, k)
        if k < _SUB:
            word = jnp.where(bit > 0, _xchg_lanes(word, k, R=R), word)
        else:
            word = jnp.bitwise_or(word, bit)
        # bits below k are never read again, so an arriving state keeps
        # its bit k; a departed slot with no arrival is zeroed
        rel = jnp.where(bit > 0, relx,
                        jnp.where(jnp.bitwise_and(rel, k) > 0, 0, rel))
        k *= 2
    return word


_PIECE_BITS = 8   # the routing word crosses the MXU in bf16-exact pieces
_BIAS = 23        # ... on top of 2**23, so the f32 result's low mantissa
#                   bits ARE the word: a bitcast, no f32 -> i32 convert


def _rows_from_lanes(w, glf, grf, *, R: int, C: int):
    """Lane-oriented [1, R] i32 word (< 2**23) and f32 side flags ->
    row-oriented, lane-REPLICATED [R, C] arrays: the word (i32; bits
    23 and up are the bias) and the two flags (f32).  One exact MXU
    contraction over the 16 sublanes of a bf16 [16, R] operand does the
    transpose AND the lane broadcast (out[r, c] = sum_k W[k, r] *
    O[k, c]: integer operands < 256 against power-of-two weights, f32
    accumulation below 2**24); a [1, R] -> [R, 1] relayout or a lane
    broadcast of a column would cost a vreg op a row tile on the
    vector units."""
    m = (1 << _PIECE_BITS) - 1
    pieces = [jnp.bitwise_and(jnp.right_shift(w, i * _PIECE_BITS), m)
              .astype(jnp.float32) for i in range(3)]
    one = jnp.ones((1, R), jnp.float32)
    W = jnp.concatenate(
        pieces + [one, glf, grf, jnp.zeros((10, R), jnp.float32)],
        axis=0).astype(jnp.bfloat16)                     # [16, R]
    sub = jax.lax.broadcasted_iota(jnp.int32, (16, 3 * C), 0)
    blk = jax.lax.broadcasted_iota(jnp.int32, (16, 3 * C), 1) // C
    # column block 0: the word (rows 0-2 its pieces, row 3 the bias);
    # blocks 1 / 2: left / right flag (rows 4 / 5)
    O = jnp.where(blk == 0, 0.0, (sub == blk + 3).astype(jnp.float32))
    for i, e in enumerate((0, _PIECE_BITS, 2 * _PIECE_BITS, _BIAS)):
        O = jnp.where((blk == 0) & (sub == i), float(1 << e), O)
    out = jax.lax.dot_general(
        W, O.astype(jnp.bfloat16), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # [R, 3C]
    return (jax.lax.bitcast_convert_type(out[:, 0:C], jnp.int32),
            out[:, C:2 * C], out[:, 2 * C:3 * C])


def _route_rows(x, w_ref, shift: int, stg_ref, emit, *, R: int):
    """Move the rows of the [R, C] block ``x`` through the butterfly
    that the lane-replicated word in ``w_ref`` (bits [shift, shift +
    log2 R) of it) describes, and hand every finished vreg to
    ``emit(v, rows8)``.

    Staged so that a vreg is loaded and stored once for every THREE
    rounds, not once a round: a stage takes the 2**m vregs whose
    indices differ in its m <= 3 bits, runs those rounds on them in
    registers and puts them down again (``stg_ref`` [2, R, C] between
    stages).  Stage 0 also does the in-vreg rounds, as one sublane
    gather a vreg."""
    nv = (R // _SUB).bit_length() - 1       # index bits of a vreg
    chunks = [range(b, min(b + 3, nv)) for b in range(0, nv, 3)] or [()]
    for s, bits in enumerate(chunks):
        m, b0 = len(bits), (bits[0] if bits else 0)
        last = s == len(chunks) - 1
        for g in range(R // _SUB >> m):
            # the stage's groups: vreg bits [b0, b0 + m) free, rest = g
            base = (g >> b0 << b0 + m) | (g & (1 << b0) - 1)
            vs = [base | c << b0 for c in range(1 << m)]
            wg = jnp.concatenate([w_ref[_vreg(v)] for v in vs], axis=0)
            if s == 0:
                idx = jnp.bitwise_and(
                    jnp.right_shift(wg, shift) if shift else wg, _SUB - 1)
                yg = jnp.concatenate(
                    [jnp.take_along_axis(
                        x[_vreg(v)], idx[_vreg(c)], axis=0,
                        mode="promise_in_bounds")
                     for c, v in enumerate(vs)], axis=0)
            else:
                yg = jnp.concatenate(
                    [stg_ref[(s - 1) % 2, _vreg(v)] for v in vs], axis=0)
            for i, b in enumerate(bits):
                k = _SUB << b
                yg = jnp.where(jnp.bitwise_and(wg, k << shift) > 0,
                               _xchg_rows(yg, _SUB << i), yg)
            for c, v in enumerate(vs):
                if last:
                    emit(v, yg[_vreg(c)])
                else:
                    stg_ref[s % 2, _vreg(v)] = yg[_vreg(c)]


def _pack_permute(x, sel_ref, cnt, blk, is_last, out_ref, *, R: int,
                  C: int):
    """Permutation packing for _scan_kernel's pack_impl hook: same
    output layout as _pack_matmul (left rows ascending at [loff,
    loff + nl), right rows REVERSED at [R - nr, R)) with O(log R)
    routing per row instead of the [R, R] one-hot contraction.

    Everything that is one number a row - the split column, go-left
    flags, prefix positions, destinations and the nine rounds' masks -
    is computed in LANE orientation ([K, R]: R / 128 vregs an
    operation, where a [R, 1] column costs R / 8) and crosses to row
    orientation once, through the MXU (_rows_from_lanes).  Writes the
    packed block to ``out_ref`` and returns ``(nl, nr, (flag_l,
    flag_r))``; the flags are the [R, C] lane-replicated f32 go-left /
    go-right bits, for the scan's ``block_cb``."""
    nb = R.bit_length() - 1
    lane = _lane_iota(R)
    e_col = (jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
             == sel_ref[SEL_FEAT]).astype(jnp.float32)
    col = jax.lax.dot_general(
        e_col, x.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # [1, R]
    valid = lane < (cnt - blk * R)
    gl = _go_left(col, sel_ref) & valid
    gr = jnp.logical_xor(gl, valid)
    glf = gl.astype(jnp.float32)
    grf = gr.astype(jnp.float32)
    nl = jnp.sum(glf).astype(jnp.int32)
    nr = jnp.sum(grf).astype(jnp.int32)
    f2 = jnp.concatenate([glf, grf], axis=0)             # [2, R]
    pos = (_prefix_lanes(f2, R=R) - f2).astype(jnp.int32)
    # last block: left tail directly below the right zone
    loff = jnp.where(is_last, R - nr - nl, 0)
    left = jax.lax.broadcasted_iota(jnp.int32, (2, R), 0) == 0
    dst = jnp.where(left, loff + pos, (R - 1) - pos)
    word = _route_words(f2 > 0, dst, R=R)
    # both sides in one word: left at bits [0, nb), right at [nb, 2nb)
    w, flag_l, flag_r = _rows_from_lanes(
        jnp.bitwise_or(word[0:1], jnp.left_shift(word[1:2], nb)),
        glf, grf, R=R, C=C)

    def _route(w_ref, stg_ref):
        w_ref[...] = w

        def _left(v, piece):          # left at [loff, loff + nl)
            out_ref[_vreg(v)] = piece

        def _right(v, piece):         # right REVERSED at [R - nr, R)
            row = v * _SUB + jax.lax.broadcasted_iota(
                jnp.int32, (_SUB, C), 0)
            out_ref[_vreg(v)] = jnp.where(row >= R - nr, piece,
                                          out_ref[_vreg(v)])

        _route_rows(x, w_ref, 0, stg_ref, _left, R=R)
        _route_rows(x, w_ref, nb, stg_ref, _right, R=R)

    pl.run_scoped(_route, pltpu.VMEM((R, C), jnp.int32),
                  pltpu.VMEM((2, R, C), x.dtype))
    return nl, nr, (flag_l, flag_r)


def perm_pack_impl(R: int, C: int):
    """The validated permute ``pack_impl`` for the shared scan
    schedule — single home for the power-of-two precondition, used by
    make_partition_perm AND fused_split.make_fused_split so the fused
    and unfused paths cannot diverge on it."""
    if R & (R - 1) or not _SUB <= R <= 1 << (_BIAS - 1) // 2:
        # both sides' log2(R)-bit routing words share one biased word
        raise ValueError(
            f"permutation packing needs a power-of-two block size "
            f"in [{_SUB}, {1 << (_BIAS - 1) // 2}] (got R={R}); use "
            f"LGBM_TPU_PART_R or LGBM_TPU_PARTITION=matmul")
    return functools.partial(_pack_permute, R=R, C=C)


def make_partition_perm(n: int, C: int, *, R: int = 512, size: int = 0,
                        dtype=jnp.float32, interpret: bool = False,
                        dynamic: bool = False, cb_block: int = 2048,
                        interpret_kernel: bool = False):
    """Permutation-scheme single-scan partition: signature/contract
    identical to partition_kernel2.make_partition_ss (the two differ
    only in the per-block packing implementation plugged into the
    shared scan schedule).  ``LGBM_TPU_PARTITION=permute`` routes grow
    here; ``matmul`` keeps the one-hot scheme for bisection."""
    check_lane_width(C, dtype)
    return make_partition_ss(
        n, C, R=R, size=size, dtype=dtype, interpret=interpret,
        dynamic=dynamic, cb_block=cb_block,
        pack_impl=perm_pack_impl(R, C),
        interpret_kernel=interpret_kernel)


# ---------------------------------------------------------------------------
# pack=2: two logical rows per 128-lane line (layout.comb_layout pack=2).
#
# The same bit-serial routing runs in the LOGICAL row domain: a logical
# shift by 1 is a lane rotate by 64 composed with a 1-line sublane
# carry, every even shift is a plain physical-line roll.  Cursor parity
# (segment starts / nl / nr are counted in logical rows, DMA moves
# whole 128-lane lines) is absorbed by one dynamic logical roll of the
# packed buffer per write plus a one-line VMEM carry re-merging the
# half-line the previous write left at the window boundary; the scan's
# _fin flushes both carries so the copyback sees fully materialised
# boundary lines.  All safety arguments are the logical-domain versions
# of partition_kernel2's (window starts round DOWN by at most one
# logical row into already-written data, rewritten idempotently from
# the carry; window ends never grow past the pack=1 bounds).
# ---------------------------------------------------------------------------


def _lane_swap(y):
    """Swap the two 64-lane halves of every line."""
    return pltpu.roll(y, PACK_W, 1)


def _lroll_fwd1(y, *, P: int):
    """Logical forward roll by 1 on a [P, 128] packed buffer:
    z[l] = y[l - 1] (logical index l = 2*line + lane_half)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    w = _lane_swap(y)
    return jnp.where(lane < PACK_W, pltpu.roll(w, 1, 0), w)


def _lroll_bwd1(y, *, P: int):
    """Logical backward roll by 1: z[l] = y[l + 1]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    w = _lane_swap(y)
    return jnp.where(lane < PACK_W, w, pltpu.roll(w, P - 1, 0))


def _lroll_fwd_dyn(y, s, *, P: int):
    """Logical forward roll by a TRACED non-negative amount s: one
    dynamic physical roll (s // 2) plus a selected odd step."""
    even = pltpu.roll(y, jax.lax.div(s, 2), 0)
    return jnp.where(jax.lax.rem(s, 2) == 1, _lroll_fwd1(even, P=P),
                     even)


def _pk2_mask(mA, mB):
    """Combine per-half [P, 1] masks into a [P, 128] lane-half mask."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (mA.shape[0], LANE), 1)
    return jnp.where(lane < PACK_W, mA, mB)


def _compact_logical(y, dA, dB, *, R: int, P: int):
    """Route logical rows backward to ``dst = r - d[r]`` with LSB-first
    bit-serial ROTATE routing (round k moves every row whose remaining
    displacement has bit k set by k rows); displacements carried as an
    [P, 1] i32 pair (half A / half B of each line), 0 for garbage rows.
    For a strict compaction (destinations strictly increasing over
    kept rows, displacement non-decreasing) it is collision-free and
    order-preserving."""
    k = 1
    while k < R:
        if k == 1:
            yr = _lroll_bwd1(y, P=P)
            drA, drB = dB, pltpu.roll(dA, P - 1, 0)
        else:
            yr = pltpu.roll(y, P - k // 2, 0)
            drA = pltpu.roll(dA, P - k // 2, 0)
            drB = pltpu.roll(dB, P - k // 2, 0)
        arrA = jnp.bitwise_and(drA, k) > 0
        arrB = jnp.bitwise_and(drB, k) > 0
        y = jnp.where(_pk2_mask(arrA, arrB), yr, y)
        dA = jnp.where(arrA, drA - k,
                       jnp.where(jnp.bitwise_and(dA, k) > 0, 0, dA))
        dB = jnp.where(arrB, drB - k,
                       jnp.where(jnp.bitwise_and(dB, k) > 0, 0, dB))
        k *= 2
    return y


def _reverse_logical(y, *, P: int):
    """Full logical reversal: bit 0 is the lane-half swap, the
    remaining bits are the physical-line reversal."""
    return _reverse_rows(_lane_swap(y), R=P)


def _pack_permute2(x, sel_ref, cnt, blk, is_last, par0, *, R: int):
    """pack=2 block compaction: x is [P, 128] physical lines holding R
    = 2P logical rows; block b covers GLOBAL logical rows
    [s0 - par0 + b*R, ... + R).  Output layout in the logical domain
    matches _pack_permute: left rows ascending at [loff, loff + nl),
    right rows REVERSED at [R - nr, R)."""
    P = R // 2
    # one-hot pair extracting the split column of BOTH lane halves in
    # one matmul (2-D iotas only — Mosaic rejects 1-D)
    lane2 = jax.lax.broadcasted_iota(jnp.int32, (LANE, 2), 0)
    half2 = jax.lax.broadcasted_iota(jnp.int32, (LANE, 2), 1)
    e2 = (lane2 == sel_ref[SEL_FEAT] + half2 * PACK_W
          ).astype(jnp.float32)                           # [128, 2]
    col2 = jax.lax.dot_general(
        x.astype(jnp.float32), e2, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [P, 2]
    line = _row_iota(P)
    lA, lB = 2 * line, 2 * line + 1
    relA = blk * R + lA - par0
    relB = blk * R + lB - par0
    vA = (relA >= 0) & (relA < cnt)
    vB = (relB >= 0) & (relB < cnt)
    glA = _go_left(col2[:, 0:1], sel_ref) & vA
    glB = _go_left(col2[:, 1:2], sel_ref) & vB
    grA = jnp.logical_xor(glA, vA)
    grB = jnp.logical_xor(glB, vB)

    def side(gA, gB):
        fA = gA.astype(jnp.float32)
        fB = gB.astype(jnp.float32)
        s_line = fA + fB
        S = _prefix_rows(s_line, R=P)          # inclusive, per line
        eA = (S - s_line).astype(jnp.int32)    # exclusive prefix @ 2p
        eB = (S - fB).astype(jnp.int32)        # exclusive prefix @ 2p+1
        n = jnp.sum(s_line).astype(jnp.int32)
        dA = jnp.where(gA, lA - eA, 0)
        dB = jnp.where(gB, lB - eB, 0)
        return n, dA, dB

    nl, dlA, dlB = side(glA, glB)
    nr, drA, drB = side(grA, grB)
    yl = _compact_logical(x, dlA, dlB, R=R, P=P)
    yr = _reverse_logical(_compact_logical(x, drA, drB, R=R, P=P), P=P)
    loff = jnp.where(is_last, R - nr - nl, 0)
    yl = _lroll_fwd_dyn(yl, loff, P=P)
    mA = lA >= R - nr
    mB = lB >= R - nr
    packed = jnp.where(_pk2_mask(mA, mB), yr, yl)
    return packed.astype(x.dtype), nl, nr


def _extract_line(buf, idx, *, P: int):
    """Line ``idx`` (traced) of a [P, 128] buffer as [1, 128], via one
    dynamic rotate + static slice."""
    return pltpu.roll(buf, jnp.where(idx == 0, 0, P - idx), 0)[0:1, :]


def _scan_kernel_p2(sel_ref, rows_in, scratch_in,
                    rows_ref, scratch_ref, out_ref,
                    vx0, vx1, skl0, skl1, skr0, skr1,
                    carry_l, carry_r, cursor,
                    sem_r, sem_wl, sem_wr,
                    *, R: int, init_cb=None, block_cb=None):
    """pack=2 single-scan partition: same phases/cursors/out contract
    as partition_kernel2._scan_kernel with all row accounting in
    LOGICAL rows and all DMA in whole 128-lane physical lines (P = R/2
    lines per block; see the pack=2 section of the module docstring
    for the parity-carry scheme).  rows/scratch are [n_phys, 128] with
    n_phys = n_logical / 2.

    ``init_cb()`` / ``block_cb(x, blk, cnt, par0)`` mirror
    partition_kernel2._scan_kernel's trace-time extension hooks
    (fused_split's pack=2 histogram accumulation): init_cb runs in
    the blk == 0 init, block_cb sees each live block's [P, 128] packed
    lines right after the read wait.  The extra ``par0`` operand is the
    segment-start parity the hook needs to place logical rows.  Hooks
    must not touch the DMA/cursor state."""
    P = R // 2
    P1 = P + 1
    blk = pl.program_id(0)
    s0 = sel_ref[SEL_S0]
    cnt = sel_ref[SEL_CNT]
    par0 = jax.lax.rem(s0, 2)
    nb_live = (cnt + par0 + R - 1) // R
    lane = jax.lax.broadcasted_iota(jnp.int32, (P1, LANE), 1)
    line = jax.lax.broadcasted_iota(jnp.int32, (P1, LANE), 0)

    @pl.when(blk == 0)
    def _init0():
        cursor[_CUR_L] = s0
        cursor[_CUR_TL] = 0
        cursor[_CUR_R] = s0 + (nb_live + 1) * R
        out_ref[0] = 0
        out_ref[1] = 0
        carry_l[...] = jnp.zeros_like(carry_l)
        carry_r[...] = jnp.zeros_like(carry_r)
        if init_cb is not None:
            init_cb()

    @pl.when(blk < nb_live)
    def _scan():
        startp = s0 // 2 + blk * P
        is_last = blk == nb_live - 1

        @pl.when(blk == 0)
        def _prime():
            pltpu.make_async_copy(
                rows_in.at[pl.ds(startp, P)], vx0, sem_r.at[0]).start()

        parity = jax.lax.rem(blk, 2)

        def _do(vx_cur, vx_next, skl, skr, cur_slot, nxt_slot):
            pltpu.make_async_copy(
                rows_in.at[pl.ds(startp, P)], vx_cur,
                sem_r.at[cur_slot]).wait()

            @pl.when(blk == 0)
            def _carry0():
                # first left write's boundary line: rows' own content
                # at line s0 // 2 (half A holds the NEIGHBOUR leaf's
                # row when s0 is odd — it must survive verbatim)
                carry_l[...] = vx_cur[0:1, :]

            @pl.when(blk + 1 < nb_live)
            def _ra():
                pltpu.make_async_copy(
                    rows_in.at[pl.ds(startp + P, P)], vx_next,
                    sem_r.at[nxt_slot]).start()

            x = vx_cur[:]
            packed, nl, nr = _pack_permute2(
                x, sel_ref, cnt, blk, is_last, par0, R=R)
            if block_cb is not None:
                block_cb(x, blk, cnt, par0)
            zline = jnp.zeros((1, LANE), packed.dtype)

            # ---- left write (skipped on the last block) ----
            cur_l = cursor[_CUR_L]
            par = jax.lax.rem(cur_l, 2)
            base_l = jnp.concatenate([packed, zline], axis=0)  # [P1]
            sl = jnp.where(par == 1, _lroll_fwd1(base_l, P=P1), base_l)
            sl = jnp.where((line == 0) & (lane < PACK_W) & (par == 1),
                           carry_l[0:1, :], sl)
            skl[:] = sl

            @pl.when(blk > 0)
            def _wl_wait():
                pltpu.make_async_copy(skl0, skl0, sem_wl).wait()

            @pl.when(jnp.logical_not(is_last))
            def _wl_go():
                pltpu.make_async_copy(
                    skl.at[pl.ds(0, P)],
                    rows_ref.at[pl.ds(cur_l // 2, P)], sem_wl).start()
                cursor[_CUR_L] = cur_l + nl
                # boundary line for the NEXT left write / final flush
                carry_l[...] = _extract_line(sl, (nl + par) // 2, P=P1)

            @pl.when(is_last)
            def _wl_last():
                cursor[_CUR_TL] = nl

            # ---- right write (descending; includes the left tail on
            # the last block via packed's loff placement) ----
            cur_r = cursor[_CUR_R]
            par_r = jax.lax.rem(cur_r, 2)
            base_r = jnp.concatenate([zline, packed], axis=0)  # [P1]
            sr = jnp.where(par_r == 1, _lroll_bwd1(base_r, P=P1), base_r)
            sr = jnp.where((line == P1 - 1) & (lane >= PACK_W)
                           & (par_r == 1), carry_r[0:1, :], sr)
            skr[:] = sr

            @pl.when(blk > 0)
            def _wr_wait():
                pltpu.make_async_copy(skr0, skr0, sem_wr).wait()

            wt = (cur_r + par_r) // 2
            pltpu.make_async_copy(
                skr.at[pl.ds(1, P)],
                scratch_ref.at[pl.ds(wt - P, P)], sem_wr).start()
            nr_eff = nr + jnp.where(is_last, nl, 0)
            bv = cur_r - nr_eff

            @pl.when(nr_eff > 0)
            def _carry_r_upd():
                carry_r[...] = _extract_line(
                    sr, bv // 2 - (wt - P1), P=P1)

            cursor[_CUR_R] = cur_r - nr

        @pl.when(parity == 0)
        def _even():
            _do(vx0, vx1, skl0, skr0, 0, 1)

        @pl.when(parity == 1)
        def _odd():
            _do(vx1, vx0, skl1, skr1, 1, 0)

    @pl.when((blk == nb_live - 1) & (nb_live > 0))
    def _fin():
        pltpu.make_async_copy(skr0, skr0, sem_wr).wait()
        tl = cursor[_CUR_TL]
        cur_l = cursor[_CUR_L]
        cur_r = cursor[_CUR_R]
        # flush the boundary carries: each target line's in-span half
        # is rewritten by the copyback, its out-of-span half holds the
        # carry's preserved content — idempotent in every parity case
        cpl = pltpu.make_async_copy(
            carry_l, rows_ref.at[pl.ds(cur_l // 2, 1)], sem_wl)
        cpl.start()
        cpl.wait()
        cpr = pltpu.make_async_copy(
            carry_r, scratch_ref.at[pl.ds((cur_r - tl) // 2, 1)],
            sem_wr)
        cpr.start()
        cpr.wait()
        out_ref[0] = cur_l - s0 + tl
        out_ref[1] = tl + (s0 + (nb_live + 1) * R - cur_r)


def _copyback_kernel_p2(sel_ref, scratch_in, rows_in, rows_ref,
                        va, vb, sem, *, CBP: int):
    """pack=2 copyback: move the logical span scratch[src0, src0 + m)
    to rows[dst0, dst0 + m).  The relative shift's parity re-splices
    every line (lane-half recombination across a CBP+1-line read
    window); every block read-merges rows' own content so both span
    boundaries and the garbage halves land exactly.  sel: [src0, dst0,
    m] in LOGICAL rows."""
    CB1 = CBP + 1
    blk = pl.program_id(0)
    src0, dst0, m = sel_ref[0], sel_ref[1], sel_ref[2]
    par_d = jnp.bitwise_and(dst0, 1)

    @pl.when(blk * 2 * CBP < m + par_d)
    def _go():
        dw = dst0 // 2 + blk * CBP
        delta = dst0 - src0
        q = jnp.bitwise_and(delta, 1)
        slp = (2 * dw - delta - q) // 2
        cpa = pltpu.make_async_copy(
            scratch_in.at[pl.ds(slp, CB1)], va, sem)
        cpa.start()
        cpa.wait()
        cpb = pltpu.make_async_copy(
            rows_in.at[pl.ds(dw, CBP)], vb, sem)
        cpb.start()
        cpb.wait()
        w = _lane_swap(va[:])
        lane = jax.lax.broadcasted_iota(jnp.int32, (CBP, LANE), 1)
        odd = jnp.where(lane < PACK_W, w[:CBP],
                        pltpu.roll(w, CB1 - 1, 0)[:CBP])
        out = jnp.where(q == 1, odd, va[:CBP])
        lineg = dw + jax.lax.broadcasted_iota(jnp.int32, (CBP, 1), 0)
        ga = 2 * lineg
        live_a = (ga >= dst0) & (ga < dst0 + m)
        live_b = (ga + 1 >= dst0) & (ga + 1 < dst0 + m)
        vb[:] = jnp.where(_pk2_mask(live_a, live_b), out, vb[:])
        cpo = pltpu.make_async_copy(
            vb, rows_ref.at[pl.ds(dw, CBP)], sem)
        cpo.start()
        cpo.wait()


def copyback_call_p2(sel, rows1, scratch1, nleft, m, *, R: int,
                     cb_block: int, n: int, dtype,
                     interpret: bool = False):
    """pack=2 twin of copyback_call: same span math in logical rows,
    physical-line grid sized for the parity spill."""
    cbp = max(cb_block // 2, 8)
    cb_kern = functools.partial(_copyback_kernel_p2, CBP=cbp)
    cnt = sel[SEL_CNT]
    par0 = jax.lax.rem(sel[SEL_S0], 2)
    tl = m - (cnt - nleft)
    nb_live = jnp.maximum(-(-(cnt + par0) // R), 0)
    t = sel[SEL_S0] + (nb_live + 1) * R
    sel_cb = jnp.stack(
        [t - m, sel[SEL_S0] + nleft - tl, m]).astype(jnp.int32)
    nb_cb = jnp.maximum(-(-(m + 2) // (2 * cbp)), 1)
    np_phys = n // 2
    return pl.pallas_call(
        cb_kern,
        name="lgbm_copyback",
        grid=(nb_cb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=_HBM),
                  pl.BlockSpec(memory_space=_HBM)],
        out_specs=pl.BlockSpec(memory_space=_HBM),
        out_shape=jax.ShapeDtypeStruct((np_phys, LANE), dtype),
        scratch_shapes=[pltpu.VMEM((cbp + 1, LANE), dtype),
                        pltpu.VMEM((cbp, LANE), dtype),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(sel_cb, scratch1, rows1)


def _emulate_partition_p2(n: int, R: int, dtype):
    """Pure-XLA pack=2 reference: unpack to one-row-per-line, run the
    stable 3-phase emulation, repack.  Segment membership/counts match
    the kernel; intra-segment ORDER does not (emulation is stable, the
    kernel reverses the right segment) — same contract as pack=1."""
    from .partition_kernel import make_partition as _mk3
    np_phys = n // 2
    part = _mk3(n, LANE, R=R, size=n, dtype=dtype, interpret=True)

    def partition(sel, rows, scratch, *_gb):
        # extra grid-blocks arg (dynamic callers) is irrelevant here:
        # the emulation always covers the full static range
        unp = rows.reshape(np_phys * 2, PACK_W)
        unp = jnp.concatenate(
            [unp, jnp.zeros_like(unp)], axis=1)        # [n, 128]
        out, _, nleft = part(sel, unp, jnp.zeros_like(unp))
        return (out[:, :PACK_W].reshape(np_phys, LANE).astype(dtype),
                scratch, nleft)

    return partition


def make_partition_p2(n: int, *, R: int = 512, size: int = 0,
                      dtype=jnp.float32, interpret: bool = False,
                      dynamic: bool = False, cb_block: int = 2048,
                      interpret_kernel: bool = False):
    """pack=2 permutation partition over a PACKED [n // 2, 128] row
    matrix holding ``n`` logical rows of <= 64 columns each (layout
    ``comb_layout(..., pack=2)``).  Contract mirrors make_partition_ss
    with all of sel / size / nleft in LOGICAL rows; partition DMA bytes
    per logical row are HALVED.  ``dynamic=True`` sizes the scan grid
    from a traced ``grid_blocks`` argument (pass >= ceil((cnt + 1) / R)
    to cover the head-parity spill block).

    Routing is ALWAYS the permutation scheme (the only pack=2 packing);
    trained paths under ``LGBM_TPU_PARTITION=matmul`` still match
    bit-for-bit because both pack=1 schemes produce the identical
    layout this kernel reproduces in the logical domain."""
    check_lane_width(LANE, dtype)
    if n % 2 or R % 2:
        raise ValueError(f"pack=2 needs even n and R (got {n}, {R})")
    if R & (R - 1):
        raise ValueError(f"pack=2 routing needs power-of-two R={R}")
    if interpret and not interpret_kernel:
        return _emulate_partition_p2(n, R, dtype)
    if interpret_kernel and dynamic:
        raise ValueError(
            "interpret_kernel supports static grids only (the Pallas "
            "interpreter cannot run a traced grid bound)")
    P = R // 2
    np_phys = n // 2
    nblocks = max((size + R - 1) // R + 1, 1)  # +1: head-parity spill
    kern = functools.partial(_scan_kernel_p2, R=R)

    def _call(sel, rows, scratch, grid_blocks):
        rows1, scratch1, res = pl.pallas_call(
            kern,
            grid=(grid_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=_HBM),
                      pl.BlockSpec(memory_space=_HBM)],
            out_specs=[pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=_HBM),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=[jax.ShapeDtypeStruct((np_phys, LANE), dtype),
                       jax.ShapeDtypeStruct((np_phys, LANE), dtype),
                       jax.ShapeDtypeStruct((2,), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((P, LANE), dtype),
                            pltpu.VMEM((P, LANE), dtype),
                            pltpu.VMEM((P + 1, LANE), dtype),
                            pltpu.VMEM((P + 1, LANE), dtype),
                            pltpu.VMEM((P + 1, LANE), dtype),
                            pltpu.VMEM((P + 1, LANE), dtype),
                            pltpu.VMEM((1, LANE), dtype),
                            pltpu.VMEM((1, LANE), dtype),
                            pltpu.SMEM((8,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            input_output_aliases={1: 0, 2: 1},
            interpret=interpret_kernel,
        )(sel, rows, scratch)
        rows2 = copyback_call_p2(
            sel, rows1, scratch1, res[0], res[1], R=R,
            cb_block=cb_block, n=n, dtype=dtype,
            interpret=interpret_kernel)
        return rows2, scratch1, res[0]

    if dynamic:
        def partition(sel, rows, scratch, grid_blocks):
            return _call(sel, rows, scratch, grid_blocks)
    else:
        def partition(sel, rows, scratch):
            return _call(sel, rows, scratch, nblocks)

    return partition


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import partition_args, register_kernel, sds


@register_kernel("partition_ss_permute", kind="partition",
                 note="single-scan kernel, butterfly-routing permutation "
                      "packing (the shipping default)")
def _analysis_partition_perm():
    n, C = 7168, 128
    return (make_partition_perm(n, C, R=512, size=2048),
            partition_args(n, C))


@register_kernel("partition_ss_permute_cat", kind="partition",
                 note="single-scan permute kernel, cat-subset bitset "
                      "sel (ISSUE 16)")
def _analysis_partition_perm_cat():
    from .layout import CAT_BITSET_WORDS
    n, C = 7168, 128
    return (make_partition_perm(n, C, R=512, size=2048),
            partition_args(n, C, sel_words=CAT_BITSET_WORDS))


@register_kernel("partition_p2", kind="partition", pack=2,
                 note="pack=2 scan + copyback over packed "
                      "[n//2, 128] lines (LGBM_TPU_COMB_PACK=2)")
def _analysis_partition_p2():
    n = 7168                   # logical rows
    fn = make_partition_p2(n, R=512, size=2048)
    return fn, (sds((8,), jnp.int32),
                sds((n // 2, LANE), jnp.float32),
                sds((n // 2, LANE), jnp.float32))


@register_kernel("partition_p2_cat", kind="partition", pack=2,
                 note="pack=2 scan + copyback, cat-subset bitset sel "
                      "(ISSUE 16)")
def _analysis_partition_p2_cat():
    from .layout import CAT_BITSET_WORDS
    n = 7168                   # logical rows
    fn = make_partition_p2(n, R=512, size=2048)
    return fn, (sds((8 + CAT_BITSET_WORDS,), jnp.int32),
                sds((n // 2, LANE), jnp.float32),
                sds((n // 2, LANE), jnp.float32))
