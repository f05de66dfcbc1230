"""Pallas TPU kernel: compute-light permutation packing for the
single-scan partition.

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
reloaded.  A [K, R] array (K <= 8) is R / 128 vregs.  Hence (ISSUE
28; 6.2k -> 1.5k VLIW bundles a block in the unfused scan, 11.0k ->
6.1k in the fused one, PERF.md section 5):

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
just equal multisets) at equal block rows (tests/test_partition_perm.py
through the interpreter).  On the chip the permute scan takes 1,024 -
2,048 rows a step and the matmul scan 512 (``scan_block_rows``), so a
leaf's rows lie in another order there and trees across
``LGBM_TPU_PARTITION=permute|matmul`` agree to f32 summation order,
not byte for byte.

Because rows move through selects and gathers - never through the MXU
- the permutation packing preserves ARBITRARY f32 column values
exactly; the matmul scheme's "columns must be bf16-exact" constraint
now binds only the histogram kernels.  dtype-agnostic in the
interpreter (the HBM-side (8,128)x2 bf16 tiling restriction on dynamic
row offsets still gates ``LGBM_TPU_COMB_DT=bf16``; see ops/grow.py).

The block schedule itself is NOT duplicated: ``_pack_permute`` plugs
into partition_kernel2's ``_scan_kernel`` through its ``pack_impl``
hook, so the DMA/cursor safety argument keeps exactly one home.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layout import COMB_ROW_SLACK, SCAN_ROWS_MIN, check_lane_width
from .partition_kernel import SEL_FEAT, _go_left
from .partition_kernel2 import make_partition_ss, scan_block_rows


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
            f"in [{_SUB}, {1 << (_BIAS - 1) // 2}] (got R={R}); "
            f"partition_kernel2.scan_block_rows gives one")
    return functools.partial(_pack_permute, R=R, C=C)


def make_partition_perm(n: int, C: int, *, R: int = SCAN_ROWS_MIN,
                        size: int = 0,
                        dtype=jnp.float32, interpret: bool = False,
                        dynamic: bool = False,
                        cb_block: int = 0,
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


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import partition_args, register_kernel


@register_kernel("partition_ss_permute", kind="partition",
                 note="single-scan kernel, butterfly-routing permutation "
                      "packing (the shipping default)")
def _analysis_partition_perm():
    n, C = 2048 + COMB_ROW_SLACK, 128
    return (make_partition_perm(n, C, R=scan_block_rows(C), size=2048),
            partition_args(n, C))


@register_kernel("partition_ss_permute_cat", kind="partition",
                 note="single-scan permute kernel, cat-subset bitset "
                      "sel (ISSUE 16)")
def _analysis_partition_perm_cat():
    from .layout import CAT_BITSET_WORDS
    n, C = 2048 + COMB_ROW_SLACK, 128
    return (make_partition_perm(n, C, R=scan_block_rows(C), size=2048),
            partition_args(n, C, sel_words=CAT_BITSET_WORDS))
