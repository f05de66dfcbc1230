"""Permutation partition scan (ISSUE 3): kernel-level contracts.

These tests run the REAL scan/copyback kernel bodies through the
Pallas interpreter (``interpret_kernel=True``) — manual DMAs, SMEM
cursors, aliased outputs and the packed row ORDER all behave as on
chip — and check them against a numpy oracle and against each other:

* permute vs matmul packing produce BIT-IDENTICAL row layouts (the
  cross-scheme tree-identity claim rests on this);
* left segments are stable, right segments exactly reversed, rows
  outside the partitioned range untouched;
* a comb line of two 128-lane planes (the width ``msltr-train-2m``
  runs) honours the same contract, across odd/even segment starts and
  counts, with the split column in the second plane;
* the 128-lane layout contract (ops/pallas/layout.py) rejects the
  BENCH_r03 regression class in EVERY kernel builder, off-chip.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.pallas.layout import LANE, check_lane_width, \
    comb_layout
from lightgbm_tpu.ops.pallas.partition_kernel import SEL_S0, SEL_CNT, \
    SEL_SIDE
from lightgbm_tpu.ops.pallas.partition_kernel2 import make_partition_ss
from lightgbm_tpu.ops.pallas.partition_kernel3 import make_partition_perm

R, C = 128, 128
SIZE = 1024
N = SIZE + 3 * R + 4096

# (s0, cnt, feat, sbin) corner configs: unaligned starts, odd counts,
# dead call, single row, all-left, full bucket
CONFIGS = [(64, 900, 3, 20), (0, 1024, 0, 31), (513, 1, 5, 10),
           (100, 0, 2, 5), (7, 777, 7, 0), (300, 512, 1, 63),
           (65, 401, 4, 15), (17, 1000, 6, 40)]


def _rows(n=N, c=C, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.zeros((n, c), np.float32)
    rows[:, :8] = rng.integers(0, 64, size=(n, 8))
    rows[:, 8] = rng.normal(size=n)        # arbitrary f32 payload: the
    rows[:, 9] = rng.random(size=n)        # permute scheme must move it
    return rows                            # bit-exactly (no MXU pass)


def _sel(s0, cnt, feat, sbin, side="right"):
    """``side``: the child the fused scan's hook histograms."""
    sel = np.zeros((8,), np.int32)
    sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = s0, cnt, feat, sbin
    sel[6] = -1
    sel[SEL_SIDE] = side == "left"
    return jnp.asarray(sel)


SIDES = ["left", "right"]


@pytest.mark.parametrize("cfg", CONFIGS)
def test_permute_matches_matmul_bitwise(cfg):
    """Same packed layout from both packing schemes, and both match
    the numpy oracle (stable left, fully reversed right)."""
    s0, cnt, feat, sbin = cfg
    rows = _rows()
    rj = jnp.asarray(rows)
    sel = _sel(*cfg)
    pm = make_partition_perm(N, C, R=R, size=SIZE, interpret=True,
                             interpret_kernel=True)
    mm = make_partition_ss(N, C, R=R, size=SIZE, interpret=True,
                           interpret_kernel=True)
    r_p, _, nl_p = pm(sel, rj, jnp.zeros_like(rj))
    r_m, _, nl_m = mm(sel, rj, jnp.zeros_like(rj))
    np.testing.assert_array_equal(np.asarray(r_p), np.asarray(r_m))
    seg = rows[s0:s0 + cnt]
    gl = seg[:, feat] <= sbin
    nl = int(nl_p)
    assert nl == int(nl_m) == int(gl.sum())
    out = np.asarray(r_p)
    np.testing.assert_array_equal(out[s0:s0 + nl], seg[gl])
    np.testing.assert_array_equal(out[s0 + nl:s0 + cnt], seg[~gl][::-1])
    np.testing.assert_array_equal(out[:s0], rows[:s0])
    np.testing.assert_array_equal(out[s0 + cnt:], rows[s0 + cnt:])


def _fuzz_rows(n, c, seed):
    """``_rows`` at any width: eight bin columns a plane, and f32
    payload the permute scheme must move bit-exactly."""
    rng = np.random.default_rng(seed)
    rows = np.zeros((n, c), np.float32)
    for lo in range(0, c, LANE):
        rows[:, lo:lo + 8] = rng.integers(0, 64, size=(n, 8))
    rows[:, 8] = rng.normal(size=n)
    rows[:, c - 1] = rng.random(size=n)
    return rows


# (block rows, lanes, bucket size, draws): the small block many times,
# and ONE draw at each block the chip runs - what scan_block_rows gives
# a one-plane comb (Higgs) and a two-plane comb (MS LTR) (ISSUE 37;
# the interpreter traces the unrolled routing, ~1 min a build at 2,048
# rows, so these two stay single)
FUZZ_BLOCKS = {
    "small_block": (R, C, SIZE, 6),
    "shipped_one_plane": (2048, C, 5000, 1),
    "shipped_two_planes": (1024, 2 * LANE, 2500, 1),
}


@pytest.mark.parametrize("case", sorted(FUZZ_BLOCKS))
def test_permute_routing_fuzz(case):
    """Randomized (s0, cnt, feat, sbin) sweep of the roll routing
    against the oracle — the collision-freedom argument, empirically —
    through the real scan + copyback kernels."""
    from lightgbm_tpu.ops.pallas.fused_split import scan_block_rows
    from lightgbm_tpu.ops.pallas.layout import to_planes, to_rows
    r, c, size, draws = FUZZ_BLOCKS[case]
    if case.startswith("shipped"):
        assert r == scan_block_rows(c)
    cb = 256
    n = size + 3 * r + 2 * cb + 64
    rng = np.random.default_rng(11)
    rows = _fuzz_rows(n, c, seed=5)
    rj = to_planes(jnp.asarray(rows))
    pm = make_partition_perm(n, c, R=r, size=size, interpret=True,
                             interpret_kernel=True, cb_block=cb)
    # s0 range respects the copyback slack contract: the tail copyback
    # block reads/writes [dst0, dst0 + cb_block) and dst0 < s0 + cnt
    for _ in range(draws):
        cnt = int(rng.integers(size // 2 if draws == 1 else 0, size + 1))
        s0 = int(rng.integers(0, n - size - 3 * r - 2 * cb))
        feat = int(rng.integers(0, 8)) + (c - LANE)  # in the last plane
        sbin = int(rng.integers(0, 64))
        r_p, _, nl_p = pm(_sel(s0, cnt, feat, sbin), rj,
                          jnp.zeros_like(rj))
        seg = rows[s0:s0 + cnt]
        gl = seg[:, feat] <= sbin
        nl = int(nl_p)
        assert nl == int(gl.sum()), (s0, cnt, feat, sbin)
        out = np.asarray(to_rows(r_p, c))
        np.testing.assert_array_equal(out[s0:s0 + nl], seg[gl])
        np.testing.assert_array_equal(out[s0 + nl:s0 + cnt],
                                      seg[~gl][::-1])
        np.testing.assert_array_equal(out[:s0], rows[:s0])
        np.testing.assert_array_equal(out[s0 + cnt:], rows[s0 + cnt:])


def test_permute_bf16_payload_exact():
    """bf16 blocks route exactly (selects/rotates move raw bits; no
    matmul precision constraint on the moved values)."""
    rng = np.random.default_rng(3)
    rows = np.zeros((N, C), np.float32)
    rows[:, :4] = rng.integers(0, 16, size=(N, 4))
    rows[:, 4] = rng.normal(size=N)
    rows_bf = jnp.asarray(rows).astype(jnp.bfloat16)
    pm = make_partition_perm(N, C, R=R, size=SIZE, dtype=jnp.bfloat16,
                             interpret=True, interpret_kernel=True)
    s0, cnt, feat, sbin = 40, 800, 2, 7
    r_p, _, nl_p = pm(_sel(s0, cnt, feat, sbin), rows_bf,
                      jnp.zeros_like(rows_bf))
    seg = np.asarray(rows_bf)[s0:s0 + cnt]
    gl = seg[:, feat] <= sbin
    nl = int(nl_p)
    assert nl == int(gl.sum())
    out = np.asarray(r_p)
    np.testing.assert_array_equal(out[s0:s0 + nl], seg[gl])
    np.testing.assert_array_equal(out[s0 + nl:s0 + cnt], seg[~gl][::-1])


@pytest.mark.parametrize("side", SIDES)
def test_fused_scan_selection_bitwise(side):
    """make_fused_split(scan=permute) partitions bit-identically to
    scan=matmul AND to the standalone kernels, with equal histograms of
    the named child (kernel-interpret composition)."""
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    rows = _rows()
    rj = jnp.asarray(rows)
    sel = _sel(64, 900, 3, 20, side)
    outs = {}
    for scan in ("permute", "matmul"):
        fused = make_fused_split(N, C, f_pad=32, padded_bins=64, R=R,
                                 size=SIZE, interpret=True, scan=scan,
                                 interpret_kernel=True)
        outs[scan] = fused(sel, rj, jnp.zeros_like(rj))
    # rows / nleft / the histogram must match bitwise; scratch (index
    # 1) is contractually don't-care between calls and its GARBAGE
    # regions differ by scheme (the matmul packs zeros into unoccupied
    # slots, the permute leaves stale copies)
    for i in (0, 2, 3):
        np.testing.assert_array_equal(np.asarray(outs["permute"][i]),
                                      np.asarray(outs["matmul"][i]))
    pm = make_partition_perm(N, C, R=R, size=SIZE, interpret=True,
                             interpret_kernel=True)
    r_p, _, nl_p = pm(sel, rj, jnp.zeros_like(rj))
    np.testing.assert_array_equal(np.asarray(outs["permute"][0]),
                                  np.asarray(r_p))
    assert int(outs["permute"][2]) == int(nl_p)


# ---------------------------------------------------------------------
# ISSUE 28: the permute compaction's row bookkeeping is LANE-dense
# (flags, prefix positions, destinations and the routing rounds on
# [K, R] arrays), crosses to row orientation once through the MXU, and
# hands its go-left bits to the fused scan's hook.  One block at a time,
# through the Pallas interpreter.
# ---------------------------------------------------------------------
def _go_left_np(col, sel):
    """numpy twin of partition_kernel._go_left."""
    from lightgbm_tpu.ops.pallas.partition_kernel import (
        SEL_CAT, SEL_DL, SEL_MEMBER, SEL_NANB, SEL_SBIN)
    col = np.asarray(col)
    at_nan = (sel[SEL_NANB] >= 0) & (col == sel[SEL_NANB])
    num = ((col <= sel[SEL_SBIN]) & ~at_nan) | (at_nan & (sel[SEL_DL] > 0))
    if len(sel) > SEL_MEMBER:
        b = col.astype(np.int64)
        words = np.asarray(sel[SEL_MEMBER:], np.int64)[b >> 5]
        cat = ((words >> (b & 31)) & 1) > 0
    else:
        cat = col == sel[SEL_SBIN]
    return cat if sel[SEL_CAT] > 0 else num


def _pack_block(x, sel, blk, is_last, r):
    """One call of _pack_permute on one [r, C] block: (packed, nl, nr,
    flag_l, flag_r)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from lightgbm_tpu.ops.pallas.partition_kernel3 import perm_pack_impl
    pack = perm_pack_impl(r, C)

    def kern(sel_ref, at_ref, x_ref, o_ref, n_ref, fl_ref, fr_ref):
        nl, nr, (fl, fr) = pack(x_ref[...], sel_ref, sel_ref[SEL_CNT],
                                at_ref[0], at_ref[1] > 0, o_ref)
        n_ref[0], n_ref[1] = nl, nr
        fl_ref[...], fr_ref[...] = fl, fr

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    blk_f32 = jax.ShapeDtypeStruct((r, C), jnp.float32)
    out = pl.pallas_call(
        kern, in_specs=[smem, smem, vmem],
        out_specs=[vmem, smem, vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct((r, C), x.dtype),
                   jax.ShapeDtypeStruct((2,), jnp.int32), blk_f32, blk_f32],
        interpret=True,
    )(jnp.asarray(sel, jnp.int32),
      jnp.asarray([blk, int(is_last)], jnp.int32), jnp.asarray(x))
    packed, n, fl, fr = (np.asarray(o) for o in out)
    return packed, int(n[0]), int(n[1]), fl, fr


# (r, cnt, blk, is_last, sbin): cnt counts from the segment start, the
# block holds rows [blk * r, (blk + 1) * r) of it
PACK_CASES = {
    "all_left": (128, 1024, 2, False, 63),
    "all_right": (128, 1024, 2, False, -1),
    "empty": (128, 256, 2, True, 20),
    "cnt_not_multiple_of_R": (128, 300, 2, True, 20),
    "last_block_loff": (128, 384, 2, True, 40),
    "last_block_all_right": (128, 300, 2, True, -1),
    "mid_block_mixed": (128, 1024, 1, False, 31),
    "real_block_size": (512, 1300, 2, True, 25),
    "three_stage_block": (1024, 2600, 2, True, 25),
    "one_vreg_block": (8, 21, 2, True, 25),
}


@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_pack_block_layout(case):
    """Left rows ascending at [loff, loff + nl) (loff = R - nr - nl on
    the last block, else 0), right rows reversed at [R - nr, R): the
    layout _pack_matmul produces, from the lane-dense bookkeeping."""
    r, cnt, blk, is_last, sbin = PACK_CASES[case]
    x = _rows(n=r, seed=len(case))
    sel = np.asarray(_sel(0, cnt, 3, sbin))
    packed, nl, nr, _, _ = _pack_block(x, sel, blk, is_last, r)
    valid = np.arange(r) < cnt - blk * r
    gl = _go_left_np(x[:, 3], sel) & valid
    gr = valid & ~gl
    assert (nl, nr) == (int(gl.sum()), int(gr.sum()))
    loff = r - nr - nl if is_last else 0
    np.testing.assert_array_equal(packed[loff:loff + nl], x[gl])
    np.testing.assert_array_equal(packed[r - nr:], x[gr][::-1])


# MiB of scoped stack the TPU compiler itself reports for the fused
# permute scan at (rows a step, lanes), read off-chip for the described
# v5e by lowering ``vmem_limit_bytes`` until it refuses (ISSUE 37,
# second session; PERF.md, Findings, PR 37)
CENSUS_STACK_MIB = {
    (512, 128): 2.966, (1024, 128): 6.046, (2048, 128): 12.416,
    (512, 256): 4.576, (1024, 256): 9.036, (2048, 256): 18.39,
    (512, 384): 6.546, (1024, 384): 12.036, (512, 512): 8.396,
    (512, 896): 14.586,
}


def test_scan_block_rows_is_one_function_of_width_and_vmem():
    """ISSUE 37: the rows a grid step of the scan moves come from the
    comb's width and the scoped VMEM a kernel gets - a power of two in
    [SCAN_ROWS_MIN, SCAN_ROWS_MAX] (down to SCAN_ROWS_WIDE past seven
    planes), never smaller for more VMEM or a
    narrower comb, 512 under the matmul compaction (O(R) a row), and
    what the compiled kernels were measured at: 2,048 rows at one
    plane, 1,024 at two.  The price bounds every reading the compiler
    gave of its own stack, and decides as the compiler did."""
    from lightgbm_tpu.ops.pallas.fused_split import (scan_block_rows,
                                                     scan_vmem_bytes)
    from lightgbm_tpu.ops.pallas.layout import (COMB_ROW_SLACK,
                                                COPYBACK_ROWS,
                                                SCAN_ROWS_MAX,
                                                SCAN_ROWS_MIN,
                                                SCAN_ROWS_WIDE,
                                                SCOPED_VMEM_LIMIT)
    assert SCOPED_VMEM_LIMIT == 16 * 2**20    # what a kernel gets unasked
    assert scan_block_rows(128) == 2048     # higgs, expo, data4
    assert scan_block_rows(256) == 1024     # msltr
    assert scan_block_rows(2048) == 128     # epsilon
    for (r, c), mib in CENSUS_STACK_MIB.items():
        price = scan_vmem_bytes(r, c) / 2**20
        assert mib <= price <= 1.25 * mib, (r, c, price)
        # all of these built under the default limit but 2,048 x 256
        assert (price <= 16) == (mib <= 16), (r, c)
    seen = set()
    for c in range(128, 2049, 128):
        prev = 0
        for limit in (2**20, 2**23, SCOPED_VMEM_LIMIT, 2**25, 2**26,
                      2**28):
            rr = scan_block_rows(c, vmem_limit=limit)
            assert SCAN_ROWS_WIDE <= rr <= SCAN_ROWS_MAX
            assert rr & (rr - 1) == 0
            # under 512 rows only where 512 do not fit
            assert rr >= SCAN_ROWS_MIN or scan_vmem_bytes(
                SCAN_ROWS_MIN, c) > limit
            assert rr >= prev                   # monotone in VMEM
            assert rr <= scan_block_rows(max(c - 128, 128),
                                         vmem_limit=limit)
            assert scan_block_rows(c, scheme="matmul",
                                   vmem_limit=limit) == 512
            prev = rr
            seen.add(rr)
    assert seen == {128, 256, 512, 1024, 2048}
    # what was sized before the block was known covers the largest
    assert COMB_ROW_SLACK >= 2 * SCAN_ROWS_MAX + COPYBACK_ROWS
    # a shard of a mesh builds its kernel from the same shapes: the
    # function has no other input
    assert {scan_block_rows(128) for _ in range(4)} == {2048}


@pytest.mark.parametrize("f_pad,b,c,ok", [
    (32, 256, 128, True),       # higgs
    (16, 256, 128, True),       # expo's bundle columns
    (144, 256, 256, True),      # msltr
    (10, 64, 128, False),       # no whole feature group: no hook
    (400, 256, 512, True),      # 50 groups: built at 8.40 MiB of stack
    (880, 256, 896, True),      # 110 groups: 14.59 MiB, the last plane
    (1000, 256, 1024, False),   # the price's 17.5 MiB is past the limit
])
def test_fused_supported_reads_the_scans_price(f_pad, b, c, ok):
    """ROADMAP C9: the predicate that decides the fused route charges
    what ``scan_block_rows`` charges, at the smallest block."""
    from lightgbm_tpu.ops.pallas.fused_split import (
        fused_supported, hook_acc_bytes, scan_vmem_bytes)
    from lightgbm_tpu.ops.pallas.layout import (SCAN_ROWS_MIN,
                                                SCOPED_VMEM_LIMIT)
    assert fused_supported(f_pad, b, c) is ok
    assert ok == bool(hook_acc_bytes(f_pad, b) and scan_vmem_bytes(
        SCAN_ROWS_MIN, c) <= SCOPED_VMEM_LIMIT)


@pytest.mark.parametrize("r", [4, 96, 4096])
def test_permute_block_size_bounds(r):
    """A power of two, at least one vreg of rows, and small enough for
    both sides' routing words to share the one biased f32 word."""
    from lightgbm_tpu.ops.pallas.partition_kernel3 import perm_pack_impl
    with pytest.raises(ValueError, match="power-of-two block size"):
        perm_pack_impl(r, C)


@pytest.mark.parametrize("pattern", ["all_left", "all_right", "empty",
                                     "partial", "random"])
def test_prefix_lanes_matches_cumsum(pattern):
    """The lane-oriented prefix (both sides scanned in one [2, R]
    array) against numpy.cumsum, at the real block size."""
    import jax
    from jax.experimental import pallas as pl
    from lightgbm_tpu.ops.pallas.partition_kernel3 import _prefix_lanes
    r = 512
    rng = np.random.default_rng(4)
    left = {"all_left": np.ones(r), "all_right": np.zeros(r),
            "empty": np.zeros(r), "partial": np.arange(r) % 3 == 0,
            "random": rng.random(r) < 0.4}[pattern].astype(bool)
    valid = {"empty": np.zeros(r, bool),
             "partial": np.arange(r) < 300}.get(pattern, np.ones(r, bool))
    f2 = np.stack([left & valid, ~left & valid]).astype(np.float32)

    def kern(f_ref, o_ref):
        o_ref[...] = _prefix_lanes(f_ref[...], R=r)

    out = pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((2, r), jnp.float32),
        interpret=True)(jnp.asarray(f2))
    np.testing.assert_array_equal(np.asarray(out), np.cumsum(f2, axis=1))


def _flag_sel(kind):
    from lightgbm_tpu.ops.pallas.layout import CAT_BITSET_WORDS
    from lightgbm_tpu.ops.pallas.partition_kernel import (
        SEL_CAT, SEL_DL, SEL_MEMBER, SEL_NANB)
    sel = np.zeros(8 + (CAT_BITSET_WORDS if kind == "cat_bitset" else 0),
                   np.int32)
    sel[SEL_CNT], sel[2], sel[3], sel[SEL_NANB] = 100, 5, 20, -1
    if kind.startswith("nan"):
        sel[SEL_NANB], sel[SEL_DL] = 63, int(kind == "nan_default_left")
    if kind.startswith("cat"):
        sel[SEL_CAT] = 1
    if kind == "cat_bitset":
        for b in (1, 7, 20, 33, 62):       # bins 33, 62: second word
            sel[SEL_MEMBER + b // 32] |= np.int32(1) << (b % 32)
    return sel


@pytest.mark.parametrize("kind", ["numerical", "nan_default_left",
                                  "nan_default_right", "cat_onehot",
                                  "cat_bitset"])
def test_hook_flags_match_recomputation(kind):
    """The go-left / go-right bits the compaction hands the fused
    scan's hook equal the hook's own row-oriented recomputation (the
    path the matmul compaction still takes), on every lane."""
    from lightgbm_tpu.ops.pallas.partition_kernel import _go_left
    r = 128
    x = _rows(n=r, seed=9)
    sel = _flag_sel(kind)
    _, nl, nr, fl, fr = _pack_block(x, sel, 0, True, r)
    # fused_split._hist_block's fallback: [R, 1] column, _go_left, valid
    valid = (np.arange(r) < sel[SEL_CNT])[:, None]
    gl2 = np.asarray(_go_left(jnp.asarray(x[:, 5:6]), jnp.asarray(sel))
                     ) & valid
    gr2 = valid & ~gl2
    assert gl2.any() and gr2.any(), "degenerate case"
    np.testing.assert_array_equal(gl2[:, 0], _go_left_np(x[:, 5], sel)
                                  & valid[:, 0])
    np.testing.assert_array_equal(fl, np.broadcast_to(gl2, (r, C)))
    np.testing.assert_array_equal(fr, np.broadcast_to(gr2, (r, C)))
    assert (nl, nr) == (int(gl2.sum()), int(gr2.sum()))


# (kind, side, planes): every predicate at one plane, and the cat
# bitset - the one the two-plane composition test below does not run -
# at two, its split column (133) in the second plane
_HOOK_CASES = [(k, s, 1) for k in ("numerical", "nan_default_left",
                                   "nan_default_right", "cat_onehot",
                                   "cat_bitset") for s in SIDES] \
    + [("cat_bitset", s, 2) for s in SIDES]


@pytest.mark.parametrize(
    "kind,side,planes", _HOOK_CASES,
    ids=[f"{s}-{k}" + ("-two_plane" if p == 2 else "")
         for k, s, p in _HOOK_CASES])
def test_fused_kernel_hook_takes_the_compactions_flags(kind, side,
                                                       planes):
    """The REAL fused scan + histogram kernel through the Pallas
    interpreter: under the permute compaction the hook masks with the
    named child's flags as the compaction hands them over, under the
    matmul one it recomputes them - same rows, same nleft and BITWISE
    the same histogram; and both agree with the reference composition
    (partition kernel + that child's comb histogram) to its
    accumulation-grouping tolerance."""
    import ml_dtypes
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    from lightgbm_tpu.ops.pallas.layout import to_planes
    f_pad, bins = (32, 64) if planes == 1 else (144, 64)
    c = planes * LANE
    rng = np.random.default_rng(7)
    rows = np.zeros((N, c), np.float32)
    rows[:, :f_pad] = rng.integers(0, bins, size=(N, f_pad))
    rows[:, f_pad:f_pad + 2] = rng.normal(size=(N, 2)).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    rj = to_planes(jnp.asarray(rows))
    sel = _flag_sel(kind)
    sel[SEL_S0], sel[SEL_CNT] = 64, 900
    sel[SEL_SIDE] = side == "left"
    if planes == 2:
        sel[2] = 133
    sel = jnp.asarray(sel)
    kw = dict(f_pad=f_pad, padded_bins=bins, R=R, size=SIZE)
    real = {scan: make_fused_split(N, c, scan=scan,
                                   fused_kernel_interpret=True, **kw)(
        sel, rj, jnp.zeros_like(rj)) for scan in ("permute", "matmul")}
    for i in (0, 2, 3):         # rows, nleft, h_side
        np.testing.assert_array_equal(np.asarray(real["permute"][i]),
                                      np.asarray(real["matmul"][i]))
    comp = make_fused_split(N, c, interpret=True, interpret_kernel=True,
                            hist_rpb=R, **kw)(sel, rj, jnp.zeros_like(rj))
    np.testing.assert_array_equal(np.asarray(real["permute"][0]),
                                  np.asarray(comp[0]))
    nleft = int(real["permute"][2])
    assert 0 < nleft == int(comp[2]) < 900
    assert np.abs(np.asarray(comp[3])).sum() > 0
    np.testing.assert_allclose(np.asarray(real["permute"][3]),
                               np.asarray(comp[3]), rtol=0, atol=1e-4)


# ISSUE 35: a third state of sel[SEL_SIDE], "no child": the hook is
# skipped inside the same kernel.  (kind, planes, scan): NaN and
# bitset-categorical splits, one and two planes, both compactions
_NO_CHILD_CASES = [(k, p, sc) for k in ("nan_default_left", "cat_bitset")
                   for p in (1, 2) for sc in ("permute", "matmul")]


@pytest.mark.parametrize(
    "kind,planes,scan", _NO_CHILD_CASES,
    ids=[f"{k}-{p}plane-{sc}" for k, p, sc in _NO_CHILD_CASES])
def test_fused_kernel_no_child_skips_only_the_hook(kind, planes, scan):
    """The REAL fused kernel through the Pallas interpreter, told
    SIDE_NONE: the rows, the scratch and nleft of the left and of the
    right call, bit for bit, and an all-zero histogram (the
    accumulator is still zeroed at block 0) - also as the raw
    accumulator, and in the composition the off-chip grow path runs."""
    import ml_dtypes
    from lightgbm_tpu.ops.pallas.fused_split import hook_histogram, \
        make_fused_split
    from lightgbm_tpu.ops.pallas.layout import to_planes
    from lightgbm_tpu.ops.pallas.partition_kernel import (
        SIDE_LEFT, SIDE_NONE, SIDE_RIGHT)
    f_pad, bins = (32, 64) if planes == 1 else (144, 64)
    c = planes * LANE
    rng = np.random.default_rng(13)
    rows = np.zeros((N, c), np.float32)
    rows[:, :f_pad] = rng.integers(0, bins, size=(N, f_pad))
    rows[:, f_pad:f_pad + 2] = rng.normal(size=(N, 2)).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    rj = to_planes(jnp.asarray(rows))
    kw = dict(f_pad=f_pad, padded_bins=bins, R=R, size=SIZE, scan=scan)
    fused = make_fused_split(N, c, fused_kernel_interpret=True, **kw)

    def call(fn, side):
        sel = _flag_sel(kind)
        sel[SEL_S0], sel[SEL_CNT], sel[SEL_SIDE] = 65, 901, side
        if planes == 2:
            sel[2] = 133
        return [np.asarray(o) for o in
                fn(jnp.asarray(sel), rj, jnp.zeros_like(rj))]

    none, left, right = (call(fused, sd) for sd in (SIDE_NONE, SIDE_LEFT,
                                                    SIDE_RIGHT))
    assert 0 < int(none[2]) < 901
    for other in (left, right):
        for i in (0, 1, 2):                    # rows, scratch, nleft
            np.testing.assert_array_equal(none[i], other[i])
        assert np.abs(other[3]).sum() > 0
    assert none[3].shape == (f_pad, bins, 2) and not none[3].any()
    if scan == "permute":
        # the raw accumulator, as ops/grow.py takes it: zeros at no
        # child, the histogram at a child
        raw = make_fused_split(N, c, fused_kernel_interpret=True,
                               raw_hist=True, **kw)
        acc_none, acc_left = call(raw, SIDE_NONE)[3], call(raw, SIDE_LEFT)[3]
        assert acc_none.ndim == 3 and not acc_none.any()
        np.testing.assert_array_equal(
            np.asarray(hook_histogram(jnp.asarray(acc_left), f_pad, bins)),
            left[3])
        comp = call(make_fused_split(N, c, interpret=True, hist_rpb=R,
                                     **kw), SIDE_NONE)
        assert int(comp[2]) == int(none[2]) and not comp[3].any()


# ---------------------------------------------------------------------
# ISSUE 29: a comb line wider than 128 lanes is stored plane-major and
# moves as one row DMA a plane.  The same scans, two planes, through the
# Pallas interpreter; the split column and the value columns sit in the
# SECOND plane.
# ---------------------------------------------------------------------
C2 = 2 * LANE


def _rows2(seed=0):
    rng = np.random.default_rng(seed)
    rows = np.zeros((N, C2), np.float32)
    rows[:, :8] = rng.integers(0, 64, size=(N, 8))
    rows[:, 130:138] = rng.integers(0, 64, size=(N, 8))
    rows[:, 140] = rng.normal(size=N)
    rows[:, 255] = rng.random(size=N)
    return rows


@pytest.mark.parametrize("cfg", [
    (64, 900, 131, 20), (0, 1024, 0, 31), (513, 1, 135, 10),
    (100, 0, 2, 5), (17, 1000, 137, 40),
    # the edge shapes: odd and even starts, cnt 0 / 1 / under a block /
    # odd, sbin 0 - every split column in the second plane
    (64, 400, 133, 15), (65, 401, 133, 15), (101, 333, 135, 7),
    (0, 512, 130, 16), (33, 64, 132, 0), (200, 0, 131, 9),
    (129, 1, 134, 31), (17, 511, 137, 30)])
def test_two_plane_scans_match_the_oracle(cfg):
    from lightgbm_tpu.ops.pallas.layout import comb_shape, to_planes, \
        to_rows
    s0, cnt, feat, sbin = cfg
    rows = _rows2()
    rj = to_planes(jnp.asarray(rows))
    assert rj.shape == comb_shape(N, C2) == (2 * N, LANE)
    np.testing.assert_array_equal(np.asarray(to_rows(rj, C2)), rows)
    sel = _sel(*cfg)
    out = {}
    for name, make in (("permute", make_partition_perm),
                       ("matmul", make_partition_ss)):
        fn = make(N, C2, R=R, size=SIZE, interpret=True,
                  interpret_kernel=True)
        r, _, nl = fn(sel, rj, jnp.zeros_like(rj))
        out[name] = (np.asarray(to_rows(r, C2)), int(nl))
    np.testing.assert_array_equal(out["permute"][0], out["matmul"][0])
    seg = rows[s0:s0 + cnt]
    gl = seg[:, feat] <= sbin
    got, nl = out["permute"]
    assert nl == out["matmul"][1] == int(gl.sum())
    np.testing.assert_array_equal(got[s0:s0 + nl], seg[gl])
    np.testing.assert_array_equal(got[s0 + nl:s0 + cnt], seg[~gl][::-1])
    np.testing.assert_array_equal(got[:s0], rows[:s0])
    np.testing.assert_array_equal(got[s0 + cnt:], rows[s0 + cnt:])
    # and the XLA emulation the off-chip grow path runs, on the same
    # plane-major array: same segments, stable order
    emu = make_partition_perm(N, C2, R=R, size=SIZE, interpret=True)
    r_e, _, nl_e = emu(sel, rj, jnp.zeros_like(rj))
    assert int(nl_e) == nl
    np.testing.assert_array_equal(
        np.asarray(to_rows(r_e, C2))[s0:s0 + cnt],
        np.concatenate([seg[gl], seg[~gl]]))


@pytest.mark.parametrize("side", SIDES)
def test_two_plane_fused_kernel_matches_the_composition(side):
    """The REAL fused scan + histogram at 144 feature columns (the MS
    LTR layout: bins through lane 143, values at 144-145, both planes
    live) against partition kernel + the named child's comb histogram."""
    import ml_dtypes
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    from lightgbm_tpu.ops.pallas.layout import to_planes
    f_pad, bins = 144, 64
    rng = np.random.default_rng(9)
    rows = np.zeros((N, C2), np.float32)
    rows[:, :f_pad] = rng.integers(0, bins, size=(N, f_pad))
    rows[:, f_pad:f_pad + 2] = rng.normal(size=(N, 2)).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    rj = to_planes(jnp.asarray(rows))
    sel = _sel(64, 900, 133, 30, side)
    kw = dict(f_pad=f_pad, padded_bins=bins, R=R, size=SIZE)
    real = {scan: make_fused_split(N, C2, scan=scan,
                                   fused_kernel_interpret=True, **kw)(
        sel, rj, jnp.zeros_like(rj)) for scan in ("permute", "matmul")}
    for i in (0, 2, 3):
        np.testing.assert_array_equal(np.asarray(real["permute"][i]),
                                      np.asarray(real["matmul"][i]))
    comp = make_fused_split(N, C2, interpret=True, interpret_kernel=True,
                            hist_rpb=R, **kw)(sel, rj, jnp.zeros_like(rj))
    np.testing.assert_array_equal(np.asarray(real["permute"][0]),
                                  np.asarray(comp[0]))
    nleft = int(real["permute"][2])
    assert 0 < nleft == int(comp[2]) < 900
    assert np.abs(np.asarray(comp[3])[128:]).sum() > 0       # plane 1
    np.testing.assert_allclose(np.asarray(real["permute"][3]),
                               np.asarray(comp[3]), rtol=0, atol=1e-4)


def test_two_plane_comb_histogram_matches_numpy():
    """The comb-direct histogram kernel over a plane-major comb of two
    planes (bins through lane 143, the values at 144-145) is BITWISE
    the numpy histogram of the window's rows: aligned, unaligned and
    odd windows and a dead (count == 0) call.  The values are small
    integers, so every grouping of the f32 sums is exact."""
    from lightgbm_tpu.ops.pallas.hist_kernel2 import build_histogram_comb
    from lightgbm_tpu.ops.pallas.layout import to_planes
    n_alloc, f_pad, bins = 2048 + 512, 144, 64
    rng = np.random.default_rng(0)
    rows = np.zeros((n_alloc, C2), np.float32)
    rows[:, :f_pad] = rng.integers(0, bins, size=(n_alloc, f_pad))
    rows[:, f_pad:f_pad + 2] = rng.integers(-8, 9, size=(n_alloc, 2))
    comb = to_planes(jnp.asarray(rows))
    for start, off, cnt in ((0, 0, 2048), (512, 0, 900), (513, 0, 901),
                            (77, 3, 333), (100, 0, 0)):
        got = build_histogram_comb(
            comb, jnp.int32(start), jnp.int32(off), jnp.int32(cnt),
            f_pad=f_pad, size=2048, padded_bins=bins,
            rows_per_block=256, interpret=True, planes=2)
        win = rows[start + off:start + off + cnt]
        want = np.zeros((f_pad, bins, 2), np.float32)
        for ch in range(2):
            for f in range(f_pad):
                want[f, :, ch] = np.bincount(
                    win[:, f].astype(np.int64),
                    weights=win[:, f_pad + ch], minlength=bins)
        np.testing.assert_array_equal(np.asarray(got), want)
        assert (np.abs(want[128:]).sum() > 0) == (cnt > 0)   # plane 1


class TestLaneContract:
    """Off-chip pin for the BENCH_r03 Mosaic regression class: every
    kernel column-slice/comb width in the repo must be a multiple of
    the 128-lane tile, enforced by each builder at trace time."""

    def test_layout_rules(self):
        for n_cols in (1, 41, 45, 64, 100, 128, 129, 300):
            assert comb_layout(n_cols) % LANE == 0
        # the exact round-3 snapshot config: 28 features padded to 32
        # + 13 stream columns at 64-lane granularity produced C=64;
        # the contract must yield 128
        assert comb_layout(45) == 128
        # and a line is whole planes at any width: 137 features + 6
        assert comb_layout(143) == comb_layout(129) == 256
        for bad in (64, 32, 127, 192 + 64):
            if bad % LANE == 0:
                continue
            with pytest.raises(ValueError):
                check_lane_width(bad)
        for ok in (128, 256, 512):
            assert check_lane_width(ok) == ok

    @pytest.mark.parametrize("bad_c", [64, 96])
    def test_every_kernel_builder_rejects_misaligned_widths(self, bad_c):
        """Each builder that DMA-slices comb rows raises off-chip for
        the widths that only Mosaic used to catch on-chip."""
        from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
        from lightgbm_tpu.ops.pallas.hist_kernel2 import \
            build_histogram_comb
        from lightgbm_tpu.ops.pallas.partition_kernel import \
            make_reference_partition
        from lightgbm_tpu.ops.pallas.stream_grad import make_init, \
            make_refresh

        with pytest.raises(ValueError):
            make_reference_partition(4096, bad_c)
        with pytest.raises(ValueError):
            make_partition_ss(4096, bad_c, size=1024)
        with pytest.raises(ValueError):
            make_partition_perm(4096, bad_c, R=128, size=1024)
        with pytest.raises(ValueError):
            make_fused_split(4096, bad_c, f_pad=32, padded_bins=64,
                             size=1024)
        with pytest.raises(ValueError):
            build_histogram_comb(
                jnp.zeros((4096, bad_c), jnp.float32), jnp.int32(0),
                jnp.int32(0), jnp.int32(8), f_pad=32, size=1024,
                padded_bins=64, interpret=True)
        with pytest.raises(ValueError):
            make_refresh(kind="l2", sigmoid=1.0, f=32, n_alloc=4096,
                         n_pad=2048, C=bad_c, R=512)
        with pytest.raises(ValueError):
            make_init(kind="l2", sigmoid=1.0, f_real=32, f=32,
                      n_alloc=4096, n_pad=2048, C=bad_c, R=512)

    def test_grow_layout_is_lane_aligned(self):
        """The grow-level layout decision (the code path the round-3
        snapshot broke) produces a 128-multiple for every physical
        feature width the device layer can emit."""
        from lightgbm_tpu.ops.pallas.stream_grad import stream_columns
        for f_pad in (8, 16, 28, 32, 64, 120, 128, 256):
            for extra in (6, stream_columns("binary"),
                          stream_columns("l2")):
                c = comb_layout(f_pad + extra)
                assert c % LANE == 0, (f_pad, extra, c)


@pytest.mark.parametrize("kind,stream", [("binary", True), ("l2", True),
                                         ("l2", False)])
def test_routing_and_grower_price_the_same_comb(kind, stream, monkeypatch):
    """ISSUE 37 (review): ``routing.resolve_layout`` hands
    ``fused_supported`` the width the grower will build its comb at -
    the stream layouts carry more columns than the non-stream one - so
    ``fused_ok`` and ``make_grow_fn``'s own decision agree where those
    extra columns cross into another plane at the edge of the price:
    888 bin columns make 7 planes off the stream route (the fused
    scan's last) and 8 on it (the unfused pair)."""
    import jax
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops import routing
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams
    f_pad, bins, n = 888, 256, 4096
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    inputs = routing.RouteInputs(
        backend="tpu", objective_kind=kind,
        stream_env="auto" if stream else "0")
    r = routing.resolve_layout(inputs, f_pad=f_pad, padded_bins=bins)
    assert (routing.decide(r).path == "stream") is stream
    gp = make_grow_fn(
        SplitHyperParams(min_data_in_leaf=20), num_leaves=15,
        padded_bins=bins, physical_bins=sds((n, f_pad), jnp.uint8),
        stream=({"kind": kind, "sigmoid": 1.0, "count": n}
                if stream else None))
    assert gp._C == (1024 if stream else 896)
    assert r.fused_ok is gp.fused is (not stream)


# ---------------------------------------------------------------------
# A comb line of sixteen planes (2,000 dense columns + the 13
# stream columns of the binary objective: 2,048 lanes, 8 KiB a line).
# Every kernel takes its block from its width: the scan 128 rows, the
# copy-back 512, the init / refresh 128, and the comb histogram sweeps
# one tile a plane.
# ---------------------------------------------------------------------
C16, F16 = 16 * LANE, 2000
N16 = 2048 + 512


def _rows16(seed=0):
    rng = np.random.default_rng(seed)
    rows = np.zeros((N16, C16), np.float32)
    rows[:, :F16] = rng.integers(0, 64, size=(N16, F16))
    rows[:, F16:F16 + 2] = rng.integers(-8, 9, size=(N16, 2))
    # the stream columns past the values: arbitrary f32 the tiled
    # histogram's last tile reads as bins of groups it drops
    rows[:, F16 + 2:] = rng.normal(size=(N16, C16 - F16 - 2)) * 100
    return rows


def test_every_comb_kernel_is_priced_under_its_limit_at_sixteen_planes():
    """The plan the benchmark asks for (``costmodel.comb_kernel_plan``):
    at 2,000 columns every comb kernel's block is priced under the limit
    it is compiled with; at one and two planes each kernel takes the
    block it took before sixteen were built."""
    from lightgbm_tpu.obs.costmodel import comb_kernel_plan
    plan = comb_kernel_plan(features=2000, max_bins=63)
    assert (plan["C"], plan["comb_planes"], plan["hist_tiles"]) == (
        2048, 16, 16)
    assert plan["stageable"]
    rows = {k: v["rows"] for k, v in plan["kernels"].items()}
    assert rows == {"scan": 128, "copyback": 512, "hist": 2048,
                    "stream": 128}
    for kern in plan["kernels"].values():
        assert kern["vmem_bytes"] <= kern["vmem_limit"] == 16 * 2**20
    for f, mb, stream, scan in ((28, 255, "binary", 2048),
                                (137, 255, None, 1024)):
        p = comb_kernel_plan(features=f, max_bins=mb, stream_kind=stream)
        assert p["hist_tiles"] == 1 and p["kernels"]["scan"]["rows"] == scan
        assert p["kernels"]["copyback"]["rows"] == 2048
        if stream:
            assert p["kernels"]["stream"]["rows"] == 512
    # past MAX_COMB_COLS nothing stages: the comb_overwide rule's fact
    assert not comb_kernel_plan(features=2100, max_bins=63)["stageable"]


@pytest.mark.parametrize("f_pad,window", [
    (256, (77, 3, 1333)), (528, (77, 3, 1333)), (F16, (77, 3, 1333)),
    (F16, (0, 0, 2048)), (F16, (100, 0, 0))])
def test_tiled_comb_histogram_is_the_untiled_one(f_pad, window,
                                                 monkeypatch):
    """Past two planes the comb-direct histogram sweeps one tile a plane
    (a grid axis over tiles, each tile's groups resident): BITWISE the
    one-tile kernel's and the numpy histogram of the window's rows, at
    widths both build (256 columns at 64 bins: three planes, the values
    in a plane of their own; 528: five planes) and at sixteen planes,
    where the last tile reads the stream columns as bins of the groups
    it drops."""
    from lightgbm_tpu.ops.pallas import hist_kernel2 as hk
    from lightgbm_tpu.ops.pallas.layout import to_planes
    c, bins = comb_layout(f_pad + 13), 64
    rows = _rows16()
    if c != C16:
        values = rows[:, F16:F16 + 2]
        rows = rows[:, :c].copy()
        rows[:, f_pad:] = 0.0
        rows[:, f_pad:f_pad + 2] = values
    comb = to_planes(jnp.asarray(rows))
    assert hk.hist_tiles(f_pad, c) == -(-f_pad // LANE) > 1
    start, off, cnt = window

    def hist():
        return np.asarray(hk.build_histogram_comb(
            comb, jnp.int32(start), jnp.int32(off), jnp.int32(cnt),
            f_pad=f_pad, size=2048, padded_bins=bins, rows_per_block=256,
            interpret=True, planes=c // LANE))

    tiled = hist()
    monkeypatch.setattr(hk, "hist_tiles", lambda *a: 1)
    np.testing.assert_array_equal(tiled, hist())
    win = rows[start + off:start + off + cnt]
    want = np.zeros((f_pad, bins, 2), np.float32)
    for ch in range(2):
        for f in range(f_pad):
            want[f, :, ch] = np.bincount(
                win[:, f].astype(np.int64), weights=win[:, f_pad + ch],
                minlength=bins)
    np.testing.assert_array_equal(tiled, want)


@pytest.mark.parametrize("cfg", [(64, 900, 1999, 20), (129, 1, 1024, 31)])
def test_sixteen_plane_scan_matches_the_oracle(cfg):
    """The scan and copy-back at the blocks a 2,048-lane comb takes
    (128 and 512 rows), through the Pallas interpreter: left segment
    stable, right reversed, rows outside untouched - split columns in
    the last plane over several blocks, and in a middle plane."""
    from lightgbm_tpu.ops.pallas.layout import to_planes, to_rows
    from lightgbm_tpu.ops.pallas.partition_kernel2 import (
        copyback_block_rows, scan_block_rows)
    r, cb = scan_block_rows(C16), copyback_block_rows(C16)
    assert (r, cb) == (128, 512)
    s0, cnt, feat, sbin = cfg
    rows = _rows16()
    rj = to_planes(jnp.asarray(rows))
    fn = make_partition_perm(N16, C16, R=r, size=SIZE, interpret=True,
                             interpret_kernel=True)
    out, _, nl = fn(_sel(*cfg), rj, jnp.zeros_like(rj))
    got = np.asarray(to_rows(out, C16))
    seg = rows[s0:s0 + cnt]
    gl = seg[:, feat] <= sbin
    assert int(nl) == int(gl.sum())
    np.testing.assert_array_equal(got[s0:s0 + int(nl)], seg[gl])
    np.testing.assert_array_equal(got[s0 + int(nl):s0 + cnt],
                                  seg[~gl][::-1])
    np.testing.assert_array_equal(got[:s0], rows[:s0])
    np.testing.assert_array_equal(got[s0 + cnt:], rows[s0 + cnt:])
