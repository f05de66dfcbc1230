"""Pallas TPU kernel: per-split "apply + find-best" consolidation.

After the bucket stage produces the smaller child's histogram, the rest of
a split is ~40 small XLA ops (the vmapped two-children split finder and ~8
dynamic row reads/writes of the packed grow state).  Executed op-by-op
inside the grow loop each costs ~5-40 us of serialized HBM<->SMEM staging
latency (see docs/PERF_NOTES.md) — more than the math.  This kernel runs
the whole tail as ONE program:

  * the split finder (reference FeatureHistogram::FindBestThreshold,
    feature_histogram.hpp:85,858 / cuda_best_split_finder.cu:209-263) runs
    on the vector core over both children at once: cumsum along bins via
    an f32-accurate bf16x3-decomposed tril matmul (the cumsum primitive
    doesn't lower in Mosaic, and a plain f32 tril matmul runs at bf16 on
    the MXU — see _cumsum_last), NaN-bin sums via a precomputed one-hot
    mask (take_along_axis doesn't lower either), candidate gains, masked
    flat argmax per child, and one-hot-of-argmax scalar extraction of the
    winning sums;
  * parent scalars arrive via a small SMEM vector (the select phase already
    read those rows); state-row writes are dynamic-index VMEM vector
    stores (SMEM cannot hold the [L, 10] state arrays — it is 1 MB total
    and each buffer pads to 128K there, which OOMed a first attempt);
  * all writes are guarded by the `done` flag (pl.when), matching the
    drop-guard semantics of the XLA tail.

Scope (the fast path): no EFB bundles, no voting/feature-parallel axes, no
forced splits, no CEGB/interaction constraints, no per-node column
sampling.  Monotone (basic method) and path smoothing ARE supported: the
constrained candidate path computes per-candidate clipped/smoothed
outputs, the sibling-order violation mask, given-output gains and the
midpoint child bounds in-kernel (GetSplitGains USE_MC/USE_SMOOTHING,
feature_histogram.hpp:786-824 + monotone_constraints.hpp:485-501).
make_grow_fn falls back to the XLA tail otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..split import SplitHyperParams
from .partition_kernel import _HBM

# sel_i layout (SMEM i32[8]); SEL_SMALL = smaller-child-is-left flag
# (pool-resident kernel only)
(SEL_LEAF, SEL_RIGHT, SEL_NODE, SEL_DONE, SEL_NLEFT, SEL_S0, SEL_PCNT,
 SEL_SMALL) = range(8)
# sel_f layout (SMEM f32[24]): best row [0:10], lstate row [10:18]

# Scoped-VMEM budget for the finder.  Measured needs (Mosaic's own OOM
# report, probed by compiling with a 1 MB limit): 20.40 MB at F*B=2048
# (4x512), 39.32 MB at 8192 (32x256), 39.13 MB at 8192 (16x512), 78.36 MB
# at 16384 (64x256) — affine in F*B, independent of B at fixed F*B and of
# L.  The limit below covers those points with 15-35% headroom.  Keep it
# tracking the need rather than blanket-large: the compiler packs other
# VMEM allocations around the scoped stack, and an over-generous limit
# squeezes them.
_VMEM_BASE = 14_000_000
_VMEM_PER_FB = 4800
_VMEM_CAP = 96 * 1024 * 1024


def vmem_limit_for(f: int, b: int) -> int:
    return _VMEM_BASE + _VMEM_PER_FB * f * b


def tail_supported(f: int, b: int) -> bool:
    """Whether the finder's footprint fits the safe scoped-VMEM cap; the
    grow loop falls back to the XLA tail above it.  Bin widths below one
    128-lane tile are also excluded: the finder's [2, F, B] -> [1, 2FB]
    flatten is an unsupported Mosaic shape cast when B % 128 != 0
    (observed at B=32: 'infer-vector-layout: unsupported shape cast')."""
    return vmem_limit_for(f, b) <= _VMEM_CAP and b % 128 == 0


def build_finder_consts(num_bins, has_nan, is_cat, padded_bins: int,
                        monotone=None):
    """[5, F, B] f32 mask tensors for the in-kernel finder (traced; built
    once per grow call from the dataset's bin metadata).

    0: valid0 — direction-0 candidates (numerical fwd merged w/ categorical)
    1: valid1 — direction-1 (missing-left) candidates
    2: nan_oh — one-hot of each feature's NaN bin (zero when !has_nan)
    3: catv   — is_cat broadcast over bins
    4: mono   — per-feature monotone sign broadcast over bins (zeros when
       monotone is off; pre-broadcast here because a [1, F] -> [1,1,F,1]
       reshape does not lower soundly in Mosaic)
    """
    b = padded_bins
    bins_r = jnp.arange(b, dtype=jnp.int32)[None, :]
    max_t = num_bins[:, None] - 2 - has_nan[:, None].astype(jnp.int32)
    num_valid = (bins_r <= max_t) & (~is_cat[:, None])
    cat_valid = (bins_r < num_bins[:, None]) & is_cat[:, None]
    nan_oh = ((bins_r == jnp.maximum(num_bins - 1, 0)[:, None])
              & has_nan[:, None])
    f = num_valid.shape[0]
    mono_row = (jnp.zeros((f,), jnp.float32) if monotone is None
                else monotone[:f].astype(jnp.float32))
    return jnp.stack([
        (num_valid | cat_valid).astype(jnp.float32),
        (num_valid & has_nan[:, None]).astype(jnp.float32),
        nan_oh.astype(jnp.float32),
        jnp.broadcast_to(is_cat[:, None].astype(jnp.float32),
                         num_valid.shape),
        jnp.broadcast_to(mono_row[:, None], num_valid.shape),
    ])


def _leaf_output_constrained(sum_g, sum_h, cnt, pout, mn, mx,
                             hp: SplitHyperParams):
    """CalculateSplittedLeafOutput with path smoothing and monotone
    clipping (feature_histogram.hpp:743-781) — the constrained-candidate
    path of the kernel tail."""
    out = _leaf_output(sum_g, sum_h, hp)
    if hp.use_smoothing:
        w = cnt / hp.path_smooth
        out = out * w / (w + 1.0) + pout / (w + 1.0)
    if hp.use_monotone:
        out = jnp.clip(out, mn, mx)
    return out


def _gain_given_output(sum_g, sum_h, out, hp: SplitHyperParams):
    """GetLeafGainGivenOutput (feature_histogram.hpp:848)."""
    sg = sum_g
    if hp.lambda_l1 > 0.0:
        sg = jnp.sign(sum_g) * jnp.maximum(jnp.abs(sum_g) - hp.lambda_l1, 0.0)
    return -(2.0 * sg * out + (sum_h + hp.lambda_l2) * out * out)


def _mono_penalty_factor(depth, penalization: float):
    """ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:355)."""
    eps = 1e-15
    small = 1.0 - penalization / jnp.exp2(depth) + eps
    large = 1.0 - jnp.exp2(penalization - 1.0 - depth) + eps
    fac = small if penalization <= 1.0 else large
    return jnp.where(penalization >= depth + 1.0, eps, fac)


def _leaf_output(sum_g, sum_h, hp: SplitHyperParams):
    """CalculateSplittedLeafOutput, unconstrained fast path
    (feature_histogram.hpp:743).  The zero-hessian guard must be a
    NORMAL float: Mosaic flushes subnormals, so the XLA tail's +1e-38
    becomes +0 here and empty candidate bins would produce 0/0 = NaN
    tensors that poison the one-hot winner extraction."""
    sg = sum_g
    if hp.lambda_l1 > 0.0:
        sg = jnp.sign(sum_g) * jnp.maximum(jnp.abs(sum_g) - hp.lambda_l1, 0.0)
    out = -sg / jnp.maximum(sum_h + hp.lambda_l2, 1e-30)
    if hp.max_delta_step > 0.0:
        out = jnp.clip(out, -hp.max_delta_step, hp.max_delta_step)
    return out


def _split_gain(sum_g, sum_h, hp: SplitHyperParams):
    """GetLeafGain (feature_histogram.hpp:785ff), unconstrained."""
    sg = sum_g
    if hp.lambda_l1 > 0.0:
        sg = jnp.sign(sum_g) * jnp.maximum(jnp.abs(sum_g) - hp.lambda_l1, 0.0)
    if hp.max_delta_step > 0.0:
        out = _leaf_output(sum_g, sum_h, hp)
        return -(2.0 * sg * out + (sum_h + hp.lambda_l2) * out * out)
    return (sg * sg) / jnp.maximum(sum_h + hp.lambda_l2, 1e-30)


def _lane_vec(vals, width, dtype=jnp.float32):
    """Scalars -> [1, width] vector via iota selects (Mosaic rejects
    tiny-vector stacks/reshapes)."""
    io = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    out = jnp.zeros((1, width), dtype)
    for k, v in enumerate(vals):
        out = jnp.where(io == k, v, out)
    return out


def _cumsum_last(x, interpret: bool = False):
    """f32-accurate inclusive prefix sum along the last (lane) axis via a
    lower-triangular matmul.

    Compiled (Mosaic) path: a plain f32 tril matmul is WRONG — Mosaic
    lowers f32 dots to a single bf16 MXU pass regardless of
    precision=HIGHEST, and split gains are small differences of large
    prefix sums; the 2^-8 relative error survives the cancellation as
    gain errors of O(100), silently steering the finder to wrong
    (feature, bin) picks (reproduced by tools/replay_apply_find.py; the
    reference accumulates histograms in double for exactly this reason,
    bin.h:32-37).  Decomposing x into three bf16 terms (8+8+8 mantissa
    bits) makes each product with the 0/1 tril exact and the f32
    accumulation carries full precision — the same scheme as XLA's
    HIGHEST f32 matmul.  (A Hillis-Steele roll+add scan was exact too
    but pltpu.roll's lane rotations ballooned scoped VMEM ~4.5x.)

    Interpret path: XLA honors precision=HIGHEST, and with
    --xla_allow_excess_precision it may algebraically re-fuse the manual
    bf16x3 terms back into one low-precision dot — so use the direct f32
    HIGHEST dot there instead."""
    rows, b = x.shape
    r_i = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    c_i = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    dn = (((1,), (0,)), ((), ()))
    if interpret:
        tril = (r_i <= c_i).astype(jnp.float32)
        return jax.lax.dot_general(
            x, tril, dimension_numbers=dn,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    tril = (r_i <= c_i).astype(jnp.bfloat16)
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=dn,
        preferred_element_type=jnp.float32)
    h1 = x.astype(jnp.bfloat16)
    r1 = x - h1.astype(jnp.float32)
    h2 = r1.astype(jnp.bfloat16)
    h3 = (r1 - h2.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(h1, tril) + dot(h2, tril) + dot(h3, tril)


def _copy_state_through(best_in, lstate_in, nodes_in, seg_in,
                        best_ref, lstate_ref, nodes_ref, seg_ref):
    """Explicitly initialise every output from its aliased input BEFORE
    the row writes.  input_output_aliases alone is NOT reliable here:
    inside the grow while_loop the compiled custom call has been observed
    to hand the kernel an UNINITIALISED output buffer (unwritten rows
    came back as zeros/junk, silently corrupting unrelated leaves' best
    rows — reproduced by tools/replay_apply_find.py; standalone calls
    were fine).  The copy is ~30 KB of VMEM traffic, noise per split."""
    best_ref[:] = best_in[:]
    lstate_ref[:] = lstate_in[:]
    nodes_ref[:] = nodes_in[:]
    seg_ref[:] = seg_in[:]


def _apply_find_kernel(sel_i, sel_f, h2_ref, fmask_ref, consts_ref,
                       iscat_ref, mono_s_ref,
                       best_in, lstate_in, nodes_in, seg_in,
                       best_ref, lstate_ref, nodes_ref, seg_ref,
                       *, hp: SplitHyperParams, L: int, f: int, b: int,
                       max_depth: int, interpret: bool = False):
    _copy_state_through(best_in, lstate_in, nodes_in, seg_in,
                        best_ref, lstate_ref, nodes_ref, seg_ref)
    _apply_find_body(sel_i, sel_f, h2_ref[:], fmask_ref, consts_ref,
                     iscat_ref, mono_s_ref, nodes_in,
                     best_ref, lstate_ref, nodes_ref,
                     seg_ref, hp=hp, L=L, f=f, b=b, max_depth=max_depth,
                     interpret=interpret)


def _apply_find_pool_kernel(sel_i, sel_f, hs_ref, fmask_ref, consts_ref,
                            iscat_ref, mono_s_ref,
                            best_in, lstate_in, nodes_in, seg_in, pool_in,
                            best_ref, lstate_ref, nodes_ref, seg_ref,
                            pool_out, vh, sem,
                            *, hp: SplitHyperParams, L: int, f: int,
                            b: int, max_depth: int):
    """Pool-resident variant: the histogram POOL stays an HBM ref; the
    kernel DMAs the parent's row in, applies the subtraction trick
    itself, and DMA-writes both children's rows — removing the per-split
    XLA pool staging copies (2 x ~39 us) and the subtraction op chain.
    hs_ref holds the smaller child's histogram; sel_i[SEL_SMALL] says
    which side it is.  pool_out is HBM-aliased to pool_in and written
    ONLY via manual DMA (a pattern verified on the device in round
    3), so untouched rows persist."""
    _copy_state_through(best_in, lstate_in, nodes_in, seg_in,
                        best_ref, lstate_ref, nodes_ref, seg_ref)
    leaf = sel_i[SEL_LEAF]
    right = sel_i[SEL_RIGHT]
    done = sel_i[SEL_DONE] > 0
    small_left = sel_i[SEL_SMALL] > 0

    cp = pltpu.make_async_copy(pool_in.at[leaf], vh, sem)
    cp.start()
    cp.wait()
    hpar = vh[:]
    hs = hs_ref[:]
    h_left = jnp.where(small_left, hs, hpar - hs)
    h_right = hpar - h_left

    @pl.when(jnp.logical_not(done))
    def _write_pool():
        vh[:] = h_left
        cpo = pltpu.make_async_copy(vh, pool_out.at[leaf], sem)
        cpo.start()
        cpo.wait()
        vh[:] = h_right
        cpo2 = pltpu.make_async_copy(vh, pool_out.at[right], sem)
        cpo2.start()
        cpo2.wait()

    _apply_find_body(sel_i, sel_f, jnp.stack([h_left, h_right]),
                     fmask_ref, consts_ref, iscat_ref, mono_s_ref,
                     nodes_in,
                     best_ref, lstate_ref, nodes_ref, seg_ref,
                     hp=hp, L=L, f=f, b=b, max_depth=max_depth,
                     interpret=False)


def _apply_find_body(sel_i, sel_f, h2, fmask_ref, consts_ref,
                     iscat_ref, mono_s_ref, nodes_in,
                     best_ref, lstate_ref, nodes_ref, seg_ref,
                     *, hp: SplitHyperParams, L: int, f: int, b: int,
                     max_depth: int, interpret: bool = False):
    leaf = sel_i[SEL_LEAF]
    right = sel_i[SEL_RIGHT]
    node = sel_i[SEL_NODE]
    done = sel_i[SEL_DONE] > 0
    nleft = sel_i[SEL_NLEFT]
    s0 = sel_i[SEL_S0]
    par_cnt = sel_i[SEL_PCNT]

    # parent rows (read by the select phase, passed in via SMEM)
    gain_rec, feat, sbin, dl, cat = (sel_f[0], sel_f[1], sel_f[2],
                                     sel_f[3], sel_f[4])
    lg, lh, lc, lo, ro = sel_f[5], sel_f[6], sel_f[7], sel_f[8], sel_f[9]
    pg, ph, pc, dep = sel_f[10], sel_f[11], sel_f[12], sel_f[13]
    par = sel_f[14]
    mn_p, mx_p = sel_f[15], sel_f[16]
    rg, rh, rc = pg - lg, ph - lh, pc - lc

    # ---- finder over both children (vector core) ----
    # h2: [2, F, 4, B] (left/right, channel-second layout padded to 4
    # channels so the pool's DMA-sliced dims are tile-aligned)
    consts = consts_ref[:]              # [4, F, B]
    valid0, valid1 = consts[0], consts[1]
    nan_oh, catv = consts[2], consts[3]
    fmask = fmask_ref[:]                # [1, F]

    # 2-channel histograms (grad, hess — reference hist_t parity);
    # candidate counts derive from cumulative hessians exactly like
    # split.derived_counts (cnt_factor = num_data / sum_hessian,
    # feature_histogram.hpp:316,868) — and the third cumsum is gone
    hg = h2[:, :, 0, :].reshape(2 * f, b)
    hh = h2[:, :, 1, :].reshape(2 * f, b)
    cg = _cumsum_last(hg, interpret).reshape(2, f, b)
    ch = _cumsum_last(hh, interpret).reshape(2, f, b)
    hg = hg.reshape(2, f, b)
    hh = hh.reshape(2, f, b)
    nan_g = jnp.sum(hg * nan_oh, axis=2)        # [2, F]
    nan_h = jnp.sum(hh * nan_oh, axis=2)

    iscat = catv > 0.5
    lg0 = jnp.where(iscat, hg, cg)
    lh0 = jnp.where(iscat, hh, ch)
    lg1 = cg + nan_g[..., None]
    lh1 = ch + nan_h[..., None]
    lgs = jnp.stack([lg0, lg1], axis=1)         # [2, 2dir, F, B]
    lhs = jnp.stack([lh0, lh1], axis=1)
    vmask = jnp.stack([jnp.broadcast_to(valid0, (2, f, b)),
                       jnp.broadcast_to(valid1, (2, f, b))], axis=1)

    child_ax = jax.lax.broadcasted_iota(jnp.int32, (2, 1, 1, 1), 0)
    csg = jnp.where(child_ax == 0, lg, rg)      # [2,1,1,1] scalar select
    csh = jnp.where(child_ax == 0, lh, rh)
    csc = jnp.where(child_ax == 0, lc, rc)
    cfac = csc / jnp.maximum(csh, 1e-38)
    lcs = jnp.floor(lhs * cfac + 0.5)
    rgs, rhs, rcs = csg - lgs, csh - lhs, csc - lcs

    ok = (
        (vmask > 0.5)
        & (lcs >= float(hp.min_data_in_leaf))
        & (rcs >= float(hp.min_data_in_leaf))
        & (lhs >= hp.min_sum_hessian_in_leaf)
        & (rhs >= hp.min_sum_hessian_in_leaf)
        & (fmask[0][None, None, :, None] > 0)
    )
    if max_depth > 0:
        ok = ok & (dep + 1.0 < float(max_depth))
    d_child = dep + 1.0
    constrained = hp.use_monotone or hp.use_smoothing
    if hp.use_monotone:
        # each child's candidates evaluate against the CHILD's bounds —
        # the parent's bounds tightened by the output midpoint
        # (BasicLeafConstraints::Update, monotone_constraints.hpp:
        # 485-501), exactly what the XLA tail stacks per child
        featp = jnp.maximum(sel_f[1].astype(jnp.int32), 0)
        mono_win = jnp.where(sel_f[4] > 0.5, 0, mono_s_ref[featp])
        midp = (lo + ro) * 0.5
        l_mn_c = jnp.where(mono_win < 0, jnp.maximum(mn_p, midp), mn_p)
        l_mx_c = jnp.where(mono_win > 0, jnp.minimum(mx_p, midp), mx_p)
        r_mn_c = jnp.where(mono_win > 0, jnp.maximum(mn_p, midp), mn_p)
        r_mx_c = jnp.where(mono_win < 0, jnp.minimum(mx_p, midp), mx_p)
    else:
        l_mn_c = r_mn_c = mn_p
        l_mx_c = r_mx_c = mx_p
    if constrained:
        # GetSplitGains USE_MC/USE_SMOOTHING (feature_histogram.hpp:
        # 786-824): per-candidate constrained outputs, sibling-order
        # violation mask, given-output gains
        monoB = consts[4][None, None]                # [1,1,F,B] f32
        cpo = jnp.where(child_ax == 0, lo, ro)       # per-child pout
        cmn = jnp.where(child_ax == 0, l_mn_c, r_mn_c)
        cmx = jnp.where(child_ax == 0, l_mx_c, r_mx_c)
        l_outs = _leaf_output_constrained(lgs, lhs, lcs, cpo, cmn, cmx,
                                          hp)
        r_outs = _leaf_output_constrained(rgs, rhs, rcs, cpo, cmn, cmx,
                                          hp)
        if hp.use_monotone:
            viol = (((monoB > 0.0) & (l_outs > r_outs))
                    | ((monoB < 0.0) & (l_outs < r_outs)))
            ok = ok & jnp.logical_not(viol)
        parent_gain = _gain_given_output(csg, csh, cpo, hp)
        gains = (_gain_given_output(lgs, lhs, l_outs, hp)
                 + _gain_given_output(rgs, rhs, r_outs, hp)
                 - parent_gain - hp.min_gain_to_split)
        if hp.use_monotone and hp.monotone_penalty > 0.0:
            fac = _mono_penalty_factor(d_child,
                                       float(hp.monotone_penalty))
            gains = jnp.where(monoB != 0.0, gains * fac, gains)
    else:
        l_outs = r_outs = None
        parent_gain = _split_gain(csg, csh, hp)
        gains = (_split_gain(lgs, lhs, hp) + _split_gain(rgs, rhs, hp)
                 - parent_gain - hp.min_gain_to_split)
    gains = jnp.where(ok, gains, -jnp.inf)
    gains_safe = jnp.where(ok, gains, 0.0)

    @pl.when(jnp.logical_not(done))
    def _write():
        for child in range(2):
            tgt = leaf if child == 0 else right
            c_sg = lg if child == 0 else rg
            c_sh = lh if child == 0 else rh
            c_sc = lc if child == 0 else rc
            c_out = lo if child == 0 else ro
            gflat = gains[child].reshape(1, 2 * f * b)
            # QUANTIZED FEATURE-MAJOR min-index argmax: the selection
            # key truncates the low mantissa bits (split.selection_key
            # semantics, inlined — Mosaic has no reduce_precision
            # lowering, but bitcast+mask is plain int vector work) so
            # ulp-level reduction-order noise cannot reorder equal
            # candidates, then ties rank by (feature, direction, bin)
            # — the reference SplitInfo tie-break ("if same gain, use
            # smaller feature", split_info.hpp) and the ordering the
            # XLA finder (ops/split.py find_best_split) and the sharded
            # chunk election use, so compiled, interpret, and every
            # learner pick the identical split.  (Mosaic's own argmax
            # breaks ties by lane order, hence the explicit
            # min-of-rank construction.)
            from ..split import SEL_DROP_BITS
            gq = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(gflat, jnp.int32)
                & jnp.int32(~((1 << SEL_DROP_BITS) - 1)), jnp.float32)
            gmax = jnp.max(gq)
            io_flat = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * f * b), 1)
            fm_rank = ((io_flat % (f * b)) // b * (2 * b)
                       + io_flat // (f * b) * b
                       + io_flat % b)
            bi_fm = jnp.min(jnp.where(gq >= gmax, fm_rank,
                                      jnp.int32(1 << 30)))   # rank-0 i32
            oh = (fm_rank == bi_fm).astype(jnp.float32)
            pick = lambda a: jnp.sum(a[child].reshape(1, 2 * f * b) * oh)
            g_ = jnp.where(gmax < -1e37, -jnp.inf, pick(gains_safe))
            blg = pick(lgs)
            blh = pick(lhs)
            blc = pick(lcs)
            bfeat = bi_fm // (2 * b)
            rem = bi_fm - bfeat * (2 * b)
            bdir = rem // b
            bbin = rem - bdir * b
            bcat = iscat_ref[bfeat].astype(jnp.float32)
            if constrained:
                b_lo = pick(l_outs)
                b_ro = pick(r_outs)
            else:
                b_lo = _leaf_output(blg, blh, hp)
                b_ro = _leaf_output(c_sg - blg, c_sh - blh, hp)
            best_row = _lane_vec([
                g_, bfeat.astype(jnp.float32), bbin.astype(jnp.float32),
                (bdir == 1).astype(jnp.float32), bcat,
                blg, blh, blc, b_lo, b_ro], 10)
            best_ref[pl.ds(tgt, 1), :] = best_row
            if child == 0:
                c_mn, c_mx = l_mn_c, l_mx_c
            else:
                c_mn, c_mx = r_mn_c, r_mx_c
            lstate_row = _lane_vec([
                c_sg, c_sh, c_sc, d_child, node.astype(jnp.float32),
                c_mn, c_mx, c_out], 8)
            lstate_ref[pl.ds(tgt, 1), :] = lstate_row
        # seg rows (i32)
        io2 = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
        seg_ref[pl.ds(leaf, 1), :] = jnp.where(io2 == 0, s0, nleft)
        seg_ref[pl.ds(right, 1), :] = jnp.where(
            io2 == 0, s0 + nleft, par_cnt - nleft)
        # parent child-pointer fix (reference Tree::Split, tree.h:541)
        pidx = jnp.maximum(par.astype(jnp.int32), 0)
        enc = -(leaf + 1).astype(jnp.float32)
        fnode = node.astype(jnp.float32)

        @pl.when(par >= 0.0)
        def _fix_parent():
            prow = nodes_in[pl.ds(pidx, 1), :]          # [1, 10]
            io10 = jax.lax.broadcasted_iota(jnp.int32, (1, 10), 1)
            new = jnp.where((io10 == 5) & (prow == enc), fnode, prow)
            new = jnp.where((io10 == 6) & (prow == enc), fnode, new)
            nodes_ref[pl.ds(pidx, 1), :] = new

        node_row = _lane_vec([
            feat, sbin, gain_rec, dl, cat,
            enc, -(right + 1).astype(jnp.float32),
            _leaf_output(pg, ph, hp), ph, pc], 10)
        nodes_ref[pl.ds(node, 1), :] = node_row


def make_apply_find(hp: SplitHyperParams, *, L: int, f: int, b: int,
                    max_depth: int, interpret: bool = False):
    """Returns apply_find(sel_i, sel_f, h2, fmask, consts, iscat, best,
    lstate, nodes, seg) -> (best, lstate, nodes, seg), state in/out
    aliased."""
    ni = L - 1
    assert tail_supported(f, b), (
        f"apply_find finder footprint at F={f}, B={b} exceeds the safe "
        f"scoped-VMEM cap ({_VMEM_CAP >> 20} MB); use the XLA tail")
    kern = functools.partial(_apply_find_kernel, hp=hp, L=L, f=f, b=b,
                             max_depth=max_depth, interpret=interpret)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)

    def apply_find(sel_i, sel_f, h2, fmask, consts, iscat, mono_s,
                   best, lstate, nodes, seg):
        return pl.pallas_call(
            kern,
            name="lgbm_apply_find",
            in_specs=[smem(), smem(), vmem(), vmem(), vmem(), smem(),
                      smem(),
                      vmem(), vmem(), vmem(), vmem()],
            out_specs=[vmem(), vmem(), vmem(), vmem()],
            out_shape=[
                jax.ShapeDtypeStruct((L, 10), jnp.float32),
                jax.ShapeDtypeStruct((L, 8), jnp.float32),
                jax.ShapeDtypeStruct((ni, 10), jnp.float32),
                jax.ShapeDtypeStruct((L, 2), jnp.int32),
            ],
            input_output_aliases={7: 0, 8: 1, 9: 2, 10: 3},
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit_for(f, b)),
        )(sel_i, sel_f, h2, fmask, consts, iscat, mono_s,
          best, lstate, nodes, seg)

    return apply_find


def make_apply_find_pool(hp: SplitHyperParams, *, L: int, f: int, b: int,
                         max_depth: int):
    """Pool-resident variant (compiled TPU only): apply_find_pool(sel_i,
    sel_f, h_small, fmask, consts, iscat, best, lstate, nodes, seg,
    pool) -> (best, lstate, nodes, seg, pool).  The [L, F, 4, B] pool
    stays in HBM, aliased in/out, parent row DMA'd in and children rows
    DMA'd out by the kernel (subtraction trick included)."""
    ni = L - 1
    assert tail_supported(f, b), (
        f"apply_find finder footprint at F={f}, B={b} exceeds the safe "
        f"scoped-VMEM cap ({_VMEM_CAP >> 20} MB); use the XLA tail")
    kern = functools.partial(_apply_find_pool_kernel, hp=hp, L=L, f=f,
                             b=b, max_depth=max_depth)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = lambda: pl.BlockSpec(memory_space=_HBM)

    def apply_find_pool(sel_i, sel_f, h_small, fmask, consts, iscat,
                        mono_s, best, lstate, nodes, seg, pool):
        # h_small and pool use the [.., F, 4, B] channel-second layout
        return pl.pallas_call(
            kern,
            name="lgbm_apply_find",
            in_specs=[smem(), smem(), vmem(), vmem(), vmem(), smem(),
                      smem(),
                      vmem(), vmem(), vmem(), vmem(), hbm()],
            out_specs=[vmem(), vmem(), vmem(), vmem(), hbm()],
            out_shape=[
                jax.ShapeDtypeStruct((L, 10), jnp.float32),
                jax.ShapeDtypeStruct((L, 8), jnp.float32),
                jax.ShapeDtypeStruct((ni, 10), jnp.float32),
                jax.ShapeDtypeStruct((L, 2), jnp.int32),
                jax.ShapeDtypeStruct(pool.shape, jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((f, 4, b), jnp.float32),
                            pltpu.SemaphoreType.DMA],
            input_output_aliases={7: 0, 8: 1, 9: 2, 10: 3, 11: 4},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit_for(f, b)),
        )(sel_i, sel_f, h_small, fmask, consts, iscat, mono_s,
          best, lstate, nodes, seg, pool)

    return apply_find_pool


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import register_kernel, sds


def _finder_args(L: int, f: int, b: int, h_lead):
    return (sds((8,), jnp.int32), sds((24,), jnp.float32),
            sds(h_lead + (f, 4, b), jnp.float32),
            sds((1, f), jnp.float32), sds((5, f, b), jnp.float32),
            sds((f,), jnp.int32), sds((f,), jnp.int32),
            sds((L, 10), jnp.float32), sds((L, 8), jnp.float32),
            sds((L - 1, 10), jnp.float32), sds((L, 2), jnp.int32))


@register_kernel("apply_find", kind="find",
                 note="split apply + best-split finder tail")
def _analysis_apply_find():
    L, f, b = 8, 16, 128
    fn = make_apply_find(SplitHyperParams(min_data_in_leaf=2), L=L,
                         f=f, b=b, max_depth=-1)
    return fn, _finder_args(L, f, b, (2,))


@register_kernel("apply_find_pool", kind="find",
                 note="pool-resident finder (HBM pool aliased "
                      "in/out, subtraction trick in-kernel)")
def _analysis_apply_find_pool():
    L, f, b = 8, 16, 128
    fn = make_apply_find_pool(SplitHyperParams(min_data_in_leaf=2),
                              L=L, f=f, b=b, max_depth=-1)
    args = _finder_args(L, f, b, ())
    return fn, args + (sds((L, f, 4, b), jnp.float32),)
