"""Best-split search over histograms.

Reference analog: FeatureHistogram::FindBestThreshold
(src/treelearner/feature_histogram.hpp:85,858 — sequential forward/backward
scans per feature with missing-direction handling) and its CUDA re-expression
(cuda_best_split_finder.cu:209-263 — block prefix sums + gain + argmax).

On TPU this is embarrassingly vectorizable: a cumulative sum over the bin
axis gives every threshold's left sums at once; gains for all
(feature, threshold, missing-direction) candidates are evaluated as one
masked tensor; the winner is a flat argmax.  No sequential scan survives.

Leaf-output / gain math mirrors feature_histogram.hpp:737-858:
  ThresholdL1(s, l1) = sign(s) * max(|s| - l1, 0)
  output  = -ThresholdL1(G, l1) / (H + l2)        (clipped by max_delta_step)
  gain(G,H) = ThresholdL1(G, l1)^2 / (H + l2)     (unconstrained case)
  split_gain = gain(G_l,H_l) + gain(G_r,H_r) - gain(G,H) - min_gain_to_split
with validity = per-child min_data_in_leaf / min_sum_hessian_in_leaf.

Missing handling: with a NaN bin (appended as the LAST bin of a feature), the
forward candidates send missing right (default_left=False) and a second
candidate set adds the NaN bin's sums to the left (default_left=True) —
equivalent to the reference's two scans.

Categorical features use one-hot candidates (bin == k goes left), the
reference's max_cat_to_onehot path; the sorted-subset search (rank-order
prefix scans in both directions) lives in this file too — see
``_cat_subset_tensors`` / ``cat_subset_member`` below.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class SplitHyperParams(NamedTuple):
    """Static hyper-parameters baked into the jitted grower."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    # categorical sorted-subset search (feature_histogram.hpp:278-475):
    # used for categorical features with more than max_cat_to_onehot
    # bins; enabled by the static use_cat_subset flag so the common
    # no-high-cardinality case pays nothing
    use_cat_subset: bool = False
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    min_data_per_group: int = 100
    # extremely randomized trees (feature_histogram.hpp USE_RAND /
    # cuda_best_split_finder.cu:1786): each node considers ONE random
    # threshold per feature instead of the full scan
    use_extra_trees: bool = False
    # monotone constraints (monotone_constraints.hpp BasicLeafConstraints)
    use_monotone: bool = False
    monotone_penalty: float = 0.0
    # intermediate method (monotone_constraints.hpp:514
    # IntermediateLeafConstraints): children bounded by each other's
    # ACTUAL outputs instead of the midpoint, and face-adjacent leaves
    # across monotone split planes get their bounds tightened (and best
    # splits recomputed) after every split
    mono_intermediate: bool = False
    # path smoothing (feature_histogram.hpp:761 USE_SMOOTHING)
    use_smoothing: bool = False
    # CEGB (cost_effective_gradient_boosting.hpp:80 DeltaGain); the lazy
    # per-row feature-acquisition costs are not supported
    use_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0


class SplitInfo(NamedTuple):
    """Best split candidate for one leaf (reference: split_info.hpp:22)."""
    gain: jnp.ndarray          # f32, split gain minus parent gain and
                               # min_gain_to_split; <= 0 means "no valid split"
    feature: jnp.ndarray       # i32 inner feature index
    threshold_bin: jnp.ndarray # i32 bin threshold (or one-hot category bin)
    default_left: jnp.ndarray  # bool
    is_categorical: jnp.ndarray  # bool
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray    # f32 (row count as float)
    left_output: jnp.ndarray   # f32 constrained/smoothed left-leaf output
    right_output: jnp.ndarray  # f32
    # the right child's sums where the finder has them on their own
    # (find_best_split_segments); None: the parent's minus the left's
    right_sum_g: Optional[jnp.ndarray] = None
    right_sum_h: Optional[jnp.ndarray] = None


# Winner SELECTION compares gains at reduced precision: the low
# SEL_DROP_BITS mantissa bits are truncated, so reduction-order noise
# (a serial jit, a shard_map program, and the Mosaic finder tail each
# accumulate the same sums in different orders, ~1 ulp apart) cannot
# reorder two mathematically-equal candidates; the survivors then
# tie-break deterministically on the smallest feature index (the
# reference SplitInfo ordering, split_info.hpp: "if same gain, use
# smaller feature").  10 bits keeps ~2^-13 relative resolution —
# far below any real gain separation, far above cross-learner noise.
# The recorded gain stays full precision; only the comparison key is
# truncated.  Mantissa masking (not lax.reduce_precision) because the
# Pallas finder tail needs the same key and Mosaic has no
# reduce_precision lowering (see pallas/stream_grad.py _round_bf16).
SEL_DROP_BITS = 10


def selection_key(g: jnp.ndarray) -> jnp.ndarray:
    """Quantized, weakly-monotonic gain key used ONLY to pick winners."""
    gi = jax.lax.bitcast_convert_type(g.astype(jnp.float32), jnp.int32)
    gi = gi & jnp.int32(~((1 << SEL_DROP_BITS) - 1))
    # sign-magnitude truncation moves values toward zero, preserving
    # order for either sign; +/-inf have zero low mantissa bits already
    return jax.lax.bitcast_convert_type(gi, jnp.float32)


def threshold_l1(s: jnp.ndarray, l1: float) -> jnp.ndarray:
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def calculate_leaf_output(
    sum_g: jnp.ndarray, sum_h: jnp.ndarray, hp: SplitHyperParams,
    count=None, parent_output=None, mn=None, mx=None,
) -> jnp.ndarray:
    """CalculateSplittedLeafOutput (feature_histogram.hpp:743-781):
    L1-thresholded ratio, max_delta_step clip, optional path smoothing
    toward the parent output, optional monotone min/max clip."""
    out = -threshold_l1(sum_g, hp.lambda_l1) / (sum_h + hp.lambda_l2 + 1e-38)
    if hp.max_delta_step > 0.0:
        out = jnp.clip(out, -hp.max_delta_step, hp.max_delta_step)
    if hp.use_smoothing and count is not None and parent_output is not None:
        w = count / hp.path_smooth
        out = out * w / (w + 1.0) + parent_output / (w + 1.0)
    if hp.use_monotone and mn is not None:
        out = jnp.clip(out, mn, mx)
    return out


def leaf_gain_given_output(
    sum_g: jnp.ndarray, sum_h: jnp.ndarray, out: jnp.ndarray,
    hp: SplitHyperParams,
) -> jnp.ndarray:
    """GetLeafGainGivenOutput (feature_histogram.hpp:848)."""
    sg = threshold_l1(sum_g, hp.lambda_l1)
    return -(2.0 * sg * out + (sum_h + hp.lambda_l2) * out * out)


def monotone_penalty_factor(depth: jnp.ndarray, penalization: float):
    """ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:355)."""
    d = depth.astype(jnp.float32)
    eps = 1e-15
    small = 1.0 - penalization / jnp.exp2(d) + eps
    large = 1.0 - jnp.exp2(penalization - 1.0 - d) + eps
    fac = jnp.where(penalization <= 1.0, small, large)
    return jnp.where(penalization >= d + 1.0, eps, fac)


def leaf_split_gain(
    sum_g: jnp.ndarray, sum_h: jnp.ndarray, hp: SplitHyperParams,
) -> jnp.ndarray:
    """GetLeafGain: 2x the loss reduction of fitting this leaf optimally."""
    sg = threshold_l1(sum_g, hp.lambda_l1)
    if hp.max_delta_step > 0.0:
        out = calculate_leaf_output(sum_g, sum_h, hp)
        # GetLeafSplitGainGivenOutput (feature_histogram.hpp:785)
        return -(2.0 * sg * out + (sum_h + hp.lambda_l2) * out * out)
    return (sg * sg) / (sum_h + hp.lambda_l2 + 1e-38)


def derived_counts(h, count, sum_h):
    """Reference count estimation (feature_histogram.hpp:316,868):
    ``cnt_factor = num_data / sum_hessian``, per-candidate count =
    ``RoundInt(hess * cnt_factor)``.  Histograms carry (grad, hess)
    pairs only — exactly the reference's hist_t layout (bin.h:32-37);
    counts are always estimated from hessians.  One documented
    deviation: the reference rounds each BIN then accumulates, here the
    CUMULATIVE hessian is rounded once (identical in both finders, and
    what the Pallas tail computes without a third cumsum)."""
    factor = count / jnp.maximum(sum_h, 1e-38)
    return jnp.floor(h * factor + 0.5)


def _candidate_tensors(
    hist, sum_g, sum_h, count, num_bins, has_nan, is_cat, feature_mask,
    allow_split, hp: SplitHyperParams, *, monotone=None, mn=None, mx=None,
    parent_output=None, depth=None, cegb_penalty=None, rand_key=None,
):
    """All (direction, feature, bin) split candidates at once.

    Returns ``(gains [2,F,B] with -inf for invalid, lg, lh, lc,
    l_out-or-None, r_out-or-None)`` — the vectorized core shared by
    ``find_best_split`` and the voting learner's per-feature gain vote
    (voting_parallel_tree_learner.cpp:344-358)."""
    f, b, _ = hist.shape
    hg, hh = hist[..., 0], hist[..., 1]

    # cumulative (inclusive) sums along the bin axis; padding bins are empty
    cg = jnp.cumsum(hg, axis=1)
    ch = jnp.cumsum(hh, axis=1)

    nan_idx = jnp.maximum(num_bins - 1, 0)
    take = lambda a: jnp.take_along_axis(a, nan_idx[:, None], axis=1)[:, 0]
    nan_g = jnp.where(has_nan, take(hg), 0.0)
    nan_h = jnp.where(has_nan, take(hh), 0.0)

    bins_r = jnp.arange(b, dtype=jnp.int32)[None, :]              # [1, B]
    # numerical thresholds: t in [0, nb - 2 - has_nan]
    max_t = num_bins[:, None] - 2 - has_nan[:, None].astype(jnp.int32)
    num_valid = (bins_r <= max_t) & (~is_cat[:, None])
    # categorical one-hot candidates: k in [0, nb); high-cardinality
    # categoricals use the sorted-subset search instead (exclusive, like
    # the reference's use_onehot dispatch, feature_histogram.hpp:315)
    cat_valid = (bins_r < num_bins[:, None]) & is_cat[:, None]
    if hp.use_cat_subset:
        cat_valid = cat_valid & (num_bins[:, None] <= hp.max_cat_to_onehot)

    # direction 0: numerical fwd (missing right) merged with categorical;
    # direction 1: numerical with missing left (only when a NaN bin exists)
    left_g0 = jnp.where(is_cat[:, None], hg, cg)
    left_h0 = jnp.where(is_cat[:, None], hh, ch)
    left_g1 = cg + nan_g[:, None]
    left_h1 = ch + nan_h[:, None]

    lg = jnp.stack([left_g0, left_g1])   # [2, F, B]
    lh = jnp.stack([left_h0, left_h1])
    lc = derived_counts(lh, count, sum_h)
    valid = jnp.stack([num_valid | cat_valid,
                       num_valid & has_nan[:, None]])

    rg, rh, rc = sum_g - lg, sum_h - lh, count - lc

    min_data = jnp.float32(hp.min_data_in_leaf)
    ok = (
        valid
        & (lc >= min_data) & (rc >= min_data)
        & (lh >= hp.min_sum_hessian_in_leaf)
        & (rh >= hp.min_sum_hessian_in_leaf)
        & (feature_mask[None, :, None] > 0)
        & allow_split
    )
    if hp.use_extra_trees and rand_key is not None:
        # extremely randomized trees: restrict each feature to ONE
        # uniformly random candidate threshold within its valid range
        # (feature_histogram.hpp USE_RAND: rand.NextInt over the scan
        # bounds; both missing directions still evaluated at that bin)
        u = jax.random.uniform(rand_key, (f,))
        hi = jnp.where(is_cat, num_bins - 1, max_t[:, 0])
        pick = jnp.floor(u * (jnp.maximum(hi, 0) + 1)).astype(jnp.int32)
        pick = jnp.clip(pick, 0, jnp.maximum(hi, 0))
        ok = ok & (bins_r == pick[:, None])[None]

    constrained = hp.use_monotone or hp.use_smoothing
    if constrained:
        # per-candidate constrained/smoothed child outputs and the
        # given-output gain (GetSplitGains USE_MC path,
        # feature_histogram.hpp:786-824)
        l_out = calculate_leaf_output(lg, lh, hp, lc, parent_output, mn, mx)
        r_out = calculate_leaf_output(rg, rh, hp, rc, parent_output, mn, mx)
        if hp.use_monotone:
            mono = monotone[None, :, None]
            viol = (((mono > 0) & (l_out > r_out))
                    | ((mono < 0) & (l_out < r_out)))
            ok = ok & ~viol
        parent_gain = leaf_gain_given_output(
            sum_g, sum_h,
            parent_output if parent_output is not None
            else calculate_leaf_output(sum_g, sum_h, hp), hp)
        gains = (leaf_gain_given_output(lg, lh, l_out, hp)
                 + leaf_gain_given_output(rg, rh, r_out, hp)
                 - parent_gain - hp.min_gain_to_split)
        if hp.use_monotone and hp.monotone_penalty > 0.0 and depth is not None:
            fac = monotone_penalty_factor(depth, hp.monotone_penalty)
            gains = jnp.where(mono != 0, gains * fac, gains)
    else:
        parent_gain = leaf_split_gain(sum_g, sum_h, hp)
        gains = (leaf_split_gain(lg, lh, hp) + leaf_split_gain(rg, rh, hp)
                 - parent_gain - hp.min_gain_to_split)
    if hp.use_cegb:
        # DeltaGain (cost_effective_gradient_boosting.hpp:80): constant
        # per-split cost scaled by rows reaching the leaf, plus the
        # caller-maintained per-feature coupled penalty
        delta = hp.cegb_tradeoff * hp.cegb_penalty_split * count
        if cegb_penalty is not None:
            delta = delta + cegb_penalty[None, :, None]
        gains = gains - delta
    gains = jnp.where(ok, gains, -jnp.inf)
    if constrained:
        return gains, lg, lh, lc, l_out, r_out
    return gains, lg, lh, lc, None, None


def cat_subset_rank(hg, hh, hc, valid, hp: SplitHyperParams):
    """Deterministic ratio-ranking of category bins for the sorted-subset
    search (feature_histogram.hpp:379-400).

    Candidate bins need enough data (reference: hessian-estimated count
    >= cat_smooth, matching the 2-channel histogram layout — non-empty
    always required so cat_smooth=0 can't admit empty/padded bins with
    NaN ratios) and are stably ranked ascending by
    grad/(hess + cat_smooth).
    ``valid`` masks real bins (< num_bins).  Returns ``(cand [.., B]
    bool, rank [.., B] i32, used [..] i32)``; rank is only meaningful
    where cand.  Shared by the finder and the split APPLICATION so the
    winning prefix reconstructs the identical set.
    """
    b = hg.shape[-1]
    cand = (hc >= hp.cat_smooth) & (hc > 0) & valid
    ratio = hg / (hh + hp.cat_smooth)
    big = jnp.float32(jnp.inf)
    r = jnp.where(cand, ratio, big)
    # rank_b = #candidates strictly before b in (ratio, bin) stable order
    r_i = r[..., :, None]                       # [.., B, 1] (bin b)
    r_j = r[..., None, :]                       # [.., 1, B] (bin j)
    idx = jnp.arange(b, dtype=jnp.int32)
    before = (r_j < r_i) | ((r_j == r_i) & (idx[None, :] < idx[:, None]))
    before = before & cand[..., None, :]
    rank = jnp.sum(before.astype(jnp.int32), axis=-1)
    used = jnp.sum(cand.astype(jnp.int32), axis=-1)
    return cand, rank, used


def cat_subset_member(hg, hh, hc, nb, k, direction, hp: SplitHyperParams):
    """[B] bool membership of the winning subset: the first ``k`` bins of
    the ratio-sorted candidate order (``direction`` 0 = ascending, 1 =
    descending).  Bins in the set go LEFT (reference cat_threshold)."""
    valid = jnp.arange(hg.shape[-1], dtype=jnp.int32) < nb
    cand, rank, used = cat_subset_rank(hg, hh, hc, valid, hp)
    rank_d = jnp.where(direction > 0, used[..., None] - 1 - rank, rank)
    return cand & (rank_d < k)


def _cat_subset_tensors(hist, sum_g, sum_h, count, num_bins, is_cat,
                        feature_mask, allow_split, hp: SplitHyperParams,
                        rand_key=None, mn=None, mx=None,
                        parent_output=None, cegb_penalty=None):
    """Sorted-subset split candidates for high-cardinality categoricals
    (feature_histogram.hpp:375-475 FindBestThresholdCategoricalInner,
    !use_onehot branch), fully vectorized: prefix index i means "the
    first i+1 ratio-sorted candidate bins go left".

    Returns (gains [2dir, F, B], lg, lh, lc) with -inf for invalid
    candidates.  Deviations from the reference, both documented:
    candidate-bin counts use the same cumulative-hessian estimate as the
    numerical path (the reference rounds per bin), and the
    min_data_per_group group-accumulator 'continue' is not applied (the
    right-child min_data_per_group bound is)."""
    f, b, _ = hist.shape
    hg, hh = hist[..., 0], hist[..., 1]
    hc = derived_counts(hh, count, sum_h)
    valid = jnp.arange(b, dtype=jnp.int32)[None, :] < num_bins[:, None]
    cand, rank, used = cat_subset_rank(hg, hh, hc, valid, hp)

    # prefix sums in rank order WITHOUT a [F, B, B] mask tensor (524 MB
    # at F=1000, B=256): scatter each channel into rank positions, cumsum
    # along bins, and read the backward direction off the forward prefix
    # (suffix of i+1 = total - prefix of used-i-1)
    iot = jnp.arange(b, dtype=jnp.int32)
    f_idx = jnp.arange(f, dtype=jnp.int32)[:, None]
    flat_pos = jnp.where(cand, f_idx * b + rank, f * b)     # OOB drops
    def _rank_cumsum(x):
        srt = jnp.zeros((f * b,), x.dtype).at[flat_pos.reshape(-1)].set(
            (x * cand).reshape(-1), mode="drop").reshape(f, b)
        return jnp.cumsum(srt, axis=1)                      # [F, B]
    cg = _rank_cumsum(hg)
    chh = _rank_cumsum(hh)
    cc = _rank_cumsum(hc)
    totg, toth, totc = cg[:, -1], chh[:, -1], cc[:, -1]

    def _dirs(cum, tot):
        fwd = cum                                           # prefix i+1
        # bwd prefix of i+1 = tot - fwd(used - i - 2), 0 when it covers
        # every candidate
        j = used[:, None] - 2 - iot[None, :]
        take_j = jnp.take_along_axis(cum, jnp.clip(j, 0, b - 1), axis=1)
        bwd = tot[:, None] - jnp.where(j >= 0, take_j, 0.0)
        return jnp.stack([fwd, bwd])                        # [2, F, B]

    lg = _dirs(cg, totg)
    lh = _dirs(chh, toth) + 1e-15
    lc = _dirs(cc, totc)
    rg, rh, rc = sum_g - lg, sum_h - lh, count - lc

    eligible = is_cat & (num_bins > hp.max_cat_to_onehot)  # [F]
    k = iot[None, None, :] + 1                             # prefix size
    max_num_cat = jnp.minimum(hp.max_cat_threshold, (used + 1) // 2)
    ok = (
        eligible[None, :, None]
        & (k <= max_num_cat[None, :, None])
        & (k <= used[None, :, None])
        & (lc >= jnp.float32(hp.min_data_in_leaf))
        & (rc >= jnp.float32(hp.min_data_in_leaf))
        & (rc >= jnp.float32(hp.min_data_per_group))
        & (lh >= hp.min_sum_hessian_in_leaf)
        & (rh >= hp.min_sum_hessian_in_leaf)
        & (feature_mask[None, :, None] > 0)
        & allow_split
    )
    if hp.use_extra_trees and rand_key is not None:
        # USE_RAND: one random prefix length per feature
        # (feature_histogram.hpp:401-406)
        f_ = hist.shape[0]
        u = jax.random.uniform(jax.random.fold_in(rand_key, 1), (f_,))
        max_thr = jnp.maximum(
            jnp.minimum(max_num_cat, used) - 1, 0)          # [F]
        pick_i = jnp.clip(jnp.floor(u * (max_thr + 1)).astype(jnp.int32),
                          0, max_thr)
        ok = ok & (iot[None, None, :] == pick_i[None, :, None])
    # gains with the categorical-boosted l2 (reference: l2 += cat_l2);
    # the parent gain/min_gain_to_split shift is applied with the
    # ORIGINAL l2 (feature_histogram.hpp:297-302 non-smoothing)
    hp2 = hp._replace(lambda_l2=hp.lambda_l2 + hp.cat_l2)
    constrained = hp.use_monotone or hp.use_smoothing
    if constrained:
        # same given-output gain formulation as the numerical candidates
        # (smoothing toward the parent; ancestor monotone bounds clip the
        # outputs; feature_histogram.hpp applies USE_SMOOTHING to the
        # categorical path too)
        l_out = calculate_leaf_output(lg, lh, hp2, lc, parent_output,
                                      mn, mx)
        r_out = calculate_leaf_output(rg, rh, hp2, rc, parent_output,
                                      mn, mx)
        parent_gain = leaf_gain_given_output(
            sum_g, sum_h,
            parent_output if parent_output is not None
            else calculate_leaf_output(sum_g, sum_h, hp), hp)
        gains = (leaf_gain_given_output(lg, lh, l_out, hp2)
                 + leaf_gain_given_output(rg, rh, r_out, hp2)
                 - parent_gain - hp.min_gain_to_split)
    else:
        l_out = r_out = None
        gains = (leaf_split_gain(lg, lh, hp2)
                 + leaf_split_gain(rg, rh, hp2)
                 - leaf_split_gain(sum_g, sum_h, hp)
                 - hp.min_gain_to_split)
    if hp.use_cegb:
        # same CEGB delta as the numerical candidates (split.py
        # _candidate_tensors; cost_effective_gradient_boosting.hpp:80)
        delta = hp.cegb_tradeoff * hp.cegb_penalty_split * count
        if cegb_penalty is not None:
            delta = delta + cegb_penalty[None, :, None]
        gains = gains - delta
    gains = jnp.where(ok, gains, -jnp.inf)
    return gains, lg, lh, lc, l_out, r_out


def per_feature_best_gain(
    hist, sum_g, sum_h, count, num_bins, has_nan, is_cat, feature_mask,
    hp: SplitHyperParams, *, monotone=None, cegb_penalty=None,
) -> jnp.ndarray:
    """Best achievable gain per feature — the voting-parallel learner's
    local ballot (parallel_tree_learner.h:151 GlobalVoting input).  Scored
    with the same monotone/CEGB adjustments as the real finder so the
    election ranks features by the gains they would actually deliver."""
    gains, *_ = _candidate_tensors(
        hist, sum_g, sum_h, count, num_bins, has_nan, is_cat, feature_mask,
        jnp.asarray(True), hp, monotone=monotone, cegb_penalty=cegb_penalty)
    best = jnp.max(gains, axis=(0, 2))   # [F]
    if hp.use_cat_subset:
        gains_s, *_ = _cat_subset_tensors(
            hist, sum_g, sum_h, count, num_bins, is_cat, feature_mask,
            jnp.asarray(True), hp)
        best = jnp.maximum(best, jnp.max(gains_s, axis=(0, 2)))
    return best


def find_best_split(
    hist: jnp.ndarray,        # [F, B, 2] (grad, hess); counts derived
    sum_g: jnp.ndarray,       # scalar leaf totals
    sum_h: jnp.ndarray,
    count: jnp.ndarray,       # scalar f32
    num_bins: jnp.ndarray,    # [F] i32 (incl. NaN bin when present)
    has_nan: jnp.ndarray,     # [F] bool
    is_cat: jnp.ndarray,      # [F] bool
    feature_mask: jnp.ndarray,  # [F] f32/bool — column sampling & constraints
    allow_split: jnp.ndarray,   # scalar bool (depth / leaf-size gates)
    hp: SplitHyperParams,
    *,
    monotone=None,            # [F] i32 in {-1,0,1} (use_monotone)
    mn=None, mx=None,         # scalar leaf output bounds (use_monotone)
    parent_output=None,       # scalar: leaf's current output (smoothing/gain)
    depth=None,               # scalar i32 (monotone_penalty)
    cegb_penalty=None,        # [F] extra per-feature gain penalty (use_cegb)
    rand_key=None,            # PRNG key (use_extra_trees randomization)
) -> SplitInfo:
    f, b, _ = hist.shape
    gains, lg, lh, lc, l_out, r_out = _candidate_tensors(
        hist, sum_g, sum_h, count, num_bins, has_nan, is_cat, feature_mask,
        allow_split, hp, monotone=monotone, mn=mn, mx=mx,
        parent_output=parent_output, depth=depth, cegb_penalty=cegb_penalty,
        rand_key=rand_key)
    constrained = hp.use_monotone or hp.use_smoothing

    if hp.use_cat_subset:
        # stack the sorted-subset candidates as two extra "directions";
        # the winner's threshold_bin is then encoded as
        # B*(1+dir) + (k-1), decoded in the grow loop
        gains_s, lg_s, lh_s, lc_s, lo_s, ro_s = _cat_subset_tensors(
            hist, sum_g, sum_h, count, num_bins, is_cat, feature_mask,
            allow_split, hp, rand_key=rand_key, mn=mn, mx=mx,
            parent_output=parent_output, cegb_penalty=cegb_penalty)
        gains = jnp.concatenate([gains, gains_s])           # [4, F, B]
        lg = jnp.concatenate([lg, lg_s])
        lh = jnp.concatenate([lh, lh_s])
        lc = jnp.concatenate([lc, lc_s])
        if constrained:
            l_out = jnp.concatenate([l_out, lo_s])
            r_out = jnp.concatenate([r_out, ro_s])

    # FEATURE-MAJOR winner selection over the QUANTIZED key: equal (to
    # selection precision) gains tie-break on the smallest feature index
    # first (then direction, then bin), matching the reference SplitInfo
    # comparison (split_info.hpp operator> / operator<=: "if same gain,
    # use smaller feature").  A plain argmax over the [D, F, B] layout
    # is direction-major and full-precision — it disagrees with the
    # chunk-parallel learners' shard election on ulp-level gain ties
    # (the feature-parallel monotone divergence); the quantized
    # feature-major rank makes serial and every sharded search pick the
    # identical split.  The Pallas finder tail (pallas/apply_find.py)
    # implements the same ordering.
    flat = gains.reshape(-1)
    d_all = gains.shape[0]
    qflat = selection_key(flat)
    gmax = jnp.max(qflat)
    io = jnp.arange(flat.shape[0], dtype=jnp.int32)
    fm_rank = ((io % (f * b)) // b * (d_all * b)      # feature major
               + io // (f * b) * b                    # then direction
               + io % b)                              # then bin
    bi_fm = jnp.min(jnp.where(qflat >= gmax, fm_rank, jnp.int32(1 << 30)))
    feat = (bi_fm // (d_all * b)).astype(jnp.int32)
    d = (bi_fm % (d_all * b)) // b
    tbin = (bi_fm % b).astype(jnp.int32)
    best = d * (f * b) + feat * b + tbin              # d-major flat index
    best_gain = flat[best]
    is_subset = jnp.asarray(False)
    if hp.use_cat_subset:
        is_subset = d >= 2
        # encode (dir, k) into threshold_bin for subset winners
        tbin = jnp.where(is_subset, b * (1 + (d - 2)) + tbin, tbin)

    pick = lambda a: a.reshape(-1)[best]
    blg, blh, blc = pick(lg), pick(lh), pick(lc)
    if constrained:
        b_lo, b_ro = pick(l_out), pick(r_out)
    else:
        b_lo = calculate_leaf_output(blg, blh, hp)
        b_ro = calculate_leaf_output(sum_g - blg, sum_h - blh, hp)
        if hp.use_cat_subset:
            # reference computes subset leaf outputs with l2 + cat_l2
            # (feature_histogram.hpp:477-489)
            hp_out = hp._replace(lambda_l2=hp.lambda_l2 + hp.cat_l2)
            b_lo = jnp.where(is_subset,
                             calculate_leaf_output(blg, blh, hp_out), b_lo)
            b_ro = jnp.where(
                is_subset,
                calculate_leaf_output(sum_g - blg, sum_h - blh, hp_out),
                b_ro)
    return SplitInfo(
        gain=best_gain,
        feature=feat,
        threshold_bin=tbin,
        default_left=(d == 1),
        is_categorical=is_cat[feat],
        left_sum_g=blg,
        left_sum_h=blh,
        left_count=blc,
        left_output=b_lo,
        right_output=b_ro,
    )


# ---------------------------------------------------------------------
# the finder over BUNDLE-SPACE histograms (EFB kept in the comb)
# ---------------------------------------------------------------------
def segment_maps(bundle, f_phys: int, padded_bins: int):
    """Static per-position maps of a bundled histogram ``[f_phys,
    padded_bins]`` (io/bundle.py layout): bin ``q`` of physical column
    ``p`` is logical bin ``q - offset_j`` of the one logical feature j
    whose stacked range ``[offset_j, offset_j + num_bins_j)`` holds it
    (an unbundled column is its feature's range at offset 0).  Numpy
    dict of ``[f_phys, padded_bins]`` arrays:

      feat   i32  owning logical feature, -1 outside every range
      lbin   i32  logical bin of the position
      cat    bool the feature is categorical (one-hot candidates)
      fix    bool bundled feature and lbin >= its default bin: the
                  left side of threshold lbin holds the default bin,
                  which no row stores - its rows sit in the column's
                  other bins, and ``segment_weights`` sums them there
                  (the reference rebuilds it from the leaf totals:
                  Dataset::FixHistogram, dataset.h:676)
      nan    bool the position is its feature's NaN bin
      valid  bool [2, ...] candidate of direction d (0: missing right
                  and categorical one-hot; 1: missing left)
      rank   i32  [2, ...] the feature-major tie-break order of
                  ``find_best_split`` (feature, then direction, then
                  bin)

    ``bundle`` carries ``has_nan`` / ``is_cat`` per logical feature
    (``device_data.to_device``); absent, no feature has either."""
    import numpy as np
    P, B = int(f_phys), int(padded_bins)
    phys = np.asarray(bundle["feat_phys"], np.int64)
    off = np.asarray(bundle["feat_offset"], np.int64)
    dflt = np.asarray(bundle["feat_default"], np.int64)
    nbl = np.asarray(bundle["num_bins_log"], np.int64)
    bundled = np.asarray(bundle["is_bundled"], bool)
    f_log = len(phys)
    has_nan = np.asarray(bundle.get("has_nan", np.zeros(f_log, bool)), bool)
    is_cat = np.asarray(bundle.get("is_cat", np.zeros(f_log, bool)), bool)
    feat = np.full((P, B), -1, np.int64)
    for j in np.flatnonzero(nbl > 0):
        feat[phys[j], off[j]:off[j] + nbl[j]] = j
    own = feat >= 0
    fj = np.maximum(feat, 0)
    lbin = np.where(own, np.arange(B)[None, :] - off[fj], 0)
    cat = own & is_cat[fj]
    nanf = own & has_nan[fj]
    fix = own & bundled[fj] & (lbin >= dflt[fj])
    nan = nanf & (lbin == nbl[fj] - 1)
    max_t = nbl[fj] - 2 - nanf
    num_valid = own & ~cat & (lbin <= max_t)
    valid = np.stack([num_valid | cat, num_valid & nanf])
    b_rank = int(max(nbl.max(), 1))
    rank = np.stack([fj * (2 * b_rank) + d * b_rank + lbin
                     for d in (0, 1)])
    i32 = lambda a: a.astype(np.int32)  # noqa: E731
    return {"feat": i32(feat), "lbin": i32(lbin), "cat": cat, "fix": fix, "nan": nan, "valid": valid,
            "rank": i32(np.where(valid, rank, 1 << 30))}


def segment_weights(maps):
    """The ``[2, D, f_phys, B, B]`` f32 0/1 matrices that turn a
    bundle-space histogram row into BOTH sides of every candidate with
    one batched contraction and no gather (built on device from the
    small maps, once a tree): ``side[s, p, q] = sum_q' h[p, q'] w[s, p,
    q', q]``, ``s`` 0 the left and 1 the right.  Every row of a leaf
    sits in exactly one bin of a column - a bundled feature's rows at
    its default sit in another member's bins, or in bin 0 - so a
    candidate's two sides are two SETS of the column's bins and each is
    summed on its own: no side is the leaf totals minus the other, and
    a small child cut from a large parent keeps the sum of its own
    rows.  Numerical candidate ``q``: the left is the prefix of its
    feature's segment up to ``q`` and, where the default bin is on the
    left (``fix``), every bin outside the segment; categorical: the bin
    itself; the right is the rest of the column.  ``w[:, 1]`` moves the
    feature's NaN bin to the left (missing goes left)."""
    seg = jnp.asarray(maps["feat"])     # a feature is one segment
    cat = jnp.asarray(maps["cat"])[:, None, :]
    fix = jnp.asarray(maps["fix"])[:, None, :]
    nan = jnp.asarray(maps["nan"])
    b = seg.shape[1]
    same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] >= 0)
    qi = jnp.arange(b, dtype=jnp.int32)
    le = qi[:, None] <= qi[None, :]                    # q' <= q
    eye = qi[:, None] == qi[None, :]
    left0 = jnp.where(cat, same & eye, (same & le) | (~same & fix))
    lefts = [left0]
    if maps["valid"][1].any():      # else no NaN bin: one direction
        lefts.append(left0 | (same & nan[:, :, None] & ~cat))
    left = jnp.stack(lefts)
    return jnp.stack([left, ~left]).astype(jnp.float32)


def find_best_split_segments(
    hist: jnp.ndarray,        # [f_phys, B, 2] bundle-space (grad, hess)
    sum_g, sum_h, count,      # scalar leaf totals
    maps,                     # segment_maps (static numpy)
    weights: jnp.ndarray,     # segment_weights(maps), [2, D, f_phys, B, B]
    pos_mask: jnp.ndarray,    # [f_phys, B] f32: feature_mask by position
    allow_split, hp: SplitHyperParams,
) -> SplitInfo:
    """``find_best_split`` over a BUNDLE-SPACE histogram: the same
    candidates (every threshold of every logical feature, both missing
    directions, categorical one-hot), the same gains and the same
    feature-major quantized election, with each logical feature's
    prefix sums taken inside its static segment of the bundle column
    and the rows at its default bin found in the column's other bins -
    what ``grow.expand`` + ``find_best_split`` give, without
    materialising (or gathering) the ``[f_log, b_log]`` logical
    histogram, and with each side of a candidate summed on its own
    (``segment_weights``).  The plain finder only: no monotone /
    smoothing / CEGB / extra-trees / sorted-subset terms
    (``grow.bundled_comb_eligible``)."""
    d_all = weights.shape[1]
    h2 = jnp.moveaxis(hist, -1, -2)                     # [P, 2, B]
    side = jnp.einsum("pcq,sdpqr->sdcpr", h2, weights,
                      precision=jax.lax.Precision.HIGHEST)
    lg, lh = side[0, :, 0], side[0, :, 1]               # [D, P, B]
    rg, rh = side[1, :, 0], side[1, :, 1]
    lc = derived_counts(lh, count, sum_h)
    rc = count - lc
    min_data = jnp.float32(hp.min_data_in_leaf)
    ok = (
        jnp.asarray(maps["valid"][:d_all])
        & (lc >= min_data) & (rc >= min_data)
        & (lh >= hp.min_sum_hessian_in_leaf)
        & (rh >= hp.min_sum_hessian_in_leaf)
        & (pos_mask[None] > 0)
        & allow_split
    )
    gains = (leaf_split_gain(lg, lh, hp) + leaf_split_gain(rg, rh, hp)
             - leaf_split_gain(sum_g, sum_h, hp) - hp.min_gain_to_split)
    flat = jnp.where(ok, gains, -jnp.inf).reshape(-1)
    qflat = selection_key(flat)
    rank = jnp.asarray(maps["rank"][:d_all]).reshape(-1)
    best = jnp.argmin(jnp.where(qflat >= jnp.max(qflat), rank,
                                jnp.int32((1 << 30) + 1))).astype(jnp.int32)
    pos = best % (rank.shape[0] // d_all)
    # no valid candidate: every gain is -inf and the election falls on
    # position 0, which may belong to no feature
    feat = jnp.maximum(jnp.asarray(maps["feat"]).reshape(-1)[pos], 0)
    blg, blh = lg.reshape(-1)[best], lh.reshape(-1)[best]
    brg, brh = rg.reshape(-1)[best], rh.reshape(-1)[best]
    return SplitInfo(
        gain=flat[best],
        feature=feat,
        threshold_bin=jnp.asarray(maps["lbin"]).reshape(-1)[pos],
        default_left=best >= rank.shape[0] // d_all,
        is_categorical=jnp.asarray(maps["cat"]).reshape(-1)[pos],
        left_sum_g=blg,
        left_sum_h=blh,
        left_count=lc.reshape(-1)[best],
        left_output=calculate_leaf_output(blg, blh, hp),
        right_output=calculate_leaf_output(brg, brh, hp),
        right_sum_g=brg,
        right_sum_h=brh,
    )
