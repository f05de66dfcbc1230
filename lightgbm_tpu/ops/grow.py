"""Device-resident leaf-wise tree growth.

Reference analog: CUDASingleGPUTreeLearner::Train
(src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp:128-253), where the
host runs the per-leaf loop and launches histogram / best-split / partition
kernels, reading back 3 scalars per split.  On TPU even that per-split
dispatch is too costly, so the WHOLE tree grows inside one jitted
``lax.fori_loop``: histogram pool, per-leaf sums, best-split records, the
row->leaf assignment vector and the tree arrays all live in HBM as loop
state; the host gets back one finished tree.

Key re-designs vs the reference:
* physical row partition kept (cuda_data_partition.cu:288-907's bit-vector +
  prefix-sum scatter) as a ``row_order`` permutation with per-leaf segments,
  compacted in static power-of-two buckets so every split is
  O(rows-in-parent) with XLA-friendly static shapes; the per-row leaf
  assignment is reconstructed ONCE per tree from the final partition;
* histogram subtraction trick kept (serial_tree_learner.cpp:287-327): only
  the smaller child is histogrammed, the sibling is parent - child;
* best-first (leaf-wise) order kept: an argmax over per-leaf cached best
  gains replaces the reference's leaf queue;
* loop-carried state is packed into few buffers and every write is
  drop-guarded instead of branching (see _GrowState) — per-split latency on
  TPU is dominated by buffer staging and serialized small ops, not FLOPs.

Tree node layout matches the reference ``Tree`` (include/LightGBM/tree.h:25):
internal nodes indexed [0, num_leaves-1), leaves encoded as ``~leaf`` in
child pointers, left child keeps the parent's leaf slot, the new right leaf
takes index ``num_leaves``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .histogram import build_histogram
from .leaf_lookup import leaf_of_position
# serving's bin-indexed bitset packer, reused verbatim so the partition
# kernels' sel membership words and the serving gather decode the SAME
# encoding (ISSUE 16)
from .predict import _members_to_words
from .split import (SplitHyperParams, SplitInfo, calculate_leaf_output,
                    cat_subset_member, find_best_split, leaf_split_gain,
                    per_feature_best_gain)
from .split import selection_key as sel_key


class TreeArrays(NamedTuple):
    """One grown tree, array-of-nodes form (reference tree.h:25)."""
    # internal nodes, [num_leaves - 1]
    split_feature: jnp.ndarray   # i32, inner (used-)feature index
    threshold_bin: jnp.ndarray   # i32
    split_gain: jnp.ndarray      # f32
    default_left: jnp.ndarray    # bool
    is_categorical: jnp.ndarray  # bool
    left_child: jnp.ndarray      # i32, node index or ~leaf
    right_child: jnp.ndarray     # i32
    internal_value: jnp.ndarray  # f32 raw output of the would-be leaf
    internal_weight: jnp.ndarray # f32 sum_hessian
    internal_count: jnp.ndarray  # f32 row count
    # leaves, [num_leaves]
    leaf_value: jnp.ndarray      # f32 raw output (shrinkage applied by boosting)
    leaf_weight: jnp.ndarray     # f32 sum_hessian
    leaf_count: jnp.ndarray      # f32
    num_leaves: jnp.ndarray      # i32 scalar, actual leaves grown
    # categorical membership per internal node, [ni, B] f32 0/1 ("bin in
    # set -> left"; reference Tree cat bitsets, tree.h:271).  Shape [1, 1]
    # when the sorted-subset search is off (one-hot sets are then implied
    # by threshold_bin).
    cat_members: jnp.ndarray
    # i32 [4], telemetry of the fused scan's one-sided histogram hook
    # (obs/counters.py): splits whose predicted smaller child was not
    # the smaller one and the rows re-histogrammed for them; splits at
    # which the hook ran at all (parents under the crossover) and the
    # parent rows it visited.  Global under the mesh learners; zeros
    # off the fused physical route.  i32 [6] under the bundled comb
    # (``side_n``): then also the splits decided by a membership set of
    # a bundle column's bins and their parent rows.  Not part of the
    # model: models/tree.py does not read it.
    side_miss: jnp.ndarray


class _GrowState(NamedTuple):
    """Loop-carried tree-growth state, PACKED into few buffers.

    TPU-tuning note: an earlier layout carried ~25 separate small arrays
    (per-leaf sums, cached best-split fields, tree node fields, ...).  The
    xplane trace showed the per-split cost dominated by HBM<->SMEM
    ``copy-start`` staging of each tiny buffer at every loop iteration —
    more time than the histogram math itself.  Packing per-leaf state into
    [L, 8] / [L, 10] matrices and tree nodes into [L-1, 10] cuts the number
    of loop-carried buffers (and their per-iteration staging copies) ~4x.

    Column layouts (f32 holds small ints / bools exactly):
      best   [L, 10]: gain, feat, bin, default_left, is_cat,
                      left {sum_g, sum_h, count}, left_out, right_out
      lstate [L, 8]:  sum_g, sum_h, count, depth, parent_node, mono_lo,
                      mono_hi, leaf_out
      nodes  [L-1, 10]: feat, bin, gain, default_left, is_cat, left_child,
                      right_child, internal {value, weight, count}
                      (child pointers use the reference ~leaf encoding)
    """
    # physical row partition (reference DataPartition, data_partition.hpp:21):
    # row_order is a permutation with each leaf's rows contiguous;
    # seg[:, 0]=begin, seg[:, 1]=rows index into it.  Lets the histogram
    # pass gather ONLY the smaller child's rows.
    row_order: jnp.ndarray       # [n] i32 ([1] dummy in physical mode)
    seg: jnp.ndarray             # [L, 2] i32
    pool: jnp.ndarray            # [L, F, 4, B] histogram pool (channel-second
                                 # padded layout; see chan4)
    best: jnp.ndarray            # [L, 10] f32
    lstate: jnp.ndarray          # [L, 8] f32
    nodes: jnp.ndarray           # [L-1, 10] f32
    used_feat: jnp.ndarray       # [L, F] f32: features used on the leaf's
                                 # path (interaction constraints)
    model_used: jnp.ndarray      # [F] f32: features used anywhere (CEGB)
    num_leaves: jnp.ndarray      # i32 scalar
    done: jnp.ndarray            # bool
    comb: jnp.ndarray            # physical mode: [n_alloc, C] permuted
                                 # row matrix ([1, 1] dummy otherwise)
    scratch: jnp.ndarray         # physical mode partition scratch
    cat_members: jnp.ndarray     # [L-1, B] f32 categorical membership
                                 # rows ([1, 1] when subset search off)
    inter: jnp.ndarray           # intermediate-monotone state [L, 3F+1]
                                 # f32: box lo | box hi | per-leaf fmask
                                 # | creation-node salt ([1, 1] when off)
    paid: jnp.ndarray            # CEGB lazy paid-rows mask [F, n] bool
                                 # ([1, 1] when off); persists ACROSS
                                 # trees via the grow return value
    side_miss: jnp.ndarray       # i32 [4]: TreeArrays.side_miss so far


# _GrowState.best column indices
_BG, _BF, _BB, _BDL, _BCAT, _BLG, _BLH, _BLC, _BLO, _BRO = range(10)
# ... and, under the bundled comb, the right child's own sums
_BRG, _BRH = 10, 11
# _GrowState.lstate column indices
_SG, _SH, _SC, _SDEP, _SPAR, _SMN, _SMX, _SOUT = range(8)


def chan4(h):
    """[..., F, B, C] channels-last histogram -> [..., F, 4, B]
    channel-second pool-row layout (channels padded to 4; the pool's
    DMA-sliced dims must be tile-aligned: bins on the 128-lane minor,
    channels on a 4-sublane multiple).  Single source of truth for the
    layout shared by grow, the pool-resident apply_find kernel, and the
    checker tools.  Histograms are (grad, hess) 2-channel since the
    count-channel removal (reference hist_t parity, bin.h:32-37)."""
    moved = jnp.moveaxis(h, -1, -2)
    pad = [(0, 0)] * (moved.ndim - 2) + [(0, 4 - moved.shape[-2]), (0, 0)]
    return jnp.pad(moved, pad)


def _pack_si(si: "SplitInfo") -> jnp.ndarray:
    """SplitInfo -> packed best-row [..., 10] (see _GrowState.best);
    [..., 12] from a finder that gives the right child's sums."""
    own_right = ([] if si.right_sum_g is None
                 else [si.right_sum_g, si.right_sum_h])
    return jnp.stack([
        si.gain,
        si.feature.astype(jnp.float32),
        si.threshold_bin.astype(jnp.float32),
        si.default_left.astype(jnp.float32),
        si.is_categorical.astype(jnp.float32),
        si.left_sum_g, si.left_sum_h, si.left_count,
        si.left_output, si.right_output,
    ] + own_right, axis=-1)


@jax.jit
def pack_tree_arrays(tas):
    """Flatten a list of TreeArrays into ONE f32 device buffer so a single
    host transfer materialises every deferred tree (each per-array pull —
    and each eager ravel/astype op — is a dispatch of its own; jit makes
    the whole pack one)."""
    parts = []
    for ta in tas:
        for x in ta:
            parts.append(jnp.ravel(x).astype(jnp.float32))
    return jnp.concatenate(parts)


def unpack_tree_arrays(flat: "jnp.ndarray", num_leaves: int, count: int,
                       cat_b: int = 0, side_n: int = 4):
    """Inverse of pack_tree_arrays: host numpy TreeArrays list."""
    import numpy as np
    L = int(num_leaves)
    ni = L - 1
    proto = _empty_tree(L, cat_b, side_n)
    flat = np.asarray(flat)
    out = []
    pos = 0
    for _ in range(count):
        fields = []
        for name, ref in zip(TreeArrays._fields, proto):
            size = int(np.prod(ref.shape)) if ref.ndim else 1
            chunk = flat[pos:pos + size]
            pos += size
            arr = chunk.reshape(ref.shape) if ref.ndim else chunk[0]
            dt = ref.dtype
            if dt == jnp.int32:
                arr = np.asarray(np.rint(arr), np.int32)
            elif dt == jnp.bool_:
                arr = np.asarray(arr) > 0.5
            else:
                arr = np.asarray(arr, np.float32)
            if not ref.ndim:
                arr = arr if np.ndim(arr) else np.asarray(arr)
            fields.append(arr)
        out.append(TreeArrays(*fields))
    assert pos == len(flat), (pos, len(flat))
    return out


def _empty_tree(num_leaves: int, cat_b: int = 0,
                side_n: int = 4) -> TreeArrays:
    ni = num_leaves - 1
    zi = lambda k: jnp.zeros((k,), jnp.int32)
    zf = lambda k: jnp.zeros((k,), jnp.float32)
    zb = lambda k: jnp.zeros((k,), jnp.bool_)
    return TreeArrays(
        split_feature=zi(ni), threshold_bin=zi(ni), split_gain=zf(ni),
        default_left=zb(ni), is_categorical=zb(ni),
        left_child=zi(ni), right_child=zi(ni),
        internal_value=zf(ni), internal_weight=zf(ni), internal_count=zf(ni),
        leaf_value=zf(num_leaves), leaf_weight=zf(num_leaves),
        leaf_count=zf(num_leaves),
        num_leaves=jnp.int32(1),
        cat_members=jnp.zeros((ni, cat_b) if cat_b else (1, 1),
                              jnp.float32),
        side_miss=zi(side_n),
    )


# physical-mode partition kernel selection.
# LGBM_TPU_PARTITION selects the single-scan kernel's per-block
# compaction: "permute" (default — butterfly-routing permutation,
# O(log R)/row, partition_kernel3) or "matmul" (the [R, R] one-hot
# contraction, O(R)/row, partition_kernel2) — bit-identical packed
# layouts at equal block rows.
# LGBM_TPU_FUSED=0 disables the fused partition+histogram split kernel
# (and the fused refresh+root-histogram in stream mode), restoring the
# separate partition / child-histogram pallas_call pair per split.
# LGBM_TPU_PART_INTERP=kernel makes the off-TPU physical path run the
# REAL scan/copyback kernels through the Pallas interpreter instead of
# the stable XLA emulation (compiled row order; equivalence-matrix
# tests use it to pin cross-scheme identity at kernel depth).
# The rows a grid step of the scan moves are no knob: the builder takes
# them from the comb's width and the scoped VMEM a kernel gets
# (partition_kernel2.scan_block_rows, ISSUE 37).
import os as _os_mod
PARTITION_IMPL = _os_mod.environ.get("LGBM_TPU_PARTITION", "permute")
if PARTITION_IMPL not in ("permute", "matmul"):
    raise ValueError(
        f"LGBM_TPU_PARTITION must be 'permute' or 'matmul', got "
        f"{PARTITION_IMPL!r}")
PART_INTERP = _os_mod.environ.get("LGBM_TPU_PART_INTERP", "")
FUSED_IMPL = _os_mod.environ.get("LGBM_TPU_FUSED", "1")
# What is sized before the device layout is final - and with it before
# the scan's block rows are known - is sized for the largest block the
# scan can take (layout.py states both arguments), on every backend:
# rows pad to whole PHYS_ROW_PAD blocks (a shard's rows under the mesh
# learners: to_device's row_pad_multiple), which every smaller block
# divides, and the comb and its scratch carry PHYS_ROW_SLACK lines past
# them, a constant, so ``n_alloc`` - part of every compiled program's
# shapes and of the measured memory peak - moves neither with the block
# the scan takes nor with the backend the planner is asked from.
from .pallas.layout import COMB_ROW_SLACK as PHYS_ROW_SLACK
from .pallas.layout import SCAN_ROWS_MAX as PHYS_ROW_PAD


_HIST_SCATTER_WARNED = set()


def _warn_hist_scatter_fallback(f_log: int, n_shards: int) -> None:
    """The reduce-scatter histogram merge needs f_log % n_shards == 0;
    anything else silently took the full-psum merge (twice the ICI
    traffic, n_shards x the search work).  Runs at TRACE time: warn
    once per (f_log, n_shards) shape and bump a host-side obs event so
    mesh bench artifacts record the slow path."""
    from ..obs.counters import events as _obs_events
    from ..utils import log
    _obs_events.record("hist_scatter_psum_fallback")
    key = (f_log, n_shards)
    if key in _HIST_SCATTER_WARNED:
        return
    _HIST_SCATTER_WARNED.add(key)
    log.warning(
        "hist_scatter: %d logical features do not divide over %d "
        "shards; falling back to the full-histogram psum merge (2x ICI "
        "traffic, %dx search work per shard).  Pad the feature count "
        "to a shard multiple (to_device col_shard_multiple / "
        "device_data.pad_features_to_shards — the gbdt data-parallel "
        "path does this automatically) to restore the reduce-scatter "
        "path.", f_log, n_shards, n_shards)


# warn-once suppression is PER RUN, not per process: obs.reset_run()
# (called between lgb.train calls, engine.py) clears these sets so a
# second training run re-reports the fallbacks ITS configuration takes
from ..obs.counters import on_reset as _obs_on_reset
from ..obs.tracer import next_phase, phase, phased
from ..obs.tracer import tracer as _obs_tracer

_obs_on_reset(_HIST_SCATTER_WARNED.clear)


def hist_scatter_eligible(hp, *, bundle=None, voting: bool = False,
                          fax=None, n_forced: int = 0,
                          cegb_coupled=None) -> bool:
    """Whether the data-parallel reduce-scatter histogram merge applies:
    every feature below needs the FULL merged histogram on each shard
    (EFB expansion, voting election, forced-split sums, per-feature
    CEGB penalties tracked against global feature ids).  Single source
    of truth for make_grow_fn, the DataParallelGrower attribute, and
    gbdt's layout/log decisions.  Cat-subset membership no longer
    blocks the scatter (ISSUE 16): the winner's [2, B] pooled row is
    recovered from its owner shard by one tiny owner-masked psum per
    split (see the member_f build in grow_core)."""
    return (bundle is None and not voting and fax is None
            and not n_forced and cegb_coupled is None
            and not (hp.use_monotone and hp.mono_intermediate))


def bundled_comb_eligible(hp, *, axis_name=None, n_forced: int = 0,
                          interaction_sets=None, cegb_coupled=None,
                          cegb_lazy=None, bynode_count: int = 0) -> bool:
    """Whether a bundled table keeps its EFB bundles IN the comb on the
    physical route (ISSUE 36): one comb column a bundle, histograms over
    bundle columns, the split finder in bundle space
    (``split.find_best_split_segments``) and the partition through a
    membership set of the bundle column's bins.  That finder is the
    plain one, so every grow option that reads a logical histogram row
    or adds a per-feature term to the gain keeps the unbundling ingest
    (``device_data.unbundle_bins``), as do the mesh learners, whose
    set-up and ledger are wired for it.  gbdt decides with it once
    (``GBDT._set_efb_form`` -> ``dd.comb_bundled``, which the routing
    decision's ``efb`` field and every priced width read) and
    make_grow_fn checks the decision it is handed against it; it reads
    the configuration, never a knob."""
    return (axis_name is None and not n_forced
            and interaction_sets is None and cegb_coupled is None
            and cegb_lazy is None and bynode_count == 0
            and not (hp.use_cat_subset or hp.use_monotone
                     or hp.use_smoothing or hp.use_cegb
                     or hp.use_extra_trees))


def bundled_split_members(bun_maps, feat, sbin, cat, nbins, padded_bins):
    """What the partition scans are told about a split of logical
    feature ``feat`` at logical bin ``sbin`` under the bundled comb:
    ``(comb column, the split is on a bundled sub-feature, [padded_bins]
    bool go-left membership of the column's bins)``.

    A split "logical bin <= sbin" on a bundled sub-feature is a SET of
    its bundle column's bins (io/bundle.py layout): its own stacked
    range up to ``sbin`` and, when its default bin is on the left,
    every bin outside the range - rows that store another sub-feature,
    or none, sit at this one's default.  An unbundled column keeps its
    threshold and needs no set; the membership returned for it is the
    one winning bin of a categorical one-hot split and empty otherwise,
    because ``sel`` carries the words at every split of the program and
    the scans read them wherever the categorical flag is up.
    ``bun_maps`` = (feat_phys, feat_offset, feat_default, is_bundled)
    device arrays; ``nbins`` the feature's logical bin count."""
    phys, off, dflt, bundled = bun_maps
    in_bun = bundled[feat]
    v = jnp.arange(int(padded_bins), dtype=jnp.int32)
    o = off[feat]
    own = (v >= o) & (v < o + nbins)
    lbin = jnp.where(own, v - o, dflt[feat])
    member = jnp.where(in_bun, lbin <= sbin, cat & (v == sbin))
    return phys[feat], in_bun, member


def _bucket_sizes(n: int, rows_per_block: int) -> list:
    """Static bucket size classes for the per-split lax.switch: halving
    from n down to a 1024-row floor (deep-tree leaves are small; the
    per-split cost is O(bucket))."""
    blk = max(min(rows_per_block, n), 1)
    stop = min(blk, 1024)
    sizes = []
    s_cur = n
    while True:
        sizes.append(s_cur)
        if s_cur <= stop:
            break
        s_cur = (s_cur + 1) // 2
    return sorted(set(sizes), reverse=True)


def make_grow_fn(
    hp: SplitHyperParams,
    *,
    num_leaves: int,
    max_depth: int = -1,
    padded_bins: int,
    rows_per_block: int = 16384,
    use_dp: bool = False,
    axis_name: str = None,
    feature_axis_name: str = None,
    voting_top_k: int = 0,
    hist_scatter: bool = False,  # data-parallel: reduce-SCATTER the
                                 # histogram over a feature-chunk axis and
                                 # search only the owned chunk (the
                                 # reference's Network::ReduceScatter +
                                 # per-rank feature ownership,
                                 # data_parallel_tree_learner.cpp:61-99,185)
    n_hist_shards: int = 1,      # static mesh size for hist_scatter
    monotone=None,           # [F] np i32 in {-1,0,1}; enables hp.use_monotone
    interaction_sets=None,   # [K, F] np bool allowed-feature sets
    cegb_coupled=None,       # [F] np f32 per-feature coupled penalties
    cegb_lazy=None,          # [F] np f32 per-feature LAZY (per-row
                             # acquisition) penalties; the grower then
                             # takes/returns a [F, n] paid-rows mask
    forced=None,             # dict(leaf, feature, bin, default_left) np arrays
    bundle=None,             # EFB mapping dict (DeviceDataset.bundle)
    bundled_comb=None,       # the EFB form the caller's route and layout
                             # were decided for (DeviceDataset.
                             # comb_bundled); the physical branch builds
                             # that form or raises.  None: a caller with
                             # no layout of its own (tools, tests)
    padded_bins_log: int = 0,  # logical bin width (defaults to padded_bins)
    bynode_count: int = 0,   # >0: sample this many features per node
    bynode_seed: int = 0,    # (ColSampler feature_fraction_bynode,
                             #  col_sampler.hpp deterministic per node)
    extra_seed: int = 6,     # extra_trees RNG seed (config extra_seed)
    debug_state: bool = False,  # grow returns (tree, leaf_id, best,
                                # lstate) for tools/ kernel debugging
    physical_bins=None,      # [n_pad, F_pad] device bins: enables the
                             # PHYSICAL partition mode (see below); the
                             # returned grow keeps the plain signature and
                             # carries the permuted row matrix internally
    stream=None,             # dict(kind, sigmoid, rate): score-resident
                             # gradient streaming (ops/pallas/stream_grad)
                             # — physical mode only; grad/hess/inbag args
                             # are ignored, gradients live in the comb
    paged=None,              # page plan dict (costmodel.page_schedule /
                             # paged.plan_pages): the comb lives as
                             # host-resident pages streamed through the
                             # double-buffered page buffers per tree
                             # (ISSUE 15) — physical serial only; the
                             # plan geometry must match the engaged
                             # comb layout exactly
    numerics: str = "off",   # NaN/Inf guardrails (ISSUE 13,
                             # resilience/numerics.py): "clamp"
                             # sanitizes grad/hess at the grow entry;
                             # "raise"/"skip" attach a device badness
                             # scalar (.last_numerics_bad) over
                             # grad/hess + the grown leaf values /
                             # split gains — where histogram and gain
                             # non-finites surface — for gbdt to act
                             # on; "off" (default) returns the exact
                             # unwrapped program (purity pin
                             # grow-numerics-off)
):
    """Build the jitted tree-growing function for a fixed dataset shape/config.

    Returns ``grow(bins, grad, hess, inbag, feature_mask, num_bins, has_nan,
    is_cat, seed) -> (TreeArrays, leaf_id)``; ``seed`` is a per-tree i32
    salt for by-node column sampling (ignored when bynode_count == 0).

    ``monotone`` / ``interaction_sets`` / ``cegb_coupled`` / ``forced`` are
    per-dataset constants folded into the trace (the reference passes them via
    Config + forced-splits JSON, serial_tree_learner.cpp:459,767-786).

    With ``axis_name`` set, the function is written for use inside
    ``shard_map`` over a row-sharded mesh axis: histograms and root sums are
    all-reduced over the axis (the data-parallel tree learner's
    ``Network::ReduceScatter`` + ``HistogramSumReducer`` merge,
    data_parallel_tree_learner.cpp:185, re-expressed as ``lax.psum`` over
    ICI).  Everything downstream (split search, tree arrays) is then
    replicated-deterministic across devices, which subsumes the reference's
    SyncUpGlobalBestSplit (parallel_tree_learner.h:191) and global leaf-count
    sync (data_parallel_tree_learner.cpp:270) with zero extra communication.
    """
    L = int(num_leaves)
    fax = feature_axis_name
    # the data-parallel learners' grow program also returns each shard's
    # own work count (TreeArrays.side_miss is global; this is not)
    shard_counter = axis_name is not None and fax is None
    if numerics not in ("off", "raise", "skip", "clamp"):
        raise ValueError(
            f"numerics must be off/raise/skip/clamp, got {numerics!r}")
    if numerics != "off" and (axis_name is not None
                              or feature_axis_name is not None):
        raise ValueError(
            "in-grow numerics sentinels are wired for the serial "
            "learner only; the mesh learners guard at the booster "
            "boundary (gbdt._before_train)")
    if numerics != "off" and debug_state:
        raise ValueError("numerics guardrails are not supported with "
                         "debug_state")
    if numerics == "clamp" and stream is not None:
        # score-resident streaming refreshes gradients in-kernel
        # inside the comb; the grad/hess args this wrapper would
        # sanitize are placeholder zeros, so "clamp" would silently
        # train unguarded — the exact failure mode the guardrails
        # exist to prevent.  raise/skip still work under streaming
        # (their post-grow leaf-value/split-gain sentinel is where
        # in-comb non-finites surface).
        raise ValueError(
            "LGBM_TPU_NUMERICS=clamp cannot guard score-resident "
            "streaming (gradients refresh in-kernel and never pass "
            "the grow entry); use raise/skip or set LGBM_TPU_STREAM=0")

    def _maybe_guard(grow_fn):
        """Opt-in numerics sentinel wrapper; numerics == "off" returns
        the callable UNTOUCHED (the grow-numerics-off purity pin)."""
        if numerics == "off":
            return grow_fn
        return _NumericsGuard(grow_fn, numerics)
    use_voting = voting_top_k > 0 and axis_name is not None
    use_ic = interaction_sets is not None
    use_cegb_pen = cegb_coupled is not None
    use_cegb_lazy = cegb_lazy is not None
    n_forced = 0 if forced is None else int(len(forced["feature"]))
    # ---- PHYSICAL partition mode ----
    # Rows live physically permuted in an [n_alloc, C] f32 HBM matrix
    # (bins | g*w h*w w | row-id bytes); each split moves the parent's
    # rows in place with the streaming partition kernel
    # (ops/pallas/partition_kernel.py) instead of gathering by a
    # row_order permutation — per-index DMA pricing made gather+scatter
    # ~23 ns/row-visit vs ~1 ns for the streaming kernel.  The reference
    # analog is CUDADataPartition's physical index movement
    # (cuda_data_partition.cu:288-907), except the DATA moves, not
    # indices, so the histogram pass reads a contiguous slice.
    physical = physical_bins is not None
    if paged is not None and not physical:
        raise ValueError(
            "the paged comb requires physical partition mode (the "
            "row_order path never holds a device-resident comb)")
    if paged is not None and axis_name is not None:
        raise ValueError(
            "the paged comb is serial-only (routing rule "
            "paged_mesh_unwired); shard the rows over a mesh instead")
    if stream is not None and not physical:
        raise ValueError(
            "score-resident gradient streaming requires physical "
            "partition mode (the scores live in the permuted row matrix)")
    if stream is not None and axis_name is not None:
        raise ValueError(
            "score-resident streaming is not yet wired for the mesh "
            "learners (scores are booster-held there)")
    # the bundle map as the CALLER saw it: the hist_scatter eligibility
    # below (routing rule scatter_efb: the mesh merge stays full-psum
    # for bundled datasets) keys on it even after the physical branch
    # consumes the map into its ingest closure
    _src_bundle = bundle
    _seg_comb = False
    if physical:
        if fax is not None:
            raise ValueError(
                "physical partition mode supports the serial and "
                "data-parallel learners only")
        if voting_top_k > 0:
            raise ValueError(
                "physical partition mode does not support the voting "
                "learner (elected-feature merges need the XLA bucket "
                "path)")
        if debug_state:
            raise ValueError(
                "debug_state is not supported in physical mode (the "
                "wrapper carries comb/scratch through the return value)")
        if hp.use_cat_subset:
            # build-time defense mirroring the cat_overwide routing
            # rule: a categorical membership bitset rides the split
            # descriptor as ceil(padded_bins/32) SMEM words appended
            # after the 8 descriptor slots (partition_kernel.SEL_MEMBER)
            # and the in-kernel word select unrolls over that count —
            # the routing model keeps wider-binned cat configs on
            # row_order, so reaching here means a caller bypassed
            # decide()
            from .pallas.layout import CAT_BITSET_WORDS, cat_bitset_fit
            _b_chk = int(padded_bins_log) or int(padded_bins)
            if not cat_bitset_fit(_b_chk):
                raise ValueError(
                    f"physical mode supports sorted-subset categorical "
                    f"splits only up to {32 * CAT_BITSET_WORDS} padded "
                    f"bins (got {_b_chk}): the membership bitset rides "
                    f"the SMEM split descriptor as "
                    f"{CAT_BITSET_WORDS} words (layout."
                    f"CAT_BITSET_WORDS); the routing model routes this "
                    f"config to the row_order path (rule cat_overwide)")
        # ---- EFB on the physical route: two comb forms ----
        # Bundled comb (ISSUE 36; bundled_comb_eligible): the comb holds
        # one column a BUNDLE, every kernel below is built at the
        # bundled geometry, the bundle map stays with the grow core -
        # its finder works in bundle space and a split on a bundled
        # sub-feature reaches the scans as a membership set of the
        # bundle column's bins (partition_kernel.SEL_MEMBER).  Expo's
        # 700 one-hot columns are 12 lanes of one plane that way, not
        # 700 lanes of six.
        # Unbundling ingest (ISSUE 12), for the grow options the
        # bundle-space finder does not cover: each bundle expands back
        # into its logical bin columns on device
        # (device_data.unbundle_bins), so the kernels and the grow core
        # see bundle=None and ordinary <= 255-bin columns in the
        # LOGICAL feature domain.
        _efb_ingest = None
        _seg_comb = bundle is not None and bundled_comb_eligible(
            hp, axis_name=axis_name, n_forced=n_forced,
            interaction_sets=interaction_sets, cegb_coupled=cegb_coupled,
            cegb_lazy=cegb_lazy, bynode_count=bynode_count)
        if bundled_comb is not None and bool(bundled_comb) != _seg_comb:
            # the route, the footprint and dd.phys_f_pad would describe
            # one form and this program be the other
            raise ValueError(
                f"the layout was decided for bundled_comb={bundled_comb} "
                f"but this grow configuration builds the "
                f"{'bundled comb' if _seg_comb else 'unbundling ingest'} "
                "(grow.bundled_comb_eligible): the caller's arguments "
                "differ from the ones the route was decided on")
        if _seg_comb:
            f_pad_p = int(physical_bins.shape[1])
            from .pallas.layout import cat_bitset_fit
            if not cat_bitset_fit(int(padded_bins)):
                raise ValueError(
                    "the bundled comb needs bundle columns of at most "
                    f"256 bins (got {int(padded_bins)}): a split on a "
                    "bundled sub-feature is a membership bitset over "
                    "them (layout.CAT_BITSET_WORDS)")
        elif bundle is not None:
            _b_log_p = int(padded_bins_log) or int(padded_bins)
            if _b_log_p > 256:
                # mirrors the non_u8_bins routing rule at the logical
                # width — the stacked bundle column width is irrelevant
                raise ValueError(
                    "physical mode requires uint8 LOGICAL bins "
                    "(max_bin <= 256); wider-binned datasets keep the "
                    "row_order path")
            from .device_data import unbundle_bins
            _efb_ingest = functools.partial(unbundle_bins, bundle=bundle)
            # kernels run at the unbundled (logical) geometry
            f_pad_p = int(len(bundle["feat_phys"]))
            padded_bins = _b_log_p
            padded_bins_log = 0
            bundle = None
        else:
            f_pad_p = int(physical_bins.shape[1])
        if _efb_ingest is None and physical_bins.dtype != jnp.uint8:
            # the kernel's column-extract and compaction matmuls run at
            # bf16 operand precision (Mosaic ignores precision=HIGHEST);
            # bin ids above 255 would round — uint16-bin datasets keep
            # the index-gather path.  (With EFB ingest the bundled
            # source may be u16; the unbundled output is u8 by
            # construction.)
            raise ValueError(
                "physical mode requires uint8 bins (max_bin <= 256)")
        if use_dp:
            raise ValueError(
                "physical mode does not support gpu_use_dp (the "
                "comb-direct histogram kernel accumulates f32; disable "
                "one of them)")
        _part_kernel_interp = PART_INTERP == "kernel"
        n_rows_p = int(physical_bins.shape[0])   # LOCAL rows (per shard)
        if stream is not None:
            from .pallas.stream_grad import stream_columns
            _n_extra = stream_columns(stream["kind"])
        else:
            # value (g*w, h*w, w) + row-id byte columns — the shared
            # constant keeps routing.resolve_layout's wide_layout
            # decision and this layout's actual column budget in step
            from .routing import NON_STREAM_EXTRA_COLS
            _n_extra = NON_STREAM_EXTRA_COLS
        if _efb_ingest is not None:
            # build-time defense mirroring the efb_overwide routing
            # rule: the routing model keeps such configs on row_order,
            # so reaching here means a caller bypassed decide()
            from .pallas.layout import MAX_COMB_COLS, comb_cols_fit
            if not comb_cols_fit(f_pad_p + _n_extra):
                raise ValueError(
                    f"EFB unbundling expands the comb layout to "
                    f"{f_pad_p + _n_extra} columns ({f_pad_p} logical "
                    f"feature columns + {_n_extra} value/rid/stream "
                    f"extras), past the {MAX_COMB_COLS}-column "
                    f"lane/VMEM budget (layout.MAX_COMB_COLS); the "
                    f"routing model routes this config to the "
                    f"row_order path (rule efb_overwide)")
        # comb storage: f32 rows at 128-lane granularity.  64-lane rows
        # do NOT work on TPU: Mosaic stores f32 HBM memrefs (1,128)-
        # tiled (a [n, 64] array is physically lane-padded to 128), so
        # every dynamic row-DMA in the partition kernel becomes a
        # 64-wide slice of a 128-wide memref and fails the "aligned to
        # tiling (128)" check — the round-3 snapshot regression.
        # bf16 storage (2x DMA + double-rate compaction matmuls) is
        # BLOCKED by Mosaic today: bf16 HBM memrefs get a forced
        # (8,128)x2 tiled layout and the partition kernel's DYNAMIC row
        # offsets (segment starts) fail "tile index divisible by 8"
        # proof — LGBM_TPU_COMB_DT=bf16 enables it anyway for when a
        # newer Mosaic lifts the restriction.
        _comb_bf16 = (_os_mod.environ.get("LGBM_TPU_COMB_DT", "f32")
                      == "bf16" and jax.default_backend() == "tpu")
        _COMB_DT = jnp.bfloat16 if _comb_bf16 else jnp.float32
        # line width from the shared layout contract (layout.py): the
        # 128-lane granularity is validated there AND by every kernel
        # builder, so the round-3 64-lane class of regression fails at
        # trace time on CPU, not at Mosaic compile time on chip.
        from .pallas.layout import (comb_layout, comb_planes, set_cols,
                                    to_rows)
        _C_PHYS = comb_layout(f_pad_p + _n_extra, _COMB_DT)
        # the comb is stored plane-major (layout.py): _PLANES matrices
        # of [lines, 128], one after the other in one array
        _PLANES = comb_planes(_C_PHYS)
        if _efb_ingest is None:
            # build-time defense mirroring the comb_overwide routing
            # rule (the efb_overwide one above covers the unbundling
            # ingest): no line reaches Mosaic wider than it stages
            from .routing import comb_stageable
            if not comb_stageable(_C_PHYS, PARTITION_IMPL):
                raise ValueError(
                    f"a comb line of {_C_PHYS} lanes ({f_pad_p} bin "
                    f"columns + {_n_extra} value/rid/stream extras) is "
                    f"past what the kernels stage through VMEM at the "
                    f"{PARTITION_IMPL} scan; the routing model routes "
                    f"this config to the row_order path (rule "
                    f"comb_overwide)")
        if PARTITION_IMPL == "permute":
            from .pallas.partition_kernel3 import \
                make_partition_perm as make_partition
        else:
            from .pallas.partition_kernel2 import \
                make_partition_ss as make_partition
        # slack lines: the longest kernel tail past the padded rows
        # (layout.COMB_ROW_SLACK: the scan's right zone + the
        # copy-back's tail block in the scratch)
        # rows a step of the comb-direct histogram reads, the tiles it
        # sweeps (one a bin plane past two planes:
        # hist_kernel2.hist_tiles) and the split of a bin its one-hots
        # take (hist_kernel2.hist_geometry's lo_n)
        from .pallas.hist_kernel2 import hist_block_rows, hist_geometry, \
            hist_tiles
        _HIST_RPB = hist_block_rows(_C_PHYS)
        _HIST_TILES = hist_tiles(f_pad_p, _C_PHYS)
        _HIST_LO_N = hist_geometry(int(padded_bins))[4]
        _n_alloc = n_rows_p + PHYS_ROW_SLACK
        if _n_alloc >= (1 << 24):
            # row ids ride in three f32 byte columns and are decoded with
            # f32 arithmetic — exact only below 2^24
            raise ValueError(
                "physical mode supports < 2^24 rows; shard larger "
                "datasets over a mesh (tree_learner=data)")
        if paged is not None:
            # the plan was priced off-chip over the same layout inputs
            # (costmodel.grow_footprint shares comb_layout); a geometry
            # mismatch means the planner and the grower disagree about
            # the engaged layout — refuse loudly rather than stream
            # wrong-shaped pages
            if (int(paged.get("C", _C_PHYS)) != _C_PHYS
                    or int(paged.get("n_alloc", _n_alloc)) != _n_alloc):
                raise ValueError(
                    f"page plan geometry (C={paged.get('C')}, n_alloc="
                    f"{paged.get('n_alloc')}) does not match the "
                    f"engaged comb layout (C={_C_PHYS}, n_alloc="
                    f"{_n_alloc}); re-plan with costmodel."
                    f"page_schedule over the engaged stream mode")
        _phys_interp = jax.default_backend() != "tpu"
        # fused partition+histogram split kernel (fused_split.py): one
        # dynamic-grid scan per split compacts the parent AND, at the
        # parents of up to _hook_cross rows a shard, accumulates one
        # child's histogram from the VMEM-resident row blocks, the
        # child the finder's record calls smaller.  The separate
        # child-histogram kernel (and its HBM re-read of the smaller
        # child's rows) has the work at the larger parents, where
        # reading the child again is cheaper than masking and
        # contracting every row of the parent, and where the record
        # named the larger child.
        from .pallas import fused_split as _fs
        from .pallas.partition_kernel import (SIDE_LEFT, SIDE_NONE,
                                              SIDE_RIGHT)
        _use_fused = (FUSED_IMPL != "0" and _fs.fused_supported(
            f_pad_p, int(padded_bins), _C_PHYS))
        # rows a grid step of the scan moves: from the comb's width and
        # the scoped VMEM (one function, equal on every shard, and the
        # same for the fused scan and the pair behind LGBM_TPU_FUSED=0:
        # a bisection knob must leave a leaf's rows in the same order).
        # Off the chip no grid step is there to amortise: the XLA
        # reference partition has no block, and the interpreter seam
        # (LGBM_TPU_PART_INTERP=kernel), whose cost is the unrolled
        # routing it traces, runs the smallest.
        _PHYS_R = _fs.SCAN_ROWS_MIN if _phys_interp else \
            _fs.scan_block_rows(_C_PHYS, scheme=PARTITION_IMPL)
        if n_rows_p % _PHYS_R != 0:
            raise ValueError(
                f"physical mode needs n_pad % {_PHYS_R} == 0 (got "
                f"{n_rows_p}); pass row_pad_multiple=grow.PHYS_ROW_PAD to "
                f"to_device")
        if paged is not None and int(paged["rows_per_page"]) % _PHYS_R:
            raise ValueError(
                f"rows_per_page={paged['rows_per_page']} must be a "
                f"multiple of the partition block R={_PHYS_R} "
                f"(LGBM_TPU_PAGE_ROWS)")
        # looked up on the module at build time (tests patch it)
        _hook_cross = _fs.hook_crossover_rows(
            f_pad_p // hist_geometry(int(padded_bins))[1])
        if _phys_interp:
            # off-TPU reference path keeps the static bucket switch (the
            # XLA emulation needs static slice sizes)
            _phys_sizes = _bucket_sizes(n_rows_p, rows_per_block)
            _ik = ({"interpret_kernel": True}
                   if _part_kernel_interp else {})
            _part_fns = {
                s: make_partition(_n_alloc, _C_PHYS, R=_PHYS_R, size=s,
                                  dtype=_COMB_DT, interpret=True, **_ik)
                for s in _phys_sizes}
        else:
            # compiled TPU: ONE dynamically-bounded kernel instance —
            # a lax.switch over static bucket sizes forces XLA to COPY
            # the whole aliased row matrix per branch per split
            # (measured: 5.4 GB/split at 10.5M rows, ~650 us/split at
            # 1M; it was the dominant per-split fixed cost)
            _phys_sizes = [n_rows_p]
            if _use_fused:
                _fused_dyn = _fs.make_fused_split(
                    _n_alloc, _C_PHYS, f_pad=f_pad_p,
                    padded_bins=int(padded_bins), R=_PHYS_R,
                    dtype=_COMB_DT, dynamic=True, scan=PARTITION_IMPL,
                    raw_hist=True)
            else:
                _part_dyn = make_partition(_n_alloc, _C_PHYS, R=_PHYS_R,
                                           dtype=_COMB_DT, dynamic=True)
        # stream mode + fused: the per-tree refresh pass ALSO builds the
        # next tree's root histogram while each block is VMEM-resident
        # (lever #5 — drops one full comb read per tree); grow then
        # takes the carried histogram instead of re-reading the matrix
        _fused_root = stream is not None and _use_fused
        # the unpaged stream program keeps no row-order leaf id: the
        # comb's score columns are the booster's score of record
        # between pulls (``pull_score`` below).  The paged comb keeps
        # the un-permute: its pages leave the device between trees.
        _lazy_score = stream is not None and paged is None

        def _comb_dot(c, weights):
            """``sum_j weights[j] * column j`` of a comb, [n_alloc]:
            one matvec a plane that holds one of the columns (a
            [n, k] column slice would lane-pad to 512 B/row, the
            round-2 OOM)."""
            import numpy as _np
            w = _np.zeros((_C_PHYS,), _np.float32)
            for j, v in weights.items():
                w[j] = v
            out = None
            for p in range(_PLANES):
                w_p = w[p * 128:(p + 1) * 128]
                if w_p.any():
                    part = jnp.matmul(
                        c[p * _n_alloc:(p + 1) * _n_alloc],
                        jnp.asarray(w_p))
                    out = part if out is None else out + part
            return out

        def _decode_rid(c):
            """The stored row-id byte columns as row ids (exact: powers
            of two x bytes <= 255, f32 accumulation < 2^24)."""
            return _comb_dot(c, {f_pad_p + 3: 65536.0, f_pad_p + 4: 256.0,
                                 f_pad_p + 5: 1.0})
        if stream is not None:
            from .pallas.stream_grad import (COL_SC, make_init,
                                             make_refresh,
                                             stream_block_rows)
            # rows a step of the init / refresh kernels: their own
            # block, from the comb's width
            _STREAM_R = stream_block_rows(_C_PHYS)
            _refresh_fn = make_refresh(
                kind=stream["kind"],
                sigmoid=float(stream.get("sigmoid", 1.0)),
                f=f_pad_p, n_alloc=_n_alloc, n_pad=n_rows_p, C=_C_PHYS,
                R=_STREAM_R, interpret=_phys_interp, dtype=_COMB_DT,
                root_hist=_fused_root, padded_bins=int(padded_bins),
                root_rpb=rows_per_block)
            _stream_init_fn = make_init(
                kind=stream["kind"],
                sigmoid=float(stream.get("sigmoid", 1.0)),
                f_real=f_pad_p, f=f_pad_p, n_alloc=_n_alloc,
                n_pad=n_rows_p, C=_C_PHYS, R=_STREAM_R,
                interpret=_phys_interp, dtype=_COMB_DT)
    if use_voting and fax is not None:
        raise ValueError("voting and feature-parallel modes are exclusive")
    if fax is not None and use_ic:
        raise ValueError(
            "interaction constraints need the global used-feature set and are "
            "not supported with the feature-parallel learner")
    if (use_voting or fax is not None) and n_forced:
        raise ValueError(
            "forced splits are not supported with feature/voting-parallel "
            "tree learners")
    if bundle is not None and fax is not None:
        raise ValueError(
            "EFB bundling and the feature-parallel learner are exclusive "
            "(bundles remap physical columns; disable one of them)")
    b_log = int(padded_bins_log) or int(padded_bins)
    if bundle is None or _seg_comb:
        # no expansion (the bundled comb's pool and finder stay in
        # bundle space): widths must agree
        b_log = int(padded_bins)
    if _seg_comb:
        from .split import (find_best_split_segments, segment_maps,
                            segment_weights)
        _seg_maps = segment_maps(bundle, f_pad_p, int(padded_bins))
        _bun_maps = (jnp.asarray(bundle["feat_phys"], jnp.int32),
                     jnp.asarray(bundle["feat_offset"], jnp.int32),
                     jnp.asarray(bundle["feat_default"], jnp.int32),
                     jnp.asarray(bundle["is_bundled"], jnp.bool_))
    elif bundle is not None:
        # EFB expansion constants (io/bundle.py layout): gather indices from
        # the physical histogram into logical feature space over the
        # (narrower) LOGICAL bin width, plus the default-bin FixHistogram
        # mask (dataset.h:676)
        import numpy as _np
        _B = padded_bins       # physical flat stride
        bun_phys = jnp.asarray(bundle["feat_phys"], jnp.int32)
        bun_off = jnp.asarray(bundle["feat_offset"], jnp.int32)
        bun_def = jnp.asarray(bundle["feat_default"], jnp.int32)
        _ks = _np.arange(b_log)[None, :]
        exp_idx = jnp.asarray(
            bundle["feat_phys"][:, None].astype(_np.int64) * _B
            + bundle["feat_offset"][:, None] + _ks, jnp.int32)
        exp_valid = jnp.asarray(_ks < bundle["num_bins_log"][:, None])
        exp_fix = jnp.asarray(
            bundle["is_bundled"][:, None]
            & (_ks == bundle["feat_default"][:, None]))
    mono_arr = None if monotone is None else jnp.asarray(monotone, jnp.int32)
    # intermediate monotone method (monotone_constraints.hpp:514): the
    # reference's recursive GoUp/GoDown tree walk re-expressed as a
    # vectorized BOX-ADJACENCY pass — each leaf carries its bin-space
    # hyper-rectangle; after every split, leaves face-adjacent across a
    # monotone split plane (exactly one disjoint feature dim, touching,
    # monotone) get their output bounds tightened by the new children's
    # ACTUAL outputs and their cached best splits recomputed from the
    # histogram pool (the walk's leaves_to_update_ + best-split
    # recompute, serial_tree_learner.cpp's ComputeBestSplitForLeaf).
    if cegb_lazy is not None and (
            axis_name is not None or feature_axis_name is not None
            or voting_top_k > 0 or physical_bins is not None
            or (hp.use_monotone and hp.mono_intermediate)):
        raise ValueError(
            "cegb_penalty_feature_lazy supports the serial row_order "
            "learner only (the per-(feature,row) paid mask is "
            "single-shard state)")
    use_mono_inter = bool(hp.use_monotone and hp.mono_intermediate)
    if use_mono_inter and (fax is not None or voting_top_k > 0):
        raise ValueError(
            "monotone_constraints_method=intermediate needs the full "
            "histogram pool on every shard and is not supported with "
            "feature/voting-parallel tree learners")
    # Pallas "apply + find" tail (ops/pallas/apply_find.py): one kernel for
    # the per-split state updates + two-children split finder.  Fast path
    # only — every gated feature falls back to the XLA tail.
    import os as _os
    _tail_env = _os.environ.get("LGBM_TPU_APPLY_IMPL", "")
    if hp.use_cat_subset and fax is not None:
        raise ValueError(
            "sorted-subset categorical splits are not supported with the "
            "feature-parallel learner (membership needs the full pooled "
            "histogram of the winning feature)")
    if hp.use_cat_subset and use_voting:
        raise ValueError(
            "sorted-subset categorical splits are not supported with the "
            "voting-parallel learner (the pooled histograms are shard-"
            "local there, so membership would diverge across shards)")
    use_scatter = (bool(hist_scatter) and axis_name is not None
                   and n_hist_shards > 1
                   and hist_scatter_eligible(
                       hp, bundle=_src_bundle, voting=use_voting,
                       fax=fax, n_forced=n_forced,
                       cegb_coupled=cegb_coupled))
    use_kernel_tail = (
        bundle is None and not use_voting and fax is None and n_forced == 0
        and not use_ic and not hp.use_cegb
        and not (hp.use_monotone and hp.mono_intermediate)
        and bynode_count == 0
        and not hp.use_cat_subset and not hp.use_extra_trees
        and not use_scatter
        and _tail_env != "xla"
        and (jax.default_backend() == "tpu"
             or _tail_env in ("pallas", "pallas_interpret")))
    ic_arr = (None if not use_ic
              else jnp.asarray(interaction_sets, jnp.float32))
    cegb_arr = (None if not use_cegb_pen
                else jnp.asarray(cegb_coupled, jnp.float32))
    lazy_arr = (None if not use_cegb_lazy
                else jnp.asarray(cegb_lazy, jnp.float32))
    if n_forced:
        fs_leaf = jnp.asarray(forced["leaf"], jnp.int32)
        fs_feat = jnp.asarray(forced["feature"], jnp.int32)
        fs_bin = jnp.asarray(forced["bin"], jnp.int32)
        fs_dl = jnp.asarray(forced["default_left"], jnp.bool_)

    # The phases of a tree (obs/tracer.py PHASES): every op below is
    # traced under exactly one, the code cut at the seams it already
    # has.  Whatever crosses the mesh scopes itself ``merge`` where it
    # is written (the root's own merges too), so no call site can
    # leave a collective out.
    @phase("merge")
    def _allreduce_sum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    @phased
    def grow_core(bins, comb_in, scratch_in, grad, hess, inbag,
                  feature_mask, num_bins, has_nan, is_cat, seed,
                  stream_rate=None, paid_in=None, root_hist_in=None):
        if physical:
            # stream mode takes no gradient inputs — the row count is the
            # static physical layout's
            n = n_rows_p if stream is not None else grad.shape[0]
            f = f_pad_p
        else:
            n, f = bins.shape   # f = LOCAL feature count (feature sharding)
        b = b_log           # logical (pool / split-search) bin width
        f_log = num_bins.shape[0]   # logical features (== f without EFB)
        # per-tree start: on the stream route the comb arrives fresh
        # and this is small; off it, the g / h / w gather by row id,
        # the comb write and the root histogram are here
        next_phase("root")
        inbag = inbag.astype(jnp.float32)

        def expand(h):
            """Physical -> logical histogram (EFB): gather every logical
            feature's stacked bin range out of its bundle column, then
            reconstruct the default bin from the leaf totals (the
            Dataset::FixHistogram trick, dataset.h:676).  Linear in h, so
            the parent-minus-child subtraction commutes with it."""
            if bundle is None or _seg_comb:
                return h
            nch = h.shape[-1]
            tot = jnp.sum(h[0], axis=0)     # leaf totals (any column)
            flat = h.reshape(-1, nch)
            gidx = jnp.minimum(exp_idx, flat.shape[0] - 1)
            hl = jnp.where(exp_valid[..., None], flat[gidx], 0.0)
            fix = tot[None, None, :] - jnp.sum(hl, axis=1, keepdims=True)
            return jnp.where(exp_fix[..., None], fix, hl)

        # feature-chunk ownership for the split SEARCH: under the
        # feature-parallel learner the chunk is this shard's columns; in
        # data-parallel hist_scatter mode it is this shard's slice of the
        # reduce-scattered histogram (data_parallel_tree_learner.cpp:
        # 61-99,185 per-rank feature ownership).  Either way the search
        # covers f_search features starting at axis_index * f_search and
        # the winner is elected by the same pmax allreduce (sync_best).
        # non-divisible feature counts fall back to the psum merge like
        # every other unsupported config (callers that want the scatter
        # guarantee divisibility via to_device col_pad_multiple) — the
        # fallback is no longer silent: it warns once per shape and
        # bumps an obs event counter so mesh bench artifacts record
        # that the run took the slow full-psum merge (ROADMAP item 4:
        # 28 features on 8 shards takes it)
        scatter_on = use_scatter and f_log % n_hist_shards == 0
        if use_scatter and not scatter_on:
            _warn_hist_scatter_fallback(int(f_log), int(n_hist_shards))
        if scatter_on:
            search_ax = axis_name
            f_search = f_log // n_hist_shards
        else:
            search_ax = fax
            f_search = f
        if search_ax is not None:
            _sc0 = (jax.lax.axis_index(search_ax).astype(jnp.int32)
                    * f_search)

            def chunk(a):
                return (None if a is None else
                        jax.lax.dynamic_slice_in_dim(a, _sc0, f_search))
        else:
            def chunk(a):
                return a

        # constraint constants are global [F_pad]; the chunked finder
        # sees only its shard's slice
        if search_ax is not None and (mono_arr is not None or use_cegb_pen):
            mono_loc = chunk(mono_arr)
            cegb_loc = chunk(cegb_arr)
        else:
            mono_loc, cegb_loc = mono_arr, cegb_arr

        if hp.use_extra_trees:
            # deterministic per (extra_seed, tree, node), like the
            # reference's per-learner CUDARandom streams
            _et_base = jax.random.fold_in(
                jax.random.PRNGKey(extra_seed), seed)

        if _seg_comb:
            # once a tree, outside the split loop: the contraction
            # weights of the bundle-space finder and the tree's feature
            # mask by histogram position (the one gather, f_phys x B
            # elements; the eligible configurations mask by tree only)
            _seg_w = segment_weights(_seg_maps)
            _seg_fmask = jnp.take(
                jnp.concatenate([feature_mask.astype(jnp.float32),
                                 jnp.zeros((1,), jnp.float32)]),
                jnp.asarray(_seg_maps["feat"]), mode="wrap")

        def finder(hist, sg, sh, cnt, depth, num_bins, has_nan, is_cat,
                   fmask, mn, mx, pout, cegb_pen, rkey):
            allow = (jnp.asarray(True) if max_depth <= 0
                     else (depth < max_depth))
            if _seg_comb:
                return find_best_split_segments(
                    hist, sg, sh, cnt, _seg_maps, _seg_w, _seg_fmask,
                    allow, hp)
            if scatter_on:
                # the histogram arrives pre-chunked (psum_scatter);
                # metadata and masks are global and slice here
                num_bins, has_nan, is_cat = (chunk(num_bins),
                                             chunk(has_nan),
                                             chunk(is_cat))
                fmask = chunk(fmask)
                cegb_pen = (chunk(cegb_pen) if cegb_pen is not None
                            else None)
            return find_best_split(hist, sg, sh, cnt, num_bins, has_nan,
                                   is_cat, fmask, allow, hp,
                                   monotone=mono_loc, mn=mn, mx=mx,
                                   parent_output=pout, depth=depth,
                                   cegb_penalty=cegb_pen,
                                   rand_key=rkey)

        @phase("merge")
        def sync_best(si: SplitInfo) -> SplitInfo:
            """Global best split across feature chunks: the reference's
            SyncUpGlobalBestSplit allreduce (parallel_tree_learner.h:191)
            as pmax-by-gain + winner broadcast over the chunk axis.
            Feature indices become global.  Works elementwise, so the same
            code serves root scalars and the vmapped child pairs."""
            if search_ax is None:
                return si
            ax_i = jax.lax.axis_index(search_ax).astype(jnp.int32)
            si = si._replace(feature=si.feature + ax_i * f_search)
            # election over the QUANTIZED gain key (split.selection_key):
            # each shard's winner gain carries reduction-order noise
            # relative to the serial learner's, so the cross-shard
            # compare must use the same ulp-tolerant key the in-chunk
            # finder used; ties then resolve to the lowest shard ==
            # lowest global feature index (chunks are contiguous), the
            # reference SplitInfo "smaller feature wins" ordering.
            gq = sel_key(si.gain)
            gmax = jax.lax.pmax(gq, search_ax)
            cand = jnp.where(gq >= gmax, ax_i, jnp.int32(1 << 30))
            win = jax.lax.pmin(cand, search_ax)  # tie-break: lowest shard
            iw = ax_i == win
            def bc(x):
                return jax.lax.psum(
                    jnp.where(iw, x, jnp.zeros_like(x)), search_ax)
            return SplitInfo(
                gain=bc(si.gain),
                feature=bc(si.feature),
                threshold_bin=bc(si.threshold_bin),
                default_left=bc(si.default_left.astype(jnp.int32)) > 0,
                is_categorical=bc(si.is_categorical.astype(jnp.int32)) > 0,
                left_sum_g=bc(si.left_sum_g),
                left_sum_h=bc(si.left_sum_h),
                left_count=bc(si.left_count),
                left_output=bc(si.left_output),
                right_output=bc(si.right_output),
            )

        if use_voting:
            el_k = min(2 * voting_top_k, int(num_bins.shape[0]))
            top_k = min(voting_top_k, int(num_bins.shape[0]))

            @phase("merge")
            def vote_sync(h_loc, fmask, cegb_pen, leaf_cnt):
                """PV-tree histogram merge (voting_parallel_tree_learner.cpp
                :151 GlobalVoting + :184 CopyLocalHistogram): each shard
                votes its local top-k features by gain, the global top-2k
                by votes are elected, and ONLY their histogram slices are
                all-reduced — comm volume O(2k*B) instead of O(F*B).
                Votes respect the caller's feature mask (column sampling /
                interaction constraints) so masked features can't occupy
                elected slots."""
                tot = jnp.sum(h_loc[0], axis=0)   # local leaf totals [2]
                g = per_feature_best_gain(
                    h_loc, tot[0], tot[1], leaf_cnt, num_bins, has_nan,
                    is_cat, fmask, hp, monotone=mono_loc,
                    cegb_penalty=cegb_pen)
                topv, topi = jax.lax.top_k(g, top_k)
                w = jnp.isfinite(topv).astype(jnp.float32)
                votes = jnp.zeros((f_log,), jnp.float32).at[topi].add(w)
                votes = jax.lax.psum(votes, axis_name)
                _, el_idx = jax.lax.top_k(votes, el_k)
                h_sel = jax.lax.psum(h_loc[el_idx], axis_name)
                h_m = jnp.zeros_like(h_loc).at[el_idx].set(h_sel)
                msk = jnp.zeros((f_log,), jnp.float32).at[el_idx].set(1.0)
                return h_m, msk

        # ---- bucketed smaller-child histogram ----
        # The reference histograms only the smaller leaf's rows
        # (serial_tree_learner.cpp:287-327).  XLA needs static shapes, so
        # a lax.switch picks the smallest bucket class >= rows-in-parent;
        # every branch is one partition + histogram pass.  Cost per split
        # drops from O(n) to O(rows-in-parent), the same asymptotics as
        # the reference.
        sizes = _phys_sizes if physical else _bucket_sizes(
            n, rows_per_block)
        sizes_arr = jnp.asarray(sizes, jnp.int32)

        if physical and stream is not None:
            # score-resident streaming: comb arrives with this tree's
            # g*w/h*w/w columns already fresh (the init kernel at first
            # call, the end-of-grow refresh pass thereafter) — no per-tree
            # gather by row id and no [n, k<128] lane-padded temporaries
            # (each would materialise at 512 B/row and OOM 10.5M rows).
            comb = comb_in
            if _phys_interp:
                # slack rows hold garbage copies (nonzero w); the XLA
                # reference path has no row window, so mask by position
                comb_l = to_rows(comb, _C_PHYS)
                pos_al = jnp.arange(_n_alloc, dtype=jnp.int32)
                gvals = (jax.lax.slice(comb_l, (0, f), (_n_alloc, f + 3))
                         * (pos_al < n).astype(jnp.float32)[:, None])
                bins_c = jax.lax.slice(comb_l, (0, 0), (_n_alloc, f))
            else:
                gvals = bins_c = None
            use_bf16_comb = False
            ncols = f + 3
        elif physical:
            # refresh the per-row value columns of the permuted row matrix
            # for this tree's gradients: ONE [n] gather by the stored row
            # ids (vs a gather per split in the row_order design), then an
            # in-place column update on the donated buffer.  Slack rows
            # ([n, n_alloc)) hold garbage copies from partition write
            # tails; their weights are zeroed by position so they never
            # contribute.
            pos_al = jnp.arange(_n_alloc, dtype=jnp.int32)
            # rid decode as ONE matvec:
            # a [n, 3] column slice would lane-pad to 512 B/row (5.4 GB
            # at 10.5M rows — the round-2 OOM).  The weighted sum is
            # exact at bf16 operand precision (powers of two x bytes
            # <= 255, f32 accumulation < 2^24).
            ridx = _decode_rid(comb_in).astype(jnp.int32)
            gv0 = jnp.stack([grad * inbag, hess * inbag, inbag], axis=1)
            gvp = jnp.take(gv0, jnp.clip(ridx, 0, n - 1), axis=0)
            gvp = gvp * (pos_al < n).astype(jnp.float32)[:, None]
            if not _phys_interp:
                # round ONCE to bf16: on TPU every histogram matmul and
                # every partition move multiplies values at bf16 operand
                # precision, so the root sums (sg0/sh0 below) must come
                # from the same rounded values or they disagree with the
                # pool histograms at bf16-noise scale (same policy as the
                # non-physical bf16 comb).  Off-TPU the interpret path
                # multiplies exact f32 — rounding would only add noise.
                # reduce_precision, NOT an astype round-trip: XLA's
                # excess-precision pass elides convert chains inside
                # large fusions (verified on-device — the round-trip was
                # a silent no-op here).
                gvp = jax.lax.reduce_precision(gvp, 8, 7)
            comb = set_cols(comb_in, gvp, f, _C_PHYS)
            gvals = gvp                     # root histogram values
            # full-width bins slice only for the off-TPU reference path;
            # on TPU the comb-direct kernel reads the matrix in place
            bins_c = (jax.lax.slice(to_rows(comb, _C_PHYS), (0, 0),
                                    (_n_alloc, f))
                      if _phys_interp else None)
            use_bf16_comb = False
            ncols = f + 3
        else:
            # one read-only [n, F+2] (bins..., g*w, h*w) matrix per
            # tree so each bucket pass does a SINGLE row gather: XLA row
            # gathers cost ~13ns per INDEX regardless of row width on
            # TPU, so one combined gather beats separate bins + values
            # gathers ~2x.  (Histograms are (grad, hess) pairs like the
            # reference's hist_t, bin.h:32-37; counts derive from
            # hessians in the finder.)  Read-only by design — loop-carried buffers
            # this size get copied by XLA on every dynamic update (a
            # NAIVE XLA physically-permuted variant measured 2.5x SLOWER
            # end-to-end for exactly that reason; the pallas physical
            # mode above avoids the copies with manual DMA).
            gvals = jnp.stack([grad * inbag, hess * inbag], axis=1)
            # bf16 on TPU: bins are exact in bf16 only up to 255 (8
            # mantissa bits), so the combined matrix is bf16 ONLY for
            # uint8 bins (max_bin <= 256); uint16 bins keep f32.
            # Env-gate: LGBM_TPU_COMB_BF16=0 forces f32.
            use_bf16_comb = (
                bins.dtype == jnp.uint8
                and jax.default_backend() == "tpu"
                and _os.environ.get("LGBM_TPU_COMB_BF16", "1") != "0")
            if use_bf16_comb:
                # ONE value precision everywhere: the small-bucket path
                # reads bf16 values from comb, so round gvals once and
                # use the rounded values for the root histogram and large
                # buckets too — otherwise the parent-minus-child
                # subtraction trick mixes f32 and bf16-rounded histograms
                # (documented tradeoff vs the reference's
                # double-precision hist, bin.h:32).
                # reduce_precision, not an astype round-trip (XLA's
                # excess-precision pass elides convert chains in fusions)
                gvals = jax.lax.reduce_precision(gvals, 8, 7)
            comb_dt = jnp.bfloat16 if use_bf16_comb else jnp.float32
            comb = jnp.concatenate(
                [bins.astype(comb_dt), gvals.astype(comb_dt)], axis=1)
            ncols = f + 2
        use_tail = use_kernel_tail
        if use_tail:
            from .pallas.apply_find import (build_finder_consts,
                                            make_apply_find,
                                            make_apply_find_pool,
                                            tail_supported)
            # large F*B finder footprints exceed the safe scoped-VMEM
            # budget; fall back to the XLA tail there
            use_tail = tail_supported(f_log, b)
        if use_tail:
            # monotone constants for the constrained tail (basic method;
            # zeros when monotone is off — the static hp flags gate the
            # kernel's constrained code).  The per-feature signs ride as
            # row 4 of finder_consts (pre-broadcast over bins) plus an
            # SMEM copy for the winning-feature scalar read.
            finder_consts = build_finder_consts(num_bins, has_nan, is_cat,
                                                b, monotone=mono_arr)
            iscat_i = is_cat.astype(jnp.int32)
            if mono_arr is not None:
                mono_s_t = mono_arr[:f_log].astype(jnp.int32)
            else:
                mono_s_t = jnp.zeros((f_log,), jnp.int32)
            _tail_interp = (jax.default_backend() != "tpu"
                            or _tail_env == "pallas_interpret")
            # compiled TPU: pool-resident kernel (subtraction trick +
            # pool row DMA in-kernel); interpret: plain kernel, pool ops
            # stay in XLA.  LGBM_TPU_POOL_TAIL=0 falls back to the plain
            # compiled kernel (bisection knob for Mosaic regressions in
            # the pool DMA path).
            tail_pool = (not _tail_interp
                         and _os.environ.get("LGBM_TPU_POOL_TAIL",
                                             "1") != "0")
            if tail_pool:
                apply_find_pool = make_apply_find_pool(
                    hp, L=L, f=f_log, b=b, max_depth=max_depth)
            else:
                apply_find = make_apply_find(
                    hp, L=L, f=f_log, b=b, max_depth=max_depth,
                    interpret=_tail_interp)
        else:
            tail_pool = False

        if bynode_count > 0:
            # per-node column sampling (ColSampler feature_fraction_bynode,
            # col_sampler.hpp): deterministic per (seed, tree, node)
            _k_bynode = min(bynode_count, int(num_bins.shape[0]))
            _base_key = jax.random.fold_in(
                jax.random.PRNGKey(bynode_seed), seed)

            def node_fmask(base, salt):
                r = jax.random.uniform(
                    jax.random.fold_in(_base_key, salt),
                    (int(num_bins.shape[0]),))
                r = jnp.where(base > 0, r, -jnp.inf)
                _, idx = jax.lax.top_k(r, _k_bynode)
                m = jnp.zeros((int(num_bins.shape[0]),),
                              jnp.float32).at[idx].set(1.0)
                return base * m
        else:
            def node_fmask(base, salt):
                return base

        @phase("merge")
        def merge_kernel_hist(h):
            """Collective tail for kernel-produced histograms (the
            physical comb-direct path bypasses hist_merge): the
            reference's ReduceScatter/allreduce merge applied to the
            already-built local histogram."""
            if scatter_on:
                return jax.lax.psum_scatter(
                    h, axis_name, scatter_dimension=0, tiled=True)
            if axis_name is not None:
                return jax.lax.psum(h, axis_name)
            return h

        def hist_merge(bins_, vals_, blk_):
            h = build_histogram(
                bins_, vals_, padded_bins=padded_bins,
                rows_per_block=blk_, use_dp=use_dp)
            with phase("merge"):
                if scatter_on:
                    # the reference's Network::ReduceScatter +
                    # HistogramSumReducer
                    # (data_parallel_tree_learner.cpp:185) verbatim:
                    # each shard receives ONLY its owned feature chunk
                    # of the merged histogram — half the ICI traffic of
                    # a full psum and 1/n_shards the downstream search
                    # work
                    return jax.lax.psum_scatter(
                        h, axis_name, scatter_dimension=0, tiled=True)
                if axis_name is not None and not use_voting:
                    # full-histogram merge as one psum over ICI.  In
                    # voting mode the merge is deferred to vote_sync so
                    # only elected features' histograms ride the
                    # interconnect.
                    h = jax.lax.psum(h, axis_name)
            return h

        # ---- root ----
        if physical and stream is not None and _fused_root:
            # fused stream mode: the root histogram arrived with the
            # call — the previous tree's refresh pass accumulated it
            # from the very blocks it was rewriting (tree 0's comes
            # from the wrapper's one-time init call).  Same rows, same
            # per-block arithmetic; the refresh groups f32 partial sums
            # in R-row blocks where the standalone kernel uses
            # rows_per_block — identity on chip rests on that grouping
            # difference washing out (tpu_smoke's digest gate is the
            # arbiter; see PERF_NOTES round 4).
            root_hist = root_hist_in
        elif physical and not _phys_interp:
            from .pallas.hist_kernel2 import build_histogram_comb
            root_hist = build_histogram_comb(
                comb, jnp.int32(0), jnp.int32(0), jnp.int32(n),
                f_pad=f, size=n, padded_bins=padded_bins,
                rows_per_block=min(rows_per_block, _HIST_RPB),
                planes=_PLANES)
            root_hist = merge_kernel_hist(root_hist)
        else:
            root_hist = expand(hist_merge(
                bins_c if physical else bins, gvals[:, :2],
                rows_per_block))
        # root grad/hess allreduce (data_parallel_tree_learner.cpp:126-152);
        # sums come from the (possibly bf16-rounded) gvals so the root
        # scalars are consistent with the histograms built from them.  In
        # stream mode there is no gvals array — every row lands in exactly
        # one bin of feature 0, so that feature's bin totals ARE the root
        # sums (the Dataset::FixHistogram totals trick, dataset.h:676).
        if physical and stream is not None and not _phys_interp:
            # stream mode: no gvals array; feature 0's bin totals ARE the
            # root (g, h) sums (FixHistogram totals trick, dataset.h:676)
            # and the row count is a static config constant (stream
            # excludes bagging; n here is the PADDED row count — slack
            # rows carry zero weight and must not count)
            tot0 = jnp.sum(root_hist[0], axis=0)   # [2]
            sg0, sh0 = tot0[0], tot0[1]
            c0 = c0_loc = jnp.float32(int(stream["count"]))
        elif physical:
            # physical gvals keeps (g*w, h*w, w) columns; w is the
            # validity/bag weight (in stream mode the inbag arg is a
            # dummy — the w column is the only count source)
            sg0 = _allreduce_sum(jnp.sum(gvals[:, 0]))
            sh0 = _allreduce_sum(jnp.sum(gvals[:, 1]))
            c0_loc = jnp.sum(gvals[:, 2])
            c0 = _allreduce_sum(c0_loc)
        else:
            sg0 = _allreduce_sum(jnp.sum(gvals[:, 0]))
            sh0 = _allreduce_sum(jnp.sum(gvals[:, 1]))
            c0_loc = jnp.sum(inbag)
            c0 = _allreduce_sum(c0_loc)
        root_out = calculate_leaf_output(sg0, sh0, hp)
        ninf32 = jnp.float32(-jnp.inf)
        pinf32 = jnp.float32(jnp.inf)
        # the root may only use features that appear in SOME interaction set
        root_fmask = (feature_mask * jnp.max(ic_arr, axis=0)
                      if use_ic else feature_mask)
        root_nmask = node_fmask(root_fmask, 0)
        if use_cegb_lazy:
            # CalculateOndemandCosts at the root: penalty[f] x #in-bag
            # rows not yet paid for f (cost_effective_gradient_boosting
            # .hpp:139-163); the coupled part joins below
            u0 = jnp.sum((1.0 - paid_in.astype(jnp.float32))
                         * inbag[None, :], axis=1)           # [F]
            lazy_root = lazy_arr * u0
        else:
            lazy_root = None
        if use_voting:
            # the vote must see the SAME (by-node-sampled) mask the finder
            # will use, like every child node
            root_merged, root_vmask = vote_sync(
                root_hist, root_nmask, cegb_loc if use_cegb_pen else None,
                c0)
        else:
            root_merged, root_vmask = root_hist, None
        pen_root = cegb_loc if use_cegb_pen else None
        if use_cegb_lazy:
            pen_root = (lazy_root if pen_root is None
                        else pen_root + lazy_root)
        si0 = finder(root_merged, sg0, sh0, c0, jnp.int32(0),
                     num_bins, has_nan, is_cat,
                     root_nmask * root_vmask if use_voting else root_nmask,
                     ninf32, pinf32, root_out,
                     pen_root,
                     jax.random.fold_in(_et_base, 0)
                     if hp.use_extra_trees else None)
        si0 = sync_best(si0)

        # (the bundled comb pools bundle-space histograms: its finder
        # reads them as they are)
        f_pool = (f_search if scatter_on
                  else f if _seg_comb else f_log)
        # pool layout [L, F, 4, B] (channel-second, padded to 4): the
        # pool-resident kernel DMA-slices rows, so the minor dim must be
        # the 128-aligned bin axis and the channel dim a sublane-tile
        # multiple (Mosaic: second-minor aligned to 4)
        pool = jnp.zeros((L, f_pool, 4, b), jnp.float32).at[0].set(
            chan4(root_hist))
        ni = L - 1
        best0 = jnp.full((L, 12 if _seg_comb else 10), -jnp.inf,
                         jnp.float32)
        best0 = best0.at[:, _BF:].set(0.0).at[0].set(_pack_si(si0))
        lstate0 = jnp.zeros((L, 8), jnp.float32)
        lstate0 = lstate0.at[0].set(jnp.stack([
            sg0, sh0, c0, jnp.float32(0), jnp.float32(-1),
            ninf32, pinf32, root_out]))
        lstate0 = (lstate0.at[1:, _SPAR].set(-1.0)
                   .at[1:, _SMN].set(-jnp.inf).at[1:, _SMX].set(jnp.inf))
        state = _GrowState(
            row_order=(jnp.zeros((1,), jnp.int32) if physical
                       else jnp.arange(n, dtype=jnp.int32)),
            seg=jnp.zeros((L, 2), jnp.int32).at[0, 1].set(n),
            pool=pool,
            best=best0,
            lstate=lstate0,
            nodes=jnp.zeros((ni, 10), jnp.float32),
            used_feat=jnp.zeros((L, f_log), jnp.float32),
            model_used=jnp.zeros((f_log,), jnp.float32),
            num_leaves=jnp.int32(1),
            done=jnp.asarray(si0.gain <= 0.0) if not n_forced
            else jnp.asarray(False),
            comb=comb if physical else jnp.zeros((1, 1), jnp.float32),
            scratch=(scratch_in if physical
                     else jnp.zeros((1, 1), jnp.float32)),
            cat_members=jnp.zeros((ni, b) if hp.use_cat_subset else (1, 1),
                                  jnp.float32),
            inter=(jnp.concatenate([
                jnp.zeros((L, f_log), jnp.float32),            # box lo
                # padded features (num_bins == 0) must read as ALWAYS
                # overlapping ([0, 0]), not inverted-empty ([0, -1]) —
                # an inverted interval counts as "disjoint" in every
                # adjacency test and silently disables the whole pass
                jnp.broadcast_to(
                    jnp.maximum(num_bins - 1, 0).astype(jnp.float32),
                    (L, f_log)),                               # box hi
                jnp.broadcast_to(root_nmask, (L, f_log)),      # fmask
                jnp.zeros((L, 1), jnp.float32)], axis=1)       # salt
                   if use_mono_inter else jnp.zeros((1, 1), jnp.float32)),
            paid=(paid_in if use_cegb_lazy
                  else jnp.zeros((1, 1), jnp.bool_)),
            side_miss=jnp.zeros((6 if _seg_comb else 4,), jnp.int32),
        )

        def body(i, st: _GrowState) -> _GrowState:
            # (traced inside ``while_body``, whose cursor the seams
            # below move: a split starts as ``glue``)
            # NOTE: the body is UNCONDITIONAL — no lax.cond identity branch.
            # When `done` flips on in this very iteration, every state write
            # is routed to an out-of-bounds index and dropped
            # (mode="drop"), and the row masks go all-False so the
            # partition writes back identical values.  The surrounding
            # while_loop then exits.  (An earlier lax.cond(done, id, split)
            # structure forced XLA to stage/copy the whole state tuple —
            # including the 25 MB histogram pool — at the branch boundary
            # every split.)
            if n_forced:
                # forced splits (serial_tree_learner.cpp:459 ForceSplits):
                # the first n_forced iterations split a pre-scheduled
                # (leaf, feature, bin); sums come from the leaf's pooled
                # histogram.  Invalid forced splits (an empty child) fall
                # back to normal best-split for that iteration.
                fi = jnp.minimum(i, n_forced - 1)
                f_leaf, f_feat = fs_leaf[fi], fs_feat[fi]
                f_bin, f_dl = fs_bin[fi], fs_dl[fi]
                row = st.pool[f_leaf, f_feat][:2]           # [2, B]
                cum = jnp.cumsum(row, axis=1)
                nanb = jnp.maximum(num_bins[f_feat] - 1, 0)
                nan_ghc = jnp.where(has_nan[f_feat], row[:, nanb], 0.0)
                f_sums = cum[:, f_bin] + jnp.where(f_dl, nan_ghc, 0.0)
                f_lg, f_lh = f_sums[0], f_sums[1]
                from .split import derived_counts as _dcnt
                f_lc = _dcnt(f_lh, st.lstate[f_leaf, _SC],
                             st.lstate[f_leaf, _SH])
                f_rc = st.lstate[f_leaf, _SC] - f_lc
                use_forced = (i < n_forced) & (f_lc > 0) & (f_rc > 0)
            else:
                use_forced = jnp.asarray(False)

            # leaf election over the quantized gain key (split.
            # selection_key): same ulp-tolerance + deterministic
            # tie-break (lowest leaf index) as the split finder, so
            # every learner grows leaves in the same order
            best_leaf = jnp.argmax(sel_key(st.best[:, _BG])).astype(
                jnp.int32)
            leaf = (jnp.where(use_forced, f_leaf, best_leaf)
                    if n_forced else best_leaf)
            brow = st.best[leaf]                       # [10]
            lrow = st.lstate[leaf]                     # [8]
            done = (brow[_BG] <= 0.0) & ~use_forced

            node = i
            right_leaf = st.num_leaves
            feat = brow[_BF].astype(jnp.int32)
            sbin = brow[_BB].astype(jnp.int32)
            dl = brow[_BDL] > 0.5
            cat = brow[_BCAT] > 0.5
            if n_forced:
                feat = jnp.where(use_forced, f_feat, feat)
                sbin = jnp.where(use_forced, f_bin, sbin)
                dl = jnp.where(use_forced, f_dl, dl)
                cat = jnp.where(use_forced, False, cat)

            if hp.use_cat_subset:
                # sorted-subset split: threshold_bin encodes (dir, k) as
                # B*(1+dir) + (k-1), >= B distinguishing it from one-hot
                # thresholds; membership is recomputed from the parent's
                # pooled histogram with the same deterministic ranking
                # the finder used.  One-hot categorical splits record a
                # one-hot row so the same member table drives every cat
                # decision downstream.
                is_sub = cat & (sbin >= b)
                d_sub = jnp.clip(sbin // b - 1, 0, 1)
                k_sub = sbin % b + 1
                if scatter_on:
                    # reduce-scattered pool: each shard holds only its
                    # owned feature chunk, so the winner's [2, B] row
                    # lives on ONE shard — recover it with an
                    # owner-masked psum (one [2, B] f32 allreduce per
                    # split; the reference instead keeps the full
                    # merged histogram everywhere).  Every shard then
                    # derives the identical member table, which is what
                    # keeps the replicated tree state deterministic.
                    lf_h = feat - _sc0
                    own_h = (lf_h >= 0) & (lf_h < f_search)
                    hrow_loc = st.pool[
                        leaf, jnp.clip(lf_h, 0, f_search - 1)][:2]
                    with phase("merge"):
                        hrow = jax.lax.psum(
                            jnp.where(own_h, hrow_loc, 0.0), search_ax)
                else:
                    hrow = st.pool[leaf, feat][:2]   # [2, B]
                from .split import derived_counts as _dcnt2
                hc_row = _dcnt2(hrow[1], lrow[_SC], lrow[_SH])
                mem_sub = cat_subset_member(
                    hrow[0], hrow[1], hc_row, num_bins[feat],
                    k_sub, d_sub, hp)
                onehot_b = jnp.arange(b, dtype=jnp.int32) == sbin
                member_f = (jnp.where(is_sub, mem_sub, onehot_b)
                            & cat).astype(jnp.float32)   # [B]

            next_phase("partition")
            # what the partition scans are told (partition_kernel.SEL_*):
            # the comb column, whether the go-left bit is a membership
            # test, and the membership words when sel carries them
            sel_col, sel_cat, sel_words = feat, cat, None
            if physical and hp.use_cat_subset:
                sel_words = _members_to_words(member_f[None])[0]
            if _seg_comb:
                sel_col, in_bun, member_b = bundled_split_members(
                    _bun_maps, feat, sbin, cat, num_bins[feat],
                    int(padded_bins))
                sel_cat = cat | in_bun
                sel_words = _members_to_words(
                    member_b.astype(jnp.float32)[None])[0]

            if fax is not None:
                ax_i = jax.lax.axis_index(fax).astype(jnp.int32)
                lf = feat - ax_i * f
                owner = (lf >= 0) & (lf < f)
                lfc = jnp.clip(lf, 0, f - 1)

            # ---- fused partition + smaller-child histogram, all inside
            # one bucket sized to the PARENT leaf's rows ----
            # Everything per-split is O(rows-in-parent): slice the
            # parent's segment of row_order into a static power-of-two
            # bucket (lax.switch), compute go-left bits, stable-compact
            # left|right (DataPartition::Split / SplitInnerKernel,
            # cuda_data_partition.cu:907), scatter the right child's
            # leaf ids, and histogram the smaller child from the
            # already-gathered bucket rows (the reference's smaller-leaf
            # pass, serial_tree_learner.cpp:287-327).
            s0 = st.seg[leaf, 0]
            par_cnt = st.seg[leaf, 1]
            if axis_name is not None:
                with phase("merge"):
                    par_sel = jax.lax.pmax(par_cnt, axis_name)
                    # the parent's GLOBAL rows (the reference's global
                    # leaf counts, data_parallel_tree_learner.cpp:270)
                    par_g = jax.lax.psum(par_cnt, axis_name)
            else:
                par_sel = par_g = par_cnt
            # the child the finder's record says is smaller (its left
            # count is the reference's hessian-derived estimate,
            # split.derived_counts; global under the mesh learners, so
            # every shard names the same side without a psum): the
            # fused scan's hook histograms that one, and a split where
            # the exact counts say otherwise re-histograms below
            lc_rec = brow[_BLC]
            if n_forced:
                lc_rec = jnp.where(use_forced, f_lc, lc_rec)
            pred_left = lc_rec * 2 <= lrow[_SC]
            # past the crossover the hook is the dearer way to the
            # smaller child's histogram (fused_split.hook_crossover_rows)
            # and the scan is told no child.  The parent's rows a shard:
            # exact on one device; under the mesh learners the leaf
            # record's global count (like lc_rec an estimate below the
            # root) over the shard count, which every shard holds - so
            # all take the same branch below, the scan waits for no
            # collective and the two count psums stay one all-reduce
            direct = jnp.bool_(False)
            if physical and _use_fused:
                direct = (par_cnt if axis_name is None else
                          lrow[_SC] / jax.lax.axis_size(axis_name)
                          ) > _hook_cross

            def make_bucket(size):
                @phased         # a branch of the switch: its own jaxpr
                def fn(_):
                    next_phase("partition")
                    start = jnp.clip(s0, 0, n - size)
                    off = s0 - start
                    idx = jax.lax.dynamic_slice(
                        st.row_order, (start,), (size,))
                    pos = jnp.arange(size, dtype=jnp.int32)
                    pos_ok = (pos >= off) & (pos < off + par_cnt) & ~done
                    # small buckets: ONE combined-row gather (per-index
                    # priced).  Large buckets: separate u8-bins + f32-vals
                    # gathers — measured faster above ~32k rows (wide f32
                    # row gathers degrade at scale).
                    if size <= 32768:
                        c_rows = jnp.take(comb, idx, axis=0)  # [S, F+3]
                        b_part = c_rows[:, :f]
                        v_part = c_rows[:, f:f + 2].astype(jnp.float32)
                    else:
                        b_part = jnp.take(bins, idx, axis=0).astype(
                            jnp.float32)
                        v_part = jnp.take(gvals, idx, axis=0)
                        c_rows = None
                    fsel = lfc if fax is not None else feat
                    # split-column extraction as a one-hot dot (a dynamic
                    # [S, 1] column slice pays per-row DMA latency).  The
                    # dot must be exact: bf16 operands hold bins <= 255
                    # exactly, but f32 operands (uint16 bins, max_bin >
                    # 256) would be multiplied at bf16 by the TPU's
                    # default matmul precision — force HIGHEST so bin ids
                    # >= 257 survive.
                    csel = bun_phys[feat] if bundle is not None else fsel
                    e_col = (jnp.arange(ncols, dtype=jnp.int32) == csel)
                    _prec = (None if use_bf16_comb
                             else jax.lax.Precision.HIGHEST)
                    colf = (jnp.matmul(c_rows, e_col.astype(c_rows.dtype),
                                       precision=_prec)
                            if c_rows is not None
                            else jnp.matmul(
                                b_part, e_col[:f].astype(b_part.dtype),
                                precision=_prec))
                    colf = colf.astype(jnp.float32)         # [S]
                    if bundle is not None:
                        # EFB: map the bundle column back to the logical
                        # feature's bin space; rows outside this feature's
                        # stacked range sit at its default bin
                        # (io/bundle.py layout)
                        po = bun_off[feat]
                        colp = colf.astype(jnp.int32)
                        inr = (colp >= po) & (colp < po + num_bins[feat])
                        col = jnp.where(inr, colp - po, bun_def[feat])
                    else:
                        col = colf.astype(jnp.int32)
                    nanb = num_bins[fsel] - 1
                    at_nan = has_nan[fsel] & (col == nanb)
                    if hp.use_cat_subset:
                        # categorical decision by set membership (covers
                        # one-hot and subset splits uniformly)
                        cat_go = jnp.take(
                            member_f, jnp.clip(col, 0, b - 1)) > 0.5
                    else:
                        cat_go = col == sbin
                    glb = jnp.where(
                        cat, cat_go,
                        ((col <= sbin) & ~at_nan) | (at_nan & dl))
                    if fax is not None:
                        # split owner broadcasts its go-left bits over
                        # the feature axis (the reference instead
                        # replicates all columns on every rank,
                        # feature_parallel_tree_learner.cpp:60-77)
                        with phase("merge"):
                            glb = jax.lax.psum(
                                jnp.where(owner, glb.astype(jnp.float32),
                                          0.0), fax) > 0.5
                    left_m = pos_ok & glb
                    right_m = pos_ok & ~glb
                    nleft_ = jnp.sum(left_m.astype(jnp.int32))
                    if use_cegb_lazy:
                        # mark the split leaf's IN-BAG rows as paid for
                        # the winning feature (UpdateLeafBestSplits,
                        # cost_effective_gradient_boosting.hpp:125-134),
                        # then count per-child unpaid rows for every
                        # feature in one mask matmul
                        bag_s = jnp.take(inbag, idx) > 0
                        wfeat = jnp.where(done, f_log, feat)
                        paid_n = st.paid.at[wfeat, idx].max(
                            pos_ok & bag_s, mode="drop")
                        unp = (1.0 - jnp.take(paid_n, idx, axis=1)
                               .astype(jnp.float32))         # [F, S]
                        msk2 = jnp.stack(
                            [(left_m & bag_s), (right_m & bag_s)],
                            axis=1).astype(jnp.float32)      # [S, 2]
                        u2 = jnp.matmul(unp, msk2)           # [F, 2]
                    else:
                        paid_n = st.paid
                        u2 = jnp.zeros((1, 2), jnp.float32)
                    cls_ = jnp.cumsum(left_m.astype(jnp.int32))
                    crs_ = jnp.cumsum(right_m.astype(jnp.int32))
                    new_local = jnp.where(
                        left_m, off + cls_ - 1,
                        jnp.where(right_m, off + nleft_ + crs_ - 1, pos))
                    seg_new = jnp.zeros((size,), jnp.int32).at[
                        new_local].set(idx)
                    row_order_new = jax.lax.dynamic_update_slice(
                        st.row_order, seg_new, (start,))
                    # smaller child by GLOBAL physical counts so every
                    # shard histograms the same side
                    nl_g = _allreduce_sum(nleft_)
                    small_left_ = nl_g * 2 <= par_g
                    next_phase("hist")
                    child_m = jnp.where(small_left_, left_m, right_m)
                    vals = v_part * child_m[:, None].astype(jnp.float32)
                    h = hist_merge(b_part, vals,
                                   min(rows_per_block, size))
                    return (row_order_new, st.comb, st.scratch,
                            nleft_, small_left_, h, paid_n, u2,
                            jnp.minimum(nl_g, par_g - nl_g))
                return fn

            def make_bucket_phys(size):
                """Physical-mode bucket: in-place streaming partition of
                the parent's contiguous row range (partition_kernel),
                then the smaller child histogrammed DIRECTLY from the row
                matrix (comb-direct kernel) — no per-index gathers,
                scatters, or sliced copies anywhere."""
                part_fn = _part_fns[size]
                # smaller child by GLOBAL counts: a shard-local count of
                # the globally-smaller side can exceed size // 2 under
                # the mesh learners, so the slice window must cover the
                # whole bucket (serial pays nothing extra: this is the
                # off-TPU reference path only)
                s_child = size if axis_name is not None else max(
                    size // 2, 1)
                rpb_h = min(rows_per_block, s_child, _HIST_RPB)

                @phased         # a branch of the switch: its own jaxpr
                def fn(_):
                    next_phase("partition")
                    nanb_sel = jnp.where(has_nan[feat],
                                         num_bins[feat] - 1,
                                         jnp.int32(-1))
                    sel = jnp.stack([
                        s0, jnp.where(done, 0, par_cnt), sel_col, sbin,
                        dl.astype(jnp.int32), sel_cat.astype(jnp.int32),
                        nanb_sel, jnp.int32(0)]).astype(jnp.int32)
                    if sel_words is not None:
                        # membership bitset rides the descriptor:
                        # ceil(b/32) i32 words appended after the 8
                        # slots (partition_kernel.SEL_MEMBER); zeroed
                        # for numerical splits, one-hot covered by the
                        # single winning bin's bit
                        sel = jnp.concatenate([sel, sel_words])
                    combp, scrp, nleft_ = part_fn(sel, st.comb,
                                                  st.scratch)
                    nlg_ = _allreduce_sum(nleft_)
                    small_left_ = nlg_ * 2 <= par_g
                    next_phase("hist")
                    child_cnt = jnp.where(small_left_, nleft_,
                                          par_cnt - nleft_)
                    child_start = jnp.where(small_left_, s0, s0 + nleft_)
                    if _phys_interp:
                        # off-TPU reference path: explicit slice + mask.
                        # Fused or not: the compiled fused route hands
                        # on the scan's histogram where it named the
                        # smaller child and the comb-direct one of
                        # (child_start, child_cnt) where it did not,
                        # so its reference is the unfused computation
                        start_c = jnp.clip(child_start, 0,
                                           _n_alloc - s_child)
                        off = child_start - start_c
                        rowsl = jax.lax.dynamic_slice(
                            to_rows(combp, _C_PHYS),
                            (start_c, jnp.int32(0)), (s_child, _C_PHYS))
                        posr = jnp.arange(s_child, dtype=jnp.int32)
                        m = ((posr >= off) & (posr < off + child_cnt)
                             & ~done).astype(jnp.float32)
                        h = hist_merge(
                            rowsl[:, :f],
                            rowsl[:, f:f + 2] * m[:, None], rpb_h)
                    else:
                        from .pallas.hist_kernel2 import \
                            build_histogram_comb
                        h = build_histogram_comb(
                            combp, child_start, jnp.int32(0),
                            jnp.where(done, 0, child_cnt),
                            f_pad=f, size=s_child,
                            padded_bins=padded_bins,
                            rows_per_block=rpb_h, planes=_PLANES)
                    return (st.row_order, combp, scrp,
                            nleft_, small_left_, h, st.paid,
                            jnp.zeros((1, 2), jnp.float32),
                            jnp.minimum(nlg_, par_g - nlg_))
                return fn

            if physical and not _phys_interp:
                # switchless single-kernel path (dynamic Mosaic grids):
                # cost is exactly proportional to the parent's rows, and
                # no lax.switch means XLA aliases the pallas in-place
                # outputs straight through the loop body — the static-
                # bucket switch forced a full copy of the row matrix per
                # split (the dominant per-split cost at every scale)
                nanb_sel = jnp.where(has_nan[feat], num_bins[feat] - 1,
                                     jnp.int32(-1))
                cnt_eff = jnp.where(done, 0, par_cnt)
                sel = jnp.stack([
                    s0, cnt_eff, sel_col, sbin, dl.astype(jnp.int32),
                    sel_cat.astype(jnp.int32), nanb_sel,
                    jnp.where(direct, SIDE_NONE, jnp.where(
                        pred_left, SIDE_LEFT, SIDE_RIGHT))
                    ]).astype(jnp.int32)
                if sel_words is not None:
                    # membership bitset rides the descriptor (see the
                    # bucket path above); sel stays i32[8] without one
                    # so the compiled program is unchanged
                    sel = jnp.concatenate([sel, sel_words])
                nb_part = jnp.maximum(-(-cnt_eff // _PHYS_R), 1)
                if _use_fused:
                    # ONE kernel: compaction scan + (under the
                    # crossover) the histogram of the child
                    # sel[SEL_SIDE] names, from the VMEM-resident
                    # blocks, as the kernel's raw accumulator
                    comb_n, scratch_n, nleft, acc_side = _fused_dyn(
                        sel, st.comb, st.scratch, nb_part)
                else:
                    comb_n, scratch_n, nleft = _part_dyn(
                        sel, st.comb, st.scratch, nb_part)
                # smaller child by GLOBAL counts so every shard
                # histograms the same side
                nl_g = _allreduce_sum(nleft)
                small_is_left = nl_g * 2 <= par_g
                next_phase("hist")
                child_cnt = jnp.where(small_is_left, nleft,
                                      par_cnt - nleft)
                child_start = jnp.where(small_is_left, s0, s0 + nleft)
                # the exactly smaller child is histogrammed directly
                # from its contiguous rows, the sibling is parent minus
                # child: unfused at every split; fused at the parents
                # past the crossover, where the scan ran without its
                # hook, and where the hook was told the other side.
                # Else the hook's histogram is taken out of the scan's
                # accumulator, in the branch that reads it.  The
                # cond's branches only READ the comb (one that handed
                # it on would put 5.4e9 bytes at 10.5M rows at a
                # branch boundary, like the static-bucket switch
                # above); a kernel gated by a row count of 0 instead
                # cost 28 ms a tree at 144 columns in launches and in
                # extracting histograms nobody read (PERF.md, PR 30)
                from .pallas.hist_kernel2 import build_histogram_comb_dyn

                def _child_hist(comb_c, cnt_c):
                    return build_histogram_comb_dyn(
                        comb_c, child_start, jnp.int32(0), cnt_c, f_pad=f,
                        padded_bins=padded_bins,
                        rows_per_block=min(rows_per_block, _HIST_RPB),
                        planes=_PLANES)

                if _use_fused:
                    h_small = jax.lax.cond(
                        (direct | (pred_left != small_is_left)) & ~done,
                        lambda comb_c, _: _child_hist(comb_c, child_cnt),
                        lambda _, acc: _fs.hook_histogram(
                            acc, f, int(padded_bins)),
                        comb_n, acc_side)
                else:
                    h_small = _child_hist(
                        comb_n, jnp.where(done, 0, child_cnt))
                h_small = merge_kernel_hist(h_small)
                small_g = jnp.minimum(nl_g, par_g - nl_g)
                row_order = st.row_order
                paid_n = st.paid
                u2 = jnp.zeros((1, 2), jnp.float32)
            else:
                mk = make_bucket_phys if physical else make_bucket
                branches = [mk(s) for s in sizes]
                if len(branches) == 1:
                    out = branches[0](None)
                else:
                    bidx = jnp.sum(
                        sizes_arr >= jnp.maximum(par_sel, 1)) - 1
                    out = jax.lax.switch(bidx, branches, None)
                (row_order, comb_n, scratch_n, nleft, small_is_left,
                 h_small, paid_n, u2, small_g) = out
            next_phase("glue")
            side_miss = st.side_miss
            if physical and _use_fused:
                # counted here, from the parent's rows, the record and
                # the exact counts, so that the off-chip reference
                # reads what the chip does (obs/counters.py:
                # side_miss_splits, rows_rehistogrammed, hook_splits,
                # rows_hooked).  A miss needs a hook that ran
                hooked = ~direct & ~done
                miss = hooked & (pred_left != small_is_left)
                hook_inc = jnp.stack([
                    miss.astype(jnp.int32), jnp.where(miss, small_g, 0),
                    hooked.astype(jnp.int32), jnp.where(hooked, par_g, 0)])
                side_miss += (jnp.pad(hook_inc, (0, 2)) if _seg_comb
                              else hook_inc)
            if _seg_comb:
                # splits, and their parent rows, whose go-left bit was
                # a membership test of a bundle column's bins
                # (obs/counters.py: member_splits, rows_member).  The
                # rows are the leaf record's count, the node's
                # internal_count, so that the sum is a share of
                # rows_partitioned, which is summed from those
                by_set = in_bun & ~done
                side_miss += jnp.pad(jnp.stack([
                    by_set.astype(jnp.int32),
                    jnp.where(by_set, lrow[_SC].astype(jnp.int32), 0)]),
                    (4, 0))
            with phase("hist"):
                h_small = expand(h_small)   # EFB physical -> logical
            rows_parent = par_cnt

            # drop-guarded write targets (out of bounds when done)
            wleaf = jnp.where(done, L, leaf)
            wright = jnp.where(done, L, right_leaf)
            wnode = jnp.where(done, L - 1, node)
            widx2 = jnp.stack([wleaf, wright])

            with phase("partition"):
                seg = st.seg.at[wleaf].set(
                    jnp.stack([s0, nleft]), mode="drop")
                seg = seg.at[wright].set(
                    jnp.stack([s0 + nleft, rows_parent - nleft]),
                    mode="drop")

            # ---- child sums ----
            pg, ph, pc = lrow[_SG], lrow[_SH], lrow[_SC]
            lg, lh, lc = brow[_BLG], brow[_BLH], brow[_BLC]
            lo, ro = brow[_BLO], brow[_BRO]
            gain_rec = brow[_BG]
            mn_p, mx_p = lrow[_SMN], lrow[_SMX]
            if n_forced:
                lg = jnp.where(use_forced, f_lg, lg)
                lh = jnp.where(use_forced, f_lh, lh)
                lc = jnp.where(use_forced, f_lc, lc)
                p_out = lrow[_SOUT]
                lo_f = calculate_leaf_output(
                    f_lg, f_lh, hp, f_lc, p_out, mn_p, mx_p)
                ro_f = calculate_leaf_output(
                    pg - f_lg, ph - f_lh, hp, pc - f_lc, p_out, mn_p, mx_p)
                lo = jnp.where(use_forced, lo_f, lo)
                ro = jnp.where(use_forced, ro_f, ro)
                gain_f = (leaf_split_gain(f_lg, f_lh, hp)
                          + leaf_split_gain(pg - f_lg, ph - f_lh, hp)
                          - leaf_split_gain(pg, ph, hp))
                gain_rec = jnp.where(use_forced, gain_f, gain_rec)
            rg, rh, rc = pg - lg, ph - lh, pc - lc
            if _seg_comb:
                rg, rh = brow[_BRG], brow[_BRH]

            if tail_pool:
                # one Pallas program for the whole split tail INCLUDING
                # the histogram pool: the kernel DMAs the parent's pool
                # row in, applies the subtraction trick, writes both
                # children's rows, and runs the finder — no XLA pool
                # staging copies or subtraction ops remain
                sel_i = jnp.stack([
                    leaf, right_leaf, node, done.astype(jnp.int32),
                    nleft, s0, par_cnt,
                    small_is_left.astype(jnp.int32)]).astype(jnp.int32)
                sel_f = jnp.concatenate(
                    [brow, lrow, jnp.zeros(6, jnp.float32)])
                with phase("find"):
                    best_n, lstate_n, nodes_n, seg_n, pool_n = \
                        apply_find_pool(
                            sel_i, sel_f, chan4(h_small),
                            feature_mask.reshape(1, f_log).astype(
                                jnp.float32),
                            finder_consts, iscat_i, mono_s_t,
                            st.best, st.lstate, st.nodes, st.seg, st.pool)
                return st._replace(
                    side_miss=side_miss,
                    row_order=row_order, comb=comb_n, scratch=scratch_n,
                    seg=seg_n, pool=pool_n,
                    best=best_n, lstate=lstate_n, nodes=nodes_n,
                    num_leaves=jnp.where(done, st.num_leaves,
                                         st.num_leaves + 1),
                    done=done,
                )

            # ---- subtraction trick (serial_tree_learner.cpp:428) ----
            next_phase("hist")
            h_parent = jnp.transpose(st.pool[leaf][:, :2, :],
                                     (0, 2, 1))            # [F, B, 2]
            h_left = jnp.where(small_is_left, h_small, h_parent - h_small)
            h_right = h_parent - h_left
            pool = (st.pool.at[wleaf].set(chan4(h_left), mode="drop")
                    .at[wright].set(chan4(h_right), mode="drop"))
            next_phase("glue")

            if use_tail:
                # interpret-mode kernel tail: pool stays in XLA
                sel_i = jnp.stack([
                    leaf, right_leaf, node, done.astype(jnp.int32),
                    nleft, s0, par_cnt, jnp.int32(0)]).astype(jnp.int32)
                sel_f = jnp.concatenate(
                    [brow, lrow, jnp.zeros(6, jnp.float32)])
                with phase("find"):
                    best_n, lstate_n, nodes_n, seg_n = apply_find(
                        sel_i, sel_f,
                        jnp.stack([chan4(h_left), chan4(h_right)]),
                        feature_mask.reshape(1, f_log).astype(jnp.float32),
                        finder_consts, iscat_i, mono_s_t,
                        st.best, st.lstate, st.nodes, st.seg)
                return st._replace(
                    side_miss=side_miss,
                    row_order=row_order, comb=comb_n, scratch=scratch_n,
                    seg=seg_n, pool=pool,
                    best=best_n, lstate=lstate_n, nodes=nodes_n,
                    num_leaves=jnp.where(done, st.num_leaves,
                                         st.num_leaves + 1),
                    done=done,
                )

            # ---- tree nodes (reference Tree::Split, tree.h:541) ----
            p = lrow[_SPAR].astype(jnp.int32)
            has_par = p >= 0
            pc_idx = jnp.maximum(p, 0)
            enc = -(leaf + 1).astype(jnp.float32)
            prow = st.nodes[pc_idx]
            new_l = jnp.where((prow[5] == enc) & has_par,
                              jnp.float32(node), prow[5])
            new_r = jnp.where((prow[6] == enc) & has_par,
                              jnp.float32(node), prow[6])
            prow = prow.at[5].set(new_l).at[6].set(new_r)
            wpc = jnp.where(done | ~has_par, L - 1, pc_idx)
            nodes = st.nodes.at[wpc].set(prow, mode="drop")
            node_row = jnp.stack([
                feat.astype(jnp.float32), sbin.astype(jnp.float32),
                gain_rec, dl.astype(jnp.float32), cat.astype(jnp.float32),
                -(leaf + 1).astype(jnp.float32),
                -(right_leaf + 1).astype(jnp.float32),
                calculate_leaf_output(pg, ph, hp), ph, pc])
            nodes = nodes.at[wnode].set(node_row, mode="drop")
            if hp.use_cat_subset:
                cat_members_n = st.cat_members.at[wnode].set(
                    member_f, mode="drop")
            else:
                cat_members_n = st.cat_members

            # ---- constraint state for the children ----
            d_child = lrow[_SDEP] + 1.0
            if use_mono_inter:
                # IntermediateLeafConstraints (monotone_constraints.hpp
                # :514): children inherit the parent's bounds verbatim;
                # the box-adjacency pass below then tightens them with
                # each other's ACTUAL outputs (UpdateConstraintsWith
                # Outputs) along with every other face-adjacent leaf
                l_mn = r_mn = mn_p
                l_mx = r_mx = mx_p
            elif hp.use_monotone:
                # BasicLeafConstraints::Update
                # (monotone_constraints.hpp:485-501): numerical split on
                # a monotone feature pins the children to either side of
                # the output midpoint
                mono_t = jnp.where(cat, 0, mono_arr[feat])
                mid = (lo + ro) / 2.0
                l_mx = jnp.where(mono_t > 0, jnp.minimum(mx_p, mid), mx_p)
                l_mn = jnp.where(mono_t < 0, jnp.maximum(mn_p, mid), mn_p)
                r_mn = jnp.where(mono_t > 0, jnp.maximum(mn_p, mid), mn_p)
                r_mx = jnp.where(mono_t < 0, jnp.minimum(mx_p, mid), mx_p)
            else:
                l_mn = r_mn = mn_p
                l_mx = r_mx = mx_p

            fnode = jnp.float32(node)
            lrow_l = jnp.stack([lg, lh, lc, d_child, fnode, l_mn, l_mx, lo])
            lrow_r = jnp.stack([rg, rh, rc, d_child, fnode, r_mn, r_mx, ro])
            lstate = st.lstate.at[widx2].set(
                jnp.stack([lrow_l, lrow_r]), mode="drop")

            if fax is not None:
                # feat is global; local scatter only on the owning shard
                used_new = jnp.where(
                    owner, st.used_feat[leaf].at[lfc].set(1.0),
                    st.used_feat[leaf])
                mu_new = jnp.where(
                    owner, st.model_used.at[lfc].set(1.0), st.model_used)
            else:
                used_new = st.used_feat[leaf].at[feat].set(1.0)
                mu_new = st.model_used.at[feat].set(1.0)
            model_used = jnp.where(done, st.model_used, mu_new)
            used_feat = st.used_feat.at[widx2].set(
                jnp.broadcast_to(used_new, (2, f_log)), mode="drop")
            if use_ic:
                # allowed features = union of constraint sets containing
                # every feature already used on this path
                # (col_sampler.hpp interaction-constraint filtering)
                contains = jnp.all(ic_arr >= used_new[None, :], axis=1)
                allowed = jnp.max(
                    ic_arr * contains[:, None].astype(jnp.float32),
                    axis=0)
                fmask_child = feature_mask * allowed
            else:
                fmask_child = feature_mask
            cegb_pen_child = (cegb_loc * (1.0 - model_used)
                              if use_cegb_pen else None)
            cegb_in_axes = None
            if use_cegb_lazy:
                # per-child on-demand costs (DeltaGain's lazy term):
                # penalty[f] x unpaid in-bag rows in that child
                lazy2 = jnp.stack([lazy_arr * u2[:, 0],
                                   lazy_arr * u2[:, 1]])     # [2, F]
                cegb_pen_child = (lazy2 if cegb_pen_child is None
                                  else cegb_pen_child[None, :] + lazy2)
                cegb_in_axes = 0

            fmask_l = node_fmask(fmask_child, i * 2 + 1)
            fmask_r = node_fmask(fmask_child, i * 2 + 2)
            if use_voting:
                h_l_m, m_l = vote_sync(h_left, fmask_l, cegb_pen_child,
                                       lc)
                h_r_m, m_r = vote_sync(h_right, fmask_r, cegb_pen_child,
                                       rc)
                finder_h = jnp.stack([h_l_m, h_r_m])
                fmask_pair = jnp.stack(
                    [fmask_l * m_l, fmask_r * m_r])
            else:
                finder_h = jnp.stack([h_left, h_right])
                fmask_pair = jnp.stack([fmask_l, fmask_r])

            if hp.use_extra_trees:
                rkeys = jnp.stack([jax.random.fold_in(_et_base, i * 2 + 1),
                                   jax.random.fold_in(_et_base, i * 2 + 2)])
            else:
                rkeys = jnp.zeros((2, 2), jnp.uint32)
            with phase("find"):
                si: SplitInfo = jax.vmap(
                    finder, in_axes=(0, 0, 0, 0, 0, None, None, None, 0,
                                     0, 0, 0, cegb_in_axes, 0)
                )(finder_h,
                  jnp.stack([lg, rg]), jnp.stack([lh, rh]),
                  jnp.stack([lc, rc]),
                  jnp.stack([d_child, d_child]),
                  num_bins, has_nan, is_cat, fmask_pair,
                  jnp.stack([l_mn, r_mn]), jnp.stack([l_mx, r_mx]),
                  jnp.stack([lo, ro]), cegb_pen_child, rkeys)
            si = sync_best(si)
            best = st.best.at[widx2].set(_pack_si(si), mode="drop")

            if use_mono_inter:
                # ---- intermediate monotone: box update, face-adjacency
                # bound tightening, best-split recompute ----
                # (monotone_constraints.hpp:514 IntermediateLeaf
                # Constraints::Update + GoUpToFindLeavesToUpdate /
                # GoDownToFindLeavesToUpdate, re-expressed as vectorized
                # geometry: a leaf is updated iff its bin-space box is
                # disjoint from a new child's box in EXACTLY one feature
                # dim, touches it there, and that dim is monotone — the
                # contact dim is provably the LCA split feature, so the
                # reference's walk conditions fall out of the boxes.)
                fi = st.inter
                blo, bhi = fi[:, :f_log], fi[:, f_log:2 * f_log]
                fml = fi[:, 2 * f_log:3 * f_log]
                salts = fi[:, 3 * f_log]
                pbl, pbh = blo[leaf], bhi[leaf]
                sbin_f = sbin.astype(jnp.float32)
                cutd = (jnp.arange(f_log) == feat) & ~cat
                lhi = jnp.where(cutd, jnp.minimum(pbh, sbin_f), pbh)
                rlo = jnp.where(cutd, jnp.maximum(pbl, sbin_f + 1.0), pbl)
                blo = (blo.at[wleaf].set(pbl, mode="drop")
                       .at[wright].set(rlo, mode="drop"))
                bhi = (bhi.at[wleaf].set(lhi, mode="drop")
                       .at[wright].set(pbh, mode="drop"))
                fml = fml.at[widx2].set(
                    jnp.stack([fmask_l, fmask_r]), mode="drop")
                salts = salts.at[widx2].set(
                    jnp.stack([(i * 2 + 1).astype(jnp.float32),
                               (i * 2 + 2).astype(jnp.float32)]),
                    mode="drop")
                monoF = mono_arr[:f_log].astype(jnp.float32)[None]
                mn0 = lstate[:, _SMN]
                mx0 = lstate[:, _SMX]

                def _adj_upd(Xlo, Xhi, Xout, mn_c, mx_c):
                    lo_d = blo > Xhi[None] + 0.5
                    hi_d = bhi < Xlo[None] - 0.5
                    disj = lo_d | hi_d                       # [L, F]
                    ndisj = jnp.sum(disj.astype(jnp.int32), axis=1)
                    above = jnp.abs(blo - (Xhi[None] + 1.0)) < 0.5
                    below = jnp.abs(bhi - (Xlo[None] - 1.0)) < 0.5
                    touch = (above | below) & disj
                    contact = touch & (monoF != 0.0)
                    one = (ndisj == 1) & (jnp.sum(
                        contact.astype(jnp.int32), axis=1) == 1)
                    m_at = jnp.sum(jnp.where(contact, monoF, 0.0), axis=1)
                    is_ab = jnp.sum(jnp.where(
                        contact, above.astype(jnp.float32), 0.0),
                        axis=1) > 0.5
                    upd_min = one & (((m_at > 0) & is_ab)
                                     | ((m_at < 0) & ~is_ab))
                    upd_max = one & (((m_at > 0) & ~is_ab)
                                     | ((m_at < 0) & is_ab))
                    mn_c = jnp.where(upd_min, jnp.maximum(mn_c, Xout),
                                     mn_c)
                    mx_c = jnp.where(upd_max, jnp.minimum(mx_c, Xout),
                                     mx_c)
                    return mn_c, mx_c

                mn_c, mx_c = _adj_upd(pbl, lhi, lo, mn0, mx0)
                mn_c, mx_c = _adj_upd(rlo, pbh, ro, mn_c, mx_c)
                changed = ((mn_c > mn0) | (mx_c < mx0)) & ~done
                lstate = (lstate.at[:, _SMN].set(
                    jnp.where(changed, mn_c, mn0))
                    .at[:, _SMX].set(jnp.where(changed, mx_c, mx0)))
                # recompute cached best splits for tightened leaves from
                # the pool (the reference's leaves_to_update_ pass)
                h_all = jnp.transpose(pool[:, :, :2, :], (0, 1, 3, 2))
                if hp.use_extra_trees:
                    rkeys_all = jax.vmap(
                        lambda s: jax.random.fold_in(_et_base, s))(
                        salts.astype(jnp.int32))
                else:
                    rkeys_all = jnp.zeros((L, 2), jnp.uint32)
                with phase("find"):
                    si_all = jax.vmap(
                        finder,
                        in_axes=(0, 0, 0, 0, 0, None, None, None, 0,
                                 0, 0, 0, None, 0))(
                        h_all, lstate[:, _SG], lstate[:, _SH],
                        lstate[:, _SC], lstate[:, _SDEP], num_bins,
                        has_nan, is_cat, fml, lstate[:, _SMN],
                        lstate[:, _SMX], lstate[:, _SOUT],
                        cegb_pen_child, rkeys_all)
                si_all = sync_best(si_all)
                best = jnp.where(changed[:, None], _pack_si(si_all),
                                 best)
                inter_n = jnp.concatenate(
                    [blo, bhi, fml, salts[:, None]], axis=1)
            else:
                inter_n = st.inter

            return st._replace(
                side_miss=side_miss,
                inter=inter_n, paid=paid_n,
                row_order=row_order, comb=comb_n, scratch=scratch_n,
                cat_members=cat_members_n,
                seg=seg, pool=pool,
                best=best, lstate=lstate, nodes=nodes,
                used_feat=used_feat, model_used=model_used,
                num_leaves=jnp.where(done, st.num_leaves,
                                     st.num_leaves + 1),
                done=done,
            )

        def while_cond(carry):
            i, st = carry
            return (i < L - 1) & ~st.done

        @phased                 # the loop body: its own jaxpr
        def while_body(carry):
            next_phase("glue")
            i, st = carry
            return i + 1, body(i, st)

        # the loop itself (its condition, its carry) is glue, and so is
        # whatever a split does under no seam of ``body``
        next_phase("glue")
        _, state = jax.lax.while_loop(
            while_cond, while_body, (jnp.int32(0), state))

        # ---- finalize tree arrays from the packed state ----
        # lstate[:, OUT] holds the constrained/smoothed output computed at
        # split time (reference: SplitInfo left/right_output -> leaf values)
        nodes, lstate = state.nodes, state.lstate
        live = jnp.arange(L) < state.num_leaves
        tree = TreeArrays(
            split_feature=nodes[:, 0].astype(jnp.int32),
            threshold_bin=nodes[:, 1].astype(jnp.int32),
            split_gain=nodes[:, 2],
            default_left=nodes[:, 3] > 0.5,
            is_categorical=nodes[:, 4] > 0.5,
            left_child=nodes[:, 5].astype(jnp.int32),
            right_child=nodes[:, 6].astype(jnp.int32),
            internal_value=nodes[:, 7],
            internal_weight=nodes[:, 8],
            internal_count=nodes[:, 9],
            leaf_value=jnp.where(live, lstate[:, _SOUT], 0.0)
            .astype(jnp.float32),
            leaf_weight=lstate[:, _SH].astype(jnp.float32),
            leaf_count=lstate[:, _SC].astype(jnp.float32),
            num_leaves=state.num_leaves,
            cat_members=state.cat_members,
            side_miss=state.side_miss,
        )
        next_phase("leafrows")
        # reconstruct the per-row leaf assignment ONCE from the partition
        # (row_order/permuted rows + seg tile [0, n)), instead of
        # scattering a [n] leaf_id vector on every split: a position's
        # leaf is the one whose segment holds it (and, on the stream
        # route, its shrunk output rides the same mask).  Off the
        # stream route the gradients are a program over ROW-order
        # scores, so the permutation is undone here, every tree; on it
        # the refresh below adds the outputs by POSITION and nothing
        # reads a row-order leaf id: the unpaged stream program stops
        # at ``lv_row`` and returns no ``leaf_id`` (``pull_score`` puts
        # the comb's scores in row order when somebody asks).
        streams = physical and stream is not None and not debug_state
        if streams:
            # shrinkage arrives as a TRACED per-call scalar: callbacks
            # (reset_parameter) may change learning_rate mid-training,
            # and a baked constant would silently desync the in-comb
            # scores from the booster's
            lv_leaf = jnp.where(state.num_leaves > 1,
                                stream_rate * lstate[:, _SOUT], 0.0)
            leaf_of_pos, lv_row = leaf_of_position(
                state.seg, n, (lv_leaf,))         # [n] by position
        else:
            leaf_of_pos, = leaf_of_position(state.seg, n)
        if streams and _lazy_score:
            leaf_id = None
        elif physical:
            # positions [0, n) always hold a permutation of the original
            # rows (partitions only permute within segment ranges); decode
            # the stored row-id bytes to undo it.  Matvec, not a [n, 3]
            # slice — the slice lane-pads to 512 B/row (5.4 GB at 10.5M)
            ridx_f = _decode_rid(state.comb)[:n].astype(jnp.int32)
            leaf_id = jnp.zeros((n,), jnp.int32).at[ridx_f].set(
                leaf_of_pos, mode="drop")
        else:
            leaf_id = jnp.zeros((n,), jnp.int32).at[state.row_order].set(
                leaf_of_pos)
        if debug_state:
            return tree, leaf_id, state.best, state.lstate
        next_phase("glue")
        if shard_counter:
            # this shard's own counts, u32 [2]: the sum of parent rows
            # over the tree's splits - a leaf's rows were scanned once
            # at each of its ancestors, so the leaf's LOCAL row count
            # (seg, never merged) times its depth, summed - and the
            # in-bag rows it gave the root.  From the finished state
            # and the root's local sum: no loop-carried value, no
            # collective.  u32: n_local * (L - 1) is under 2^32 for
            # every n_local the 3-byte row id allows.
            dep = jnp.where(live, lstate[:, _SDEP], 0.0).astype(jnp.uint32)
            shard_rows = jnp.stack([
                jnp.sum(state.seg[:, 1].astype(jnp.uint32) * dep),
                c0_loc.astype(jnp.uint32)])
            if physical:
                return tree, leaf_id, state.comb, state.scratch, shard_rows
            return tree, leaf_id, shard_rows
        if streams:
            # prepare the NEXT tree in-place: every comb position's score
            # gains this tree's shrunk leaf output (positions already sit
            # inside their leaf's segment), then g/h recompute from the
            # new scores — one streaming pass, no gathers.  Mirrors the
            # async score-update tail in gbdt (rate * leaf_value[leaf]).
            next_phase("refresh")
            if _fused_root:
                # fused refresh: the pass that rewrites scores/gradients
                # also accumulates the NEXT tree's root histogram from
                # the blocks it already holds in VMEM
                comb_r, root_next = _refresh_fn(
                    state.comb, lv_row.reshape(1, n))
                return (tree, leaf_id, comb_r, state.scratch,
                        root_next)
            comb_r = _refresh_fn(state.comb, lv_row.reshape(1, n))
            return tree, leaf_id, comb_r, state.scratch
        if physical:
            return tree, leaf_id, state.comb, state.scratch
        if use_cegb_lazy:
            return tree, leaf_id, state.paid
        return tree, leaf_id

    if physical:
        if _fused_root:
            def grow_p_raw(comb, scratch, grad, hess, inbag, fm, nb, hn,
                           ic, seed, rate, root_h):
                return grow_core(None, comb, scratch, grad, hess, inbag,
                                 fm, nb, hn, ic, seed, stream_rate=rate,
                                 root_hist_in=root_h)
        else:
            def grow_p_raw(comb, scratch, grad, hess, inbag, fm, nb, hn,
                           ic, seed, rate):
                return grow_core(None, comb, scratch, grad, hess, inbag,
                                 fm, nb, hn, ic, seed, stream_rate=rate)

        if axis_name is not None:
            # mesh mode: hand the UNJITTED core + layout constants to the
            # data-parallel grower, which shard_maps it and carries the
            # per-shard comb/scratch matrices as sharded global arrays
            # (stream mode — and with it the fused-root carry — is
            # serial-only, so core keeps the 11-arg signature)
            return MeshPhysicalPieces(
                core=grow_p_raw, n_alloc=_n_alloc, C=_C_PHYS,
                f_pad=f_pad_p, n_local=n_rows_p, dtype=_COMB_DT,
                fused=_use_fused, ingest=_efb_ingest,
                padded_bins=int(padded_bins),
                scan_block_rows=_PHYS_R)
        # donation: the carried comb/scratch matrices alias their
        # outputs (the whole point of the in-place design), and the
        # fused-root carry donates the [f_pad, B, 2] root histogram
        # too — without it every grow call double-allocates the carry
        # while the previous tree's is still live (the ISSUE-9
        # donation audit surfaced it; lightgbm_tpu/analysis hbm-budget
        # pins all three aliases in the lowered program)
        grow_p = jax.jit(grow_p_raw,
                         donate_argnums=(0, 1, 11) if _fused_root
                         else (0, 1))
        if _fused_root:
            # tree 0's root histogram: one standalone call replicating
            # EXACTLY what the unfused root branch computes from the
            # freshly-initialised comb; every later tree's arrives from
            # the previous grow call's fused refresh
            if _phys_interp:
                @jax.jit
                def _root0_fn(comb):
                    comb_l = to_rows(comb, _C_PHYS)
                    pos_al = jnp.arange(_n_alloc, dtype=jnp.int32)
                    gv = (jax.lax.slice(comb_l, (0, f_pad_p),
                                        (_n_alloc, f_pad_p + 3))
                          * (pos_al < n_rows_p
                             ).astype(jnp.float32)[:, None])
                    bc = jax.lax.slice(comb_l, (0, 0),
                                       (_n_alloc, f_pad_p))
                    return build_histogram(
                        bc, gv[:, :2], padded_bins=padded_bins,
                        rows_per_block=rows_per_block)
            else:
                def _root0_fn(comb):
                    from .pallas.hist_kernel2 import build_histogram_comb
                    return build_histogram_comb(
                        comb, jnp.int32(0), jnp.int32(0),
                        jnp.int32(n_rows_p), f_pad=f_pad_p,
                        size=n_rows_p, padded_bins=padded_bins,
                        rows_per_block=min(rows_per_block, _HIST_RPB),
                        planes=_PLANES)
        else:
            _root0_fn = None
        if stream is not None:
            # in-place permutation re-anchor (LGBM_TPU_CKPT_AT_REFRESH,
            # ISSUE 15 satellite): recover the ANCHORED-ORDER bins
            # block from the carried comb itself — scatter the real
            # rows back to initial row order by their stored row-id
            # bytes and slice the bin columns (bin ids are exact
            # integers in the comb, so the u8 cast round-trips
            # bit-perfectly).  reanchor_inplace then re-runs the exact
            # stream-init over it, skipping the bins-matrix re-read
            # (2.8 GB of host DMA per save at 100M x 28 on the paged
            # path) and the EFB unbundle re-ingest.  The VALUE columns
            # must rebuild through the init formulas — the carried
            # refresh values differ at ulp level (the bf16-split score
            # recombination rounds), and byte-identical resume is the
            # contract.
            def _reanchor_bins(comb):
                # (a transposing copy above one plane; once a
                # checkpoint, not once a tree)
                comb_l = to_rows(comb, _C_PHYS)
                real = jax.lax.slice(comb_l, (0, 0),
                                     (n_rows_p, _C_PHYS))
                rid = _decode_rid(comb)[:n_rows_p].astype(jnp.int32)
                bins_perm = jax.lax.slice(
                    real, (0, 0), (n_rows_p, f_pad_p))
                anchored = (jnp.zeros((n_rows_p, f_pad_p),
                                      jnp.float32)
                            .at[rid].set(bins_perm.astype(jnp.float32)))
                return anchored.astype(jnp.uint8)

            _reanchor_fn = jax.jit(_reanchor_bins)
        else:
            _reanchor_fn = None
        if _lazy_score:
            # the row-order train score, on demand: each position's
            # score is the sum of its three bf16-exact terms in the
            # order the refresh sums them (exactly the f32 it split),
            # its row the decoded id bytes; one lane product a column,
            # never a [n, 3] slice, which lane-pads to 512 B a row.
            # Reads the comb and leaves it as it is: not donated.
            @jax.jit
            @phase("leafrows")
            def _pull_score_fn(comb):
                rid = _decode_rid(comb)[:n_rows_p].astype(jnp.int32)
                hi, mid, lo = (
                    _comb_dot(comb, {f_pad_p + COL_SC + i: 1.0})[:n_rows_p]
                    for i in range(3))
                # [K, n_pad] with the route's one tree an iteration
                return jnp.zeros((n_rows_p,), jnp.float32).at[rid].set(
                    hi + mid + lo, mode="drop")[None]
        else:
            _pull_score_fn = None
        return _maybe_guard(_PhysicalGrow(
            grow_p, physical_bins, _n_alloc, _C_PHYS, f_pad_p,
            stream_init=(_stream_init_fn
                         if stream is not None else None),
            dtype=_COMB_DT, fused=_use_fused,
            root0_fn=_root0_fn, ingest=_efb_ingest,
            paged_plan=paged, reanchor_fn=_reanchor_fn,
            scan_block_rows=_PHYS_R, pull_score_fn=_pull_score_fn,
            hist_tiles=_HIST_TILES, hist_block_rows=_HIST_RPB,
            hist_lo_n=_HIST_LO_N))

    if use_cegb_lazy:
        @jax.jit
        def grow_lazy(bins, grad, hess, inbag, feature_mask, num_bins,
                      has_nan, is_cat, seed, paid):
            return grow_core(bins, None, None, grad, hess, inbag,
                             feature_mask, num_bins, has_nan, is_cat,
                             seed, paid_in=paid)

        return _maybe_guard(grow_lazy)

    @jax.jit
    def grow(bins, grad, hess, inbag, feature_mask, num_bins, has_nan,
             is_cat, seed):
        return grow_core(bins, None, None, grad, hess, inbag,
                         feature_mask, num_bins, has_nan, is_cat, seed)

    return _maybe_guard(grow)


class MeshPhysicalPieces(NamedTuple):
    """Physical-partition grow core for the mesh learners: the caller
    (parallel/data_parallel.py) shard_maps ``core`` over the row axis and
    carries the [n_alloc, C] comb/scratch matrices as sharded arrays.
    ``core(comb, scratch, grad, hess, inbag, fm, num_bins, has_nan,
    is_cat, seed, rate) -> (tree, leaf_id, comb, scratch, shard_rows)``;
    shapes are PER-SHARD (n_local rows; ``shard_rows`` is u32 [2]: the
    shard's own sum of parent rows over the tree's splits, and the
    in-bag rows it gave the root)."""
    core: object
    n_alloc: int            # comb lines (rows + PHYS_ROW_SLACK)
    C: int                  # line width
    f_pad: int              # comb feature columns (UNBUNDLED under EFB)
    n_local: int
    dtype: object = jnp.float32
    fused: bool = False     # per-split fused partition+histogram kernel
    ingest: object = None   # EFB: bins_local -> unbundled u8 block
                            # (device_data.unbundle_bins closure); the
                            # caller applies it inside its shard_mapped
                            # comb init so each shard unbundles locally
    padded_bins: int = 0    # engaged per-column bin width (LOGICAL
                            # under EFB) — what the mesh caller prices
                            # histogram-merge collectives with
    scan_block_rows: int = 0    # rows a grid step of the scan moves


def phys_init_comb(bins_local, n_alloc: int, C: int, f_pad: int,
                   dtype=jnp.float32):
    """Build the physical row matrix from a (local) [n, f_pad] u8 bin
    block: bins as numeric columns + LOCAL row-id bytes at f_pad+3..5
    (the value columns are refreshed per tree by the grower).  All
    stored values are bf16-exact by the layout contract, so ``dtype``
    may be bfloat16 (half the DMA bytes of f32).  The matrix is
    plane-major (layout.py), built a plane at a time: no [n_alloc, C]
    row matrix is ever made."""
    from .pallas.layout import LANE, set_cols
    rid = jnp.arange(n_alloc, dtype=jnp.int32)
    rid_bytes = (rid // 65536, (rid // 256) % 256, rid % 256)
    planes = []
    for lo in range(0, C, LANE):
        plane = jnp.zeros((n_alloc, LANE), dtype)
        if lo < bins_local.shape[1]:
            plane = jax.lax.dynamic_update_slice(
                plane, bins_local[:, lo:lo + LANE].astype(dtype), (0, 0))
        planes.append(plane)
    comb = planes[0] if len(planes) == 1 else jnp.concatenate(planes)
    for i, v in enumerate(rid_bytes):
        comb = set_cols(comb, v.astype(dtype)[:, None], f_pad + 3 + i, C)
    return comb


from .pallas.layout import comb_shape  # noqa: E402


class _PhysicalGrow:
    """Stateful wrapper for physical-partition mode: carries the permuted
    row matrix + scratch across trees (donated each call) while keeping
    the plain ``grow(bins, ...) -> (tree, leaf_id)`` calling convention
    (the ``bins`` argument is accepted and ignored — the rows live inside
    the carried matrix).  On the unpaged stream route (``lazy_score``)
    ``leaf_id`` is None: the tree's outputs were added to the comb's
    score columns by position, nothing was put in row order, and
    ``pull_score()`` is how the booster reads the row-order score."""

    def __init__(self, grow_p, bins_dev, n_alloc, C, f_pad,
                 stream_init=None, dtype=jnp.float32, fused=False,
                 root0_fn=None, ingest=None,
                 paged_plan=None, reanchor_fn=None, scan_block_rows=0,
                 pull_score_fn=None, hist_tiles=0, hist_block_rows=0,
                 hist_lo_n=0):
        self._grow_p = grow_p
        self._bins_dev = bins_dev
        # EFB (ISSUE 12): the carried bins stay BUNDLED (the smaller
        # HBM retention); the jitted ingest unbundles them into the
        # logical layout each time the comb (re)builds
        self._ingest = None if ingest is None else jax.jit(ingest)
        self._n_alloc = n_alloc
        self._C = C
        self._f_pad = f_pad
        self._comb = None
        self._scratch = None
        self._stream_init = stream_init
        self._dtype = dtype
        self._stream_aux_fn = None   # set by gbdt before the first tree
        self._stream_rate_fn = None  # () -> current shrinkage rate
        self.fused = fused           # fused partition+histogram splits
        # rows a grid step of the scan moves (obs: Tree::grow's
        # scan_block_rows / scan_steps)
        self.scan_block_rows = int(scan_block_rows)
        # the comb's 128-lane planes, the tiles a comb histogram sweeps,
        # the rows a step of it reads and the split of a bin its
        # one-hots take (obs: Tree::grow's comb_planes / hist_tiles /
        # hist_block_rows / hist_lo_n)
        self.comb_planes = C // 128
        self.hist_tiles = int(hist_tiles)
        self.hist_block_rows = int(hist_block_rows)
        self.hist_lo_n = int(hist_lo_n)
        self._root0_fn = root0_fn    # fused stream: tree-0 root hist
        self._root_hist = None       # fused stream: carried root hist
        # paged comb (ISSUE 15): pages live host-side between trees and
        # stream through the double-buffered page buffers per call
        self.paged = paged_plan      # plan dict or None
        self._pages = None           # ops/paged.PageStore once built
        self._reanchor_fn = reanchor_fn  # stream: in-place re-anchor
        self._pull_score_fn = pull_score_fn
        # the comb's score columns are the score of record between pulls
        self.lazy_score = pull_score_fn is not None
        self._grow_batch_p = None    # lazily-jitted batched-K scan core

    def set_stream_aux(self, fn, rate_fn=None) -> None:
        """Streaming mode: ``fn() -> [2 + n_consts, n_pad]`` aux rows
        (current scores, validity mask, objective constants) consumed
        once when the row matrix is first built.  ``rate_fn`` returns the
        CURRENT shrinkage rate each call (callbacks may change it)."""
        self._stream_aux_fn = fn
        self._stream_rate_fn = rate_fn

    def reset_stream(self) -> None:
        """Invalidate the carried row matrix; the next call rebuilds it
        from fresh scores via the aux provider (used after rollbacks,
        which mutate the booster's scores behind the comb's back).  On
        the paged path the host pages drop with it — the re-anchor
        contract covers the per-page permutations too."""
        self._comb = None
        self._scratch = None
        self._root_hist = None
        if self._pages is not None:
            self._pages.drop()

    def pull_score(self):
        """The train score the comb carries by position, in ROW order
        ([1, n_pad] f32), or None before the comb first builds (the
        booster's own copy is then the only one).  One program, run
        when somebody reads ``GBDT.train_score``; the comb stays as it
        is."""
        if self._comb is None:
            return None
        score = self._pull_score_fn(self._comb)
        # while tracing: this program's ops are ``leafrows``'s
        _obs_tracer.program("pull_score", self._pull_score_fn, self._comb)
        return score

    def reanchor_inplace(self) -> bool:
        """Checkpoint re-anchor at the stream refresh boundary WITHOUT
        re-reading the bins matrix (LGBM_TPU_CKPT_AT_REFRESH=1): the
        anchored-order bins block is recovered from the carried comb
        itself (one scatter by the stored row ids), then the exact
        stream-init rebuilds the value columns from the current
        scores — bit-identical to the full rebuild a resumed process
        performs, because the bins block round-trips exactly and the
        value formulas are the same program.  Returns False (caller
        falls back to reset_stream) off the stream path or before the
        first build; the carried root histogram drops either way (its
        accumulation order follows the row order)."""
        if self._reanchor_fn is None or self._stream_init is None:
            return False
        if self._stream_aux_fn is None:
            return False
        comb = self._window()
        if comb is None:
            return False
        bins_anchored = self._reanchor_fn(comb)
        shape = comb_shape(self._n_alloc, self._C)
        comb0 = jnp.zeros(shape, self._dtype)
        self._put_window(self._stream_init(
            comb0, bins_anchored, self._stream_aux_fn()))
        self._scratch = jnp.zeros(shape, self._dtype)
        self._root_hist = None
        return True

    def _window(self):
        """The grow-time comb window: the carried device matrix, or
        the page sweep's assembled window on the paged path."""
        if self._pages is not None:
            return (self._pages.fetch_window() if self._pages.built
                    else None)
        return self._comb

    def _put_window(self, comb) -> None:
        if self._pages is not None:
            self._pages.flush_window(comb)
            self._comb = None
        else:
            self._comb = comb

    def _init_buffers(self):
        f_pad, n_alloc, C = self._f_pad, self._n_alloc, self._C
        shape = comb_shape(n_alloc, C)
        bins_src = (self._bins_dev if self._ingest is None
                    else self._ingest(self._bins_dev))
        if self.paged is not None and self._pages is None:
            from .paged import PageStore
            self._pages = PageStore(
                n_alloc=n_alloc, C=C,
                rows_per_page=int(self.paged["rows_per_page"]),
                dtype=self._dtype)
        if self._stream_init is not None:
            if self._stream_aux_fn is None:
                raise RuntimeError(
                    "stream mode needs set_stream_aux before training")
            comb0 = jnp.zeros(shape, self._dtype)
            comb = self._stream_init(
                comb0, bins_src, self._stream_aux_fn())
        else:
            init = jax.jit(functools.partial(
                phys_init_comb, n_alloc=n_alloc, C=C, f_pad=f_pad,
                dtype=self._dtype))
            comb = init(bins_src)
        self._put_window(comb)
        self._scratch = jnp.zeros(shape, self._dtype)

    def __call__(self, bins, grad, hess, inbag, feature_mask, num_bins,
                 has_nan, is_cat, seed):
        if self._comb is None and (self._pages is None
                                   or not self._pages.built):
            self._init_buffers()
        comb = self._window()
        if self._stream_init is not None:
            # gradients live in the row matrix; the args are unused
            grad = hess = inbag = jnp.zeros((1,), jnp.float32)
            rate = jnp.float32(self._stream_rate_fn()
                               if self._stream_rate_fn else 0.0)
        else:
            rate = jnp.float32(0.0)
        if self._root0_fn is not None:
            # fused stream mode: the root histogram rides across grow
            # calls (each tree's refresh pass builds the next one)
            if self._root_hist is None:
                self._root_hist = self._root0_fn(comb)
            args = (comb, self._scratch, grad, hess, inbag,
                    feature_mask, num_bins, has_nan, is_cat, seed, rate,
                    self._root_hist)
            out = self._grow_p(*args)
            ta, leaf_id, comb_n, self._scratch, self._root_hist = out[:5]
        else:
            args = (comb, self._scratch, grad, hess, inbag,
                    feature_mask, num_bins, has_nan, is_cat, seed, rate)
            out = self._grow_p(*args)
            ta, leaf_id, comb_n, self._scratch = out[:4]
        # while tracing: what this program's instruction names mean
        # (once; the donated buffers are asked only their shapes)
        _obs_tracer.program("grow", self._grow_p, *args)
        self._put_window(comb_n)
        return ta, leaf_id

    def batched_fn(self):
        """The jitted batched-K core: ONE compiled dispatch scanning the
        raw grow program over a leading class axis, the comb/scratch
        matrices threaded through the scan carry exactly the way the
        serial per-class calls thread them between dispatches (class k
        starts from class k-1's final permutation — the property that
        makes the batched trees byte-identical to the serial-K path by
        construction; a vmap over K would need K independent combs and
        diverge).  The per-split [L, F, 4, B] hist arena lives inside
        the scan body, so XLA allocates it ONCE and reuses it across
        classes rather than materializing a [K, L, F, 4, B] block.
        Exposed (not just cached privately) so the analyzer's
        ``grow_physical_mc`` entry lowers the same program the booster
        dispatches."""
        if self._grow_batch_p is None:
            raw = self._grow_p.__wrapped__

            def _scan_k(comb, scratch, gradK, hessK, inbag, fmK,
                        num_bins, has_nan, is_cat, seedK):
                def body(carry, xs):
                    comb_c, scr_c = carry
                    g, h, fm, sd = xs
                    out = raw(comb_c, scr_c, g, h, inbag, fm,
                              num_bins, has_nan, is_cat, sd,
                              jnp.float32(0.0))
                    ta, lid, comb_n, scr_n = out[:4]
                    return (comb_n, scr_n), (ta, lid)

                with phase("glue"):
                    (comb, scratch), ys = jax.lax.scan(
                        body, (comb, scratch), (gradK, hessK, fmK, seedK))
                return ys[0], ys[1], comb, scratch

            self._grow_batch_p = jax.jit(_scan_k, donate_argnums=(0, 1))
        return self._grow_batch_p

    def grow_batch(self, bins, gradK, hessK, inbag, fmK, num_bins,
                   has_nan, is_cat, seedK):
        """Grow all K class trees in one compiled dispatch (ISSUE 19).
        ``gradK``/``hessK``/``fmK``/``seedK`` carry a leading [K] axis;
        the bins argument is accepted and ignored like ``__call__``'s.
        Returns stacked ``(taK, leaf_idK)`` — every leaf array gains a
        leading [K] axis and ``leaf_idK`` is [K, n]; per-class device
        slices of these are bitwise the serial outputs.  Ineligible
        modes raise loudly rather than silently serializing — routing
        (``mc_batch_paged`` / ``mc_batch_requires_physical``) must gate
        the call sites."""
        if self._stream_init is not None:
            raise RuntimeError(
                "batched multiclass grow is a physical non-stream "
                "path (stream keeps the multi_tree_iter rule)")
        if self._pages is not None or self.paged is not None:
            raise RuntimeError(
                "batched multiclass grow does not engage on the paged "
                "comb (routing rule mc_batch_paged)")
        if self._comb is None:
            self._init_buffers()
        out = self.batched_fn()(
            self._comb, self._scratch, gradK, hessK, inbag, fmK,
            num_bins, has_nan, is_cat, jnp.asarray(seedK, jnp.int32))
        taK, leaf_idK, self._comb, self._scratch = out[:4]
        return taK, leaf_idK

    def paged_geometry(self):
        """The ENGAGED page geometry (None when unpaged) — what the
        tests equality-check against ``costmodel.page_schedule`` and
        bench records embed in their paged block."""
        if self._pages is None:
            return None
        geo = self._pages.geometry()
        geo["stats"] = dict(self._pages.stats)
        return geo


class _NumericsGuard:
    """Opt-in NaN/Inf sentinel wrapper around a built grow callable
    (ISSUE 13, ``LGBM_TPU_NUMERICS``; policy semantics in
    resilience/numerics.py).

    * ``clamp`` sanitizes grad/hess (NaN -> 0, ±Inf -> ±1e30, clamped)
      in a separate tiny jit BEFORE delegating — the grow program
      itself is untouched;
    * ``raise`` / ``skip`` delegate first, then attach one i32 device
      scalar (``.last_numerics_bad``) counting non-finites across
      grad/hess and the grown tree's leaf values + split gains (where
      histogram and gain non-finites surface).  The PULL is the
      caller's (gbdt checks it post-grow and raises NumericalFault /
      NumericsSkip) so the async dispatch chain stays intact until the
      booster decides to look.

    Everything else (``fused``, ``set_stream_aux``,
    ``reset_stream``) delegates to the wrapped callable.  ``off``
    never constructs this class at all — ``make_grow_fn`` returns the
    unwrapped program (the ``grow-numerics-off`` purity pin)."""

    def __init__(self, fn, policy: str):
        self._fn = fn
        self.numerics_policy = policy
        self.last_numerics_bad = None

    def __call__(self, bins, grad, hess, *rest):
        from ..resilience import numerics as _numerics
        if self.numerics_policy == "clamp":
            grad, hess = _numerics.sanitize_fn()(grad, hess)
            return self._fn(bins, grad, hess, *rest)
        out = self._fn(bins, grad, hess, *rest)
        ta = out[0]
        self.last_numerics_bad = _numerics.count_bad_fn()(
            grad, hess, ta.leaf_value, ta.split_gain)
        return out

    def grow_batch(self, bins, gradK, hessK, *rest):
        """Batched-K variant (ISSUE 19): clamp sanitizes the [K, n]
        gradient block in one jit; raise/skip attach a [K] PER-CLASS
        bad vector so a poisoned class degrades to a zero stump
        without dropping its siblings (the caller pulls per class)."""
        from ..resilience import numerics as _numerics
        if self.numerics_policy == "clamp":
            gradK, hessK = _numerics.sanitize_fn()(gradK, hessK)
            return self._fn.grow_batch(bins, gradK, hessK, *rest)
        out = self._fn.grow_batch(bins, gradK, hessK, *rest)
        taK = out[0]
        self.last_numerics_bad = jax.vmap(_numerics.count_bad_fn())(
            gradK, hessK, taK.leaf_value, taK.split_gain)
        return out

    def __getattr__(self, name):
        # only reached when normal lookup fails: delegate wrapped-fn
        # attributes (fused, stream hooks)
        return getattr(self._fn, name)
