"""Device tree traversal (bin space and raw space).

Reference analog: Tree::Predict / NumericalDecisionInner walks
(include/LightGBM/tree.h:133,360) and the CUDA score updater's leaf-indexed
AddScore (src/boosting/cuda/cuda_score_updater.cu).

Binned rows take one of two routes in :func:`predict_leaf_bins`, by the
bins' dtype.  **u8 bins: the decision-matrix replay.**  Rows go in blocks
of B (:func:`replay_block_rows`: the largest multiple of 256 whose
temporaries, with the tree's path matrix, fit ``REPLAY_BYTES``; a static
ragged block closes).  For a block, one matmul of its bins (bf16) by the
one-hot of each inner node's physical column reads every node's column
for every row; :func:`_goes_left` decides every node at once; one matmul
of the +1 / -1 decisions by the tree's path matrix (:func:`tree_paths`)
counts, for each leaf, the nodes on its path the row agrees with, and the
row's leaf is the one whose count is its path length.  Both matmuls are
exact: a bin <= 255 and +-1 are exact in bfloat16 and the sums are
integers in f32.  The work is a row's decisions at every node, whatever
the tree's depth, on the MXU with no per-row gather (one XLA fusion a
block on the v5e).  **i32 bins** (past 256 levels) and a tree whose path
matrix leaves no room in ``REPLAY_BYTES`` for 256 rows: the lock-step
walk (:func:`_walk_leaves`), a ``fori_loop`` of ``split_feature.shape[0]``
steps with every row advanced one node a step (a per-row gather of its
column and node fields; leaves encode as negative node ids so finished
rows stop moving).  Both routes decide by one rule, :func:`_goes_left`.

Used for: validation-set score updates each iteration, DART's
add/subtract-tree score manipulation, and batch prediction of binned data.

Forest kernels (ISSUE 14, the serving engine): :class:`ServingForest`
stacks EVERY tree of a trained booster into one set of padded node
arrays (``[T, ni_max]`` / ``[T, nl_max]``) plus per-feature quantizer
tables, so a whole batch traverses the whole forest level-synchronously
— one gather per level over the ``[rows, trees]`` node-pointer matrix —
with on-device raw->bin quantization (callers send raw f32 rows, not
pre-binned data) and the summed scores written into a DONATED buffer.
``serve/model.py`` builds the arrays from host trees; ``serve/engine.py``
adds the bucketed jit dispatch around :func:`forest_scores`.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class DeviceTree(NamedTuple):
    """Bin-space tree for device traversal (subset of ops.grow.TreeArrays)."""
    split_feature: jnp.ndarray   # [ni] i32 inner feature idx
    threshold_bin: jnp.ndarray   # [ni] i32
    default_left: jnp.ndarray    # [ni] bool
    is_categorical: jnp.ndarray  # [ni] bool
    left_child: jnp.ndarray      # [ni] i32
    right_child: jnp.ndarray     # [ni] i32
    leaf_value: jnp.ndarray      # [nl] f32
    num_leaves: jnp.ndarray      # scalar i32
    # categorical membership bitset words over BINS, [ni, W] i32 (W =
    # ceil(B/32)); [ni, 0] when every cat split is one-hot (threshold_bin
    # then holds the single bin).  Reference: Tree::CategoricalDecision
    # bitset walk, tree.h:271-279.
    cat_words: jnp.ndarray


def _members_to_words(members: jnp.ndarray) -> jnp.ndarray:
    """[ni, B] f32/bool 0/1 membership -> [ni, ceil(B/32)] i32 bitset
    words (i32 wraparound keeps the bit pattern for bit 31)."""
    ni, b = members.shape
    w = -(-b // 32)
    m = members.astype(jnp.int32)
    if w * 32 != b:
        m = jnp.pad(m, ((0, 0), (0, w * 32 - b)))
    m = m.reshape(ni, w, 32)
    shifts = (jnp.int32(1) << jnp.arange(32, dtype=jnp.int32))
    return jnp.sum(m * shifts[None, None, :], axis=-1, dtype=jnp.int32)


def device_tree_from_arrays(ta) -> DeviceTree:
    cm = ta.cat_members
    ni = ta.split_feature.shape[0]
    if cm.shape[0] == ni and cm.shape[1] > 1:
        words = _members_to_words(cm)
    else:
        words = jnp.zeros((ni, 0), jnp.int32)
    return DeviceTree(
        split_feature=ta.split_feature,
        threshold_bin=ta.threshold_bin,
        default_left=ta.default_left,
        is_categorical=ta.is_categorical,
        left_child=ta.left_child,
        right_child=ta.right_child,
        leaf_value=ta.leaf_value,
        num_leaves=ta.num_leaves,
        cat_words=words,
    )


class _Split(NamedTuple):
    """Inner nodes' splits as the bin-space rule reads them; each field
    broadcasts against the stored column values it decides."""
    threshold: jnp.ndarray
    default_left: jnp.ndarray
    categorical: jnp.ndarray
    num_bins: jnp.ndarray        # of the node's feature
    has_nan: jnp.ndarray
    offset: jnp.ndarray = None   # EFB: the feature's first bin in its
    default_bin: jnp.ndarray = None  # bundle column, and its default bin


def _splits_at(tree: DeviceTree, nd, num_bins, has_nan, feat_map):
    """(physical column, :class:`_Split`) of the nodes ``nd`` (an index
    array, or ``slice(None)`` for every node)."""
    feat = tree.split_feature[nd]
    phys, off, dflt = feat, None, None
    if feat_map is not None:
        fp_, fo_, fd_ = feat_map
        phys, off, dflt = fp_[feat], fo_[feat], fd_[feat]
    return phys, _Split(tree.threshold_bin[nd], tree.default_left[nd],
                        tree.is_categorical[nd], num_bins[feat],
                        has_nan[feat], off, dflt)


def _goes_left(col, s: _Split, word_of):
    """NumericalDecisionInner / CategoricalDecisionInner in bin space:
    does a row whose split column stores ``col`` (i32) go left.  Under
    EFB the column is a bundle's and is mapped back to the feature's own
    bin space first (rows outside its stacked range -> its default bin).
    ``word_of(b)``: the node's bitset word that holds bin ``b`` (bit
    ``b % 32``), or None where every categorical split is one-hot."""
    b = col
    if s.offset is not None:
        inr = (col >= s.offset) & (col < s.offset + s.num_bins)
        b = jnp.where(inr, col - s.offset, s.default_bin)
    at_nan = s.has_nan & (b == s.num_bins - 1)
    if word_of is None:
        cat_go = b == s.threshold
    else:
        cat_go = ((word_of(b) >> (b % 32)) & 1) > 0
    return jnp.where(s.categorical, cat_go,
                     ((b <= s.threshold) & ~at_nan)
                     | (at_nan & s.default_left))


# the decision-matrix replay's working set: the path matrix, the column
# one-hot and one block of rows' temporaries (module docstring)
REPLAY_BYTES = 32 << 20
_BLOCK_ALIGN = 256


def replay_block_rows(bins, ni: int) -> int:
    """Rows a block of the decision-matrix replay of ``bins`` ([n,
    F_phys]) under a tree of ``ni`` inner nodes holds, from shapes alone;
    0 where the lock-step walk replays instead: bins past 256 levels
    (i32: not exact in bfloat16), no inner node, or a tree whose path
    matrix leaves no room for a block of ``_BLOCK_ALIGN`` rows."""
    n, f = bins.shape
    if bins.dtype != jnp.uint8 or ni == 0:
        return 0
    nl = ni + 1
    fixed = ni * nl * 6 + f * ni * 2      # i32 build + bf16 P, bf16 S
    # a row: its u8 block and bf16 copy, the f32 columns and bf16 signs,
    # the f32 path sums and their compare
    per_row = 3 * f + 6 * ni + 8 * nl
    rows = (REPLAY_BYTES - fixed) // per_row // _BLOCK_ALIGN * _BLOCK_ALIGN
    if rows < _BLOCK_ALIGN:
        return 0
    return min(rows, n)


def tree_paths(tree: DeviceTree):
    """The tree's path matrix and path lengths, from its child arrays:
    ``paths[j, l]`` (bf16 [ni, ni + 1]) is +1 where leaf ``l`` lies
    under node ``j``'s left child, -1 under its right, 0 elsewhere (and
    on every node past ``num_leaves - 1``); ``path_len[l]`` (f32) is the
    number of nodes over leaf ``l``, -1 for a leaf past ``num_leaves``.
    A row whose decisions are ``s[j]`` = +1 (left) / -1 reaches leaf
    ``l`` iff ``sum_j s[j] paths[j, l] == path_len[l]``: exactly one
    leaf, and a stump's leaf 0 (length 0) takes every row.  Node-sized
    work: a ``while`` that climbs from every leaf at once, the tree's
    depth in trips."""
    ni = tree.left_child.shape[0]
    nl = ni + 1
    nodes = jnp.arange(ni, dtype=jnp.int32)
    real = nodes < tree.num_leaves - 1
    # ``ni`` is neither a node nor a leaf code: a node past the tree's
    # points at nothing
    lc = jnp.where(real, tree.left_child, ni)
    rc = jnp.where(real, tree.right_child, ni)

    def parent_of(codes):
        is_l = lc[:, None] == codes[None, :]
        is_r = rc[:, None] == codes[None, :]
        up = jnp.max(jnp.where(is_l | is_r, nodes[:, None], -1), axis=0)
        return up, jnp.where(jnp.any(is_l, axis=0), 1, -1)

    node_up, node_side = parent_of(nodes)
    leaf_up, leaf_side = parent_of(~jnp.arange(nl, dtype=jnp.int32))

    def climb(carry):
        p, cur, side, depth = carry
        p = p + jnp.where(nodes[:, None] == cur[None, :], side[None, :], 0)
        at = jnp.maximum(cur, 0)
        up = jnp.where(cur >= 0, node_up[at], -1)
        return p, up, node_side[at], depth + (cur >= 0)

    p, _, _, depth = jax.lax.while_loop(
        lambda c: jnp.any(c[1] >= 0), climb,
        (jnp.zeros((ni, nl), jnp.int32), leaf_up, leaf_side,
         jnp.zeros(nl, jnp.int32)))
    path_len = jnp.where(jnp.arange(nl) < tree.num_leaves, depth, -1)
    return p.astype(jnp.bfloat16), path_len.astype(jnp.float32)


def _matmul_leaves(tree, bins, num_bins, has_nan, feat_map, paths,
                   block):
    """The decision-matrix replay: rows in blocks of ``block``, each
    block's node columns read by one matmul against the nodes' column
    one-hot, every node decided at once by :func:`_goes_left`, the leaf
    found by one matmul against the path matrix."""
    n, f = bins.shape
    ni = tree.split_feature.shape[0]
    w = tree.cat_words.shape[1]
    p, path_len = tree_paths(tree) if paths is None else paths
    phys, s = _splits_at(tree, slice(None), num_bins, has_nan, feat_map)
    s = jax.tree.map(lambda a: a[None, :], s)
    onehot = (jnp.arange(f, dtype=jnp.int32)[:, None]
              == phys[None, :]).astype(jnp.bfloat16)      # [F_phys, ni]
    leaf_ids = jnp.arange(ni + 1, dtype=jnp.int32)

    def word_of(b):
        # the node's word by a select over its W words, not a gather
        q = b // 32
        word = jnp.zeros_like(b)
        for k in range(w):
            word = jnp.where(q == k, tree.cat_words[None, :, k], word)
        return word

    def leaves(blk):
        # a bin <= 255 times 1.0 is exact in bf16 x bf16 -> f32
        col = jnp.dot(blk.astype(jnp.bfloat16), onehot,
                      preferred_element_type=jnp.float32)
        go = _goes_left(col.astype(jnp.int32), s,
                        word_of if w > 0 else None)
        hits = jnp.dot(jnp.where(go, 1.0, -1.0).astype(jnp.bfloat16), p,
                       preferred_element_type=jnp.float32)
        return jnp.max(jnp.where(hits == path_len[None, :],
                                 leaf_ids[None, :], -1), axis=1)

    whole = n // block
    out = jnp.zeros(n, jnp.int32)
    if whole:
        def body(i, out):
            at = i * block
            blk = jax.lax.dynamic_slice_in_dim(bins, at, block)
            return jax.lax.dynamic_update_slice_in_dim(out, leaves(blk),
                                                       at, 0)
        out = jax.lax.fori_loop(0, whole, body, out)
    if n > whole * block:
        out = out.at[whole * block:].set(leaves(bins[whole * block:]))
    return out


@jax.jit
def predict_leaf_bins(
    tree: DeviceTree,
    bins: jnp.ndarray,       # [n, F_phys] uint8/int32
    num_bins: jnp.ndarray,   # [F_log] i32
    has_nan: jnp.ndarray,    # [F_log] bool
    feat_map=None,           # EFB: (feat_phys, feat_offset, feat_default)
    paths=None,              # tree_paths(tree), where a caller shares it
) -> jnp.ndarray:
    """Rows -> leaf index, in bin space (NumericalDecisionInner).

    u8 bins take the decision-matrix replay (module docstring); i32
    bins and trees whose path matrix outgrows ``REPLAY_BYTES`` take the
    lock-step walk, one node a step.  With ``feat_map`` set (EFB device
    layout), tree features are logical and the split reads the bundle
    column, mapping back to the feature's own bin space."""
    block = replay_block_rows(bins, tree.split_feature.shape[0])
    if block:
        return _matmul_leaves(tree, bins, num_bins, has_nan, feat_map,
                              paths, block)
    return _walk_leaves(tree, bins, num_bins, has_nan, feat_map)


def _walk_leaves(tree, bins, num_bins, has_nan, feat_map=None):
    """The lock-step walk: every row advanced one node a step, a per-row
    gather of its split column and of each node field, for
    ``split_feature.shape[0]`` steps whatever the tree's depth; leaves
    encode as negative node ids, so a finished row stops moving."""
    n = bins.shape[0]
    max_steps = tree.split_feature.shape[0]  # depth <= num internal nodes
    w = tree.cat_words.shape[1]

    def body(_, node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        phys, s = _splits_at(tree, nd, num_bins, has_nan, feat_map)
        # per-row feature gather
        col = jnp.take_along_axis(
            bins, phys[:, None].astype(jnp.int32),
            axis=1)[:, 0].astype(jnp.int32)
        # bitset membership walk (Tree::CategoricalDecision)
        go_left = _goes_left(col, s, None if w == 0 else (
            lambda b: jnp.take(tree.cat_words.reshape(-1),
                               nd * w + (b // 32))))
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(active, nxt, node)

    if max_steps == 0:
        return jnp.zeros(n, jnp.int32)
    node = jnp.zeros(n, jnp.int32)
    node = jax.lax.fori_loop(0, max_steps, body, node)
    return (~node).astype(jnp.int32)


def add_tree_score(score, tree: DeviceTree, bins, num_bins, has_nan, scale,
                   feat_map=None):
    """score += scale * tree(bins); the ScoreUpdater::AddScore analog."""
    leaf = predict_leaf_bins(tree, bins, num_bins, has_nan,
                             feat_map=feat_map)
    return score + scale * tree.leaf_value[leaf]


def tree_to_device(tree, dataset) -> DeviceTree:
    """Finalized host Tree -> bin-space DeviceTree (leaf values include
    shrinkage and any folded-in init bias).  ``dataset`` supplies the
    original->inner feature mapping."""
    import numpy as np
    ni = tree.num_leaves - 1
    orig_to_inner = {int(o): i for i, o in enumerate(dataset.used_feature_map)}
    inner = np.array(
        [orig_to_inner[int(f)] for f in tree.split_feature[:ni]], np.int32)
    default_left = (tree.decision_type[:ni].astype(np.int32) & 2) > 0
    is_cat = (tree.decision_type[:ni].astype(np.int32) & 1) > 0
    # categorical membership: expand the per-node inner bitsets (over
    # bins) into fixed-width word rows for the device walk.  Trees loaded
    # from model text carry only the RAW-value bitsets
    # (cat_boundaries_inner stays [0]); rebuild bin membership through
    # the mapper's value->bin table in that case.
    if getattr(tree, "num_cat", 0):
        max_b = max(int(m.num_bins) for m in dataset.mappers)
        w = -(-max_b // 32)
        words = np.zeros((ni, w), np.uint32)
        have_inner = len(tree.cat_boundaries_inner) > tree.num_cat
        for i in range(ni):
            if not is_cat[i]:
                continue
            slot = int(tree.threshold[i])
            if have_inner:
                lo = int(tree.cat_boundaries_inner[slot])
                hi = int(tree.cat_boundaries_inner[slot + 1])
                row = tree.cat_threshold_inner[lo:hi]
                words[i, :hi - lo] = row
            else:
                mapper = dataset.mappers[inner[i]]
                lo = int(tree.cat_boundaries[slot])
                hi = int(tree.cat_boundaries[slot + 1])
                raw = tree.cat_threshold[lo:hi]
                for v, bn in zip(mapper.cat_values, mapper.cat_bins):
                    word_i = int(v) // 32
                    if word_i < hi - lo and (
                            int(raw[word_i]) >> (int(v) % 32)) & 1:
                        words[i, int(bn) // 32] |= np.uint32(
                            1 << (int(bn) % 32))
        cat_words = jnp.asarray(words.view(np.int32).reshape(ni, w))
    else:
        cat_words = jnp.zeros((ni, 0), jnp.int32)
    return DeviceTree(
        split_feature=jnp.asarray(inner if ni else np.zeros(0, np.int32)),
        threshold_bin=jnp.asarray(tree.threshold_bin[:ni].astype(np.int32)),
        default_left=jnp.asarray(default_left),
        is_categorical=jnp.asarray(is_cat),
        left_child=jnp.asarray(tree.left_child[:ni].astype(np.int32)),
        right_child=jnp.asarray(tree.right_child[:ni].astype(np.int32)),
        leaf_value=jnp.asarray(tree.leaf_value.astype(np.float32)),
        num_leaves=jnp.int32(tree.num_leaves),
        cat_words=cat_words,
    )


# ---------------------------------------------------------------------
# forest-tensorized serving kernels (ISSUE 14)
# ---------------------------------------------------------------------
class ServingForest(NamedTuple):
    """Every tree of a booster slice stacked into padded device arrays,
    plus the per-(inner)-feature quantizer tables.

    Node arrays are ``[T, ni_pad]`` with ``ni_pad`` (and the leaf
    table's ``nl_pad``) padded up to 128-lane multiples since ISSUE 18
    — the serve kernel DMAs them into VMEM as whole HBM rows, and the
    lane contract (``ops/pallas/layout.check_lane_width``) wants minor
    dims in 128-lane granularity; child pointers never visit the pad
    nodes, so the XLA gather walk is indifferent to the padding.  A
    single-leaf tree starts at ``init_node = -1`` and never moves on
    the gather walk; its node-0 children are BOTH ``~0`` so the
    kernel path (which starts every tree at node 0) parks on leaf 0
    after one step.
    Categorical membership uses the RAW-value bitsets (the reference's
    ``cat_threshold`` words, tree.h:271-279) — NOT the bin bitsets the
    training walk uses — so the compiled walk bit-matches the host
    reference walk (``Tree.predict_leaf``) for unseen/rare categories.
    The quantizer's ``ub`` rows are the f64 bin upper bounds rounded
    DOWN to f32: for any f32 input x, ``x <= ub_f32`` is then exactly
    ``x <= ub_f64``, so bin-space threshold comparisons reproduce the
    host's raw-space decisions bit-for-bit."""
    # node arrays [T, ni_max]
    split_feature: jnp.ndarray   # i32 inner feature idx
    threshold_bin: jnp.ndarray   # i32
    default_left: jnp.ndarray    # bool (NaN direction).  The walk
                                 # decodes it from node_meta bit 0
                                 # since the packed-word change; the
                                 # array itself stays for the model
                                 # digest and host-side diagnostics
                                 # and rides the dispatch unread
    is_categorical: jnp.ndarray  # bool
    left_child: jnp.ndarray      # i32, ~leaf encoding
    right_child: jnp.ndarray     # i32
    leaf_value: jnp.ndarray      # [T, nl_pad] f32 — or bf16 under
                                 # LGBM_TPU_SERVE_LEAF_BF16 (scores
                                 # still accumulate f32; the gathers
                                 # below upcast right after the read)
    init_node: jnp.ndarray       # [T] i32: 0, or -1 for single-leaf
    cat_words: jnp.ndarray       # [T, ni_pad * W] i32 raw-value
                                 # bitsets, stored FLAT per tree so
                                 # the serve kernel DMAs lane-clean
                                 # [T, ni_pad*W] HBM rows (W recovers
                                 # as shape[1] // ni_pad)
    cat_nbits: jnp.ndarray       # [T, ni_pad] i32 valid bits per node
    # quantizer tables [F] / [F, B] (F = inner features)
    used_cols: jnp.ndarray       # i32 original column per inner feature
    ub: jnp.ndarray              # f32 upper bounds (floor-rounded), +inf pad
    default_bin: jnp.ndarray     # i32 bin of value 0.0
    num_bins: jnp.ndarray        # i32
    has_nan: jnp.ndarray         # bool (missing_type == NAN)
    missing_zero: jnp.ndarray    # bool (missing_type == ZERO)
    # packed per-node metadata word [T, ni_pad] i32 (PERF_NOTES round
    # 17 headroom #1, widened by ISSUE 18):
    #   (nan_bin << 3) | (is_categorical << 2) | (has_nan << 1)
    #                  | default_left
    # baked per node at build time, so the level-synchronous walk
    # reads ONE word per (row, tree) instead of re-gathering the
    # feature-indexed num_bins/has_nan arrays and the default_left
    # node array every level.  Bit 2 lets the serve kernel drop the
    # separate is_categorical array from its VMEM-resident set; the
    # XLA gather walk keeps its is_categorical gather (the priced
    # 6-gather/28 B serving_traversal_bytes contract is unchanged).
    node_meta: jnp.ndarray
    # per-inner-feature categorical flag [F] bool: which columns of the
    # kernel's single [n, F] i32 matrix carry int-truncated raw values
    # (categorical membership) instead of quantized bins — the column
    # select in quantize_rows_kernel.  The gather walk never reads it
    # (it re-gathers raw values per level instead).
    cat_col: jnp.ndarray


# any finite value quantizes below this; +inf rows land here so they
# compare greater than every threshold bin (the host walk's
# ``v <= f64max -> False``) and miss the NaN bin equality check
# (np, not jnp: a module-level jnp constant would run a computation at
# import and break jax.distributed.initialize in multi-process workers)
_BIG_BIN = np.int32(1 << 24)
_KZERO = 1e-35


def quantize_rows(forest: ServingForest, raw_used: jnp.ndarray) -> jnp.ndarray:
    """[n, F] raw f32 (inner-feature order) -> [n, F] i32 logical bins,
    mirroring the HOST walk's missing semantics (``Tree.predict_leaf``):
    NaN -> nan bin (missing NAN) else the bin of 0.0; |v| <= 1e-35 ->
    the zero bin under zero_as_missing; +inf -> a sentinel past every
    threshold.  Categorical columns pass through the searchsorted too
    but their bins are never read (the walk uses raw values)."""
    b = jax.vmap(
        lambda ub, col: jnp.searchsorted(ub, col, side="left")
    )(forest.ub, raw_used.T).T.astype(jnp.int32)
    isnan = jnp.isnan(raw_used)
    db = forest.default_bin[None, :]
    b = jnp.where(forest.missing_zero[None, :]
                  & (jnp.abs(raw_used) <= _KZERO), db, b)
    b = jnp.where(isnan,
                  jnp.where(forest.has_nan[None, :],
                            forest.num_bins[None, :] - 1, db), b)
    return jnp.where(raw_used == jnp.inf, _BIG_BIN, b)


def quantize_rows_kernel(forest: ServingForest,
                         raw_used: jnp.ndarray) -> jnp.ndarray:
    """[n, F] raw f32 -> the serve kernel's SINGLE [n, F] i32 input:
    quantized bins on numerical columns, int-truncated raw values on
    categorical columns (NaN/inf -> -1, which the kernel's bitset test
    rejects like the host walk).  Folding the cat columns in here is
    what lets the kernel stream ONE i32 row matrix through its
    double-buffered VMEM tiles instead of a second f32 raw tile —
    ``costmodel.serving_kernel_bytes`` prices exactly one [n, F] i32
    pass for this reason."""
    b = quantize_rows(forest, raw_used)
    iv = jnp.where(jnp.isfinite(raw_used), raw_used,
                   -1.0).astype(jnp.int32)
    return jnp.where(forest.cat_col[None, :], iv, b)


def _forest_walk(forest: ServingForest, raw_used, bins, n_steps: int):
    """[n, F] bins/raw -> [n, T] leaf indices: lock-step node-pointer
    chase over ALL trees at once, one flat gather per node field per
    level (``n_steps`` = the forest's max depth, a static build fact)."""
    n = raw_used.shape[0]
    t_cnt, ni = forest.split_feature.shape
    tri = jnp.arange(t_cnt, dtype=jnp.int32)[None, :]      # [1, T]
    sf = forest.split_feature.reshape(-1)
    tb_f = forest.threshold_bin.reshape(-1)
    cat_f = forest.is_categorical.reshape(-1)
    lc_f = forest.left_child.reshape(-1)
    rc_f = forest.right_child.reshape(-1)
    nm_f = forest.node_meta.reshape(-1)
    nbits_f = forest.cat_nbits.reshape(-1)
    # cat_words is stored flat ([T, ni * W], node-major) since the
    # ISSUE-18 restack; node nd of tree t keeps its W words contiguous
    # at flat offset gidx * w, same as the old [T, ni, W] layout
    w = forest.cat_words.shape[-1] // max(ni, 1)

    def body(_, node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        gidx = tri * ni + nd                               # [n, T]
        feat = sf[gidx]
        b = jnp.take_along_axis(bins, feat, axis=1)
        # the packed metadata word replaces the per-level has_nan /
        # num_bins feature gathers and the default_left node gather:
        # nan-bin equality + NaN direction decode from one i32
        meta = nm_f[gidx]
        at_nan = ((meta & 2) > 0) & (b == (meta >> 3))
        go_num = ((b <= tb_f[gidx]) & ~at_nan) | (at_nan
                                                  & ((meta & 1) > 0))
        if w > 0:
            # raw-value bitset membership (Tree::CategoricalDecision):
            # int-truncate like the host, NaN/inf -> -1 -> right
            v = jnp.take_along_axis(raw_used, feat, axis=1)
            iv = jnp.where(jnp.isfinite(v), v, -1.0).astype(jnp.int32)
            ok = (iv >= 0) & (iv < nbits_f[gidx])
            ivc = jnp.clip(iv, 0, w * 32 - 1)
            word = forest.cat_words.reshape(-1)[gidx * w + ivc // 32]
            go_cat = ok & (((word >> (ivc % 32)) & 1) > 0)
            go_left = jnp.where(cat_f[gidx], go_cat, go_num)
        else:
            go_left = go_num
        nxt = jnp.where(go_left, lc_f[gidx], rc_f[gidx])
        return jnp.where(active, nxt, node)

    node = jnp.broadcast_to(forest.init_node[None, :], (n, t_cnt))
    if n_steps > 0:
        node = jax.lax.fori_loop(0, n_steps, body, node)
    # n_steps equals the forest's max depth, so every row has parked at
    # a leaf (~leaf < 0); the min() keeps a hypothetical straggler in
    # range instead of reading past leaf_value
    return ~jnp.minimum(node, -1)


def forest_leaves(forest: ServingForest, raw, n_real, *,
                  n_steps: int) -> jnp.ndarray:
    """[n, Forig] raw rows -> [n, T] leaf indices (the exactness side
    of the parity contract; rows >= n_real are bucket padding)."""
    raw_used = raw[:, forest.used_cols]
    bins = quantize_rows(forest, raw_used)
    leaf = _forest_walk(forest, raw_used, bins, n_steps)
    rows = jax.lax.broadcasted_iota(jnp.int32, (raw.shape[0], 1), 0)
    return jnp.where(rows < n_real, leaf, 0)


def forest_scores(forest: ServingForest, raw, n_real, score_buf, *,
                  n_steps: int) -> jnp.ndarray:
    """One bucketed serving dispatch: quantize [n, Forig] raw f32 rows
    on device, walk the whole forest level-synchronously, and sum leaf
    values per class into the DONATED ``score_buf`` ([n, K] f32 — the
    engine rotates a per-bucket buffer pool through the donation so
    steady-state dispatches allocate nothing).  ``n_real`` rides as a
    traced scalar — the body must never consume the true row count at
    trace time, or every batch size in a bucket would recompile (the
    ROUTING_RETRACE contract); rows past it are bucket padding and
    come back zero."""
    n = raw.shape[0]
    t_cnt = forest.split_feature.shape[0]
    k = score_buf.shape[1]
    raw_used = raw[:, forest.used_cols]
    bins = quantize_rows(forest, raw_used)
    leaf = _forest_walk(forest, raw_used, bins, n_steps)
    nl = forest.leaf_value.shape[1]
    tri = jnp.arange(t_cnt, dtype=jnp.int32)[None, :]
    # upcast right after the gather: leaf_value may be bf16 under
    # LGBM_TPU_SERVE_LEAF_BF16, but scores always accumulate f32
    vals = forest.leaf_value.reshape(-1)[tri * nl + leaf].astype(
        jnp.float32)                                       # [n, T]
    # t = it*K + kk (the models-list ordering) -> sum over iterations
    per_class = vals.reshape(n, t_cnt // max(k, 1), k).sum(axis=1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    # score_buf * 0 keeps the donated buffer live in the program so the
    # input/output aliasing survives lowering (the PR-9 audit class)
    return jnp.where(rows < n_real, score_buf * 0.0 + per_class, 0.0)


_FOREST_FIELDS = len(ServingForest._fields)


def forest_scores_flat(*args, n_steps: int):
    """Flat-argument wrapper for the static analyzer: the registered
    ``serve_forest`` entrypoint declares the donated score-buffer
    argnum on a flat signature (``analysis/entries.py``), so the
    hbm-budget pass can audit that the donation survives lowering."""
    forest = ServingForest(*args[:_FOREST_FIELDS])
    raw, n_real, score_buf = args[_FOREST_FIELDS:]
    return forest_scores(forest, raw, n_real, score_buf,
                         n_steps=n_steps)
