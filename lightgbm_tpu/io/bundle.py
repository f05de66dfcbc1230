"""Exclusive Feature Bundling (EFB).

Reference: src/io/dataset.cpp:102-247 (``FindGroups`` /
``FastFeatureBundling``) — mutually (near-)exclusive sparse features are
bundled into one bin column with stacked bin ranges, so the histogram pass
costs one column per bundle instead of one per feature.

TPU re-design: the HOST dataset keeps the logical per-feature view (mappers,
bin matrix or sparse store, model space are unchanged); bundling happens at
device-layout time.  The device bin matrix carries one physical column per
bundle and the histogram kernels run over physical columns.  What turns a
bundle-space histogram into split candidates of logical features depends
on the route (ops/routing.py, ``RouteDecision.efb``): on the physical route
with the plain finder the comb keeps the bundle columns and
``split.find_best_split_segments`` takes each feature's prefix sums inside
its static range of the bundle column (ISSUE 36); with the other grow
options and under the mesh learners the comb ingest unbundles the columns
(``device_data.unbundle_bins``); on ``row_order`` a gather expands the
histogram to logical features (``grow.expand``).  No row stores a bundled
feature's default bin: ``expand`` rebuilds it from the leaf totals (the
``FixHistogram`` trick, dataset.h:676), the ingest writes it back into
the rows, and the bundle-space finder sums the column's other bins, where
those rows sit.  Split search, tree structure and the saved model always
speak original features — bundles are invisible above the histogram,
exactly like the reference.

Bundle column layout: bin 0 = "every sub-feature at its default bin";
sub-feature j owns [offset_j, offset_j + num_bins_j) and a row maps to
``offset_j + logical_bin`` when its bin differs from j's default.  Rows
that are non-default in two sub-features (conflicts, bounded by
``max_conflict_rate``) keep the later feature's value, like the
reference's overwrite semantics.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from ..utils import log


@dataclasses.dataclass
class BundleInfo:
    """Device-layout bundling plan over the LOGICAL used-feature axis."""
    # per logical feature
    feat_phys: np.ndarray      # [f_log] i32 physical column
    feat_offset: np.ndarray    # [f_log] i32 bin offset within the column
    feat_default: np.ndarray   # [f_log] i32 default (most frequent) bin
    is_bundled: np.ndarray     # [f_log] bool
    # physical columns
    num_phys: int
    phys_num_bins: np.ndarray  # [num_phys] i32
    conflict_rows: int = 0     # sampled rows non-default in two members
                               # of one bundle (<= max_conflict_rate)

    @property
    def any_bundled(self) -> bool:
        return bool(self.is_bundled.any())


def find_bundles(
    bin_matrix,                      # [n, f_log] logical bins, or a
                                     # dataset_core.SparseBins store
    num_bins: np.ndarray,            # [f_log]
    has_nan: np.ndarray,             # [f_log] bool
    is_cat: np.ndarray,              # [f_log] bool
    *,
    max_conflict_rate: float = 0.0,
    sparse_threshold: float = 0.8,
    max_bundle_bins: int = 255,
    sample_rows: int = 100_000,
    min_bundle_size: int = 2,
) -> Optional[BundleInfo]:
    """Greedy conflict-bounded bundling (FindGroups, dataset.cpp:102).

    Only dense-ish NUMERICAL features without a NaN bin are left unbundled
    candidates: bundling needs a dominant default bin to stack ranges.
    Returns None when no bundle with >= min_bundle_size members exists.
    """
    n, f = bin_matrix.shape
    if f == 0 or n == 0:
        return None
    rows = min(n, sample_rows)
    if rows < n:
        # random sample (the reference's FindGroups samples random row
        # indices; a prefix would bias default-bin/conflict estimates on
        # time-ordered data)
        sidx = np.random.default_rng(1).choice(n, size=rows, replace=False)
        sample = bin_matrix[np.sort(sidx)]
    else:
        sample = (bin_matrix if isinstance(bin_matrix, np.ndarray)
                  else bin_matrix[np.arange(n)])

    default_bin = np.zeros(f, np.int32)
    nz_masks: List[Optional[np.ndarray]] = [None] * f
    candidates: List[int] = []
    for j in range(f):
        col = sample[:, j]
        counts = np.bincount(col, minlength=int(num_bins[j]))
        default_bin[j] = int(np.argmax(counts))
        if has_nan[j] or is_cat[j]:
            continue
        nz = col != default_bin[j]
        if nz.mean() <= 1.0 - sparse_threshold:
            nz_masks[j] = nz
            candidates.append(j)

    if len(candidates) < min_bundle_size:
        return None

    # order by nonzero count descending (reference sorts by conflict count)
    candidates.sort(key=lambda j: -int(nz_masks[j].sum()))
    max_conflicts = int(max_conflict_rate * rows)
    bundles: List[List[int]] = []
    bundle_nz: List[np.ndarray] = []
    bundle_conflicts: List[int] = []
    bundle_bins: List[int] = []
    for j in candidates:
        nzj = nz_masks[j]
        placed = False
        for b in range(len(bundles)):
            nb_j = int(num_bins[j])
            if bundle_bins[b] + nb_j > max_bundle_bins:
                continue
            conflicts = int((bundle_nz[b] & nzj).sum())
            if bundle_conflicts[b] + conflicts <= max_conflicts:
                bundles[b].append(j)
                bundle_nz[b] = bundle_nz[b] | nzj
                bundle_conflicts[b] += conflicts
                bundle_bins[b] += nb_j
                placed = True
                break
        if not placed:
            bundles.append([j])
            bundle_nz.append(nzj.copy())
            bundle_conflicts.append(0)
            bundle_bins.append(1 + int(num_bins[j]))

    bundles_all = bundles
    bundles = [b for b in bundles if len(b) >= min_bundle_size]
    if not bundles:
        return None

    feat_phys = np.zeros(f, np.int32)
    feat_offset = np.zeros(f, np.int32)
    is_bundled = np.zeros(f, bool)
    phys_num_bins: List[int] = []
    in_bundle = {j for b in bundles for j in b}
    p = 0
    for j in range(f):
        if j in in_bundle:
            continue
        feat_phys[j] = p
        phys_num_bins.append(int(num_bins[j]))
        p += 1
    for b in bundles:
        off = 1   # bin 0 = all-default
        for j in b:
            feat_phys[j] = p
            feat_offset[j] = off
            is_bundled[j] = True
            off += int(num_bins[j])
        phys_num_bins.append(off)
        p += 1

    info = BundleInfo(
        feat_phys=feat_phys, feat_offset=feat_offset,
        feat_default=default_bin, is_bundled=is_bundled,
        num_phys=p, phys_num_bins=np.asarray(phys_num_bins, np.int32),
        conflict_rows=int(sum(
            c for b, c in zip(bundles_all, bundle_conflicts)
            if len(b) >= min_bundle_size)))
    log.info("EFB: bundled %d sparse features into %d columns "
             "(%d physical columns total, was %d)",
             int(is_bundled.sum()), len(bundles), p, f)
    return info


def build_physical_matrix(bin_matrix: np.ndarray,
                          info: BundleInfo) -> np.ndarray:
    """Materialise the bundled device layout from the logical bin matrix."""
    n, f = bin_matrix.shape
    dtype = (np.uint16 if int(info.phys_num_bins.max()) > 256
             else bin_matrix.dtype)
    out = np.zeros((n, info.num_phys), dtype=dtype)
    for j in range(f):
        p = int(info.feat_phys[j])
        col = bin_matrix[:, j]
        if not info.is_bundled[j]:
            out[:, p] = col
        else:
            nz = col != info.feat_default[j]
            out[nz, p] = (col[nz].astype(np.int64)
                          + int(info.feat_offset[j])).astype(dtype)
    return out


def physical_from_sparse(store, info: BundleInfo) -> np.ndarray:
    """:func:`build_physical_matrix` from the stored entries of a
    ``dataset_core.SparseBins`` store: a bundle column is zero except
    where a row stores a non-default bin of one of its members, so it
    is written from those entries alone (in feature order: a conflict
    row keeps the later feature, as above).  An unbundled column, and a
    member whose implicit zero is not its default bin, go through one
    dense column each."""
    n = store.shape[0]
    dtype = (np.uint16 if int(info.phys_num_bins.max()) > 256
             else store.dtype)
    out = np.zeros((n, info.num_phys), dtype=dtype)
    by_entry = np.asarray(info.is_bundled
                          & (store.zero_bin == info.feat_default))
    for j in np.flatnonzero(~by_entry):
        lo, hi = store.indptr[j], store.indptr[j + 1]
        col = np.full(n, store.zero_bin[j], dtype)
        col[store.rows[lo:hi]] = store.bins[lo:hi]
        p = int(info.feat_phys[j])
        if not info.is_bundled[j]:
            out[:, p] = col
        else:
            nz = col != info.feat_default[j]
            out[nz, p] = col[nz] + dtype(info.feat_offset[j])
    feat = store.feature_of_entry()
    keep = by_entry[feat] & (store.bins != info.feat_default[feat])
    feat = feat[keep]
    out[store.rows[keep], info.feat_phys[feat]] = (
        store.bins[keep].astype(np.int64)
        + info.feat_offset[feat]).astype(dtype)
    return out
