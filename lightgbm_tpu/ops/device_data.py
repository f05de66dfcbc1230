"""Device-resident dataset (HBM bin matrix + feature metadata).

Reference analog: CUDARowData / CUDAColumnData
(include/LightGBM/cuda/cuda_row_data.hpp:31, cuda_column_data.hpp:140) which
copy the binned features to device in a packed layout sized to shared memory.
Here the layout is one dense ``[rows, features]`` uint8/int16 matrix padded so
the histogram kernel's feature groups tile exactly onto the MXU
(``DivideCUDAFeatureGroups`` analog: bins padded to a uniform power-of-16
width, features padded to a multiple of the matmul group size).

Downstream, physical-partition mode widens these bins into the comb row
matrix whose LINE layout (one logical row a line, in 128-lane planes) is
governed by ``ops/pallas/layout.py comb_layout`` — the contract every
partition/histogram/stream kernel builder validates at trace time (the
round-3 64-lane regression class, BENCH_r03.json).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..io.binning import BinType, MissingType
from ..io.dataset_core import BinnedDataset
from .histogram import bins_per_feature_padded, feature_group_size


def pad_features_to_shards(f: int, group: int, n_shards: int) -> int:
    """Feature-axis padding that keeps BOTH contracts: whole histogram
    matmul groups (``f % group == 0``) AND the data-parallel
    reduce-scatter merge precondition (``f % n_shards == 0``,
    ``grow.hist_scatter_eligible`` / ``_warn_hist_scatter_fallback``)
    — i.e. the smallest multiple of lcm(group, n_shards) >= f.

    This is the ROADMAP-item-3 fix for ``hist_scatter_psum_fallback``:
    the old layout multiplied the group size by the shard count
    (``group * n_shards`` columns of padding granularity), which both
    over-padded (f=28, group=8, 8 shards -> 64 columns instead of 32)
    and was skipped entirely by direct ``to_device`` callers, leaving
    their mesh runs on the silent full-psum path.  The static analyzer
    registers this function's outputs as mesh configs
    (``analysis/entries.py``) so a regression here is a lint finding,
    not a run-time warning."""
    import math
    if n_shards <= 1:
        m = max(int(group), 1)
    else:
        g = max(int(group), 1)
        m = g * n_shards // math.gcd(g, n_shards)
    return int(np.ceil(max(int(f), 1) / m) * m)


def unbundle_bins(bins: jnp.ndarray, bundle) -> jnp.ndarray:
    """Expand a bundled physical bin block back into one uint8 column
    PER LOGICAL FEATURE, on device: the comb ingest of the bundled
    tables whose grow configuration the bundle-space finder does not
    cover (``grow.bundled_comb_eligible``; the plain configurations
    keep one comb column a bundle since ISSUE 36).

    ``bins`` is the bundled ``[n, F_phys_pad]`` device matrix
    (uint8/uint16 — a stacked bundle column may exceed 255 bins even
    when every logical feature is uint8); ``bundle`` is the
    ``DeviceDataset.bundle`` mapping dict.  Per logical feature j the
    bundle column value v decodes as ``v - offset_j`` when v lies in
    j's stacked range ``[offset_j, offset_j + num_bins_j)`` and as j's
    default (most frequent) bin otherwise — the same semantics the
    row_order path's histogram expansion (``grow.expand`` +
    FixHistogram) and the bundled comb's finder
    (``split.find_best_split_segments``) apply at histogram level,
    applied at ROW level once, at ingest.  With zero bundling conflicts
    (the default ``max_conflict_rate=0.0``) the result is bit-identical
    to the never-bundled logical bin matrix.

    Unbundled features ride the same formula (offset 0, always in
    range); padded logical features (num_bins 0) decode to bin 0.  The
    output is uint8: callers gate on uint8 LOGICAL bins
    (``padded_bins_log <= 256``) before ingesting."""
    phys = jnp.asarray(bundle["feat_phys"], jnp.int32)
    off = jnp.asarray(bundle["feat_offset"], jnp.int32)
    dflt = jnp.asarray(bundle["feat_default"], jnp.int32)
    nb = jnp.asarray(bundle["num_bins_log"], jnp.int32)
    v = jnp.take(bins, phys, axis=1).astype(jnp.int32)  # [n, f_log_pad]
    in_range = (v >= off[None, :]) & (v < (off + nb)[None, :])
    return jnp.where(in_range, v - off[None, :],
                     dflt[None, :]).astype(jnp.uint8)


@dataclasses.dataclass
class DeviceDataset:
    bins: jnp.ndarray          # [n_pad, F_phys_pad] uint8/uint16 PHYSICAL
    num_bins: jnp.ndarray      # [F_log_pad] i32 LOGICAL (0 for padding)
    has_nan: jnp.ndarray       # [F_log_pad] bool
    is_cat: jnp.ndarray        # [F_log_pad] bool
    padded_bins: int           # PHYSICAL per-column bin width (bundles)
    padded_bins_log: int       # LOGICAL per-feature bin width (<= physical)
    num_features: int          # real (unpadded) logical feature count
    num_data: int              # real (unpadded) row count
    # EFB mapping (None when no bundling): logical feature -> physical
    # column / bin offset / default bin (io/bundle.py BundleInfo, padded)
    bundle: "object" = None    # dict(feat_phys, feat_offset, feat_default,
                               #      is_bundled, num_bins_log, has_nan,
                               #      is_cat) np arrays

    @property
    def f_pad(self) -> int:
        """Physical (histogram) column count."""
        return self.bins.shape[1]

    @property
    def f_log(self) -> int:
        """Logical feature count (split-search / feature-mask space)."""
        return int(self.num_bins.shape[0])

    @property
    def n_pad(self) -> int:
        return self.bins.shape[0]

    # -- physical-path geometry under EFB -----------------------------
    # Two comb forms (ISSUE 36).  ``comb_bundled``: one comb column a
    # BUNDLE, the kernels at the bundled geometry, the finder in bundle
    # space (``split.find_best_split_segments``) - what gbdt sets when
    # ``grow.bundled_comb_eligible`` holds.  Otherwise the comb ingests
    # the UNBUNDLED layout (``unbundle_bins``, ISSUE 12), one column a
    # logical feature.  These are the numbers the routing model
    # (gbdt._route_inputs -> routing.resolve_layout), the grow build,
    # the page planner and the costmodel footprint all price — sharing
    # them here keeps them from disagreeing about what is allocated.
    comb_bundled: bool = False

    @property
    def phys_f_pad(self) -> int:
        """Comb feature columns of the physical path: the bundle
        columns under the bundled comb, the unbundled logical width
        under the unbundling ingest, the plain padded width without
        EFB."""
        if self.bundle is None or self.comb_bundled:
            return self.f_pad
        return self.f_log

    @property
    def phys_padded_bins(self) -> int:
        """Per-column bin width the physical path's kernels see."""
        return self.padded_bins if self.comb_bundled else \
            self.padded_bins_log

    @property
    def phys_bins_u8(self) -> bool:
        """Whether the physical path's ingested columns are uint8: the
        stored columns themselves, except under the unbundling ingest,
        where the LOGICAL bin width decides (a stacked bundle column
        may be u16 while every logical feature fits u8)."""
        if self.bundle is None or self.comb_bundled:
            return bool(self.bins.dtype == jnp.uint8)
        return self.padded_bins_log <= 256


def to_device(ds: BinnedDataset, row_pad_multiple: int = 1,
              col_pad_multiple: int = 1, put_fn=None,
              use_bundles: bool = True,
              col_shard_multiple: int = 1) -> DeviceDataset:
    """``put_fn`` (optional) places the padded host matrix on devices — the
    data-parallel learner passes a sharded device_put.  ``col_pad_multiple``
    MULTIPLIES the matmul group size so each shard of a feature-sharded
    mesh keeps whole histogram matmul groups (the feature-parallel learner
    passes the shard count; analog of the reference's per-rank feature
    load balancing, feature_parallel_tree_learner.cpp:38-57).
    ``col_shard_multiple`` instead pads the feature axis to the smallest
    multiple of lcm(group, n_shards) — the data-parallel reduce-scatter
    merge only needs ``f_log % n_shards == 0``, and the lcm padding keeps
    that WITHOUT the group x shards over-padding
    (``pad_features_to_shards``).
    ``use_bundles=False`` disables the EFB physical layout (the
    feature-parallel learner shards physical columns and needs the
    identity mapping)."""
    n, f = ds.num_data, ds.num_features
    nbins = ds.num_bins_per_feature
    info = getattr(ds, "bundle_info", None) if use_bundles else None
    if info is not None and not info.any_bundled:
        info = None
    max_bins_log = int(nbins.max()) if f else 16
    if info is not None:
        # (from the stored entries of sparse input: the logical
        # [n, f] matrix is not made)
        phys = ds.physical_matrix(info)
        max_bins = max(max_bins_log, int(info.phys_num_bins.max()))
    else:
        phys = ds.bin_matrix
        max_bins = max_bins_log
    b = bins_per_feature_padded(max_bins)
    b_log = (bins_per_feature_padded(max_bins_log) if info is not None
             else b)
    g = feature_group_size(b) * max(int(col_pad_multiple), 1)
    if info is not None:
        # EFB graduation (ISSUE 12): the physical fast path ingests
        # the UNBUNDLED [n, f_log_pad] u8 matrix (unbundle_bins) and
        # histograms it at the LOGICAL bin width, whose matmul group
        # size can differ from the bundled layout's — pad the logical
        # feature axis so BOTH group sizes divide it (lcm), keeping
        # the row_order expansion AND the unbundled comb-direct
        # histogram on whole groups.
        import math
        g_log = feature_group_size(b_log) * max(int(col_pad_multiple), 1)
        g_l = g * g_log // math.gcd(g, g_log)
    else:
        g_l = g
    fp = phys.shape[1]
    if int(col_shard_multiple) > 1:
        f_phys_pad = pad_features_to_shards(fp, g, col_shard_multiple)
        f_log_pad = pad_features_to_shards(f, g_l, col_shard_multiple)
    else:
        f_phys_pad = int(np.ceil(max(fp, 1) / g) * g)
        f_log_pad = int(np.ceil(max(f, 1) / g_l) * g_l)

    if f_phys_pad != fp:
        phys = np.pad(phys, ((0, 0), (0, f_phys_pad - fp)))
    if row_pad_multiple > 1 and n % row_pad_multiple:
        n_pad = -(-n // row_pad_multiple) * row_pad_multiple
        phys = np.pad(phys, ((0, n_pad - n), (0, 0)))
    num_bins = np.zeros(f_log_pad, dtype=np.int32)
    num_bins[:f] = nbins
    has_nan = np.zeros(f_log_pad, dtype=bool)
    is_cat = np.zeros(f_log_pad, dtype=bool)
    for j, m in enumerate(ds.mappers):
        has_nan[j] = m.has_nan_bin
        is_cat[j] = m.bin_type == BinType.CATEGORICAL

    bundle = None
    if info is not None:
        bundle = {
            "feat_phys": np.pad(info.feat_phys, (0, f_log_pad - f)),
            "feat_offset": np.pad(info.feat_offset, (0, f_log_pad - f)),
            "feat_default": np.pad(info.feat_default, (0, f_log_pad - f)),
            "is_bundled": np.pad(info.is_bundled, (0, f_log_pad - f)),
            "num_bins_log": num_bins.copy(),
            # per logical feature, for the bundle-space finder's
            # static position maps (split.segment_maps)
            "has_nan": has_nan.copy(),
            "is_cat": is_cat.copy(),
        }

    put = put_fn if put_fn is not None else jnp.asarray
    return DeviceDataset(
        bins=put(phys),
        num_bins=jnp.asarray(num_bins),
        has_nan=jnp.asarray(has_nan),
        is_cat=jnp.asarray(is_cat),
        padded_bins=b,
        padded_bins_log=b_log,
        num_features=f,
        num_data=n,
        bundle=bundle,
    )
