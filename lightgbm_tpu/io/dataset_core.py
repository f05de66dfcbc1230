"""Binned dataset + metadata (host side).

TPU-native re-design of the reference IO layer (include/LightGBM/dataset.h:425
``Dataset``, dataset.h:45 ``Metadata``, src/io/dataset_loader.cpp
``ConstructBinMappersFromTextData`` / ``ConstructFromSampleData``).

Layout choice: instead of per-feature-group sparse/dense ``Bin`` columns with
an EFB bundling pass (dataset.cpp:102-247), the TPU dataset is a single dense
``[rows, features]`` uint8/uint16 bin matrix — the same layout
``CUDARowData`` materialises on device (cuda_row_data.hpp:31) because the
accelerator histogram kernel wants contiguous per-row feature tuples.
Trivial (single-bin) features are dropped at construction, mirroring
``feature_pre_filter``.  EFB is unnecessary: a bundled column and the dense
matrix cost the same in this layout.

The binary dataset cache (reference ``save_binary`` / LoadFromBinFile,
dataset_loader.cpp:356) is an ``.npz`` with the bin matrix, mappers and
metadata — bins are found once and reloaded.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..config import Config
from ..utils import log
from ..utils.random import sample_indices
from .binning import BinMapper, BinType


@dataclasses.dataclass
class Metadata:
    """Per-row training metadata (reference: dataset.h:45)."""

    label: Optional[np.ndarray] = None          # float32 [n]
    weight: Optional[np.ndarray] = None         # float32 [n]
    init_score: Optional[np.ndarray] = None     # float64 [n * num_class]
    query_boundaries: Optional[np.ndarray] = None  # int32 [num_queries + 1]

    num_data: int = 0

    def set_label(self, label) -> None:
        self.label = np.ascontiguousarray(label, dtype=np.float32).reshape(-1)

    def set_weight(self, weight) -> None:
        if weight is None:
            self.weight = None
            return
        w = np.ascontiguousarray(weight, dtype=np.float32).reshape(-1)
        self.weight = w

    def set_init_score(self, init_score) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.ascontiguousarray(init_score, dtype=np.float64).reshape(-1)

    def set_group(self, group) -> None:
        """Accepts per-query sizes (like the reference's query file) and
        stores cumulative boundaries (dataset.h:222)."""
        if group is None:
            self.query_boundaries = None
            return
        g = np.ascontiguousarray(group, dtype=np.int64).reshape(-1)
        if len(g) and g[-1] == self.num_data and np.all(np.diff(g) >= 0) and g[0] != self.num_data:
            # already boundaries
            bounds = np.concatenate([[0], g]) if g[0] != 0 else g
        else:
            bounds = np.concatenate([[0], np.cumsum(g)])
        if self.num_data and bounds[-1] != self.num_data:
            log.fatal("Sum of query counts (%d) != num_data (%d)", bounds[-1], self.num_data)
        self.query_boundaries = bounds.astype(np.int32)

    def check(self, num_data: int) -> None:
        self.num_data = num_data
        if self.label is not None and len(self.label) != num_data:
            log.fatal("Length of label (%d) != num_data (%d)", len(self.label), num_data)
        if self.weight is not None and len(self.weight) != num_data:
            log.fatal("Length of weight (%d) != num_data (%d)", len(self.weight), num_data)


class BinnedDataset:
    """The quantized training matrix + per-feature mappers.

    ``bin_matrix`` is ``[num_data, num_used_features]`` uint8 (uint16 when any
    feature has > 256 bins).  ``mappers[j]`` quantizes original feature
    ``used_feature_map[j]``.
    """

    def __init__(self) -> None:
        # the dense logical bin matrix; for scipy.sparse input it is
        # made from ``sparse_bins`` only when something reads it (the
        # ``bin_matrix`` property)
        self._bin_matrix: Optional[np.ndarray] = None
        self.sparse_bins: Optional[SparseBins] = None
        self.mappers: List[BinMapper] = []
        self.used_feature_map: np.ndarray = np.array([], dtype=np.int32)
        self.num_total_features: int = 0
        self.feature_names: List[str] = []
        self.metadata = Metadata()
        # raw numerical values of used features, retained only when
        # linear_tree=true (reference Dataset::raw_data_, dataset.h:948)
        self.raw_matrix: Optional[np.ndarray] = None
        # EFB plan (io/bundle.py BundleInfo) or None; the device layout
        # stacks bundled sparse features into shared physical columns
        self.bundle_info = None

    # ------------------------------------------------------------------
    @property
    def bin_matrix(self) -> Optional[np.ndarray]:
        if self._bin_matrix is None and self.sparse_bins is not None:
            log.info("densifying the sparse bin store: %d x %d bins",
                     *self.sparse_bins.shape)
            self._bin_matrix = self.sparse_bins.to_dense()
        return self._bin_matrix

    @bin_matrix.setter
    def bin_matrix(self, mat: Optional[np.ndarray]) -> None:
        self._bin_matrix = mat
        self.sparse_bins = None

    def _bins_shape(self):
        if self._bin_matrix is not None:
            return self._bin_matrix.shape
        return (0, 0) if self.sparse_bins is None else self.sparse_bins.shape

    @property
    def num_data(self) -> int:
        return self._bins_shape()[0]

    @property
    def num_features(self) -> int:
        return self._bins_shape()[1]

    def physical_matrix(self, info) -> np.ndarray:
        """The bundled device layout of ``info`` (io/bundle.py): from
        the stored entries of sparse input, 8 a row at Expo's shape,
        without the ``[n, f]`` logical matrix between."""
        from .bundle import build_physical_matrix, physical_from_sparse
        if self._bin_matrix is None and self.sparse_bins is not None:
            return physical_from_sparse(self.sparse_bins, info)
        return build_physical_matrix(self.bin_matrix, info)

    @property
    def num_bins_per_feature(self) -> np.ndarray:
        return np.array([m.num_bins for m in self.mappers], dtype=np.int32)

    # ------------------------------------------------------------------
    @classmethod
    def construct(
        cls,
        data: np.ndarray,
        config: Config,
        *,
        label=None,
        weight=None,
        group=None,
        init_score=None,
        feature_names: Optional[Sequence[str]] = None,
        categorical_indices: Optional[Sequence[int]] = None,
        reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Build from a raw feature matrix.

        With ``reference`` given, reuse its bin mappers (validation sets must
        be binned identically to the train set — reference basic.py:1194
        ``reference=`` semantics / dataset.h ``CreateValid``).

        scipy.sparse CSR/CSC input is binned without densifying the float
        matrix (the reference's SparseBin path, src/io/sparse_bin.hpp):
        zeros take the zero bin in one vector fill, only stored entries are
        quantized individually.  The output bin matrix is dense regardless —
        the TPU histogram kernel wants the CUDARowData row-tuple layout.
        """
        sp = _is_scipy_sparse(data)
        if sp:
            n, num_total = data.shape
        else:
            data = _as_2d_float(data)
            n, num_total = data.shape
        self = cls()
        self.num_total_features = num_total
        self.feature_names = (
            list(feature_names) if feature_names is not None
            else [f"Column_{i}" for i in range(num_total)]
        )
        if len(self.feature_names) != num_total:
            log.fatal("feature_names length mismatch")

        if reference is not None:
            if num_total != reference.num_total_features:
                log.fatal(
                    "The number of features in data (%d) does not match the "
                    "reference dataset (%d)", num_total,
                    reference.num_total_features)
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.num_total_features = reference.num_total_features
            self.feature_names = reference.feature_names
        else:
            # sampling for bin finding (reference bin_construct_sample_cnt,
            # dataset_loader.cpp:203 sampling pass)
            sample_cnt = min(config.bin_construct_sample_cnt, n)
            sidx = sample_indices(n, sample_cnt, config.data_random_seed)
            if sp:
                # row-sample in CSR, then CSC for cheap per-column access
                sample_csc = data.tocsr()[sidx].tocsc()
                sample = _SparseColumnView(sample_csc)
            else:
                sample = data[sidx]
            self._find_mappers(sample, num_total, sample_cnt, config,
                               categorical_indices)

        # quantize — native OpenMP loop (src/native/tgb_native.cpp
        # TGB_ApplyBins) when built, vectorized numpy otherwise
        dtype = (np.uint16 if any(m.num_bins > 256 for m in self.mappers)
                 else np.uint8)
        if sp:
            # sparse: only stored entries are quantized; every other
            # cell is its column's zero bin (sparse_bin.hpp delta-page
            # analog).  The entries stay as they are - the bundled
            # device layout is built from them directly, and the dense
            # logical matrix only if something asks for it
            csc = data.tocsc()
            self.sparse_bins = SparseBins.from_csc(
                csc, self.used_feature_map, self.mappers, dtype)
        else:
            mat = None
            if self.mappers:
                from .. import native
                if native.available():
                    applier = native.BinApplier(
                        self.mappers, self.used_feature_map, dtype)
                    mat = applier.apply(data)
            if mat is None:
                mat = np.empty((n, len(self.mappers)), dtype=dtype)
                for j, (orig, m) in enumerate(
                        zip(self.used_feature_map, self.mappers)):
                    mat[:, j] = m.values_to_bins(
                        data[:, orig]).astype(dtype)
            self.bin_matrix = mat
        if config.linear_tree and self.mappers:
            if sp:
                view = _SparseColumnView(csc)   # csc from the quantize pass
                self.raw_matrix = np.stack(
                    [view[:, int(orig)] for orig in self.used_feature_map],
                    axis=1).astype(np.float32)
            else:
                self.raw_matrix = np.ascontiguousarray(
                    data[:, self.used_feature_map], dtype=np.float32)

        self.metadata.num_data = n
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_init_score(init_score)
        self.metadata.set_group(group)
        self.metadata.check(n)
        self._maybe_bundle(config, reference)
        return self

    # ------------------------------------------------------------------
    def _maybe_bundle(self, config: Config, reference) -> None:
        """EFB plan (dataset.cpp:102 FindGroups); validation sets inherit
        the training set's plan so their device layout matches."""
        if reference is not None:
            self.bundle_info = getattr(reference, "bundle_info", None)
            return
        if not config.enable_bundle or len(self.mappers) < 2:
            return
        from ..obs import tracer
        from .bundle import find_bundles
        with tracer.span("Dataset::bundle") as sp:
            # a sparse store hands find_bundles its sampled rows dense
            self.bundle_info = find_bundles(
                self.sparse_bins if self._bin_matrix is None
                else self._bin_matrix, self.num_bins_per_feature,
                np.array([m.has_nan_bin for m in self.mappers], bool),
                np.array([m.bin_type == BinType.CATEGORICAL
                          for m in self.mappers], bool))
            info = self.bundle_info
            sp.set(features_bundled=0 if info is None
                   else int(info.is_bundled.sum()),
                   bundles=0 if info is None
                   else int(info.num_phys - (~info.is_bundled).sum()),
                   conflict_rows=0 if info is None
                   else int(info.conflict_rows))

    # ------------------------------------------------------------------
    def _find_mappers(self, sample, num_total: int, sample_cnt: int,
                      config: Config, categorical_indices) -> None:
        """Per-feature bin finding over sampled rows (the
        ConstructBinMappersFromTextData core, dataset_loader.cpp:1012).

        With ``pre_partition=true`` in a multi-process run, each process
        holds a DISJOINT row partition: bin-finding is partitioned across
        processes by feature and the serialized mappers are allgathered
        so every process bins with IDENTICAL boundaries (the reference's
        distributed binning, dataset_loader.cpp:1152-1178).  NOTE: this
        synchronizes the BINNING layer only; assembling the per-process
        row partitions into the global device array for the data-parallel
        learner is not wired up yet (today's multi-process flow feeds the
        full dataset to every process, reference pre_partition=false
        semantics)."""
        cat_set = set(categorical_indices or [])
        max_bin_by_feature = config.max_bin_by_feature

        def find_one(j: int) -> BinMapper:
            mb = (max_bin_by_feature[j]
                  if j < len(max_bin_by_feature) else config.max_bin)
            return BinMapper.find_bin(
                sample[:, j],
                total_sample_cnt=sample_cnt,
                max_bin=mb,
                min_data_in_bin=config.min_data_in_bin,
                bin_type=(BinType.CATEGORICAL if j in cat_set
                          else BinType.NUMERICAL),
                use_missing=config.use_missing,
                zero_as_missing=config.zero_as_missing,
            )

        nproc = 1
        if config.pre_partition:
            # no exception guard: a failure here in a multi-process run
            # must not silently fall back to divergent local-only binning
            import jax
            nproc = jax.process_count()
        if nproc > 1:
            all_mappers = _sync_distributed_mappers(find_one, num_total)
        else:
            all_mappers = [find_one(j) for j in range(num_total)]

        mappers: List[BinMapper] = []
        used: List[int] = []
        for j, m in enumerate(all_mappers):
            if m.is_trivial and config.feature_pre_filter:
                continue  # single-bin feature can never split
            mappers.append(m)
            used.append(j)
        self.mappers = mappers
        self.used_feature_map = np.array(used, dtype=np.int32)
        if not used:
            log.warning("There are no meaningful features which satisfy "
                        "the provided configuration.")

    # ------------------------------------------------------------------
    @classmethod
    def construct_from_sequences(
        cls,
        seqs: List,
        config: Config,
        *,
        label=None,
        weight=None,
        group=None,
        init_score=None,
        feature_names: Optional[Sequence[str]] = None,
        categorical_indices: Optional[Sequence[int]] = None,
        reference: Optional["BinnedDataset"] = None,
    ) -> "BinnedDataset":
        """Two-pass streaming construction from row-access Sequences.

        Reference: basic.py Sequence support (`_init_from_seqs`) over the
        C-API streaming push (`LGBM_DatasetPushRows*`, c_api.h:175-278) —
        pass 1 random-samples rows for bin finding, pass 2 streams batches
        through the quantizer into a preallocated bin slab, so the full
        float matrix never exists in memory.
        """
        lens = [len(s) for s in seqs]
        n = int(sum(lens))
        if n == 0:
            log.fatal("Sequences contain no rows")
        first_seq = next(s for s, m in zip(seqs, lens) if m > 0)
        first = np.atleast_2d(np.asarray(first_seq[0:1], dtype=np.float64))
        num_total = first.shape[1]
        self = cls()
        self.num_total_features = num_total
        self.feature_names = (
            list(feature_names) if feature_names is not None
            else [f"Column_{i}" for i in range(num_total)])

        offsets = np.concatenate([[0], np.cumsum(lens)])
        if reference is not None:
            self.mappers = reference.mappers
            self.used_feature_map = reference.used_feature_map
            self.num_total_features = reference.num_total_features
            self.feature_names = reference.feature_names
        else:
            sample_cnt = min(config.bin_construct_sample_cnt, n)
            sidx = np.sort(sample_indices(n, sample_cnt,
                                          config.data_random_seed))
            sample = np.empty((sample_cnt, num_total), dtype=np.float64)
            for i, gi in enumerate(sidx):
                s = int(np.searchsorted(offsets, gi, side="right")) - 1
                sample[i] = np.asarray(seqs[s][int(gi - offsets[s])],
                                       dtype=np.float64)
            self._find_mappers(sample, num_total, sample_cnt, config,
                               categorical_indices)

        dtype = (np.uint16 if any(m.num_bins > 256 for m in self.mappers)
                 else np.uint8)
        mat = np.empty((n, len(self.mappers)), dtype=dtype)
        applier = None
        if self.mappers:
            from .. import native
            if native.available():
                applier = native.BinApplier(
                    self.mappers, self.used_feature_map, dtype)
        raw = (np.empty((n, len(self.mappers)), np.float32)
               if config.linear_tree and self.mappers else None)
        row0 = 0
        for s in seqs:
            bs = int(getattr(s, "batch_size", 0) or 4096)
            for start in range(0, len(s), bs):
                chunk = np.atleast_2d(np.asarray(
                    s[start:start + bs], dtype=np.float64))
                done = False
                if applier is not None:
                    done = applier.apply_rows(chunk, mat, row0)
                if not done:
                    for j, (orig, m) in enumerate(
                            zip(self.used_feature_map, self.mappers)):
                        mat[row0:row0 + len(chunk), j] = (
                            m.values_to_bins(chunk[:, orig]).astype(dtype))
                if raw is not None:
                    raw[row0:row0 + len(chunk)] = chunk[:, self.used_feature_map]
                row0 += len(chunk)
        assert row0 == n, (row0, n)
        self.bin_matrix = mat
        self.raw_matrix = raw

        self.metadata.num_data = n
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_init_score(init_score)
        self.metadata.set_group(group)
        self.metadata.check(n)
        self._maybe_bundle(config, reference)
        return self

    # ------------------------------------------------------------------
    def subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row subset sharing mappers (reference Dataset::CopySubrow)."""
        out = BinnedDataset()
        out.mappers = self.mappers
        out.used_feature_map = self.used_feature_map
        out.num_total_features = self.num_total_features
        out.feature_names = self.feature_names
        out.bin_matrix = self.bin_matrix[indices]
        out.bundle_info = self.bundle_info
        if self.raw_matrix is not None:
            out.raw_matrix = self.raw_matrix[indices]
        md = self.metadata
        out.metadata.num_data = len(indices)
        if md.label is not None:
            out.metadata.label = md.label[indices]
        if md.weight is not None:
            out.metadata.weight = md.weight[indices]
        if md.init_score is not None:
            k = len(md.init_score) // md.num_data
            out.metadata.init_score = (
                md.init_score.reshape(k, md.num_data)[:, indices].reshape(-1))
        if md.query_boundaries is not None:
            log.warning("Row subset of a ranked dataset drops query info")
        return out

    # ------------------------------------------------------------------
    # Binary cache (reference: save_binary / LoadFromBinFile)
    def save_binary(self, path: str) -> None:
        meta: Dict[str, Any] = {
            "num_total_features": self.num_total_features,
            "feature_names": self.feature_names,
            "mappers": [m.to_dict() for m in self.mappers],
        }
        arrays: Dict[str, np.ndarray] = {
            "bin_matrix": self.bin_matrix,
            "used_feature_map": self.used_feature_map,
            "meta_json": np.frombuffer(
                json.dumps(meta).encode("utf-8"), dtype=np.uint8),
        }
        if self.raw_matrix is not None:
            arrays["raw_matrix"] = self.raw_matrix
        md = self.metadata
        for name in ("label", "weight", "init_score", "query_boundaries"):
            v = getattr(md, name)
            if v is not None:
                arrays[name] = v
        # np.savez appends .npz; keep the user's exact path like the
        # reference's `data.bin` files
        tmp = path + ".npz" if not path.endswith(".npz") else path
        np.savez_compressed(tmp, **arrays)
        if tmp != path:
            import os
            os.replace(tmp, path)
        log.info("Saved binary dataset to %s", path)

    @classmethod
    def load_binary(cls, path: str) -> "BinnedDataset":
        with open(path, "rb") as fh:
            z = np.load(fh, allow_pickle=False)
            z = dict(z)
        self = cls()
        meta = json.loads(bytes(z["meta_json"]).decode("utf-8"))
        self.num_total_features = meta["num_total_features"]
        self.feature_names = meta["feature_names"]
        self.mappers = [BinMapper.from_dict(d) for d in meta["mappers"]]
        self.bin_matrix = z["bin_matrix"]
        self.used_feature_map = z["used_feature_map"]
        if "raw_matrix" in z:
            self.raw_matrix = z["raw_matrix"]
        md = self.metadata
        md.num_data = self.bin_matrix.shape[0]
        for name in ("label", "weight", "init_score", "query_boundaries"):
            if name in z:
                setattr(md, name, z[name])
        return self


def _sync_distributed_mappers(find_one, num_total: int) -> list:
    """Distributed bin-mapper construction (dataset_loader.cpp:1152-1178):
    features are partitioned round-robin across processes, each process
    finds bins for its owned features from ITS data partition, and the
    serialized mappers are allgathered so every process ends up with the
    identical full mapper list.  Two allgather rounds (byte lengths, then
    padded pickled payloads) through jax.experimental.multihost_utils —
    a tiny host payload, exactly the reference's Allgather of serialized
    BinMappers."""
    import pickle

    import jax
    from jax.experimental import multihost_utils as mhu

    rank = jax.process_index()
    nproc = jax.process_count()
    owned = {j: find_one(j).to_dict()
             for j in range(num_total) if j % nproc == rank}
    blob = np.frombuffer(pickle.dumps(owned), dtype=np.uint8)
    lens = np.asarray(mhu.process_allgather(
        np.asarray([blob.size], np.int32))).reshape(nproc)
    buf = np.zeros(int(lens.max()), np.uint8)
    buf[:blob.size] = blob
    bufs = np.asarray(mhu.process_allgather(buf)).reshape(nproc, -1)
    merged: Dict[int, BinMapper] = {}
    for r in range(nproc):
        part = pickle.loads(bytes(bufs[r][:int(lens[r])]))
        for j, d in part.items():
            merged[j] = BinMapper.from_dict(d)
    missing = [j for j in range(num_total) if j not in merged]
    if missing:
        raise RuntimeError(
            f"distributed bin sync lost features {missing[:5]}...")
    return [merged[j] for j in range(num_total)]


def _is_scipy_sparse(data) -> bool:
    return (hasattr(data, "tocsc") and hasattr(data, "tocsr")
            and not isinstance(data, np.ndarray))


class SparseBins:
    """The binned form of scipy.sparse input: the stored entries of
    every used feature, quantized, in CSC order (``indptr`` [f + 1],
    ``rows`` i32 [nnz], ``bins`` [nnz]), and each feature's ``zero_bin``
    for every cell that stores nothing.  Reads like a matrix where a
    reader wants a few rows (``store[row_indices]``, dense)."""

    def __init__(self, n, indptr, rows, bins, zero_bin):
        self.n, self.indptr, self.rows, self.bins = n, indptr, rows, bins
        self.zero_bin = zero_bin

    @classmethod
    def from_csc(cls, csc, used_feature_map, mappers, dtype):
        zero_bin = np.array(
            [m.values_to_bins(np.zeros(1))[0] for m in mappers], dtype)
        counts = np.array([csc.indptr[o + 1] - csc.indptr[o]
                           for o in used_feature_map], np.int64)
        indptr = np.concatenate([[0], np.cumsum(counts)])
        rows = np.empty(int(indptr[-1]), np.int32)
        bins = np.empty(int(indptr[-1]), dtype)
        for j, (orig, m) in enumerate(zip(used_feature_map, mappers)):
            lo, hi = csc.indptr[orig], csc.indptr[orig + 1]
            if hi > lo:
                rows[indptr[j]:indptr[j + 1]] = csc.indices[lo:hi]
                bins[indptr[j]:indptr[j + 1]] = m.values_to_bins(
                    np.asarray(csc.data[lo:hi], np.float64))
        return cls(int(csc.shape[0]), indptr, rows, bins, zero_bin)

    @property
    def shape(self):
        return self.n, len(self.zero_bin)

    @property
    def dtype(self):
        return self.bins.dtype

    def feature_of_entry(self) -> np.ndarray:
        return np.repeat(np.arange(len(self.zero_bin), dtype=np.int32),
                         np.diff(self.indptr))

    def _zero_rows(self, n_rows: int) -> np.ndarray:
        mat = np.empty((n_rows, len(self.zero_bin)), self.dtype)
        mat[:] = self.zero_bin[None, :]
        return mat

    def to_dense(self) -> np.ndarray:
        mat = self._zero_rows(self.n)
        mat[self.rows, self.feature_of_entry()] = self.bins
        return mat

    def __getitem__(self, row_idx) -> np.ndarray:
        row_idx = np.asarray(row_idx)
        pos = np.full(self.n, -1, np.int64)
        pos[row_idx] = np.arange(len(row_idx))
        at = pos[self.rows]
        keep = at >= 0
        mat = self._zero_rows(len(row_idx))
        mat[at[keep], self.feature_of_entry()[keep]] = self.bins[keep]
        return mat


class _SparseColumnView:
    """``view[:, j]`` -> dense float64 column of a CSC matrix (bin-finding
    samples only touch one column at a time, so the full matrix is never
    densified)."""

    def __init__(self, csc):
        self._csc = csc

    def __getitem__(self, key):
        _, j = key
        col = np.zeros(self._csc.shape[0], dtype=np.float64)
        lo, hi = self._csc.indptr[j], self._csc.indptr[j + 1]
        col[self._csc.indices[lo:hi]] = self._csc.data[lo:hi]
        return col


def _as_2d_float(data) -> np.ndarray:
    if hasattr(data, "toarray") and not isinstance(data, np.ndarray):
        data = data.toarray()  # scipy sparse
    arr = np.asarray(data)
    if hasattr(arr, "dtype") and arr.dtype == object:
        arr = arr.astype(np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    if arr.ndim != 2:
        log.fatal("Data must be 2-dimensional, got %d dims", arr.ndim)
    return np.ascontiguousarray(arr, dtype=np.float64)
