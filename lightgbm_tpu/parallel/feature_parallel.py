"""Feature-parallel tree learner: the feature axis sharded over the mesh.

Reference: src/treelearner/feature_parallel_tree_learner.cpp — each rank owns
a disjoint feature subset, finds its local best split, and the global best is
elected with SyncUpGlobalBestSplit (parallel_tree_learner.h:191).  The
reference replicates all rows on every rank so no partition communication is
needed; here the bin matrix itself is column-sharded (the "TP" layout of
SURVEY.md §2.10), so the split owner broadcasts its go-left bit-vector over
the feature axis instead — one O(rows) psum per split.

Supports a hybrid mesh: rows over the ``data`` axis AND columns over the
``feature`` axis (tpu_mesh_axes="data:D,feature:F").  Histograms then merge
over ``data`` while the best split is elected over ``feature`` — the
reference has no such combined mode (tree_learner is one of data|feature).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.grow import TreeArrays, make_grow_fn
from ..ops.split import SplitHyperParams
from ..utils import log
from .mesh import DATA_AXIS, FEATURE_AXIS, pad_rows_to_shards


class MeshProbe:
    """Mesh geometry + placement helpers, buildable BEFORE the grow fn —
    the caller needs num_col_shards to size feature padding (and the
    [f_pad]-shaped constraint arrays) ahead of constructing the grower."""

    def __init__(self, mesh: Optional[Mesh]):
        if mesh is None:
            # default: every device on the feature axis
            mesh = Mesh(np.array(jax.devices()), (FEATURE_AXIS,))
        if FEATURE_AXIS not in mesh.shape:
            log.fatal("feature-parallel learner needs a '%s' mesh axis; "
                      "got %s (set tpu_mesh_axes)", FEATURE_AXIS,
                      dict(mesh.shape))
        self.mesh = mesh
        self.num_col_shards = mesh.shape[FEATURE_AXIS]
        self.num_row_shards = mesh.shape.get(DATA_AXIS, 1)
        self.data_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None

    def shard_rows(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Rows shard over 'data' when present, else replicate."""
        if self.data_axis:
            spec = P(self.data_axis, *([None] * (arr.ndim - 1)))
        else:
            spec = P()
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def shard_bins(self, mat: jnp.ndarray) -> jnp.ndarray:
        return jax.device_put(
            mat, NamedSharding(self.mesh, P(self.data_axis, FEATURE_AXIS)))


class FeatureParallelGrower:
    """Grow fn over a feature-sharded (optionally also row-sharded) mesh."""

    @staticmethod
    def probe_mesh(mesh: Optional[Mesh]) -> MeshProbe:
        return MeshProbe(mesh)

    def __init__(
        self,
        hp: SplitHyperParams,
        *,
        num_leaves: int,
        max_depth: int = -1,
        padded_bins: int,
        rows_per_block: int = 8192,
        use_dp: bool = False,
        mesh: Optional[Mesh] = None,
        **grow_kwargs,
    ):
        self._probe = MeshProbe(mesh)
        self.mesh = self._probe.mesh
        self.num_col_shards = self._probe.num_col_shards
        self.num_row_shards = self._probe.num_row_shards
        data_ax = self._probe.data_axis
        # per-tree collective-count bound for the obs ledger (root +
        # one best-split election per split), matching data_parallel's
        # per-dispatch accounting so bytes_moved units agree
        self._num_leaves = int(num_leaves)
        grow = make_grow_fn(
            hp, num_leaves=num_leaves, max_depth=max_depth,
            padded_bins=padded_bins, rows_per_block=rows_per_block,
            use_dp=use_dp, axis_name=data_ax,
            feature_axis_name=FEATURE_AXIS, **grow_kwargs)

        row = P(data_ax) if data_ax else P()
        col = P(FEATURE_AXIS)
        rep = P()
        tree_specs = TreeArrays(*([rep] * len(TreeArrays._fields)))
        self._sharded_grow = jax.jit(jax.shard_map(
            grow, mesh=self.mesh,
            in_specs=(P(data_ax, FEATURE_AXIS), row, row, row,
                      col, col, col, col, rep),
            out_specs=(tree_specs, row),
            check_vma=False,
        ))

    def shard_rows(self, arr: jnp.ndarray) -> jnp.ndarray:
        return self._probe.shard_rows(arr)

    def shard_bins(self, mat: jnp.ndarray) -> jnp.ndarray:
        return self._probe.shard_bins(mat)

    def padded_rows(self, n: int, block: int) -> int:
        return pad_rows_to_shards(n, self.num_row_shards, 1)

    def __call__(self, bins, grad, hess, inbag, feature_mask, num_bins,
                 has_nan, is_cat, seed=0):
        # obs span + collective ledger record (tracing only): the
        # feature-parallel collective is the per-split best-split
        # election — a pmax over the packed SplitInfo vector
        # (sync_best), tiny next to the data-parallel histogram merges
        # but still a cross-shard barrier worth a row in the ledger
        import time as _time

        from ..obs import tracer as obs_tracer
        traced = obs_tracer.enabled
        t0 = _time.perf_counter() if traced else 0.0
        with obs_tracer.span(
                "FeatureParallelGrower::grow",
                col_shards=self.num_col_shards,
                row_shards=self.num_row_shards) as sp:
            out = self._sharded_grow(bins, grad, hess, inbag,
                                     feature_mask, num_bins, has_nan,
                                     is_cat, jnp.int32(seed))
            sp.block_on(out[1])
        if traced:
            import numpy as np

            from ..obs import ledger as obs_ledger
            from ..obs.costmodel import collective_bytes
            shards = self.num_col_shards * max(self.num_row_shards, 1)
            # per-DISPATCH total, same units as data_parallel's record:
            # one ~16-float packed SplitInfo election per split plus
            # the root, bounded by num_leaves merges per tree
            est = collective_bytes("pmax", 16 * 4, shards) \
                * self._num_leaves
            # per-shard series keyed by DEVICE in the mesh's own axis
            # order: each row shard's in-bag sum covers every column
            # shard in its row slice (rows are replicated over the
            # feature axis), expanded repeat- or tile-wise depending
            # on whether the data axis is major or minor in
            # tpu_mesh_axes.  Keeps the per_shard lists the same
            # length as `shards` under the "list index == mesh
            # position" contract mesh_summary / obs collectives use.
            per_shard_rows = None
            try:
                nr = max(self.num_row_shards, 1)
                row_sums = np.asarray(
                    jnp.sum(jnp.reshape(inbag, (nr, -1)), axis=1))
                names = tuple(self.mesh.axis_names)
                data_minor = (DATA_AXIS in names
                              and FEATURE_AXIS in names
                              and names.index(DATA_AXIS)
                              > names.index(FEATURE_AXIS))
                expand = np.tile if data_minor else np.repeat
                per_shard_rows = [float(v) for v in
                                  expand(row_sums,
                                         self.num_col_shards)]
            except Exception:   # odd shapes: skip the series
                pass
            obs_ledger.record_collective(
                "FeatureParallelGrower::pmax",
                bytes_moved=est, shards=shards,
                per_shard_rows=per_shard_rows,
                per_shard_bytes=[est] * shards,
                wall_s=_time.perf_counter() - t0,
                merges_est=self._num_leaves)
        return out
