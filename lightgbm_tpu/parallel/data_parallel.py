"""Data-parallel tree learner: rows sharded over the mesh.

Reference: src/treelearner/data_parallel_tree_learner.cpp — the primary
distributed strategy (BASELINE: tree_learner=data on v5e-16).  The
reference's four per-split communication points map to:

  root grad/hess Allreduce (cpp:126-152)      -> lax.psum of 3 scalars
  histogram Network::ReduceScatter (cpp:185)  -> lax.psum_scatter over the
                                                 feature axis: each shard
                                                 owns 1/n of the merged
                                                 histogram (half the ICI
                                                 traffic of a psum; falls
                                                 back to psum for EFB /
                                                 voting / forced splits /
                                                 cat-subset configs)
  SyncUpGlobalBestSplit (cpp:260)             -> pmax election over owned-
                                                 chunk best splits (shared
                                                 with the feature learner)
  global leaf counts (cpp:270)                -> free: counts come from the
                                                 reduce-scattered histogram

Raw rows never cross devices — only O(F x B) histogram summaries ride the
ICI, exactly the reference's "shard the big axis, exchange small summaries"
structure (SURVEY.md section 5 long-context note).

The whole per-tree grow loop runs inside ONE shard_map-ped jit: per-device
row partitions update locally, tree arrays come out replicated.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.grow import (MeshPhysicalPieces, TreeArrays, make_grow_fn,
                        phys_init_comb)
from ..obs.tracer import phase
from ..ops.split import SplitHyperParams
from ..utils import log
from .mesh import DATA_AXIS, build_mesh, pad_rows_to_shards


class DataParallelGrower:
    """Drop-in replacement for the serial grow fn over a row-sharded mesh.

    With ``physical_bins`` set, each shard keeps its rows PHYSICALLY
    permuted in a per-shard [n_alloc, C] comb matrix and runs the same
    streaming partition + comb-direct histogram kernels as the serial
    learner — the reference property that the parallel learners wrap the
    SAME device kernels (data_parallel_tree_learner.cpp:279-281
    templating over the serial learner).  The comb/scratch matrices ride
    across trees as row-sharded global arrays donated to each call."""

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
        physical_bins=None,     # global row-sharded [n_pad, f_pad] u8
        **grow_kwargs,
    ):
        self.mesh = mesh if mesh is not None else build_mesh()
        self.num_shards = self.mesh.shape[DATA_AXIS]
        # layout constants the obs collective ledger prices traffic
        # with (obs/costmodel.collective_bytes); num_leaves bounds the
        # per-tree collective count (root + one merge per split)
        self._num_leaves = int(num_leaves)
        self._padded_bins = int(padded_bins)
        # PV-tree voting bounds the merge payload to ~2k elected
        # features; the ledger's analytical ICI pricing follows suit
        self._voting_k = int(grow_kwargs.get("voting_top_k", 0) or 0)
        import os
        from ..ops.grow import hist_scatter_eligible
        forced = grow_kwargs.get("forced")
        self.hist_scatter = (
            grow_kwargs.pop("hist_scatter", True)
            and os.environ.get("LGBM_TPU_HIST_SCATTER", "1") != "0"
            and self.num_shards > 1
            and hist_scatter_eligible(
                hp, bundle=grow_kwargs.get("bundle"),
                voting=grow_kwargs.get("voting_top_k", 0) > 0,
                n_forced=0 if forced is None else len(forced["feature"]),
                cegb_coupled=grow_kwargs.get("cegb_coupled")))
        self.physical = physical_bins is not None
        self.fused = False   # set from the grow pieces in physical mode
        self.scan_block_rows = 0    # likewise: rows a step of the scan
        self._f_pad = None   # histogram columns a merge moves
        self._comb = None
        self._scratch = None
        self._sharded_batch = None   # lazily-built batched-K scan core
        # the last dispatch's per-shard counts: each shard's own sum of
        # parent rows over its tree's splits and its in-bag rows,
        # [n_shards * 2] ([K, n_shards * 2] batched), counted by the
        # grow program traced or not and left on the devices until
        # ``shard_work_rows()`` is asked
        self.last_shard_rows = None

        row = P(DATA_AXIS)
        row2d = P(DATA_AXIS, None)
        rep = P()
        tree_specs = TreeArrays(*([rep] * len(TreeArrays._fields)))

        if self.physical:
            n_pad, f_pad = physical_bins.shape
            assert n_pad % self.num_shards == 0
            local_spec = jax.ShapeDtypeStruct(
                (n_pad // self.num_shards, f_pad), physical_bins.dtype)
            pieces: MeshPhysicalPieces = make_grow_fn(
                hp, num_leaves=num_leaves, max_depth=max_depth,
                padded_bins=padded_bins, rows_per_block=rows_per_block,
                use_dp=use_dp, axis_name=DATA_AXIS,
                hist_scatter=self.hist_scatter,
                n_hist_shards=self.num_shards,
                physical_bins=local_spec, **grow_kwargs)
            self._pieces = pieces
            self.fused = pieces.fused
            self.scan_block_rows = int(pieces.scan_block_rows)
            self._f_pad = int(pieces.f_pad)
            self._bins_global = physical_bins
            # EFB under the mesh learners is the unbundling ingest
            # (grow.bundled_comb_eligible keeps the bundled comb to the
            # serial learner): the merge collectives move LOGICAL-width
            # histograms, so the ledger prices that width, not the
            # bundled storage width
            if pieces.padded_bins:
                self._padded_bins = int(pieces.padded_bins)
            self._sharded_core = jax.jit(jax.shard_map(
                pieces.core, mesh=self.mesh,
                in_specs=(row2d, row2d, row, row, row, rep, rep, rep,
                          rep, rep, rep),
                out_specs=(tree_specs, row, row2d, row2d, row),
                check_vma=False,
            ), donate_argnums=(0, 1))
            _init_part = functools.partial(
                phys_init_comb, n_alloc=pieces.n_alloc, C=pieces.C,
                f_pad=pieces.f_pad, dtype=pieces.dtype)
            _ingest = pieces.ingest

            def _init_local(bins_local):
                # EFB (ISSUE 12): each shard unbundles its OWN bundled
                # row block on device before the comb ingest — raw
                # (unbundled) columns never cross the ICI
                if _ingest is not None:
                    bins_local = _ingest(bins_local)
                return _init_part(bins_local)

            self._sharded_init = jax.jit(jax.shard_map(
                _init_local,
                mesh=self.mesh, in_specs=(row2d,), out_specs=row2d,
                check_vma=False,
            ))
        else:
            grow = make_grow_fn(
                hp, num_leaves=num_leaves, max_depth=max_depth,
                padded_bins=padded_bins, rows_per_block=rows_per_block,
                use_dp=use_dp, axis_name=DATA_AXIS,
                hist_scatter=self.hist_scatter,
                n_hist_shards=self.num_shards, **grow_kwargs)
            self._sharded_grow = jax.jit(jax.shard_map(
                grow, mesh=self.mesh,
                in_specs=(row2d, row, row, row, rep, rep, rep, rep, rep),
                out_specs=(tree_specs, row, row),
                check_vma=False,
            ))

    def _batched_core(self):
        """Batched multiclass core (ISSUE 19): ONE shard_map-ped jit
        scanning the per-shard grow core over a leading class axis.
        The comb/scratch shards thread through the scan carry exactly
        as the serial per-class dispatches thread them (class k starts
        from class k-1's final per-shard permutation), and the per-
        split histogram-merge collectives run inside the scan body —
        so the K trees' ICI traffic rides one dispatch instead of K."""
        if self._sharded_batch is None:
            core = self._pieces.core
            row = P(DATA_AXIS)
            row2d = P(DATA_AXIS, None)
            rep = P()
            krow = P(None, DATA_AXIS)   # [K, n]: rows sharded, K local
            tree_specs = TreeArrays(*([rep] * len(TreeArrays._fields)))

            def _core_k(comb, scratch, gradK, hessK, inbag, fmK,
                        num_bins, has_nan, is_cat, seedK):
                def body(carry, xs):
                    comb_c, scr_c = carry
                    g, h, fm, sd = xs
                    tree, lid, comb_n, scr_n, rows = core(
                        comb_c, scr_c, g, h, inbag, fm, num_bins,
                        has_nan, is_cat, sd, jnp.float32(0.0))
                    return (comb_n, scr_n), (tree, lid, rows)

                with phase("glue"):
                    (comb, scratch), (treeK, lidK, rowsK) = jax.lax.scan(
                        body, (comb, scratch),
                        (gradK, hessK, fmK, seedK))
                return treeK, lidK, comb, scratch, rowsK

            self._sharded_batch = jax.jit(jax.shard_map(
                _core_k, mesh=self.mesh,
                in_specs=(row2d, row2d, krow, krow, row, rep, rep,
                          rep, rep, rep),
                out_specs=(tree_specs, krow, row2d, row2d, krow),
                check_vma=False,
            ), donate_argnums=(0, 1))
        return self._sharded_batch

    def grow_batch(self, bins, gradK, hessK, inbag, fmK, num_bins,
                   has_nan, is_cat, seedK):
        """Grow all K class trees in one sharded dispatch; mirrors
        ``_PhysicalGrow.grow_batch`` (stacked ``taK``/``leaf_idK``,
        per-class slices bitwise the serial outputs)."""
        import time as _time

        from ..obs import tracer as obs_tracer
        if not self.physical:
            raise RuntimeError(
                "batched multiclass grow needs the physical mesh path "
                "(routing rule mc_batch_requires_physical)")
        k = int(gradK.shape[0])
        traced = obs_tracer.enabled
        t0 = _time.perf_counter() if traced else 0.0
        with obs_tracer.span(
                "DataParallelGrower::grow", shards=self.num_shards,
                hist_merge=("reduce-scatter" if self.hist_scatter
                            else "psum"),
                physical=True, batched=k) as sp:
            if self._comb is None:
                self._comb = self._sharded_init(self._bins_global)
                self._scratch = jnp.zeros_like(self._comb)
            (treeK, leaf_idK, self._comb, self._scratch,
             self.last_shard_rows) = self._batched_core()(
                self._comb, self._scratch, gradK, hessK, inbag,
                fmK, num_bins, has_nan, is_cat,
                jnp.asarray(seedK, jnp.int32))
            sp.block_on(leaf_idK)
        if traced:
            self._ledger_collective(_time.perf_counter() - t0, trees=k)
        return treeK, leaf_idK

    def reset_stream(self) -> None:
        """Invalidate the carried per-shard row matrix; the next call
        rebuilds it from the sharded bins in the initial row order
        (the serial ``_PhysicalGrow.reset_stream`` contract — checkpoint
        re-anchoring and rollbacks call this so a resumed process and
        the surviving one observe the same comb permutation)."""
        self._comb = None
        self._scratch = None

    def shard_rows(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Place a row-indexed array onto the mesh (pad rows first)."""
        spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def padded_rows(self, n: int, block: int) -> int:
        return pad_rows_to_shards(n, self.num_shards, 1)

    def shard_work_rows(self):
        """The last dispatch's per-shard counts on the host, f64 [trees,
        n_shards, 2] (rows partitioned, in-bag rows): one small
        transfer of a finished program's output, no program of its
        own; kept, so a second reader pays nothing.  None before the
        first tree, and where this process cannot address every shard
        (pre-partitioned multi-host data)."""
        rows = self.last_shard_rows
        if rows is None or isinstance(rows, np.ndarray):
            return rows
        if not rows.is_fully_addressable:
            return None
        rows = self.last_shard_rows = np.asarray(
            jax.device_get(rows), np.float64).reshape(
                -1, self.num_shards, 2)
        return rows

    def _merge_bytes(self, merges: int) -> int:
        """Analytical per-shard ICI bytes of ``merges`` histogram
        merges (obs/costmodel).  Voting mode prices the bounded merge
        (the elected ~2k feature slices + the vote psum) instead of the
        full-histogram payload."""
        from ..obs.costmodel import learner_dispatch_bytes
        return learner_dispatch_bytes(
            "psum_scatter" if self.hist_scatter else "psum",
            f_pad=self._f_pad, padded_bins=self._padded_bins,
            n_shards=self.num_shards, num_leaves=int(merges),
            voting_top_k=self._voting_k)

    def tree_span_args(self, splits: float, trees: int = 1) -> dict:
        """What the ``Tree::grow`` span says of the mesh, from the
        finished tree(s) (``splits`` in all), static shapes and the
        grow program's own per-shard counter (one 32-byte transfer
        after the span's barrier): no device work."""
        merges = int(splits) + int(trees)      # the splits + the roots
        args = {"shards": self.num_shards,
                "hist_merge": "scatter" if self.hist_scatter else "psum",
                "merges": merges,
                "merge_bytes": self._merge_bytes(merges)}
        rows = self.shard_work_rows()
        if rows is not None:
            per_shard = rows[..., 0].sum(axis=0)
            args["shard_rows_partitioned"] = [float(v) for v in per_shard]
            args["shard_rows_max"] = float(per_shard.max())
        return args

    def _ledger_collective(self, wall_s: float, trees: int = 1) -> None:
        """Per-grow collective record for the run ledger (tracing only;
        it dispatches nothing): analytical ICI bytes the per-split
        histogram merges moved at most (obs/costmodel, ``num_leaves``
        merges a tree) plus the PER-SHARD in-bag row counts keyed by
        shard id, which the grow program returns beside the tree — a
        skewed bag makes every collective wait on the fullest shard,
        and the per-shard series is what the mesh flight recorder
        (ledger.mesh_summary, obs diff) roots the straggler skew in."""
        from ..obs import ledger as obs_ledger
        from ..obs import tracer as obs_tracer

        n = self.num_shards
        kind = "psum_scatter" if self.hist_scatter else "psum"
        # batched multiclass: K trees' merges ride one dispatch
        est = self._merge_bytes(self._num_leaves * max(int(trees), 1))
        rows = self.shard_work_rows()
        # one bag serves the K trees of a batched dispatch
        per_shard_rows = (None if rows is None
                          else [float(v) for v in rows[0, :, 1]])
        # a ring collective moves the same per-shard bytes on every
        # shard; recorded per shard anyway so measured per-plane bytes
        # (obs collectives) join against the same shape
        rec = obs_ledger.record_collective(
            f"{type(self).__name__}::{kind}", bytes_moved=est, shards=n,
            per_shard_rows=per_shard_rows,
            per_shard_bytes=[est] * n,
            wall_s=wall_s,
            merges_est=self._num_leaves * max(int(trees), 1))
        obs_tracer.instant("collective",
                           **{k: v for k, v in rec.items()
                              if k not in ("name", "per_shard")},
                           collective=rec["name"])

    def __call__(self, bins, grad, hess, inbag, feature_mask, num_bins,
                 has_nan, is_cat, seed=0):
        # span covers the whole sharded dispatch (the per-split psum /
        # psum_scatter allreduces execute INSIDE this jit; their sum is
        # what this span measures once the barrier lands) — no-op
        # unless the obs tracer is live
        import time as _time

        from ..obs import tracer as obs_tracer
        traced = obs_tracer.enabled
        t0 = _time.perf_counter() if traced else 0.0
        with obs_tracer.span(
                "DataParallelGrower::grow", shards=self.num_shards,
                hist_merge=("reduce-scatter" if self.hist_scatter
                            else "psum"),
                physical=self.physical) as sp:
            if not self.physical:
                self._f_pad = int(bins.shape[1])
                program = self._sharded_grow
                args = (bins, grad, hess, inbag, feature_mask, num_bins,
                        has_nan, is_cat, jnp.int32(seed))
                tree, leaf_id, self.last_shard_rows = program(*args)
            else:
                if self._comb is None:
                    self._comb = self._sharded_init(self._bins_global)
                    self._scratch = jnp.zeros_like(self._comb)
                program = self._sharded_core
                args = (self._comb, self._scratch, grad, hess, inbag,
                        feature_mask, num_bins, has_nan, is_cat,
                        jnp.int32(seed), jnp.float32(0.0))
                (tree, leaf_id, self._comb, self._scratch,
                 self.last_shard_rows) = program(*args)
            # while tracing: what this program's instruction names
            # mean (once; a donated buffer is asked only its shape)
            obs_tracer.program("grow", program, *args)
            sp.block_on(leaf_id)
        # ledger record OUTSIDE the span: the wall must include the
        # span-exit device barrier, or the collective cost reads as the
        # async enqueue time
        if traced:
            self._ledger_collective(_time.perf_counter() - t0)
        return tree, leaf_id
