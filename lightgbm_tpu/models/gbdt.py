"""GBDT boosting orchestration.

Reference: src/boosting/gbdt.{h,cpp} (TrainOneIter gbdt.cpp:437,
BoostFromAverage gbdt.cpp:412, Bagging gbdt.cpp:230-330, UpdateScore
gbdt.cpp:580-607) re-designed so the per-iteration hot path is entirely
device-resident: gradients (objective jnp math), tree growth (one jitted
fori_loop), and train/valid score updates (leaf gathers) never copy row-sized
arrays to the host.  The host keeps the model list (finalized Trees), does
bagging RNG bookkeeping, and reads back only tiny per-tree summaries —
mirroring the cuda_exp property that boosting runs fully on-GPU
(gbdt.cpp:101 boosting_on_gpu_).

The init score (boost_from_average) is folded into the first tree via
AddBias, matching gbdt.cpp:505-512, so saved models are self-contained.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset_core import BinnedDataset
from ..metric import Metric
from ..obs import counters as obs_counters
from ..obs import counters_from_tree as obs_counters_from_tree
from ..obs import events as obs_events
from ..obs import hbm_live_bytes as obs_hbm_live_bytes
from ..obs import ledger as obs_ledger
from ..obs import tracer as obs_tracer
from ..obs.counters import tree_depth
from ..obs.tracer import phase as obs_phase
from ..objective.base import ObjectiveFunction
from ..ops.device_data import DeviceDataset, to_device
from ..ops.grow import make_grow_fn
from ..ops.leaf_lookup import leaf_table_lookup
from ..ops.predict import (DeviceTree, add_tree_score,
                           device_tree_from_arrays, predict_leaf_bins,
                           replay_block_rows, tree_paths, tree_to_device)
from ..ops.split import SplitHyperParams
# module-level bindings (the gbdt purge/reimport convention): each
# generation's booster must poison/guard/record through ITS OWN
# resilience stores, not the newest generation's
from ..resilience import faults as resilience_faults
from ..resilience import numerics as resilience_numerics
from ..utils import log
from ..utils.random import make_rng
from .tree import Tree


class _ValidSet:
    def __init__(self, name: str, data: BinnedDataset, dd_bins, metrics):
        self.name = name
        self.data = data
        self.bins = dd_bins
        self.metrics = metrics
        self.score = None  # [K, n] device
        self.raw = None    # [n, f] device raw values (linear_tree only)


class _PendingEval(NamedTuple):
    dataset: str
    metric: Metric
    value: jax.Array        # the metric program's output, not pulled


def _eval_dispatch(ds_name, metric, arg) -> _PendingEval:
    """A metric's device program dispatched, under the tracer's
    ``eval:<data set>:<metric>``."""
    fn = metric.device_program()
    value = fn(arg)
    obs_tracer.program(f"eval:{ds_name}:{metric.NAME}", fn, arg)
    return _PendingEval(ds_name, metric, value)


def make_score_tail(num_bins, has_nan, fmap=None):
    """The jitted score tail ``GBDT`` dispatches after every tree:
    ``tail(ta, leaf_id, score_k, vbins, vscores_k, rate, init_score)``
    -> (the train score plus the tree, or None where ``leaf_id`` is
    None; each valid score plus the tree, replayed over that set's bins;
    the device tree with shrunk leaf values).  Its ops are ``score``'s,
    each valid set's replay ``valid``'s."""

    @jax.jit
    @obs_phase("score")
    def tail(ta, leaf_id, score_k, vbins, vscores_k, rate, init_score):
        is_real = ta.num_leaves > 1
        if leaf_id is None:
            # the unpaged stream route: the refresh added this
            # delta to the comb's scores, by position
            new_score = None
        else:
            delta = jnp.where(
                is_real,
                rate * leaf_table_lookup(ta.leaf_value, leaf_id), 0.0)
            new_score = score_k + delta
        dt = device_tree_from_arrays(ta)
        new_vscores, paths = [], None
        for vb, vsk in zip(vbins, vscores_k):
            # a phase of its own inside the tail: with no valid set
            # the program is the one it always was
            with obs_phase("valid"):
                paths = tree_paths(dt) if paths is None else paths
                leaf_v = predict_leaf_bins(dt, vb, num_bins, has_nan,
                                           feat_map=fmap, paths=paths)
                dv = jnp.where(
                    is_real,
                    rate * leaf_table_lookup(ta.leaf_value, leaf_v), 0.0)
                new_vscores.append(vsk + dv)
        # replay replica: shrunk values (+ boost-from-average bias,
        # which the host path folds in via add_bias / single_leaf)
        lv = jnp.where(is_real, ta.leaf_value * rate, 0.0) + init_score
        return new_score, tuple(new_vscores), dt._replace(leaf_value=lv)

    return tail


def _jit_with_operands(fn, example):
    """``jax.jit(fn)`` with every array ``fn`` closes over handed in as
    an operand, not baked in as a constant.  An objective closes over
    row-sized arrays (labels, weights, a ranking objective's padded
    tables): as constants they are part of the program text, so the
    compiler chews through tens of megabytes of them (19.7 s for the
    rank gradients at 2.27M rows) and the persistent cache misses for
    every new label vector; as operands the program is a function of the
    shapes alone."""
    closed = jax.make_jaxpr(fn)(example)
    run = jax.jit(lambda consts, x: jax.core.eval_jaxpr(
        closed.jaxpr, consts, x))
    # a partial: the tracer's ``Program::ops`` table lowers ``.func``
    # at ``.args``
    return functools.partial(run, closed.consts)


class GBDT:
    """The `gbdt` booster (reference boosting.cpp:35 factory name)."""

    NAME = "gbdt"

    def __init__(
        self,
        config: Config,
        train_set: Optional[BinnedDataset],
        objective: Optional[ObjectiveFunction],
        metrics: Sequence[Metric] = (),
    ):
        self.config = config
        self.train_set = train_set
        self.objective = objective
        self.models: List[Tree] = []
        self.iter_ = 0
        self.shrinkage_rate = config.learning_rate
        self.average_output = False  # RF sets True
        self.best_iteration = -1
        self.valid_sets: List[_ValidSet] = []
        self._train_metrics = list(metrics)
        self._init_score_applied = False
        self._rng_feature = make_rng(config.feature_fraction_seed)
        self._rng_bagging = make_rng(config.bagging_seed)
        # bin-space device replicas of finalized trees (shrunk, biased),
        # aligned with self.models; used for valid replay / rollback / DART
        self._device_trees: List[DeviceTree] = []
        # per-tree device linear-leaf params (const, coef, feat_idx) or None,
        # aligned with _device_trees (linear_tree only)
        self._device_linear: List = []
        # deferred host finalization: (models index, ta, kidx, init_score,
        # rate) tuples for trees grown but not yet pulled to host.  Keeps
        # the boosting loop a pure async device dispatch chain — no
        # device->host sync per iteration (the cuda_exp "boosting stays on
        # GPU" property, gbdt.cpp:101, taken one step further).
        self._pending: List = []
        self._stalled = False
        self._cegb_paid = None   # CEGB lazy paid-rows mask [F, n] (set
                                 # in _setup_training when enabled)
        # async stall detection: per-iteration device num_leaves scalars,
        # checked opportunistically (non-blocking is_ready) each iteration
        self._nl_pending: List = []   # (iter, num_leaves device scalar)
        self._nl_expected: Dict[int, int] = {}
        self._nl_seen: Dict[int, List[int]] = {}

        self.num_tree_per_iteration = (
            objective.num_models() if objective is not None
            else max(config.num_class, 1))

        if train_set is not None:
            self._setup_training()

    # ------------------------------------------------------------------
    def _setup_training(self) -> None:
        import jax as _jax

        ds = self.train_set
        cfg = self.config
        # numerics guardrail policy (ISSUE 13): read + validate ONCE at
        # setup — a typo'd LGBM_TPU_NUMERICS fails loudly here instead
        # of silently training unguarded.  The serial learner guards
        # IN-GROW (make_grow_fn wraps the built callable); the mesh /
        # pre-partitioned learners guard at the booster boundary
        # (_before_train -> resilience.numerics.host_guard)
        self._numerics = resilience_numerics.policy()
        self._numerics_in_grow = False
        # sorted-subset categorical search (feature_histogram.hpp:278)
        # activates when any categorical feature exceeds max_cat_to_onehot
        from ..io.binning import BinType
        has_big_cats = any(
            m.bin_type == BinType.CATEGORICAL
            and m.num_bins > cfg.max_cat_to_onehot
            for m in ds.mappers)
        if has_big_cats and cfg.tree_learner in ("feature", "voting"):
            log.warning(
                "sorted-subset categorical splits are not supported with "
                "tree_learner=%s; high-cardinality categoricals fall back "
                "to one-hot splits", cfg.tree_learner)
            has_big_cats = False
        elif has_big_cats:
            log.info(
                "sorted-subset categorical search enabled (a categorical "
                "feature exceeds max_cat_to_onehot=%d); splits ride the "
                "physical fast path as bitset membership words in the "
                "partition descriptor (ISSUE 16) — only the Mosaic "
                "finder tail is disabled for this dataset",
                cfg.max_cat_to_onehot)
        self.hp = SplitHyperParams(
            lambda_l1=cfg.lambda_l1,
            lambda_l2=cfg.lambda_l2,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_delta_step=cfg.max_delta_step,
            path_smooth=cfg.path_smooth,
            cat_l2=cfg.cat_l2,
            cat_smooth=cfg.cat_smooth,
            use_cat_subset=has_big_cats,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            max_cat_threshold=cfg.max_cat_threshold,
            min_data_per_group=cfg.min_data_per_group,
            use_extra_trees=cfg.extra_trees,
        )
        # multi-host process group first (reference Network::Init from
        # config, application.cpp:171): after this, jax.devices() spans
        # every machine's chips and the mesh learners scale unchanged
        if cfg.num_machines > 1:
            from ..parallel.network import Network
            Network.init(cfg)
        # learner selection (reference tree_learner.cpp:16 factory matrix):
        # serial -> single device; data -> rows sharded over the mesh;
        # feature -> columns sharded; voting -> data-parallel with top-k
        # histogram election.
        use_dist = (cfg.tree_learner in ("data", "feature", "voting")
                    and len(_jax.devices()) > 1)
        if cfg.tree_learner != "serial" and not use_dist:
            # the reference does the same with num_machines=1; said out
            # loud so a four-chip run that landed on one chip shows it
            log.warning(
                "tree_learner=%s with %d visible device(s): training "
                "with the serial learner", cfg.tree_learner,
                len(_jax.devices()))
        from .constraints import build_grow_constraints
        if use_dist and cfg.tree_learner == "feature":
            from ..parallel.feature_parallel import FeatureParallelGrower
            from ..parallel.mesh import build_mesh, parse_mesh_axes
            mesh = (build_mesh(cfg) if parse_mesh_axes(cfg.tpu_mesh_axes)
                    else None)   # default: all devices on the feature axis
            # device layout FIRST: the feature axis pads to whole per-shard
            # matmul groups, and the [f_pad]-shaped constraint arrays must be
            # sized to that final padding
            probe = FeatureParallelGrower.probe_mesh(mesh)
            self.dd = to_device(
                ds, row_pad_multiple=probe.num_row_shards,
                col_pad_multiple=probe.num_col_shards,
                put_fn=lambda m: probe.shard_bins(jnp.asarray(m)),
                use_bundles=False)   # EFB remaps columns; see grow.py guard
            hp_updates, grow_kwargs = build_grow_constraints(
                cfg, ds, self.dd.f_log)
            if hp_updates:
                self.hp = self.hp._replace(**hp_updates)
            grow_kwargs.update(self._bynode_kwargs(cfg, ds))
            grow_kwargs["extra_seed"] = cfg.extra_seed
            grow_kwargs["padded_bins_log"] = self.dd.padded_bins_log
            self._grow_kwargs = grow_kwargs
            self._set_efb_form("feature")
            grower = FeatureParallelGrower(
                self.hp, num_leaves=cfg.num_leaves, max_depth=cfg.max_depth,
                padded_bins=self.dd.padded_bins,
                rows_per_block=cfg.tpu_rows_per_block,
                use_dp=cfg.gpu_use_dp, mesh=probe.mesh, **self._grow_kwargs)
            self.grow = grower
            self._row_put = grower.shard_rows
            from ..ops import routing as routing_mod
            self._routing = routing_mod.decide(self._route_inputs(
                "feature",
                grower.num_col_shards * grower.num_row_shards, self.dd))
            log.info("Using feature-parallel tree learner: %d column "
                     "shard(s) x %d row shard(s)", grower.num_col_shards,
                     grower.num_row_shards)
        else:
            def _build_constraints(dd_layout):
                """Constraint arrays are sized [dd.f_log], so they build
                AFTER the final device layout is chosen."""
                hp_updates, grow_kwargs = build_grow_constraints(
                    cfg, ds, dd_layout.f_log)
                if hp_updates:
                    self.hp = self.hp._replace(**hp_updates)
                grow_kwargs.update(self._bynode_kwargs(cfg, ds))
                grow_kwargs["extra_seed"] = cfg.extra_seed
                grow_kwargs["padded_bins_log"] = dd_layout.padded_bins_log
                self._grow_kwargs = grow_kwargs

            if use_dist:
                from ..parallel.data_parallel import DataParallelGrower
                from ..parallel.voting_parallel import VotingParallelGrower
                from ..parallel.mesh import (DATA_AXIS, build_mesh)
                from ..ops.grow import hist_scatter_eligible
                from jax.sharding import NamedSharding, PartitionSpec as P
                mesh = build_mesh(cfg)
                n_sh = mesh.shape[DATA_AXIS]
                # reduce-scatter mode pads feature columns to a shard
                # multiple; the layout must be FINAL before the constraint
                # arrays (sized [f_log]) and the grower are built.  The
                # grower re-derives the same eligibility from its actual
                # grow_kwargs, so attribute and layout stay in agreement.
                from ..config import env_knob as _env_knob
                binfo = getattr(ds, "bundle_info", None)
                scat = (cfg.tree_learner == "data" and n_sh > 1
                        and (binfo is None or not binfo.any_bundled)
                        and _env_knob("LGBM_TPU_HIST_SCATTER") != "0")

                pre_part = (cfg.pre_partition
                            and _jax.process_count() > 1)

                def _row_put(m):
                    spec = P(DATA_AXIS, *([None] * (np.ndim(m) - 1)))
                    return jax.device_put(
                        jnp.asarray(m), NamedSharding(mesh, spec))

                # physical partition mode for the mesh learners: each
                # shard runs the SAME streaming partition + comb-direct
                # histogram kernels as the serial learner, with psum /
                # psum_scatter merges (the reference's parallel learners
                # template over the serial device kernels,
                # data_parallel_tree_learner.cpp:279-281).  Rows pad to
                # whole partition blocks PER SHARD (the largest block
                # the scan can take: the layout is not final yet).
                from ..ops.grow import PHYS_ROW_PAD
                binfo_nb = binfo is None or not binfo.any_bundled
                # pre-layout routing probe (ISSUE 10): whether the
                # physical mesh path is still in play decides the row
                # padding BEFORE the final device layout exists, so
                # this cell is decided with optimistic shape facts and
                # re-decided (self._routing) once the layout is final
                from ..ops import routing as routing_mod
                phys_mesh = routing_mod.decide(routing_mod.RouteInputs(
                    learner=cfg.tree_learner, n_shards=n_sh,
                    backend=_jax.default_backend(),
                    efb_bundled=not binfo_nb,
                    gpu_use_dp=bool(cfg.gpu_use_dp),
                    cegb_lazy=bool(cfg.cegb_penalty_feature_lazy),
                    cat_subset=bool(self.hp.use_cat_subset),
                    **routing_mod.env_snapshot())).path == "physical"
                if pre_part:
                    # pre-partitioned multi-process data (reference
                    # dataset_loader.cpp:241-334 partitioned loading +
                    # dataset.h:107 CheckOrPartition): THIS process holds
                    # only its own rows; the global device array is
                    # assembled from per-process local shards — no
                    # cross-host row movement.  Everything except the
                    # grower boundary stays process-local.
                    from jax.experimental import multihost_utils
                    ldev = n_sh // _jax.process_count()
                    mult = ldev * (PHYS_ROW_PAD if phys_mesh else 1)
                    local_need = -(-ds.num_data // mult) * mult
                    all_need = multihost_utils.process_allgather(
                        np.asarray([local_need], np.int64))
                    local_pad = int(np.max(all_need))
                    n_global = local_pad * _jax.process_count()
                    self._npad_local = local_pad
                    self._pre_part = True

                    def _prepart_put(m):
                        m = np.asarray(m)
                        pad = [(0, local_pad - m.shape[0])] +                             [(0, 0)] * (m.ndim - 1)
                        mp = np.ascontiguousarray(np.pad(m, pad))
                        spec = P(DATA_AXIS, *([None] * (m.ndim - 1)))
                        return jax.make_array_from_process_local_data(
                            NamedSharding(mesh, spec), mp,
                            (n_global,) + m.shape[1:])

                    self._prepart_put = _prepart_put
                    # reduce-scatter mode pads the feature axis to an
                    # lcm(group, n_shards) multiple — the fast-path
                    # precondition (f_log % n_sh == 0) without the old
                    # group x shards over-padding
                    # (device_data.pad_features_to_shards)
                    self.dd = to_device(
                        ds, row_pad_multiple=1,
                        col_shard_multiple=(n_sh if scat else 1),
                        put_fn=_prepart_put)
                else:
                    self._pre_part = False
                    self.dd = to_device(
                        ds, row_pad_multiple=(n_sh * PHYS_ROW_PAD
                                              if phys_mesh else n_sh),
                        col_shard_multiple=(n_sh if scat else 1),
                        put_fn=_row_put)
                _build_constraints(self.dd)
                self._set_efb_form(cfg.tree_learner)
                # final routing cell over the REAL layout (bin dtype,
                # bundle survival, per-shard row count): the decision
                # the bench record embeds and the golden matrix pins
                self._routing = routing_mod.decide(self._route_inputs(
                    cfg.tree_learner, n_sh, self.dd))
                phys_mesh = self._routing.path == "physical"
                if cfg.tree_learner == "voting":
                    grower = VotingParallelGrower(
                        self.hp, num_leaves=cfg.num_leaves,
                        max_depth=cfg.max_depth,
                        padded_bins=self.dd.padded_bins,
                        rows_per_block=cfg.tpu_rows_per_block,
                        use_dp=cfg.gpu_use_dp, top_k=cfg.top_k, mesh=mesh,
                        bundle=self.dd.bundle, **self._grow_kwargs)
                    log.info("Using voting-parallel tree learner over %d "
                             "devices (top_k=%d)", grower.num_shards,
                             cfg.top_k)
                else:
                    grower = DataParallelGrower(
                        self.hp, num_leaves=cfg.num_leaves,
                        max_depth=cfg.max_depth,
                        padded_bins=self.dd.padded_bins,
                        rows_per_block=cfg.tpu_rows_per_block,
                        use_dp=cfg.gpu_use_dp, mesh=mesh,
                        bundle=self.dd.bundle, hist_scatter=scat,
                        bundled_comb=(self.dd.comb_bundled if phys_mesh
                                      else None),
                        physical_bins=(self.dd.bins if phys_mesh
                                       else None),
                        **self._grow_kwargs)
                    log.info(
                        "Using data-parallel tree learner over %d devices"
                        "%s%s", grower.num_shards,
                        " (reduce-scattered histograms)"
                        if grower.hist_scatter else "",
                        " (physical row partition)"
                        if grower.physical else "")
                self.grow = grower
                self._row_put = (jnp.asarray if self._pre_part
                                 else grower.shard_rows)
            else:
                # single-device layout; rows pad to the partition
                # kernel's block multiple up front so the physical
                # partition mode can reuse this layout without a second
                # to_device pass
                from ..ops.grow import PHYS_ROW_PAD
                self.dd = to_device(ds, row_pad_multiple=PHYS_ROW_PAD)
                _build_constraints(self.dd)
                self._set_efb_form("serial")
                # path selection (ISSUE 10): the declarative routing
                # model replaces the inline use_phys/use_stream boolean
                # soup.  The same named predicates (ops/routing.py
                # RULES) drive the static routing matrix
                # (lightgbm_tpu/analysis/routing_matrix.json), so the
                # runtime and the analyzer cannot disagree about which
                # path a config engages or why it fell back —
                # physical partition mode (rows move in place with
                # streaming DMA; the serial-learner TPU default;
                # LGBM_TPU_PHYS: auto = TPU only, 0 off, interpret
                # force-on off-TPU) and score-resident gradient
                # streaming on top of it (stream_grad.py: the comb
                # matrix carries scores + objective constants; gated to
                # objectives whose gradient formula the kernel knows
                # and configs where the in-matrix score is the whole
                # story).
                from ..ops import routing as routing_mod
                self._routing = routing_mod.decide(
                    self._route_inputs("serial", 1, self.dd))
                use_phys = self._routing.path in ("physical", "stream")
                use_stream = self._routing.path == "stream"
                obj_kind = routing_mod.objective_kind(self.objective)
                stream_spec = (None if not use_stream else {
                    "kind": obj_kind,
                    "sigmoid": float(getattr(self.objective, "sigmoid",
                                             1.0)),
                    # true (unpadded) row count: the 2-channel histograms
                    # carry no count channel, and the padded layout's
                    # zero-weight slack rows must not count at the root
                    "count": int(ds.num_data)})
                # paged comb (ISSUE 15): when the routing model says
                # the footprint cannot sit fully resident (or
                # LGBM_TPU_PAGED=1 forces it), plan the page geometry
                # off-chip (costmodel.page_schedule over the ENGAGED
                # stream/fused, LGBM_TPU_PAGE_ROWS override) and
                # hand it to the grower — the kernels' row-block grids
                # extend over host-resident pages streamed through the
                # double-buffered page buffers
                page_plan = None
                if use_phys and self._routing.paged:
                    from ..config import env_knob as _env_knob
                    from ..obs.costmodel import hbm_limit_bytes
                    from ..ops.paged import plan_pages
                    _pr = _env_knob("LGBM_TPU_PAGE_ROWS")
                    page_plan = plan_pages(
                        rows=self.dd.n_pad,
                        f_pad=self.dd.phys_f_pad,
                        padded_bins=self.dd.phys_padded_bins,
                        num_leaves=cfg.num_leaves,
                        stream=use_stream,
                        fused=self._routing.fused,
                        stream_kind=(obj_kind if use_stream
                                     else "binary"),
                        num_class=max(self.num_tree_per_iteration, 1),
                        rows_per_page=(int(_pr) if _pr not in
                                       ("auto", "", "0") else None),
                        force=routing_mod.env_snapshot()[
                            "paged_env"] == "1",
                        limit_bytes=hbm_limit_bytes())
                self._page_plan = page_plan
                self.grow = make_grow_fn(
                    self.hp,
                    num_leaves=cfg.num_leaves,
                    max_depth=cfg.max_depth,
                    padded_bins=self.dd.padded_bins,
                    rows_per_block=cfg.tpu_rows_per_block,
                    use_dp=cfg.gpu_use_dp,
                    bundle=self.dd.bundle,
                    bundled_comb=self.dd.comb_bundled if use_phys else None,
                    physical_bins=self.dd.bins if use_phys else None,
                    stream=stream_spec,
                    paged=page_plan,
                    numerics=self._numerics,
                    **self._grow_kwargs,
                )
                self._numerics_in_grow = self._numerics != "off"
                self._lazy_score = bool(
                    getattr(self.grow, "lazy_score", False))
                if use_stream:
                    # rate read per call: reset_parameter callbacks may
                    # change learning_rate mid-training
                    self.grow.set_stream_aux(
                        self._stream_aux,
                        rate_fn=lambda: self.shrinkage_rate)
                    self._stream_grad = True
                    log.info("Score-resident gradient streaming enabled "
                             "(%s gradients computed in the row matrix)",
                             self.objective.NAME)
                if use_phys:
                    log.info("Using physical row-partition mode "
                             "(streaming in-place splits)")
                    if page_plan is not None:
                        log.info(
                            "Paged comb engaged: %d pages x %d rows/"
                            "page (%.2f GiB resident of a %.2f GiB "
                            "budget; ~%.1f s/tree host DMA at %.0f "
                            "GB/s, overlapped with compute)",
                            page_plan["n_pages"],
                            page_plan["rows_per_page"],
                            page_plan["resident_bytes"] / 2**30,
                            page_plan["limit_bytes"] / 2**30,
                            page_plan["overhead_s_per_tree"],
                            page_plan["host_bw_gbps"])
                if "cegb_lazy" in self._grow_kwargs:
                    # persistent per-(feature, row) acquisition mask
                    # (feature_used_in_data_, cost_effective_gradient_
                    # boosting.hpp:169); rides across trees through the
                    # grow call
                    self._cegb_paid = jnp.zeros(
                        (int(self.dd.num_bins.shape[0]), self.dd.n_pad),
                        jnp.bool_)
                self._row_put = jnp.asarray
        # loud, structured fallbacks (ISSUE 10): every config-caused
        # row_order fallback bumps a routing_fallback_* obs event and
        # logs once naming the responsible knob — replacing the silent
        # use_phys=False of earlier rounds
        from ..ops import routing as _routing_mod
        _routing_mod.report_fallbacks(self._routing)
        with obs_tracer.span("Train::layout") as _lsp:
            if obs_tracer.enabled:
                _lsp.set(**self.layout_info())
        # score/gradient arrays live at padded length — the LOCAL one
        # under pre-partitioned multi-process data (only the grower
        # boundary sees the assembled global arrays)
        n = (self._npad_local if getattr(self, "_pre_part", False)
             else self.dd.n_pad)
        self._n_rows_host = n
        nr = self._n_real = ds.num_data
        # linear trees (reference linear_tree_learner.cpp): retained raw
        # numerical values go on device for per-leaf model fitting
        self._raw_dev = None
        if cfg.linear_tree:
            if self.objective is not None and self.objective.NEEDS_RENEW:
                log.fatal("linear_tree is not supported with objective %s "
                          "(per-leaf percentile refit conflicts with linear "
                          "leaf models)", cfg.objective)
            if self.NAME in ("dart", "rf"):
                log.fatal("linear_tree is not supported with boosting=%s",
                          self.NAME)
            if ds.raw_matrix is None:
                log.fatal("linear_tree=true but the dataset kept no raw "
                          "values; pass linear_tree in the Dataset params")
            raw = np.ascontiguousarray(ds.raw_matrix, np.float32)
            if n != nr:
                raw = np.pad(raw, ((0, n - nr), (0, 0)))
            self._raw_dev = self._row_put(raw)
        k = self.num_tree_per_iteration
        init = np.zeros((k, n), dtype=np.float32)
        if ds.metadata.init_score is not None:
            s = np.asarray(ds.metadata.init_score, np.float64)
            s = s.reshape(k, nr) if s.size == k * nr else s.reshape(1, nr)
            init[:, :nr] += s
            self._has_init_score = True
        else:
            self._has_init_score = False
        self.train_score = jnp.asarray(init)  # [K, n_pad]
        lab = ds.metadata.label
        self._label = (None if lab is None else self._row_put(
            np.pad(np.asarray(lab, np.float32), (0, n - nr))))
        self._valid_rows = self._row_put(
            (np.arange(n) < nr).astype(np.float32))
        for m in self._train_metrics:
            m.init(ds.metadata, nr)
        # per-class "need train" flag (reference class_need_train_)
        self._class_need_train = [True] * k
        # batched multiclass (ISSUE 19): all K class trees in ONE
        # compiled grow dispatch per iteration.  Engagement is the
        # routing model's call (mc_batched: multi_tree on the physical
        # path, LGBM_TPU_MC_BATCH knob, unpaged); the runtime
        # additionally requires the fast deferred score tail (no
        # linear trees, no renew objectives, gbdt/goss boosting — the
        # per-class tails stay serial and a non-fast tail would erase
        # the dispatch saving) and a grower exposing the batched core
        # (pre-partitioned multi-process assembly stays per-class)
        self._mc_batched = bool(
            k > 1 and getattr(self._routing, "mc_batched", False)
            and not getattr(self, "_pre_part", False)
            and getattr(self, "_cegb_paid", None) is None
            and self._raw_dev is None
            and (self.objective is None
                 or not self.objective.NEEDS_RENEW)
            and self.NAME in ("gbdt", "goss")
            and hasattr(getattr(self.grow, "_fn", self.grow),
                        "grow_batch"))
        if self._mc_batched:
            log.info("Batched multiclass grow engaged: %d class trees "
                     "per compiled dispatch", k)

    # ------------------------------------------------------------------
    def _set_efb_form(self, learner: str) -> None:
        """Decide ONCE which EFB form a bundled table takes on the
        physical route (ISSUE 36) and write it on the device layout
        (``dd.comb_bundled``), after the grow constraints are built and
        before anything reads a width: the routing inputs, the page
        planner and the cost model price what ``dd.phys_*`` say, and
        ``make_grow_fn`` is handed the same answer and raises if its
        own arguments would build the other form."""
        from ..ops.grow import bundled_comb_eligible
        gk = self._grow_kwargs
        self.dd.comb_bundled = (
            self.dd.bundle is not None and bundled_comb_eligible(
                self.hp, axis_name=None if learner == "serial" else learner,
                n_forced=0 if gk.get("forced") is None else 1,
                interaction_sets=gk.get("interaction_sets"),
                cegb_coupled=gk.get("cegb_coupled"),
                cegb_lazy=gk.get("cegb_lazy"),
                bynode_count=int(gk.get("bynode_count", 0) or 0)))

    def _route_inputs(self, learner: str, n_shards: int, dd):
        """RouteInputs snapshot for the ENGAGED learner and FINAL
        device layout (ISSUE 10): the config / dataset / env-knob
        facts the declarative routing model (``ops/routing.py``)
        decides the physical/stream/merge path from.  The same
        fields key the static routing matrix, so the cell this returns
        is directly testable against the golden enumeration
        (tests/test_routing.py).  Call AFTER ``_build_constraints``:
        the forced-split / CEGB / monotone facts come from the built
        ``_grow_kwargs`` and the (possibly updated) hyper-params."""
        import jax as _jax

        from ..ops import routing as routing_mod
        from ..ops.grow import PHYS_ROW_SLACK
        cfg = self.config
        bag_on = (cfg.bagging_freq > 0
                  and (cfg.bagging_fraction < 1.0
                       or cfg.pos_bagging_fraction < 1.0
                       or cfg.neg_bagging_fraction < 1.0))
        n_shards = max(int(n_shards), 1)
        gk = getattr(self, "_grow_kwargs", {}) or {}
        base = routing_mod.RouteInputs(
            learner=learner, n_shards=n_shards,
            backend=_jax.default_backend(),
            efb_bundled=dd.bundle is not None,
            efb_comb=dd.comb_bundled,
            # the stored columns under the bundled comb; under the
            # unbundling ingest the LOGICAL bin width decides (the
            # comb takes unbundled u8 columns even when a stacked
            # bundle column stores u16)
            bins_u8=dd.phys_bins_u8,
            rows_over_limit=bool(dd.n_pad // n_shards
                                 >= (1 << 24) - PHYS_ROW_SLACK),
            f_log_shard_divisible=(n_shards <= 1
                                   or dd.f_log % n_shards == 0),
            gpu_use_dp=bool(cfg.gpu_use_dp),
            # config-level truthiness (not grow_kwargs presence): a
            # lazy-CEGB request blocks the physical path even where the
            # constraint builder warn-and-ignores it (mesh learners) —
            # the pre-refactor gate's exact semantics
            cegb_lazy=bool(cfg.cegb_penalty_feature_lazy),
            cat_subset=bool(self.hp.use_cat_subset),
            bagging=bool(bag_on),
            linear_tree=bool(cfg.linear_tree),
            boosting=self.NAME,
            objective_kind=routing_mod.objective_kind(self.objective),
            multi_tree=self.num_tree_per_iteration != 1,
            forced_splits=gk.get("forced") is not None,
            mono_intermediate=bool(self.hp.use_monotone
                                   and self.hp.mono_intermediate),
            cegb_coupled=gk.get("cegb_coupled") is not None,
            **routing_mod.env_snapshot())
        # geometry facts at the width the physical path actually
        # allocates (bundle columns or the unbundled logical layout, by
        # the form set above); rows + leaves let resolve_layout price
        # the footprint against the HBM budget (over_budget — the
        # ISSUE-15 paging fact)
        return routing_mod.resolve_layout(
            base, f_pad=dd.phys_f_pad, padded_bins=dd.phys_padded_bins,
            rows=dd.n_pad, num_leaves=cfg.num_leaves,
            num_class=max(self.num_tree_per_iteration, 1))

    def layout_info(self) -> Dict:
        """What the device layout holds, for the ``Train::layout``
        event of the set-up and for a benchmark's check: the stored bin
        columns, the logical features they stand for, the EFB bundles
        among them, and the bytes of one comb line (0 off the physical
        route, which holds no comb), its 128-lane planes, the tiles
        a comb histogram sweeps and the split of a bin its one-hots
        take (``hist_lo_n``; 0 off the physical route)."""
        dd, b = self.dd, self.dd.bundle
        pieces = getattr(self.grow, "_pieces", None)
        width = pieces.C if pieces is not None else getattr(
            self.grow, "_C", 0)
        dtype = (pieces.dtype if pieces is not None
                 else getattr(self.grow, "_dtype", jnp.float32))
        return {
            "phys_cols": int(dd.f_pad),
            "logical_features": int(dd.num_features),
            "bundles": 0 if b is None else int(
                len(np.unique(b["feat_phys"][b["is_bundled"]]))),
            "comb_cols": int(dd.phys_f_pad),
            "comb_line_bytes": int(width) * jnp.dtype(dtype).itemsize,
            "comb_planes": int(width) // 128,
            "hist_tiles": int(getattr(self.grow, "hist_tiles", 0)),
            "hist_lo_n": int(getattr(self.grow, "hist_lo_n", 0)),
        }

    def routing_info(self) -> Optional[Dict]:
        """The engaged routing decision as a JSON-ready dict (bench
        records embed it; ``obs diff`` treats digest mismatches as
        incomparable), or None before training setup.  Once a compiled
        serving model has been built for this booster (ISSUE 14), its
        identity block (digest, tree count, slice) rides along under
        ``serving``."""
        r = getattr(self, "_routing", None)
        if r is None:
            return None
        info = r.to_json()
        serving = getattr(self, "_serving_info", None)
        if serving is not None:
            info["serving"] = serving
        plan = getattr(self, "_page_plan", None)
        if plan is not None:
            info["page_plan"] = {
                k: plan[k] for k in
                ("rows_per_page", "n_pages", "page_bytes",
                 "resident_bytes", "sweeps_per_tree",
                 "dma_bytes_per_tree", "overhead_s_per_tree")
                if k in plan}
            geo = getattr(self.grow, "paged_geometry", lambda: None)()
            if geo is not None:
                info["page_plan"]["engaged"] = geo
        return info

    def note_serving(self, serving_info: Dict) -> None:
        """Record the compiled ServingModel identity (serve/model.py
        ``to_json``) so routing_info() reports the serving digest."""
        self._serving_info = dict(serving_info)

    # ------------------------------------------------------------------
    def set_init_model(self, trees: List[Tree]) -> None:
        """Continued training (reference init_model / continued-training via
        predictor-initialized scores, application.cpp:94-97): keep the old
        model's trees so the final booster is self-contained.  Must be called
        before the first iteration; the caller is responsible for setting
        init_score to the old model's raw predictions."""
        if self.models:
            log.fatal("set_init_model must be called before training starts")
        if (self._raw_dev is None
                and any(getattr(t, "is_linear", False) for t in trees)):
            log.fatal("init_model contains linear trees; pass "
                      "linear_tree=true so the dataset keeps raw values")
        for t in trees:
            if t.num_leaves > 1 and (
                    t.threshold_bin is None or not t.threshold_bin.any()):
                self._rebin_tree(t)
                # rebinned against a dataset the tree was NOT grown on:
                # thresholds are approximate, so compiled serving must
                # keep this booster on the exact host walk (the
                # predict_rebinned_model routing rule; checkpoint
                # restore rebins too but against the SAME dataset —
                # exact, pinned byte-identical — so it stays unmarked)
                t.rebinned = True
            self.models.append(t)
            self._device_trees.append(tree_to_device(t, self.train_set))
            self._device_linear.append(self._linear_params_of(t))
        self.num_init_iteration = len(trees) // self.num_tree_per_iteration

    num_init_iteration = 0

    def _rebin_tree(self, t: Tree) -> None:
        """Fill bin-space thresholds for a tree loaded from a model file so
        it can run on the binned matrix (valid replay / DART)."""
        inner_of = {int(o): i for i, o in enumerate(self.train_set.used_feature_map)}
        ni = t.num_leaves - 1
        tb = np.zeros(ni, np.int32)
        for i in range(ni):
            f = int(t.split_feature[i])
            if f not in inner_of:
                continue  # pruned feature: threshold stays 0 (all left)
            m = self.train_set.mappers[inner_of[f]]
            if int(t.decision_type[i]) & 1:
                # categorical: first raw value in the bitset -> its bin
                cat_idx = int(t.threshold[i])
                lo, hi = t.cat_boundaries[cat_idx], t.cat_boundaries[cat_idx + 1]
                words = t.cat_threshold[lo:hi]
                vals = [w * 32 + b for w in range(hi - lo) for b in range(32)
                        if (words[w] >> b) & 1]
                if vals:
                    tb[i] = int(m.values_to_bins(np.array([float(vals[0])]))[0])
            else:
                ub = m.upper_bounds
                tb[i] = int(np.searchsorted(ub, t.threshold[i], side="left"))
        t.threshold_bin = tb

    # ------------------------------------------------------------------
    # deterministic checkpoint/resume (ISSUE 13, resilience/checkpoint)
    # ------------------------------------------------------------------
    def checkpoint_state(self) -> Dict:
        """The exact boosting state a ``lightgbm_tpu/ckpt/v1`` snapshot
        captures beyond the forest itself: the running f32 score
        vector (verbatim — re-deriving scores through the host
        prediction path is NOT bit-identical), the stateful host RNG
        streams, and the small host counters.  Bagging/GOSS masks are
        stateless functions of seed x iteration and are re-derived at
        restore."""
        self._flush_pending()
        return {
            "iteration": int(self.iter_),
            "train_score": np.asarray(self.train_score, np.float32),
            "rng_feature": self._rng_feature.bit_generator.state,
            "rng_bagging": self._rng_bagging.bit_generator.state,
            "shrinkage_rate": float(self.shrinkage_rate),
            "class_need_train": [bool(b)
                                 for b in self._class_need_train],
            "cegb_paid": (np.asarray(self._cegb_paid)
                          if self._cegb_paid is not None else None),
        }

    def restore_checkpoint_state(self, models: List[Tree], *,
                                 iteration: int, train_score,
                                 rng_feature=None, rng_bagging=None,
                                 shrinkage_rate=None,
                                 class_need_train=None,
                                 cegb_paid=None) -> None:
        """Install a ckpt/v1 snapshot: replaces the forest and every
        piece of per-run state so the next ``train_one_iter`` grows the
        SAME tree the uninterrupted run grew at ``iteration``.  Works
        on a fresh booster (process-death resume) and on a live one
        (in-process fault recovery) — current state is discarded."""
        k = self.num_tree_per_iteration
        # discard current state: deferred host pulls, stall probes and
        # the bagging cache all belong to the run being replaced
        self._pending = []
        self._nl_pending = []
        self._nl_expected.clear()
        self._nl_seen.clear()
        self._stalled = False
        self._cached_bag = None
        self.models = []
        self._device_trees = []
        self._device_linear = []
        for t in models:
            if t.num_leaves > 1 and (t.threshold_bin is None
                                     or not t.threshold_bin.any()):
                self._rebin_tree(t)
            self.models.append(t)
            self._device_trees.append(tree_to_device(t, self.train_set))
            self._device_linear.append(self._linear_params_of(t))
        self.iter_ = int(iteration)
        score = np.asarray(train_score, np.float32)
        k_n = (k, self._n_rows_host)
        if score.shape != k_n:
            raise ValueError(
                f"checkpoint score shape {score.shape} does not match "
                f"this run's padded score layout {k_n}")
        self.train_score = jnp.asarray(score)
        if rng_feature is not None:
            self._rng_feature.bit_generator.state = rng_feature
        if rng_bagging is not None:
            self._rng_bagging.bit_generator.state = rng_bagging
        if shrinkage_rate is not None:
            self.shrinkage_rate = float(shrinkage_rate)
        if class_need_train is not None:
            self._class_need_train = [bool(b) for b in class_need_train]
        if cegb_paid is not None:
            self._cegb_paid = jnp.asarray(cegb_paid)
        # mid-cycle bagging cache: masks are stateless in (seed, cycle
        # start), so re-derive the mask the uninterrupted run would
        # still be holding when the checkpoint landed mid-cycle
        cfg = self.config
        if cfg.bagging_freq > 0 and self.iter_ % cfg.bagging_freq != 0:
            self._bagging_mask(self.iter_
                               - self.iter_ % cfg.bagging_freq)
        self._reanchor_physical()
        for vs in self.valid_sets:
            self._replay_valid(vs)

    def _reanchor_physical(self) -> None:
        """Reset the carried physical row permutation (serial
        ``_PhysicalGrow`` and the mesh ``DataParallelGrower`` both
        carry the comb across trees).  Leaf-value float sums accumulate
        in comb row order, so the checkpoint layer calls this right
        after every save: the surviving process and a process resuming
        from that snapshot then observe the SAME (initial) row order —
        the last piece of the byte-identical-resume contract.  In
        stream mode the rebuild also re-ingests the restored scores.
        Row-order paths carry no permutation: no-op.

        ``LGBM_TPU_CKPT_AT_REFRESH=1`` (ISSUE 15 satellite): on the
        stream path the save lands at a refresh boundary — the tree's
        fused refresh pass just rebuilt every value column — so the
        re-anchor happens IN PLACE (one anchored-order scatter by the
        stored row ids) instead of dropping the comb for the full
        re-ingest the round-16 notes flag; kill+resume stays
        byte-identical (tests/test_resilience.py pins it)."""
        reset = getattr(self.grow, "reset_stream", None)
        if reset is None:
            return
        self._sync_train_score()
        from ..config import env_knob
        if env_knob("LGBM_TPU_CKPT_AT_REFRESH") == "1":
            inplace = getattr(self.grow, "reanchor_inplace", None)
            if inplace is not None and inplace():
                return
        reset()

    # ------------------------------------------------------------------
    def add_valid(self, data: BinnedDataset, name: str,
                  metrics: Sequence[Metric]) -> None:
        from ..ops.device_data import to_device as _dd
        # valid layout must match training: unbundled when the training
        # layout is (e.g. the feature-parallel learner disables EFB)
        ddv = _dd(data, use_bundles=(self.dd.bundle is not None))
        vs = _ValidSet(name, data, ddv.bins, list(metrics))
        if self._raw_dev is not None:
            if data.raw_matrix is None:
                log.fatal("linear_tree: validation dataset kept no raw "
                          "values (construct it with the same params)")
            vs.raw = jnp.asarray(
                np.ascontiguousarray(data.raw_matrix, np.float32))
        self._replay_valid(vs)
        for m in vs.metrics:
            m.init(data.metadata, data.num_data)
        self.valid_sets.append(vs)

    def _replay_valid(self, vs: _ValidSet) -> None:
        """(Re)build a valid set's score from its init score + the
        CURRENT forest (bin space, finalized leaf values already carry
        shrinkage + init bias).  Used when a valid set joins and when a
        checkpoint restore replaces the forest out from under it."""
        data = vs.data
        k = self.num_tree_per_iteration
        init = np.zeros((k, data.num_data), np.float32)
        if data.metadata.init_score is not None:
            s = np.asarray(data.metadata.init_score, np.float64)
            init += (s.reshape(k, -1) if s.size == k * data.num_data
                     else s.reshape(1, -1))
        vs.score = jnp.asarray(init)
        for i, dt in enumerate(self._device_trees):
            kidx = i % k
            linp = (self._device_linear[i]
                    if i < len(self._device_linear) else None)
            if linp is not None:
                from .linear import linear_leaf_output
                const_d, coef_d, fi_d, lv_d = linp
                leaf_v = predict_leaf_bins(dt, vs.bins, self.dd.num_bins,
                                           self.dd.has_nan,
                                           feat_map=self._fmap)
                out_v = linear_leaf_output(leaf_v, vs.raw, const_d, coef_d,
                                           fi_d, lv_d)
                vs.score = vs.score.at[kidx].set(vs.score[kidx] + out_v)
            else:
                vs.score = vs.score.at[kidx].set(
                    add_tree_score(vs.score[kidx], dt, vs.bins,
                                   self.dd.num_bins, self.dd.has_nan, 1.0,
                                   feat_map=self._fmap))

    # ------------------------------------------------------------------
    # bagging (reference gbdt.cpp:230-330); returns in-bag mask [n] f32
    def _bagging_mask(self, it: int) -> Optional[jnp.ndarray]:
        cfg = self.config
        need = (cfg.bagging_freq > 0 and
                (cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
                 or cfg.neg_bagging_fraction < 1.0))
        if not need:
            return None
        if it % cfg.bagging_freq != 0 and self._cached_bag is not None:
            return self._cached_bag
        n = self._n_rows_host
        key = jax.random.PRNGKey((cfg.bagging_seed * 2654435761 + it) & 0x7FFFFFFF)
        u = jax.random.uniform(key, (n,))
        if cfg.pos_bagging_fraction != 1.0 or cfg.neg_bagging_fraction != 1.0:
            pos = self._label > 0
            p = jnp.where(pos, cfg.pos_bagging_fraction, cfg.neg_bagging_fraction)
            mask = (u < p).astype(jnp.float32)
        else:
            mask = (u < cfg.bagging_fraction).astype(jnp.float32)
        self._cached_bag = mask
        return mask

    _cached_bag = None

    _fmask_const = None

    _stream_grad = False

    _numerics = "off"          # LGBM_TPU_NUMERICS policy (ISSUE 13)

    _numerics_in_grow = False  # serial learner: sentinel lives in-grow

    _routing = None   # RouteDecision of the engaged path (ISSUE 10)

    # ------------------------------------------------------------------
    # the row-order train score [K, n_pad].  Off the unpaged stream
    # route it is kept every tree.  On it (``_lazy_score``) the comb's
    # score columns are the score of record - the refresh adds every
    # tree's shrunk outputs there, by position, and no training
    # iteration reads a row-order score -, ``_train_score`` is the last
    # value somebody asked for and ``_score_behind`` counts the trees
    # grown since: a read runs ``grow.pull_score()`` once (one program:
    # ``TrainScore::materialise``) and clears the count, an assignment
    # stores and clears it.  Whatever drops or rebuilds the comb reads
    # the score first (``_sync_train_score``): after ``reset_stream``
    # the only up-to-date copy is gone.  A traced run's barriers wait
    # on what a program returned, never on the property.
    #
    # A numerics policy of ``raise`` / ``skip`` keeps such a booster
    # EAGER: it pulls after every tree the sentinel let through, so
    # that a dropped tree (whose outputs the comb already holds)
    # leaves ``_train_score`` at the last-good score, from which the
    # comb is then rebuilt.
    _train_score = None
    _score_behind = 0
    _lazy_score = False

    @property
    def train_score(self):
        self._sync_train_score()
        return self._train_score

    @train_score.setter
    def train_score(self, value) -> None:
        self._train_score = value
        self._score_behind = 0

    def _sync_train_score(self) -> None:
        """Bring ``_train_score`` up to date: for a reader, and before
        the comb, the only other copy, is dropped or rebuilt."""
        if not self._score_behind:
            return
        with obs_tracer.span("TrainScore::materialise",
                             trees_behind=self._score_behind) as _sp:
            obs_events.record("TrainScore::materialise")
            score = self.grow.pull_score()
            if score is None:
                raise RuntimeError(
                    f"the comb was dropped with {self._score_behind} "
                    "trees the booster's train score does not hold")
            self.train_score = score
            _sp.block_on(score)

    def _stream_aux(self):
        """Aux rows for the streaming init kernel: [2 + n_consts, n_pad]
        (current scores incl. boost-from-average/init_score, validity
        mask, per-row objective constants pre-split into bf16-exact
        terms).  Called once, lazily, when the row matrix first builds —
        and again after a rollback invalidates it."""
        from ..ops.pallas.stream_grad import (binary_consts, build_aux,
                                              l2_consts)
        obj = self.objective
        npad, nr = self.dd.n_pad, self._n_real

        def pad(x):
            return jnp.pad(jnp.asarray(x, jnp.float32), (0, npad - nr))

        @jax.jit
        def build(score, valid):
            if obj.NAME == "binary":
                consts = binary_consts(pad(obj._sign),
                                       pad(obj._label_weight))
                return build_aux("binary", score, valid, consts)
            w = (jnp.ones((npad,), jnp.float32) if obj.weight is None
                 else pad(obj.weight))
            return build_aux("l2", score, valid,
                             l2_consts(pad(obj._target), w))

        return build(self.train_score[0], self._valid_rows)

    def _feature_mask(self, tree_seed: int) -> jnp.ndarray:
        cfg = self.config
        f_pad = self.dd.f_log   # feature masks live in LOGICAL space
        f = self.dd.num_features
        if cfg.feature_fraction >= 1.0:
            # constant mask: build + transfer once, not once per tree
            if self._fmask_const is None:
                mask = np.zeros(f_pad, np.float32)
                mask[:f] = 1.0
                self._fmask_const = jnp.asarray(mask)
            return self._fmask_const
        mask = np.zeros(f_pad, np.float32)
        k = max(1, int(np.ceil(f * cfg.feature_fraction)))
        sel = self._rng_feature.choice(f, size=k, replace=False)
        mask[sel] = 1.0
        return jnp.asarray(mask)

    @staticmethod
    def _bynode_kwargs(cfg, ds):
        """ColSampler by-node sampling config (feature_fraction_bynode).
        The per-node count is a fraction of the BY-TREE-sampled active set
        (reference ColSampler samples from used_feature_indices_), not of
        the total feature count."""
        if cfg.feature_fraction_bynode >= 1.0:
            return {}
        if cfg.tree_learner == "feature":
            log.warning("feature_fraction_bynode is ignored with the "
                        "feature-parallel learner (per-shard sampling "
                        "would not be a global sample)")
            return {}
        k_tree = ds.num_features
        if cfg.feature_fraction < 1.0:
            k_tree = max(1, int(np.ceil(k_tree * cfg.feature_fraction)))
        k = max(1, int(np.ceil(k_tree * cfg.feature_fraction_bynode)))
        return {"bynode_count": k,
                "bynode_seed": cfg.feature_fraction_seed}

    @property
    def _fmap(self):
        """EFB device mapping for bin-space tree replay, or None."""
        b = self.dd.bundle
        if b is None:
            return None
        if self._fmap_cache is None:
            self._fmap_cache = (jnp.asarray(b["feat_phys"]),
                                jnp.asarray(b["feat_offset"]),
                                jnp.asarray(b["feat_default"]))
        return self._fmap_cache

    _fmap_cache = None

    # ------------------------------------------------------------------
    def get_training_score(self) -> jnp.ndarray:
        return self.train_score

    def train_one_iter(
        self,
        gradients: Optional[np.ndarray] = None,
        hessians: Optional[np.ndarray] = None,
    ) -> bool:
        """One boosting iteration.  Returns True when training cannot
        continue (no splittable leaves), like GBDT::TrainOneIter."""
        # one call site whether tracing or not: the Python stack above a
        # Pallas kernel is part of how its compiled program is found in
        # the persistent cache, so a second branch here would make a run
        # with the tracer live from the start compile everything anew
        with obs_tracer.span("GBDT::TrainOneIter", iteration=self.iter_):
            return self._train_one_iter_impl(gradients, hessians)

    def _sample_phase_hbm(self, phase: str) -> None:
        """Live-buffer watermark census (obs.hbm_live_bytes) at PHASE
        granularity (ISSUE 9): an upper bound on device HBM held by
        live jax arrays, sampled at each reference phase while tracing
        — ``Tree::grow`` and ``UpdateScore`` between the dispatch and
        its ``::wait``, under the running program: the walk is host
        bookkeeping and a dispatched program's outputs are live arrays
        already, so it reads the bytes it read after the barrier and
        the device does not wait for it — the measured side of the
        footprint model's per-phase live-sets
        (obs/costmodel.grow_footprint), rendered by ``obs mem`` as the
        memory timeline.  Tracing off: never called
        on the hot path (every call site is behind ``tracer.enabled``),
        and the census is host-side only — the grow jaxpr is pinned
        unchanged by the ``grow-phase-hbm`` purity pin.  Module-level
        obs bindings (not a lazy ``from ..obs import``): a purge/
        reimport must keep this generation's samples in ITS OWN
        ledger — a call-time import resolves through sys.modules to
        the newest generation and records into someone else's."""
        with obs_tracer.span("HbmCensus", phase=phase):
            b = obs_hbm_live_bytes()
            obs_tracer.instant("hbm_live_bytes", phase=phase, bytes=b)
            obs_ledger.record_phase_hbm(phase, b)

    def _skip_poisoned_tree(self, exc) -> None:
        """Policy ``skip`` (ISSUE 13): drop the poisoned tree and keep
        the model list aligned with a zero stump; the skip is loud (obs
        event + warning) but training continues."""
        obs_events.record("numerics_skip")
        log.warning("numerics sentinel (%s=skip): dropping poisoned "
                    "tree — %s", resilience_numerics.NUMERICS_ENV, exc)
        t = Tree.single_leaf(0.0)
        self.models.append(t)
        self._device_trees.append(tree_to_device(t, self.train_set))
        self._device_linear.append(None)

    def _train_one_iter_impl(self, gradients, hessians) -> bool:
        cfg = self.config
        k = self.num_tree_per_iteration
        try:
            with obs_tracer.span("BeforeTrain", iteration=self.iter_):
                grad, hess, inbag, init_scores = self._before_train(
                    gradients, hessians)
        except resilience_numerics.NumericsSkip as e:
            # the booster-boundary guard (mesh learners) rejected this
            # iteration's gradients: every class gets a zero stump
            for _ in range(k):
                self._skip_poisoned_tree(e)
            self.iter_ += 1
            return False
        if obs_tracer.enabled:
            self._sample_phase_hbm("BeforeTrain")

        should_continue = False
        if k > 1 and getattr(self, "_mc_batched", False):
            # batched multiclass (ISSUE 19): ONE grow dispatch carries
            # all K class trees; per-class gating/skip semantics live
            # inside _train_iter_batched
            should_continue = self._train_iter_batched(
                grad, hess, inbag, init_scores)
        else:
            for kidx in range(k):
                if not self._class_need_train[kidx]:
                    # reference class_need_train_ gating (gbdt.cpp): a
                    # class whose first-round tree stumped out skips
                    # growing and gets a zero stump to keep
                    # models[it*k + kidx] aligned
                    t = Tree.single_leaf(0.0)
                    self.models.append(t)
                    self._device_trees.append(
                        tree_to_device(t, self.train_set))
                    self._device_linear.append(None)
                    continue
                with obs_tracer.span("GradSlice", kidx=kidx):
                    # eager device slices (two small programs a class;
                    # placeholders in stream mode, sliced all the same)
                    g_k, h_k = grad[kidx], hess[kidx]
                try:
                    tree = self._train_one_tree(
                        g_k, h_k, inbag, kidx, init_scores[kidx])
                except resilience_numerics.NumericsSkip as e:
                    self._skip_poisoned_tree(e)
                    should_continue = True
                    continue
                if tree is not None:
                    should_continue = True
        self.iter_ += 1
        # deferred path: opportunistic stall check — read back num_leaves
        # scalars that have already materialised on device.  Throttled to
        # every 8th iteration: is_ready() and the scalar fetch serialize
        # the async dispatch pipeline (a per-iteration probe was measured
        # at ~30% of 1M-row throughput, builder-measured pre-PR-1), while
        # all-stump iterations are nearly free, so a stall still stops
        # training within ~10 cheap iterations instead of the 32-flush.
        if self._nl_pending and self.iter_ % 8 == 0:
            with obs_tracer.span("StallProbe"):
                self._probe_stall()
        # fallback periodic flush keeps host trees warm and catches the
        # stall even if is_ready never reports
        if self._pending and self.iter_ % 32 == 0:
            self._flush_pending()
        if self._stalled:
            should_continue = False
        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return not should_continue

    def _probe_stall(self) -> None:
        # FIFO dispatch completes in order, so probe only the HEAD
        while self._nl_pending:
            it, nl = self._nl_pending[0]
            if hasattr(nl, "is_ready") and not nl.is_ready():
                break
            self._nl_pending.pop(0)
            self._nl_seen.setdefault(it, []).append(int(nl))
        for it, counts in list(self._nl_seen.items()):
            if len(counts) == self._nl_expected.get(it, -1):
                if all(c <= 1 for c in counts):
                    self._stalled = True
                del self._nl_seen[it]
                del self._nl_expected[it]

    def _train_iter_batched(self, grad, hess, inbag,
                            init_scores) -> bool:
        """Batched multiclass iteration (ISSUE 19): grow all K class
        trees in ONE compiled dispatch.  The scan-over-K grow core
        threads the carried comb through the classes exactly the way
        the serial per-class dispatches do (class k starts from class
        k-1's final permutation), so every per-class slice of the
        stacked outputs is bitwise the serial tree.  Per-class
        semantics are preserved:

        * the SAME ``tree_seed`` per class, and feature-mask RNG draws
          happen in class order and ONLY for active classes (the
          serial loop ``continue``s before the draw);
        * ``class_need_train`` stumps ride zeroed grad/hess plus an
          all-zero feature mask in their scan slot — the root gain
          never clears, the split loop runs zero iterations, and the
          carried comb permutation is untouched (bitwise what skipping
          the dispatch leaves behind);
        * a poisoned class degrades to a zero stump without dropping
          its siblings via the per-class [K] numerics-bad vector.

        The per-class score tails stay serial over bitwise device
        slices of the stacked arrays (the deferred tail is one small
        dispatch per class; the K-fold saving targets the grow loop's
        dispatch floor)."""
        k = self.num_tree_per_iteration
        active = [bool(self._class_need_train[kidx])
                  for kidx in range(k)]

        def _append_stump():
            t = Tree.single_leaf(0.0)
            self.models.append(t)
            self._device_trees.append(
                tree_to_device(t, self.train_set))
            self._device_linear.append(None)

        if not any(active):
            for _ in range(k):
                _append_stump()
            return False
        seeds = np.zeros(k, np.int64)
        masks: List = [None] * k
        for kidx in range(k):
            seeds[kidx] = (self.iter_ * max(k, 1)) + kidx
            if active[kidx]:
                masks[kidx] = self._feature_mask(int(seeds[kidx]))
        zero_mask = jnp.zeros_like(
            next(m for m in masks if m is not None))
        fmK = jnp.stack([m if m is not None else zero_mask
                         for m in masks])
        if all(active):
            gK, hK = grad, hess
        else:
            act = jnp.asarray(np.asarray(active, np.float32))
            gK = grad * act[:, None]
            hK = hess * act[:, None]
        with obs_tracer.span("Tree::grow", batched=k) as _gsp:
            obs_events.record("grow_dispatch")
            taK, leaf_idK = self.grow.grow_batch(
                self.dd.bins, gK, hK, inbag, fmK,
                self.dd.num_bins, self.dd.has_nan, self.dd.is_cat,
                np.asarray(seeds, np.int32))
            if obs_tracer.enabled:
                self._sample_phase_hbm("Tree::grow")
                _gsp.wait(leaf_idK)
                self._record_work_counters(
                    _gsp, taK, [kidx for kidx in range(k) if active[kidx]])
        badK = None
        if (self._numerics in ("raise", "skip")
                and getattr(self.grow, "last_numerics_bad", None)
                is not None):
            # one [K] host pull per iteration (vs one scalar per tree
            # serially) — the per-class semantics are unchanged
            badK = np.asarray(self.grow.last_numerics_bad)
        should_continue = False
        for kidx in range(k):
            if not active[kidx]:
                _append_stump()
                continue
            if badK is not None and int(badK[kidx]):
                if self._numerics == "raise":
                    raise resilience_numerics.NumericalFault(
                        "grad/hess/leaf/gain", self.iter_,
                        int(badK[kidx]))
                self._skip_poisoned_tree(
                    resilience_numerics.NumericsSkip(
                        "grad/hess/leaf/gain", self.iter_,
                        int(badK[kidx])))
                should_continue = True
                continue
            ta_k = jax.tree.map(lambda a, _k=kidx: a[_k], taK)
            with obs_tracer.span("UpdateScore") as _usp:
                r = self._finish_tree_async(
                    ta_k, leaf_idK[kidx], kidx, init_scores[kidx])
                _usp.block_on(self.train_score)
                if obs_tracer.enabled:
                    self._sample_phase_hbm("UpdateScore")
            if r:
                should_continue = True
        return should_continue

    def _before_train(self, gradients, hessians):
        """Pre-grow iteration setup (reference BeforeTrain: bagging,
        gradient refresh, boost-from-average): returns (grad, hess,
        inbag, init_scores)."""
        cfg = self.config
        n = self.train_set.num_data
        k = self.num_tree_per_iteration

        init_scores = np.zeros(k)
        if gradients is None or hessians is None:
            # boost from average before the first iteration
            if (not self.models and not self._has_init_score
                    and self.objective is not None and cfg.boost_from_average):
                init_scores = np.asarray(self.objective.boost_from_score(),
                                         np.float64).reshape(k)
                if getattr(self, "_pre_part", False):
                    # percentile-based boosts (l1/quantile/...) compute
                    # from local rows; rank 0's value is authoritative
                    # so every rank starts from the SAME score (sum-
                    # syncable objectives already merged globally)
                    from ..parallel.network import Network
                    if Network.is_initialized():
                        mask = 1.0 if Network.rank() == 0 else 0.0
                        init_scores = np.asarray([
                            Network.global_sum([v * mask])[0]
                            for v in init_scores], np.float64)
                if np.any(np.abs(init_scores) > 1e-35):
                    self.train_score = self.train_score + init_scores[:, None]
                    for vs in self.valid_sets:
                        vs.score = vs.score + init_scores[:, None]
                    log.info("Start training from score %s",
                             np.array2string(init_scores, precision=6))
            if self._stream_grad:
                # gradients live in the physical row matrix and refresh
                # in-kernel; the grow wrapper ignores these placeholders
                grad = hess = jnp.zeros((k, 1), jnp.float32)
            else:
                score = self.get_training_score()
                # gradient refresh span ("Boosting" in the reference
                # timer names); barriered so traces show real device
                # time, not the async enqueue
                with obs_tracer.span(
                        "Boosting", **(self.objective.span_args()
                                       if self.objective is not None
                                       else {})) as _sp:
                    grad, hess = self._compute_gradients(score)
                    _sp.block_on(hess)
        else:
            if self._stream_grad:
                log.fatal("explicit gradients are not supported with "
                          "score-resident gradient streaming; set "
                          "objective=none or LGBM_TPU_STREAM=0")
            grad = np.asarray(gradients, np.float32).reshape(k, n)
            hess = np.asarray(hessians, np.float32).reshape(k, n)
            npad = self._n_rows_host
            if npad != n:
                grad = np.pad(grad, ((0, 0), (0, npad - n)))
                hess = np.pad(hess, ((0, 0), (0, npad - n)))
            grad, hess = jnp.asarray(grad), jnp.asarray(hess)

        if self._stream_grad:
            # an armed LGBM_TPU_FAULT=nan drill cannot poison here —
            # gradients refresh in-kernel inside the comb — and a
            # drill silently not firing would fake a green leg, so
            # the harness says so loudly (one-shot, like firing)
            resilience_faults.warn_unfireable_nan(self.iter_)
            inbag = jnp.zeros((1,), jnp.float32)
        else:
            # fault injection (ISSUE 13): LGBM_TPU_FAULT=nan@i poisons
            # the materialised gradients HERE, where every non-stream
            # path sees them — the numerics guardrails are the
            # detection side (in-grow for the serial learner, the
            # host_guard below for the mesh / pre-partitioned ones)
            grad, hess = resilience_faults.maybe_poison(
                grad, hess, self.iter_)
            if self._numerics != "off" and not self._numerics_in_grow:
                grad, hess = resilience_numerics.host_guard(
                    grad, hess, self._numerics, self.iter_)
            grad, hess, inbag = self._sample(grad, hess, self.iter_)
        return grad, hess, inbag, init_scores

    # ------------------------------------------------------------------
    def _localize_rows(self, arr):
        """This process's contiguous row block of a global row-sharded
        array (pre-partitioned mode): concatenate the addressable shards
        in row order."""
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return jnp.concatenate(
            [jnp.asarray(np.asarray(s.data)) for s in shards], axis=0)

    # ------------------------------------------------------------------
    _grad_fn = None

    def _compute_gradients(self, score):
        """One jitted dispatch for the whole objective gradient pass
        (slice, GetGradients math, pad).  Eager op-by-op dispatch costs a
        host dispatch per op — this was measured at ~55ms/iter on 1M rows
        vs ~2ms fused (builder-measured pre-PR-1)."""
        if self.objective is None:
            log.fatal("No objective function and no custom gradients provided")
        if self._grad_fn is None:
            k = self.num_tree_per_iteration
            nr, npad = self._n_real, self._n_rows_host
            obj = self.objective

            @obs_phase("gradients")
            def fn(score):
                s = score[:, :nr]
                g, h = obj.get_gradients(s if k > 1 else s[0])
                g = g.reshape(k, nr)
                h = h.reshape(k, nr)
                if npad != nr:
                    g = jnp.pad(g, ((0, 0), (0, npad - nr)))
                    h = jnp.pad(h, ((0, 0), (0, npad - nr)))
                return g, h

            # stateful objectives (RankXENDCG's per-iteration noise key)
            # must re-trace each call; everything else gets one cached jit
            self._grad_fn = (fn if obj.STATEFUL_GRADIENTS
                             else _jit_with_operands(fn, score))
        out = tuple(self._grad_fn(score))
        if isinstance(self._grad_fn, functools.partial):
            obs_tracer.program("gradients", self._grad_fn.func,
                               *self._grad_fn.args, score)
        return out

    def _sample(self, grad, hess, it):
        """Bagging hook; GOSS overrides (reference goss.hpp)."""
        inbag = self._bagging_mask(it)
        if inbag is None:
            inbag = self._valid_rows
        else:
            inbag = inbag * self._valid_rows
        return grad, hess, inbag

    def _train_one_tree(self, g, h, inbag, kidx, init_score) -> Optional[Tree]:
        """Grow, renew, shrink, update scores; returns finalized host Tree
        or None when the tree is a stump (no split possible)."""
        # held so a numerics sentinel below can roll the CEGB paid
        # mask back when it drops the tree that advanced it (the grow
        # call does not donate this buffer, so the old array stays
        # valid)
        cegb_prev = getattr(self, "_cegb_paid", None)
        with obs_tracer.span("Tree::grow", kidx=kidx) as _gsp:
            tree_seed = (self.iter_ * max(self.num_tree_per_iteration, 1)
                         + kidx)
            fmask = self._feature_mask(tree_seed)
            # grow-dispatch ledger pin (ISSUE 19): the serial loop pays
            # one grow dispatch PER CLASS TREE; the batched multiclass
            # path records exactly one per iteration
            obs_events.record("grow_dispatch")
            if getattr(self, "_pre_part", False):
                ta, leaf_id_g = self.grow(
                    self.dd.bins, self._prepart_put(g),
                    self._prepart_put(h), self._prepart_put(inbag),
                    fmask,
                    self.dd.num_bins, self.dd.has_nan, self.dd.is_cat,
                    tree_seed)
                self._leaf_id_global = leaf_id_g
                leaf_id = self._localize_rows(leaf_id_g)
                ta = jax.tree.map(
                    lambda a: jnp.asarray(np.asarray(a)), ta)
            elif getattr(self, "_cegb_paid", None) is not None:
                out = self.grow(
                    self.dd.bins, g, h, inbag, fmask,
                    self.dd.num_bins, self.dd.has_nan, self.dd.is_cat,
                    tree_seed, self._cegb_paid)
                ta, leaf_id, self._cegb_paid = out[:3]
            else:
                ta, leaf_id = self.grow(
                    self.dd.bins, g, h, inbag, fmask,
                    self.dd.num_bins, self.dd.has_nan, self.dd.is_cat,
                    tree_seed)
            if obs_tracer.enabled:
                self._sample_phase_hbm("Tree::grow")
                # (an output of the program: with no row-order leaf id,
                # its tree)
                _gsp.wait(ta if leaf_id is None else leaf_id)
                self._record_work_counters(_gsp, ta, [kidx])
        if (self._numerics in ("raise", "skip")
                and getattr(self.grow, "last_numerics_bad", None)
                is not None):
            # opt-in sentinel pull (one i32 scalar per tree): the grown
            # tree has NOT been appended or scored yet, so raise/skip
            # leave the booster at its last-good state
            bad = int(self.grow.last_numerics_bad)
            if bad:
                if getattr(self, "_cegb_paid", None) is not None:
                    # the grow output already advanced the paid mask;
                    # the dropped tree must not leave features marked
                    # paid-for by a tree that will never exist
                    self._cegb_paid = cegb_prev
                if self._lazy_score:
                    # the comb already holds the dropped tree's
                    # outputs: rebuild it from the last-good score
                    self.grow.reset_stream()
                if self._numerics == "raise":
                    raise resilience_numerics.NumericalFault(
                        "grad/hess/leaf/gain", self.iter_, bad)
                raise resilience_numerics.NumericsSkip(
                    "grad/hess/leaf/gain", self.iter_, bad)
        fast = (self._raw_dev is None
                and (self.objective is None
                     or not self.objective.NEEDS_RENEW)
                and self.NAME in ("gbdt", "goss"))
        if fast:
            with obs_tracer.span("UpdateScore") as _usp:
                r = self._finish_tree_async(ta, leaf_id, kidx, init_score)
                # the tail's outputs; never the property, which would
                # run the pull
                _usp.block_on((self._train_score, self._device_trees[-1]))
                if obs_tracer.enabled:
                    self._sample_phase_hbm("UpdateScore")
            return r
        nl = int(ta.num_leaves)
        lin = None
        if self._raw_dev is not None and nl > 1:
            # per-leaf linear models (LinearTreeLearner::CalculateLinear)
            from .linear import fit_linear_models, leaf_path_features
            feat_idx = leaf_path_features(
                ta, np.asarray(self.dd.is_cat), self.config.num_leaves)
            coef, const, ok, lin_pred = fit_linear_models(
                ta, leaf_id, self._raw_dev, g, h, inbag, feat_idx,
                self.config.linear_lambda, self.config.num_leaves)
            lin = {"feat_idx": feat_idx, "coef": coef, "const": const,
                   "ok": ok, "pred": lin_pred,
                   "feat_dev": jnp.asarray(feat_idx),
                   "coef_dev": jnp.asarray(coef, jnp.float32),
                   "const_dev": jnp.asarray(const, jnp.float32)}
        if nl <= 1:
            # always append a stump so models[it*k + kidx] stays aligned
            # across classes (reference always pushes a tree per class)
            t = self._finalize_host_tree(nl, ta, kidx, len(self.models),
                                         float(init_score), 0.0)
            self.models.append(t)
            self._device_trees.append(tree_to_device(t, self.train_set))
            self._device_linear.append(None)
            return None

        leaf_values = ta.leaf_value
        if self.objective is not None and self.objective.NEEDS_RENEW:
            leaf_values = self._renew_leaf_values(ta, leaf_id, kidx, inbag)
            ta = ta._replace(leaf_value=leaf_values)

        # device score updates (train incl. out-of-bag + all valid sets)
        rate = self.shrinkage_rate
        train_out = lin["pred"] if lin is not None else leaf_values[leaf_id]
        self.train_score = self.train_score.at[kidx].set(
            self.train_score[kidx] + rate * train_out)
        dt = device_tree_from_arrays(ta)
        for vs in self.valid_sets:
            if lin is not None:
                from .linear import linear_leaf_output
                leaf_v = predict_leaf_bins(dt, vs.bins, self.dd.num_bins,
                                           self.dd.has_nan,
                                           feat_map=self._fmap)
                out_v = linear_leaf_output(
                    leaf_v, vs.raw, lin["const_dev"], lin["coef_dev"],
                    lin["feat_dev"], ta.leaf_value)
                vs.score = vs.score.at[kidx].set(vs.score[kidx] + rate * out_v)
            else:
                vs.score = vs.score.at[kidx].set(
                    add_tree_score(vs.score[kidx], dt, vs.bins,
                                   self.dd.num_bins, self.dd.has_nan, rate,
                                   feat_map=self._fmap))

        tree = self._finalize_host_tree(nl, ta, kidx, len(self.models),
                                        init_score, rate, lin=lin)
        self.models.append(tree)
        self._device_trees.append(tree_to_device(tree, self.train_set))
        self._device_linear.append(self._linear_params_of(tree))
        return tree

    def _record_work_counters(self, span, ta, kidxs) -> None:
        """The work counters (obs/counters.py) of the tree(s) just
        grown, derived on the host from the tree itself: one pull of
        its small arrays (a few KB; a transfer, not a program; with
        them the four numbers the grow program counts for its fused
        scan, ``ta.side_miss``) after the ``Tree::grow`` barrier,
        recorded as before
        (``obs_counters.record`` + ``tracer.count``) and set as args of
        the ``Tree::grow`` span.  Only ever called while tracing, and
        the same whether the tracer was enabled before the booster was
        built or after: the grow program knows nothing of it.  A
        batched grow hands ``ta`` stacked [K, ...]; ``kidxs`` then
        names the active classes.  A data-parallel grower adds what it
        knows of the mesh (``tree_span_args``: shards, merges and their
        analytical bytes, and each shard's own rows, which the grow
        program counts traced or not)."""
        with obs_tracer.span("WorkCounters"):
            small = jax.device_get(
                (ta.num_leaves, ta.left_child, ta.right_child,
                 ta.internal_count, ta.leaf_count, ta.side_miss))
        fused = (bool(getattr(self.grow, "fused", False))
                 and jax.default_backend() == "tpu")
        batched = np.ndim(small[0]) > 0
        scan_r = int(getattr(self.grow, "scan_block_rows", 0))
        shards = int(getattr(self.grow, "num_shards", 1)) if scan_r else 1
        total: Dict[str, float] = {}
        for kidx in kidxs:
            arrs = tuple(a[kidx] for a in small) if batched else small
            d = obs_counters.record(obs_counters_from_tree(
                *arrs, fused=fused, scan_block_rows=scan_r,
                shards=shards))
            for name, val in d.items():
                obs_tracer.count(name, val, kidx=kidx)
                total[name] = total.get(name, 0.0) + val
            total["tree_depth"] = total.get("tree_depth", 0) + tree_depth(
                *arrs[:3])
        mesh_args = getattr(self.grow, "tree_span_args", None)
        if mesh_args is not None:
            total.update(mesh_args(total.get("splits", 0.0), len(kidxs)))
        if scan_r:
            total["scan_block_rows"] = scan_r
        # the comb's planes, the tiles a comb histogram sweeps, the rows
        # a step of it reads and the split of a bin, from the built
        # program
        for name in ("comb_planes", "hist_tiles", "hist_block_rows",
                     "hist_lo_n"):
            if getattr(self.grow, name, 0):
                total[name] = int(getattr(self.grow, name))
        span.set(**total)

    def _async_tail_fn(self):
        """One jitted dispatch for the whole post-grow tail (train-score
        delta, valid replays, replay replica) instead of one dispatch
        per eager op."""
        key = len(self.valid_sets)
        if getattr(self, "_tail_cache_key", None) == key:
            return self._tail_cache
        self._tail_cache = make_score_tail(self.dd.num_bins,
                                           self.dd.has_nan, self._fmap)
        self._tail_cache_key = key
        return self._tail_cache

    def _finish_tree_async(self, ta, leaf_id, kidx, init_score):
        """Asynchronous tree finalization: all score updates and the valid
        replay replica stay on device; the host Tree is materialised lazily
        by _flush_pending.  A stump (num_leaves==1) contributes zero score
        delta on device, matching the sync path's skip.  ``leaf_id`` None
        (the unpaged stream route): the train half is skipped - the comb
        holds the new score by position - and ``train_score`` falls one
        more tree behind; the valid replay, the device tree and the
        pending-tree bookkeeping are the same."""
        rate = self.shrinkage_rate
        tail = self._async_tail_fn()
        lazy = leaf_id is None
        # the eager ops and the jitted tail each may block in the
        # runtime (a launch, an allocation), so each has its own name
        with obs_tracer.span("UpdateScore::set", op="slice"):
            score_k = None if lazy else self.train_score[kidx]
            vscores_k = tuple(vs.score[kidx] for vs in self.valid_sets)
        with obs_tracer.span("UpdateScore::tail") as tail_span:
            vbins = tuple(vs.bins for vs in self.valid_sets)
            tail_args = (ta, leaf_id, score_k, vbins, vscores_k,
                         jnp.float32(rate), jnp.float32(init_score))
            new_score, new_vscores, dt = tail(*tail_args)
            if vbins:
                # the replay's work from shapes: every row of every set
                # decides each of the tree's nodes (replay_steps, a
                # row's decisions), by the decision matrix on u8 bins
                ni = int(ta.split_feature.shape[-1])
                tail_span.set(
                    valid_sets=len(vbins),
                    valid_rows=sum(int(b.shape[0]) for b in vbins),
                    replay_matmul_rows=sum(int(b.shape[0]) for b in vbins
                                           if replay_block_rows(b, ni)),
                    replay_steps=len(vbins) * ni)
        obs_tracer.program("score", tail, *tail_args)
        with obs_tracer.span("UpdateScore::set", op="set"):
            if lazy:
                self._score_behind += 1
            else:
                self.train_score = self.train_score.at[kidx].set(new_score)
            for vs, sk in zip(self.valid_sets, new_vscores):
                vs.score = vs.score.at[kidx].set(sk)
        if lazy and self._numerics in ("raise", "skip"):
            self._sync_train_score()            # eager: see train_score
        self._device_trees.append(dt)
        self._device_linear.append(None)
        self.models.append(None)
        self._pending.append(
            (len(self.models) - 1, ta, kidx, float(init_score), rate))
        self._nl_pending.append((self.iter_, ta.num_leaves))
        self._nl_expected[self.iter_] = (
            self._nl_expected.get(self.iter_, 0) + 1)
        return True

    def _finalize_host_tree(self, nl, ta, kidx, model_idx, init_score,
                            rate, lin=None) -> Tree:
        """Shared host finalization for the sync and deferred paths: stump
        bookkeeping, bin->real-threshold conversion, linear-leaf fields,
        shrinkage and boost-from-average bias."""
        if nl <= 1:
            first_round = ((self.num_init_iteration + 1)
                           * self.num_tree_per_iteration)
            if model_idx < first_round:
                self._class_need_train[kidx] = False
            return Tree.single_leaf(init_score)
        t = Tree.from_device(ta, self.train_set)
        if lin is not None:
            t.is_linear = True
            t.leaf_const = lin["const"][:nl].copy()
            t.leaf_coeff, t.leaf_features = [], []
            t.leaf_features_inner = []
            for l in range(nl):
                fl = lin["feat_idx"][l]
                fl = fl[fl >= 0] if lin["ok"][l] else fl[:0]
                t.leaf_features_inner.append(fl.astype(np.int32))
                t.leaf_features.append(
                    self.train_set.used_feature_map[fl].astype(np.int32))
                t.leaf_coeff.append(lin["coef"][l, :len(fl)].copy())
        t.apply_shrinkage(rate)
        if abs(init_score) > 1e-35:
            t.add_bias(init_score)
        return t

    def _flush_pending(self) -> None:
        """Materialise deferred trees on host.  All pending tree arrays are
        packed into ONE flat device buffer and pulled in a single transfer
        (per-array pulls pay a device-to-host transfer each)."""
        if not self._pending:
            return
        with obs_tracer.span("FlushPending", trees=len(self._pending)):
            self._flush_pending_impl()

    def _flush_pending_impl(self) -> None:
        from ..ops.grow import pack_tree_arrays, unpack_tree_arrays
        # chunked so the jitted pack's trace size (14 ops/tree) stays
        # bounded no matter how many trees deferred; chunks PAD to CHUNK
        # (repeating the first tree) so every flush hits one cached jit
        # trace — the pack retraces per distinct tree count otherwise,
        # costing seconds per novel flush size mid-training
        CHUNK = 32
        host_tas = []
        for c0 in range(0, len(self._pending), CHUNK):
            chunk = [p[1] for p in self._pending[c0:c0 + CHUNK]]
            n_real = len(chunk)
            if n_real < CHUNK:
                chunk = chunk + [chunk[0]] * (CHUNK - n_real)
            packed = pack_tree_arrays(chunk)
            host_tas.extend(unpack_tree_arrays(
                packed, self.config.num_leaves, CHUNK,
                cat_b=(self.dd.padded_bins_log or self.dd.padded_bins)
                if self.hp.use_cat_subset else 0,
                side_n=int(chunk[0].side_miss.shape[-1]))[:n_real])
        k = self.num_tree_per_iteration
        stumps_by_iter: Dict[int, List[bool]] = {}
        for (idx, _ta, kidx, init_score, rate), ta in zip(
                self._pending, host_tas):
            nl = int(ta.num_leaves)
            self.models[idx] = self._finalize_host_tree(
                nl, ta, kidx, idx, init_score, rate)
            stumps_by_iter.setdefault(idx // k, []).append(nl <= 1)
        # an iteration whose k trees are ALL stumps means the sync path
        # would have stopped there; flag it (sticky) so training halts at
        # the next boundary.  Detection is delayed by up to the flush
        # interval — extra stump iterations may be recorded.
        if any(len(v) == k and all(v) for v in stumps_by_iter.values()):
            self._stalled = True
        self._pending.clear()

    def _linear_params_of(self, t: Tree):
        """Device (const, coef, feat_idx) for a finalized linear tree, or
        None.  Used for valid-set replay of already-finalized trees (the
        counterpart of tree_to_device for linear leaves)."""
        if not getattr(t, "is_linear", False):
            return None
        feats = t.leaf_features_inner
        coefs = t.leaf_coeff
        if feats is None:
            # loaded model: rebuild inner ids from original feature ids,
            # keeping coefficients PAIRED with surviving features (a model
            # feature pruned from this dataset drops its coefficient too)
            inner_of = {int(o): i for i, o in
                        enumerate(self.train_set.used_feature_map)}
            feats, coefs = [], []
            dropped = 0
            for fl, cl in zip(t.leaf_features, t.leaf_coeff):
                keep = [(inner_of[int(f)], c) for f, c in zip(fl, cl)
                        if int(f) in inner_of]
                dropped += len(fl) - len(keep)
                feats.append(np.array([i for i, _ in keep], np.int32))
                coefs.append(np.array([c for _, c in keep], np.float64))
            if dropped:
                log.warning("linear tree replay: %d leaf-model features are "
                            "not present in this dataset; their terms are "
                            "dropped", dropped)
        nl = t.num_leaves
        kmax = max((len(f) for f in feats), default=0)
        kmax = max(kmax, 1)
        fi = np.full((nl, kmax), -1, np.int32)
        co = np.zeros((nl, kmax), np.float32)
        for l in range(nl):
            k = len(feats[l])
            fi[l, :k] = feats[l]
            co[l, :k] = np.asarray(coefs[l][:k], np.float32)
        return (jnp.asarray(np.asarray(t.leaf_const, np.float32)),
                jnp.asarray(co), jnp.asarray(fi),
                jnp.asarray(np.asarray(t.leaf_value, np.float32)))

    # per-leaf percentile refit for l1/quantile/mape/huber — fully on
    # device (one lexsort + segment reductions; the cuda_exp
    # RenewTreeOutputCUDA analog).  The previous host version pulled the
    # full residual vector and looped leaves in numpy every tree,
    # O(num_leaves * n) host work that broke the async dispatch chain.
    def _renew_leaf_values(self, ta, leaf_id, kidx, inbag) -> jnp.ndarray:
        from ..objective.regression import device_renew_leaf_values
        alpha = float(self.objective.renew_leaf_percentile())
        nr = self._n_real
        score = self.get_training_score()[kidx][:nr]
        resid = jnp.asarray(self.objective.leaf_residual(score))
        w = self.objective.renew_weight()
        weighted = w is not None
        wv = (jnp.asarray(w) if weighted
              else jnp.ones((nr,), jnp.float32))
        L = int(ta.leaf_value.shape[0])
        if getattr(self, "_pre_part", False):
            # pre-partitioned multi-process data: percentiles must cover
            # the GLOBAL rows (each rank holds a disjoint subset) — run
            # the segment-sort refit SPMD on globally assembled arrays
            # (replicated [L] result), like every other collective
            npl = self._n_rows_host
            padr = npl - nr
            resid_g = self._prepart_put(
                np.pad(np.asarray(resid, np.float32), (0, padr)))
            w_g = self._prepart_put(
                np.pad(np.asarray(wv, np.float32), (0, padr)))
            valid_g = self._prepart_put(np.pad(
                (np.asarray(inbag)[:nr] > 0), (0, padr)))
            lid_g = self._leaf_id_global.astype(jnp.int32)
            return device_renew_leaf_values(
                resid_g, w_g, lid_g, valid_g,
                jnp.asarray(np.asarray(ta.leaf_value)),
                L=L, alpha=alpha, weighted=weighted)
        lid = jnp.asarray(leaf_id)[:nr].astype(jnp.int32)
        valid = jnp.asarray(inbag)[:nr] > 0
        return device_renew_leaf_values(
            resid, wv, lid, valid, jnp.asarray(ta.leaf_value),
            L=L, alpha=alpha, weighted=weighted)

    # ------------------------------------------------------------------
    def eval(self, dataset: Optional[str] = None
             ) -> List[Tuple[str, str, float, bool]]:
        """[(dataset_name, metric_name, value, higher_better)] like
        GBDT::OutputMetric; ``dataset`` (``"training"`` or a valid
        set's name): that data set's metrics alone, no other's computed,
        as LGBM_BoosterGetEval(data_idx) asks for one.

        Rank metrics (AUC/NDCG) evaluate ON DEVICE when possible — the
        host path pulls the full score vector every eval, ~44 MB/iter at
        Higgs scale with metric_freq=1; the device path pulls scalars,
        all of a call's at one barrier (``Eval::wait``)."""
        todo = []
        if self._train_metrics and dataset in (None, "training"):
            todo.append(("training", self._train_metrics, None))
        todo += [(vs.name, vs.metrics, vs) for vs in self.valid_sets
                 if vs.metrics and dataset in (None, vs.name)]
        with obs_tracer.span("Eval", datasets=len(todo),
                             metrics=sum(len(t[1]) for t in todo)) as span:
            return self._eval_impl(todo, span)

    def _eval_impl(self, todo, span) -> List[Tuple[str, str, float, bool]]:
        out = []        # a metric's results, or its device value to pull
        rows = 0
        k = self.num_tree_per_iteration
        for ds_name, metrics, vs in todo:
            # the train score is read only here: on the stream route a
            # read runs a program (``train_score``)
            score = self.train_score if vs is None else vs.score
            n_real = self._n_real if vs is None else None
            rows += int(metrics[0].num_data)
            if k == 1:
                dev_ms = [m for m in metrics if hasattr(m, "eval_device")]
            else:
                # multiclass device eval (VERDICT r2 weak #4): softmax
                # conversion + logloss/error on device; only scalars
                # cross to host
                dev_ms = [m for m in metrics
                          if hasattr(m, "eval_device_prob")]
            host_ms = [m for m in metrics if m not in dev_ms]
            if k == 1:
                for m in dev_ms:
                    raw_dev = score[0][:m.num_data]
                    if self.average_output:
                        raw_dev = raw_dev / max(self.iter_, 1)
                    out.append(_eval_dispatch(ds_name, m, raw_dev))
            elif dev_ms:
                raw_dev = score[:, :dev_ms[0].num_data]
                if self.average_output:
                    raw_dev = raw_dev / max(self.iter_, 1)
                prob_dev = (self.objective.convert_output(raw_dev)
                            if self.objective is not None else raw_dev)
                for m in dev_ms:
                    out.append(_eval_dispatch(ds_name, m, prob_dev))
            if host_ms:
                prob, raw = self._converted_scores(score, n_real)
                for m in host_ms:
                    out.append([(ds_name, name, v, hb)
                                for name, v, hb in m.eval(prob, raw)])
        span.set(rows=rows)
        pending = [p.value for p in out if isinstance(p, _PendingEval)]
        if pending:
            span.wait(pending)
            pulled = iter(jax.device_get(pending))
            out = [[(p.dataset, name, v, hb) for name, v, hb
                    in p.metric.device_results(next(pulled))]
                   if isinstance(p, _PendingEval) else p for p in out]
        return [r for results in out for r in results]

    def _converted_scores(self, score, n_real: Optional[int] = None):
        k = self.num_tree_per_iteration
        raw = score if k > 1 else score[0]
        if n_real is not None and raw.shape[-1] != n_real:
            raw = raw[..., :n_real]
        if self.average_output:
            raw = raw / max(self.iter_, 1)
        conv = (self.objective.convert_output(raw)
                if self.objective is not None else raw)
        return np.asarray(conv, np.float64), np.asarray(raw, np.float64)

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        return self.iter_

    def rollback_one_iter(self) -> None:
        """Reference RollbackOneIter: drop the latest iteration's trees and
        subtract their contribution from all scores (finalized leaf values
        already include shrinkage, so the replay scale is -1)."""
        self._flush_pending()
        # dropping an iteration invalidates a stall verdict: the sync path
        # re-evaluates every iteration, so resuming must be possible
        self._stalled = False
        self._nl_pending = []
        self._nl_expected.clear()
        self._nl_seen.clear()
        if self.iter_ <= 0:
            return
        # un-pulled trees live in the comb alone, which this drops
        self._sync_train_score()
        k = self.num_tree_per_iteration
        for kidx in reversed(range(k)):
            if not self.models:
                break
            self.models.pop()
            dt = self._device_trees.pop()
            linp = (self._device_linear.pop()
                    if self._device_linear else None)

            def _undo(score, bins, raw):
                if linp is not None:
                    from .linear import linear_leaf_output
                    const_d, coef_d, fi_d, lv_d = linp
                    leaf = predict_leaf_bins(dt, bins, self.dd.num_bins,
                                             self.dd.has_nan,
                                             feat_map=self._fmap)
                    return score - linear_leaf_output(leaf, raw, const_d,
                                                      coef_d, fi_d, lv_d)
                return add_tree_score(score, dt, bins, self.dd.num_bins,
                                      self.dd.has_nan, -1.0,
                                      feat_map=self._fmap)

            self.train_score = self.train_score.at[kidx].set(
                _undo(self.train_score[kidx], self.dd.bins, self._raw_dev))
            for vs in self.valid_sets:
                vs.score = vs.score.at[kidx].set(
                    _undo(vs.score[kidx], vs.bins, vs.raw))
        self.iter_ -= 1
        if self._stream_grad:
            # the comb's score column still includes the dropped tree;
            # rebuild it from the rolled-back scores at the next call
            self.grow.reset_stream()
