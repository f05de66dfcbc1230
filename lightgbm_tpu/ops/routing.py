"""Declarative fast-path routing model (ISSUE 10).

The trainer's value lives in its fast paths — physical partition mode
(~25x the row_order path at 1M rows, round-2 table), score-resident
gradient streaming on top of it, and the mesh reduce-scatter
histogram merge.  Until this module, the predicates that select those
paths lived as inline boolean soup in ``models/gbdt.py`` (``use_phys``
/ ``use_stream``) and ``ops/grow.py`` (``hist_scatter_eligible``):
neither the static analyzer nor CI could see them, so a config that
silently fell to the 0.04x row_order path was only discoverable by
benchmarking it on a chip.

This module is the single source of truth both sides consume:

* the RUNTIME (``GBDT._setup_training``) builds a :class:`RouteInputs`
  snapshot of its config/dataset/env facts and calls :func:`decide`;
  the returned :class:`RouteDecision` names the engaged path AND the
  named rule behind every fast-path loss (``report_fallbacks`` turns
  the config-caused ones into obs events + warn-once log lines);
* the ANALYZER (``analysis/passes/routing.py``) enumerates the
  config x env-knob x shape lattice with :func:`enumerate_matrix` and
  audits the checked-in golden matrix
  (``lightgbm_tpu/analysis/routing_matrix.json``, schema
  ``lightgbm_tpu/routing/v1``) against a fresh enumeration — a silent
  routing change or an unjustified fast-path loss is a lint finding
  on CPU, not a chip-run surprise.

Because both consume the same :data:`RULES` table, a runtime fallback
warning and a static finding can never disagree about WHY a config
lost its fast path.

The PREDICT side (ISSUE 14) follows the same shape:
:data:`PREDICT_RULES` / :func:`predict_decide` choose between the
compiled serving engine (``lightgbm_tpu/serve``) and the host
reference walk for ``Booster.predict``; the golden matrix carries the
predict-side lattice as ``predict_cells`` and
:func:`report_predict_fallbacks` makes the config-caused host
fallbacks loud (``routing_fallback_predict_*`` events).

Regenerate the golden matrix after changing any rule:

    python -m lightgbm_tpu.ops.routing
"""
from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

ROUTING_SCHEMA = "lightgbm_tpu/routing/v1"

# the one-number headline the bench-priority ranking prices fallbacks
# with: the round-2 table's physical-vs-row_order throughput ratio
ROW_ORDER_SLOWDOWN_X = 25.0


# ---------------------------------------------------------------------
# inputs: every fact the routing predicates read, in one flat record
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class RouteInputs:
    """One cell of the config x env-knob x shape lattice.

    ``learner`` is the ENGAGED learner ("serial" when a mesh learner
    was requested but only one device exists).  Shape facts arrive as
    booleans (``rows_over_limit``, ``efb_overwide``) so a runtime
    snapshot and a lattice cell share one key space; ``fused_ok``
    (``fused_split.fused_supported`` over the actual geometry) is
    runtime-only and deliberately NOT part of :meth:`key`."""

    # engaged learner / mesh
    learner: str = "serial"            # serial | data | feature | voting
    n_shards: int = 1
    backend: str = "tpu"               # jax.default_backend()
    # dataset / shape facts
    efb_bundled: bool = False          # EFB produced bundled columns
    bins_u8: bool = True               # bin matrix fits uint8
    rows_over_limit: bool = False      # per-shard n_pad >= 2^24 - slack
    efb_comb: bool = False             # the bundles stay IN the comb
                                       # (grow.bundled_comb_eligible:
                                       # serial learner, plain finder;
                                       # only meaningful with
                                       # efb_bundled)
    efb_overwide: bool = False         # the comb's f_pad + extras >
                                       # layout.MAX_COMB_COLS, at the
                                       # width the engaged EFB form
                                       # allocates: bundle columns
                                       # under efb_comb, the UNBUNDLED
                                       # logical width otherwise (only
                                       # meaningful with efb_bundled)
    comb_overwide: bool = False        # a comb of no EFB bundles whose
                                       # line no kernel stages:
                                       # f_pad + extras past
                                       # layout.MAX_COMB_COLS, or a
                                       # scan block over its price at
                                       # the engaged scheme
    fused_ok: bool = True              # fused_supported(f_pad, B, C)
    f_log_shard_divisible: bool = True
    over_budget: bool = False          # grow_footprint peak exceeds
                                       # the HBM budget (ISSUE 15: the
                                       # fact that engages paging)
    # config facts
    gpu_use_dp: bool = False
    cegb_lazy: bool = False
    cat_subset: bool = False           # hp.use_cat_subset
    bagging: bool = False
    linear_tree: bool = False
    boosting: str = "gbdt"             # gbdt | dart | goss | rf
    objective_kind: str = "l2"         # binary | l2 | other | none
    multi_tree: bool = False           # num_tree_per_iteration != 1
    forced_splits: bool = False
    mono_intermediate: bool = False    # hp.use_monotone and intermediate
    cegb_coupled: bool = False
    # env-knob snapshot (normalized; see env_snapshot)
    phys_env: str = "auto"             # auto | 0 | interpret
    stream_env: str = "auto"           # auto | 0
    paged_env: str = "auto"            # auto | 0 | 1 (LGBM_TPU_PAGED)
    partition_env: str = "permute"     # permute | matmul
    fused_env: bool = True
    hist_scatter_env: bool = True
    mc_batch_env: str = "auto"         # auto | 0 | 1 (LGBM_TPU_MC_BATCH)

    def key(self) -> str:
        """Stable lattice-cell key (matrix row id).  ``fused_ok`` is
        excluded: it is a pure geometry fact that only modulates the
        ``fused`` flag, and the matrix enumerates the supported case."""
        b = lambda v: "1" if v else "0"  # noqa: E731
        return (
            f"learner={self.learner};shards={self.n_shards};"
            f"be={self.backend};"
            f"efb={b(self.efb_bundled)};ebc={b(self.efb_comb)};"
            f"u8={b(self.bins_u8)};"
            f"over={b(self.rows_over_limit)};"
            f"ew={b(self.efb_overwide)};"
            f"fdiv={b(self.f_log_shard_divisible)};"
            f"dp={b(self.gpu_use_dp)};cegb={b(self.cegb_lazy)};"
            f"cat={b(self.cat_subset)};bag={b(self.bagging)};"
            f"lin={b(self.linear_tree)};boost={self.boosting};"
            f"obj={self.objective_kind};"
            f"k={'multi' if self.multi_tree else '1'};"
            f"forced={b(self.forced_splits)};"
            f"mono={b(self.mono_intermediate)};"
            f"cegbc={b(self.cegb_coupled)};"
            f"phys={self.phys_env};stream={self.stream_env};"
            f"part={self.partition_env};fused={b(self.fused_env)};"
            f"scat={b(self.hist_scatter_env)};"
            f"ob={b(self.over_budget)};pg={self.paged_env};"
            f"mcb={self.mc_batch_env}"
            # only where it holds: every other cell keeps the key it had
            + (";cw=1" if self.comb_overwide else ""))


# ---------------------------------------------------------------------
# rules: named predicates with the responsible knob + a reason string.
# ``blocks`` names the path a firing rule takes away; ``loud`` marks
# the config-caused row_order fallbacks the ISSUE-10 satellite makes
# structured (obs event + warn-once log via report_fallbacks).
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class Rule:
    name: str
    blocks: str                  # physical | stream | paged |
                                 # mc_batch | hist_scatter
    knob: str                    # config field or LGBM_TPU_* env knob
    reason: str
    pred: Callable[[RouteInputs], bool] = field(repr=False, default=None)
    loud: bool = False


RULES: Tuple[Rule, ...] = (
    # -- physical partition eligibility (gbdt use_phys) ----------------
    # efb_bundle is GONE (ISSUE 12): EFB no longer costs the fast
    # path.  A bundled table rides it in one of two comb forms, named
    # by RouteDecision.efb: "bundled" - one comb column a bundle, the
    # finder in bundle space, the partition through a membership set
    # (ISSUE 36; the serial learner with the plain finder,
    # grow.bundled_comb_eligible) - or "unbundled" - the bundles
    # expanded into logical bin columns at comb ingest
    # (device_data.unbundle_bins; the mesh learners and the grow
    # options the bundle-space finder does not cover).  What remains is
    # the narrow shape fact below, priced at the width the engaged form
    # allocates: a comb past the column budget.
    Rule("efb_overwide", "physical", "enable_bundle",
         "the comb of this EFB-bundled table (its bundle columns, or "
         "its logical columns where the bundles unbundle at ingest) "
         "would pass the lane/VMEM column budget "
         "(layout.MAX_COMB_COLS); blocks that wide cannot stage "
         "through VMEM",
         lambda i: i.efb_bundled and i.efb_overwide, loud=True),
    # every comb kernel takes its block from its width, up to
    # sixteen planes; a dense line past that (or a scan scheme whose
    # block is over its price there) cannot be staged at all
    Rule("comb_overwide", "physical", "num_features",
         "the comb line of this table (its bin columns + the value / "
         "row-id / stream columns) is wider than the kernels stage "
         "through VMEM (layout.MAX_COMB_COLS, 16 planes; the scan's "
         "block at the engaged scheme over its price)",
         lambda i: i.comb_overwide, loud=True),
    # cat_subset is GONE (ISSUE 16): sorted-subset categorical splits
    # ride the fast path — membership ships as a bin-indexed bitset of
    # ceil(padded_bins/32) i32 words appended to the SMEM split
    # descriptor (partition_kernel.SEL_MEMBER), decoded in-kernel by
    # every partition/fused scheme.  What remains is the narrow shape
    # fact below: a bin width past the bitset word budget.
    Rule("cat_overwide", "physical", "max_bin",
         "the categorical membership bitset would exceed the "
         "8-word/256-bin SMEM descriptor budget "
         "(layout.CAT_BITSET_WORDS); sorted-subset splits over wider "
         "bins keep the row_order path",
         lambda i: i.cat_subset and not i.bins_u8, loud=True),
    Rule("non_u8_bins", "physical", "max_bin",
         "bins are wider than uint8 (max_bin > 256); the partition "
         "kernel's bf16 extract matmuls would round bin ids",
         lambda i: not i.bins_u8, loud=True),
    Rule("n_pad_overflow", "physical", "tree_learner",
         "padded rows exceed the 2^24 f32-exact row-id limit; shard "
         "over a mesh (tree_learner=data) to restore the fast path",
         lambda i: i.rows_over_limit, loud=True),
    Rule("gpu_use_dp", "physical", "gpu_use_dp",
         "double-precision histograms disable the f32 comb-direct "
         "histogram kernel",
         lambda i: i.gpu_use_dp, loud=True),
    Rule("cegb_lazy", "physical", "cegb_penalty_feature_lazy",
         "the per-(feature,row) paid mask is not plumbed through the "
         "partition kernel",
         lambda i: i.cegb_lazy, loud=True),
    Rule("learner_row_order", "physical", "tree_learner",
         "the feature/voting-parallel learners run the XLA row_order "
         "path per shard",
         lambda i: i.learner in ("feature", "voting")),
    Rule("phys_env_off", "physical", "LGBM_TPU_PHYS",
         "physical partition mode disabled by LGBM_TPU_PHYS=0",
         lambda i: i.phys_env == "0"),
    Rule("backend_not_tpu", "physical", "LGBM_TPU_PHYS",
         "no TPU backend (LGBM_TPU_PHYS=interpret forces the off-TPU "
         "reference path)",
         lambda i: (i.phys_env not in ("0", "interpret")
                    and i.backend != "tpu")),
    # -- score-resident streaming eligibility (gbdt use_stream) --------
    Rule("stream_env_off", "stream", "LGBM_TPU_STREAM",
         "score-resident streaming disabled by LGBM_TPU_STREAM=0",
         lambda i: i.stream_env == "0"),
    Rule("objective_not_streamable", "stream", "objective",
         "the streaming refresh kernel knows binary and l2 gradient "
         "formulas only",
         lambda i: i.objective_kind not in ("binary", "l2")),
    Rule("boosting_not_gbdt", "stream", "boosting",
         "DART/GOSS/RF mutate scores or sample weights behind the row "
         "matrix's back",
         lambda i: i.boosting != "gbdt"),
    Rule("multi_tree_iter", "stream", "num_class",
         "K trees per iteration share one score matrix; the in-matrix "
         "score is not the whole story",
         lambda i: i.multi_tree),
    Rule("bagging_on", "stream", "bagging_freq",
         "bagging weights are not representable in the streamed score "
         "columns",
         lambda i: i.bagging),
    Rule("linear_tree", "stream", "linear_tree",
         "per-leaf linear refits rewrite scores outside the kernel",
         lambda i: i.linear_tree),
    Rule("mesh_stream_unwired", "stream", "tree_learner",
         "score-resident streaming is serial-only (mesh scores are "
         "booster-held)",
         lambda i: i.learner != "serial"),
    # -- paged comb for larger-than-HBM shapes (ISSUE 15) --------------
    Rule("paged_env_off", "paged", "LGBM_TPU_PAGED",
         "paged comb disabled by LGBM_TPU_PAGED=0; an over-budget "
         "shape then trains fully resident (OOM on chip)",
         lambda i: i.paged_env == "0"),
    Rule("paged_mesh_unwired", "paged", "tree_learner",
         "the paged comb is serial-only today (the mesh growers carry "
         "their comb as shard_map-sharded global arrays, not host "
         "pages); shard the rows instead, or compose with ROADMAP "
         "item 3 for sharded out-of-core training",
         lambda i: i.learner != "serial", loud=True),
    # -- batched multiclass grow (ISSUE 19) ----------------------------
    Rule("mc_batch_env_off", "mc_batch", "LGBM_TPU_MC_BATCH",
         "batched multiclass grow disabled by LGBM_TPU_MC_BATCH=0; "
         "the K class trees train as K serial grow dispatches per "
         "iteration",
         lambda i: i.mc_batch_env == "0"),
    Rule("mc_batch_paged", "mc_batch", "LGBM_TPU_PAGED",
         "the paged comb re-assembles its host-page window around "
         "every grow dispatch; a batched K-scan would pin the window "
         "across all K class trees and defeat the page sweep's "
         "DMA/compute overlap, so paged multiclass trains serial-K",
         lambda i: i.paged_env == "1" or i.over_budget, loud=True),
    # -- data-parallel reduce-scatter merge (hist_scatter_eligible) ----
    Rule("hist_scatter_env_off", "hist_scatter", "LGBM_TPU_HIST_SCATTER",
         "reduce-scatter histogram merge disabled by "
         "LGBM_TPU_HIST_SCATTER=0",
         lambda i: not i.hist_scatter_env),
    Rule("scatter_efb", "hist_scatter", "enable_bundle",
         "the reduce-scatter merge's per-shard feature ownership is "
         "not yet wired for bundled datasets (the unbundled ingest "
         "pads logical features at a different granularity); the "
         "merge stays full-psum",
         lambda i: i.efb_bundled),
    # scatter_cat_subset is GONE (ISSUE 16): the winner's pooled
    # histogram row is recovered from its owner shard by one
    # owner-masked [2, B] psum per split (grow.py member_f build), so
    # cat-subset membership no longer needs the full merged histogram
    Rule("scatter_forced", "hist_scatter", "forcedsplits_filename",
         "forced-split sums need the full merged histogram",
         lambda i: i.forced_splits),
    Rule("scatter_cegb_coupled", "hist_scatter",
         "cegb_penalty_feature_coupled",
         "per-feature coupled penalties track global feature ids",
         lambda i: i.cegb_coupled),
    Rule("scatter_mono_intermediate", "hist_scatter",
         "monotone_constraints_method",
         "the intermediate monotone walk recomputes bests from the "
         "full histogram pool",
         lambda i: i.mono_intermediate),
    Rule("scatter_f_log_indivisible", "hist_scatter", "tree_learner",
         "f_log % n_shards != 0 "
         "(device_data.pad_features_to_shards restores it)",
         lambda i: not i.f_log_shard_divisible),
)

RULE_BY_NAME: Dict[str, Rule] = {r.name: r for r in RULES}

# contextual reason names decide() emits without a predicate row
_VOTING_ELECTION = "voting_election"
_PAGED_REQUIRES_PHYSICAL = "paged_requires_physical"
_MC_BATCH_REQUIRES_PHYSICAL = "mc_batch_requires_physical"

# non-stream physical comb extras: g*w, h*w, w value columns + 3
# row-id byte columns.  Shared with ops/grow.py's layout sizing so the
# model's column count and the grower's can never disagree (stream
# layouts get their count from stream_grad.stream_columns).
NON_STREAM_EXTRA_COLS = 6


# ---------------------------------------------------------------------
# decision
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class RouteDecision:
    """The engaged path plus the named rule behind every loss."""
    path: str                   # stream | physical | row_order
    scheme: str                 # permute | matmul | none
    fused: bool
    learner: str
    n_shards: int
    hist_merge: str             # scatter | psum | none
    reasons: Tuple[str, ...]        # why not the next-faster path
    merge_reasons: Tuple[str, ...]  # why the mesh merge is psum
    program_key: str
    cell: str                   # the RouteInputs.key() this decided
    paged: bool = False         # paged comb engaged (ISSUE 15)
    paged_reasons: Tuple[str, ...] = ()  # why a wanted paging fell off
    mc_batched: bool = False    # batched multiclass grow (ISSUE 19)
    mc_batch_reasons: Tuple[str, ...] = ()  # why multiclass is serial-K
    # the engaged EFB form (ISSUE 36): none (no bundle found) | bundled
    # (bundle columns in the comb) | unbundled (logical columns in the
    # comb, expanded at ingest) | expand (row_order: bundled bin matrix,
    # histograms expanded by grow.expand)
    efb: str = "none"
    # logical comb rows a 128-lane line: a constant, not a field, since
    # the two-rows-a-line layout went (ISSUE 32).  Kept for its readers
    # - benchmarks/kinds/train.py check_route against expect_route
    # {"pack": 1}, chip_smoke.py, and digest() of saved checkpoints -
    # until a benchmark issue takes it out of expect_route (ROADMAP C11)
    pack = 1

    def digest(self) -> str:
        """12-hex identity of the ENGAGED path (not the reasons): two
        bench records whose digests differ trained different paths and
        are incomparable (obs diff / tools/perf_gate.py exit 2)."""
        ident = {
            "path": self.path, "pack": self.pack, "scheme": self.scheme,
            "fused": self.fused, "learner": self.learner,
            "n_shards": self.n_shards, "hist_merge": self.hist_merge,
            "paged": self.paged,
        }
        if self.efb != "none":
            # the EFB form is part of the path of a bundled table; a
            # table without bundles keeps the digest it always had
            # (saved checkpoints carry it and refuse another)
            ident["efb"] = self.efb
        return hashlib.sha256(
            json.dumps(ident, sort_keys=True).encode()).hexdigest()[:12]

    def to_json(self) -> dict:
        return {
            "schema": ROUTING_SCHEMA,
            "path": self.path, "pack": self.pack, "scheme": self.scheme,
            "fused": self.fused, "learner": self.learner,
            "n_shards": self.n_shards, "hist_merge": self.hist_merge,
            "paged": self.paged,
            "mc_batched": self.mc_batched,
            "efb": self.efb,
            "reasons": list(self.reasons),
            "merge_reasons": list(self.merge_reasons),
            "paged_reasons": list(self.paged_reasons),
            "mc_batch_reasons": list(self.mc_batch_reasons),
            "program_key": self.program_key,
            "cell": self.cell,
            "digest": self.digest(),
        }


def decide(i: RouteInputs) -> RouteDecision:
    """Evaluate the rule table over one lattice cell.  Pure and
    jax-free: the analyzer enumerates thousands of cells with nothing
    executing."""
    phys_block = [r for r in RULES
                  if r.blocks == "physical" and r.pred(i)]
    use_phys = not phys_block
    stream_block: List[Rule] = []
    if use_phys:
        stream_block = [r for r in RULES
                        if r.blocks == "stream" and r.pred(i)]
    use_stream = use_phys and not stream_block
    path = ("stream" if use_stream
            else "physical" if use_phys else "row_order")

    scheme = i.partition_env if use_phys else "none"
    fused = bool(use_phys and i.fused_env and i.fused_ok)

    # paged comb (ISSUE 15): wanted when the footprint model says the
    # shape cannot sit fully resident (over_budget, the auto default)
    # or when LGBM_TPU_PAGED=1 forces it; engages only on the
    # physical/stream path (the row_order path never holds the comb)
    paged, paged_reasons = False, []
    # an over-budget shape WANTS paging even under LGBM_TPU_PAGED=0 —
    # the paged_env_off rule then records why it trains resident
    want_paged = i.paged_env == "1" or i.over_budget
    if want_paged:
        if not use_phys:
            paged_reasons = [_PAGED_REQUIRES_PHYSICAL]
        else:
            paged_block = [r for r in RULES
                           if r.blocks == "paged" and r.pred(i)]
            paged_reasons = [r.name for r in paged_block]
            paged = not paged_block

    # batched multiclass grow (ISSUE 19): wanted whenever the iteration
    # trains K > 1 class trees; engages only on the physical path (the
    # stream path already blocks multi_tree via multi_tree_iter, and
    # the row_order grow has no carried comb to scan over).  A
    # multiclass physical cell that stays serial-K MUST carry a named
    # reason — the analyzer's ROUTING_UNJUSTIFIED_FALLBACK audit
    # enforces it over the golden matrix.
    mc_batched, mc_batch_reasons = False, []
    if i.multi_tree:
        if path != "physical":
            mc_batch_reasons = [_MC_BATCH_REQUIRES_PHYSICAL]
        else:
            mc_block = [r for r in RULES
                        if r.blocks == "mc_batch" and r.pred(i)]
            mc_batch_reasons = [r.name for r in mc_block]
            mc_batched = not mc_block

    if i.learner == "data" and i.n_shards > 1:
        merge_block = [r for r in RULES
                       if r.blocks == "hist_scatter" and r.pred(i)]
        hist_merge = "psum" if merge_block else "scatter"
        merge_reasons = [r.name for r in merge_block]
    elif i.learner == "voting":
        # PV-tree election merges the bounded top-k payload via psum
        hist_merge, merge_reasons = "psum", [_VOTING_ELECTION]
    else:
        hist_merge, merge_reasons = "none", []

    efb = ("none" if not i.efb_bundled
           else "expand" if not use_phys
           else "bundled" if i.efb_comb else "unbundled")
    reasons = [r.name for r in
               (phys_block if not use_phys else stream_block)]
    program_key = "|".join([
        path, scheme, f"fused{int(fused)}",
        i.learner, f"shards{i.n_shards}", hist_merge,
        f"dp{int(i.gpu_use_dp)}", f"cegb{int(i.cegb_lazy)}",
        f"cat{int(i.cat_subset)}", f"efb-{efb}",
        f"u8{int(i.bins_u8)}", f"paged{int(paged)}",
        f"mcb{int(mc_batched)}"])
    return RouteDecision(
        path=path, scheme=scheme, fused=fused,
        learner=i.learner, n_shards=i.n_shards, hist_merge=hist_merge,
        reasons=tuple(reasons),
        merge_reasons=tuple(merge_reasons), program_key=program_key,
        cell=i.key(), paged=paged, paged_reasons=tuple(paged_reasons),
        mc_batched=mc_batched,
        mc_batch_reasons=tuple(mc_batch_reasons), efb=efb)


# ---------------------------------------------------------------------
# runtime glue
# ---------------------------------------------------------------------
def objective_kind(objective) -> str:
    """The streaming-kernel gradient class of an objective instance."""
    if objective is None:
        return "none"
    return {"binary": "binary",
            "regression": "l2"}.get(objective.NAME, "other")


def env_snapshot() -> Dict[str, object]:
    """Normalized env-knob fields for :class:`RouteInputs`.

    ``LGBM_TPU_PARTITION`` / ``LGBM_TPU_FUSED`` are read from
    ``ops.grow``'s import-time constants (what the kernels actually
    baked), the call-time knobs through
    ``config.env_knob`` (the documented ENV_KNOBS read — the ISSUE-10
    satellite that retired the inline ``os.environ`` soup in
    ``gbdt.py``)."""
    from ..config import env_knob
    from . import grow as grow_mod
    phys = env_knob("LGBM_TPU_PHYS")
    if phys not in ("0", "interpret"):
        phys = "auto"
    stream = "0" if env_knob("LGBM_TPU_STREAM") == "0" else "auto"
    paged = env_knob("LGBM_TPU_PAGED")
    if paged not in ("0", "1"):
        paged = "auto"
    mcb = env_knob("LGBM_TPU_MC_BATCH")
    if mcb not in ("0", "1"):
        mcb = "auto"
    return dict(
        phys_env=phys,
        stream_env=stream,
        paged_env=paged,
        mc_batch_env=mcb,
        partition_env=grow_mod.PARTITION_IMPL,
        fused_env=grow_mod.FUSED_IMPL != "0",
        hist_scatter_env=env_knob("LGBM_TPU_HIST_SCATTER") != "0",
    )


def comb_stageable(C: int, scheme: str = "permute") -> bool:
    """Whether every comb kernel stages a line of ``C`` lanes: the
    line within ``layout.MAX_COMB_COLS`` and the scan's block at
    ``scheme`` within its price (the copy-back, histogram and stream
    kernels size theirs from the width down to a few rows).  The shape
    fact behind ``comb_overwide``."""
    from .pallas.layout import SCOPED_VMEM_LIMIT, comb_cols_fit
    from .pallas.partition_kernel2 import scan_block_rows, scan_vmem_bytes
    return comb_cols_fit(C) and scan_vmem_bytes(
        scan_block_rows(C, scheme=scheme), C) <= SCOPED_VMEM_LIMIT


def resolve_layout(i: RouteInputs, *, f_pad: int,
                   padded_bins: int, rows: int = None,
                   num_leaves: int = 0,
                   num_class: int = 1) -> RouteInputs:
    """Fill the geometry-derived fields (``efb_overwide``,
    ``fused_ok`` — and, when ``rows`` is given,
    ``over_budget``, the ISSUE-15 paging fact) from the final device
    layout.  ``f_pad`` / ``padded_bins`` are the widths the physical
    path's comb and kernels are built at (``DeviceDataset.phys_f_pad``
    / ``phys_padded_bins``): under EFB the bundle columns where
    ``efb_comb`` keeps them, the unbundled logical geometry otherwise.
    The stream decision feeds the column count (streaming layouts
    carry extra objective columns), so this runs a provisional
    :func:`decide` first — the geometry never feeds back into the
    stream decision, so one round fixes the point.  ``over_budget`` is
    then priced over the decision RE-RUN with the resolved geometry
    fields: pricing it at the provisional decision (fused_ok still
    defaults) would disagree with the engaged fused footprint by
    exactly the fused-root-carry bytes, and a limit landing in that
    band would make routing promise a paging the planner then
    refuses."""
    d0 = decide(i)
    if d0.path == "stream":
        from .pallas.stream_grad import stream_columns
        n_extra = stream_columns(i.objective_kind)
    else:
        n_extra = NON_STREAM_EXTRA_COLS
    from .pallas.fused_split import fused_supported
    from .pallas.layout import comb_cols_fit, comb_layout
    # the width the grower builds its comb at (ops/grow.py: _C_PHYS)
    C = comb_layout(int(f_pad) + n_extra)
    resolved = replace(
        i, efb_overwide=bool(i.efb_bundled
                          and not comb_cols_fit(f_pad + n_extra)),
        comb_overwide=bool(not i.efb_bundled
                           and not comb_stageable(C, i.partition_env)),
        fused_ok=bool(fused_supported(int(f_pad), int(padded_bins), C)))
    if rows is None:
        return resolved
    d1 = decide(resolved)
    if d1.path not in ("physical", "stream"):
        return resolved
    from ..obs.costmodel import grow_footprint, hbm_limit_bytes
    fp = grow_footprint(
        rows=int(rows), f_pad=int(f_pad),
        padded_bins=int(padded_bins),
        num_leaves=max(int(num_leaves), 2),
        stream=d1.path == "stream",
        fused=d1.fused,
        stream_kind=(i.objective_kind
                     if i.objective_kind in ("binary", "l2")
                     else "l2"),
        n_shards=max(int(i.n_shards), 1),
        # ISSUE 19: K multiplies the gradient/score/tree-array terms
        # (and, batched, the stacked grow outputs) — the over_budget
        # fact must price the multiclass footprint or paging engages
        # K-fold too late
        num_class=max(int(num_class), 1),
        mc_batched=d1.mc_batched)
    return replace(resolved, over_budget=bool(
        fp["peak_bytes"] > hbm_limit_bytes()))


# ---------------------------------------------------------------------
# predict-side routing (ISSUE 14): compiled-serve vs host-walk rules
# ---------------------------------------------------------------------
@dataclass(frozen=True)
class PredictInputs:
    """One cell of the predict-side lattice: the facts that decide
    whether ``Booster.predict`` routes through the compiled serving
    engine (``lightgbm_tpu/serve``) or the host reference walk."""

    backend: str = "tpu"          # jax.default_backend()
    serve_env: str = "auto"       # auto | 1 | 0 (LGBM_TPU_SERVE)
    loaded_model: bool = False    # model from text: quantizer derived
                                  # from the trees (ISSUE 18)
    rebinned_model: bool = False  # init_model trees: approx thresholds
    linear_tree: bool = False
    pred_contrib: bool = False
    pred_leaf: bool = False
    pred_early_stop: bool = False
    # ISSUE 18: the serve_kernel dimension — whether a compiled-path
    # predict dispatches through the VMEM-resident Pallas traversal
    # kernel or the XLA gather walk
    serve_kernel_env: str = "auto"  # auto | 1 | 0 | interpret
                                    # (LGBM_TPU_SERVE_KERNEL /
                                    #  LGBM_TPU_SERVE_INTERP=kernel)
    forest_overwide: bool = False   # stacked forest exceeds the VMEM
                                    # scratch cap (layout.serve_forest_fit)

    def key(self) -> str:
        b = lambda v: "1" if v else "0"  # noqa: E731
        return (f"predict:be={self.backend};serve={self.serve_env};"
                f"loaded={b(self.loaded_model)};"
                f"reb={b(self.rebinned_model)};"
                f"lin={b(self.linear_tree)};"
                f"contrib={b(self.pred_contrib)};"
                f"leaf={b(self.pred_leaf)};"
                f"es={b(self.pred_early_stop)};"
                f"kern={self.serve_kernel_env};"
                f"ow={b(self.forest_overwide)}")


PREDICT_RULES: Tuple[Rule, ...] = (
    Rule("serve_env_off", "serve", "LGBM_TPU_SERVE",
         "compiled serving disabled by LGBM_TPU_SERVE=0",
         lambda i: i.serve_env == "0"),
    Rule("serve_backend_auto", "serve", "LGBM_TPU_SERVE",
         "LGBM_TPU_SERVE=auto compiles the serving engine on the TPU "
         "backend only; set LGBM_TPU_SERVE=1 to compile it here too",
         lambda i: i.serve_env == "auto" and i.backend != "tpu"),
    Rule("predict_contrib", "serve", "predict_contrib",
         "SHAP contributions walk per-node cover statistics the "
         "stacked forest arrays do not carry",
         lambda i: i.pred_contrib, loud=True),
    Rule("predict_leaf_index", "serve", "predict_leaf_index",
         "pred_leaf output stays on the host walk (the compiled "
         "engine's leaf path is diagnostics-only, "
         "ServingEngine.predict_leaves)",
         lambda i: i.pred_leaf, loud=True),
    Rule("predict_early_stop", "serve", "pred_early_stop",
         "margin-based prediction early stopping makes the tree count "
         "data-dependent; the fixed-shape bucketed programs sum every "
         "tree",
         lambda i: i.pred_early_stop, loud=True),
    # predict_loaded_model RETIRED (ISSUE 18 / ROADMAP 2d): the
    # serving stack now derives an exact bin-space quantizer from the
    # trees' own f32-floored thresholds, so text-loaded boosters serve
    # compiled.  The loaded_model fact stays in the cell key so the
    # graduation is visible in the golden matrix diff.
    Rule("predict_rebinned_model", "serve", "input_model",
         "continued-training (init_model) trees carry rebinned "
         "bin-space thresholds that only APPROXIMATE their raw "
         "thresholds against the new dataset's bins; the host walk "
         "compares raw values exactly",
         lambda i: i.rebinned_model, loud=True),
    Rule("predict_linear_tree", "serve", "linear_tree",
         "per-leaf linear models read raw feature vectors at the "
         "leaves, outside the stacked node arrays",
         lambda i: i.linear_tree, loud=True),
    # -- serve_kernel block (ISSUE 18): whether a COMPILED predict
    # dispatches through the VMEM-resident Pallas traversal kernel or
    # the XLA gather walk.  These rules never route host — they pick
    # the program behind the compiled path.
    Rule("serve_kernel_env_off", "serve_kernel",
         "LGBM_TPU_SERVE_KERNEL",
         "the Pallas serving kernel is disabled by "
         "LGBM_TPU_SERVE_KERNEL=0; the compiled path runs the XLA "
         "gather walk",
         lambda i: i.serve_kernel_env == "0"),
    Rule("serve_kernel_backend_auto", "serve_kernel",
         "LGBM_TPU_SERVE_KERNEL",
         "LGBM_TPU_SERVE_KERNEL=auto runs the XLA gather walk on every "
         "backend: the v5e compiler refuses the Pallas traversal kernel "
         "on jax 0.9.0 (NotImplementedError: Only 2D gather is "
         "supported; PR 22).  Only LGBM_TPU_SERVE_KERNEL=1 asks for the "
         "kernel, on the TPU backend alone, where it fails with the "
         "compiler's own error until the kernel is rewritten or deleted "
         "(LGBM_TPU_SERVE_INTERP=kernel engages the interpreter-mode "
         "kernel anywhere for parity tests)",
         lambda i: (i.serve_kernel_env == "auto"
                    or (i.serve_kernel_env == "1"
                        and i.backend != "tpu"))),
    Rule("serve_forest_overwide", "serve_kernel", "num_iterations",
         "the stacked forest exceeds the kernel's VMEM scratch cap "
         "(layout.serve_forest_fit); the compiled path runs the XLA "
         "gather walk, which streams nodes from HBM per level",
         lambda i: (i.forest_overwide
                    and i.serve_kernel_env != "0"), loud=True),
)

PREDICT_RULE_BY_NAME: Dict[str, Rule] = {r.name: r for r in PREDICT_RULES}


@dataclass(frozen=True)
class PredictDecision:
    """compiled-serve vs host-walk, with the named rule behind every
    host fallback (the predict analog of :class:`RouteDecision`)."""
    path: str                    # compiled | host
    reasons: Tuple[str, ...]
    serve_requested: bool        # LGBM_TPU_SERVE=1 (explicit)
    cell: str
    # ISSUE 18: which program the compiled path runs — True when the
    # VMEM-resident Pallas traversal kernel is engaged, False when the
    # XLA gather walk serves (host-path cells are always False)
    kernel: bool = False
    kernel_reasons: Tuple[str, ...] = ()
    kernel_requested: bool = False  # LGBM_TPU_SERVE_KERNEL=1 (explicit)


def predict_env_snapshot() -> str:
    """Normalized ``LGBM_TPU_SERVE`` value: auto | 1 | 0."""
    from ..config import env_knob
    v = env_knob("LGBM_TPU_SERVE")
    if v in ("0", "1"):
        return v
    return "auto"


def predict_kernel_env_snapshot() -> str:
    """Normalized serve-kernel knob: ``LGBM_TPU_SERVE_INTERP=kernel``
    wins (the parity seam runs the real kernel through the Pallas
    interpreter on any backend), else ``LGBM_TPU_SERVE_KERNEL``
    normalized to auto | 1 | 0."""
    from ..config import env_knob
    if env_knob("LGBM_TPU_SERVE_INTERP") == "kernel":
        return "interpret"
    v = env_knob("LGBM_TPU_SERVE_KERNEL")
    if v in ("0", "1"):
        return v
    return "auto"


def predict_decide(i: PredictInputs) -> PredictDecision:
    """Evaluate the predict rule table over one cell (pure, jax-free —
    the matrix enumerates it like the training lattice).  The serve
    block decides compiled vs host; the serve_kernel block then picks
    the compiled path's program (Pallas traversal kernel vs XLA gather
    walk) — a kernel rule never routes host."""
    block = [r for r in PREDICT_RULES
             if r.blocks == "serve" and r.pred(i)]
    kblock = [r for r in PREDICT_RULES
              if r.blocks == "serve_kernel" and r.pred(i)]
    path = "host" if block else "compiled"
    return PredictDecision(
        path=path,
        reasons=tuple(r.name for r in block),
        serve_requested=i.serve_env == "1",
        cell=i.key(),
        kernel=path == "compiled" and not kblock,
        kernel_reasons=tuple(r.name for r in kblock),
        kernel_requested=i.serve_kernel_env == "1")


def encode_predict_cell(d: PredictDecision) -> str:
    return (f"path={d.path};kernel={int(d.kernel)};"
            f"why={'+'.join(d.reasons) or '-'};"
            f"kwhy={'+'.join(d.kernel_reasons) or '-'}")


def enumerate_predict_inputs() -> List[PredictInputs]:
    """The audited predict-side lattice: backend x LGBM_TPU_SERVE x
    the full flag cross product under the kernel defaults, plus the
    ISSUE-18 serve_kernel sweep (kernel env x forest_overwide) over
    the clean flag config and the key interaction cells."""
    cells: List[PredictInputs] = []
    seen = set()

    def add(i: PredictInputs):
        k = i.key()
        if k not in seen:
            seen.add(k)
            cells.append(i)

    for be in ("tpu", "cpu"):
        for env in ("auto", "1", "0"):
            for loaded in _BOOL:
                for reb in _BOOL:
                    for lin in _BOOL:
                        for contrib in _BOOL:
                            for leaf in _BOOL:
                                for es in _BOOL:
                                    add(PredictInputs(
                                        backend=be, serve_env=env,
                                        loaded_model=loaded,
                                        rebinned_model=reb,
                                        linear_tree=lin,
                                        pred_contrib=contrib,
                                        pred_leaf=leaf,
                                        pred_early_stop=es))
            # serve_kernel sweep (ISSUE 18) over the clean flag config
            for kern in ("auto", "1", "0", "interpret"):
                for ow in _BOOL:
                    add(PredictInputs(backend=be, serve_env=env,
                                      serve_kernel_env=kern,
                                      forest_overwide=ow))
            # interaction cells: the graduated loaded-model path and a
            # host-routed flag must both leave the kernel disengaged /
            # engaged exactly as the compiled path dictates
            add(PredictInputs(backend=be, serve_env=env,
                              loaded_model=True, forest_overwide=True))
            add(PredictInputs(backend=be, serve_env=env,
                              pred_contrib=True, forest_overwide=True))
    return cells


_PREDICT_WARNED: set = set()


def report_predict_fallbacks(d: PredictDecision) -> None:
    """Make config-caused losses of the compiled serving path loud and
    structured: one ``routing_fallback_<rule>`` obs event per loud rule
    on every host-routed predict, plus a warn-once log line — but only
    when the caller EXPLICITLY requested serving (LGBM_TPU_SERVE=1); a
    contrib/leaf predict under the auto default is a deliberate host
    ask, not a lost fast path.  Events follow the same logic one level
    up: when a QUIET availability rule already routed host (serving
    disabled by env, or auto on a non-TPU backend), nothing was lost —
    recording contrib/leaf events there would make two records differ
    structurally just for running different predict KINDS.

    The serve_kernel block (ISSUE 18) gets the same treatment on the
    COMPILED path: a forest too wide for the kernel's VMEM scratch cap
    (``serve_forest_overwide``, loud) records an event on every
    dispatch-eligible predict and warns once when the kernel was
    explicitly requested — a quiet kernel rule (env off, non-TPU
    backend under auto) suppresses it, nothing was lost there."""
    from ..obs.counters import events
    from ..utils import log
    if (d.path == "compiled" and not d.kernel
            and not any(not PREDICT_RULE_BY_NAME[n].loud
                        for n in d.kernel_reasons
                        if n in PREDICT_RULE_BY_NAME)):
        for name in d.kernel_reasons:
            rule = PREDICT_RULE_BY_NAME.get(name)
            if rule is None or not rule.loud:
                continue
            events.record(f"routing_fallback_{rule.name}")
            if not d.kernel_requested or rule.name in _PREDICT_WARNED:
                continue
            _PREDICT_WARNED.add(rule.name)
            log.warning(
                "routing: the VMEM-resident serving kernel is "
                "disengaged by %s (%s); the compiled path serves "
                "through the XLA gather walk — the predict-side "
                "lattice is lightgbm_tpu/analysis/routing_matrix.json",
                rule.knob, rule.reason)
    if d.path != "host":
        return
    if any(not PREDICT_RULE_BY_NAME[n].loud
           for n in d.reasons if n in PREDICT_RULE_BY_NAME):
        return
    from ..obs.counters import events
    from ..utils import log
    for name in d.reasons:
        rule = PREDICT_RULE_BY_NAME.get(name)
        if rule is None or not rule.loud:
            continue
        events.record(f"routing_fallback_{rule.name}")
        if not d.serve_requested or rule.name in _PREDICT_WARNED:
            continue
        _PREDICT_WARNED.add(rule.name)
        log.warning(
            "routing: the compiled serving path is disengaged by %s "
            "(%s); prediction falls back to the host reference walk — "
            "the predict-side lattice is "
            "lightgbm_tpu/analysis/routing_matrix.json",
            rule.knob, rule.reason)


# warn-once suppression is per RUN (obs.reset_run clears it between
# lgb.train calls), same lifecycle as grow.py's fallback caches
_ROUTING_WARNED: set = set()


def report_fallbacks(d: RouteDecision) -> None:
    """Make every config-caused row_order fallback loud and structured
    (ISSUE-10 satellite): one ``routing_fallback_<rule>`` obs event
    per loud rule plus a warn-once log line naming the config knob —
    replacing the silent ``use_phys=False`` of earlier rounds.  Env-
    and backend-caused fallbacks (deliberate user choices) stay
    quiet."""
    from ..obs.counters import events
    from ..utils import log
    # paged losses (ISSUE 15): a shape that WANTED paging (over budget
    # or forced) but lost it to a named rule trains fully resident —
    # an on-chip OOM, so the loud rules get the same structured
    # treatment as the row_order fallbacks
    for name in d.paged_reasons:
        rule = RULE_BY_NAME.get(name)
        if rule is None or not rule.loud:
            continue
        events.record(f"routing_fallback_{rule.name}")
        if rule.name in _ROUTING_WARNED:
            continue
        _ROUTING_WARNED.add(rule.name)
        log.warning(
            "routing: the paged comb was wanted (over-budget "
            "footprint, or LGBM_TPU_PAGED=1) but is disengaged by %s "
            "(%s); the shape trains fully HBM-resident — an "
            "over-budget shape will OOM on chip.  The full lattice is "
            "lightgbm_tpu/analysis/routing_matrix.json",
            rule.knob, rule.reason)
    # batched-multiclass losses (ISSUE 19): a multiclass physical
    # config that trains serial-K for a loud named rule pays the
    # K-fold dispatch floor every iteration — structured like the
    # paged losses above (quiet rules are deliberate user knobs)
    for name in d.mc_batch_reasons:
        rule = RULE_BY_NAME.get(name)
        if rule is None or not rule.loud:
            continue
        events.record(f"routing_fallback_{rule.name}")
        if rule.name in _ROUTING_WARNED:
            continue
        _ROUTING_WARNED.add(rule.name)
        log.warning(
            "routing: batched multiclass grow is disengaged by %s "
            "(%s); the K class trees train as K serial grow "
            "dispatches per iteration.  The full lattice is "
            "lightgbm_tpu/analysis/routing_matrix.json",
            rule.knob, rule.reason)
    if d.path != "row_order":
        return
    for name in d.reasons:
        rule = RULE_BY_NAME.get(name)
        if rule is None or not rule.loud:
            continue
        events.record(f"routing_fallback_{rule.name}")
        if rule.name in _ROUTING_WARNED:
            continue
        _ROUTING_WARNED.add(rule.name)
        log.warning(
            "routing: the physical fast path is disengaged by %s "
            "(%s); training falls back to the row_order path (~%dx "
            "slower at 1M rows) — the full lattice is "
            "lightgbm_tpu/analysis/routing_matrix.json",
            rule.knob, rule.reason, int(ROW_ORDER_SLOWDOWN_X))


def _register_reset() -> None:
    from ..obs.counters import on_reset
    on_reset(_ROUTING_WARNED.clear)
    on_reset(_PREDICT_WARNED.clear)


_register_reset()


# ---------------------------------------------------------------------
# lattice enumeration + golden matrix
# ---------------------------------------------------------------------
_BOOL = (False, True)
# (objective_kind, multi_tree): binary / l2 / multiclass-shaped /
# other single-model objectives (rank, tweedie, custom)
_OBJ = (("binary", False), ("l2", False),
        ("other", True), ("other", False))

ENV_TPU = dict(backend="tpu", phys_env="auto", stream_env="auto",
               partition_env="permute",
               fused_env=True, hist_scatter_env=True)
# the CPU equivalence-test environment (tests force the reference
# physical path with LGBM_TPU_PHYS=interpret)
ENV_CPU = dict(ENV_TPU, backend="cpu", phys_env="interpret")

_LEARNERS = (("serial", 1), ("data", 8))


def enumerate_inputs() -> List[RouteInputs]:
    """The audited lattice: the full config cross product under the
    shipping TPU env AND the CPU test env, an env-knob sweep over the
    clean base config, plus the shape/boosting/learner edge cells.
    Deterministic order, deduplicated by cell key."""
    cells: List[RouteInputs] = []
    seen = set()

    def add(**kw):
        i = RouteInputs(**kw)
        k = i.key()
        if k not in seen:
            seen.add(k)
            cells.append(i)

    # 1a. FULL config lattice x learner under the shipping TPU env —
    # the production question ("which real-world configs silently lose
    # 25x", ROADMAP item 4)
    for learner, shards in _LEARNERS:
        for efb in _BOOL:
            for u8 in _BOOL:
                for cat in _BOOL:
                    for dp in _BOOL:
                        for cegb in _BOOL:
                            for bag in _BOOL:
                                for obj, multi in _OBJ:
                                    add(learner=learner,
                                        n_shards=shards,
                                        efb_bundled=efb,
                                        # the form
                                        # bundled_comb_eligible gives
                                        # these cells at run time
                                        efb_comb=(efb and not cat
                                                  and not cegb
                                                  and learner
                                                  == "serial"),
                                        bins_u8=u8,
                                        cat_subset=cat,
                                        gpu_use_dp=dp,
                                        cegb_lazy=cegb,
                                        bagging=bag,
                                        objective_kind=obj,
                                        multi_tree=multi, **ENV_TPU)
    # 1b. one-knob-at-a-time config cells under the CPU test envs
    # (LGBM_TPU_PHYS=interpret, plus its phys-off / stream-off
    # variants) — the cells the runtime-parity golden test
    # (tests/test_routing.py) trains and compares on CPU
    for env in (ENV_CPU,
                dict(ENV_CPU, phys_env="0"),
                dict(ENV_CPU, stream_env="0")):
        for learner, shards in _LEARNERS:
            for obj, multi in _OBJ:
                for flip in (None, "efb_bundled", "bins_u8",
                             "cat_subset", "gpu_use_dp", "cegb_lazy",
                             "bagging", "linear_tree", "cat_overwide",
                             "comb_overwide"):
                    kw = dict(objective_kind=obj, multi_tree=multi)
                    if flip == "bins_u8":
                        kw[flip] = False
                    elif flip == "cat_overwide":
                        # ISSUE 16: the one cat shape that still loses
                        # the fast path — subset splits past the
                        # 256-bin bitset budget (necessarily u16 bins)
                        kw["cat_subset"] = True
                        kw["bins_u8"] = False
                    elif flip is not None:
                        kw[flip] = True
                    if flip == "efb_bundled" and learner == "serial":
                        # ... in both comb forms: the plain finder keeps
                        # the bundles, any other grow option unbundles
                        add(learner=learner, n_shards=shards,
                            efb_comb=True, **kw, **env)
                    add(learner=learner, n_shards=shards, **kw, **env)
    # 2. env-knob sweep over the clean base config
    for learner, shards in _LEARNERS:
        for be, phys in (("tpu", "auto"), ("tpu", "0"),
                         ("cpu", "auto"), ("cpu", "0"),
                         ("cpu", "interpret")):
            for part in ("permute", "matmul"):
                for fused in _BOOL:
                    for stream in ("auto", "0"):
                        for scat in _BOOL:
                            add(learner=learner, n_shards=shards,
                                backend=be, phys_env=phys,
                                partition_env=part, fused_env=fused,
                                stream_env=stream,
                                hist_scatter_env=scat)
    # 3. shape / learner / boosting edge cells
    for env in (ENV_TPU, ENV_CPU):
        for learner, shards in _LEARNERS:
            add(learner=learner, n_shards=shards, rows_over_limit=True,
                **env)
            # ISSUE 12: the one EFB shape that still loses the fast
            # path — a bundle expansion past the comb column budget
            add(learner=learner, n_shards=shards, efb_bundled=True,
                efb_overwide=True, **env)
            add(learner=learner, n_shards=shards, efb_bundled=True,
                efb_comb=learner == "serial", efb_overwide=True, **env)
            # a dense line no kernel can stage
            add(learner=learner, n_shards=shards, comb_overwide=True,
                **env)
        # ISSUE 36: a bundled table whose grow options the bundle-space
        # finder does not cover keeps the unbundling ingest
        add(learner="serial", n_shards=1, efb_bundled=True,
            forced_splits=True, **env)
        add(learner="serial", n_shards=1, efb_bundled=True,
            mono_intermediate=True, **env)
        add(learner="data", n_shards=8, f_log_shard_divisible=False,
            **env)
        add(learner="data", n_shards=8, forced_splits=True, **env)
        add(learner="data", n_shards=8, mono_intermediate=True, **env)
        add(learner="data", n_shards=8, cegb_coupled=True, **env)
        add(learner="feature", n_shards=8, **env)
        add(learner="voting", n_shards=8, **env)
        for boost in ("dart", "goss", "rf"):
            add(learner="serial", n_shards=1, boosting=boost, **env)
        add(learner="serial", n_shards=1, linear_tree=True, **env)
        # ISSUE 15: the paged dimension — over-budget shapes under the
        # auto default, the LGBM_TPU_PAGED force/off overrides, and
        # the edges where a wanted paging falls off (mesh learner,
        # paged off, a row_order config that never holds the comb)
        for learner, shards in _LEARNERS:
            add(learner=learner, n_shards=shards, over_budget=True,
                **env)
            add(learner=learner, n_shards=shards, over_budget=True,
                **dict(env, paged_env="0"))
            add(learner=learner, n_shards=shards,
                **dict(env, paged_env="1"))
        add(learner="serial", n_shards=1, over_budget=True,
            **dict(env, stream_env="0"))
        add(learner="serial", n_shards=1, over_budget=True,
            **dict(env, fused_env=False))
        add(learner="serial", n_shards=1, over_budget=True,
            **dict(env, partition_env="matmul"))
        add(learner="serial", n_shards=1, over_budget=True,
            gpu_use_dp=True, **env)
        add(learner="serial", n_shards=1, over_budget=True,
            rows_over_limit=True, **env)
        # ISSUE 19: the batched-multiclass dimension — the
        # LGBM_TPU_MC_BATCH off/force overrides and the edges where a
        # wanted batch falls off (paged comb pinning the window, a
        # row_order config with no carried comb to scan over).  The
        # full 1a lattice already covers multi_tree under the auto
        # knob.
        for learner, shards in _LEARNERS:
            for mcb in ("0", "1"):
                add(learner=learner, n_shards=shards,
                    objective_kind="other", multi_tree=True,
                    **dict(env, mc_batch_env=mcb))
            add(learner=learner, n_shards=shards,
                objective_kind="other", multi_tree=True,
                over_budget=True, **env)
            add(learner=learner, n_shards=shards,
                objective_kind="other", multi_tree=True,
                **dict(env, paged_env="1"))
        add(learner="serial", n_shards=1, objective_kind="other",
            multi_tree=True, cegb_lazy=True, **env)
    return cells


def encode_cell(d: RouteDecision) -> str:
    """One-line cell encoding (diff-friendly golden file)."""
    j = lambda xs: "+".join(xs) or "-"  # noqa: E731
    return (f"path={d.path};scheme={d.scheme};"
            f"fused={int(d.fused)};merge={d.hist_merge};"
            f"paged={int(d.paged)};mcb={int(d.mc_batched)};"
            f"efb={d.efb};"
            f"why={j(d.reasons)};"
            f"merge_why={j(d.merge_reasons)};"
            f"paged_why={j(d.paged_reasons)};"
            f"mcb_why={j(d.mc_batch_reasons)};prog={d.program_key}")


def decode_cell(enc: str) -> dict:
    """Inverse of :func:`encode_cell` (the analyzer audits the
    CHECKED-IN cells, so a hand-mutated golden must still parse)."""
    out: Dict[str, object] = {}
    for part in enc.split(";"):
        k, _, v = part.partition("=")
        if not _:
            raise ValueError(f"unparseable cell field {part!r}")
        out[k] = v
    lists = {k: ([] if out.get(k, "-") == "-"
                 else str(out[k]).split("+"))
             for k in ("why", "merge_why", "paged_why", "mcb_why")}
    return {
        "path": out["path"],
        "scheme": out["scheme"], "fused": bool(int(out["fused"])),
        "merge": out["merge"],
        "paged": bool(int(out.get("paged", 0))),
        "mc_batched": bool(int(out.get("mcb", 0))),
        "efb": out.get("efb", "none"),
        "reasons": lists["why"],
        "merge_reasons": lists["merge_why"],
        "paged_reasons": lists["paged_why"],
        "mc_batch_reasons": lists["mcb_why"],
        "program_key": out.get("prog", ""),
    }


# crude real-world config-share estimates per loud fallback rule —
# the bench-priority ranking the next chip run reads (PERF_NOTES
# rounds 13/15/19).  efb_bundle (0.45, the round-13 leader) GRADUATED
# in ISSUE 12 (only the rare over-wide expansion still falls back);
# cat_subset (0.20, the round-15 leader) GRADUATED in ISSUE 16 —
# membership bitsets ride the split descriptor onto every fast-path
# scheme, and only the cat-over-256-bins corner (cat_overwide, which
# co-fires with non_u8_bins) still falls back.  u16 bins now lead.
FALLBACK_POPULATION: Dict[str, float] = {
    "non_u8_bins": 0.12,
    "n_pad_overflow": 0.08,
    "gpu_use_dp": 0.04,
    "cegb_lazy": 0.02,
    "cat_overwide": 0.02,
    "efb_overwide": 0.01,
    "comb_overwide": 0.01,
}


def enumerate_matrix() -> dict:
    """The full golden routing matrix document (training cells +
    ISSUE-14 predict-side cells)."""
    cells: Dict[str, str] = {}
    path_counts: Dict[str, int] = {}
    reason_counts: Dict[str, int] = {}
    paged_count = 0
    paged_reason_counts: Dict[str, int] = {}
    mc_batched_count = 0
    mc_batch_reason_counts: Dict[str, int] = {}
    for i in enumerate_inputs():
        d = decide(i)
        cells[i.key()] = encode_cell(d)
        path_counts[d.path] = path_counts.get(d.path, 0) + 1
        if d.paged:
            paged_count += 1
        for name in d.paged_reasons:
            paged_reason_counts[name] = (
                paged_reason_counts.get(name, 0) + 1)
        if d.mc_batched:
            mc_batched_count += 1
        for name in d.mc_batch_reasons:
            mc_batch_reason_counts[name] = (
                mc_batch_reason_counts.get(name, 0) + 1)
        if d.path == "row_order":
            for name in d.reasons:
                reason_counts[name] = reason_counts.get(name, 0) + 1
    predict_cells: Dict[str, str] = {}
    predict_paths: Dict[str, int] = {}
    for pi in enumerate_predict_inputs():
        pd = predict_decide(pi)
        predict_cells[pi.key()] = encode_predict_cell(pd)
        predict_paths[pd.path] = predict_paths.get(pd.path, 0) + 1
    priority = []
    for name, share in FALLBACK_POPULATION.items():
        rule = RULE_BY_NAME[name]
        priority.append({
            "reason": name,
            "knob": rule.knob,
            "est_config_share": share,
            "slowdown_x": ROW_ORDER_SLOWDOWN_X,
            "priority": round(share * ROW_ORDER_SLOWDOWN_X, 2),
            "cells": reason_counts.get(name, 0),
        })
    priority.sort(key=lambda p: (-p["priority"], p["reason"]))
    return {
        "schema": ROUTING_SCHEMA,
        "cells": cells,
        "predict_cells": predict_cells,
        "summary": {
            "n_cells": len(cells),
            "paths": path_counts,
            "fallback_reasons": reason_counts,
            "paged_cells": paged_count,
            "paged_fallback_reasons": paged_reason_counts,
            "mc_batched_cells": mc_batched_count,
            "mc_batch_fallback_reasons": mc_batch_reason_counts,
            "bench_priority": priority,
            "n_predict_cells": len(predict_cells),
            "predict_paths": predict_paths,
        },
    }


def canonical_bytes(doc: dict) -> bytes:
    """The byte-for-byte form the golden file is checked against."""
    return (json.dumps(doc, indent=0, sort_keys=True) + "\n").encode()


def default_matrix_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analysis", "routing_matrix.json")


def write_matrix(path: Optional[str] = None) -> Tuple[str, dict]:
    path = path or default_matrix_path()
    doc = enumerate_matrix()
    with open(path, "wb") as fh:
        fh.write(canonical_bytes(doc))
    return path, doc


if __name__ == "__main__":
    import sys
    out_path, out_doc = write_matrix(
        sys.argv[1] if len(sys.argv) > 1 else None)
    summary = out_doc["summary"]
    print(f"wrote {out_path}: {summary['n_cells']} cells, "
          f"paths={summary['paths']}")
