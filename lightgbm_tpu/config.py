"""Parameter / config system.

TPU-native re-design of the reference config layer
(include/LightGBM/config.h:34 ``Config`` struct; src/io/config.cpp:230
``Config::Set``; src/io/config_auto.cpp generated alias table).  One Python
dataclass is the single source of truth: every training/IO/objective/metric
parameter is a typed field, ``ALIASES`` maps the reference's full alias
vocabulary onto canonical names, ``Config.from_params`` parses a user dict or
``key=value`` strings, and ``check_conflicts`` mirrors
``Config::CheckParamConflict`` (config.cpp:286).

The parameter string serialised into saved models (``boosting.h:316``
GetLoadedParam) is produced by :meth:`Config.to_param_string`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, fields
from typing import Any, Dict, List, Optional, Sequence, Union

from .utils import log

# ---------------------------------------------------------------------------
# Alias table (reference: config_auto.cpp:10, ~150 entries).
# Maps alias -> canonical parameter name.
# ---------------------------------------------------------------------------
ALIASES: Dict[str, str] = {
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective",
    "app": "objective",
    "application": "objective",
    "loss": "objective",
    "boosting_type": "boosting",
    "boost": "boosting",
    "train": "data",
    "train_data": "data",
    "train_data_file": "data",
    "data_filename": "data",
    "test": "valid",
    "valid_data": "valid",
    "valid_data_file": "valid",
    "test_data": "valid",
    "test_data_file": "valid",
    "valid_filenames": "valid",
    "num_iteration": "num_iterations",
    "n_iter": "num_iterations",
    "num_tree": "num_iterations",
    "num_trees": "num_iterations",
    "num_round": "num_iterations",
    "num_rounds": "num_iterations",
    "nrounds": "num_iterations",
    "num_boost_round": "num_iterations",
    "n_estimators": "num_iterations",
    "max_iter": "num_iterations",
    "shrinkage_rate": "learning_rate",
    "eta": "learning_rate",
    "num_leaf": "num_leaves",
    "max_leaves": "num_leaves",
    "max_leaf": "num_leaves",
    "max_leaf_nodes": "num_leaves",
    "tree": "tree_learner",
    "tree_type": "tree_learner",
    "tree_learner_type": "tree_learner",
    "num_thread": "num_threads",
    "nthread": "num_threads",
    "nthreads": "num_threads",
    "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed",
    "random_state": "seed",
    "hist_pool_size": "histogram_pool_size",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_samples_leaf": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "bagging": "bagging_fraction",
    "pos_sub_row": "pos_bagging_fraction",
    "pos_subsample": "pos_bagging_fraction",
    "pos_bagging": "pos_bagging_fraction",
    "neg_sub_row": "neg_bagging_fraction",
    "neg_subsample": "neg_bagging_fraction",
    "neg_bagging": "neg_bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "sub_feature_bynode": "feature_fraction_bynode",
    "colsample_bynode": "feature_fraction_bynode",
    "extra_tree": "extra_trees",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "n_iter_no_change": "early_stopping_round",
    "max_tree_output": "max_delta_step",
    "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "l1_regularization": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "lambda": "lambda_l2",
    "l2_regularization": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints",
    "monotone_constraint": "monotone_constraints",
    "monotonic_cst": "monotone_constraints",
    "monotone_constraining_method": "monotone_constraints_method",
    "mc_method": "monotone_constraints_method",
    "monotone_splits_penalty": "monotone_penalty",
    "ms_penalty": "monotone_penalty",
    "mc_penalty": "monotone_penalty",
    "feature_contrib": "feature_contri",
    "fc": "feature_contri",
    "fp": "feature_contri",
    "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename",
    "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename",
    "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    "model_input": "input_model",
    "model_in": "input_model",
    "model_output": "output_model",
    "model_out": "output_model",
    "save_period": "snapshot_freq",
    "linear_trees": "linear_tree",
    "max_bins": "max_bin",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "data_seed": "data_random_seed",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "is_enable_bundle": "enable_bundle",
    "bundle": "enable_bundle",
    "is_pre_partition": "pre_partition",
    "two_round_loading": "two_round",
    "use_two_round_loading": "two_round",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "group_id": "group_column",
    "query_column": "group_column",
    "query": "group_column",
    "query_id": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "cat_feature": "categorical_feature",
    "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "categorical_features": "categorical_feature",
    "is_save_binary": "save_binary",
    "is_save_binary_file": "save_binary",
    "is_predict_raw_score": "predict_raw_score",
    "predict_rawscore": "predict_raw_score",
    "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index",
    "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib",
    "contrib": "predict_contrib",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "predict_name": "output_result",
    "prediction_name": "output_result",
    "pred_name": "output_result",
    "name_pred": "output_result",
    "convert_model_file": "convert_model",
    "num_classes": "num_class",
    "unbalance": "is_unbalance",
    "unbalanced_sets": "is_unbalance",
    "metrics": "metric",
    "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at",
    "ndcg_at": "eval_at",
    "map_eval_at": "eval_at",
    "map_at": "eval_at",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "port": "local_listen_port",
    "machine_list_file": "machine_list_filename",
    "machine_list": "machine_list_filename",
    "mlist": "machine_list_filename",
    "workers": "machines",
    "nodes": "machines",
}

_LIST_INT = List[int]
_LIST_FLOAT = List[float]
_LIST_STR = List[str]

# ---------------------------------------------------------------------------
# LGBM_TPU_* environment knobs (name -> (default, one-line effect)).
# These are NOT training parameters: they are bisection/override knobs
# for the TPU hot path, read at import time (or first use).  Single
# source of truth for the docs — tools/gen_parameter_docs.py renders
# this table into docs/Parameters.md; the prose lives in the README's
# "Environment knobs" section.  Keep the three lists in sync by editing
# HERE and regenerating.
# ---------------------------------------------------------------------------
ENV_KNOBS: Dict[str, tuple] = {
    "LGBM_TPU_FUSED": ("1", "0 disables the fused partition+histogram "
                            "split kernel (separate pallas_call pair)"),
    "LGBM_TPU_PARTITION": ("permute", "single-scan partition packing: "
                                      "permute (O(log R) rounds) or "
                                      "matmul ([R,R] one-hot)"),
    "LGBM_TPU_PART_INTERP": ("off", "kernel runs the REAL scan/copyback "
                                    "bodies through the Pallas "
                                    "interpreter off-TPU"),
    "LGBM_TPU_COMB_DT": ("f32", "bf16 stores the physical comb matrix "
                                "in bf16 (blocked by Mosaic tiling "
                                "today; profile_partition records "
                                "status)"),
    "LGBM_TPU_COMB_BF16": ("1", "0 forces the bucketed combined gather "
                                "matrix to f32"),
    "LGBM_TPU_APPLY_IMPL": ("kernel", "xla / pallas_interpret override "
                                      "for the apply+find tail"),
    "LGBM_TPU_POOL_TAIL": ("1", "0 disables the pool-resident "
                                "apply+find kernel"),
    "LGBM_TPU_PHYS": ("auto", "0 disables physical partition mode; "
                              "interpret forces it on non-TPU backends "
                              "(read via config.env_knob by the "
                              "ops/routing.py path-selection model)"),
    "LGBM_TPU_STREAM": ("auto", "0 disables score-resident gradient "
                                "streaming (read via config.env_knob "
                                "by the ops/routing.py model)"),
    "LGBM_TPU_HIST_IMPL": ("auto", "histogram backend override: "
                                   "pallas2 / matmul / scatter / "
                                   "pallas_interpret"),
    "LGBM_TPU_HIST_SCATTER": ("1", "0 disables the reduce-scatter "
                                   "histogram merge in the "
                                   "data-parallel learner"),
    "LGBM_TPU_TRACE": ("off", "path to a JSON-lines phase trace; "
                              "enables the obs tracer + device "
                              "counters + run ledger"),
    "LGBM_TPU_TRACE_MAX_EVENTS": ("200000", "in-memory event cap for "
                                            "the tracer"),
    "LGBM_TPU_XPLANE": ("off", "directory for a jax.profiler xplane "
                               "capture (profile_lib blocks; bench.py "
                               "timed window) — obs spans mirror as "
                               "TraceAnnotations and bench records "
                               "gain a device block; decode with "
                               "obs attr"),
    "LGBM_TPU_PEAK_BW_GBPS": ("auto", "roofline HBM peak in GB/s for "
                                      "obs report --roofline; auto = "
                                      "the record's device_kind in "
                                      "costmodel.DEVICE_KINDS (an "
                                      "unknown kind is an error)"),
    "LGBM_TPU_PEAK_TFLOPS": ("auto", "roofline compute peak in TFLOP/s "
                                     "for obs report --roofline; auto "
                                     "= the record's device_kind"),
    "LGBM_TPU_VMEM_GEN": ("auto", "TPU generation whose VMEM size the "
                                  "static analyzer's vmem-budget pass "
                                  "prices kernels against (v4 / v5e / "
                                  "v5p); auto = the live TPU's "
                                  "device_kind (unknown kinds are an "
                                  "error), off-chip the v5e analysis "
                                  "target"),
    "LGBM_TPU_VMEM_LIMIT_MB": ("off", "absolute per-kernel VMEM "
                                      "budget in MiB for python -m "
                                      "lightgbm_tpu.analysis "
                                      "(overrides the per-generation "
                                      "size minus compiler reserve)"),
    "LGBM_TPU_HBM_GEN": ("auto", "TPU generation whose HBM size the "
                                 "footprint model (obs mem) and the "
                                 "analyzer's hbm-budget pass price "
                                 "residency against (v4 / v5e / v5p); "
                                 "auto = the live TPU's device_kind, "
                                 "off-chip the v5e analysis target"),
    "LGBM_TPU_HBM_LIMIT_GB": ("off", "absolute per-chip HBM budget in "
                                     "GiB for obs mem and python -m "
                                     "lightgbm_tpu.analysis (overrides "
                                     "the per-generation size minus "
                                     "the runtime reserve)"),
    "LGBM_TPU_PEAK_HOST_BW_GBPS": ("32", "host<->HBM staging bandwidth "
                                         "the page-schedule planner "
                                         "(obs mem --plan) prices "
                                         "per-tree DMA overhead "
                                         "against (PCIe-class "
                                         "default)"),
    "LGBM_TPU_PAGED": ("auto", "paged comb for larger-than-HBM "
                               "training (ops/paged.py): auto engages "
                               "when the grow footprint exceeds the "
                               "HBM budget (LGBM_TPU_HBM_LIMIT_GB / "
                               "per-generation table), 1 forces "
                               "paging on any shape, 0 keeps the comb "
                               "fully resident (the routing model's "
                               "paged dimension)"),
    "LGBM_TPU_MC_BATCH": ("auto", "batched multiclass training "
                                  "(ISSUE 19): auto grows all K class "
                                  "trees in ONE compiled dispatch per "
                                  "iteration on the physical unpaged "
                                  "path (trees byte-identical to the "
                                  "serial-K loop), 0 keeps the K "
                                  "serial grow dispatches, 1 forces "
                                  "the batched request (the routing "
                                  "model's mc_batch dimension)"),
    "LGBM_TPU_PAGE_ROWS": ("auto", "logical rows per comb page on the "
                                   "paged path (multiple of the "
                                   "partition block R); auto takes "
                                   "the costmodel.page_schedule "
                                   "planner's choice"),
    "LGBM_TPU_CHIPRUN_DIR": ("off", "run directory for the chip-run "
                                    "autopilot (tools/chip_run.py "
                                    "journal + logs + records; also "
                                    "the default dir whose disk "
                                    "headroom obs doctor checks)"),
    "LGBM_TPU_DOCTOR_MIN_DISK_GB": ("2", "capture-dir free-disk floor "
                                         "for the obs doctor disk "
                                         "layer (below it warns, "
                                         "below a quarter of it "
                                         "errors; 0 disables)"),
    "LGBM_TPU_CKPT_DIR": ("off", "checkpoint directory for "
                                 "deterministic train checkpoint/"
                                 "resume (lightgbm_tpu/ckpt/v1; "
                                 "engine.train resumes from the "
                                 "latest valid checkpoint found "
                                 "here)"),
    "LGBM_TPU_CKPT_EVERY": ("10", "checkpoint cadence in boosting "
                                  "iterations (0 = resume-only, "
                                  "never write)"),
    "LGBM_TPU_CKPT_KEEP": ("2", "how many completed checkpoints to "
                                "retain (older ones are pruned "
                                "after each save)"),
    "LGBM_TPU_CKPT_AT_REFRESH": ("0", "1 re-anchors the physical row "
                                      "permutation IN PLACE at each "
                                      "checkpoint save on the stream "
                                      "path (one anchored-order "
                                      "gather at the refresh "
                                      "boundary, where the value "
                                      "columns were just rebuilt "
                                      "anyway) instead of dropping "
                                      "the comb for a full re-ingest "
                                      "— kill+resume stays "
                                      "byte-identical"),
    "LGBM_TPU_FAULT": ("off", "fault injection: <class>@<iteration> "
                              "with class in death | nan | oom | "
                              "hang (resilience/faults.py; each "
                              "spec fires once per process)"),
    "LGBM_TPU_FAULT_RETRIES": ("2", "bounded resume-from-checkpoint "
                                    "retries for recoverable "
                                    "injected/observed faults at the "
                                    "engine boundary"),
    "LGBM_TPU_NUMERICS": ("off", "NaN/Inf guardrails on grad/hess/"
                                 "histogram/gain in the grow path: "
                                 "raise | skip | clamp (off "
                                 "compiles the identical grow "
                                 "program — analyzer purity pin "
                                 "grow-numerics-off)"),
    "LGBM_TPU_SERVE": ("auto", "compiled forest serving for "
                               "Booster.predict (lightgbm_tpu/serve): "
                               "auto engages on the TPU backend only, "
                               "1 forces it on any backend, 0 keeps "
                               "the host reference walk (read via "
                               "config.env_knob by the ops/routing.py "
                               "predict_decide rules)"),
    "LGBM_TPU_SERVE_KERNEL": ("auto", "VMEM-resident Pallas serving "
                                      "traversal (ops/pallas/"
                                      "serve_kernel.py): refused by "
                                      "the v5e compiler on jax 0.9.0 "
                                      "as of PR 22 (Only 2D gather is "
                                      "supported), so auto and 0 keep "
                                      "every dispatch on the XLA "
                                      "gather walk; only 1 asks for "
                                      "the kernel (TPU backend, forest "
                                      "inside the layout."
                                      "serve_forest_fit VMEM cap, else "
                                      "the loud serve_forest_overwide "
                                      "fallback) and fails with the "
                                      "compiler's error"),
    "LGBM_TPU_SERVE_INTERP": ("off", "kernel runs the REAL serving "
                                     "traversal kernel body through "
                                     "the Pallas interpreter off-TPU "
                                     "(the serve-side analog of "
                                     "LGBM_TPU_PART_INTERP — the "
                                     "parity suite's proof seam)"),
    "LGBM_TPU_SERVE_LEAF_BF16": ("0", "store stacked leaf values as "
                                      "bfloat16 (halves leaf-gather "
                                      "bytes on BOTH serving "
                                      "traversal paths; scores still "
                                      "accumulate f32).  Off by "
                                      "default: scores round to "
                                      "~8-bit leaf mantissas, and "
                                      "the serving digest carries "
                                      "the knob so mixed bench "
                                      "records never compare"),
    "LGBM_TPU_SERVE_BUCKETS": ("16:65536", "FLOOR:CAP power-of-two "
                                           "row buckets for compiled "
                                           "serving batch shapes — "
                                           "novel sizes pad into an "
                                           "existing bucket and never "
                                           "retrace"),
    "LGBM_TPU_SERVE_QUEUE": ("2", "double-buffered dispatch queue "
                                  "depth for the serving small-batch "
                                  "path (submit batch t+1 while t is "
                                  "in flight)"),
    "LGBM_TPU_SERVE_METRICS": ("off", "serving flight recorder "
                                      "(serve/flight.py): off "
                                      "disables (identical compiled "
                                      "program, one branch per "
                                      "dispatch), mem aggregates "
                                      "in-process only, any other "
                                      "value is the directory "
                                      "digest-segmented "
                                      "servemetrics/v1 JSONL windows "
                                      "rotate into atomically — "
                                      "rendered by python -m "
                                      "lightgbm_tpu.obs serve"),
    "LGBM_TPU_SERVE_METRICS_WINDOW_S": ("60", "serving flight-"
                                              "recorder aggregation "
                                              "window in seconds: "
                                              "latency histograms / "
                                              "queue occupancy / "
                                              "padding waste roll "
                                              "into one emitted "
                                              "window record per "
                                              "cadence (a model-"
                                              "digest change closes "
                                              "the window early — "
                                              "hot-swap streams "
                                              "never merge)"),
    "LGBM_TPU_PULSE": ("off", "live heartbeat streams (obs/pulse.py): "
                              "off disables (no emitter allocated, "
                              "identical compiled programs — the "
                              "grow-pulse-off purity pin), mem "
                              "aggregates in-process only, any other "
                              "value is the directory pulse/v1 JSONL "
                              "streams rotate into atomically — "
                              "tailed by python -m lightgbm_tpu.obs "
                              "watch and merged by obs timeline"),
    "LGBM_TPU_PULSE_EVERY_S": ("10", "pulse heartbeat cadence in "
                                     "seconds: beats are rate-limited "
                                     "to one emission per cadence "
                                     "(lifecycle events always emit); "
                                     "the watch stall threshold is "
                                     "stall_k x this promise, read "
                                     "from each stream's own "
                                     "records"),
}


def env_knob(name: str, environ=None) -> str:
    """Documented read of one ``LGBM_TPU_*`` environment knob (ISSUE-10
    satellite): the name must be registered in :data:`ENV_KNOBS` (the
    table ``tools/gen_parameter_docs.py`` renders into
    docs/Parameters.md), and an unset/empty variable returns the
    table's default — so every knob the routing model
    (``ops/routing.py``) consumes is documented and analyzable by
    construction.  Raises ``KeyError`` for an unregistered name: an
    undocumented knob read is a bug, not a feature."""
    if name not in ENV_KNOBS:
        raise KeyError(
            f"{name!r} is not a registered LGBM_TPU knob; add it to "
            "config.ENV_KNOBS (and regenerate docs/Parameters.md) "
            "before reading it")
    import os
    val = (environ if environ is not None else os.environ).get(name, "")
    return val if val != "" else ENV_KNOBS[name][0]


@dataclass
class Config:
    """All parameters, canonical names and defaults matching the reference
    (include/LightGBM/config.h).  Fields are grouped as in the reference docs.
    """

    # -- core --
    config: str = ""
    task: str = "train"
    objective: str = "regression"
    boosting: str = "gbdt"
    data: str = ""
    valid: _LIST_STR = field(default_factory=list)
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    tree_learner: str = "serial"
    num_threads: int = 0
    device_type: str = "tpu"
    seed: int = 0
    deterministic: bool = False

    # -- learning control --
    force_col_wise: bool = False
    force_row_wise: bool = False
    histogram_pool_size: float = -1.0
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    bagging_fraction: float = 1.0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    feature_fraction_seed: int = 2
    extra_trees: bool = False
    extra_seed: int = 6
    early_stopping_round: int = 0
    first_metric_only: bool = False
    max_delta_step: float = 0.0
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    linear_lambda: float = 0.0
    min_gain_to_split: float = 0.0
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4
    top_rate: float = 0.2
    other_rate: float = 0.1
    min_data_per_group: int = 100
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20
    monotone_constraints: _LIST_INT = field(default_factory=list)
    monotone_constraints_method: str = "basic"
    monotone_penalty: float = 0.0
    feature_contri: _LIST_FLOAT = field(default_factory=list)
    forcedsplits_filename: str = ""
    refit_decay_rate: float = 0.9
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    cegb_penalty_feature_lazy: _LIST_FLOAT = field(default_factory=list)
    cegb_penalty_feature_coupled: _LIST_FLOAT = field(default_factory=list)
    path_smooth: float = 0.0
    interaction_constraints: str = ""
    verbosity: int = 1

    # -- IO / dataset --
    input_model: str = ""
    output_model: str = "LightGBM_model.txt"
    saved_feature_importance_type: int = 0
    snapshot_freq: int = -1
    linear_tree: bool = False
    max_bin: int = 255
    max_bin_by_feature: _LIST_INT = field(default_factory=list)
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    is_enable_sparse: bool = True
    enable_bundle: bool = True
    use_missing: bool = True
    zero_as_missing: bool = False
    feature_pre_filter: bool = True
    pre_partition: bool = False
    two_round: bool = False
    header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_feature: str = ""
    forcedbins_filename: str = ""
    save_binary: bool = False
    precise_float_parser: bool = False
    parser_config_file: str = ""

    # -- predict --
    start_iteration_predict: int = 0
    num_iteration_predict: int = -1
    predict_raw_score: bool = False
    predict_leaf_index: bool = False
    predict_contrib: bool = False
    predict_disable_shape_check: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0
    output_result: str = "LightGBM_predict_result.txt"

    # -- convert model --
    convert_model_language: str = ""
    convert_model: str = "gbdt_prediction.cpp"

    # -- objective --
    objective_seed: int = 5
    num_class: int = 1
    is_unbalance: bool = False
    scale_pos_weight: float = 1.0
    sigmoid: float = 1.0
    boost_from_average: bool = True
    reg_sqrt: bool = False
    alpha: float = 0.9
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    lambdarank_truncation_level: int = 30
    lambdarank_norm: bool = True
    label_gain: _LIST_FLOAT = field(default_factory=list)

    # -- metric --
    metric: _LIST_STR = field(default_factory=list)
    metric_freq: int = 1
    is_provide_training_metric: bool = False
    eval_at: _LIST_INT = field(default_factory=lambda: [1, 2, 3, 4, 5])
    multi_error_top_k: int = 1
    auc_mu_weights: _LIST_FLOAT = field(default_factory=list)

    # -- network (reference: socket/MPI machine list; here: jax mesh) --
    num_machines: int = 1
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_filename: str = ""
    machines: str = ""

    # -- device --
    gpu_platform_id: int = -1
    gpu_device_id: int = -1
    gpu_use_dp: bool = False
    num_gpu: int = 1
    # TPU-specific (no reference analog): mesh shape for distributed training
    # and histogram kernel selection.
    tpu_mesh_axes: str = ""          # e.g. "data:8" or "data:4,feature:2"
    tpu_histogram_impl: str = "auto"  # auto | xla | pallas
    tpu_rows_per_block: int = 8192    # row-block size for histogram streaming

    # ------------------------------------------------------------------
    @staticmethod
    def canonical_name(name: str) -> str:
        name = name.strip().lower()
        return ALIASES.get(name, name)

    @classmethod
    def param_names(cls) -> List[str]:
        return [f.name for f in fields(cls)]

    @classmethod
    def from_params(
        cls,
        params: Optional[Union[Dict[str, Any], str, Sequence[str]]] = None,
        **kwargs: Any,
    ) -> "Config":
        """Build a Config from a dict / ``"k=v k2=v2"`` string / kwargs.

        Reference: Config::Set (config.cpp:230) + KV2Map (config.cpp:16).
        Unknown keys warn (the reference warns about unknown parameters too).
        When the same canonical parameter is given via several aliases, the
        first occurrence wins and later ones warn (config.cpp:42 behavior).
        """
        merged: Dict[str, Any] = {}
        provenance: Dict[str, str] = {}

        def _add(key: str, value: Any) -> None:
            canon = cls.canonical_name(key)
            if canon in merged:
                if merged[canon] != value:
                    log.warning(
                        "%s is set=%r, %s=%r will be ignored. "
                        "Current value: %s=%r",
                        provenance[canon], merged[canon], key, value,
                        canon, merged[canon],
                    )
                return
            merged[canon] = value
            provenance[canon] = key

        if isinstance(params, str):
            params = params.replace("\n", " ").split()
        if isinstance(params, dict):
            for k, v in params.items():
                _add(k, v)
        elif params is not None:
            for tok in params:
                tok = tok.strip()
                if not tok or tok.startswith("#"):
                    continue
                if "=" not in tok:
                    log.warning("Unknown parameter token %r (expected key=value)", tok)
                    continue
                k, v = tok.split("=", 1)
                _add(k, v.split("#", 1)[0].strip())
        for k, v in kwargs.items():
            _add(k, v)

        cfg = cls()
        valid_names = set(cls.param_names())
        explicit = []
        for k, v in merged.items():
            if k not in valid_names:
                log.warning("Unknown parameter: %s", k)
                continue
            setattr(cfg, k, _coerce(cls, k, v))
            explicit.append(k)
        cfg._explicit = explicit
        cfg.check_conflicts()
        return cfg

    def explicit_params(self) -> Dict[str, Any]:
        """The parameters explicitly set by the user (canonical names) —
        what the reference persists into the model file (GetLoadedParam,
        boosting.h:316) and what the CLI forwards to train()."""
        return {k: getattr(self, k) for k in getattr(self, "_explicit", [])}

    # ------------------------------------------------------------------
    def check_conflicts(self) -> None:
        """Mirror of Config::CheckParamConflict (config.cpp:286): normalise
        inconsistent combinations instead of failing where the reference does.
        """
        if self.num_leaves < 2:
            log.warning("num_leaves must be >= 2; set to 2")
            self.num_leaves = 2
        if self.max_depth > 0:
            # reference caps num_leaves at 2^max_depth
            cap = 1 << min(self.max_depth, 30)
            if self.num_leaves > cap:
                log.warning(
                    "Accuracy may be bad since num_leaves (%d) > 2^max_depth (%d)",
                    self.num_leaves, cap)
                self.num_leaves = cap
        if self.boosting == "rf":
            if self.bagging_freq <= 0 or self.bagging_fraction >= 1.0 or self.bagging_fraction <= 0.0:
                log.fatal("Random forest needs bagging_freq > 0 and 0 < bagging_fraction < 1")
        if self.boosting == "goss":
            # reference >=4.0 folds goss into data_sample_strategy; keep the
            # 3.x behavior: goss disables bagging.
            self.bagging_fraction = 1.0
            self.bagging_freq = 0
        if (self.pos_bagging_fraction != 1.0 or self.neg_bagging_fraction != 1.0) and (
            self.bagging_freq == 0
        ):
            log.warning("pos/neg bagging fractions need bagging_freq > 0; ignoring")
            self.pos_bagging_fraction = 1.0
            self.neg_bagging_fraction = 1.0
        if self.objective in ("lambdarank", "rank_xendcg") and not self.metric:
            self.metric = ["ndcg"]
        if self.max_bin < 2:
            log.fatal("max_bin must be >= 2")
        if self.device_type not in ("cpu", "tpu", "gpu", "cuda", "cuda_exp"):
            log.fatal("Unknown device_type %s", self.device_type)
        if self.tree_learner not in ("serial", "feature", "data", "voting"):
            log.fatal("Unknown tree_learner %s", self.tree_learner)

    # ------------------------------------------------------------------
    def to_param_string(self) -> str:
        """Serialise non-default parameters (reference: GetLoadedParam,
        saved in the model file's ``parameters:`` section)."""
        default = Config()
        parts = []
        for f in fields(self):
            v = getattr(self, f.name)
            if v != getattr(default, f.name):
                if isinstance(v, list):
                    v = ",".join(str(x) for x in v)
                parts.append(f"[{f.name}: {v}]")
        return "\n".join(parts)

    def copy(self, **overrides: Any) -> "Config":
        return dataclasses.replace(self, **overrides)


def _coerce(cls, name: str, value: Any) -> Any:
    """Coerce a raw (possibly string) value to the field's declared type."""
    ftype = cls.__dataclass_fields__[name].type
    if isinstance(ftype, str):
        ftype_s = ftype
    else:  # typing object
        ftype_s = str(ftype)
    try:
        if ftype_s in ("int", "<class 'int'>"):
            return int(float(value))
        if ftype_s in ("float", "<class 'float'>"):
            return float(value)
        if ftype_s in ("bool", "<class 'bool'>"):
            if isinstance(value, str):
                return value.strip().lower() in ("true", "1", "+", "yes", "y", "on")
            return bool(value)
        if ftype_s in ("str", "<class 'str'>"):
            return str(value)
        # list types
        if "List[int]" in ftype_s or "_LIST_INT" in ftype_s:
            return _to_list(value, int)
        if "List[float]" in ftype_s or "_LIST_FLOAT" in ftype_s:
            return _to_list(value, float)
        if "List[str]" in ftype_s or "_LIST_STR" in ftype_s:
            return _to_list(value, str)
    except (TypeError, ValueError):
        log.fatal("Bad value %r for parameter %s", value, name)
    return value


def _to_list(value: Any, typ) -> list:
    if value is None:
        return []
    if isinstance(value, (list, tuple)):
        return [typ(v) for v in value]
    if isinstance(value, str):
        value = value.strip()
        if not value:
            return []
        return [typ(float(v)) if typ is int else typ(v) for v in value.split(",")]
    return [typ(value)]
