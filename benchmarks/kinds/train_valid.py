"""Cells of kind ``train_valid``: ``train_wide``'s job with the seed's
held-out rows passed to ``lgb.train`` as its validation set, and the
configuration's metric evaluated on them every iteration.

Traffic: one job, closed loop.  ``rows`` training rows and, after them,
``heldout_rows`` more from ``epsilon_data.make_epsilon_like(seed)`` (the
training rows one table for every seed, the held-out rows the seed's
own draw).  The training rows go through ``lgb.Dataset`` from the dense
numpy array, the held-out rows through ``lgb.Dataset(...,
reference=dtrain)`` - binned by the training set's mappers - and
``lgb.train(params, dtrain, valid_sets=[dvalid], valid_names=["test"],
callbacks=[record_evaluation, the window clock])``: every iteration
replays the new tree over the valid rows and evaluates ``metric`` there
(``metric_freq`` 1, no training metric, no early stopping);
``warmup_iters``, then a window of ``--seconds``.  A run whose window
closes with fewer than ``quality_trees`` trees trains the rest after
it, with no evaluation, outside every clock.

``correct`` is ``kinds/train_wide.py``'s whole list (the plan, route
and layout, no fallback event, nothing built inside the window, finite
scores, tree 0 against float64 over all training rows, the first
``quality_trees`` trees walked over the raw held-out rows against
``Booster.predict``, their ``auc_share``, and its bfloat16 control)
plus, after the window and outside every clock, against
``reference_valid.py``'s float64 walk of each tree of the model text
over the RAW valid rows:

* every valid row's score at the window's close - the booster's own
  valid score, pulled once - against the float64 sum over the trees
  built by then of the leaf the walk sends the row to
  (``valid_score_abs``; 0 rows may differ);
* every AUC the program recorded (``record_evaluation``; one an
  iteration) against the float64 midrank AUC of the reference scores
  after the same number of trees (``valid_auc_abs``);
* **the control**: the valid score kept in bfloat16 through the score
  check, and its AUC with the arithmetic in bfloat16
  (``reference_valid.auc_in_bf16``) through the AUC check.  They have
  to refuse both: a control either check passes makes the run's own
  ``correct`` false.  (The AUC of the bfloat16 score in float64
  arithmetic is printed beside it: rounding the score moves the AUC
  little more than the program's float32 sums do.)
"""
from __future__ import annotations

import os
import time

import numpy as np

import epsilon_data
import reference
import reference_mesh
import reference_valid
import reference_wide
from kinds.train import WindowClock, _window_spans, check_route
from kinds.train_mesh import Tree0Scores
from kinds.train_wide import ask_plan, check_tree0, control_faults
from kinds_common import CompileCount, fallback_events

VALID_NAME = "test"


class ValidClock(WindowClock):
    """``kinds/train.py``'s clock, run after ``record_evaluation`` so
    that the closing iteration's metric is recorded too; at the close,
    after its clock has stopped, it pulls the booster's valid score
    once."""

    order = 30          # record_evaluation's is 20

    def __init__(self, *args):
        super().__init__(*args)
        self.valid_score = None
        self.trees_at_close = 0

    def __call__(self, env):
        try:
            super().__call__(env)
        except self.lgb.callback.EarlyStopException:
            inner = env.model._inner
            self.valid_score = np.asarray(inner.valid_sets[0].score)[0]
            self.trees_at_close = env.model.num_trees()
            raise


def valid_readings(score, recorded_auc, ref_scores, y, score_abs):
    """What the two valid checks read of a valid score after
    ``len(recorded_auc)`` trees and of the AUCs recorded after each
    tree: the rows more than ``score_abs`` from the float64 sum, the
    largest distance, and the largest distance of a recorded AUC from
    the float64 midrank AUC after as many trees."""
    want = ref_scores[len(recorded_auc) - 1]
    off = np.abs(np.asarray(score, np.float64) - want)
    ref_auc = [reference_valid.auc(ref_scores[i], y)
               for i in range(len(recorded_auc))]
    auc_off = np.abs(np.asarray(recorded_auc) - np.asarray(ref_auc))
    return {"rows": len(want), "trees": len(recorded_auc),
            "valid_rows_off": int(np.count_nonzero(~(off <= score_abs))),
            "valid_score_max_abs_diff": float(off.max()),
            "auc_max_abs_diff": float(auc_off.max()),
            "auc_worst_after_trees": int(np.argmax(auc_off)) + 1,
            "auc_first": ref_auc[0], "auc_last": ref_auc[-1]}


def run(ctx) -> dict:
    cell, config, note = ctx.cell, ctx.config, ctx.note
    t = time.perf_counter()
    plan = ask_plan(config, cell)
    note("plan", **plan, plan_s=time.perf_counter() - t)

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs import tracer as obs_tracer

    compiles = CompileCount()
    n_train = int(cell["rows"])
    n_held = int(cell["heldout_rows"])
    params = dict(config["params"])
    tol = cell["tolerances"]

    t = time.perf_counter()
    x, y, logit = epsilon_data.make_epsilon_like(
        n_train + n_held, seed=ctx.seed, table_rows=n_train)
    draw_s = time.perf_counter() - t
    x_train, y_train = x[:n_train], y[:n_train]
    x_held, y_held, logit_held = x[n_train:], y[n_train:], logit[n_train:]

    t = time.perf_counter()
    dtrain = lgb.Dataset(x_train, label=y_train,
                         params={"max_bin": params["max_bin"]})
    dtrain.construct()
    bin_s = time.perf_counter() - t
    t = time.perf_counter()
    dvalid = lgb.Dataset(x_held, label=y_held, reference=dtrain)
    dvalid.construct()
    valid_bin_s = time.perf_counter() - t
    note("data", rows=n_train, valid_rows=n_held, columns=x.shape[1],
         label_mean=float(y_train.mean()), draw_s=draw_s, bin_s=bin_s,
         valid_bin_s=valid_bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit("train_valid: a traced run needs "
                             "warmup_iters >= 2")
        tracer = obs_tracer
        trace_dir = os.path.join(ctx.out_dir, "profile")
    clock = ValidClock(lgb, cell, ctx.seconds, compiles, trace_dir, tracer,
                       os.path.join(ctx.out_dir, "spans.jsonl"))
    tree0_scores = Tree0Scores()
    recorded = {}
    events0 = obs_events.totals()
    t_train = time.perf_counter()
    bst = lgb.train(params, dtrain, num_boost_round=1_000_000,
                    valid_sets=[dvalid], valid_names=[VALID_NAME],
                    callbacks=[tree0_scores, lgb.record_evaluation(recorded),
                               clock])
    if clock.t_close is None:
        raise RuntimeError("training ended before the window closed")
    peak = ctx.memory_peak_bytes()
    window_s = clock.t_close - clock.t_open
    iters = clock.iters_at_close - clock.iters_at_open
    note("window", iterations=iters, window_s=window_s,
         warmup_s=clock.t_open - t_train, trees=bst.num_trees(),
         compiles_before_window=clock.compiles_at_open,
         cache_hits=compiles.cache_hits,
         compiles_in_window=clock.compiles_in_window,
         traced_slice_iterations=clock.slice_iters)

    # ---- correct ----------------------------------------------------
    faults = []
    r, route_faults = check_route(bst, config["expect_route"],
                                  int(cell["chips"]), n_train)
    faults += route_faults
    note("route", **r.to_json())
    layout = bst._inner.layout_info()
    note("layout", **layout)
    for key in ("comb_line_bytes", "comb_planes", "hist_tiles"):
        if layout.get(key) != plan[key]:
            faults.append(f"layout {key} is {layout.get(key)!r}; the "
                          f"plan said {plan[key]!r}")
    fell = fallback_events(obs_events, events0)
    if fell:
        faults.append(f"routing fallbacks fired: {fell}")
    if clock.compiles_in_window:
        faults.append(f"{clock.compiles_in_window} programs were built "
                      "inside the window")
    if not all(np.isfinite(s) for s in clock.score_sums):
        faults.append(f"train score is not finite: {clock.score_sums}")
    metric = str(params["metric"])
    recorded_auc = list(recorded.get(VALID_NAME, {}).get(metric, []))
    if list(recorded) != [VALID_NAME] \
            or len(recorded_auc) != clock.trees_at_close:
        faults.append(f"the program recorded {len(recorded_auc)} "
                      f"{metric} values on {sorted(recorded)} for "
                      f"{clock.trees_at_close} iterations")

    # the trees auc_share reads, trained after the window where it
    # closed with fewer, with the programs the window ran
    t = time.perf_counter()
    q = int(cell["quality_trees"])
    if tracer is not None:
        tracer.disable()
    while bst.num_trees() < q:
        bst.update()
    note("top_up", trees_at_close=clock.trees_at_close,
         trees=bst.num_trees(), top_up_s=time.perf_counter() - t)

    t = time.perf_counter()
    text = bst.model_to_string(num_iteration=-1)
    trees = reference.parse_model(text)
    n_bad = sum(not np.all(np.isfinite(tr.leaf_value)) for tr in trees)
    if len(trees) < q:
        faults.append(f"{len(trees)} trees in all, fewer than {q}")
    root = reference_wide.root_gains(
        x_train, y_train, trees[0],
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        min_data_in_leaf=int(params.get("min_data_in_leaf", 20)),
        min_sum_hessian_in_leaf=float(
            params.get("min_sum_hessian_in_leaf", 1e-3)))
    if not root.ok(tol["root_gain_rel"], tol["root_best_floor"]):
        faults.append(f"tree 0's root split fails the reference: {root}")
    below = reference_wide.root_gain_of_bf16_sums(
        x_train, y_train, trees[0],
        lambda_l2=float(params.get("lambda_l2", 0.0)))
    note("root_split", **root.__dict__, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    tree0_faults, read, ref0 = check_tree0(trees[0], tree0_scores.scores,
                                           x_train, y_train, params, tol)
    faults += tree0_faults
    note("tree0_leaves", **read, allowed_value=tol["leaf_value_abs"],
         allowed_row_score=tol["row_score_abs"],
         check_s=time.perf_counter() - t)

    # every tree the checks read, walked once over the raw valid rows
    t = time.perf_counter()
    walked = reference_valid.parse_model(text)[
        :max(q, clock.trees_at_close)]
    ref_scores = reference_valid.scores_after(
        walked, reference_valid.leaves(walked, x_held))
    note("valid_walk", trees=len(walked), rows=n_held,
         walk_s=time.perf_counter() - t)

    t = time.perf_counter()
    raw_held = ref_scores[q - 1]
    auc_model = reference_valid.auc(raw_held, y_held)
    auc_oracle = reference_valid.auc(logit_held, y_held)
    auc_share = 100.0 * (auc_model - 0.5) / (auc_oracle - 0.5)
    if not auc_share >= tol["auc_share_floor"]:
        faults.append(f"auc_share {auc_share:.2f} is under "
                      f"{tol['auc_share_floor']}")
    note("quality", trees=q, auc=auc_model, auc_oracle=auc_oracle,
         auc_share=auc_share, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    n_ref = int(cell["model_check_rows"])
    served = bst.predict(x_held[:n_ref], raw_score=True, num_iteration=q)
    diff = float(np.max(np.abs(served - raw_held[:n_ref])))
    allowed = tol["walk_abs_per_13_trees"] * max(q, 13) / 13.0
    if not (np.all(np.isfinite(served)) and diff <= allowed):
        faults.append(f"Booster.predict and the reference walk differ by "
                      f"{diff} over {n_ref} rows (allowed {allowed})")
    # the precision below: the walk over leaf values kept in bfloat16
    bf16_leaf = [reference_mesh.to_bf16(tr.leaf_value) for tr in walked[:q]]
    leaf_q = reference_valid.leaves(walked[:q], x_held[:n_ref])
    walk_bf16 = sum(v[leaf_q[i]] for i, v in enumerate(bf16_leaf))
    diff_bf16 = float(np.max(np.abs(served - walk_bf16)))
    note("model_check", rows=n_ref, trees=q, max_abs_diff=diff,
         allowed=allowed, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    score = clock.valid_score
    read_v = valid_readings(score, recorded_auc, ref_scores, y_held,
                            tol["valid_score_abs"])
    if read_v["valid_rows_off"]:
        faults.append(f"{read_v['valid_rows_off']} valid rows' scores "
                      f"differ from the float64 walk's by more than "
                      f"{tol['valid_score_abs']}")
    if not read_v["auc_max_abs_diff"] <= tol["valid_auc_abs"]:
        faults.append(f"a recorded AUC is {read_v['auc_max_abs_diff']} "
                      f"from the float64 midrank AUC (allowed "
                      f"{tol['valid_auc_abs']})")
    note("valid_check", **read_v, allowed_score=tol["valid_score_abs"],
         allowed_auc=tol["valid_auc_abs"], check_s=time.perf_counter() - t)

    t = time.perf_counter()
    refused, read = control_faults(
        trees[0], tree0_scores.scores[:n_train], ref0, root, below,
        diff_bf16, allowed, x_train, y_train, params, tol)
    if not refused:
        faults.append("the checks passed the control kept in bfloat16: "
                      "they cannot tell it from the program")
    # the valid score kept in bfloat16, and its AUC in bfloat16, as the
    # last recorded value
    below_score = reference_mesh.to_bf16(score)
    at_close = ref_scores[len(recorded_auc) - 1:len(recorded_auc)]
    below_v = valid_readings(
        below_score, [reference_valid.auc_in_bf16(below_score, y_held)],
        at_close, y_held, tol["valid_score_abs"])
    # the AUC hardly sees the score's rounding: in float64 arithmetic
    # the bfloat16 score's AUC is near the reference's
    below_score_auc = abs(reference_valid.auc(below_score, y_held)
                          - reference_valid.auc(at_close[0], y_held))
    passed = [name for name, ok in (
        ("score", below_v["valid_rows_off"] == 0),
        ("AUC", below_v["auc_max_abs_diff"] <= tol["valid_auc_abs"])) if ok]
    if passed:
        faults.append("the valid checks passed the valid score kept in "
                      f"bfloat16 by its {' and '.join(passed)}")
    note("control", correct=not refused, faults=refused,
         **read, valid_rows_off=below_v["valid_rows_off"],
         valid_score_max_abs_diff=below_v["valid_score_max_abs_diff"],
         valid_auc_abs_diff=below_v["auc_max_abs_diff"],
         valid_auc_f64_of_bf16_score_abs_diff=below_score_auc,
         valid_checks_passed=passed, check_s=time.perf_counter() - t)
    if faults:
        note("faults", faults=faults)

    return {
        "correct": not faults,
        "attempted": bst.num_trees(),
        "failed": n_bad,
        "setup_end": clock.t_open,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "train_iters_per_s": iters / window_s,
            "auc_share": auc_share,
        },
        "spans": _window_spans(tracer),
        "trace_dir": trace_dir,
        "counters": {"iterations": iters,
                     "slice_iterations": clock.slice_iters},
    }
