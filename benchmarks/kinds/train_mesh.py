"""Cells of kind ``train_mesh``: ``lgb.train`` with a mesh learner
(``tree_learner=data``) over the chips of one host, timed by
``kinds/train.py``'s window clock.

Traffic: one job, closed loop.  ``rows`` training rows and
``heldout_rows`` more from ``higgs_data.make_higgs_like(seed)``, through
``lgb.Dataset`` and ``lgb.train``; no ``valid_sets``.

``correct`` is ``kinds/train.py``'s (the expected route and no fallback
event, the comb's rows on every chip, nothing built inside the window,
finite scores, tree 0's root split, the first trees walked in numpy
against ``Booster.predict``, ``auc_share``) plus what shows that rows on
every shard were partitioned and summed as ONE data set, against
``reference_mesh.py`` over all training rows, after the window and
outside every clock:

* every training row's score after tree 0 - pulled once, during the
  warm-up, from the run that is then timed - is the value of the leaf
  the float64 walk sends that row to: a row lost, doubled or sent the
  wrong way on any shard is a row with another leaf's value, so the
  255 leaf counts of the program's partition ARE the reference's,
  exactly;
* tree 0's ``leaf_count`` line of the model text, which is the merged
  hessian over one row's hessian in float32 (the reference library's
  estimate, ``ops/split.derived_counts``) and not a count of rows,
  is within ``leaf_count_abs`` rows of the reference's counts, leaf by
  leaf and in its sum;
* tree 0's leaf values agree with ``-lr G / (H + lambda_l2)`` from the
  float64 sums of the operands the configuration states (bf16), plus
  the start score the model text folds into the first tree
  (``leaf_value_abs``: between what the program reads and what a merge
  in bfloat16 would, which every run prints beside it; ``PERF.md``).
"""
from __future__ import annotations

import os
import time

import numpy as np

import higgs_data
import reference
import reference_mesh
from kinds.train import WindowClock, _window_spans, check_route
from kinds_common import CompileCount, fallback_events


class Tree0Scores:
    """A callback: the train scores after the first iteration, on the
    host.  Two iterations before the window opens, so it is set-up."""

    def __init__(self):
        self.scores = None

    def __call__(self, env):
        if env.iteration == 0:
            self.scores = np.asarray(env.model._inner.train_score)[0]


def check_tree0(model_text, tree0, scores0, x, y, params, tol, shards):
    """(faults, what was read): tree 0 against the float64 reference
    over all training rows."""
    faults = []
    n = len(y)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    ref = reference_mesh.binary_leaf_sums(tree0, x, y)
    got_count = reference_mesh.tree0_leaf_counts(model_text)
    count_diff = int(np.max(np.abs(got_count - ref.count)))
    if abs(int(got_count.sum()) - n) > tol["leaf_count_abs"]:
        faults.append(f"tree 0's leaf counts sum to {got_count.sum()}, "
                      f"not to the {n} training rows (allowed "
                      f"{tol['leaf_count_abs']})")
    if count_diff > tol["leaf_count_abs"]:
        worst = int(np.argmax(np.abs(got_count - ref.count)))
        faults.append(
            f"tree 0's leaf counts differ from the reference's by up to "
            f"{count_diff} rows (leaf {worst}: {got_count[worst]} against "
            f"{ref.count[worst]}; allowed {tol['leaf_count_abs']})")
    # the limit is on the reference at the precision the configuration
    # states (bf16 operands, sums above float32); the plain float64
    # reading and the reading a bf16 merge would give go beside it
    want = ref.leaf_values(lr, l2, operands="bf16")
    value_diff = float(np.max(np.abs(tree0.leaf_value - want)))
    if not value_diff <= tol["leaf_value_abs"]:
        worst = int(np.argmax(np.abs(tree0.leaf_value - want)))
        faults.append(
            f"tree 0's leaf values differ from -lr G / H of the float64 "
            f"sums by up to {value_diff} (leaf {worst}: "
            f"{tree0.leaf_value[worst]} against {want[worst]}; allowed "
            f"{tol['leaf_value_abs']})")
    # the partition itself: each row's score after tree 0 against the
    # model's value of the leaf the reference walks it to
    off = np.abs(scores0[:n] - tree0.leaf_value[ref.leaf])
    strays = int(np.count_nonzero(~(off <= tol["row_score_abs"])))
    if strays:
        faults.append(
            f"{strays} training rows scored another leaf's value after "
            f"tree 0 than the leaf the reference walks them to")
    below_value, below_count = ref.merged_in_bf16(lr, shards, l2)
    return faults, {
        "leaves": int(tree0.num_leaves), "rows": n,
        "leaf_count_sum": int(got_count.sum()),
        "leaf_count_max_abs_diff": count_diff,
        "leaf_count_max_abs_diff_of_a_bf16_merge": int(np.max(np.abs(
            below_count - ref.count))),
        "leaf_value_max_abs_diff": value_diff,
        "leaf_value_max_abs_diff_f64_operands": float(np.max(np.abs(
            tree0.leaf_value - ref.leaf_values(lr, l2)))),
        "leaf_value_max_abs_diff_of_a_bf16_merge": float(np.max(np.abs(
            below_value - want))),
        "rows_in_another_leaf": strays,
        "row_score_max_abs_diff": float(off.max()),
        "smallest_leaf": int(ref.count.min()),
        "largest_leaf": int(ref.count.max())}


def run(ctx) -> dict:
    cell, config, note = ctx.cell, ctx.config, ctx.note
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs import tracer as obs_tracer

    compiles = CompileCount()
    n_train = int(cell["rows"])
    n_held = int(cell["heldout_rows"])
    params = dict(config["params"])
    tol = cell["tolerances"]

    t = time.perf_counter()
    x, y, logit = higgs_data.make_higgs_like(n_train + n_held, seed=ctx.seed)
    draw_s = time.perf_counter() - t
    x_held, y_held, logit_held = x[n_train:], y[n_train:], logit[n_train:]
    x_train, y_train = x[:n_train], y[:n_train]

    t = time.perf_counter()
    dtrain = lgb.Dataset(x_train, label=y_train,
                         params={"max_bin": params["max_bin"]})
    dtrain.construct()
    bin_s = time.perf_counter() - t
    note("data", rows=n_train, heldout_rows=n_held,
         label_mean=float(y_train.mean()), draw_s=draw_s, bin_s=bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit(
                "train_mesh: a traced run needs warmup_iters >= 2")
        tracer = obs_tracer
        trace_dir = os.path.join(ctx.out_dir, "profile")
    clock = WindowClock(lgb, cell, ctx.seconds, compiles, trace_dir, tracer,
                        os.path.join(ctx.out_dir, "spans.jsonl"))
    tree0_scores = Tree0Scores()
    events0 = obs_events.totals()
    t_train = time.perf_counter()
    bst = lgb.train(params, dtrain, num_boost_round=1_000_000,
                    callbacks=[tree0_scores, clock])
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
    fell = fallback_events(obs_events, events0)
    if fell:
        faults.append(f"routing fallbacks fired: {fell}")
    if clock.compiles_in_window:
        faults.append(f"{clock.compiles_in_window} programs were built "
                      "inside the window")
    if not all(np.isfinite(s) for s in clock.score_sums):
        faults.append(f"train score is not finite: {clock.score_sums}")

    t = time.perf_counter()
    model_text = bst.model_to_string()
    trees = reference.parse_model(model_text)
    q = int(cell["quality_trees"])
    n_bad = sum(not np.all(np.isfinite(tr.leaf_value)) for tr in trees)
    if len(trees) < q:
        faults.append(f"{len(trees)} trees in all, fewer than {q}")
    root = reference.root_split(
        x_train, y_train, trees[0],
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        min_data_in_leaf=int(params.get("min_data_in_leaf", 20)),
        min_sum_hessian_in_leaf=float(
            params.get("min_sum_hessian_in_leaf", 1e-3)))
    if not root.ok(tol["root_gain_rel"], tol["root_grid_floor"]):
        faults.append(f"tree 0's root split fails the reference: {root}")
    note("root_split", **root.__dict__, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    tree0_faults, read = check_tree0(model_text, trees[0],
                                     tree0_scores.scores, x_train, y_train,
                                     params, tol, int(cell["chips"]))
    faults += tree0_faults
    note("tree0_leaves", **read, allowed_count=tol["leaf_count_abs"],
         allowed_value=tol["leaf_value_abs"],
         check_s=time.perf_counter() - t)

    t = time.perf_counter()
    first = trees[:q]
    raw_held = reference.walk(first, x_held)
    auc_model = reference.auc(raw_held, y_held)
    auc_oracle = reference.auc(logit_held, y_held)
    auc_share = 100.0 * (auc_model - 0.5) / (auc_oracle - 0.5)
    if not auc_share >= tol["auc_share_floor"]:
        faults.append(f"auc_share {auc_share:.2f} is under "
                      f"{tol['auc_share_floor']}")
    note("quality", trees=len(first), auc=auc_model, auc_oracle=auc_oracle,
         auc_share=auc_share, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    n_ref = int(cell["model_check_rows"])
    served = bst.predict(x_held[:n_ref], raw_score=True,
                         num_iteration=len(first))
    diff = float(np.max(np.abs(served - raw_held[:n_ref])))
    allowed = tol["walk_abs_per_13_trees"] * max(len(first), 13) / 13.0
    if not (np.all(np.isfinite(served)) and diff <= allowed):
        faults.append(f"Booster.predict and the reference walk differ by "
                      f"{diff} over {n_ref} rows (allowed {allowed})")
    note("model_check", rows=n_ref, trees=len(first), max_abs_diff=diff,
         allowed=allowed, check_s=time.perf_counter() - t)
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
