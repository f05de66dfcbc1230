"""Cells of kind ``train_wide``: ``lgb.train`` on a wide dense table,
timed by ``kinds/train.py``'s window clock.

Traffic: one job, closed loop.  ``rows`` training rows and, after them,
``heldout_rows`` more from ``epsilon_data.make_epsilon_like(seed)``: the
training rows are the generator's one table, the same for every seed
as the Epsilon file is one file, and the held-out rows are the seed's
own draw.  The training rows go through ``lgb.Dataset`` from the dense
numpy array and ``lgb.train`` as they are, with no ``valid_sets`` and
no parameter the configuration does not state; ``warmup_iters``, then a
window of ``--seconds``.

**The plan.**  Before any full-size array is made, the program is asked
what it builds at this width: ``obs.costmodel.comb_kernel_plan`` from
the column count and ``max_bin`` alone - the comb's planes, the tiles a
comb histogram sweeps, each comb kernel's rows a step and the scoped
VMEM they are priced at against the limit they are built under.  A
program that cannot answer (one from before the plan existed has none and
would spend its set-up compiling kernels the chip refuses), or whose
answer is not the cell's (``comb_planes``, ``hist_tiles``, a price over
its limit), makes the run say so and exit 1, in seconds.

``correct`` is ``kinds/train.py``'s list (the expected route and no
fallback event, nothing built inside the window, finite scores, the
first ``quality_trees`` trees walked in numpy over the raw held-out
rows against ``Booster.predict``, their ``auc_share``) plus, over all
training rows, after the window and outside every clock:

* every training row's score after tree 0 - pulled once, during the
  warm-up - is the value of the leaf the float64 walk over its raw
  values sends it to (``row_score_abs``; 0 rows may differ);
* tree 0's root gain against the float64 gain of the same split
  (``root_gain_rel``) and against the best root gain over every column
  on the reference's own 63-bin equal-count grid of its raw values
  (``root_best_floor``; ``reference_wide.py``);
* tree 0's leaf values against ``-lr G / H`` from float64 sums of the
  bf16 operands the configuration states (``leaf_value_abs``);
* the comb's line, planes and histogram tiles are the cell's.

**The control.**  Each run also puts the precision below the
configuration's in the program's place - tree 0's leaf values from its
leaf sums rounded to bfloat16, every row's score rounded to bfloat16,
the root gain from the split's sums rounded to bfloat16, the walked
trees' leaf values in bfloat16 - and sends it through the same checks
(``check_tree0``, ``RootGains.ok``, the walk's limit).  The checks
have to refuse it: a control they pass makes the run's own ``correct``
false, since checks that cannot tell the two apart judge nothing.  Its
readings are each limit's second reading (``PERF.md``).
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

import epsilon_data
import reference
import reference_mesh
import reference_wide
from kinds.train import WindowClock, _window_spans, check_route
from kinds.train_mesh import Tree0Scores
from kinds_common import CompileCount, fallback_events


WALK_ROWS = 1 << 16


def ask_plan(config, cell) -> dict:
    """The program's plan for this width, or exit 1 saying why not."""
    try:
        from lightgbm_tpu.obs.costmodel import comb_kernel_plan
        plan = comb_kernel_plan(features=int(config["n_features"]),
                                max_bins=int(config["params"]["max_bin"]),
                                stream_kind=config["params"]["objective"])
    except Exception as e:          # noqa: BLE001 - any failure is the answer
        print(f"train_wide: the program has no plan for a "
              f"{config['n_features']}-column comb ({type(e).__name__}: "
              f"{e}); it would reach the chip's compiler with kernels "
              "sized for a narrower line", file=sys.stderr)
        raise SystemExit(1)
    want = cell["layout"]
    wrong = [f"{k} {plan.get(k)!r}, not {v!r}" for k, v in want.items()
             if plan.get(k) != v]
    wrong += [f"{name} is priced at {k['vmem_bytes']} B of scoped VMEM "
              f"over its {k['vmem_limit']}"
              for name, k in plan["kernels"].items()
              if k["vmem_bytes"] > k["vmem_limit"]]
    if not plan["stageable"]:
        wrong.append("the comb is not stageable")
    if wrong:
        print("train_wide: the program's plan for this width is not the "
              "cell's: " + "; ".join(wrong), file=sys.stderr)
        raise SystemExit(1)
    return plan


def check_tree0(tree0, scores0, x, y, params, tol, ref=None):
    """(faults, what was read, the float64 leaf sums): tree 0 against
    the float64 reference over all training rows.  ``ref``: the sums of
    a walk already made of the same tree structure."""
    faults = []
    n = len(y)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    if ref is None:
        ref = reference_mesh.binary_leaf_sums(tree0, x, y)
    want = ref.leaf_values(lr, l2, operands="bf16")
    value_diff = float(np.max(np.abs(tree0.leaf_value - want)))
    if not value_diff <= tol["leaf_value_abs"]:
        worst = int(np.argmax(np.abs(tree0.leaf_value - want)))
        faults.append(
            f"tree 0's leaf values differ from -lr G / H of the float64 "
            f"sums by up to {value_diff} (leaf {worst}: "
            f"{tree0.leaf_value[worst]} against {want[worst]}; allowed "
            f"{tol['leaf_value_abs']})")
    off = np.abs(scores0[:n] - tree0.leaf_value[ref.leaf])
    strays = int(np.count_nonzero(~(off <= tol["row_score_abs"])))
    if strays:
        faults.append(
            f"{strays} training rows scored another leaf's value after "
            f"tree 0 than the leaf the reference walks them to")
    return faults, {
        "leaves": int(tree0.num_leaves), "rows": n,
        "leaf_value_max_abs_diff": value_diff,
        "leaf_value_max_abs_diff_f64_operands": float(np.max(np.abs(
            tree0.leaf_value - ref.leaf_values(lr, l2)))),
        "rows_in_another_leaf": strays,
        "row_score_max_abs_diff": float(off.max()),
        "smallest_leaf": int(ref.count.min()),
        "largest_leaf": int(ref.count.max()),
        "depth": tree0.depth()}, ref


def control_faults(tree0, scores0, ref, root, gain_of_bf16_sums,
                   walk_diff_of_bf16_leaves, walk_allowed, x, y, params,
                   tol):
    """(faults, what was read) of the control: the precision below the
    configuration's in the program's place, through the program's own
    checks.  Tree 0's leaf values from its leaf sums rounded to
    bfloat16, every training row's score rounded to bfloat16, the root
    gain from the split's sums rounded to bfloat16."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    below_value, _ = ref.merged_in_bf16(lr, 1, l2)
    faults, read, _ = check_tree0(
        dataclasses.replace(tree0, leaf_value=below_value),
        reference_mesh.to_bf16(scores0), x, y, params, tol, ref=ref)
    root_below = dataclasses.replace(
        root, gain_recorded=gain_of_bf16_sums,
        rel_err=abs(root.gain_recomputed - gain_of_bf16_sums)
        / abs(gain_of_bf16_sums))
    if not root_below.ok(tol["root_gain_rel"], tol["root_best_floor"]):
        faults.append(f"root split {root_below.rel_err:.3g} off")
    if not walk_diff_of_bf16_leaves <= walk_allowed:
        faults.append(f"walk {walk_diff_of_bf16_leaves:.3g} off")
    read.update(root_gain_rel=root_below.rel_err,
                walk_max_abs_diff=walk_diff_of_bf16_leaves)
    return faults, read


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
    note("data", rows=n_train, heldout_rows=n_held, columns=x.shape[1],
         label_mean=float(y_train.mean()), draw_s=draw_s, bin_s=bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit("train_wide: a traced run needs warmup_iters >= 2")
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

    t = time.perf_counter()
    trees = reference.parse_model(bst.model_to_string())
    q = int(cell["quality_trees"])
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
    # the precision below: the split's sums rounded to bfloat16
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

    t = time.perf_counter()
    first = trees[:q]
    # in blocks of rows: the walk takes its rows to float64
    raw_held = np.concatenate([
        reference.walk(first, x_held[lo:lo + WALK_ROWS])
        for lo in range(0, n_held, WALK_ROWS)])
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
    # the precision below: the walk over leaf values kept in bfloat16
    bf16_trees = [dataclasses.replace(
        tr, leaf_value=reference_mesh.to_bf16(tr.leaf_value))
        for tr in first]
    diff_bf16 = float(np.max(np.abs(
        served - reference.walk(bf16_trees, x_held[:n_ref]))))
    note("model_check", rows=n_ref, trees=len(first), max_abs_diff=diff,
         allowed=allowed, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    refused, read = control_faults(
        trees[0], tree0_scores.scores[:n_train], ref0, root, below,
        diff_bf16, allowed, x_train, y_train, params, tol)
    note("control", correct=not refused, faults=refused, **read,
         check_s=time.perf_counter() - t)
    if not refused:
        faults.append("the checks passed the control kept in bfloat16: "
                      "they cannot tell it from the program")
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
