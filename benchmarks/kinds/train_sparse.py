"""Cells of kind ``train_sparse``: ``lgb.train`` on a scipy CSR matrix of
one-hot columns, timed by ``kinds/train.py``'s window clock over a fixed
count of iterations (``CountClock``).

Traffic: one job, closed loop.  ``rows`` training rows and, after them,
``heldout_rows`` more from ``expo_data.make_expo_like(seed)``: the
training rows are the generator's one table, the same for every seed
as the Expo file is one file, and the held-out rows are the seed's own
draw (``expo_data.py`` says why).  The training rows go through
``lgb.Dataset(csr, label)`` and ``lgb.train`` as they are, with no
``valid_sets`` and no parameter the configuration does not state.

**The probe.**  Before any full-size array is made, the first
``probe_rows`` rows of the same seed go through ``lgb.Dataset`` and a
``lgb.Booster`` (no iteration, nothing compiled) and the route it
decided is read: the cell is about the EFB bundles staying in the comb,
and a program that expands 700 one-hot columns into 700 comb lanes
would need six times the chip's memory at the timed size.  Where the
route does not say ``efb: bundled`` (a program from before ISSUE 36
says nothing of ``efb``) the run says so and exits 1, in seconds.

``correct`` is ``kinds/train.py``'s list (the expected route and no
fallback event, nothing built inside the window, finite scores, the
first ``quality_trees`` trees - here all that a run has when its window
closes - walked in numpy over RAW held-out rows against
``Booster.predict``, their ``auc_share``) plus, against ``reference_efb.py``
over all training rows, after the window and outside every clock:

* every training row's score after tree 0 - pulled once, during the
  warm-up, from the run that is then timed - is the value of the leaf
  the float64 walk over raw stored values sends that row to
  (``row_score_abs``; 0 rows may differ: a membership set that
  misplaces one default-bin level strands every row of that level);
* tree 0's root gain against the float64 gain of the same split
  (``root_gain_rel``) and against the best root gain of every logical
  column (``root_best_floor``);
* tree 0's leaf values against ``-lr G / H`` from float64 sums of the
  bf16 operands the configuration states (``leaf_value_abs``: between
  what the program reads and what leaf sums kept in bfloat16 would,
  which every run prints beside it; ``PERF.md``);
* the comb's line is ``comb_line_bytes`` wide.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

import expo_data
import reference
import reference_efb
import reference_mesh
from kinds.train import WindowClock, _window_spans, check_route
from kinds.train_mesh import Tree0Scores
from kinds_common import CompileCount, fallback_events


class CountClock(WindowClock):
    """``kinds/train.py``'s clock with the window's length given as a
    COUNT of iterations, ``round(iters_per_window_s x --seconds)`` of
    the cell's file, instead of the first iteration past ``--seconds``.

    The clock's reading is the same thing - iterations between two host
    pulls of the train score over the time between them, the host
    running ahead of the device in between as it does for every user -
    but which iterations are timed no longer depends on how fast they
    ran.  In the other train cells that does not matter: their trees
    cost the same.  Here a tree costs more the later it is grown (a
    split on a one-hot column peels one level off its parent and the
    scan moves the whole parent, and the later trees peel more), so a
    run that closed its window an iteration later read a lower rate:
    29, 32 and 32 iterations read 0.712 / 0.687 / 0.677 iters/s (my
    chip run, PR 36).  The count is set so that the window is about
    ``--seconds`` long on the v5e; ``PERF.md`` has the readings."""

    def __init__(self, *args, iterations: int):
        super().__init__(*args)
        self.iterations = int(iterations)

    def __call__(self, env):
        if self.t_open is not None:
            # the parent closes the window at the first iteration past
            # ``seconds``: never before the count is reached, then at once
            last = env.iteration + 1 - self.iters_at_open >= self.iterations
            self.seconds = 0.0 if last else float("inf")
        super().__call__(env)


def probe_route(lgb, params, cell, seed) -> dict:
    """The route a small table of the same shape takes."""
    n = int(cell["probe_rows"])
    x, y, _ = expo_data.make_expo_like(n, seed=seed, table_rows=n)
    d = lgb.Dataset(x, label=y, params={"max_bin": params["max_bin"]})
    return lgb.Booster(params, d)._inner.routing_info()


def check_tree0(tree0, scores0, cols, y, params, tol):
    """(faults, what was read): tree 0 against the float64 reference
    over all training rows."""
    faults = []
    n = len(y)
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    leaf = reference_efb.leaf_of_rows(tree0, cols)
    y64 = np.asarray(y, np.float64)
    ref = reference_mesh.LeafSums(
        count=np.bincount(leaf, minlength=tree0.num_leaves).astype(np.int64),
        sum_y=np.bincount(leaf, weights=y64, minlength=tree0.num_leaves),
        p=float(y64.mean()), leaf=leaf, y=y64)
    want = ref.leaf_values(lr, l2, operands="bf16")
    value_diff = float(np.max(np.abs(tree0.leaf_value - want)))
    if not value_diff <= tol["leaf_value_abs"]:
        worst = int(np.argmax(np.abs(tree0.leaf_value - want)))
        faults.append(
            f"tree 0's leaf values differ from -lr G / H of the float64 "
            f"sums by up to {value_diff} (leaf {worst}: "
            f"{tree0.leaf_value[worst]} against {want[worst]}; allowed "
            f"{tol['leaf_value_abs']})")
    off = np.abs(scores0[:n] - tree0.leaf_value[leaf])
    strays = int(np.count_nonzero(~(off <= tol["row_score_abs"])))
    if strays:
        faults.append(
            f"{strays} training rows scored another leaf's value after "
            f"tree 0 than the leaf the reference walks them to")
    # the precision below: every leaf's two sums rounded to bfloat16
    below_value, _ = ref.merged_in_bf16(lr, 1, l2)
    return faults, {
        "leaves": int(tree0.num_leaves), "rows": n,
        "leaf_value_max_abs_diff": value_diff,
        "leaf_value_max_abs_diff_f64_operands": float(np.max(np.abs(
            tree0.leaf_value - ref.leaf_values(lr, l2)))),
        "leaf_value_max_abs_diff_of_bf16_sums": float(np.max(np.abs(
            below_value - want))),
        "rows_in_another_leaf": strays,
        "row_score_max_abs_diff": float(off.max()),
        "smallest_leaf": int(ref.count.min()),
        "largest_leaf": int(ref.count.max()),
        "depth": tree0.depth()}


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
    want_efb = config["expect_route"]["efb"]

    t = time.perf_counter()
    probed = probe_route(lgb, params, cell, ctx.seed)
    note("probe", rows=int(cell["probe_rows"]), efb=probed.get("efb"),
         path=probed.get("path"), reasons=probed.get("reasons"),
         probe_s=time.perf_counter() - t)
    if probed.get("efb") != want_efb:
        print(f"train_sparse: the route of a {cell['probe_rows']}-row probe "
              f"says efb={probed.get('efb')!r}, not {want_efb!r} (path "
              f"{probed.get('path')!r}): this program does not keep the EFB "
              f"bundles in the comb, and {n_train} x "
              f"{config['n_features']} rows would not fit the chip "
              "unbundled", file=sys.stderr)
        raise SystemExit(1)

    t = time.perf_counter()
    x, y, logit = expo_data.make_expo_like(n_train + n_held, seed=ctx.seed,
                                           table_rows=n_train)
    draw_s = time.perf_counter() - t
    x_train, y_train = x[:n_train], y[:n_train]
    x_held, y_held, logit_held = x[n_train:], y[n_train:], logit[n_train:]

    t = time.perf_counter()
    dtrain = lgb.Dataset(x_train, label=y_train,
                         params={"max_bin": params["max_bin"]})
    dtrain.construct()
    bin_s = time.perf_counter() - t
    note("data", rows=n_train, heldout_rows=n_held, columns=x.shape[1],
         stored=int(x_train.nnz), label_mean=float(y_train.mean()),
         draw_s=draw_s, bin_s=bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit(
                "train_sparse: a traced run needs warmup_iters >= 2")
        tracer = obs_tracer
        trace_dir = os.path.join(ctx.out_dir, "profile")
    clock = CountClock(
        lgb, cell, ctx.seconds, compiles, trace_dir, tracer,
        os.path.join(ctx.out_dir, "spans.jsonl"),
        iterations=max(2, round(float(cell["iters_per_window_s"])
                                * ctx.seconds)))
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
    if layout["comb_line_bytes"] != int(cell["comb_line_bytes"]):
        faults.append(f"a comb line is {layout['comb_line_bytes']} bytes, "
                      f"expected {cell['comb_line_bytes']}")
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
    # what a tree cost: the rows of its parents, summed, over the rows
    # (the scan moves a parent whole).  It grows with the tree's number
    # here, which is why the window is a count of iterations
    note("tree_cost", window_trees=[clock.iters_at_open,
                                    clock.iters_at_close],
         row_visits_per_row=[
             round(float(np.sum(t.internal_count[:int(t.num_leaves) - 1],
                                dtype=np.float64)) / n_train, 3)
             for t in bst._models])
    q = int(cell["quality_trees"])
    n_bad = sum(not np.all(np.isfinite(tr.leaf_value)) for tr in trees)
    if len(trees) < q:
        faults.append(f"{len(trees)} trees in all, fewer than {q}")
    cols = reference_efb.Columns(x_train)
    root = reference_efb.root_gains(
        cols, y_train, trees[0],
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        min_data_in_leaf=int(params.get("min_data_in_leaf", 20)),
        min_sum_hessian_in_leaf=float(
            params.get("min_sum_hessian_in_leaf", 1e-3)))
    if not root.ok(tol["root_gain_rel"], tol["root_best_floor"]):
        faults.append(f"tree 0's root split fails the reference: {root}")
    note("root_split", **root.__dict__, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    tree0_faults, read = check_tree0(trees[0], tree0_scores.scores, cols,
                                     y_train, params, tol)
    faults += tree0_faults
    note("tree0_leaves", **read, allowed_value=tol["leaf_value_abs"],
         allowed_row_score=tol["row_score_abs"],
         check_s=time.perf_counter() - t)
    del cols

    t = time.perf_counter()
    first = trees[:q]
    held_cols = reference_efb.Columns(x_held)
    raw_ten = reference_efb.walk(first[:10], held_cols)
    raw_held = raw_ten + reference_efb.walk(first[10:], held_cols)
    auc_oracle = reference.auc(logit_held, y_held)

    def share(raw):
        return 100.0 * (reference.auc(raw, y_held) - 0.5) / (auc_oracle - 0.5)

    auc_share = share(raw_held)
    if not auc_share >= tol["auc_share_floor"]:
        faults.append(f"auc_share {auc_share:.2f} is under "
                      f"{tol['auc_share_floor']}")
    # (the first ten trees' beside it: what ISSUE 36 named, and what
    # the cell's file says of the two)
    note("quality", trees=len(first), auc=reference.auc(raw_held, y_held),
         auc_oracle=auc_oracle, auc_share=auc_share,
         auc_share_first_10=share(raw_ten), check_s=time.perf_counter() - t)

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
