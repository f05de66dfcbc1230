"""Cells of kind ``train_rank``: ``lgb.train`` with a ranking objective
over query-grouped rows, timed by ``kinds/train.py``'s window clock.

Traffic: ``queries`` training queries of ``msltr_data.py``'s fixed
lengths (``rows`` documents in all) through ``lgb.Dataset(x, label,
group=...)`` and ``lgb.train``, no ``valid_sets``; ``heldout_queries``
further queries stay on the host for the quality check.  Closed loop:
one job, the next iteration starts when the last ends.

Everything that decides ``correct`` runs after the window, against
``reference_rank.py`` and ``reference.py``: the expected route and no
fallback event; nothing built inside the window; finite scores; the
program's gradients at the model's own training scores against the
float64 loops, on whole queries; tree 0's root split against the
reference's gradients at the start score; the first trees walked in
numpy against ``Booster.predict``; held-out NDCG@k as a share of the
oracle's.

``auc_share`` (the benchmark's end-to-end quality metric, which every
cell reports) is ``kinds/train.py``'s formula with "relevant" for the
positive class: the pooled held-out AUC of grade > 0 against grade 0 of
the model's first ``auc_trees`` trees, as a share of the noiseless
relevance's on the same rows.  The tree count is fixed and large, so
that the share's spread over seeds stays under half its bound (the
readings are in ``PERF.md``): a run that closed its window with fewer
trees (a traced run does) trains the missing ones after the window,
outside every clock, so the number does not depend on the run's speed.

Before any data is made the kind checks that the program exposes what
the cell reads (the rank objective's pair counters, which the
``Boosting`` span carries): a program without them exits 1 with one
line, at once.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

import msltr_data
import reference
import reference_rank
from kinds.train import WindowClock, _window_spans, check_route
from kinds_common import CompileCount, fallback_events


def gradient_queries(sizes: np.ndarray, count: int, seed: int) -> np.ndarray:
    """At least ``count`` whole queries for the gradient check: the
    longest, a length-1 query, one of each power-of-two length class
    (the program buckets by those), the rest drawn from ``seed``."""
    must = {int(np.argmax(sizes)), int(np.argmin(sizes))}
    klass = np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)
    for k in np.unique(klass):
        must.add(int(np.flatnonzero(klass == k)[0]))
    rest = np.setdiff1d(np.arange(len(sizes)), sorted(must))
    rng = np.random.default_rng([seed, 7])
    extra = rng.choice(rest, size=max(min(count - len(must), len(rest)), 0),
                       replace=False)
    return np.sort(np.concatenate([sorted(must), extra]).astype(np.int64))


def run(ctx) -> dict:
    cell, config, note = ctx.cell, ctx.config, ctx.note
    import lightgbm_tpu as lgb
    from lightgbm_tpu.objective import rank as program_rank
    if not hasattr(program_rank.RankingObjective, "span_args"):
        print("train_rank: this program's rank objective has no "
              "span_args (the Boosting span's pairs_visited and "
              "pair_slots); the cell cannot be read", file=sys.stderr)
        raise SystemExit(1)
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs import tracer as obs_tracer

    compiles = CompileCount()
    params = dict(config["params"])
    tol = cell["tolerances"]
    k_at = int(params["ndcg_eval_at"][0])

    t = time.perf_counter()
    x, grade, oracle, train_len, held_len = msltr_data.make_msltr_like(
        ctx.seed, n_train=int(cell["queries"]),
        n_held=int(cell["heldout_queries"]), longest=int(cell["longest"]),
        total_rows=cell["rows"])
    draw_s = time.perf_counter() - t
    n_train = int(train_len.sum())
    if cell["rows"] is not None and n_train != int(cell["rows"]):
        raise RuntimeError(f"{n_train} training rows, not {cell['rows']}")
    x_train, y_train = x[:n_train], grade[:n_train]
    x_held, y_held, oracle_held = x[n_train:], grade[n_train:], \
        oracle[n_train:]
    qb_train = np.concatenate([[0], np.cumsum(train_len)])
    qb_held = np.concatenate([[0], np.cumsum(held_len)])

    t = time.perf_counter()
    dtrain = lgb.Dataset(x_train, label=y_train, group=train_len,
                         params={"max_bin": params["max_bin"]})
    dtrain.construct()
    bin_s = time.perf_counter() - t
    note("data", rows=n_train, queries=len(train_len),
         longest=int(train_len.max()), shortest=int(train_len.min()),
         heldout_rows=len(y_held), heldout_queries=len(held_len),
         grade_shares=(np.bincount(y_train.astype(np.int64), minlength=5)
                       / n_train).tolist(),
         draw_s=draw_s, bin_s=bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit("train_rank: a traced run needs "
                             "warmup_iters >= 2")
        tracer = obs_tracer
        trace_dir = os.path.join(ctx.out_dir, "profile")
    clock = WindowClock(lgb, cell, ctx.seconds, compiles, trace_dir, tracer,
                        os.path.join(ctx.out_dir, "spans.jsonl"))
    events0 = obs_events.totals()
    t_train = time.perf_counter()
    bst = lgb.train(params, dtrain, num_boost_round=1_000_000,
                    callbacks=[clock])
    if clock.t_close is None:
        raise RuntimeError("training ended before the window closed")
    peak = ctx.memory_peak_bytes()
    window_s = clock.t_close - clock.t_open
    iters = clock.iters_at_close - clock.iters_at_open
    objective = bst._inner.objective
    spans = _window_spans(tracer)
    trees_at_close = bst.num_trees()
    note("window", iterations=iters, window_s=window_s,
         warmup_s=clock.t_open - t_train, trees=bst.num_trees(),
         compiles_before_window=clock.compiles_at_open,
         cache_hits=compiles.cache_hits,
         compiles_in_window=clock.compiles_in_window,
         traced_slice_iterations=clock.slice_iters,
         pair_counters=objective.span_args())

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

    # the program's gradients at the model's own scores (not at zeros:
    # all-tied scores test only the tie rule), on whole queries
    t = time.perf_counter()
    rank_kw = dict(
        sigmoid=float(params.get("sigmoid", 1.0)),
        truncation_level=int(params.get("lambdarank_truncation_level", 30)),
        norm=bool(params.get("lambdarank_norm", True)))
    # through the gradient program the loop itself ran (compiled before
    # the window): ``get_gradients`` plus the row padding
    score_dev = bst._inner.get_training_score()
    score = np.asarray(score_dev)[0, :n_train]
    g_prog, h_prog = (np.asarray(a, np.float64)[0, :n_train] for a in
                      bst._inner._compute_gradients(score_dev))
    picked = gradient_queries(train_len, int(cell["gradient_queries"]),
                              ctx.seed)
    g_ref, h_ref = reference_rank.lambdarank(
        score, y_train, qb_train, queries=picked, loops=True, **rank_kw)
    worst = {"grad": 0.0, "hess": 0.0}
    for q in picked:
        rows = slice(qb_train[q], qb_train[q + 1])
        for name, got, want in (("grad", g_prog[rows], g_ref[rows]),
                                ("hess", h_prog[rows], h_ref[rows])):
            top = float(np.max(np.abs(want)))
            err = float(np.max(np.abs(got - want)))
            rel = err / top if top > 0 else (0.0 if err == 0 else np.inf)
            worst[name] = max(worst[name], rel)
    if not (np.all(np.isfinite(g_prog)) and np.all(np.isfinite(h_prog))
            and max(worst.values()) <= tol["gradient_rel"]):
        faults.append(f"get_gradients differs from the float64 loops by "
                      f"{worst} of a query's largest value over "
                      f"{len(picked)} queries (allowed "
                      f"{tol['gradient_rel']})")
    note("gradient_check", queries=len(picked),
         rows=int(train_len[picked].sum()),
         longest=int(train_len[picked].max()),
         shortest=int(train_len[picked].min()),
         score_spread=float(np.ptp(score)), worst_rel=worst,
         allowed=tol["gradient_rel"], check_s=time.perf_counter() - t)

    # auc_share reads a fixed number of trees whatever the run's speed:
    # the missing ones are trained here, after the window and every check
    # of the window's own state, with the programs the window ran
    t = time.perf_counter()
    auc_trees = int(cell["auc_trees"])
    if tracer is not None:
        tracer.disable()
    while bst.num_trees() < auc_trees:
        bst.update()
    note("top_up", trees_at_close=trees_at_close, trees=bst.num_trees(),
         auc_trees=auc_trees, built=compiles.built - clock.compiles_at_open
         - clock.compiles_in_window, top_up_s=time.perf_counter() - t)

    t = time.perf_counter()
    # every tree: the window's end left ``best_iteration`` at its close
    trees = reference.parse_model(bst.model_to_string(num_iteration=-1))
    q_trees = int(cell["quality_trees"])
    n_bad = sum(not np.all(np.isfinite(tr.leaf_value)) for tr in trees)
    if len(trees) < q_trees:
        faults.append(f"{len(trees)} trees in all, fewer than {q_trees}")
    # tree 0 grew from the gradients at the start score, which for a
    # ranking objective is 0 for every row (no boost_from_average)
    g0, h0 = reference_rank.lambdarank(
        np.zeros(n_train), y_train, qb_train, **rank_kw)
    root = reference_rank.root_split_gh(
        x_train, g0, h0, trees[0],
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        min_data_in_leaf=int(params.get("min_data_in_leaf", 20)),
        min_sum_hessian_in_leaf=float(
            params.get("min_sum_hessian_in_leaf", 1e-3)))
    if not root.ok(tol["root_gain_rel"], tol["root_grid_floor"]):
        faults.append(f"tree 0's root split fails the reference: {root}")
    note("root_split", **root.__dict__, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    first = trees[:q_trees]
    raw_held = reference.walk(first, x_held)
    ndcg_model = reference_rank.ndcg_at(k_at, raw_held, y_held, qb_held)
    ndcg_oracle = reference_rank.ndcg_at(k_at, oracle_held, y_held, qb_held)
    ndcg_const = reference_rank.ndcg_at(k_at, np.zeros(len(y_held)), y_held,
                                        qb_held)
    ndcg_share = (100.0 * (ndcg_model - ndcg_const)
                  / (ndcg_oracle - ndcg_const))
    if not ndcg_share >= tol["ndcg_share_floor"]:
        faults.append(f"ndcg_share {ndcg_share:.2f} is under "
                      f"{tol['ndcg_share_floor']}")
    note("quality", trees=len(first), at=k_at, ndcg=ndcg_model,
         ndcg_oracle=ndcg_oracle, ndcg_const=ndcg_const,
         ndcg_share=ndcg_share, check_s=time.perf_counter() - t)

    t = time.perf_counter()
    auc_first = trees[:auc_trees]
    # the walk stops at each quarter of the trees, so the note can say
    # how the share grows with them
    by_trees, raw_auc, lo = {}, 0.0, 0
    auc_oracle = reference.auc(oracle_held, y_held)
    for k in sorted({max(auc_trees * i // 4, 1) for i in (1, 2, 3, 4)}):
        raw_auc = raw_auc + reference.walk(auc_first[lo:k], x_held)
        by_trees[k] = (100.0 * (reference.auc(raw_auc, y_held) - 0.5)
                       / (auc_oracle - 0.5))
        lo = k
    auc_share = by_trees[auc_trees]
    if not (len(trees) >= auc_trees and np.isfinite(auc_share)):
        faults.append(f"auc_share {auc_share} of {len(auc_first)} trees "
                      f"(asked: {auc_trees})")
    note("auc", trees=len(auc_first), rows=len(y_held),
         auc_oracle=auc_oracle, auc_share=auc_share,
         auc_share_by_trees=by_trees, check_s=time.perf_counter() - t)

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
        "attempted": trees_at_close,
        "failed": n_bad,
        "setup_end": clock.t_open,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_iters_per_s": iters / window_s,
                       "auc_share": auc_share},
        "spans": spans,
        "trace_dir": trace_dir,
        "counters": {"iterations": iters,
                     "slice_iterations": clock.slice_iters},
    }
