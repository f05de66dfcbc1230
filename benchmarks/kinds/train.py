"""Cells of kind ``train``: ``lgb.train`` timed between two host pulls.

The clock is ``chip_smoke.py``'s: a callback lets ``warmup_iters``
iterations go by (every program compiles there), pulls the train score
to the host, and starts the window.  After each later iteration it reads
the host clock without a pull; once ``--seconds`` have passed it pulls
again, stops the clock and ends training.  The rate is the iterations
between the two pulls over the time between them.  Everything that
decides ``correct`` runs after the window, against ``reference.py``.
"""
from __future__ import annotations

import os
import time

import numpy as np

import higgs_data
import reference
import trace as bench_trace
from kinds_common import CompileCount, fallback_events

def host_pull(bst) -> float:
    """Execution barrier: the train scores, summed, on the host."""
    import jax.numpy as jnp
    return float(jnp.sum(bst._inner.train_score))


class WindowClock:
    """The callback.  ``lgb.train`` calls it after every iteration."""

    def __init__(self, lgb, cell, seconds, compiles, trace_dir, tracer,
                 spans_path=None):
        self.lgb = lgb
        self.warmup = int(cell["warmup_iters"])
        self.seconds = float(seconds)
        self.compiles = compiles
        self.trace_dir = trace_dir          # None: no profiler slice
        self.slice_s = float(cell.get("trace_slice_s", 5.0))
        self.tracer = tracer
        self.spans_path = spans_path
        self.t_open = self.t_close = None
        self.iters_at_open = 0
        self.iters_at_close = 0
        self.compiles_at_open = 0
        self.compiles_in_window = 0
        self.score_sums = []
        self._slice = None                  # open TraceAnnotation
        self._slice_t0 = 0.0
        self.slice_iters = 0

    # the profiler's slice sits at the head of the window, closed by a
    # pull of its own so that its last iteration's device work is in it
    def _open_slice(self):
        import jax
        jax.profiler.start_trace(self.trace_dir)
        self.tracer.annotate(True)
        self._slice = jax.profiler.TraceAnnotation(bench_trace.SLICE_NAME)
        self._slice.__enter__()
        self._slice_t0 = time.perf_counter()

    def _close_slice(self, bst):
        import jax
        host_pull(bst)
        self._slice.__exit__(None, None, None)
        self._slice = None
        self.tracer.annotate(False)
        jax.profiler.stop_trace()

    def __call__(self, env):
        done = env.iteration + 1
        if self.tracer is not None and not self.tracer.enabled:
            # after the booster is built, so the traced run grows its
            # trees with the untraced run's programs: a tracer live at
            # construction compiles the grow program anew with device
            # counters and dispatches three root-scale probes a tree
            self.tracer.enable(self.spans_path)
        if done < self.warmup:
            return
        if done == self.warmup:
            # the program defers its host trees and flushes them every 32
            # iterations through a jit of its own; saving the model is
            # the public call that flushes, so that program is built here
            # and not at iteration 32
            env.model.model_to_string()
            self.score_sums.append(host_pull(env.model))
            self.compiles_at_open = self.compiles.built
            self.iters_at_open = done
            if self.tracer is not None:
                self.tracer.instant("bench::window_open")
            if self.trace_dir is not None:
                self._open_slice()
            self.t_open = time.perf_counter()
            return
        now = time.perf_counter()
        if self._slice is not None:
            self.slice_iters += 1
            if (self.slice_iters >= 2
                    and now - self._slice_t0 >= self.slice_s) \
                    or now - self.t_open >= self.seconds:
                self._close_slice(env.model)
        if now - self.t_open >= self.seconds:
            self.score_sums.append(host_pull(env.model))
            self.t_close = time.perf_counter()
            self.iters_at_close = done
            self.compiles_in_window = (self.compiles.built
                                       - self.compiles_at_open)
            raise self.lgb.callback.EarlyStopException(env.iteration, [])


def check_route(bst, expect, chips, n_rows):
    """The route ``chip_smoke.py`` asserts, read from the cell's
    configuration instead of the code.  Returns a list of faults."""
    r = bst._inner._routing
    faults = []
    for key, want in expect.items():
        got = getattr(r, key)
        got = list(got) if isinstance(got, tuple) else got
        if got != want:
            faults.append(f"route.{key} is {got!r}, expected {want!r}")
    if chips > 1:
        import jax
        shards = bst._inner.grow._comb.addressable_shards
        rows = {str(s.device): int(s.data.shape[0]) for s in shards}
        share = n_rows / chips
        if sorted(rows) != sorted(str(d) for d in jax.devices()) or any(
                not share <= n <= share + 8192 for n in rows.values()):
            faults.append(f"comb is not ~{share:.0f} rows on each of "
                          f"{chips} devices: {rows}")
    return r, faults


def run(ctx) -> dict:
    cell, config, note = ctx.cell, ctx.config, ctx.note
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs import tracer as obs_tracer

    compiles = CompileCount()
    n_train = int(cell["rows"])
    n_valid = int(cell.get("valid_rows", 0))
    n_held = int(cell["heldout_rows"])
    params = dict(config["params"])

    t = time.perf_counter()
    x, y, logit = higgs_data.make_higgs_like(
        n_train + n_valid + n_held, seed=ctx.seed)
    draw_s = time.perf_counter() - t
    cut = n_train + n_valid
    x_held, y_held, logit_held = x[cut:], y[cut:], logit[cut:]
    x_train, y_train = x[:n_train], y[:n_train]

    t = time.perf_counter()
    dtrain = lgb.Dataset(x_train, label=y_train,
                         params={"max_bin": params["max_bin"]})
    dtrain.construct()
    valid_sets = None
    if n_valid:
        dvalid = lgb.Dataset(x[n_train:cut], label=y[n_train:cut],
                             reference=dtrain)
        dvalid.construct()
        valid_sets = [dvalid]
    bin_s = time.perf_counter() - t
    note("data", rows=n_train, valid_rows=n_valid, heldout_rows=n_held,
         label_mean=float(y_train.mean()), draw_s=draw_s, bin_s=bin_s)

    trace_dir = None
    tracer = None
    if ctx.trace:
        # the callback turns the tracer on after the first iteration
        if int(cell["warmup_iters"]) < 2:
            raise SystemExit("train: a traced run needs warmup_iters >= 2")
        tracer = obs_tracer
        trace_dir = os.path.join(ctx.out_dir, "profile")
    clock = WindowClock(lgb, cell, ctx.seconds, compiles, trace_dir, tracer,
                        os.path.join(ctx.out_dir, "spans.jsonl"))
    events0 = obs_events.totals()
    t_train = time.perf_counter()
    bst = lgb.train(params, dtrain, num_boost_round=1_000_000,
                    valid_sets=valid_sets, callbacks=[clock])
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
    trees = reference.parse_model(bst.model_to_string())
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
    tol = cell["tolerances"]
    if not root.ok(tol["root_gain_rel"], tol["root_grid_floor"]):
        faults.append(f"tree 0's root split fails the reference: {root}")
    note("root_split", **root.__dict__, check_s=time.perf_counter() - t)

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


def _window_spans(tracer):
    """The tracer's span events that began inside the window."""
    if tracer is None:
        return []
    events = tracer.events
    opened = [e["ts"] for e in events if e["name"] == "bench::window_open"]
    if not opened:
        return []
    return [e for e in events if e["ph"] == "X" and e["ts"] >= opened[0]]
