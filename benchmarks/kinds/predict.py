"""Cells of kind ``predict``: a forest made from the seed, loaded with
``lgb.Booster(model_str=...)`` and served by ``Booster.predict`` in a
closed loop: ``callers`` (1) caller sends ``request_rows``-row requests
back to back from a ``pool_rows``-row pool, in the pool's order.  The
benchmark times each request itself on the host clock (the program's
own latency histograms step by 19%).
"""
from __future__ import annotations

import os
import time

import numpy as np

import forest
import higgs_data
import reference
import trace as bench_trace
from kinds_common import CompileCount, fallback_events


def run(ctx) -> dict:
    cell, note = ctx.cell, ctx.note
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.ops import routing

    if int(cell.get("callers", 1)) != 1:
        raise SystemExit("kinds/predict.py drives one caller")
    compiles = CompileCount()
    n_req, n_pool = int(cell["request_rows"]), int(cell["pool_rows"])
    n_check = int(cell["check_rows"])
    slices = n_pool // n_req
    if n_check % n_req or n_pool % n_req:
        raise SystemExit("check_rows and pool_rows are whole requests")

    t = time.perf_counter()
    text = forest.make_forest(ctx.seed, int(cell["trees"]),
                                 int(cell["leaves"]))
    trees = reference.parse_model(text)
    depth = max(tr.depth() for tr in trees)
    forest_s = time.perf_counter() - t
    pool, _, _ = higgs_data.make_higgs_like(n_pool, seed=ctx.seed)
    bst = lgb.Booster(model_str=text)
    note("forest", trees=len(trees), leaves=max(tr.num_leaves
                                                for tr in trees),
         max_depth=depth, text_bytes=len(text), make_s=forest_s)

    faults = []
    d = bst._predict_route(routing, bst._models, pred_leaf=False,
                           pred_contrib=False, early_stop=False)
    if d.path != "compiled":
        faults.append(f"predict route is {d.path} ({d.reasons})")
    events0 = obs_events.totals()

    def request(i: int):
        lo = (i % slices) * n_req
        t0 = time.perf_counter()
        out = bst.predict(pool[lo:lo + n_req], raw_score=True)
        return time.perf_counter() - t0, out

    for i in range(int(cell["warmup_requests"])):
        request(i)
    eng = bst.serving_engine()
    stats0 = eng.stats()
    built0 = compiles.built
    trace_dir = os.path.join(ctx.out_dir, "profile") if ctx.trace else None
    slice_s = float(cell.get("trace_slice_s", 5.0))
    slice_dispatches = 0
    sliced = None
    if ctx.trace:
        jax.profiler.start_trace(trace_dir)
        sliced = jax.profiler.TraceAnnotation(bench_trace.SLICE_NAME)
        sliced.__enter__()

    def close_slice() -> int:
        sliced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return eng.stats()["dispatches"] - stats0["dispatches"]

    lat, outs, bad = [], [], 0
    t_open = time.perf_counter()
    while True:
        i = len(lat)
        dt, out = request(i)
        lat.append(dt)
        bad += not (out.shape == (n_req,) and np.all(np.isfinite(out)))
        if i % slices < n_check // n_req:
            outs.append((i % slices, out))
        now = time.perf_counter()
        done = now - t_open >= ctx.seconds
        if sliced is not None and (done or now - t_open >= slice_s):
            slice_dispatches = close_slice()
            sliced = None
        if done:
            break
    t_close = time.perf_counter()
    peak = ctx.memory_peak_bytes()
    stats1 = eng.stats()
    window_s = t_close - t_open
    lat_ms = np.array(lat) * 1e3
    note("window", requests=len(lat), rows=len(lat) * n_req,
         window_s=window_s, latency_ms_p50=float(np.median(lat_ms)),
         latency_ms_max=float(lat_ms.max()), latency_samples=len(lat),
         dispatches=stats1["dispatches"] - stats0["dispatches"],
         programs=stats1["programs"], buckets=stats1["buckets"],
         compiles_in_window=compiles.built - built0)

    if stats1["programs"] != stats0["programs"] or compiles.built != built0:
        faults.append(f"a program was built inside the window: "
                      f"{stats0['programs']} -> {stats1['programs']}")
    fell = fallback_events(obs_events, events0)
    if fell:
        faults.append(f"routing fallbacks fired: {fell}")
    t = time.perf_counter()
    ref = reference.walk(trees, pool[:n_check])
    worst = max(float(np.max(np.abs(
        out - ref[s * n_req:(s + 1) * n_req]))) for s, out in outs)
    tol = cell["tolerances"]["walk_abs"]
    if not worst <= tol:
        faults.append(f"served scores differ from the reference walk by "
                      f"{worst} (allowed {tol})")
    note("model_check", rows=n_check, requests_compared=len(outs),
         max_abs_diff=worst, allowed=tol, check_s=time.perf_counter() - t)
    if faults:
        note("faults", faults=faults)
    return {
        "correct": not faults and not bad,
        "attempted": len(lat),
        "failed": int(bad),
        "setup_end": t_open,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "predict_rows_per_s": len(lat) * n_req / window_s,
            "predict_p95_ms": float(np.percentile(lat_ms, 95)),
        },
        "spans": [],
        "trace_dir": trace_dir,
        "counters": {"dispatches": slice_dispatches},
    }
