"""Benchmark: boosting iterations/sec on a Higgs-shaped problem.

Metric of record (BASELINE.json): boosting iters/sec on Higgs-like data.
The reference baseline is 500 iterations in 130.094 s (docs/Experiments.rst:
110-124, 2x E5-2690v4) = 3.843 iters/sec with num_leaves=255, 28 features.

Run: ``python bench.py`` (full, needs the TPU) or ``python bench.py --smoke``
(small shapes, any backend).  Prints ONE JSON line — a schema-versioned
record (``profile_lib.BENCH_SCHEMA``); ``--json PATH`` also writes it to a
file (the BENCH_r*.json round artifacts), readable with
``python -m lightgbm_tpu.obs report --bench``.

With ``LGBM_TPU_TRACE`` set the whole run is traced (obs tracer): the
record gains per-phase breakdowns (BeforeTrain / Tree::grow /
UpdateScore and their ``::wait`` children ...), work counter totals and
the per-iteration run-ledger trajectory (``obs/metrics.py``), and
``"traced": true`` flags that the barriers perturb the iters/sec number
— capture the metric of record and the phase profile in separate runs.
Every record (bench/v3) carries a hostname-free provenance header and
the engaged knob set; compare two records with
``python -m lightgbm_tpu.obs diff A.json B.json`` and judge a traced
record against the analytical cost model with
``python -m lightgbm_tpu.obs report --bench --roofline``.

With ``LGBM_TPU_XPLANE=dir`` set the timed window additionally runs
under a ``jax.profiler`` xplane capture (tracing auto-enables so the
join has phases to work with): obs spans mirror as
``TraceAnnotation("obs::<phase>")`` and the record gains a ``device``
block — per-kernel device times decoded by the in-repo xplane reader
(``lightgbm_tpu.obs.xattr``).  Attribute it with
``python -m lightgbm_tpu.obs attr dir --bench REC.json --roofline``.
Like tracing, a captured run's iters/sec is not the metric of record.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))

REFERENCE_HIGGS_ITERS_PER_SEC = 500.0 / 130.094


def make_higgs_like(n_rows: int, n_features: int = 28, seed: int = 0):
    """Synthetic stand-in for the Higgs task (zero-egress environment):
    kinematic-style continuous features, nonlinear decision surface."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    # a few derived "high-level" features like Higgs' mass combinations
    w = rng.normal(size=(n_features,))
    logit = (x @ w * 0.3
             + 0.8 * x[:, 0] * x[:, 1]
             - 0.6 * np.abs(x[:, 2])
             + 0.5 * x[:, 3] ** 2)
    y = (logit + rng.logistic(size=n_rows) > 0).astype(np.float32)
    return x, y


def make_onehot_like(n_rows: int, n_onehot: int, n_features: int = 28,
                     seed: int = 0):
    """Higgs-style dense features PLUS ``n_onehot`` one-hot indicator
    columns (the sparse-tabular shape EFB exists for).  The default
    ``enable_bundle=true`` bundles the indicators into a handful of
    physical columns; since ISSUE 12 the physical fast path ingests
    them UNBUNDLED, so the EFB bench pair (tools/chip_plan.json
    bench_efb_*) sizes the graduated class directly."""
    x, y = make_higgs_like(n_rows, n_features, seed)
    rng = np.random.default_rng(seed + 1)
    c = rng.integers(0, n_onehot, size=n_rows)
    onehot = np.zeros((n_rows, n_onehot), np.float32)
    onehot[np.arange(n_rows), c] = 1.0
    return np.hstack([onehot, x]), y


def make_multiclass_like(n_rows: int, num_class: int,
                         n_features: int = 28, seed: int = 0):
    """Higgs-style dense features with a K-way label whose classes are
    separated by HIDDEN per-class split structure: every class gets a
    private feature-pair threshold rule on top of a shared linear
    field, so the learned trees differ per class and the K class trees
    of one boosting iteration do real, distinct work — the shape the
    ISSUE-19 batched-multiclass bench pair (tools/chip_plan.json
    bench_multiclass_batched / bench_multiclass_serial) sizes the ONE-
    dispatch-per-iteration saving on."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n_rows, n_features)).astype(np.float32)
    w = rng.normal(size=(n_features, num_class))
    logits = (x @ w) * 0.4
    for c in range(num_class):
        j0, j1 = rng.choice(n_features, size=2, replace=False)
        t0, t1 = rng.normal(scale=0.5, size=2)
        logits[:, c] += 1.5 * np.logical_xor(x[:, j0] > t0,
                                             x[:, j1] > t1)
    y = np.argmax(logits + rng.gumbel(size=logits.shape),
                  axis=1).astype(np.float32)
    return x, y


def make_categorical_like(n_rows: int, n_cats: int, n_cat_cols: int,
                          n_features: int = 28, seed: int = 0):
    """Higgs-style dense features PLUS ``n_cat_cols`` high-cardinality
    categorical columns with ``n_cats`` categories each (the Criteo-ish
    shape sorted-subset splits exist for).  Category frequencies are
    Zipf-skewed — a few head categories dominate and a long tail is
    rare — so ``cat_smooth``/``min_data_per_group`` filtering sees
    realistic counts.  A hidden good-subset per column drives the
    label, so subset candidates win over one-hot — the ISSUE-16 bench
    pair (tools/chip_plan.json bench_cat / bench_cat_onehot) sizes the
    graduated class directly."""
    x, y = make_higgs_like(n_rows, n_features, seed)
    rng = np.random.default_rng(seed + 2)
    probs = 1.0 / np.arange(1.0, n_cats + 1.0) ** 1.1
    probs /= probs.sum()
    cats = rng.choice(n_cats, size=(n_rows, n_cat_cols),
                      p=probs).astype(np.float32)
    flip = np.zeros(n_rows, np.float32)
    for j in range(n_cat_cols):
        good = rng.choice(n_cats, size=max(n_cats // 3, 1),
                          replace=False)
        flip += np.isin(cats[:, j], good)
    y = np.logical_xor(y > 0,
                       flip >= (n_cat_cols + 1) // 2).astype(np.float32)
    return np.hstack([cats, x]), y, list(range(n_cat_cols))


def run_bench(n_rows: int, num_iters: int, num_leaves: int,
              warmup: int, xplane: bool = True, onehot: int = 0,
              enable_bundle: bool = True, ckpt=None,
              categorical: str = "", cat_onehot: bool = False,
              multiclass: int = 0) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import events as obs_events

    # events are process-global; snapshot so THIS point's record only
    # carries events recorded during its own build/train (trace-time
    # fallbacks fire at grower construction, before the timed window —
    # a reset at t0 would lose them)
    _ev0 = obs_events.totals()
    # --onehot K appends K one-hot indicator columns (the EFB shape);
    # --no-bundle trains the unbundled-equivalent config — the ISSUE-12
    # bench pair that sizes the graduated fallback class on chip
    # --categorical K,C appends C categorical columns of K categories
    # (the cat-subset shape; ISSUE-16 bench pair); --cat-onehot trains
    # the same data with subset search disabled (one-hot candidates
    # only) — the pre-graduation baseline side
    # --multiclass K trains a K-class softmax model (K trees per
    # boosting iteration) on hidden per-class split structure — the
    # ISSUE-19 A/B pair compares the batched ONE-dispatch grow
    # (LGBM_TPU_MC_BATCH=auto) against the serial-K loop (=0) on the
    # same data; trees are byte-identical, so the delta is pure
    # dispatch/compile floor
    cat_cols = []
    n_cats = 0
    if multiclass:
        x, y = make_multiclass_like(n_rows, multiclass)
    elif categorical:
        n_cats, n_cat_cols = (int(v) for v in categorical.split(","))
        x, y, cat_cols = make_categorical_like(n_rows, n_cats,
                                               n_cat_cols)
    elif onehot:
        x, y = make_onehot_like(n_rows, onehot)
    else:
        x, y = make_higgs_like(n_rows)
    ds_params = {"max_bin": 255, "enable_bundle": enable_bundle}
    if cat_cols:
        ds_params["min_data_in_bin"] = 1
    train = lgb.Dataset(x, label=y, params=ds_params,
                        categorical_feature=cat_cols or "auto")
    params = {
        "objective": "multiclass" if multiclass else "binary",
        "num_leaves": num_leaves,
        "learning_rate": 0.1,
        "verbosity": -1,
        "max_bin": 255,
        "enable_bundle": enable_bundle,
        "metric": "multi_logloss" if multiclass else "auc",
        "metric_freq": 0,
    }
    if multiclass:
        params["num_class"] = multiclass
    if cat_cols:
        params["min_data_per_group"] = 5
        # one-hot baseline: a threshold above the cardinality keeps
        # every categorical split a single-category candidate
        params["max_cat_to_onehot"] = (n_cats + 1 if cat_onehot
                                       else min(n_cats - 1, 4))
    booster = lgb.Booster(params=params, train_set=train)

    def force_sync():
        # execution barrier: a host pull of the train scores
        import jax.numpy as jnp
        return float(jnp.sum(booster._inner.train_score))

    # checkpoint/resume (ISSUE 13, --resume): a preempted bench step
    # picks its training back up from the latest ckpt/v1 snapshot under
    # ckpt_dir instead of restarting tree 0 (chip_run re-runs the
    # quarantined step; the merged journal shows the resume), and the
    # timed window snapshots every LGBM_TPU_CKPT_EVERY iterations — the
    # overhead delta vs the un-checkpointed record IS the capture
    # (PERF_NOTES round 16)
    resumed = 0
    ckpt_saves = 0
    if ckpt is not None:
        from lightgbm_tpu import resilience as res
        os.makedirs(ckpt.dir, exist_ok=True)
        resumed = res.maybe_resume(booster, ckpt.dir, every=ckpt.every)
        booster.resumed_from = resumed

    def maybe_ckpt():
        nonlocal ckpt_saves
        if ckpt is not None and ckpt.every > 0 \
                and booster._inner.iter_ % ckpt.every == 0:
            from lightgbm_tpu import resilience as res
            res.save_booster(booster, ckpt.dir, keep=ckpt.keep,
                             every=ckpt.every)
            ckpt_saves += 1

    # warmup: compile + first iterations; force one deferred-tree flush
    # so the pack jit (and any periodic-flush cost) is compiled before
    # the timed window.  A resumed booster already holds its warmup
    # trees — adding more would train a different model than the run
    # being resumed.
    if resumed == 0:
        for _ in range(warmup):
            booster.update()
    elif warmup + num_iters - booster._inner.iter_ > 0:
        # a fresh process resuming still pays jit compilation: the
        # first post-resume update is the compile-payer and must stay
        # OUT of the timed window or the resumed record understates
        # throughput (and obs diff vs the un-checkpointed record
        # overstates snapshot overhead).  The trajectory is unchanged
        # — the total-tree-count invariant below just sees one more
        # landed iteration — but a crossed save boundary must still
        # save (each save re-anchors the physical row permutation)
        booster.update()
        maybe_ckpt()
    booster._inner._flush_pending()
    force_sync()
    # paged comb (ISSUE 15): snapshot the page-DMA counters at t0 so
    # the paged block below reports the TIMED WINDOW's sweeps only
    # (the ingest flush and warmup sweeps would otherwise inflate it)
    _pg_store = getattr(getattr(booster._inner, "grow", None),
                        "_pages", None)
    _pg0 = dict(_pg_store.stats) if _pg_store is not None else {}
    # remaining timed iterations: the TOTAL tree count (warmup +
    # num_iters) is the invariant a kill/resume cycle preserves
    num_iters = max(warmup + num_iters - booster._inner.iter_, 0)
    # live pulse (ISSUE 20): the heartbeat stream is armed OUTSIDE the
    # timed window — the forced beat below pays the file-open/rotate
    # cost before t0, and the in-loop beats are cadence rate-limited so
    # a steady-state iteration only reads the clock.  With
    # LGBM_TPU_PULSE=off no emitter is allocated at all (the
    # grow-pulse-off purity pin proves the trained program is
    # byte-identical).
    from lightgbm_tpu.obs import pulse as pulse_mod
    pulse_em = pulse_mod.emitter("bench")
    if pulse_em is not None:
        pulse_em.beat("bench::warmup_done", iteration=0,
                      total=num_iters, force=True)
    from lightgbm_tpu.obs import counters as obs_counters
    from lightgbm_tpu.obs import ledger as obs_ledger
    from lightgbm_tpu.obs import tracer as obs_tracer
    if obs_tracer.enabled:
        # phases/counters/ledger in the record must cover THIS point's
        # timed window only — not the warmup trees or earlier scaling
        # points
        obs_tracer.reset()
        obs_counters.reset()
        obs_ledger.reset()

    # xplane capture of the timed window (ISSUE 6): with
    # LGBM_TPU_XPLANE=dir the steady-state iterations run under the
    # jax profiler, the obs tracer mirrors every span as a
    # TraceAnnotation, and the record gains a `device` block decoded
    # by the in-repo xplane reader (obs attr) — per-kernel device
    # times joined to phases.  Like tracing, a captured run's
    # iters/sec is NOT the metric of record.
    import contextlib
    xdir = os.environ.get("LGBM_TPU_XPLANE", "") if xplane else ""
    _pre_pb: set = set()
    if xdir:
        import glob as _glob
        from profile_lib import xplane_capture
        _pre_pb = set(_glob.glob(os.path.join(xdir, "**", "*.xplane.pb"),
                                 recursive=True))
        capture = xplane_capture(xdir)
    else:
        capture = contextlib.nullcontext()

    t0 = time.perf_counter()
    with capture:
        if obs_tracer.enabled:
            # traced runs also record the per-iteration TRAJECTORY (run
            # ledger): phase-wall deltas, counter deltas, HBM watermark —
            # this is what makes the record diffable median-of-k.  The
            # per-iteration sampling perturbs walls, but a traced run's
            # timing is already not the metric of record
            t_prev = t0
            for i in range(num_iters):
                booster.update()
                maybe_ckpt()
                if pulse_em is not None:
                    pulse_em.beat("bench::timed", iteration=i,
                                  total=num_iters)
                t_now = time.perf_counter()
                obs_ledger.sample(i, wall_s=t_now - t_prev)
                t_prev = t_now
        else:
            for i in range(num_iters):
                booster.update()
                maybe_ckpt()
                if pulse_em is not None:
                    pulse_em.beat("bench::timed", iteration=i,
                                  total=num_iters)
        force_sync()
        elapsed = time.perf_counter() - t0

    if pulse_em is not None:
        # terminal marker: a benchfail path never reaches this, so the
        # watchdog classifies its silent tail as STALLED
        pulse_em.event("end", iteration=num_iters)
    iters_per_sec = num_iters / max(elapsed, 1e-9)
    auc = booster._eval("training", None)
    from profile_lib import bench_record
    rec = bench_record(
        f"boosting_iters_per_sec_"
        f"{f'mc{multiclass}_' if multiclass else ''}"
        f"higgs{n_rows // 1000}k_{num_leaves}leaves",
        round(iters_per_sec, 4), "iters/sec",
        vs_baseline=round(iters_per_sec / REFERENCE_HIGGS_ITERS_PER_SEC,
                          4),
        rows=n_rows, iters=num_iters, leaves=num_leaves,
        # A/B provenance: the knobs that reroute the trained path ride
        # in every record so BENCH_r* artifacts can't be confused
        # across partition-scheme / fused sweeps
        knobs={
            "partition": os.environ.get("LGBM_TPU_PARTITION",
                                        "permute"),
            "fused": os.environ.get("LGBM_TPU_FUSED", "1") != "0",
            "categorical": categorical,
            "cat_onehot": bool(cat_onehot),
            "num_class": int(multiclass) if multiclass else 1,
            # the batch the grower ACTUALLY engaged, not the env
            # request (paged / streaming / pre-partitioned configs
            # fall back to serial-K with a named routing rule)
            "mc_batched": bool(getattr(booster._inner, "_mc_batched",
                                       False)),
        })
    # engaged routing decision (ISSUE 10): the full cell + digest ride
    # in every record so `obs diff` / tools/perf_gate.py can refuse to
    # compare records that trained different engaged paths (a
    # row_order baseline vs a physical candidate answers a different
    # question than a regression)
    routing = booster._inner.routing_info()
    if routing is not None:
        rec["routing"] = routing
    if ckpt is not None:
        # resume provenance (ISSUE 13): resumed_from > 0 means this
        # record continued a preempted step from its snapshot rather
        # than restarting tree 0; saves > 0 means the iters/sec above
        # carries the checkpoint-write overhead being measured
        rec["ckpt"] = {"dir": ckpt.dir, "every": ckpt.every,
                       "resumed_from": resumed,
                       "iters_timed": num_iters, "saves": ckpt_saves}
    if pulse_em is not None:
        # pulse provenance (ISSUE 20): where the heartbeat stream
        # landed, the final in-window rate estimate and how many beats
        # the cadence limiter actually let through
        rec["pulse"] = {
            "stream": pulse_em.path or "mem",
            "every_s": pulse_em.every_s,
            "beats": pulse_em.beats,
            "iters_per_sec_ema": (round(pulse_em.ema, 4)
                                  if pulse_em.ema is not None else None),
        }
    ev = {k: v - _ev0.get(k, 0)
          for k, v in obs_events.totals().items()
          if v - _ev0.get(k, 0) > 0}
    if ev:
        # structural events (e.g. hist_scatter psum fallback, a routing
        # fallback) recorded by THIS point — a bench that silently took
        # a slow path is visible in its own artifact
        rec["events"] = ev
    # layout shape block: what the analytical cost model
    # (obs/costmodel.py, `obs report --roofline`) needs to price this
    # record's counters in HBM bytes / FLOPs
    inner = booster._inner
    # f_pad/padded_bins are the widths the ENGAGED path works at: the
    # physical comb ingests the UNBUNDLED logical layout under EFB
    # (ISSUE 12), while the row_order path histograms the bundled
    # storage; bins_cols/bins_itemsize price the device bin matrix
    # itself (bundled — possibly u16 — either way)
    _route = inner.routing_info() or {}
    _phys = _route.get("path") in ("physical", "stream")
    rec["shape"] = {
        "rows": n_rows,
        "features": x.shape[1],
        "f_pad": int(inner.dd.phys_f_pad if _phys
                     else inner.dd.bins.shape[1]),
        "padded_bins": int(inner.dd.phys_padded_bins if _phys
                           else inner.dd.padded_bins),
        "bins_cols": int(inner.dd.bins.shape[1]),
        "bins_itemsize": int(inner.dd.bins.dtype.itemsize),
        "bundled": bool(inner.dd.bundle is not None),
        "trees": num_iters,
        "stream": bool(getattr(inner, "_stream_grad", False)),
        "cat_cols": len(cat_cols),
        "num_class": int(multiclass) if multiclass else 1,
    }
    # paged block (ISSUE 15): when the paged comb engaged, record the
    # plan geometry next to the MEASURED page-DMA walls so the next
    # chip run can price the double-buffer overlap (predicted
    # dma-s/tree assumes full overlap with compute; measured_dma_s is
    # what the host staging actually cost this run — on CPU the sweep
    # is synchronous, so the delta IS the overlap headroom)
    _plan = _route.get("page_plan")
    if _plan is not None:
        paged_block = {
            "n_pages": _plan.get("n_pages"),
            "rows_per_page": _plan.get("rows_per_page"),
            "page_bytes": _plan.get("page_bytes"),
            "resident_bytes": _plan.get("resident_bytes"),
            "predicted_dma_bytes_per_tree":
                _plan.get("dma_bytes_per_tree"),
            "predicted_dma_s_per_tree":
                _plan.get("overhead_s_per_tree"),
        }
        eng = _plan.get("engaged")
        if eng is not None:
            st = {k: eng.get("stats", {}).get(k, 0) - _pg0.get(k, 0)
                  for k in ("cycles", "dma_bytes", "fetch_s",
                            "flush_s")}
            cycles = max(int(st["cycles"]), 1)
            dma_s = float(st["fetch_s"]) + float(st["flush_s"])
            paged_block["measured"] = {
                "sweeps": int(st["cycles"]),
                "dma_bytes": int(st["dma_bytes"]),
                "fetch_s": round(float(st["fetch_s"]), 6),
                "flush_s": round(float(st["flush_s"]), 6),
                "dma_s_per_sweep": round(dma_s / cycles, 6),
                "dma_frac_of_wall": round(dma_s / max(elapsed, 1e-9),
                                          4),
            }
        rec["paged"] = paged_block
    if obs_tracer.enabled:
        # the tracer's span barriers serialize the async dispatch
        # chain, so a traced run's iters/sec is NOT the metric of
        # record — flag it and attach the per-phase breakdown the
        # barriers bought us, plus the per-iteration ledger trajectory
        rec["traced"] = True
        rec["phases"] = obs_tracer.summary()
        rec["counters"] = obs_counters.totals()
        rec["ledger"] = obs_ledger.to_record()
        # schema-additive `memory` block (ISSUE 9): predicted
        # per-buffer footprint + measured residency peaks + the
        # measured-vs-predicted join verdict.  The block must never
        # fail the bench — model errors land in the block itself.
        from lightgbm_tpu.obs import mem as obs_mem
        try:
            rec["memory"] = obs_mem.memory_block(rec)
        except Exception as e:  # pragma: no cover - shape-dependent
            rec["memory"] = {"schema": obs_mem.MEM_SCHEMA,
                             "error": str(e)[:400]}
    if xdir:
        # schema-additive `device` block: per-kernel device times from
        # THIS point's capture (files the session just wrote), joined
        # with the phases above when traced.  Attribution must never
        # fail the bench — decode errors land in the block itself.
        from lightgbm_tpu.obs import xattr
        try:
            import glob as _glob
            post = set(_glob.glob(os.path.join(xdir, "**",
                                               "*.xplane.pb"),
                                  recursive=True))
            # only files THIS capture wrote: decoding leftovers from an
            # earlier run in a reused dir would embed device times that
            # were never measured here
            new = sorted(post - _pre_pb)
            if not new:
                raise xattr.XplaneParseError(
                    "capture wrote no new *.xplane.pb under "
                    f"{xdir} (stale files from earlier runs are "
                    "ignored)")
            spaces = [xattr.load_xspace(p) for p in new]
            rec["device"] = xattr.device_block(xdir, spaces, rec=rec)
        except Exception as e:  # pragma: no cover - depends on backend
            rec["device"] = {"schema": xattr.DEVICE_SCHEMA,
                             "source": xdir, "error": str(e)[:400]}
    return rec


def run_serve_bench(n_rows: int, *, batch: int, trees: int,
                    num_leaves: int, smoke: bool = False) -> dict:
    """Serving bench (ISSUE 14): train a booster, compile it into the
    forest-tensorized engine, then measure BOTH serving shapes in one
    record — bulk scoring (rows/sec over ``n_rows`` raw f32 rows,
    pipelined bucket-cap chunks) and the latency-bounded small-batch
    path (p50/p99 of submit->result through the double-buffered
    ServingQueue at ``batch`` rows per request).  The record's
    ``serving`` block carries the bucket set, the retrace count after
    warmup (MUST be 0 — perf_gate and obs trend flag anything else)
    and the model digest; the routing block carries the serving digest
    too, so records from different compiled models are incomparable."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import env_knob
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs.costmodel import (serving_kernel_bytes,
                                            serving_traversal_bytes)
    from lightgbm_tpu.serve import ServingEngine, ServingModel, ServingQueue

    _ev0 = obs_events.totals()
    train_rows = min(n_rows, 200_000)
    x, y = make_higgs_like(train_rows)
    train = lgb.Dataset(x, label=y, params={"max_bin": 255})
    booster = lgb.Booster(params={
        "objective": "binary", "num_leaves": num_leaves,
        "learning_rate": 0.1, "verbosity": -1, "max_bin": 255,
    }, train_set=train)
    for _ in range(trees):
        booster.update()

    model = ServingModel.from_booster(booster)
    booster._inner.note_serving(model.to_json())
    engine = ServingEngine(model)
    xq, _ = make_higgs_like(n_rows, seed=7)
    xq = np.ascontiguousarray(xq, np.float32)

    # warmup compiles every bucket this run will touch: the bulk
    # bucket-cap chunks (plus the tail chunk's bucket) and the
    # small-batch bucket.  After this point the program count is
    # pinned — any growth is a retrace the record must confess.
    engine.predict(xq[:min(n_rows, engine.bucket_max)])
    tail = n_rows % engine.bucket_max
    if tail and n_rows > engine.bucket_max:
        # the tail chunk's (smaller) bucket; when the whole set fits
        # in one bucket the line above already compiled it
        engine.predict(xq[:tail])
    engine.predict(xq[:batch])
    warm_programs = engine.stats()["programs"]
    engine.mark_warm()

    t0 = time.perf_counter()
    scores = engine.predict(xq)
    bulk_s = time.perf_counter() - t0
    assert scores.shape[0] == n_rows
    bulk_rps = n_rows / max(bulk_s, 1e-9)

    # latency path: sustained small batches through the async queue.
    # One queue submit is ONE bucketed dispatch, so the request size is
    # capped by the bucket cap (bulk predict() chunks, submit does not)
    if batch > engine.bucket_max:
        print(f"serve bench: clamping --batch {batch} to the bucket "
              f"cap {engine.bucket_max}", file=sys.stderr)
        batch = engine.bucket_max
    batch = min(batch, n_rows)
    queue = ServingQueue(engine)
    n_batches = 64 if smoke else 512
    starts = [(i * batch) % max(n_rows - batch, 1)
              for i in range(n_batches)]
    # latency is measured at the source since ISSUE 17: the queue
    # stamps each submit and its completion handler records the
    # submit->drain delta into mergeable log-bucketed histograms (no
    # host sample list) — the bench just keeps the pipeline flowing
    done = 0
    for i, s in enumerate(starts):
        queue.submit(xq[s:s + batch])
        # steady state: keep `depth` batches in flight, complete the
        # rest in submit order
        while i + 1 - done > queue.depth:
            queue.result()
            done += 1
    done += len(queue.drain())
    assert done == len(starts)
    lat = queue.latency_percentiles()
    p50, p99, p999 = lat["p50_ms"], lat["p99_ms"], lat["p999_ms"]
    retraces = engine.stats()["programs"] - warm_programs

    from profile_lib import bench_record
    rec = bench_record(
        f"serving_rows_per_sec_higgs{n_rows // 1000}k_{trees}trees",
        round(bulk_rps, 1), "rows/sec",
        vs_baseline=round(bulk_rps / 1_000_000, 4),   # the >=1M/s/chip target
        rows=n_rows, iters=trees, leaves=num_leaves,
        knobs={
            "serve": env_knob("LGBM_TPU_SERVE"),
            "serve_buckets": env_knob("LGBM_TPU_SERVE_BUCKETS"),
            "serve_kernel": env_knob("LGBM_TPU_SERVE_KERNEL"),
            "queue_depth": queue.depth,
        })
    stats = engine.stats()
    # price by the ENGAGED traversal (ISSUE 18): the VMEM-resident
    # kernel moves forest bytes ONCE per dispatch + row bytes once
    # (serving_kernel_bytes — padding waste is the MARGINAL row cost,
    # the forest term is paid either way), the gather walk re-streams
    # the node fields per level (serving_traversal_bytes); the A/B
    # bench pair (bench_serve_kernel vs bench_serve_gather) compares
    # achieved rows/sec against these two contracts
    geomf = {k: v for k, v in engine._flight_geom.items()
             if k != "kernel"}
    if engine.kernel_mode:
        def _price(rows: int) -> int:
            return serving_kernel_bytes(rows, **geomf)
    else:
        def _price(rows: int) -> int:
            return serving_traversal_bytes(rows, **geomf)
    rec["serving"] = {
        "schema": "lightgbm_tpu/serving/v1",
        "digest": model.digest,
        "kernel": engine.kernel_mode or "gather",
        "trees": model.n_trees,
        "max_depth": model.n_steps,
        "bulk_rows": n_rows,
        "bulk_rows_per_sec": round(bulk_rps, 1),
        "batch": batch,
        "batch_bucket": engine.bucket_for(batch),
        "buckets": stats["buckets"],
        "queue_depth": queue.depth,
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "p999_ms": round(p999, 3),
        "retraces_after_warmup": int(retraces),
        "dispatches": stats["dispatches"],
        "rows_true": stats["rows_true"],
        "rows_padded": stats["rows_padded"],
        # analytical bytes of ONE bulk dispatch at the PADDED bucket
        # size it actually runs: what the roofline prices the achieved
        # rows/sec against
        "predicted_dispatch_bytes": _price(
            engine.bucket_for(min(n_rows, engine.bucket_max))),
    }
    # padding waste across the whole run (ISSUE 17): bytes the padded
    # rows cost minus what the true rows would have — the flight
    # recorder prices the same delta per window; both gate like walls.
    # Marginal pricing on the kernel path: _price(0) is the per-
    # dispatch forest DMA, charged once per dispatch in the total but
    # never to the padding rows
    waste = (_price(stats["rows_padded"] - stats["rows_true"])
             - _price(0))
    total_bytes = (_price(stats["rows_padded"]) - _price(0)
                   + stats["dispatches"] * _price(0))
    rec["serving"]["padding_waste_bytes"] = int(waste)
    rec["serving"]["padding_waste_ratio"] = round(
        waste / max(total_bytes, 1), 4)
    if engine._flight is not None:
        # the recorder observed this bench: close the open window and
        # note where the JSONL stream went so obs serve can join
        engine._flight.flush()
        rec["serving"]["servemetrics"] = {
            "schema": "lightgbm_tpu/servemetrics/v1",
            "windows": engine._flight.windows_emitted,
            "emit_dir": engine._flight.emit_dir or None,
            "window_s": engine._flight.window_s,
        }
    routing = booster._inner.routing_info()
    if routing is not None:
        rec["routing"] = routing
    ev = {k: v - _ev0.get(k, 0)
          for k, v in obs_events.totals().items()
          if v - _ev0.get(k, 0) > 0}
    if ev:
        rec["events"] = ev
    rec["shape"] = {
        "rows": n_rows, "features": int(xq.shape[1]),
        "trees": model.n_trees, "train_rows": train_rows,
    }
    return rec


def _emit_failure(json_path: str, rec: dict) -> None:
    """Write the classified failure artifact with plain json (no
    profile_lib / jax: a dead backend must still leave a record)."""
    try:
        # pulse stamp (ISSUE 20): the benchfail artifact carries the
        # LAST heartbeat this process emitted — where training was
        # (phase/iteration/rate) when it died, next to the classified
        # cause.  Must never mask the failure it is stamping.
        from lightgbm_tpu.obs import pulse as pulse_mod
        hb = pulse_mod.last_heartbeat()
        if hb is not None and "pulse" not in rec:
            rec["pulse"] = {"last_heartbeat": hb}
    except Exception:
        pass
    print(json.dumps(rec))
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rec, f, indent=1, sort_keys=True)
            f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI / CPU")
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--leaves", type=int, default=0)
    ap.add_argument("--json", default="",
                    help="also write the record to this path "
                         "(BENCH_r*.json round artifact)")
    ap.add_argument("--serve", action="store_true",
                    help="serving bench (ISSUE 14): bulk rows/sec + "
                         "small-batch p50/p99 through the compiled "
                         "forest engine; the record gains a `serving` "
                         "block (retraces after warmup must be 0)")
    ap.add_argument("--batch", type=int, default=256,
                    help="small-batch size for the --serve latency "
                         "path (the millions-of-users request shape)")
    ap.add_argument("--onehot", type=int, default=0,
                    help="append this many one-hot indicator columns "
                         "(the EFB shape; ISSUE-12 bench pair)")
    ap.add_argument("--no-bundle", action="store_true",
                    help="disable EFB bundling (the unbundled-"
                         "equivalent side of the bench pair)")
    ap.add_argument("--categorical", default="", metavar="K,C",
                    help="append C categorical columns of K categories "
                         "each (the cat-subset shape; ISSUE-16 bench "
                         "pair)")
    ap.add_argument("--cat-onehot", action="store_true",
                    help="with --categorical: disable subset search "
                         "(max_cat_to_onehot above the cardinality) — "
                         "the one-hot baseline side of the bench pair")
    ap.add_argument("--multiclass", type=int, default=0, metavar="K",
                    help="train a K-class softmax model (K trees per "
                         "boosting iteration) on hidden per-class "
                         "split structure; the ISSUE-19 bench pair "
                         "A/Bs the batched ONE-dispatch grow "
                         "(LGBM_TPU_MC_BATCH=auto) against serial-K "
                         "(=0)")
    ap.add_argument("--no-preflight", action="store_true",
                    help="skip the obs doctor environment preflight "
                         "(backend / libtpu / TPU env vars / disk)")
    ap.add_argument("--resume", action="store_true",
                    help="checkpoint/resume this bench step (ISSUE "
                         "13): resume from the latest ckpt/v1 "
                         "snapshot under LGBM_TPU_CKPT_DIR (default "
                         "./bench_ckpt) and snapshot every "
                         "LGBM_TPU_CKPT_EVERY iterations — a "
                         "preempted step continues instead of "
                         "restarting tree 0")
    ap.add_argument("--pulse", default="", metavar="DIR|mem",
                    help="arm the live heartbeat stream (ISSUE 20): "
                         "sets LGBM_TPU_PULSE so this run appends "
                         "pulse/v1 beats a sidecar `obs watch` can "
                         "tail; the record gains a `pulse` block and "
                         "a benchfail artifact stamps the last "
                         "heartbeat")
    args = ap.parse_args()
    if args.pulse:
        # the env knob is the single source of truth (engine.train and
        # the serving recorder read it too) — the flag just sets it
        # for this process before any emitter is consulted
        os.environ["LGBM_TPU_PULSE"] = args.pulse

    ckpt_pol = None
    if args.resume:
        if not (args.smoke or args.rows):
            print("bench: --resume needs a single-shape run (--smoke "
                  "or --rows N); the default scaling sweep trains "
                  "three different shapes against one checkpoint",
                  file=sys.stderr)
            sys.exit(2)
        # one source of truth for the knob parsing (resilience's
        # CkptPolicy); --resume asks for checkpointing explicitly, so
        # an unset/off dir knob gets a default instead of disabling
        from lightgbm_tpu.resilience import policy_from_env
        try:
            ckpt_pol = policy_from_env(default_dir="bench_ckpt")
        except ValueError as e:
            # malformed cadence knobs surface as a classified message
            # + exit 2, not a raw traceback (the bench exit contract)
            print(f"bench: invalid checkpoint policy: {e}",
                  file=sys.stderr)
            sys.exit(2)

    # ISSUE 11: the doctor preflight runs the cheap environment layers
    # BEFORE any dataset is built — the BENCH_r03 class (libtpu dying
    # on TPU_WORKER_HOSTNAMES) fails here with a classified finding
    # and a structured artifact instead of 500 lines of bring-up log
    from lightgbm_tpu.obs import doctor as obs_doctor
    if not args.no_preflight:
        pf = obs_doctor.preflight(
            capture_dir=os.environ.get("LGBM_TPU_XPLANE") or None)
        from lightgbm_tpu.obs import findings as obs_findings
        errs = obs_findings.errors(pf.get("findings") or [])
        if errs:
            for line in obs_doctor.render_doctor(pf):
                print(line, file=sys.stderr)
            cls = next((f.get("detail", {}).get("bringup_class")
                        for f in errs
                        if f.get("detail", {}).get("bringup_class")),
                       None)
            _emit_failure(args.json, obs_doctor.failure_record(
                "preflight", bringup_class=cls,
                detail="; ".join(f["message"] for f in errs)[:800],
                doctor_block=pf,
                metric="boosting_iters_per_sec_higgs"))
            # a corrupt/unusable checkpoint keeps the resilience exit
            # contract (2 = unusable state), other preflight findings
            # stay exit 1
            sys.exit(2 if any(f.get("code") == "CKPT_CORRUPT"
                              for f in errs) else 1)

    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if not args.smoke:
        # a measurement path that finds no chip fails before any data
        # is made; only --smoke runs on whatever backend is there
        import jax
        plat = jax.devices()[0].platform
        if plat != "tpu":
            print(f"bench: JAX found no TPU (platform {plat!r}); only "
                  "--smoke runs without one", file=sys.stderr)
            sys.exit(1)

    if os.environ.get("LGBM_TPU_XPLANE"):
        # an xplane run is an ATTRIBUTION run: enable the tracer
        # (in-memory when LGBM_TPU_TRACE gave no path) so phases,
        # counters and the ledger ride the record for the device-block
        # join, and spans mirror into TraceAnnotations during capture
        from lightgbm_tpu.obs import tracer as _obs_tracer
        if not _obs_tracer.enabled:
            _obs_tracer.enable(None)

    def emit(result):
        print(json.dumps(result))
        if args.json:
            from profile_lib import write_bench_record
            write_bench_record(args.json, result)

    # any death during build/compile/train is classified into the
    # named bring-up classes (obs/doctor.py BRINGUP_CLASSES) and
    # leaves a structured artifact — what BENCH_r03 should have been
    # instead of a raw log tail
    from lightgbm_tpu.resilience import (CheckpointError, FaultError,
                                         ResumeRefused)
    try:
        if args.serve:
            if args.smoke:
                emit(run_serve_bench(args.rows or 20000,
                                     batch=min(args.batch, 64),
                                     trees=args.iters or 5,
                                     num_leaves=args.leaves or 31,
                                     smoke=True))
            else:
                emit(run_serve_bench(args.rows or 1_000_000,
                                     batch=args.batch,
                                     trees=args.iters or 100,
                                     num_leaves=args.leaves or 255))
            return
        if args.smoke:
            emit(run_bench(args.rows or 20000, args.iters or 5,
                           args.leaves or 31, warmup=2,
                           onehot=args.onehot,
                           enable_bundle=not args.no_bundle,
                           ckpt=ckpt_pol,
                           categorical=args.categorical,
                           cat_onehot=args.cat_onehot,
                           multiclass=args.multiclass))
            return
        if args.rows:
            emit(run_bench(args.rows, args.iters or 30,
                           args.leaves or 255, warmup=3,
                           onehot=args.onehot,
                           enable_bundle=not args.no_bundle,
                           ckpt=ckpt_pol,
                           categorical=args.categorical,
                           cat_onehot=args.cat_onehot,
                           multiclass=args.multiclass))
            return

        # Default: the HONEST benchmark shape — the reference baseline
        # is measured on Higgs 10.5M x 28 (docs/Experiments.rst:110-124),
        # so the metric of record matches it; smaller scaling points
        # ride along so scale behaviour is visible in every round's
        # artifact.
        points = []
        shapes = ((1_000_000, 30), (4_000_000, 10), (10_500_000, 10))
        for idx, (rows, iters) in enumerate(shapes):
            points.append(
                (rows, run_bench(rows, args.iters or iters,
                                 args.leaves or 255, warmup=3,
                                 # one capture per run: attribute the
                                 # headline 10.5M point, not all three
                                 xplane=(idx == len(shapes) - 1))))
        result = dict(points[-1][1])
        result["scaling"] = [
            {"rows": r, "iters_per_sec": p["value"],
             "vs_baseline": p["vs_baseline"]} for r, p in points]
        emit(result)
    except (KeyboardInterrupt, SystemExit):
        raise
    except (CheckpointError, ResumeRefused) as e:
        # an unusable/foreign checkpoint is exit 2 with a structured
        # artifact (the resilience CLI contract) — never a traceback
        rec = obs_doctor.failure_record(
            "resume", bringup_class="checkpoint_corrupt"
            if isinstance(e, CheckpointError) else "resume_refused",
            detail=str(e), metric="boosting_iters_per_sec_higgs")
        rec["finding"] = e.finding
        _emit_failure(args.json, rec)
        print(f"bench: REFUSED to resume: {e}", file=sys.stderr)
        sys.exit(e.exit_code)
    except FaultError as e:
        # a classified-but-unrecovered training fault: the benchfail
        # artifact carries the full faultreport/v1
        rec = obs_doctor.failure_record(
            "train", bringup_class=e.report.get("class"),
            detail=str(e), metric="boosting_iters_per_sec_higgs")
        rec["faultreport"] = e.report
        _emit_failure(args.json, rec)
        print(f"bench: FAILED with classified fault "
              f"{e.report.get('class')!r} — see the structured record"
              + (f" ({args.json})" if args.json else ""),
              file=sys.stderr)
        sys.exit(e.exit_code)
    except Exception as e:   # noqa: BLE001 - classified, then fatal
        cls = obs_doctor.classify_exception(e)
        _emit_failure(args.json, obs_doctor.failure_record(
            "run", bringup_class=cls["class"] if cls else None,
            detail=f"{type(e).__name__}: {e}",
            metric="boosting_iters_per_sec_higgs"))
        print(f"bench: FAILED during run: "
              f"{'classified as ' + cls['class'] if cls else 'no known bring-up class'}"
              f" — see the structured record"
              + (f" ({args.json})" if args.json else ""),
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
