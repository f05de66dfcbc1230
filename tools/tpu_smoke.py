"""Compiled-TPU smoke gate — run before committing anything that touches
``ops/pallas/`` or the physical comb layout, and before the end-of-round
snapshot.

The CPU test suite runs every Mosaic kernel in interpret mode on a forced
8-device CPU mesh, so a device-only layout change can pass 167 tests and
still fail to *compile* on the real chip (round-3 snapshot regression:
64-lane comb vs the (1,128) memref tiling).  This script is the missing
device gate: it trains real trees through the compiled physical+stream
path at two shapes, with monotone constraints off and on, and fails loudly
on any compile or runtime error.

Run: ``python tools/tpu_smoke.py`` (needs the TPU; 4-5 min on the v5e,
dominated by Mosaic compiles).  Exit code 0 = green.  ``--fast`` skips
the 1M shape.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the gate validates the DEFAULT shipping path — pin every env knob that
# could silently reroute it before jax/lightgbm_tpu import
for _k, _v in (("LGBM_TPU_PHYS", ""), ("LGBM_TPU_STREAM", ""),
               ("LGBM_TPU_COMB_DT", "f32"), ("LGBM_TPU_APPLY_IMPL", ""),
               ("LGBM_TPU_COMB_BF16", ""), ("LGBM_TPU_POOL_TAIL", ""),
               ("LGBM_TPU_FUSED", ""), ("LGBM_TPU_PARTITION", ""),
               ("LGBM_TPU_PART_INTERP", "")):
    if _v:
        os.environ[_k] = _v
    else:
        os.environ.pop(_k, None)


def _purge_lgb_modules():
    """Drop every lightgbm_tpu module so env knobs read at import time
    (LGBM_TPU_FUSED and friends) take effect on the next import."""
    for m in [k for k in list(sys.modules) if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]


def _check(name: str, n_rows: int, num_leaves: int, *, monotone=None,
           iters: int = 3) -> float:
    import numpy as np
    import jax.numpy as jnp
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(7)
    f = 28
    x = rng.normal(size=(n_rows, f)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * x[:, 2] * x[:, 3]
         + rng.logistic(size=n_rows) > 0).astype(np.float32)
    params = {
        "objective": "binary",
        "num_leaves": num_leaves,
        "learning_rate": 0.1,
        "verbosity": -1,
        "max_bin": 255,
    }
    if monotone is not None:
        params["monotone_constraints"] = monotone
    train = lgb.Dataset(x, label=y, params={"max_bin": 255})
    bst = lgb.Booster(params=params, train_set=train)
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    bst._inner._flush_pending()
    # execution barrier: a host value pull
    s = float(jnp.sum(bst._inner.train_score))
    dt = time.perf_counter() - t0
    if not np.isfinite(s):
        raise RuntimeError(f"{name}: non-finite training score {s}")
    grower = bst._inner.grow
    phys = bool(getattr(grower, "_grow_p", None) is not None
                or type(grower).__name__ == "_PhysicalGrow"
                or getattr(grower, "physical", False))
    if not phys:
        # the whole point of the gate is the compiled physical-path
        # Mosaic kernels; a gather-path run proves nothing
        raise RuntimeError(
            f"{name}: grower is {type(grower).__name__}, not the "
            "physical-partition path — the gate did not exercise the "
            "Mosaic kernels it exists to test")
    fused = bool(getattr(grower, "fused", False))
    if os.environ.get("LGBM_TPU_FUSED", "1") != "0" and not fused:
        # the shipping default is the FUSED partition+histogram split
        # kernel; if the grower silently fell back to the separate pair
        # the gate would be testing dead code
        raise RuntimeError(
            f"{name}: fused partition+histogram path did not engage "
            "(grower.fused is False with LGBM_TPU_FUSED unset)")
    print(f"[tpu_smoke] {name}: {iters} trees in {dt:.1f}s "
          f"(physical={phys}, fused={fused}, score_norm={s:.4f})")
    return dt


def _tree_digest(n_rows: int, num_leaves: int, iters: int = 3):
    """Train and return an exact per-tree digest (splits, thresholds,
    leaf-value BYTES) for the fused-vs-unfused identity check."""
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(7)
    f = 28
    x = rng.normal(size=(n_rows, f)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.3 * x[:, 2] * x[:, 3]
         + rng.logistic(size=n_rows) > 0).astype(np.float32)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 255})
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": num_leaves,
        "learning_rate": 0.1, "verbosity": -1, "max_bin": 255,
    }, train_set=ds)
    for _ in range(iters):
        bst.update()
    bst._inner._flush_pending()
    return [(int(t.num_leaves),
             t.split_feature[:int(t.num_leaves) - 1].tolist(),
             t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
             np.asarray(t.leaf_value).tobytes())
            for t in bst._inner.models]


def _check_knob_identity(env_key: str, values, label: str,
                         n_rows: int = 50_048, num_leaves: int = 63):
    """Train under two values of one LGBM_TPU_* knob and demand
    BYTE-identical tree digests (splits, thresholds, leaf-value
    bytes).  Serves both bisection knobs below."""
    digests = {}
    for knob in values:
        os.environ[env_key] = knob
        _purge_lgb_modules()
        try:
            digests[knob] = _tree_digest(n_rows, num_leaves)
        finally:
            os.environ.pop(env_key, None)
    _purge_lgb_modules()
    a_key, b_key = values
    if digests[a_key] != digests[b_key]:
        if len(digests[a_key]) != len(digests[b_key]):
            raise RuntimeError(f"{label}: tree counts differ")
        for i, (a, b) in enumerate(zip(digests[a_key], digests[b_key])):
            if a != b:
                raise RuntimeError(
                    f"{label}: trees diverge at tree {i}: "
                    f"leaves {a[0]} vs {b[0]}, features "
                    f"{a[1][:6]} vs {b[1][:6]}")
    print(f"[tpu_smoke] {label}: {len(digests[a_key])} trees "
          f"bit-identical ({env_key}={a_key} vs {b_key})")


def _check_fused_identity():
    """Compiled fused vs unfused paths must grow bit-identical trees
    (the interpret-mode contract tests/test_fused.py pins off-TPU)."""
    _check_knob_identity("LGBM_TPU_FUSED", ("1", "0"), "fused-identity")


def _check_partition_identity(block: int = 512, draws: int = 6,
                              n_rows: int = 48 * 2048) -> None:
    """Compiled permute vs matmul compaction must leave BIT-identical
    packed combs and equal ``nleft`` (ISSUE 3): the permute packing
    reproduces the matmul scheme's exact row layout - left ascending,
    right reversed.  The interpreter pins that
    (tests/test_partition_perm.py); what Mosaic makes of the butterfly
    routing can only be held to it here.  Kernel against kernel at
    EQUAL block rows, at one plane and at two: since ISSUE 37 the
    growers hand the two schemes other blocks
    (partition_kernel2.scan_block_rows), so their TREES agree to f32
    summation order only.  The permute scan is also held to the numpy
    oracle at the block it ships with at each width."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas.layout import (COMB_ROW_SLACK, LANE,
                                                to_planes, to_rows)
    from lightgbm_tpu.ops.pallas.partition_kernel import SEL_CNT, SEL_S0
    from lightgbm_tpu.ops.pallas.partition_kernel2 import (
        make_partition_ss, scan_block_rows)
    from lightgbm_tpu.ops.pallas.partition_kernel3 import \
        make_partition_perm

    rng = np.random.default_rng(5)
    n = n_rows + COMB_ROW_SLACK
    for planes in (1, 2):
        C = planes * LANE
        # whole bytes in every lane: exact through the matmul
        # compaction's bf16 MXU pass, and rows told apart by content
        rows = rng.integers(0, 256, size=(n, C)).astype(np.float32)
        comb = to_planes(jnp.asarray(rows))
        shipped = scan_block_rows(C)
        fns = {
            ("permute", block): make_partition_perm(
                n, C, R=block, dynamic=True),
            ("matmul", block): make_partition_ss(
                n, C, R=block, dynamic=True),
            ("permute", shipped): make_partition_perm(
                n, C, R=shipped, dynamic=True),
        }
        fns = {k: jax.jit(f) for k, f in fns.items()}
        # unaligned starts and counts over many blocks, one row, a dead
        # call, a whole-comb parent; the split column in the last plane
        cases = [(3, 1), (777, 0), (0, n_rows)] + [
            (int(rng.integers(0, n_rows // 2)),
             int(rng.integers(block, n_rows // 2)))
            for _ in range(draws)]
        for s0, cnt in cases:
            sel = np.zeros((8,), np.int32)
            sel[SEL_S0], sel[SEL_CNT] = s0, cnt
            sel[2] = C - LANE + int(rng.integers(0, 8))
            sel[3], sel[6] = int(rng.integers(16, 240)), -1
            seg = rows[s0:s0 + cnt]
            gl = seg[:, sel[2]] <= sel[3]
            want = rows.copy()
            want[s0:s0 + cnt] = np.concatenate([seg[gl], seg[~gl][::-1]])
            out = {}
            for (scheme, r), fn in fns.items():
                r2, _, nleft = fn(jnp.asarray(sel), comb,
                                  jnp.zeros_like(comb),
                                  jnp.int32(max(-(-cnt // r), 1)))
                out[scheme, r] = (np.asarray(to_rows(r2, C)), int(nleft))
            for key, (got, nleft) in out.items():
                if nleft != int(gl.sum()) or not np.array_equal(got, want):
                    raise RuntimeError(
                        f"partition-identity: {key[0]} scan at {key[1]} "
                        f"rows a step, {planes} plane(s), s0={s0} "
                        f"cnt={cnt} sel={sel.tolist()}: nleft {nleft} "
                        f"(oracle {int(gl.sum())}), "
                        f"{int((got != want).any(axis=1).sum())} lines "
                        "differ from the oracle's packed comb")
        print(f"[tpu_smoke] partition-identity: {planes} plane(s), "
              f"{len(cases)} parents: permute == matmul == oracle at "
              f"{block} rows a step, permute == oracle at {shipped}")


def _check_trace(n_rows: int = 50_048, num_leaves: int = 31,
                 iters: int = 3) -> dict:
    """Observability gate: with LGBM_TPU_TRACE set, a compiled-path run
    must emit a well-formed JSON-lines trace containing all four
    reference grow phases plus the gradient-refresh span, and device
    counters that match the trained trees' structure exactly.  Returns
    the run-ledger block (per-iteration trajectory) so --json embeds
    it in the smoke record."""
    import tempfile
    import time as _time

    import numpy as np

    path = os.path.join(tempfile.mkdtemp(prefix="lgbm_smoke_"),
                        "trace.jsonl")
    os.environ["LGBM_TPU_TRACE"] = path
    _purge_lgb_modules()
    try:
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import counters as obs_counters
        from lightgbm_tpu.obs import ledger as obs_ledger
        from lightgbm_tpu.obs import tracer as obs_tracer

        rng = np.random.default_rng(11)
        x = rng.normal(size=(n_rows, 28)).astype(np.float32)
        y = (x[:, 0] - 0.5 * x[:, 1]
             + rng.logistic(size=n_rows) > 0).astype(np.float32)
        ds = lgb.Dataset(x, label=y, params={"max_bin": 255})
        bst = lgb.Booster(params={
            "objective": "binary", "num_leaves": num_leaves,
            "verbosity": -1, "max_bin": 255}, train_set=ds)
        obs_ledger.reset()
        t_prev = _time.perf_counter()
        for i in range(iters):
            bst.update()
            t_now = _time.perf_counter()
            obs_ledger.sample(i, wall_s=t_now - t_prev)
            t_prev = t_now
        bst._inner._flush_pending()
        tot = obs_counters.totals()
        splits_model = sum(int(t.num_leaves) - 1
                           for t in bst._inner.models)
        rows_model = sum(int(t.internal_count.sum())
                         for t in bst._inner.models if t.num_leaves > 1)
        obs_tracer.close()
        from lightgbm_tpu.obs.report import load_events, phase_summary
        events, meta = load_events(path)   # raises on malformed lines
        names = {ev["name"] for ev in events}
        need = {"BeforeTrain", "Tree::grow", "Tree::grow::wait",
                "WorkCounters", "UpdateScore", "UpdateScore::tail",
                "UpdateScore::set", "UpdateScore::wait", "HbmCensus"}
        missing = need - names
        if missing:
            raise RuntimeError(f"trace is missing phase spans: {missing}")
        if not meta.get("schema"):
            raise RuntimeError("trace has no schema metadata line")
        if int(tot.get("splits", 0)) != splits_model or splits_model == 0:
            raise RuntimeError(
                f"splits counter {tot.get('splits')} != model "
                f"{splits_model}")
        if abs(tot.get("rows_partitioned", 0) - rows_model) > 1.0:
            raise RuntimeError(
                f"rows_partitioned counter {tot.get('rows_partitioned')} "
                f"!= model internal_count sum {rows_model}")
        if os.environ.get("LGBM_TPU_FUSED", "1") != "0" \
                and tot.get("fused_splits", 0) != tot.get("splits"):
            raise RuntimeError(
                "fused_splits counter does not cover every split on the "
                f"default compiled path: {tot}")
        led = obs_ledger.to_record()
        n_led = len(led.get("iterations", []))
        if n_led != iters:
            raise RuntimeError(
                f"run ledger sampled {n_led} iterations, expected "
                f"{iters}")
        # mesh flight recorder (ISSUE 8): a SERIAL single-chip run must
        # record no collective rows and no mesh block — one appearing
        # here means the serial path silently routed through a mesh
        # learner, or the telemetry invented ICI traffic.  (The mesh
        # side of the recorder is gated by ci_tier1.sh --mesh-obs /
        # tools/multichip_probe.py.)
        if led.get("collectives") or led.get("mesh"):
            raise RuntimeError(
                "serial smoke run recorded mesh collective rows: "
                f"{led.get('collectives')}")
        print(f"[tpu_smoke] trace: {len(events)} events, "
              f"{len(phase_summary(events))} phases, counters match "
              f"{splits_model} splits / {rows_model} rows, ledger "
              f"{n_led} iterations")
        return led
    finally:
        os.environ.pop("LGBM_TPU_TRACE", None)
        _purge_lgb_modules()


def _check_memory(n_rows: int = 50_048, num_leaves: int = 63,
                  iters: int = 3, tol: float = 0.10) -> dict:
    """Memory gate (ISSUE 9): train the smoke shape through the
    compiled physical path, then demand the footprint model's
    predicted peak covers the allocator's measured high-water mark
    (``peak_bytes_in_use``).  Runs FIRST — the allocator peak is
    process-wide, so a larger shape trained earlier would mask this
    shape's residency.  A measured peak above predicted (beyond
    tolerance) means a silent copy or retention the model does not
    price — exactly what must be found before the paged-comb refactor
    designs against the model.  Returns the gate's numbers for the
    --json record."""
    import numpy as np
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import hbm_high_water_bytes
    from lightgbm_tpu.obs.costmodel import grow_footprint

    rng = np.random.default_rng(17)
    f = 28
    x = rng.normal(size=(n_rows, f)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1]
         + rng.logistic(size=n_rows) > 0).astype(np.float32)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 255})
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": num_leaves,
        "verbosity": -1, "max_bin": 255}, train_set=ds)
    for _ in range(iters):
        bst.update()
    bst._inner._flush_pending()
    float(jnp.sum(bst._inner.train_score))   # execution barrier
    inner = bst._inner
    grower = inner.grow
    fp = grow_footprint(
        rows=n_rows,
        f_pad=int(inner.dd.phys_f_pad),
        padded_bins=int(inner.dd.phys_padded_bins),
        num_leaves=num_leaves,
        stream=bool(getattr(inner, "_stream_grad", False)),
        fused=bool(getattr(grower, "fused", True)),
        bins_cols=int(inner.dd.bins.shape[1]),
        bins_itemsize=int(inner.dd.bins.dtype.itemsize))
    measured = hbm_high_water_bytes()
    if measured is None:
        raise RuntimeError(
            "memory gate: allocator reports no peak_bytes_in_use on "
            "this chip — the residency join cannot run")
    if measured > fp["peak_bytes"] * (1.0 + tol):
        raise RuntimeError(
            f"memory gate: measured allocator peak "
            f"{measured / 1e6:.1f} MB exceeds the predicted peak "
            f"{fp['peak_bytes'] / 1e6:.1f} MB "
            f"({fp['peak_phase']}) by more than {tol:.0%} — a silent "
            "copy or retention the footprint model does not price")
    print(f"[tpu_smoke] memory: predicted peak "
          f"{fp['peak_bytes'] / 1e6:.1f} MB ({fp['peak_phase']}) "
          f">= measured allocator peak {measured / 1e6:.1f} MB")
    return {"predicted_peak_bytes": int(fp["peak_bytes"]),
            "predicted_peak_phase": fp["peak_phase"],
            "measured_peak_bytes": int(measured)}


def _check_device_attr(n_rows: int = 50_048, num_leaves: int = 31
                       ) -> dict:
    """Device-attribution gate (ISSUE 6): capture an xplane around two
    compiled-path iterations, decode it with the IN-REPO pure-python
    reader, and demand a device plane whose classified kernels include
    the fused split — proving `obs attr` will attribute the next chip
    run without TF or TensorBoard.  Returns the record's `device`
    block."""
    import shutil
    import tempfile

    xdir = tempfile.mkdtemp(prefix="lgbm_smoke_xplane_")
    try:
        return _run_device_attr(xdir, n_rows, num_leaves)
    finally:
        # chip captures run tens of MB; the per-run gate must not fill
        # /tmp on the TPU host
        shutil.rmtree(xdir, ignore_errors=True)


def _run_device_attr(xdir: str, n_rows: int, num_leaves: int) -> dict:
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import tracer as obs_tracer
    from lightgbm_tpu.obs import xattr

    rng = np.random.default_rng(13)
    x = rng.normal(size=(n_rows, 28)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1]
         + rng.logistic(size=n_rows) > 0).astype(np.float32)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 255})
    bst = lgb.Booster(params={
        "objective": "binary", "num_leaves": num_leaves,
        "verbosity": -1, "max_bin": 255}, train_set=ds)
    bst.update()            # compile outside the capture
    bst._inner._flush_pending()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from profile_lib import pull, xplane_capture
    with xplane_capture(xdir):
        if not obs_tracer.annotating:
            raise RuntimeError(
                "tracer.annotate(True) did not engage under "
                "xplane_capture — obs spans will not correlate")
        for _ in range(2):
            bst.update()
        bst._inner._flush_pending()
        pull(bst._inner.train_score)
    spaces = [s for _, s in xattr.load_capture(xdir)]
    block = xattr.device_block(xdir, spaces)
    if not block["planes"]:
        raise RuntimeError(
            "xplane capture holds no TPU device plane — profiler "
            "broken on this chip?")
    kernels = block["kernels"]
    if os.environ.get("LGBM_TPU_FUSED", "1") != "0" \
            and kernels.get("fused_split", {}).get("device_ms", 0) <= 0:
        raise RuntimeError(
            "no fused_split device time attributed (classified: "
            f"{sorted(kernels)}) — kernel names drifted past the "
            "xattr classifier?")
    total = sum(k["device_ms"] for k in kernels.values())
    print(f"[tpu_smoke] device attr: {len(block['planes'])} plane(s), "
          f"{total:.3f} ms attributed, classes "
          f"{sorted(k for k in kernels)}")
    return block


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the 1M-row shape (compile check only)")
    ap.add_argument("--json", default="",
                    help="write the gate's timings as a JSON record "
                         "(lands next to BENCH_r*.json; '-' = stdout "
                         "only)")
    args = ap.parse_args()

    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    plat = jax.devices()[0].platform
    if plat != "tpu":
        print(f"[tpu_smoke] FAIL: platform is {plat!r} — this gate must "
              "run on the real TPU chip", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    timings = {}
    shapes = [("50k/63leaves", 50_048, 63)]
    if not args.fast:
        shapes.append(("1M/255leaves", 1_000_000, 255))
    try:
        # memory gate FIRST: the allocator peak is process-wide, so
        # the bigger shapes below would mask the smoke shape's
        # residency (ISSUE 9)
        tme = time.perf_counter()
        mem_gate = _check_memory()
        timings["memory"] = time.perf_counter() - tme
        for name, rows, leaves in shapes:
            timings[name] = _check(name, rows, leaves)
            timings[name + "/monotone"] = _check(
                name + "/monotone", rows, leaves,
                monotone=[1, -1] + [0] * 26)
        # fused partition+histogram split kernel: must engage by default
        # (asserted inside _check) AND grow bit-identical trees vs the
        # separate partition/hist pair
        tfi = time.perf_counter()
        _check_fused_identity()
        timings["fused_identity"] = time.perf_counter() - tfi
        # permutation vs matmul partition packing: bit-identical packed
        # combs from the compiled kernels at equal block rows, one plane
        # and two (the ISSUE-3 equivalence bar, at kernel level since
        # ISSUE 37 gave the schemes other blocks; the interpret-mode
        # matrix lives in tests/test_partition_perm.py)
        tpi = time.perf_counter()
        _check_partition_identity()
        timings["partition_identity"] = time.perf_counter() - tpi
        # observability gate: tracer output well-formed, all reference
        # phases present, counters exact on the compiled path, run
        # ledger sampled per iteration
        ttr = time.perf_counter()
        trace_ledger = _check_trace()
        timings["trace"] = time.perf_counter() - ttr
        # device-time attribution: xplane capture decoded by the
        # in-repo reader, fused kernel classified (ISSUE 6)
        txa = time.perf_counter()
        device_attr = _check_device_attr()
        timings["device_attr"] = time.perf_counter() - txa
    except Exception as e:  # noqa: BLE001 - the gate must catch everything
        print(f"[tpu_smoke] FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    total = time.perf_counter() - t0
    print(f"[tpu_smoke] GREEN in {total:.1f}s "
          f"({len(shapes) * 2} configs + memory gate + fused identity "
          "+ partition identity + trace gate + device "
          "attr, compiled TPU path)")
    if args.json:
        # schema-versioned record so the smoke timings land next to the
        # BENCH_r*.json artifacts (obs report --bench reads both)
        import json

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from profile_lib import bench_record
        rec = bench_record("tpu_smoke_wall_seconds", round(total, 2), "s",
                           checks={k: round(v, 2)
                                   for k, v in timings.items()},
                           # knob provenance so A/B smoke records can't
                           # be confused across scheme sweeps
                           # (bench_record adds the git/jax/device
                           # provenance header itself since bench/v3)
                           knobs={
                               "partition": os.environ.get(
                                   "LGBM_TPU_PARTITION", "permute"),
                               "fused": os.environ.get(
                                   "LGBM_TPU_FUSED", "1") != "0",
                           },
                           # per-iteration trajectory from the trace
                           # gate's traced train (obs run ledger)
                           ledger=trace_ledger,
                           # memory gate: predicted vs measured
                           # allocator peak on the smoke shape
                           memory_gate=mem_gate,
                           # per-kernel device times from the attr
                           # gate's xplane capture (obs attr)
                           device=device_attr)
        print(json.dumps(rec))
        if args.json != "-":
            with open(args.json, "w") as f:
                json.dump(rec, f, indent=1, sort_keys=True)
                f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
