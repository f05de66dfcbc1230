"""Trace / bench report + diff tool: ``python -m lightgbm_tpu.obs``.

``report`` reads a JSON-lines trace written under ``LGBM_TPU_TRACE``
and prints a per-phase summary (total / count / mean, tree-ordered by
total), the counter totals, and optionally re-emits the events as a
single Chrome trace JSON array (``--chrome out.json``) loadable in
chrome://tracing or Perfetto.  ``report --bench`` summarizes
schema-versioned ``BENCH_r*.json`` records — both ``bench/v3``
(provenance + embedded run ledger) and the older ``bench/v2`` layout —
and ``--roofline`` joins the analytical cost model
(``obs/costmodel.py``) with the measured phase walls into a
roofline-utilization table.

``diff`` is the perf-regression gate (``obs/regress.py``): compare two
bench records, counters exact, walls thresholded, per-kernel device
times (the ``device`` block) thresholded too, exit non-zero on a
regression.

``attr`` is device-time kernel attribution (``obs/xattr.py``): decode
an xplane capture with the in-repo pure-python reader, classify
Mosaic/XLA kernels onto the cost-model entries, and render per-kernel
device time / predicted HBM bytes / achieved GB/s plus the per-phase
dispatch-overhead join against a traced bench record — on mesh
captures it also roots the straggler (which shard plane, which phase,
which kernel class).

``collectives`` is measured-vs-predicted ICI validation
(``obs/collectives.py``): extract collective events (all-reduce /
reduce-scatter / all-gather) with their transfer sizes per device
plane and join them against the bench record's analytical ledger rows
(``costmodel.collective_bytes``) per learner dispatch, exact or
flagged.

``mem`` is the HBM flight recorder (``obs/mem.py``): the exact
per-buffer footprint table + per-phase live-sets the cost model
predicts for a record's shape, the measured residency timeline the run
ledger sampled, the measured-vs-predicted allocator-peak join
(exceeding tolerance = finding), and ``--plan`` — the page-schedule
planner for larger-than-HBM shapes (``costmodel.page_schedule``).

``doctor`` is the layered environment preflight (``obs/doctor.py``,
findings schema ``lightgbm_tpu/doctor/v1``): backend/device
enumeration, libtpu/PJRT plugin presence, the ``TPU_WORKER_HOSTNAMES``
env class that killed BENCH_r03 (``--log`` classifies a captured
bring-up log), topology vs ``--mesh F,S``, reported HBM/VMEM vs the
costmodel tables, an xplane capture->decode smoke, and capture-dir
disk headroom.  ``tools/chip_run.py`` runs it as its first, gating
step.

``trend`` is the bench-trajectory view (``obs/trend.py``): a
routing-digest-aware table over a directory of BENCH records with
drift flags between comparable consecutive records and re-capture
pointers on legacy v1/v2 artifacts.

All CLI paths parse defensively through the shared helper
(``obs/findings.py``): every subcommand exits 0 (clean) / 1
(findings) / 2 (unusable input) with one clear message per file —
never a traceback (the S3 contract in tests/test_obs_tools.py).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Iterable, List, Tuple

# canonical bench-record schema ids: regress.KNOWN_SCHEMAS and
# tools/profile_lib.BENCH_SCHEMA import from HERE — a v4 bump edits
# this one site
BENCH_SCHEMA_V2 = "lightgbm_tpu/bench/v2"
BENCH_SCHEMA_V3 = "lightgbm_tpu/bench/v3"


def load_events(path: str, strict: bool = True
                ) -> Tuple[List[dict], dict]:
    """Parse a JSON-lines trace; returns (events, metadata).

    ``strict=False`` (the CLI default) skips unparseable lines —
    counting them in ``metadata["skipped_lines"]`` — so a trace
    truncated mid-write (killed run) still reports; ``strict=True``
    (the programmatic default, e.g. tpu_smoke's trace gate) raises on
    the first malformed line.
    """
    events, meta = [], {}
    skipped = 0
    with open(path) as f:
        for line_no, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError as e:
                if strict:
                    raise ValueError(
                        f"{path}:{line_no}: invalid JSON line: {e}"
                    ) from e
                skipped += 1
                continue
            if not isinstance(ev, dict):
                if strict:
                    raise ValueError(
                        f"{path}:{line_no}: expected a JSON object, "
                        f"got {type(ev).__name__}")
                skipped += 1
                continue
            if ev.get("ph") == "M":
                meta = ev
            else:
                events.append(ev)
    if skipped:
        meta = dict(meta, skipped_lines=skipped)
    return events, meta


def phase_summary(events: Iterable[dict]) -> Dict[str, dict]:
    """{span name: {total_s, count, mean_s}} from complete-span events."""
    acc: Dict[str, List[float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        a = acc.setdefault(ev["name"], [0.0, 0])
        a[0] += ev.get("dur", 0.0) / 1e6
        a[1] += 1
    return {name: {"total_s": a[0], "count": a[1],
                   "mean_s": a[0] / max(a[1], 1)}
            for name, a in sorted(acc.items(), key=lambda kv: -kv[1][0])}


def counter_totals(events: Iterable[dict]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for ev in events:
        if ev.get("ph") == "C":
            out[ev["name"]] = out.get(ev["name"], 0.0) \
                + float(ev.get("args", {}).get("value", 0.0))
    return out


def write_chrome_trace(events: List[dict], out_path: str) -> None:
    """Wrap the line events into the Chrome trace array format."""
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)


def print_trace_report(path: str, chrome_out: str = "",
                       strict: bool = False) -> None:
    events, meta = load_events(path, strict=strict)
    if meta.get("schema"):
        print(f"trace {path} (schema {meta['schema']}):")
    else:
        print(f"trace {path} (no metadata line):")
    if meta.get("skipped_lines"):
        print(f"  WARNING: {meta['skipped_lines']} unparseable line(s) "
              "skipped (truncated trace?)")
    summary = phase_summary(events)
    if summary:
        width = max(len(n) for n in summary)
        print(f"  {'phase'.ljust(width)}  {'total':>10}  {'count':>7}  "
              f"{'mean':>10}")
        for name, s in summary.items():
            print(f"  {name.ljust(width)}  {s['total_s']:>9.4f}s  "
                  f"{s['count']:>7d}  {s['mean_s'] * 1e3:>8.3f}ms")
    elif not events:
        print("  (no events)")
    counters = counter_totals(events)
    for name, v in sorted(counters.items()):
        print(f"  counter {name}: {v:g}")
    if chrome_out:
        write_chrome_trace(events, chrome_out)
        print(f"  chrome trace -> {chrome_out}")


def _load_bench(path: str) -> dict:
    from .regress import load_record
    return load_record(path)


def print_bench_report(paths: List[str], roofline: bool = False,
                       peak_bw: float = 0.0,
                       peak_tflops: float = 0.0) -> int:
    rc = 0
    for path in paths:
        try:
            rec = _load_bench(path)
        except ValueError as e:
            print(f"obs report: {e}")
            rc = 1
            continue
        if rec.get("_legacy_multichip"):
            # pre-ISSUE-8 MULTICHIP_r*.json dryrun artifact: tolerated
            # with a clear fallback message, not a generic schema error
            status = ("ok" if rec.get("ok")
                      else f"FAILED (rc={rec.get('rc')})")
            print(f"{path}: legacy multichip dryrun artifact "
                  f"(pre-bench/v3): n_devices={rec.get('n_devices')}, "
                  f"{status}")
            print("  no metric/ledger to report — re-capture with "
                  "tools/multichip_probe.py for a diffable bench/v3 "
                  "record with the multichip block")
            continue
        schema = rec.get("schema", "(pre-v2, unversioned)")
        print(f"{path}: schema={schema}")
        if rec.get("_schema_note"):
            print(f"  WARNING: {rec['_schema_note']}")
        prov = rec.get("provenance")
        if prov:
            print(f"  provenance: git {prov.get('git_sha', '?')}, "
                  f"jax {prov.get('jax', '?')}, "
                  f"{prov.get('backend', '?')}/"
                  f"{prov.get('device_kind', '?')}"
                  f" x{prov.get('n_devices', '?')}")
        elif schema == BENCH_SCHEMA_V2:
            print("  (bench/v2 record: no provenance block — "
                  "re-capture for v3)")
        print(f"  {rec.get('metric', '?')}: {rec.get('value', '?')} "
              f"{rec.get('unit', '')} (vs_baseline "
              f"{rec.get('vs_baseline', '?')})")
        if rec.get("knobs"):
            print(f"  knobs: {json.dumps(rec['knobs'], sort_keys=True)}")
        for pt in rec.get("scaling", []):
            print(f"    rows={pt.get('rows'):>9}: "
                  f"{pt.get('iters_per_sec')} iters/sec")
        phases = rec.get("phases", {})
        for name, s in phases.items():
            if isinstance(s, dict):
                print(f"    phase {name}: {s.get('total_s', 0):.4f}s "
                      f"x{s.get('count', 0)}")
        for name, v in sorted(rec.get("counters", {}).items()):
            print(f"    counter {name}: {v:g}")
        for name, v in sorted(rec.get("events", {}).items()):
            print(f"    event {name}: {v:g}")
        ledger = rec.get("ledger") or {}
        iters = ledger.get("iterations") or []
        if iters:
            from .regress import _median
            walls = [r["wall_s"] for r in iters if r.get("wall_s")]
            print(f"    ledger: {len(iters)} iterations"
                  + (f", median wall {_median(walls) * 1e3:.2f}ms"
                     if walls else ""))
        dev = rec.get("device") or {}
        if dev.get("error"):
            print(f"    device block: capture failed: {dev['error']}")
        elif dev and not dev.get("planes"):
            print("    device block: capture held no device plane "
                  "(host-only run — re-capture on chip for kernel "
                  "attribution)")
        elif dev.get("kernels"):
            total = sum(k.get("device_ms", 0.0)
                        for k in dev["kernels"].values())
            print(f"    device: {len(dev.get('planes', []))} plane(s), "
                  f"{total:.3f} ms attributed — inspect with "
                  "obs attr")
            skew = dev.get("skew") or {}
            if skew.get("ratio"):
                print(f"      shard skew x{skew['ratio']:g} "
                      f"({skew['min_ms']:.3f}..{skew['max_ms']:.3f} ms)")
            strag = dev.get("straggler") or {}
            if strag.get("plane"):
                # .get defaults throughout: a truncated device block
                # must degrade to a partial line, never a traceback
                top = ", ".join(
                    f"{c.get('kernel', '?')} "
                    f"+{float(c.get('delta_ms', 0.0)):.3f} ms "
                    f"(phase {c.get('phase', '-')})"
                    for c in strag.get("causes", [])[:3])
                print(f"      straggler {strag['plane']} "
                      f"+{float(strag.get('delta_ms', 0.0)):.3f} ms "
                      f"vs {strag.get('vs_plane', 'fastest')}"
                      + (f": {top}" if top else ""))
            for phase, j in (dev.get("phases") or {}).items():
                print(f"      {phase}: device {j['device_ms']:.3f} ms, "
                      f"dispatch overhead "
                      f"{j['dispatch_overhead_ms']:.3f} ms")
        memb = rec.get("memory") or {}
        if memb.get("predicted"):
            # .get defaults throughout: a truncated memory block must
            # degrade to a partial line, never a traceback
            pred = memb["predicted"]
            meas = memb.get("measured") or {}
            meas_txt = ""
            mpk = meas.get("alloc_peak_bytes",
                           meas.get("live_peak_bytes"))
            if mpk is not None:
                meas_txt = f", measured peak {float(mpk) / 1e6:.2f} MB"
            print(f"    memory: predicted peak "
                  f"{float(pred.get('peak_bytes', 0)) / 1e6:.2f} MB "
                  f"({pred.get('peak_phase', '?')}){meas_txt} — "
                  "inspect with obs mem")
            if memb.get("finding"):
                print(f"      FINDING: {memb['finding']}")
        for coll in ledger.get("collectives", []):
            skew = ""
            if coll.get("skew_max") is not None:
                skew = (f", shard rows {coll.get('skew_min'):g}.."
                        f"{coll.get('skew_max'):g}")
            print(f"    collective {coll.get('name')}: "
                  f"~{coll.get('bytes_moved', 0) / 1e6:.2f} MB moved"
                  f"{skew}")
        mesh_led = ledger.get("mesh") or {}
        if mesh_led:
            # defensive: a truncated/hand-edited mesh block (series
            # without the derived ratios) renders partially, never a
            # traceback (the S3 CLI contract)
            skew_s = mesh_led.get("skew_series") or []
            med = mesh_led.get("skew_median_ratio")
            mx = mesh_led.get("skew_max_ratio")
            skew_txt = ""
            if skew_s and med is not None and mx is not None:
                skew_txt = (f", skew ratio median x{med:g} "
                            f"max x{mx:g} "
                            f"over {len(skew_s)} dispatch(es)")
            print(f"    mesh: {mesh_led.get('shards')} shard(s), "
                  f"{mesh_led.get('dispatches')} dispatch(es), "
                  f"~{float(mesh_led.get('bytes_moved_total') or 0) / 1e6:.2f} "
                  f"MB ICI per shard{skew_txt}")
        sv = rec.get("serving") or {}
        if sv:
            # .get defaults throughout: a truncated serving block must
            # degrade to a partial line, never a traceback (satellite:
            # the block used to be silent in the report view)
            retr = sv.get("retraces_after_warmup")
            print(f"    serving: digest {sv.get('digest', '?')}, "
                  f"{sv.get('bulk_rows_per_sec', '?')} rows/sec bulk, "
                  f"p99 {sv.get('p99_ms', '?')} ms"
                  + (f", p999 {sv.get('p999_ms')} ms"
                     if sv.get("p999_ms") is not None else "")
                  + f", {retr if retr is not None else '?'} "
                    "retrace(s) after warmup")
            waste = sv.get("padding_waste_ratio")
            if isinstance(waste, (int, float)):
                print(f"      padding waste {waste:.1%} of dispatched "
                      "bytes — inspect windows with obs serve")
        mc = rec.get("multichip") or {}
        if mc:
            mesh_ax = (mc.get("mesh") or {}).get("axes")
            print(f"    multichip: schema={mc.get('schema', '?')}, "
                  f"mesh {mesh_ax}, "
                  f"{mc.get('n_shards', '?')} shard(s)")
        if roofline:
            rc = max(rc, _print_roofline(rec, peak_bw, peak_tflops))
    return rc


def _print_roofline(rec: dict, peak_bw: float,
                    peak_tflops: float) -> int:
    from .costmodel import (RecordModelError, RooflineNotMeasured,
                            roofline_peak, roofline_table)
    try:
        rows = roofline_table(rec, peak_bw_gbps=peak_bw or None,
                              peak_tflops=peak_tflops or None)
        # the header states the roof roofline_table judged against
        # (flag, then env override, then the record's device_kind)
        bw = roofline_peak(rec, "bw_gbps", peak_bw or None)
        tf = roofline_peak(rec, "tflops", peak_tflops or None)
    except RooflineNotMeasured as e:
        print(f"    roofline: {e}")
        return 0
    except RecordModelError as e:
        print(f"    roofline: {e}")
        return 1
    print(f"    roofline (peak {bw:g} GB/s, {tf:g} TFLOPs):")
    print(f"      {'phase':<20} {'pred GB':>9} {'wall':>9} "
          f"{'GB/s':>8} {'%bw':>6} {'%flops':>7}  bound")
    for r in rows:
        if "gbps" in r:
            print(f"      {r['phase']:<20} {r['pred_gb']:>9.3f} "
                  f"{r['wall_s']:>8.3f}s {r['gbps']:>8.1f} "
                  f"{r['bw_util']:>6.1%} {r['flops_util']:>7.2%}  "
                  f"{r['bound']}")
        else:
            print(f"      {r['phase']:<20} {r['pred_gb']:>9.3f} "
                  f"{'(no wall measured)':>26}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs",
        description="trace / bench reporting + perf diff for "
                    "lightgbm_tpu telemetry")
    sub = ap.add_subparsers(dest="cmd", required=True)
    rp = sub.add_parser("report", help="summarize a JSONL trace or "
                                       "BENCH_r*.json records")
    rp.add_argument("paths", nargs="+",
                    help="trace .jsonl file(s) or, with --bench, "
                         "BENCH_r*.json record(s)")
    rp.add_argument("--bench", action="store_true",
                    help="treat paths as schema-versioned bench records")
    rp.add_argument("--chrome", default="",
                    help="also write a Chrome trace array to this path")
    rp.add_argument("--roofline", action="store_true",
                    help="with --bench: join the analytical cost model "
                         "with measured phase walls (traced v3 records)")
    rp.add_argument("--peak-bw", type=float, default=0.0,
                    help="roofline HBM peak in GB/s (default: "
                         "LGBM_TPU_PEAK_BW_GBPS, else the record's "
                         "device_kind in costmodel.DEVICE_KINDS)")
    rp.add_argument("--peak-tflops", type=float, default=0.0,
                    help="roofline compute peak in TFLOPs (default: "
                         "LGBM_TPU_PEAK_TFLOPS, else the record's "
                         "device_kind)")
    atp = sub.add_parser("attr", help="device-time kernel attribution "
                                      "from an xplane capture")
    atp.add_argument("xplane", help="capture dir (recursive "
                                    "*.xplane.pb glob) or one .pb file")
    atp.add_argument("--bench", default="",
                     help="traced bench/v3 record: joins cost-model "
                          "HBM bytes (achieved GB/s per kernel) and "
                          "per-phase dispatch overhead")
    atp.add_argument("--roofline", action="store_true",
                     help="with --bench: add %%-of-peak-BW columns")
    atp.add_argument("--peak-bw", type=float, default=0.0,
                     help="roofline HBM peak in GB/s (default: "
                          "LGBM_TPU_PEAK_BW_GBPS, else the record's "
                          "device_kind)")
    atp.add_argument("--top", type=int, default=0,
                     help="also print per-plane detail with the top N "
                          "raw op names")
    atp.add_argument("--json", default="", dest="json_out",
                     help="write the device block (bench/v3 "
                          "rec['device'] shape) to this path")
    atp.add_argument("--no-tf", action="store_true",
                     help="skip the optional tensorflow.tsl fast path "
                          "(force the pure-python decoder)")
    cp = sub.add_parser("collectives",
                        help="measured-vs-predicted ICI validation "
                             "from an xplane capture")
    cp.add_argument("xplane", help="capture dir (recursive "
                                   "*.xplane.pb glob) or one .pb file")
    cp.add_argument("--bench", default="",
                    help="traced mesh bench/v3 record whose ledger "
                         "collective rows are the analytical side of "
                         "the join")
    cp.add_argument("--json", default="", dest="json_out",
                    help="write the collectives block to this path")
    cp.add_argument("--no-tf", action="store_true",
                    help="skip the optional tensorflow.tsl fast path "
                         "(force the pure-python decoder)")
    mp = sub.add_parser("mem", help="HBM footprint report + "
                                    "measured-vs-predicted residency "
                                    "join + page planner")
    mp.add_argument("paths", nargs="*",
                    help="traced bench/v3 record(s); optional with "
                         "--plan --rows --features")
    mp.add_argument("--plan", action="store_true",
                    help="emit a page schedule (costmodel."
                         "page_schedule) for a larger-than-HBM shape")
    mp.add_argument("--rows", type=int, default=0,
                    help="plan geometry: real row count")
    mp.add_argument("--features", type=int, default=0,
                    help="plan geometry: padded feature count (f_pad)")
    mp.add_argument("--bins", type=int, default=None,
                    help="plan geometry: padded bin width (default: "
                         "the record's, else 256)")
    mp.add_argument("--leaves", type=int, default=None,
                    help="plan geometry: num_leaves (default: the "
                         "record's, else 255)")
    mp.add_argument("--shards", type=int, default=None,
                    help="plan geometry: row shards (default: the "
                         "record's, else 1)")
    mp.add_argument("--stream", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="plan geometry: stream-mode layout "
                         "(--no-stream adds the grad/hess/inbag "
                         "per-row buffers; default: the record's "
                         "stream flag, else stream on)")
    mp.add_argument("--rows-per-page", type=int, default=0,
                    help="validate this page size instead of choosing "
                         "one")
    mp.add_argument("--mem-tol", type=float, default=None,
                    help="measured-over-predicted tolerance "
                         "(default 0.10)")
    dcp = sub.add_parser("doctor",
                         help="layered environment preflight for the "
                              "next chip run (exit 1 on findings)")
    dcp.add_argument("--mesh", default="",
                     help="expected mesh as F,S — device count is "
                          "checked against F*S")
    dcp.add_argument("--log", default="",
                     help="classify a captured bring-up failure log "
                          "into a named class (the BENCH_r03 "
                          "regression pin)")
    dcp.add_argument("--expect-backend", default="auto",
                     choices=["auto", "cpu", "tpu", "gpu"],
                     help="fail unless this backend resolves "
                          "(default: whatever resolves is reported)")
    dcp.add_argument("--dir", default="", dest="capture_dir",
                     help="capture dir whose disk headroom is checked "
                          "(default: LGBM_TPU_CHIPRUN_DIR or .)")
    dcp.add_argument("--json", default="", dest="json_out",
                     help="write the doctor block "
                          "(lightgbm_tpu/doctor/v1) to this path")
    dcp.add_argument("--no-xplane-smoke", action="store_true",
                     help="skip the capture->decode smoke (e.g. when "
                          "another profiler session is live)")
    tp = sub.add_parser("trend",
                        help="bench-trajectory table over a directory "
                             "of BENCH records, with drift flags")
    tp.add_argument("paths", nargs="+",
                    help="record directory (its *.json, sorted) or "
                         "explicit bench record paths")
    tp.add_argument("--drift-tol", type=float, default=None,
                    help="relative drift tolerance between comparable "
                         "consecutive records (default 0.25)")
    tp.add_argument("--json", default="", dest="json_out",
                    help="write the trend block "
                         "(lightgbm_tpu/trend/v1) to this path")
    svp = sub.add_parser("serve",
                         help="serving flight-recorder window report "
                              "(servemetrics/v1 JSONL, digest-"
                              "segmented, SLO findings)")
    svp.add_argument("paths", nargs="+",
                     help="servemetrics directory (its *.jsonl, "
                          "sorted) or explicit JSONL window file(s)")
    svp.add_argument("--slo-p99-ms", type=float, default=0.0,
                     help="flag a segment whose merged p99 exceeds "
                          "this many ms (0 = no latency SLO)")
    svp.add_argument("--slo-p999-ms", type=float, default=0.0,
                     help="flag a segment whose merged p999 exceeds "
                          "this many ms (0 = no tail SLO)")
    svp.add_argument("--max-pad-waste", type=float, default=0.0,
                     help="flag a segment whose padding-waste ratio "
                          "of dispatched bytes exceeds this fraction "
                          "(0 = no waste budget)")
    svp.add_argument("--json", default="", dest="json_out",
                     help="write the summary block (lightgbm_tpu/"
                          "servemetrics-summary/v1) to this path")
    wp = sub.add_parser("watch",
                        help="stall watchdog over live pulse "
                             "heartbeat streams (pulse/v1 JSONL; "
                             "exit 1 on STALLED / RATE_COLLAPSE / "
                             "CKPT_OVERDUE / SERVING_SLO)")
    wp.add_argument("paths", nargs="+",
                    help="pulse directory (its pulse-*.jsonl, "
                         "sorted) or explicit stream file(s)")
    wp.add_argument("--once", action="store_true",
                    help="evaluate one pass and exit (CI / the "
                         "chip_run sidecar); default tails the "
                         "streams until interrupted")
    wp.add_argument("--now", type=float, default=0.0,
                    help="pin the evaluation clock to this epoch "
                         "second (fixture determinism; 0 = wall "
                         "clock)")
    wp.add_argument("--interval", type=float, default=0.0,
                    help="live re-evaluation period in seconds "
                         "(default: half the smallest stream "
                         "cadence)")
    wp.add_argument("--stall-k", type=float, default=0.0,
                    help="missed-cadence multiple before a stream is "
                         "STALLED (default 3)")
    wp.add_argument("--rate-drop", type=float, default=-1.0,
                    help="EMA-vs-trailing-median floor for "
                         "RATE_COLLAPSE (default 0.4; 0 disables)")
    wp.add_argument("--ckpt-slack", type=float, default=0.0,
                    help="promised-checkpoint-cadence multiple "
                         "before CKPT_OVERDUE (default 2)")
    wp.add_argument("--slo-p99-ms", type=float, default=0.0,
                    help="flag a serving stream whose last window "
                         "p99 exceeds this many ms (0 = no SLO)")
    tlp = sub.add_parser("timeline",
                         help="unified cross-process timeline: pulse "
                              "streams + chip_run journal + ckpt "
                              "manifests + servemetrics windows on "
                              "one clock")
    tlp.add_argument("paths", nargs="+",
                     help="run directory (pulse-*.jsonl, "
                          "journal.jsonl, servemetrics-*.jsonl, "
                          "ckpt_*/manifest.json) or explicit source "
                          "file(s)")
    dp = sub.add_parser("diff", help="noise-aware perf diff of two "
                                     "bench records (the CI gate)")
    dp.add_argument("baseline", help="baseline bench record (A.json)")
    dp.add_argument("candidate", help="candidate bench record (B.json)")
    dp.add_argument("--wall-tol", type=float, default=None,
                    help="relative wall-time tolerance (default 0.25)")
    dp.add_argument("--min-wall", type=float, default=None,
                    help="ignore phases below this wall in seconds "
                         "(default 0.002)")
    dp.add_argument("--allow-knob-mismatch", action="store_true",
                    help="diff records captured under different "
                         "engaged knob sets anyway")
    args = ap.parse_args(argv)
    # every subcommand body runs under the shared guard
    # (obs/findings.py): expected failures return 0/1/2 themselves,
    # anything that escapes becomes one line + exit 2 — no subcommand
    # may traceback on bad input (the ISSUE-11 consolidation)
    from . import findings as _F
    if args.cmd == "doctor":
        from .doctor import run_doctor_cli
        return run_doctor_cli(mesh=args.mesh, log=args.log,
                              expect_backend=args.expect_backend,
                              json_out=args.json_out,
                              capture_dir=args.capture_dir,
                              xplane_smoke=not args.no_xplane_smoke)
    if args.cmd == "trend":
        from .trend import DEFAULT_DRIFT_TOL, run_trend
        return run_trend(args.paths,
                         tol=(args.drift_tol
                              if args.drift_tol is not None
                              else DEFAULT_DRIFT_TOL),
                         json_out=args.json_out)
    if args.cmd == "serve":
        from .servemetrics import run_serve
        return run_serve(args.paths, slo_p99_ms=args.slo_p99_ms,
                         slo_p999_ms=args.slo_p999_ms,
                         max_pad_waste=args.max_pad_waste,
                         json_out=args.json_out)
    if args.cmd == "watch":
        from .pulse import run_watch
        return run_watch(args.paths, once=args.once, now=args.now,
                         interval_s=args.interval,
                         stall_k=args.stall_k,
                         rate_drop=args.rate_drop,
                         ckpt_slack=args.ckpt_slack,
                         slo_p99_ms=args.slo_p99_ms)
    if args.cmd == "timeline":
        from .pulse import run_timeline
        return run_timeline(args.paths)
    if args.cmd == "mem":
        from .mem import DEFAULT_MEM_TOL, run_mem
        return _F.guard("obs mem")(run_mem)(
            args.paths, plan=args.plan, rows=args.rows,
            features=args.features, bins=args.bins,
            leaves=args.leaves,
            shards=args.shards, stream=args.stream,
            rows_per_page=args.rows_per_page,
            tol=(args.mem_tol if args.mem_tol is not None
                 else DEFAULT_MEM_TOL))
    if args.cmd == "collectives":
        from .collectives import run_collectives
        return _F.guard("obs collectives")(run_collectives)(
            args.xplane, bench=args.bench, json_out=args.json_out,
            prefer_tf=not args.no_tf)
    if args.cmd == "attr":
        from .xattr import run_attr
        return _F.guard("obs attr")(run_attr)(
            args.xplane, bench=args.bench,
            roofline=args.roofline, peak_bw=args.peak_bw,
            top=args.top, json_out=args.json_out,
            prefer_tf=not args.no_tf)
    if args.cmd == "diff":
        from .regress import (DEFAULT_MIN_WALL_S, DEFAULT_WALL_TOL,
                              diff_paths)
        return _F.guard("obs diff")(diff_paths)(
            args.baseline, args.candidate,
            wall_tol=(args.wall_tol if args.wall_tol is not None
                      else DEFAULT_WALL_TOL),
            min_wall_s=(args.min_wall if args.min_wall is not None
                        else DEFAULT_MIN_WALL_S),
            allow_knob_mismatch=args.allow_knob_mismatch)
    if args.bench:
        return _F.guard("obs report")(print_bench_report)(
            args.paths, roofline=args.roofline, peak_bw=args.peak_bw,
            peak_tflops=args.peak_tflops)
    if args.chrome and len(args.paths) > 1:
        ap.error("--chrome takes exactly one trace path (the "
                 "converted file would be silently overwritten "
                 "per input)")
    rc = 0
    for p in args.paths:
        try:
            print_trace_report(p, chrome_out=args.chrome)
        except (OSError, ValueError) as e:
            # per-file unreadability is a FINDING here (exit 1, the
            # pinned report contract): the other paths stay readable
            print(f"obs report: {p}: {e}")
            rc = max(rc, _F.EXIT_FINDINGS)
    return rc


if __name__ == "__main__":
    sys.exit(main())
