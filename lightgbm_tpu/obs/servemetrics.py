"""Serving flight-recorder window reader + ``python -m
lightgbm_tpu.obs serve`` (ISSUE 17 tentpole, render side).

The recorder (``serve/flight.py``) rotates digest-segmented window
records (schema ``lightgbm_tpu/servemetrics/v1``) into JSONL files
under ``LGBM_TPU_SERVE_METRICS=<dir>``.  This module consumes them:

* windows group into SEGMENTS by consecutive model digest — a
  hot-swap boundary starts a new segment and two segments NEVER merge
  (the same incomparability contract routing digests follow in
  ``obs diff``);
* per segment the per-bucket latency histograms merge bin-wise and
  p50/p99/p999 are DERIVED from the merged counts (the mergeable-
  histogram contract: no sample list ever existed);
* padding waste renders as a ratio of cost-model dispatch bytes,
  queue occupancy as mean/max against the configured cap;
* SLO-threshold findings ride the shared ``obs/findings.py`` schema:
  a retrace-after-warmup is ALWAYS an error (the same-bucket
  contract); ``--slo-p99-ms`` / ``--slo-p999-ms`` / ``--max-pad-waste``
  opt into latency and waste gates; ``serve_error_*`` class events
  surface as warnings.

Exit codes follow the shared contract: 0 clean, 1 error-severity
findings, 2 nothing readable (truncated / legacy / foreign input —
one clear line, never a traceback).

``python -m lightgbm_tpu.obs.servemetrics`` regenerates the
checked-in synthetic fixture (``tests/data/servemetrics_r01.jsonl`` /
``servemetrics_expected.txt``) that ci leg 16 byte-compares.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, List, Tuple

from ..serve.flight import LatencyHistogram, SERVEMETRICS_SCHEMA
from . import findings as F

SUMMARY_SCHEMA = "lightgbm_tpu/servemetrics-summary/v1"


# ---------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------
def read_windows_file(path: str) -> List[Dict[str, Any]]:
    """Every window record in one JSONL file; raises ``ValueError``
    with a clear one-line reason on anything unreadable (empty,
    truncated mid-line, legacy/foreign schema)."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise ValueError(f"{path}: cannot read: {e}") from e
    if not text.strip():
        raise ValueError(
            f"{path}: empty file (expected servemetrics/v1 JSONL "
            "windows from LGBM_TPU_SERVE_METRICS=<dir>)")
    windows: List[Dict[str, Any]] = []
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            raise ValueError(
                f"{path}:{ln}: not valid JSON ({e}) — servemetrics "
                "files are one window object per line and rotate "
                "atomically; a torn line means the file was truncated "
                "by a foreign writer") from e
        schema = rec.get("schema") if isinstance(rec, dict) else None
        if schema != SERVEMETRICS_SCHEMA:
            raise ValueError(
                f"{path}:{ln}: schema {schema!r} is not "
                f"{SERVEMETRICS_SCHEMA} — legacy/foreign record; "
                "re-capture with LGBM_TPU_SERVE_METRICS=<dir>")
        windows.append(rec)
    return windows


def load_windows(paths: List[str]
                 ) -> Tuple[List[Dict[str, Any]], List[str]]:
    """Windows from files and/or directories (a directory expands to
    its sorted ``*.jsonl``); returns ``(windows, problems)`` where
    problems are per-file unreadable reasons (the caller exits 2 when
    NO window survived)."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            files += sorted(glob.glob(os.path.join(p, "*.jsonl")))
        else:
            files.append(p)
    windows: List[Dict[str, Any]] = []
    problems: List[str] = []
    for path in files:
        try:
            windows += read_windows_file(path)
        except ValueError as e:
            problems.append(str(e))
    if not files:
        problems.append(f"no *.jsonl servemetrics files under "
                        f"{paths[0]!r}" if paths else "no input paths")
    return windows, problems


# ---------------------------------------------------------------------
# segmentation + merge (digest boundaries never merge)
# ---------------------------------------------------------------------
def segment_windows(windows: List[Dict[str, Any]]
                    ) -> List[Dict[str, Any]]:
    """Windows in time order, grouped into consecutive same-digest
    segments with merged histograms and summed scalars."""
    ws = sorted(windows, key=lambda w: (
        float(w.get("window_start") or 0.0), int(w.get("seq") or 0)))
    segs: List[Dict[str, Any]] = []
    for w in ws:
        d = str(w.get("digest") or "?")
        if not segs or segs[-1]["digest"] != d:
            segs.append({"digest": d, "windows": []})
        segs[-1]["windows"].append(w)
    for s in segs:
        s.update(_merge_segment(s["windows"]))
    return segs


def _merge_segment(ws: List[Dict[str, Any]]) -> Dict[str, Any]:
    hist: Dict[int, LatencyHistogram] = {}
    out: Dict[str, Any] = {
        "n_windows": len(ws), "dispatches": 0, "rows_true": 0,
        "rows_padded": 0, "padding_waste_bytes": 0, "dispatch_bytes": 0,
        "queue_samples": 0, "queue_depth_sum": 0, "queue_depth_max": 0,
        "queue_depth_cap": 0, "events": {},
    }
    t0, t1 = None, None
    for w in ws:
        out["dispatches"] += int(w.get("dispatches") or 0)
        out["rows_true"] += int(w.get("rows_true") or 0)
        out["rows_padded"] += int(w.get("rows_padded") or 0)
        out["padding_waste_bytes"] += int(
            w.get("padding_waste_bytes") or 0)
        out["dispatch_bytes"] += int(w.get("dispatch_bytes") or 0)
        q = w.get("queue") or {}
        out["queue_samples"] += int(q.get("samples") or 0)
        out["queue_depth_sum"] += int(q.get("depth_sum") or 0)
        out["queue_depth_max"] = max(out["queue_depth_max"],
                                     int(q.get("depth_max") or 0))
        out["queue_depth_cap"] = max(out["queue_depth_cap"],
                                     int(q.get("depth_cap") or 0))
        for name, n in (w.get("events") or {}).items():
            out["events"][name] = out["events"].get(name, 0) + int(n)
        for b, sparse in ((w.get("latency") or {}).get("buckets")
                          or {}).items():
            try:
                bucket = int(b)
            except (TypeError, ValueError):
                continue
            h = hist.setdefault(bucket, LatencyHistogram())
            h.merge(LatencyHistogram.from_sparse(sparse))
        s, e = w.get("window_start"), w.get("window_end")
        if isinstance(s, (int, float)):
            t0 = s if t0 is None else min(t0, s)
        if isinstance(e, (int, float)):
            t1 = e if t1 is None else max(t1, e)
    out["span_s"] = round(float(t1) - float(t0), 3) \
        if t0 is not None and t1 is not None else None
    out["buckets"] = {
        b: {"count": h.count,
            "p50_ms": round(h.percentile_s(50.0) * 1e3, 3),
            "p99_ms": round(h.percentile_s(99.0) * 1e3, 3),
            "p999_ms": round(h.percentile_s(99.9) * 1e3, 3)}
        for b, h in sorted(hist.items())}
    merged = LatencyHistogram()
    for h in hist.values():
        merged.merge(h)
    out["latency_count"] = merged.count
    out["p50_ms"] = round(merged.percentile_s(50.0) * 1e3, 3)
    out["p99_ms"] = round(merged.percentile_s(99.0) * 1e3, 3)
    out["p999_ms"] = round(merged.percentile_s(99.9) * 1e3, 3)
    out["padding_waste_ratio"] = round(
        out["padding_waste_bytes"] / out["dispatch_bytes"], 4) \
        if out["dispatch_bytes"] else 0.0
    out["retraces_after_warmup"] = int(
        out["events"].get("serve_retrace_after_warmup", 0))
    return out


# ---------------------------------------------------------------------
# findings + render
# ---------------------------------------------------------------------
def score_segments(segs: List[Dict[str, Any]], *,
                   slo_p99_ms: float = 0.0, slo_p999_ms: float = 0.0,
                   max_pad_waste: float = 0.0
                   ) -> List[Dict[str, Any]]:
    out: List[Dict[str, Any]] = []
    for s in segs:
        d = s["digest"]
        if s["retraces_after_warmup"] > 0:
            out.append(F.make_finding(
                "serve", "SERVING_RETRACE",
                f"segment {d}: {s['retraces_after_warmup']} "
                "retrace(s) after warmup — a novel batch shape "
                "compiled mid-serving (the bucketed-dispatch "
                "same-bucket contract)", digest=d))
        if slo_p99_ms > 0 and s["latency_count"] \
                and s["p99_ms"] > slo_p99_ms:
            out.append(F.make_finding(
                "serve", "SLO_P99",
                f"segment {d}: p99 {s['p99_ms']:g} ms exceeds the "
                f"{slo_p99_ms:g} ms SLO", digest=d,
                p99_ms=s["p99_ms"]))
        if slo_p999_ms > 0 and s["latency_count"] \
                and s["p999_ms"] > slo_p999_ms:
            out.append(F.make_finding(
                "serve", "SLO_P999",
                f"segment {d}: p999 {s['p999_ms']:g} ms exceeds the "
                f"{slo_p999_ms:g} ms SLO", digest=d,
                p999_ms=s["p999_ms"]))
        if max_pad_waste > 0 \
                and s["padding_waste_ratio"] > max_pad_waste:
            out.append(F.make_finding(
                "serve", "PAD_WASTE",
                f"segment {d}: padding waste "
                f"{s['padding_waste_ratio']:.1%} of dispatched bytes "
                f"exceeds the {max_pad_waste:.0%} budget — batch "
                "sizes land far below their buckets (tune "
                "LGBM_TPU_SERVE_BUCKETS)", digest=d))
        errs = {k: v for k, v in s["events"].items()
                if k.startswith("serve_error_")}
        if errs:
            out.append(F.make_finding(
                "serve", "SERVE_ERRORS",
                f"segment {d}: rejected dispatches: "
                + ", ".join(f"{k[len('serve_error_'):]}={v}"
                            for k, v in sorted(errs.items())),
                severity="warning", digest=d))
    return out


def render_segments(segs: List[Dict[str, Any]],
                    problems: List[str],
                    found: List[Dict[str, Any]]) -> List[str]:
    n_win = sum(s["n_windows"] for s in segs)
    lines = [f"serve metrics: {n_win} window(s), {len(segs)} "
             f"segment(s)"
             + (f", {len(problems)} unreadable file(s)"
                if problems else "")]
    for s in segs:
        span = (f"{s['span_s']:g}s span, "
                if s.get("span_s") is not None else "")
        lines.append(
            f"  segment {s['digest']}: {s['n_windows']} window(s), "
            f"{span}{s['dispatches']} dispatch(es), "
            f"{s['rows_padded']} rows padded ({s['rows_true']} true)")
        if s["buckets"]:
            lines.append(f"    {'bucket':>8}  {'count':>7}  "
                         f"{'p50_ms':>8}  {'p99_ms':>8}  "
                         f"{'p999_ms':>8}")
            for b, h in s["buckets"].items():
                lines.append(f"    {b:>8}  {h['count']:>7}  "
                             f"{h['p50_ms']:>8.3f}  "
                             f"{h['p99_ms']:>8.3f}  "
                             f"{h['p999_ms']:>8.3f}")
        if s["dispatch_bytes"]:
            lines.append(
                f"    padding waste: {s['padding_waste_ratio']:.1%} "
                f"of {s['dispatch_bytes'] / 1e6:.1f} MB dispatched")
        if s["queue_samples"]:
            mean = s["queue_depth_sum"] / s["queue_samples"]
            lines.append(
                f"    queue depth: mean {mean:.2f}, max "
                f"{s['queue_depth_max']} (cap {s['queue_depth_cap']}), "
                f"{s['queue_samples']} sample(s)")
        if s["events"]:
            lines.append("    events: " + ", ".join(
                f"{k}={v}" for k, v in sorted(s["events"].items())))
    for msg in problems:
        lines.append(f"  unreadable: {msg}")
    lines += F.render(found)
    return lines


@F.guard("obs serve")
def run_serve(paths: List[str], *, slo_p99_ms: float = 0.0,
              slo_p999_ms: float = 0.0, max_pad_waste: float = 0.0,
              json_out: str = "") -> int:
    """CLI body for ``python -m lightgbm_tpu.obs serve``."""
    if not paths:
        return F.cli_error("obs serve",
                           "need a servemetrics directory or JSONL "
                           "path(s) (LGBM_TPU_SERVE_METRICS=<dir>)")
    missing = [p for p in paths
               if not os.path.isdir(p) and not os.path.exists(p)]
    if missing:
        return F.cli_error("obs serve",
                           f"no such file or directory: {missing[0]}")
    windows, problems = load_windows(paths)
    if not windows:
        reason = problems[0] if problems else "no windows found"
        return F.cli_error("obs serve", reason)
    segs = segment_windows(windows)
    found = score_segments(segs, slo_p99_ms=slo_p99_ms,
                           slo_p999_ms=slo_p999_ms,
                           max_pad_waste=max_pad_waste)
    for line in render_segments(segs, problems, found):
        print(line)
    if json_out:
        block = {"schema": SUMMARY_SCHEMA,
                 "segments": [{k: v for k, v in s.items()
                               if k != "windows"} for s in segs],
                 "findings": found}
        with open(json_out, "w") as f:
            json.dump(block, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"servemetrics summary -> {json_out}")
    n = len(F.errors(found))
    print(f"obs serve: {n} finding(s)" if n else
          "obs serve: clean across "
          f"{len(segs)} segment(s)")
    return F.EXIT_FINDINGS if n else F.EXIT_CLEAN


# ---------------------------------------------------------------------
# checked-in fixture (regenerate:
#   python -m lightgbm_tpu.obs.servemetrics)
# ---------------------------------------------------------------------
def synthetic_serve_windows() -> List[Dict[str, Any]]:
    """Deterministic windows spanning what the table must render: a
    clean two-window steady segment, then a hot-swapped digest whose
    single window retraces and rejects a bad-width dispatch (the
    injected error the fixture table pins at exit 1)."""
    from ..serve.flight import ServingFlightRecorder
    t = [1_000_000.0]
    rec = ServingFlightRecorder(window_s=5.0, clock=lambda: t[0])
    geom = {"trees": 64, "levels": 6, "features": 28, "num_class": 1}
    for _ in range(2):
        for i in range(60):
            rec.on_dispatch("abcdef012345", 64,
                            64 if i % 2 == 0 else 48,
                            novel=False, warm=True, geom=geom)
            rec.observe_latency("abcdef012345", 64,
                                0.0031 if i % 10 == 0 else 0.0012)
            rec.sample_queue_depth("abcdef012345", 1 + (i & 1), 2)
            t[0] += 0.05
        t[0] += 2.0
    for i in range(20):
        rec.on_dispatch("9f8e7d6c5b4a", 128, 100,
                        novel=(i == 0), warm=True, geom=geom)
        rec.observe_latency("9f8e7d6c5b4a", 128, 0.0042)
        rec.sample_queue_depth("9f8e7d6c5b4a", 2, 2)
        t[0] += 0.05
    rec.record_event("9f8e7d6c5b4a", "serve_error_input_width")
    rec.flush()
    return rec.snapshot()


def _regen_fixture() -> None:   # pragma: no cover - dev tool
    import contextlib
    import io
    here = os.path.dirname(os.path.abspath(__file__))
    data_dir = os.path.join(here, os.pardir, os.pardir, "tests",
                            "data")
    fx = os.path.join(data_dir, "servemetrics_r01.jsonl")
    with open(fx, "w") as f:
        for rec in synthetic_serve_windows():
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    print(f"wrote {fx}")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_serve([fx])
    assert rc == F.EXIT_FINDINGS, \
        f"fixture must flag its injected retrace (rc={rc})"
    out = buf.getvalue().replace(data_dir + os.sep, "")
    exp = os.path.join(data_dir, "servemetrics_expected.txt")
    with open(exp, "w") as f:
        f.write(out)
    print(f"wrote {exp}")


if __name__ == "__main__":   # pragma: no cover - fixture regeneration
    _regen_fixture()
