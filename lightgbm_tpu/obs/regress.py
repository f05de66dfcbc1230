"""Perf-regression gate: noise-aware comparison of two bench records
(ISSUE 5 tentpole 3).

``python -m lightgbm_tpu.obs diff BASELINE.json CANDIDATE.json``
compares two schema-versioned bench records (bench/v2 or v3, including
the per-iteration ledger trajectories v3 records embed) and classifies
every difference:

* **walls are thresholded** — iters/sec, phase totals and per-iteration
  medians are noisy; a difference only counts as a regression past
  ``--wall-tol`` (default 25%), and spans below ``--min-wall`` are
  ignored entirely (a 0.4 ms span doubling is scheduler noise, not a
  kernel regression);
* **median-of-k aware** — when both records embed a ledger trajectory,
  per-phase and per-iteration comparisons use the MEDIAN across
  iterations, not the total (one straggler iteration — a GC pause, a
  recompile — cannot fail the gate);
* **counters are exact** — splits / rows_partitioned /
  rows_histogrammed / fused_splits are deterministic functions of the
  trained trees; ANY difference means the candidate trained different
  trees or took a different kernel path, and is flagged regardless of
  tolerance;
* **events gate structure** — an obs event appearing in the candidate
  (``hist_scatter_psum_fallback``, a ``routing_fallback_*``) means a
  slow path silently engaged: flagged;
* **device kernels are thresholded like walls** (ISSUE 6) — records
  carrying a ``device`` block (xplane-attributed per-kernel device
  times, ``obs attr``) compare per kernel class under the same
  ``--wall-tol`` / ``--min-wall`` rules; a kernel class APPEARING in
  the candidate above the floor (a kernel newly on the hot path) is a
  regression, one disappearing is surfaced as changed;
* **HBM residency peaks are thresholded like walls** (ISSUE 9) —
  records carrying measured memory peaks (the ``memory`` block's
  live-array / allocator maxima, or the raw ledger residency series)
  compare under the same ``--wall-tol`` when BOTH records measured;
  peaks below 64 KiB are allocator-rounding noise and ignored;
* **knob mismatches are incomparable** — records captured under
  different engaged knob sets (partition / fused) answer
  different questions; the diff refuses (exit 2) unless
  ``--allow-knob-mismatch``;
* **mesh records gate the flight recorder** (ISSUE 8) — records whose
  ledgers carry mesh collective rows compare shard counts first
  (mismatch = incomparable, exit 2: an 8-shard record and a 16-shard
  record answer different questions), then the analytical collective
  BYTES exactly (deterministic functions of shape and shard count —
  any drift means the cost model or the engaged merge changed) and
  the per-dispatch shard-skew ratio under the wall tolerance (a bag
  that suddenly loads one shard 2x is a regression even when the
  total row count is unchanged).  Legacy ``MULTICHIP_r*.json`` dryrun
  artifacts ({n_devices, rc, ok, tail}) are recognized with a clear
  fallback message — re-capture with ``tools/multichip_probe.py``.

``tools/perf_gate.py`` wraps this as the CI gate ``tools/ci_tier1.sh``
runs (self-diff must pass, an injected 2x phase regression must fail).
Exit codes: 0 clean, 1 regression(s), 2 incomparable / unreadable.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from .report import BENCH_SCHEMA_V2, BENCH_SCHEMA_V3

DEFAULT_WALL_TOL = 0.25
DEFAULT_MIN_WALL_S = 2e-3

# units where a LARGER candidate value is an improvement
HIGHER_IS_BETTER_UNITS = {"iters/sec", "rows/sec", "items/sec"}

KNOWN_SCHEMAS = (BENCH_SCHEMA_V2, BENCH_SCHEMA_V3)


def load_record(path: str) -> Dict[str, Any]:
    """Read one bench record with clear failure messages (S3: empty /
    truncated / non-JSON inputs must not traceback)."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        raise ValueError(f"{path}: cannot read: {e}") from e
    if not text.strip():
        raise ValueError(f"{path}: empty file (expected one JSON bench "
                         "record, e.g. from bench.py --json)")
    try:
        rec = json.loads(text)
    except json.JSONDecodeError as e:
        raise ValueError(
            f"{path}: not valid JSON ({e}); bench records are a single "
            "JSON object — was the file truncated mid-write?") from e
    if not isinstance(rec, dict):
        raise ValueError(f"{path}: expected a JSON object bench record, "
                         f"got {type(rec).__name__}")
    schema = rec.get("schema")
    if schema is None and "n_devices" in rec and "rc" in rec:
        # pre-ISSUE-8 MULTICHIP_r*.json dryrun artifact: {n_devices,
        # rc, ok, skipped, tail} — no metric, no ledger, nothing to
        # diff.  Recognized so every reader gives the same actionable
        # message instead of a generic "unknown schema".
        rec["_legacy_multichip"] = True
        rec.setdefault("_schema_note",
                       "legacy multichip dryrun artifact (n_devices="
                       f"{rec.get('n_devices')}, ok={rec.get('ok')}); "
                       "carries no bench metric or ledger — re-capture "
                       "with tools/multichip_probe.py for a diffable "
                       "bench/v3 record")
        return rec
    if schema not in KNOWN_SCHEMAS:
        # pre-v2 / foreign records still diff best-effort, but say so
        rec.setdefault("_schema_note",
                       f"unknown schema {schema!r} (best-effort diff; "
                       f"known: {', '.join(KNOWN_SCHEMAS)})")
    return rec


def _median(values: List[float]) -> float:
    vs = sorted(values)
    n = len(vs)
    if n == 0:
        return 0.0
    if n % 2:
        return vs[n // 2]
    return 0.5 * (vs[n // 2 - 1] + vs[n // 2])


def _ledger_phase_medians(rec: Dict[str, Any]) -> Dict[str, float]:
    """Per-phase MEDIAN wall across the record's ledger iterations
    ({} when the record carries no trajectory)."""
    iters = (rec.get("ledger") or {}).get("iterations") or []
    series: Dict[str, List[float]] = {}
    for row in iters:
        for name, dur in (row.get("phases") or {}).items():
            series.setdefault(name, []).append(float(dur))
    return {name: _median(vals) for name, vals in series.items()}


def _device_kernel_seconds(rec: Dict[str, Any]) -> Dict[str, float]:
    """Per-kernel-class device time in SECONDS from the record's
    xplane-attributed ``device`` block ({} when the record carries
    none) — so the wall tolerance / min-wall floor apply unchanged."""
    kernels = (rec.get("device") or {}).get("kernels") or {}
    out: Dict[str, float] = {}
    for name, k in kernels.items():
        ms = k.get("device_ms") if isinstance(k, dict) else None
        if isinstance(ms, (int, float)):
            out[name] = float(ms) / 1e3
    return out


def _ledger_iter_walls(rec: Dict[str, Any]) -> List[float]:
    iters = (rec.get("ledger") or {}).get("iterations") or []
    return [float(r["wall_s"]) for r in iters if r.get("wall_s")]


def _mem_peaks(rec: Dict[str, Any]) -> Dict[str, float]:
    """Measured HBM residency peaks in BYTES (ISSUE 9): from the
    record's ``memory`` block when present, recomputed from the raw
    ledger residency series otherwise ({} for untraced records) — so
    peak bytes gate like walls even on records written before the
    memory block existed."""
    meas = (rec.get("memory") or {}).get("measured") or {}
    out: Dict[str, float] = {}
    live = meas.get("live_peak_bytes")
    alloc = meas.get("alloc_peak_bytes")
    if live is None and alloc is None:
        # one extractor for the ledger residency series (obs/mem.py) —
        # the gate and the obs mem report must read the same numbers
        from .mem import measured_from_record
        series = measured_from_record(rec)
        live = series.get("live_peak_bytes")
        alloc = series.get("alloc_peak_bytes")
    if live is not None:
        out["hbm_live_peak_bytes"] = float(live)
    if alloc is not None:
        out["hbm_alloc_peak_bytes"] = float(alloc)
    return out


# residency peaks below this are noise (allocator rounding on tiny
# CPU-suite shapes), mirroring DEFAULT_MIN_WALL_S for walls
MIN_MEM_BYTES = 64 << 10


def _mesh_view(rec: Dict[str, Any]) -> Dict[str, Any]:
    """The record's mesh flight-recorder view: shard count, dispatch
    count, total analytical collective bytes and the per-dispatch skew
    ratios — from the ledger ``mesh`` summary when present, recomputed
    from the raw collective rows otherwise ({} for serial records)."""
    ledger = rec.get("ledger") or {}
    colls = ledger.get("collectives") or []
    mc = rec.get("multichip") or {}
    out: Dict[str, Any] = {}
    mesh = ledger.get("mesh") or {}
    shards = mc.get("n_shards") or mesh.get("shards") or max(
        (int(c.get("shards", 0)) for c in colls), default=0)
    if not shards and not colls:
        return out
    out["shards"] = int(shards)
    out["dispatches"] = mesh.get("dispatches", len(colls))
    out["bytes"] = mesh.get("bytes_moved_total", sum(
        int(c.get("bytes_moved", 0)) for c in colls))
    ratios = [s for s in (mesh.get("skew_series") or [])
              if s is not None]
    if not ratios:
        for c in colls:
            hi, lo = c.get("skew_max"), c.get("skew_min")
            if hi is not None and lo:
                ratios.append(float(hi) / float(lo))
    if ratios:
        out["skew_median_ratio"] = _median(ratios)
    return out


def _finding(kind: str, name: str, status: str, baseline, candidate,
             note: str = "") -> Dict[str, Any]:
    f = {"kind": kind, "name": name, "status": status,
         "baseline": baseline, "candidate": candidate}
    if (isinstance(baseline, (int, float)) and baseline
            and isinstance(candidate, (int, float))):
        f["ratio"] = round(candidate / baseline, 4)
    if note:
        f["note"] = note
    return f


def _diff_wall(kind: str, name: str, a: float, b: float, tol: float,
               min_wall: float, higher_better: bool = False
               ) -> Optional[Dict[str, Any]]:
    if max(a, b) < min_wall:
        return None
    if a <= 0 or b <= 0:
        return _finding(kind, name, "changed", a, b,
                        "non-positive wall; cannot threshold")
    worse = (b < a * (1 - tol)) if higher_better else (b > a * (1 + tol))
    better = (b > a * (1 + tol)) if higher_better else (b < a * (1 - tol))
    if worse:
        return _finding(kind, name, "regression", a, b,
                        f"beyond the {tol:.0%} wall tolerance")
    if better:
        return _finding(kind, name, "improvement", a, b)
    return None


def diff_records(base: Dict[str, Any], cand: Dict[str, Any], *,
                 wall_tol: float = DEFAULT_WALL_TOL,
                 min_wall_s: float = DEFAULT_MIN_WALL_S,
                 check_knobs: bool = True
                 ) -> Tuple[List[Dict[str, Any]], List[str]]:
    """Compare two records; returns ``(findings, incomparable)``.

    ``incomparable`` is non-empty when the records cannot honestly be
    diffed (different metric, different engaged knob set); findings are
    still produced for whatever IS comparable.
    """
    findings: List[Dict[str, Any]] = []
    incomparable: List[str] = []

    for side, rec in (("baseline", base), ("candidate", cand)):
        if rec.get("_legacy_multichip"):
            incomparable.append(
                f"{side} is a legacy multichip dryrun artifact "
                f"(n_devices={rec.get('n_devices')}, "
                f"ok={rec.get('ok')}): it carries no metric or ledger "
                "to diff — re-capture with tools/multichip_probe.py")
    if incomparable:
        return findings, incomparable

    for rec in (base, cand):
        if rec.get("_schema_note"):
            findings.append(_finding("schema", rec.get("schema", "?"),
                                     "note", None, None,
                                     rec["_schema_note"]))

    # -- comparability gates -------------------------------------------
    if base.get("metric") != cand.get("metric"):
        incomparable.append(
            f"metric mismatch: {base.get('metric')!r} vs "
            f"{cand.get('metric')!r}")
    if check_knobs:
        bk, ck = base.get("knobs") or {}, cand.get("knobs") or {}
        for key in sorted(set(bk) | set(ck)):
            if bk.get(key) != ck.get(key):
                incomparable.append(
                    f"engaged knob mismatch: {key}={bk.get(key)!r} vs "
                    f"{ck.get(key)!r} (records answer different "
                    "questions; pass --allow-knob-mismatch to force)")
        # routing-path mismatch (ISSUE 10): the digest identifies the
        # ENGAGED path (stream/physical/row_order x pack x scheme x
        # merge); records that trained different paths are
        # incomparable — a 25x path change is not a "regression"
        br = base.get("routing") or {}
        cr = cand.get("routing") or {}
        if (br.get("digest") and cr.get("digest")
                and br["digest"] != cr["digest"]):
            incomparable.append(
                "routing-path mismatch: "
                f"{br.get('path')}/pack{br.get('pack')}/"
                f"{br.get('scheme')}/{br.get('hist_merge')} "
                f"(digest {br['digest']}) vs "
                f"{cr.get('path')}/pack{cr.get('pack')}/"
                f"{cr.get('scheme')}/{cr.get('hist_merge')} "
                f"(digest {cr['digest']}) — the records trained "
                "different engaged paths (the cell lattice is "
                "lightgbm_tpu/analysis/routing_matrix.json); pass "
                "--allow-knob-mismatch to force")
    bb, cb = base.get("backend"), cand.get("backend")
    if bb and cb and bb != cb:
        incomparable.append(f"backend mismatch: {bb!r} vs {cb!r}")

    # -- metric of record (thresholded wall) ---------------------------
    if base.get("metric") == cand.get("metric") \
            and isinstance(base.get("value"), (int, float)) \
            and isinstance(cand.get("value"), (int, float)):
        unit = base.get("unit", "")
        f = _diff_wall("metric", f"{base['metric']} [{unit}]",
                       float(base["value"]), float(cand["value"]),
                       wall_tol, 0.0,
                       higher_better=unit in HIGHER_IS_BETTER_UNITS)
        if f:
            findings.append(f)

    # -- counters: exact -----------------------------------------------
    bc = base.get("counters") or {}
    cc = cand.get("counters") or {}
    for name in sorted(set(bc) | set(cc)):
        if bc.get(name, 0) != cc.get(name, 0):
            findings.append(_finding(
                "counter", name, "regression", bc.get(name),
                cc.get(name),
                "device counters are deterministic — any difference "
                "means different trees or a different kernel path"))

    # -- events: structural --------------------------------------------
    be = base.get("events") or {}
    ce = cand.get("events") or {}
    for name in sorted(set(be) | set(ce)):
        if be.get(name, 0) == ce.get(name, 0):
            continue
        status = ("regression" if ce.get(name, 0) > be.get(name, 0)
                  else "improvement")
        findings.append(_finding(
            "event", name, status, be.get(name, 0), ce.get(name, 0),
            "a structural fallback event changed between records"))

    # -- serving block (ISSUE 14): bulk throughput, latency tail, and
    # the retrace pin (a bucketed dispatch that compiled mid-serving
    # broke the same-bucket contract — exact, like the counters) ------
    bs, cs = base.get("serving") or {}, cand.get("serving") or {}
    if bs and cs and check_knobs and bs.get("digest") and \
            cs.get("digest") and bs["digest"] != cs["digest"]:
        # the serving digest identifies the exact compiled forest
        # content: records that served different models answer
        # different questions (rows/sec over a different tree stack
        # is not a regression)
        incomparable.append(
            "serving-model mismatch: compiled forest digest "
            f"{bs['digest']} vs {cs['digest']} — the records served "
            "different compiled models; pass --allow-knob-mismatch "
            "to force")
        bs, cs = {}, {}
    if bs and cs:
        a, b = bs.get("bulk_rows_per_sec"), cs.get("bulk_rows_per_sec")
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            f = _diff_wall("serving", "bulk_rows_per_sec", float(a),
                           float(b), wall_tol, 0.0, higher_better=True)
            if f:
                findings.append(f)
        a, b = bs.get("p99_ms"), cs.get("p99_ms")
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            f = _diff_wall("serving", "p99_latency", float(a) / 1e3,
                           float(b) / 1e3, wall_tol, 1e-4)
            if f:
                findings.append(f)
        # ISSUE 17: the flight-recorder tail and waste gate like walls
        # — p999 under the same tolerance/floor as p99, padding waste
        # as a RATIO of cost-model dispatch bytes (ratios under 1% are
        # bucket-rounding noise, the MIN_MEM_BYTES analogue)
        a, b = bs.get("p999_ms"), cs.get("p999_ms")
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            f = _diff_wall("serving", "p999_latency", float(a) / 1e3,
                           float(b) / 1e3, wall_tol, 1e-4)
            if f:
                findings.append(f)
        a = bs.get("padding_waste_ratio")
        b = cs.get("padding_waste_ratio")
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            f = _diff_wall("serving", "padding_waste_ratio", float(a),
                           float(b), wall_tol, 0.01)
            if f:
                findings.append(f)
    # the retrace contract is ABSOLUTE, not pairwise: a candidate that
    # retraced after warmup broke the same-bucket pin regardless of
    # what (or whether) a baseline served
    cs_abs = cand.get("serving") or {}
    retr = cs_abs.get("retraces_after_warmup")
    if isinstance(retr, (int, float)) and retr > 0:
        findings.append(_finding(
            "serving", "retraces_after_warmup", "regression",
            (base.get("serving") or {}).get("retraces_after_warmup", 0),
            retr,
            "the candidate's bucketed serving dispatch retraced "
            "after warmup — a novel batch shape compiled "
            "mid-serving (the ROUTING_RETRACE same-bucket "
            "contract is broken)"))

    # -- phase walls: ledger medians when both have a trajectory -------
    bm, cm = _ledger_phase_medians(base), _ledger_phase_medians(cand)
    if bm and cm:
        for name in sorted(set(bm) & set(cm)):
            f = _diff_wall("phase-median", name, bm[name], cm[name],
                           wall_tol, min_wall_s)
            if f:
                findings.append(f)
    bp = base.get("phases") or {}
    cp = cand.get("phases") or {}
    for name in sorted(set(bp) | set(cp)):
        if name in bm and name in cm:
            # the trajectory medians above already judged this phase —
            # comparing the summary TOTAL as well would re-expose the
            # gate to the single-straggler failures median-of-k exists
            # to absorb
            continue
        a, b = bp.get(name), cp.get(name)
        if a is None or b is None:
            present = bp if a is not None else cp
            wall = float((present.get(name) or {}).get("total_s", 0.0))
            if wall < min_wall_s:
                continue
            # a phase APPEARING in the candidate is new work (a slow
            # path engaged) — that is the regression; a phase that
            # disappeared is usually the improvement being shipped, so
            # it is surfaced but does not fail the gate
            findings.append(_finding(
                "phase", name,
                "regression" if b is not None else "changed",
                (a or {}).get("total_s"), (b or {}).get("total_s"),
                "phase present only in the candidate (new traced code "
                "path engaged)" if b is not None else
                "phase present only in the baseline (code path "
                "disappeared — verify this was intended)"))
            continue
        f = _diff_wall("phase", name, float(a.get("total_s", 0.0)),
                       float(b.get("total_s", 0.0)), wall_tol,
                       min_wall_s)
        if f:
            findings.append(f)

    # -- per-kernel device times (xplane-attributed `device` block) ----
    # only when BOTH records were captured: an uncaptured baseline
    # means the axis was never measured, not that every kernel is new
    bdk = _device_kernel_seconds(base)
    cdk = _device_kernel_seconds(cand)
    if not bdk or not cdk:
        bdk = cdk = {}
    for name in sorted(set(bdk) | set(cdk)):
        a, b = bdk.get(name), cdk.get(name)
        if a is None or b is None:
            wall = b if a is None else a
            if wall < min_wall_s:
                continue
            findings.append(_finding(
                "device-kernel", name,
                "regression" if b is not None else "changed", a, b,
                "kernel class present only in the candidate (a kernel "
                "newly on the device hot path)" if b is not None else
                "kernel class present only in the baseline (left the "
                "device hot path — verify this was intended)"))
            continue
        f = _diff_wall("device-kernel", name, a, b, wall_tol,
                       min_wall_s)
        if f:
            findings.append(f)

    # -- HBM residency peaks: thresholded like walls (ISSUE 9) ---------
    # an unmeasured BASELINE means the axis was never captured (not
    # that every byte is new) — but a TRACED candidate whose residency
    # series vanished is the sampling silently breaking, the same loss
    # class the mesh gate below refuses to read as clean
    bmp, cmp_ = _mem_peaks(base), _mem_peaks(cand)
    if bmp and cmp_:
        for name in sorted(set(bmp) & set(cmp_)):
            f = _diff_wall("memory", name, bmp[name], cmp_[name],
                           wall_tol, MIN_MEM_BYTES)
            if f:
                findings.append(f)
    elif bmp and (cand.get("ledger") or {}).get("iterations"):
        findings.append(_finding(
            "memory", "hbm_peaks", "regression",
            max(bmp.values()), None,
            "measured HBM residency series present in the baseline "
            "but missing from the traced candidate — the residency "
            "sampling (gbdt phase census / ledger hbm_* keys) "
            "silently disengaged"))

    # -- mesh flight recorder: shard count, collective bytes, skew -----
    bmesh, cmesh = _mesh_view(base), _mesh_view(cand)
    if bmesh and cmesh:
        if bmesh["shards"] != cmesh["shards"]:
            incomparable.append(
                f"shard-count mismatch: {bmesh['shards']} vs "
                f"{cmesh['shards']} (mesh records over different shard "
                "counts answer different questions; re-capture on the "
                "same mesh shape)")
        else:
            # analytical collective bytes are deterministic functions
            # of layout shape x shard count x dispatch count: exact,
            # like the device counters
            for name, key in (("collective_bytes", "bytes"),
                              ("collective_dispatches", "dispatches")):
                if bmesh.get(key) != cmesh.get(key):
                    findings.append(_finding(
                        "mesh", name, "regression", bmesh.get(key),
                        cmesh.get(key),
                        "analytical ICI accounting is deterministic — "
                        "any difference means a different merge path "
                        "or a cost-model drift"))
            bs = bmesh.get("skew_median_ratio")
            cs = cmesh.get("skew_median_ratio")
            if bs is not None and cs is not None:
                f = _diff_wall("mesh", "shard_skew_ratio(median)",
                               bs, cs, wall_tol, 0.0)
                if f:
                    findings.append(f)
    elif bmesh or cmesh:
        # BOTH directions fail the gate: mesh rows appearing means a
        # mesh learner engaged where the baseline ran serial; mesh
        # rows DISAPPEARING means the mesh path (or its telemetry)
        # silently disengaged — exactly the loss the flight recorder
        # exists to catch, so it must not read as a clean diff
        present = "candidate" if cmesh else "baseline"
        findings.append(_finding(
            "mesh", "collectives", "regression",
            bmesh.get("shards"), cmesh.get("shards"),
            f"mesh collective rows present only in the {present} — "
            + ("a mesh learner engaged where the baseline ran serial"
               if cmesh else
               "the mesh learner or its collective recording silently "
               "disengaged in the candidate")))

    # -- per-iteration trajectory (median wall) ------------------------
    bw, cw = _ledger_iter_walls(base), _ledger_iter_walls(cand)
    if bw and cw:
        f = _diff_wall("trajectory", "iter_wall_s(median)", _median(bw),
                       _median(cw), wall_tol, min_wall_s)
        if f:
            findings.append(f)

    return findings, incomparable


def regressions(findings: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [f for f in findings if f["status"] == "regression"]


def format_findings(findings: List[Dict[str, Any]],
                    incomparable: List[str]) -> str:
    lines: List[str] = []
    for msg in incomparable:
        lines.append(f"  INCOMPARABLE  {msg}")
    for f in findings:
        val = ""
        if isinstance(f.get("baseline"), (int, float)) \
                and isinstance(f.get("candidate"), (int, float)):
            val = (f"  {f['baseline']:g} -> {f['candidate']:g}"
                   + (f"  (x{f['ratio']:g})" if "ratio" in f else ""))
        note = f"  [{f['note']}]" if f.get("note") else ""
        lines.append(f"  {f['status'].upper():<12}{f['kind']}/"
                     f"{f['name']}{val}{note}")
    if not lines:
        lines.append("  records match within tolerance")
    return "\n".join(lines)


def diff_paths(a_path: str, b_path: str, *,
               wall_tol: float = DEFAULT_WALL_TOL,
               min_wall_s: float = DEFAULT_MIN_WALL_S,
               allow_knob_mismatch: bool = False) -> int:
    """CLI body shared by ``obs diff`` and ``tools/perf_gate.py``:
    prints the comparison, returns the exit code."""
    from .findings import cli_error
    try:
        base = load_record(a_path)
        cand = load_record(b_path)
    except ValueError as e:
        return cli_error("obs diff", e)
    findings, incomparable = diff_records(
        base, cand, wall_tol=wall_tol, min_wall_s=min_wall_s,
        check_knobs=not allow_knob_mismatch)
    print(f"obs diff: {a_path} (baseline) vs {b_path} (candidate), "
          f"wall tolerance {wall_tol:.0%}")
    print(format_findings(findings, incomparable))
    regs = regressions(findings)
    if incomparable:
        print(f"obs diff: INCOMPARABLE ({len(incomparable)} blocking "
              "mismatches)")
        return 2
    if regs:
        print(f"obs diff: {len(regs)} regression(s) flagged")
        return 1
    print("obs diff: clean")
    return 0
