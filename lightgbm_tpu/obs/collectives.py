"""Measured-vs-predicted ICI validation: ``obs collectives`` (ISSUE 8
tentpole 2).

The mesh learners' run-ledger rows price every grow dispatch's
collective traffic ANALYTICALLY (``costmodel.collective_bytes`` — ring
all-reduce / reduce-scatter / pmax factors over the histogram payload).
Until this module, nothing ever checked those numbers against a real
capture: the scale-out path would be flown on an unvalidated model.

``collectives_block`` joins the two sides:

* **measured** — collective events per device plane from an xplane
  capture (``xattr.plane_collective_events``: op name, count, device
  ms, and the transfer bytes their stats report — ``bytes_accessed`` /
  ``transfer_size`` class stat names);
* **predicted** — the bench/v3 record's ledger collective rows, one
  per learner grow dispatch, each carrying the analytical per-shard
  ``bytes_moved``.

The comparison is EXACT-OR-FLAGGED, the same discipline as the cost
model's byte contracts (``tests/test_obs_tools.py``): per shard plane,
measured bytes must equal the summed per-dispatch prediction to the
byte, or the plane is flagged ``MISMATCH`` with the signed delta —
a tolerance here would let the cost model drift exactly where ROADMAP
item 3's v5e-16 run needs it to be trustworthy.

CLI: ``python -m lightgbm_tpu.obs collectives CAPTURE [--bench
REC.json] [--json OUT]``.  Exit codes: 0 every plane joins exactly
(or measured-only render when no bench record is given); 1 decoded
but not validatable (no device plane, no collective events against a
predicting ledger, a capture without byte stats) or any plane
mismatched; 2 unreadable input — never a traceback.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional

from .xattr import (XSpace, XplaneParseError, _is_device_plane,
                    load_capture, plane_collective_events)

COLLECTIVES_SCHEMA = "lightgbm_tpu/collectives/v1"


def _ledger_rows(rec: Optional[Dict[str, Any]]) -> List[Dict[str, Any]]:
    if not rec:
        return []
    return list((rec.get("ledger") or {}).get("collectives") or [])


def collectives_block(source: str, spaces: Iterable[XSpace],
                      rec: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """The ``obs collectives`` result (schema
    ``lightgbm_tpu/collectives/v1``): per-plane measured collective
    traffic, the ledger's per-dispatch analytical prediction, and the
    exact per-shard join."""
    planes: List[Dict[str, Any]] = []
    for space in spaces:
        for plane in space.planes:
            if not _is_device_plane(plane.name):
                continue
            evs = plane_collective_events(plane)
            known = [e["bytes"] for e in evs if e["bytes"] is not None]
            planes.append({
                "plane": plane.name,
                "events": evs,
                "total_device_ms": round(sum(e["device_ms"]
                                             for e in evs), 6),
                "measured_bytes": (sum(known) if known else None),
                "event_count": sum(e["count"] for e in evs),
                # stats COVERAGE: how many collective ops actually
                # carried a bytes stat.  Partial coverage keeps its
                # exact/mismatch verdict (an unpriced noise op without
                # a stat is the normal healthy shape) but is surfaced
                # so a MISMATCH on a partially-stat'd capture reads as
                # "check the capture" before "fix the cost model"
                "ops_with_bytes": len(known),
                "ops_total": len(evs),
            })
    block: Dict[str, Any] = {
        "schema": COLLECTIVES_SCHEMA,
        "source": source,
        "planes": planes,
    }
    rows = _ledger_rows(rec)
    if rows:
        pred_total = sum(int(r.get("bytes_moved", 0)) for r in rows)
        shards = max((int(r.get("shards", 0)) for r in rows), default=0)
        block["predicted"] = {
            "dispatches": len(rows),
            "bytes_per_shard": pred_total,
            "shards": shards,
            "rows": [{"name": r.get("name", "?"),
                      "bytes_moved": int(r.get("bytes_moved", 0)),
                      "merges_est": r.get("merges_est")}
                     for r in rows],
        }
        join: List[Dict[str, Any]] = []
        for p in planes:
            meas = p["measured_bytes"]
            if meas is None:
                status = ("no-collective-events" if p["event_count"] == 0
                          else "no-bytes-stat")
                join.append({"plane": p["plane"], "measured": None,
                             "predicted": pred_total,
                             "status": status})
                continue
            delta = int(meas) - pred_total
            join.append({"plane": p["plane"], "measured": int(meas),
                         "predicted": pred_total, "delta": delta,
                         "status": "exact" if delta == 0
                         else "mismatch"})
        block["join"] = join
        if shards and planes and len(planes) != shards:
            block["note"] = (
                f"capture holds {len(planes)} device plane(s) but the "
                f"ledger recorded {shards} shards — partial capture? "
                "per-plane joins above still hold per shard")
    return block


def _fmt_bytes(b: Optional[int]) -> str:
    return "-" if b is None else f"{b:,}"


def render_collectives(block: Dict[str, Any]) -> List[str]:
    """Deterministic table lines (pinned byte-for-byte by the CI
    mesh-obs leg against the checked-in fixture expectation)."""
    lines: List[str] = []
    planes = block.get("planes", [])
    for p in planes:
        cov = ""
        if p.get("ops_total") and p["ops_with_bytes"] < p["ops_total"]:
            cov = (f" (bytes stats on {p['ops_with_bytes']}/"
                   f"{p['ops_total']} op(s))")
        lines.append(f"plane {p['plane']}: {p['event_count']} "
                     f"collective event(s), "
                     f"{p['total_device_ms']:.3f} ms device time, "
                     f"measured bytes "
                     f"{_fmt_bytes(p['measured_bytes'])}{cov}")
        for e in p["events"]:
            lines.append(f"  {e['name']:<28} x{e['count']:<3} "
                         f"{e['device_ms']:>9.3f} ms  "
                         f"{_fmt_bytes(e['bytes']):>14} B")
    pred = block.get("predicted")
    if pred:
        lines.append(f"predicted (run ledger): {pred['dispatches']} "
                     f"learner dispatch(es) over {pred['shards']} "
                     f"shard(s), {_fmt_bytes(pred['bytes_per_shard'])} "
                     "B per shard")
        for i, r in enumerate(pred["rows"]):
            merges = (f" (merges_est {r['merges_est']})"
                      if r.get("merges_est") is not None else "")
            lines.append(f"  dispatch {i}: {r['name']}  "
                         f"{_fmt_bytes(r['bytes_moved'])} B{merges}")
    for j in block.get("join", []):
        if j["status"] == "exact":
            lines.append(f"join {j['plane']}: measured "
                         f"{_fmt_bytes(j['measured'])} B == predicted "
                         f"{_fmt_bytes(j['predicted'])} B  EXACT")
        elif j["status"] == "mismatch":
            lines.append(f"join {j['plane']}: measured "
                         f"{_fmt_bytes(j['measured'])} B vs predicted "
                         f"{_fmt_bytes(j['predicted'])} B  MISMATCH "
                         f"({j['delta']:+,} B)")
        else:
            lines.append(f"join {j['plane']}: {j['status']} — cannot "
                         "validate measured ICI bytes on this plane")
    if block.get("note"):
        lines.append(f"note: {block['note']}")
    return lines


def run_collectives(xplane: str, *, bench: str = "",
                    json_out: str = "", prefer_tf: bool = True) -> int:
    """``python -m lightgbm_tpu.obs collectives`` body.  Exit codes:
    0 every shard plane joins the analytical contract exactly (or
    measured-only summary when no --bench record is given); 1 decoded
    but not validatable or mismatched; 2 unreadable input."""
    from .findings import cli_error
    try:
        loaded = load_capture(xplane, prefer_tf=prefer_tf)
    except XplaneParseError as e:
        return cli_error("obs collectives", e)
    rec = None
    if bench:
        from .regress import load_record
        try:
            rec = load_record(bench)
        except ValueError as e:
            return cli_error("obs collectives", e)
        if rec.get("_legacy_multichip"):
            print(f"obs collectives: {bench}: legacy multichip dryrun "
                  "artifact carries no run ledger — re-capture with "
                  "tools/multichip_probe.py")
            return 2
    print(f"obs collectives: {xplane}: {len(loaded)} xplane file(s)")
    spaces = [s for _, s in loaded]
    block = collectives_block(xplane, spaces, rec=rec)
    if not block["planes"]:
        print("obs collectives: no TPU/GPU device plane in the capture "
              "— host-only trace? measured ICI validation needs a "
              "device capture")
        return 1
    for line in render_collectives(block):
        print(line)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(block, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"collectives block -> {json_out}")
    join = block.get("join", [])
    if rec is not None:
        rows = _ledger_rows(rec)
        if not rows:
            print("obs collectives: bench record has no ledger "
                  "collective rows (serial run, or captured without "
                  "LGBM_TPU_TRACE) — nothing to validate against")
            return 1
        # gate rules: a MISMATCH or a plane whose collective events
        # carry no bytes stat fails; a plane with NO collective events
        # at all (an idle device beyond the mesh in the capture dir)
        # is reported but only fails when nothing joined exactly —
        # the block's own "partial capture" note promises per-plane
        # joins still hold per shard
        bad = [j for j in join
               if j["status"] in ("mismatch", "no-bytes-stat")]
        exact = [j for j in join if j["status"] == "exact"]
        idle = [j for j in join
                if j["status"] == "no-collective-events"]
        if bad:
            print(f"obs collectives: {len(bad)} plane(s) failed the "
                  "exact measured-vs-predicted join")
            return 1
        if not exact:
            print("obs collectives: no plane carried collective "
                  "events to validate")
            return 1
        if idle:
            print(f"obs collectives: {len(idle)} idle plane(s) with "
                  "no collective events (outside the mesh?) — not "
                  "counted against the join")
        print(f"obs collectives: all {len(exact)} shard plane(s) "
              "match the analytical contract exactly")
        return 0
    # measured-only mode: useful, but says so
    total = sum(p["event_count"] for p in block["planes"])
    if not total:
        print("obs collectives: capture holds no collective events "
              "(single-chip run?)")
        return 1
    print("obs collectives: measured-only summary (pass --bench "
          "REC.json to validate against the analytical contract)")
    return 0
