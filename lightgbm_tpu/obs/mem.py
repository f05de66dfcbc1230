"""HBM flight recorder: per-buffer footprint reporting, the
measured-vs-predicted residency join, and the page-schedule planner
CLI (ISSUE 9 tentpole).

``python -m lightgbm_tpu.obs mem REC.json`` reads a traced bench/v3
record and renders:

* the exact per-buffer footprint table the cost model predicts for the
  record's shape (``costmodel.grow_footprint`` — the same closed-form
  contracts tests/test_mem.py proves equal to the real grow jaxprs'
  buffer sizes),
* the per-phase live-sets and the predicted peak vs the per-generation
  HBM budget (``LGBM_TPU_HBM_GEN`` / ``LGBM_TPU_HBM_LIMIT_GB``),
* the measured memory timeline — per-phase ``hbm_phase_bytes``
  watermarks and the per-iteration live / allocator peaks the run
  ledger sampled,
* the JOIN: a measured allocator peak exceeding the predicted peak
  beyond tolerance is a FINDING (exit 1) — it means a silent copy or
  an unexpected retention the footprint model does not know about,
  exactly the class of drift the paged-comb refactor must not design
  against.

``obs mem --plan --rows N --features F`` (or ``--plan`` on a record)
runs ``costmodel.page_schedule``: the page geometry, per-tree
host<->HBM DMA bytes and predicted overhead for a larger-than-HBM
shape — the ROADMAP item 5 design artifact.

Exit codes: 0 clean, 1 finding (measured exceeds predicted, or a
planned geometry cannot fit), 2 unreadable / untraced input — never a
traceback (the S3 CLI contract).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from . import costmodel

MEM_SCHEMA = "lightgbm_tpu/mem/v1"
# measured allocator peak may exceed the predicted live-set peak by
# this fraction before the join flags it (allocator rounding,
# fragmentation, runtime-internal staging)
DEFAULT_MEM_TOL = 0.10


class MemRecordError(ValueError):
    """A bench record lacks what the memory model needs."""


def _mb(b) -> str:
    return f"{float(b) / 1e6:.2f} MB"


def footprint_from_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    """``costmodel.grow_footprint`` over a bench/v3 record's shape and
    engaged-knob blocks."""
    shape = rec.get("shape")
    if not shape:
        raise MemRecordError(
            "memory model needs a bench/v3 record with a 'shape' block "
            "(re-capture with bench.py --json; got schema "
            f"{rec.get('schema', '(unversioned)')!r})")
    knobs = rec.get("knobs") or {}
    mc = rec.get("multichip") or {}
    return costmodel.grow_footprint(
        rows=int(shape.get("rows", rec.get("rows", 0))),
        f_pad=int(shape["f_pad"]),
        padded_bins=int(shape["padded_bins"]),
        num_leaves=int(rec.get("leaves", 31)),
        stream=bool(shape.get("stream", False)),
        fused=bool(knobs.get("fused", True)),
        n_shards=int(mc.get("n_shards", 1)),
        # EFB: the bin matrix stays bundled; the record's f_pad is the
        # width the comb was allocated at (bundle columns, or the
        # unbundled width under the unbundling ingest); older records
        # lack the fields and fall back to the no-bundling identity
        bins_cols=int(shape.get("bins_cols", 0)),
        bins_itemsize=int(shape.get("bins_itemsize", 1)))


def measured_from_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    """Measured residency series from the record's embedded ledger:
    per-iteration live / allocator peaks plus the per-phase watermark
    timeline ({} when the record carries no trajectory)."""
    iters = (rec.get("ledger") or {}).get("iterations") or []
    live = [int(r["hbm_live_bytes"]) for r in iters
            if r.get("hbm_live_bytes") is not None]
    alloc = [int(r["hbm_peak_bytes"]) for r in iters
             if r.get("hbm_peak_bytes") is not None]
    phases: Dict[str, List[int]] = {}
    for r in iters:
        for name, b in (r.get("hbm_phase_bytes") or {}).items():
            phases.setdefault(name, []).append(int(b))
    out: Dict[str, Any] = {}
    if live:
        out["live_peak_bytes"] = max(live)
        out["live_series_len"] = len(live)
    if alloc:
        out["alloc_peak_bytes"] = max(alloc)
    if phases:
        out["phase_peak_bytes"] = {name: max(v)
                                   for name, v in sorted(phases.items())}
    return out


def memory_block(rec: Dict[str, Any],
                 tol: float = DEFAULT_MEM_TOL) -> Dict[str, Any]:
    """The schema-additive ``memory`` block bench/v3 records embed
    (bench.py writes it for traced runs): compact predicted footprint +
    measured peaks + the join verdict."""
    fp = footprint_from_record(rec)
    measured = measured_from_record(rec)
    block: Dict[str, Any] = {
        "schema": MEM_SCHEMA,
        "predicted": {
            "peak_bytes": fp["peak_bytes"],
            "peak_phase": fp["peak_phase"],
            "persistent_bytes": fp["persistent_bytes"],
            "phase_live": dict(fp["phase_live"]),
            "buffers": {name: b["bytes"]
                        for name, b in fp["buffers"].items()},
            "geometry": dict(fp["geometry"]),
        },
    }
    if measured:
        block["measured"] = measured
    finding = join_finding(fp, measured, tol=tol)
    if finding:
        block["finding"] = finding
    return block


def join_finding(fp: Dict[str, Any], measured: Dict[str, Any],
                 tol: float = DEFAULT_MEM_TOL) -> Optional[str]:
    """The measured-vs-predicted verdict: the allocator peak (preferred
    — it sees transient scratch the live census cannot) must not exceed
    the predicted peak beyond ``tol``.  Returns the finding message, or
    None when clean / unmeasured."""
    meas = measured.get("alloc_peak_bytes",
                        measured.get("live_peak_bytes"))
    if meas is None:
        return None
    pred = fp["peak_bytes"]
    if meas > pred * (1.0 + tol):
        src = ("allocator" if "alloc_peak_bytes" in measured
               else "live-array")
        return (f"measured {src} peak {_mb(meas)} exceeds the "
                f"predicted peak {_mb(pred)} by more than {tol:.0%} — "
                "a silent copy or unexpected retention the footprint "
                "model does not price; find it before designing the "
                "page schedule against this model")
    return None


# ---------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------
def print_mem_report(rec: Dict[str, Any], path: str,
                     tol: float = DEFAULT_MEM_TOL) -> int:
    fp = footprint_from_record(rec)
    measured = measured_from_record(rec)
    geo = fp["geometry"]
    print(f"{path}: memory [{MEM_SCHEMA}]")
    print(f"  geometry: rows={geo['rows']} (n_local={geo['n_local']}, "
          f"n_alloc={geo['n_alloc']}), f_pad={geo['f_pad']}, "
          f"bins={geo['padded_bins']}, "
          f"C={geo['C']}, stream={'on' if geo['stream'] else 'off'}, "
          f"fused={'on' if geo['fused'] else 'off'}, "
          f"shards={geo['n_shards']}, leaves={geo['num_leaves']}")
    print("  predicted buffers (per shard):")
    width = max(len(n) for n in fp["buffers"])
    for name, b in fp["buffers"].items():
        shp = "x".join(str(d) for d in b["shape"])
        cnt = f" x{b['count']}" if b.get("count", 1) > 1 else ""
        tags = [b["scope"]] + (["donated"] if b.get("donated") else [])
        print(f"    {name.ljust(width)}  {shp:>16}{cnt:<4} "
              f"{_mb(b['bytes']):>12}  [{', '.join(tags)}]")
    live_txt = " | ".join(f"{name} {_mb(v)}"
                          for name, v in fp["phase_live"].items())
    print(f"  phase live-sets: {live_txt}")
    limit = costmodel.hbm_limit_bytes()
    _, gen = costmodel.hbm_generation_bytes()
    used = fp["peak_bytes"] / limit
    print(f"  predicted peak: {_mb(fp['peak_bytes'])} "
          f"({fp['peak_phase']}); HBM budget {limit / 2**30:.2f} GiB "
          f"({gen}) — {used:.1%} used")
    rc = 0
    if fp["peak_bytes"] > limit:
        print("  FINDING: predicted peak exceeds the HBM budget — run "
              "obs mem --plan for a page schedule")
        rc = 1
    if not measured:
        print("  measured: (no ledger residency series — re-capture "
              "with LGBM_TPU_TRACE set)")
        return rc
    m_live = measured.get("live_peak_bytes")
    m_alloc = measured.get("alloc_peak_bytes")
    parts = []
    if m_live is not None:
        parts.append(f"live peak {_mb(m_live)} over "
                     f"{measured['live_series_len']} iteration(s)")
    if m_alloc is not None:
        parts.append(f"allocator peak {_mb(m_alloc)}")
    print(f"  measured: {', '.join(parts)}")
    for name, v in (measured.get("phase_peak_bytes") or {}).items():
        pred_phase = fp["phase_live"].get(name)
        vs = (f" (predicted {_mb(pred_phase)})"
              if pred_phase is not None else "")
        print(f"    phase {name}: {_mb(v)}{vs}")
    finding = join_finding(fp, measured, tol=tol)
    if finding:
        print(f"  FINDING: {finding}")
        return 1
    meas = m_alloc if m_alloc is not None else m_live
    print(f"  join: measured peak {_mb(meas)} <= predicted "
          f"{_mb(fp['peak_bytes'])} (+{tol:.0%} tolerance) — OK")
    return rc


def print_plan(*, rows: int, f_pad: int, padded_bins: int,
               num_leaves: int, stream: bool,
               n_shards: int, rows_per_page: Optional[int] = None
               ) -> int:
    plan = costmodel.page_schedule(
        rows=rows, f_pad=f_pad, padded_bins=padded_bins,
        num_leaves=num_leaves, stream=stream,
        n_shards=n_shards, rows_per_page=rows_per_page)
    print(f"page schedule: rows={plan['rows']} "
          f"(n_local={plan['n_local']}), "
          f"HBM budget {plan['limit_bytes'] / 2**30:.2f} GiB")
    print(f"  unpaged peak: {_mb(plan['unpaged_peak_bytes'])}")
    if not plan.get("paged"):
        print("  fits unpaged — no paging needed")
        return 0
    if plan.get("error"):
        print(f"  FINDING: {plan['error']}")
        return 1
    print(f"  rows/page: {plan['rows_per_page']} "
          f"({plan['n_pages']} pages, {_mb(plan['page_bytes'])} per "
          f"page buffer)")
    print(f"  resident: {_mb(plan['resident_bytes'])} (3 page buffers "
          f"+ fixed arenas) — "
          f"{'fits' if plan['fits'] else 'DOES NOT FIT'}")
    print(f"  per-tree host<->HBM DMA: "
          f"{_mb(plan['dma_bytes_per_tree'])} over "
          f"{plan['sweeps_per_tree']} sweeps "
          f"-> {plan['overhead_s_per_tree'] * 1e3:.1f} ms/tree at "
          f"{plan['host_bw_gbps']:g} GB/s host BW")
    return 0 if plan["fits"] else 1


# ---------------------------------------------------------------------
# checked-in fixture (tests/data/synthetic_mem_record.json + pinned
# obs mem table) — regenerate with ``python -m lightgbm_tpu.obs.mem``
# after an intended model/format change, like the xattr fixtures
# ---------------------------------------------------------------------
def synthetic_mem_record() -> Dict[str, Any]:
    """A deterministic traced-record stand-in: the 50k/63-leaf smoke
    shape on the stream path, with a hand-written residency
    trajectory sitting safely below the model's predicted peak."""
    iters = []
    for i in range(3):
        iters.append({
            "iteration": i,
            "wall_s": 0.05,
            "hbm_live_bytes": 40_000_000 + 1_000_000 * i,
            "hbm_peak_bytes": 46_000_000 + 500_000 * i,
            "hbm_phase_bytes": {
                "BeforeTrain": 38_000_000 + 1_000_000 * i,
                "Tree::grow": 42_000_000 + 1_000_000 * i,
                "UpdateScore": 40_500_000 + 1_000_000 * i,
            },
        })
    rec = {
        "schema": "lightgbm_tpu/bench/v3",
        "metric": "boosting_iters_per_sec_higgs50k_63leaves",
        "value": 10.0,
        "unit": "iters/sec",
        "backend": "tpu",
        "leaves": 63,
        "knobs": {"partition": "permute", "fused": True},
        "shape": {"rows": 50_000, "features": 28, "f_pad": 28,
                  "padded_bins": 256, "trees": 3, "stream": True},
        "traced": True,
        "ledger": {"schema": "lightgbm_tpu/ledger/v1",
                   "iterations": iters},
    }
    rec["memory"] = memory_block(rec)
    return rec


def _regen_fixture() -> None:  # pragma: no cover - dev tool
    import contextlib
    import io
    import json
    import os
    data_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "tests", "data")
    rec = synthetic_mem_record()
    rec_path = os.path.join(data_dir, "synthetic_mem_record.json")
    with open(rec_path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
        f.write("\n")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = print_mem_report(rec, "tests/data/synthetic_mem_record"
                                   ".json")
    assert rc == 0, f"fixture report must be clean (rc={rc})"
    out_path = os.path.join(data_dir, "synthetic_mem_expected.txt")
    with open(out_path, "w") as f:
        f.write(buf.getvalue())
    print(f"wrote {rec_path}\nwrote {out_path}")


def run_mem(paths: List[str], *, plan: bool = False,
            rows: int = 0, features: int = 0,
            bins: Optional[int] = None, leaves: Optional[int] = None,
            shards: Optional[int] = None,
            stream: Optional[bool] = None, rows_per_page: int = 0,
            tol: float = DEFAULT_MEM_TOL) -> int:
    """CLI body for ``python -m lightgbm_tpu.obs mem``.  ``None``
    geometry params mean "not passed": the standalone ``--plan`` path
    fills planner defaults, the record path reads the record's shape /
    knob blocks — an EXPLICIT flag always wins over the record."""
    from .findings import cli_error
    from .regress import load_record
    if plan and not paths:
        if not rows or not features:
            return cli_error("obs mem", "--plan without a record "
                                        "needs --rows and --features")
        try:
            return print_plan(
                rows=rows, f_pad=features,
                padded_bins=256 if bins is None else bins,
                num_leaves=255 if leaves is None else leaves,
                stream=True if stream is None else stream,
                n_shards=1 if shards is None else shards,
                rows_per_page=rows_per_page or None)
        except ValueError as e:
            return cli_error("obs mem", e)
    rc = 0
    for path in paths:
        try:
            rec = load_record(path)
        except ValueError as e:
            rc = max(rc, cli_error("obs mem", e))
            continue
        if rec.get("_legacy_multichip"):
            print(f"{path}: legacy multichip dryrun artifact "
                  "(pre-bench/v3) — carries no shape or ledger to "
                  "price; re-capture with tools/multichip_probe.py")
            rc = max(rc, 2)
            continue
        try:
            rc = max(rc, print_mem_report(rec, path, tol=tol))
        except (MemRecordError, costmodel.RecordModelError,
                ValueError) as e:
            rc = max(rc, cli_error("obs mem", f"{path}: {e}"))
            continue
        if plan:
            shape = rec.get("shape") or {}
            knobs = rec.get("knobs") or {}
            mc = rec.get("multichip") or {}
            try:
                rc = max(rc, print_plan(
                    rows=rows or int(shape.get("rows", 0)),
                    f_pad=features or int(shape.get("f_pad", 0)),
                    padded_bins=(int(shape.get("padded_bins", 256))
                                 if bins is None else bins),
                    num_leaves=(int(rec.get("leaves", 255))
                                if leaves is None else leaves),
                    stream=(bool(shape.get("stream", True))
                            if stream is None else stream),
                    n_shards=(int(mc.get("n_shards", 1))
                              if shards is None else shards),
                    rows_per_page=rows_per_page or None))
            except ValueError as e:
                rc = max(rc, cli_error("obs mem", f"{path}: {e}"))
    return rc


if __name__ == "__main__":   # pragma: no cover - fixture regeneration
    _regen_fixture()
