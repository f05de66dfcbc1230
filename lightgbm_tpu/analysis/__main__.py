"""CLI: ``python -m lightgbm_tpu.analysis [--strict] [--json] ...``.

Exit codes: 0 = clean (no unallowlisted errors; warnings tolerated
unless --strict), 1 = findings, 2 = usage / internal error.  CPU-only
by design: tracing never executes device code, so CI runs this under
``JAX_PLATFORMS=cpu`` (ci_tier1.sh leg 6).
"""
from __future__ import annotations

import argparse
import json
import sys

from .allowlist import AllowlistError
from .findings import SEV_ERROR
from .run import PASS_NAMES, run_analysis


def _parse_mesh(s: str):
    try:
        f_log, n_shards = (int(x) for x in s.split(","))
        return f_log, n_shards
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--mesh wants F_LOG,N_SHARDS (got {s!r})")


def _parse_hbm_geometry(s: str):
    try:
        parts = tuple(int(x) for x in s.split(","))
        if len(parts) not in (2, 3, 4):
            raise ValueError
        return parts
    except ValueError:
        raise argparse.ArgumentTypeError(
            "--hbm-geometry wants ROWS,F_PAD[,PADDED_BINS"
            f"[,ROWS_PER_PAGE]] (got {s!r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="Static kernel-contract analyzer (trace-only; "
                    "runs on CPU).")
    ap.add_argument("--strict", action="store_true",
                    help="warnings also fail the run")
    ap.add_argument("--json", action="store_true",
                    help="emit the lightgbm_tpu/analysis/v1 report "
                         "to stdout")
    ap.add_argument("--passes", default=None,
                    help="comma-separated subset of: "
                         + ",".join(PASS_NAMES))
    ap.add_argument("--fixture", action="append", default=[],
                    metavar="NAME",
                    help="inject a seeded-violation fixture "
                         "(analysis/fixtures/) into the run; the run "
                         "then MUST report findings (CI red-team leg)")
    ap.add_argument("--mesh", action="append", default=[],
                    type=_parse_mesh, metavar="F_LOG,N_SHARDS",
                    help="check a data-parallel mesh shape against "
                         "the hist_scatter reduce-scatter "
                         "precondition")
    ap.add_argument("--hbm-geometry", action="append", default=[],
                    type=_parse_hbm_geometry,
                    metavar="ROWS,F_PAD[,BINS[,ROWS_PER_PAGE]]",
                    help="price a training shape against the HBM "
                         "budget with the exact footprint model; a "
                         "page size switches to the paged resident-"
                         "set check (obs mem --plan emits one)")
    ap.add_argument("--routing-matrix", default=None, metavar="PATH",
                    help="golden routing matrix the routing pass "
                         "audits (default: lightgbm_tpu/analysis/"
                         "routing_matrix.json; regenerate with "
                         "python -m lightgbm_tpu.ops.routing)")
    ap.add_argument("--allowlist", default=None, metavar="PATH",
                    help="allowlist file (default: "
                         "lightgbm_tpu/analysis/allowlist.json)")
    ap.add_argument("--list", action="store_true", dest="list_entries",
                    help="list registered entrypoints and exit")
    args = ap.parse_args(argv)

    if args.list_entries:
        from . import registry
        registry.collect()
        for name, e in sorted(registry.KERNELS.items()):
            print(f"{name:32s} kind={e.kind:<10s} [{e.module}]")
        for name in sorted(registry.PURITY_PINS):
            print(f"{name:32s} kind=purity-pin")
        return 0

    passes = (args.passes.split(",") if args.passes else None)
    try:
        report = run_analysis(
            passes=passes, fixtures=args.fixture, mesh=args.mesh,
            allowlist_path=args.allowlist, strict=args.strict,
            hbm_geometry=args.hbm_geometry,
            routing_matrix_path=args.routing_matrix)
    except AllowlistError as e:
        print(f"analysis: allowlist error: {e}", file=sys.stderr)
        return 2
    except ValueError as e:
        print(f"analysis: {e}", file=sys.stderr)
        return 2

    doc = report.to_json()
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        _render(report, doc)

    if args.fixture:
        # red-team semantics: a seeded-violation run FAILS (exit 1)
        # when the violation is detected — warning or error — and
        # exits 0 when the pass went blind, so the CI inversion gate
        # ("--fixture ... must exit nonzero") catches blindness
        if any(f.fixture for f in report.findings):
            return 1
        print("analysis: FIXTURE NOT DETECTED — injected "
              f"{args.fixture} produced no finding; exiting 0 so the "
              f"CI inversion gate fails", file=sys.stderr)
        return 0
    return 1 if report.failing() else 0


def _render(report, doc) -> None:
    s = doc["summary"]
    print(f"static analysis [{doc['schema']}]: "
          f"{len(report.passes)} passes over "
          f"{len(report.entries)} entrypoints — "
          f"{s['errors']} error(s), {s['warnings']} warning(s), "
          f"{s['allowlisted']} allowlisted")
    for f in sorted(report.findings,
                    key=lambda f: (f.severity != SEV_ERROR,
                                   f.pass_name, f.where)):
        tag = ("ALLOWED" if f.allowlisted
               else f.severity.upper())
        fx = " [fixture]" if f.fixture else ""
        print(f"  {tag:7s} {f.pass_name} {f.code}{fx}\n"
              f"          at {f.where}\n"
              f"          {f.message}")
        if f.allowlisted:
            print(f"          justification: {f.justification}")


if __name__ == "__main__":
    sys.exit(main())
