"""hbm-budget pass: per-entrypoint HBM residency + donation audit +
geometry checks, at trace/lower time (ISSUE 9).

Three checks, all off-chip:

* **residency** — every registered entrypoint's argument + output
  buffers (donation-aliased outputs counted once) against the
  per-generation HBM budget (``costmodel.hbm_limit_bytes`` —
  ``LGBM_TPU_HBM_GEN`` / ``LGBM_TPU_HBM_LIMIT_GB``, mirroring the
  vmem-budget knobs).  A call whose live set cannot fit fails as an
  OOM on the next chip run; here it fails at analysis time.
* **donation audit** — entries DECLARE their donated argnums
  (``register_kernel(donate=...)``); the pass checks the claim against
  the LOWERED program's ``tf.aliasing_output`` attributes, where jax
  records which donations it could actually honor.  A declared
  donation that was silently dropped (no shape/dtype-matching output)
  double-allocates the buffer every call — at comb scale that is
  gigabytes of phantom residency.  This subsumes the legacy
  ``tools/check_hbm_alias.py`` stage-0 probe's static half.
* **geometry** — training shapes passed via ``--hbm-geometry
  ROWS,F_PAD[,PADDED_BINS[,ROWS_PER_PAGE]]`` are priced with the exact
  footprint
  model (``costmodel.grow_footprint``): an unpaged shape over budget
  is a finding; with a page size the resident set of
  ``costmodel.page_schedule`` is checked instead — the off-chip
  acceptance test for ROADMAP item 5 page schedules.

Lowering never compiles or executes anything (``backend_compile`` is
never reached), so the pass runs under ``JAX_PLATFORMS=cpu`` like the
rest of the pipeline.
"""
from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

from ...obs import costmodel
from ..findings import Finding, SEV_ERROR, SEV_WARNING

PASS_NAME = "hbm-budget"

WARN_FRACTION = 0.8   # findings start before the cliff

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
}

_MAIN_RE = re.compile(r"func\.func public @main\((?P<args>.*?)\)"
                      r"\s*->\s*\((?P<res>.*?)\)\s*\{", re.DOTALL)
_ARG_RE = re.compile(r"%arg(?P<idx>\d+):\s*tensor<(?P<ty>[^>]*)>"
                     r"\s*(?P<attrs>\{[^}]*\})?")
_RES_RE = re.compile(r"tensor<(?P<ty>[^>]*)>")


def _tensor_bytes(ty: str) -> int:
    """Bytes of one ``tensor<...>`` type string (``8x128xf32`` or the
    scalar ``f32``); unknown element types price as 0."""
    parts = ty.strip().split("x")
    dt = parts[-1]
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 0        # dynamic dim — not ours, skip
        n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 0)


def parse_main_signature(text: str):
    """(args, results) of the lowered module's public main:
    ``args = [(lowered_idx, type_str, bytes, aliased)]``,
    ``results = [bytes]``."""
    m = _MAIN_RE.search(text)
    if not m:
        raise ValueError("lowered module has no public @main signature")
    args = []
    for am in _ARG_RE.finditer(m.group("args")):
        attrs = am.group("attrs") or ""
        args.append((int(am.group("idx")), am.group("ty"),
                     _tensor_bytes(am.group("ty")),
                     "tf.aliasing_output" in attrs))
    results = [_tensor_bytes(rm.group("ty"))
               for rm in _RES_RE.finditer(m.group("res"))]
    return args, results


_NP_TO_MLIR = {
    "float64": "f64", "float32": "f32", "float16": "f16",
    "bfloat16": "bf16", "int64": "i64", "uint64": "ui64",
    "int32": "i32", "uint32": "ui32", "int16": "i16",
    "uint16": "ui16", "int8": "i8", "uint8": "ui8", "bool": "i1",
}


def _mlir_type(aval) -> str:
    """``tensor<...>`` body for one abstract arg (``9216x128xf32``)."""
    dt = _NP_TO_MLIR.get(str(getattr(aval, "dtype", "")), "?")
    dims = "x".join(str(int(d)) for d in getattr(aval, "shape", ()))
    return f"{dims}x{dt}" if dims else dt


def align_lowered_args(original_args, lowered_args,
                       kept=None) -> Dict[int, bool]:
    """Map ORIGINAL argnums to their lowered aliasing flag.  jit
    prunes unused args from the lowered signature but preserves order.
    When the lowering exposes ``kept_var_idx`` (``kept``), the mapping
    is exact: lowered arg i IS original argnum kept[i].  Fallback: an
    order-preserving greedy match on the MLIR type string — correct
    whenever no pruned arg shares a type with a later kept one (true
    for every current entry; the exact path makes the ambiguity moot
    on modern jax)."""
    out: Dict[int, bool] = {}
    if kept is not None and len(kept) == len(lowered_args):
        for (_, _, _, aliased), argnum in zip(lowered_args, kept):
            out[int(argnum)] = aliased
        return out
    j = 0
    n = len(original_args)
    for _, ty, nbytes, aliased in lowered_args:
        while j < n and _mlir_type(original_args[j]) != ty.strip():
            j += 1
        if j >= n:
            break               # parse drift; leave the rest unmapped
        out[j] = aliased
        j += 1
    return out


def entry_residency_bytes(text: str, original_args=(),
                          kept=None) -> Tuple[int, Set[int]]:
    """(resident bytes of one call, aliased ORIGINAL argnums):
    argument bytes + result bytes, minus the result bytes donation
    lets XLA serve from argument buffers (an aliased pair occupies ONE
    buffer)."""
    args, results = parse_main_signature(text)
    arg_bytes = sum(b for _, _, b, _ in args)
    res_bytes = sum(results)
    saved = sum(b for _, _, b, al in args if al)
    mapping = align_lowered_args(original_args, args, kept=kept)
    aliased = {argnum for argnum, al in mapping.items() if al}
    return arg_bytes + res_bytes - saved, aliased


def check_geometry(rows: int, f_pad: int, padded_bins: int = 256,
                   rows_per_page: int = 0, *, num_leaves: int = 255,
                   stream: bool = True,
                   n_shards: int = 1) -> List[Finding]:
    """Price one training geometry against the HBM budget; the
    in-process half of ``--hbm-geometry`` (tests and the planner
    acceptance drive it directly)."""
    limit = costmodel.hbm_limit_bytes()
    where = (f"geometry:rows={rows},f_pad={f_pad}"
             + (f",rows_per_page={rows_per_page}" if rows_per_page
                else ""))
    out: List[Finding] = []
    if rows_per_page:
        plan = costmodel.page_schedule(
            rows=rows, f_pad=f_pad, padded_bins=padded_bins,
            num_leaves=num_leaves, stream=stream,
            n_shards=n_shards, rows_per_page=rows_per_page)
        if not plan.get("fits"):
            out.append(Finding(
                pass_name=PASS_NAME, code="HBM_PAGED_OVER_BUDGET",
                severity=SEV_ERROR, where=where,
                message=(
                    f"paged resident set "
                    f"{plan.get('resident_bytes', 0) / 2**30:.2f} GiB "
                    f"(3 page buffers + fixed arenas) exceeds the "
                    f"{limit / 2**30:.2f} GiB budget — shrink "
                    f"rows_per_page")))
        return out
    fp = costmodel.grow_footprint(
        rows=rows, f_pad=f_pad, padded_bins=padded_bins,
        num_leaves=num_leaves, stream=stream,
        n_shards=n_shards)
    if fp["peak_bytes"] > limit:
        out.append(Finding(
            pass_name=PASS_NAME, code="HBM_GEOMETRY_OVER_BUDGET",
            severity=SEV_ERROR, where=where,
            message=(
                f"unpaged footprint peak "
                f"{fp['peak_bytes'] / 2**30:.2f} GiB "
                f"({fp['peak_phase']}) exceeds the "
                f"{limit / 2**30:.2f} GiB budget — page the comb "
                f"(obs mem --plan emits the schedule)")))
    elif fp["peak_bytes"] > WARN_FRACTION * limit:
        out.append(Finding(
            pass_name=PASS_NAME, code="HBM_GEOMETRY_NEAR_BUDGET",
            severity=SEV_WARNING, where=where,
            message=(
                f"unpaged footprint peak "
                f"{fp['peak_bytes'] / 2**30:.2f} GiB is within "
                f"{100 - int(WARN_FRACTION * 100)}% of the "
                f"{limit / 2**30:.2f} GiB budget")))
    return out


def _jaxpr_residency_bytes(entry) -> Tuple[int, Set[int]]:
    """Residency from the traced jaxpr's in/out avals — the fallback
    for entries with no declared donation (compiled-TPU kernel
    registrations cannot LOWER on the CPU analysis host, but they
    trace fine; without aliasing info every buffer counts once)."""
    import numpy as np
    traced = entry.trace()
    total = 0
    for v in list(traced.jaxpr.invars) + list(traced.jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        try:
            itemsize = np.dtype(aval.dtype).itemsize
        except TypeError:
            continue
        total += costmodel.buffer_bytes(aval.shape, itemsize)
    return total, set()


def run(ctx) -> List[Finding]:
    budget = costmodel.hbm_limit_bytes()
    _, gen = costmodel.hbm_generation_bytes()
    out: List[Finding] = []
    for entry in ctx.entries:
        try:
            if entry.donate:
                # declared donations need the LOWERED program — that
                # is where jax records which aliases it honored.
                # Donation-declaring entries are the grow-level jits,
                # which trace the interpret path off-TPU and lower
                # cleanly on the CPU analysis host.
                text, orig_args, kept = entry.lowered_info()
                resident, aliased = entry_residency_bytes(
                    text, orig_args, kept=kept)
            else:
                resident, aliased = _jaxpr_residency_bytes(entry)
        except Exception as e:
            out.append(ctx.trace_error(PASS_NAME, entry, e))
            continue
        where = f"entry:{entry.name}"
        # donation audit: every DECLARED donation must have survived
        # lowering as a real buffer alias
        for argnum in entry.donate:
            if argnum not in aliased:
                out.append(Finding(
                    pass_name=PASS_NAME, code="DONATION_DROPPED",
                    severity=SEV_ERROR,
                    where=f"{where} arg:{argnum}",
                    message=(
                        f"argument {argnum} is declared donated but "
                        f"carries no tf.aliasing_output in the "
                        f"lowered program — jax dropped the donation "
                        f"(no shape/dtype-matching output), so the "
                        f"buffer is double-allocated every call"),
                    entry=entry.name, fixture=entry.fixture))
        if resident > budget:
            out.append(Finding(
                pass_name=PASS_NAME, code="HBM_OVER_BUDGET",
                severity=SEV_ERROR, where=where,
                message=(
                    f"argument+output residency "
                    f"{resident / 2**30:.2f} GiB exceeds the {gen} "
                    f"budget {budget / 2**30:.2f} GiB"),
                entry=entry.name, fixture=entry.fixture))
        elif resident > WARN_FRACTION * budget:
            out.append(Finding(
                pass_name=PASS_NAME, code="HBM_NEAR_BUDGET",
                severity=SEV_WARNING, where=where,
                message=(
                    f"argument+output residency "
                    f"{resident / 2**30:.2f} GiB is within "
                    f"{100 - int(WARN_FRACTION * 100)}% of the {gen} "
                    f"budget {budget / 2**30:.2f} GiB"),
                entry=entry.name, fixture=entry.fixture))
    for g in getattr(ctx, "hbm_geometries", []):
        for f in check_geometry(*g):
            f.fixture = False
            out.append(f)
    return out
