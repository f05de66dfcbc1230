"""Jaxpr traversal for the analyzer: recursive walks (through pjit /
scan / while / cond sub-jaxprs) and structured extraction of
``pallas_call`` equations.

What a traced pallas_call exposes (jax 0.4.x):

* ``params["jaxpr"]`` — the KERNEL jaxpr; its invars are
  ``AbstractMemoryRef``s with concrete shapes/dtypes and a memory
  space that stringifies to ``smem`` / ``vmem`` / ``any`` (HBM) /
  ``semaphore_mem``.  Order: scalar-prefetch operands, then inputs,
  then outputs, then scratch (counts from ``params["grid_mapping"]``).
* ``params["name_and_src_info"]`` — kernel function name + file:line.
* ``params["compiler_params"]`` — per-call Mosaic knobs
  (``vmem_limit_bytes`` when a builder sets one).

These give the passes exactly what the BENCH_r03 regression needed
checked: the PHYSICAL memref geometry each kernel will present to
Mosaic, available off-chip at trace time.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Iterator, List, Optional


def walk_eqns(jaxpr) -> Iterator[Any]:
    """Yield every eqn of a (closed) jaxpr and all nested sub-jaxprs,
    including pallas kernel jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from walk_eqns(sub)


def _sub_jaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        out.extend(_jaxprs_in(v))
    return out


def _jaxprs_in(v) -> List[Any]:
    # a Jaxpr or ClosedJaxpr hiding in params (pjit: 'jaxpr'; scan /
    # while / cond: 'jaxpr' / 'cond_jaxpr' / 'body_jaxpr' / 'branches';
    # pallas_call: the kernel 'jaxpr')
    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
        return [v]
    if isinstance(v, (tuple, list)):
        out = []
        for x in v:
            out.extend(_jaxprs_in(x))
        return out
    return []


@dataclass
class RefInfo:
    """One kernel-visible memref operand."""
    role: str          # "scalar" | "in" | "out" | "scratch"
    shape: tuple
    dtype: str
    space: str         # "smem" | "vmem" | "hbm" | "any" | "semaphore" | "?"

    @property
    def nbytes(self) -> int:
        if self.space == "semaphore":
            return 0
        import numpy as np

        from ..obs.costmodel import buffer_bytes
        try:
            itemsize = np.dtype(self.dtype).itemsize
        except TypeError:
            return 0
        return buffer_bytes(self.shape, itemsize)


@dataclass
class PallasCallInfo:
    """Everything the passes need from one traced pallas_call eqn."""
    kernel_name: str
    src: str                      # "file:line" of the kernel function
    grid: tuple
    interpret: bool
    refs: List[RefInfo] = field(default_factory=list)
    vmem_limit_bytes: Optional[int] = None
    jaxpr: Any = None             # the kernel jaxpr (host-sync walks it)

    def vmem_refs(self, roles=("in", "out", "scratch")) -> List[RefInfo]:
        return [r for r in self.refs
                if r.space == "vmem" and r.role in roles]

    def any_refs(self) -> List[RefInfo]:
        """Unblocked refs the kernel DMAs by hand: ``pltpu.HBM`` and
        ``pl.ANY`` (which Mosaic places in HBM) alike."""
        return [r for r in self.refs if r.space in ("hbm", "any")]


def _space_of(aval) -> str:
    ms = getattr(aval, "memory_space", None)
    s = str(ms).lower() if ms is not None else ""
    if "sem" in s:
        return "semaphore"
    for name in ("smem", "vmem", "hbm", "any"):
        if name in s:
            return name
    # blocked BlockSpecs without an explicit space land in VMEM
    if hasattr(aval, "shape"):
        return "vmem" if ms is None else "?"
    return "?"


def _ref_info(role: str, aval) -> RefInfo:
    return RefInfo(
        role=role,
        shape=tuple(int(d) for d in getattr(aval, "shape", ())),
        dtype=str(getattr(aval, "dtype", "")),
        space=_space_of(aval))


def _scoped_peak(jaxpr) -> List[RefInfo]:
    """The refs of the dearest chain of nested ``pl.run_scoped``
    allocations inside a kernel jaxpr, as scratch: a scope's buffers
    live while its body runs, so nested scopes add and sibling scopes
    (the two parity branches of a ping-pong body) do not."""
    best: List[RefInfo] = []
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in getattr(inner, "eqns", ()):
        own: List[RefInfo] = []
        if eqn.primitive.name == "run_scoped":
            # the body's invars are the allocated refs (what it closes
            # over arrives as constvars)
            body = eqn.params["jaxpr"]
            own = [_ref_info("scratch", v.aval)
                   for v in getattr(body, "jaxpr", body).invars]
        for sub in _sub_jaxprs(eqn):
            chain = own + _scoped_peak(sub)
            if (sum(r.nbytes for r in chain)
                    > sum(r.nbytes for r in best)):
                best = chain
    return best


def pallas_calls(traced) -> List[PallasCallInfo]:
    """Extract every pallas_call (recursively) from a traced
    entrypoint."""
    out = []
    for eqn in walk_eqns(traced):
        if eqn.primitive.name != "pallas_call":
            continue
        p = eqn.params
        gm = p.get("grid_mapping")
        kj = p.get("jaxpr")
        nsi = p.get("name_and_src_info")
        name = getattr(nsi, "name", None) or str(nsi or "?")
        src = getattr(nsi, "src_info", "") or ""
        src = src.strip().lstrip("at ").strip()
        inner = getattr(kj, "jaxpr", kj)
        invars = list(getattr(inner, "invars", []))
        n_scalar = int(getattr(gm, "num_index_operands", 0) or 0)
        n_in = int(getattr(gm, "num_inputs", 0) or 0)
        n_out = int(getattr(gm, "num_outputs", 0) or 0)
        n_scr = int(getattr(gm, "num_scratch_operands", 0) or 0)
        roles = (["scalar"] * n_scalar + ["in"] * n_in
                 + ["out"] * n_out + ["scratch"] * n_scr)
        if len(roles) != len(invars):
            # grid_mapping operand counts drifted (jax upgrade renamed
            # a field): degrading to unknown roles would silently
            # price every footprint at 0 bytes and blind vmem-budget
            # while the strict run stays green — fail the entry loudly
            # instead (the passes surface this as TRACE_FAILED)
            raise ValueError(
                f"pallas_call {name}: grid_mapping operand counts "
                f"({n_scalar}+{n_in}+{n_out}+{n_scr}) do not cover "
                f"{len(invars)} kernel refs — jax GridMapping layout "
                f"drifted; update jaxpr_tools.pallas_calls")
        refs = [_ref_info(role, v.aval) for role, v in zip(roles, invars)]
        refs += _scoped_peak(kj)
        cp = p.get("compiler_params")
        vlim = None
        if cp is not None:
            if isinstance(cp, dict):
                for v in cp.values():
                    vlim = getattr(v, "vmem_limit_bytes",
                                   None) or (v.get("vmem_limit_bytes")
                                             if isinstance(v, dict)
                                             else None)
                    if vlim:
                        break
            else:
                vlim = getattr(cp, "vmem_limit_bytes", None)
        grid = tuple(getattr(gm, "grid", ()) or ())
        out.append(PallasCallInfo(
            kernel_name=str(name), src=src, grid=grid,
            interpret=bool(p.get("interpret", False)), refs=refs,
            vmem_limit_bytes=int(vlim) if vlim else None, jaxpr=kj))
    return out
