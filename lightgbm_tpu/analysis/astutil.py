"""AST-level analysis of Pallas kernel modules.

The jaxpr passes see what a kernel DOES to memory shapes; this module
sees what the kernel SOURCE promises about manual-DMA discipline — the
``make_async_copy`` / ``.start()`` / ``.wait()`` protocol whose safety
argument today lives only in partition_kernel2's comments.

Scope rules (deliberately conservative so real schedules with
deferred cross-step waits stay clean):

* Semaphore pairing is aggregated per TOP-LEVEL function (the kernel
  body plus its nested ``pl.when`` closures): a semaphore that is
  ``start()``-ed somewhere but ``wait()``-ed nowhere in that scope can
  never be drained by the schedule — flagged.
* Straight-line rules run per statement list (each function / nested
  closure / branch body independently): reads of an in-flight copy's
  destination, writes to an in-flight copy's source or destination,
  and writes to an SMEM cursor that a CONSTRUCTED-but-unstarted copy's
  index expressions reference (the descriptor would be issued against
  a mutated cursor).
* Kernel-body discovery: first args of ``pl.pallas_call`` resolved
  through ``functools.partial`` bindings, closed transitively over
  same-module calls — host-sync source checks apply to exactly these
  functions.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple


def expr_base(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute/subscript/call chain:
    ``rows_ref.at[pl.ds(c, R)]`` -> ``rows_ref``."""
    while True:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        else:
            return None


def names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_make_async_copy(call: ast.AST) -> bool:
    if not isinstance(call, ast.Call):
        return False
    f = call.func
    return ((isinstance(f, ast.Attribute)
             and f.attr == "make_async_copy")
            or (isinstance(f, ast.Name)
                and f.id == "make_async_copy"))


@dataclass
class CopyRec:
    """One tracked make_async_copy."""
    var: str                 # bound name ("" for chained anonymous)
    src_base: str
    dst_base: str
    sem_base: str
    index_names: Set[str]    # names the src/dst slice exprs read
                             # (cursor aliasing rule)
    line: int
    started: bool = False
    waited: bool = False


@dataclass
class DmaEvent:
    """A straight-line violation found while simulating one list."""
    code: str
    line: int
    detail: str


@dataclass
class FunctionReport:
    name: str
    line: int
    sem_starts: Dict[str, int] = field(default_factory=dict)
    sem_waits: Dict[str, int] = field(default_factory=dict)
    events: List[DmaEvent] = field(default_factory=list)
    never_started: List[CopyRec] = field(default_factory=list)
    has_dma: bool = False


class ModuleAnalysis:
    """Parsed view of one kernel module."""

    def __init__(self, path: str, source: str = None):
        self.path = path
        self.rel = rel_path(path)
        src = source if source is not None else open(path).read()
        self.tree = ast.parse(src, filename=path)
        self.functions: Dict[str, List[ast.FunctionDef]] = {}
        self._collect_functions(self.tree)
        self.partial_map = self._collect_partials()
        self.kernel_bodies = self._kernel_body_set()

    # -- discovery ----------------------------------------------------
    def _collect_functions(self, node) -> None:
        for child in ast.walk(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                # simple names COLLIDE across builders (``kern`` is
                # bound in several of them), so every def per name is
                # kept and downstream consumers
                # scan all of them
                self.functions.setdefault(child.name, []).append(child)

    def _collect_partials(self) -> Dict[str, Set[str]]:
        """Function-aliasing bindings, module-wide and SET-valued (the
        same local name — ``kern`` — binds different kernels in
        different builders): ``kern = functools.partial(F, ...)``,
        ``kern_fn = A if cond else B``, ``kern = F``."""
        out: Dict[str, Set[str]] = {}

        def add(name: str, node) -> None:
            for base in self._fn_candidates(node):
                out.setdefault(name, set()).add(base)

        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if isinstance(tgt, ast.Name):
                add(tgt.id, node.value)
        return out

    def _fn_candidates(self, v) -> Set[str]:
        """Names a value expression could bind as a callable: partial
        first args, IfExp branches, plain names."""
        if isinstance(v, ast.Name):
            return {v.id}
        if isinstance(v, ast.IfExp):
            return self._fn_candidates(v.body) | \
                self._fn_candidates(v.orelse)
        if (isinstance(v, ast.Call)
                and (getattr(v.func, "attr", None) == "partial"
                     or (isinstance(v.func, ast.Name)
                         and v.func.id == "partial"))
                and v.args):
            return self._fn_candidates(v.args[0])
        return set()

    def _resolve(self, base: Optional[str]) -> Set[str]:
        """Close an alias over the partial map (bounded depth)."""
        if not base:
            return set()
        out, frontier = set(), {base}
        for _ in range(4):
            nxt = set()
            for b in frontier:
                if b in self.functions:
                    out.add(b)
                nxt |= self.partial_map.get(b, set())
            frontier = nxt - out
            if not frontier:
                break
        return out

    def _kernel_body_set(self) -> Set[str]:
        roots: Set[str] = set()
        for node in ast.walk(self.tree):
            if (isinstance(node, ast.Call)
                    and ((isinstance(node.func, ast.Attribute)
                          and node.func.attr == "pallas_call")
                         or (isinstance(node.func, ast.Name)
                             and node.func.id == "pallas_call"))
                    and node.args):
                roots |= self._resolve(expr_base(node.args[0]))
        # transitive closure over same-module calls (wrappers like
        # ``def kern(*refs): _refresh_kernel(*refs, ...)`` and shared
        # helpers like _hist_accumulate)
        seen: Set[str] = set()
        frontier = set(roots)
        while frontier:
            fn = frontier.pop()
            if fn in seen:
                continue
            seen.add(fn)
            for node in self.functions[fn]:
                for call in ast.walk(node):
                    if isinstance(call, ast.Call):
                        for base in self._resolve(expr_base(call.func)):
                            if base not in seen:
                                frontier.add(base)
        return seen

    # -- DMA protocol -------------------------------------------------
    def dma_reports(self) -> List[FunctionReport]:
        """One report per TOP-LEVEL function that (transitively)
        performs manual DMA."""
        out = []
        for node in self.tree.body:
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            rep = FunctionReport(name=node.name, line=node.lineno)
            self._scan_function(node, rep)
            if rep.has_dma:
                out.append(rep)
        return out

    def _scan_function(self, fn, rep: FunctionReport) -> None:
        self._simulate_list(fn.body, rep)

    def _simulate_list(self, stmts, rep: FunctionReport,
                       outer_constructed: Dict[str, CopyRec] = None
                       ) -> None:
        # copies constructed in an ENCLOSING scope stay resolvable (a
        # ``cp.start()`` inside a pl.when closure must count toward
        # cp's semaphore, not vanish); the dict is copied so sibling
        # scopes don't see each other's constructions, but the
        # CopyRec objects are shared so started/waited mutations
        # propagate back to the constructing scope
        constructed: Dict[str, CopyRec] = dict(outer_constructed or {})
        own: Set[str] = set()
        inflight: List[CopyRec] = []

        def retire_sem(sem: str) -> None:
            for rec in inflight:
                if rec.sem_base == sem:
                    rec.waited = True
            inflight[:] = [r for r in inflight if not r.waited]
            for rec in list(constructed.values()):
                if rec.sem_base == sem:
                    rec.waited = True

        def count(table: Dict[str, int], sem: str) -> None:
            table[sem] = table.get(sem, 0) + 1

        def make_rec(var: str, call: ast.Call) -> CopyRec:
            rep.has_dma = True
            args = call.args
            src = args[0] if len(args) > 0 else None
            dst = args[1] if len(args) > 1 else None
            sem = args[2] if len(args) > 2 else None
            idx = set()
            for a in (src, dst):
                if a is not None:
                    idx |= names_in(a)
            return CopyRec(
                var=var,
                src_base=expr_base(src) or "?",
                dst_base=expr_base(dst) or "?",
                sem_base=expr_base(sem) or "?",
                index_names=idx, line=call.lineno)

        for st in stmts:
            # nested closures (pl.when bodies) and branches: fresh
            # straight-line state, shared semaphore accounting, with
            # the current constructed-copy bindings visible inside
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._simulate_list(st.body, rep, constructed)
                continue
            if isinstance(st, (ast.If, ast.For, ast.While, ast.With)):
                for body in (getattr(st, "body", []),
                             getattr(st, "orelse", [])):
                    if body:
                        self._simulate_list(body, rep, constructed)
                continue

            # cp = make_async_copy(...)
            if (isinstance(st, ast.Assign) and len(st.targets) == 1
                    and isinstance(st.targets[0], ast.Name)
                    and _is_make_async_copy(st.value)):
                constructed[st.targets[0].id] = make_rec(
                    st.targets[0].id, st.value)
                own.add(st.targets[0].id)
                continue

            # .start() / .wait(), named or chained
            if isinstance(st, ast.Expr) and isinstance(st.value,
                                                       ast.Call):
                call = st.value
                f = call.func
                if isinstance(f, ast.Attribute) and f.attr in ("start",
                                                               "wait"):
                    tgt = f.value
                    if _is_make_async_copy(tgt):
                        rec = make_rec("", tgt)
                        if f.attr == "start":
                            rec.started = True
                            inflight.append(rec)
                            count(rep.sem_starts, rec.sem_base)
                        else:
                            count(rep.sem_waits, rec.sem_base)
                            retire_sem(rec.sem_base)
                        continue
                    if isinstance(tgt, ast.Name) \
                            and tgt.id in constructed:
                        rec = constructed[tgt.id]
                        if f.attr == "start":
                            rec.started = True
                            inflight.append(rec)
                            count(rep.sem_starts, rec.sem_base)
                        else:
                            rec.waited = True
                            count(rep.sem_waits, rec.sem_base)
                            retire_sem(rec.sem_base)
                        continue

            # any other statement: enforce the straight-line rules
            reads = names_in(st)
            writes: Set[str] = set()
            if isinstance(st, (ast.Assign, ast.AugAssign)):
                targets = (st.targets if isinstance(st, ast.Assign)
                           else [st.target])
                for t in targets:
                    b = expr_base(t)
                    if b:
                        writes.add(b)
                    # target index expressions are reads, the target
                    # base is a write — drop it from the read set
                reads -= writes
            for rec in inflight:
                if rec.dst_base in reads:
                    rep.events.append(DmaEvent(
                        "DMA_READ_BEFORE_WAIT", st.lineno,
                        f"reads {rec.dst_base!r}, the destination of "
                        f"the DMA started at line {rec.line} "
                        f"(sem {rec.sem_base}) before its wait"))
                for b in writes & {rec.dst_base, rec.src_base}:
                    rep.events.append(DmaEvent(
                        "DMA_WRITE_INFLIGHT", st.lineno,
                        f"writes {b!r} while the DMA started at line "
                        f"{rec.line} (sem {rec.sem_base}) is in "
                        f"flight"))
            for rec in constructed.values():
                if rec.started or rec.waited:
                    continue
                hit = writes & rec.index_names
                for b in hit:
                    rep.events.append(DmaEvent(
                        "DMA_CURSOR_ALIAS", st.lineno,
                        f"writes {b!r}, which the copy constructed at "
                        f"line {rec.line} reads in its index "
                        f"expressions, before that copy starts"))

        # end of list: copies constructed HERE that never started AND
        # never waited anywhere (nested scopes share the CopyRec, so a
        # start inside a pl.when closure clears the flag) are dead
        # descriptors
        for name in own:
            rec = constructed[name]
            if not rec.started and not rec.waited:
                rep.never_started.append(rec)

    # -- host-sync source rules --------------------------------------
    HOST_CALLS = {
        ("np", "asarray"), ("np", "array"), ("numpy", "asarray"),
        ("numpy", "array"), ("jax", "device_get"),
        ("jnp", "device_get"),
    }

    def host_sync_hits(self) -> List[Tuple[str, int, str]]:
        """(func, line, what) for host-pull constructs inside kernel
        bodies — trace-time device pulls the jit boundary can't see."""
        out = []
        for name in sorted(self.kernel_bodies):
            for fn in self.functions[name]:
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    f = node.func
                    if isinstance(f, ast.Attribute):
                        if f.attr in ("item", "block_until_ready") \
                                and not node.args:
                            out.append((name, node.lineno,
                                        f".{f.attr}()"))
                            continue
                        base = expr_base(f.value)
                        if (base, f.attr) in self.HOST_CALLS:
                            out.append((name, node.lineno,
                                        f"{base}.{f.attr}()"))
        return out


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def rel_path(path: str) -> str:
    """Repo-relative form of an analyzed file path (the ``where``
    anchor findings and the fixture-file set use)."""
    return os.path.relpath(path, _repo_root()) if os.path.isabs(path) \
        else path


def default_kernel_files() -> List[str]:
    """The ops/pallas kernel modules (fixtures are added per run)."""
    d = os.path.join(_repo_root(), "lightgbm_tpu", "ops", "pallas")
    return sorted(
        os.path.join(d, f) for f in os.listdir(d)
        if f.endswith(".py") and f != "__init__.py")
