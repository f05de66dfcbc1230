"""Entrypoint registry for the static analyzer.

Kernel modules REGISTER themselves here (the ISSUE-7 registration
hooks): each ``ops/pallas/*.py`` builder family ships a
``@register_kernel`` block that returns a representative compiled-path
build plus ABSTRACT args (``jax.ShapeDtypeStruct`` — the analyzer
never materialises an array, so tracing is device-free and runs under
``JAX_PLATFORMS=cpu``).  The analyzer imports the kernel modules
(:func:`collect`), which populates the tables as a side effect.

Three registries live here:

* ``KERNELS``      name -> :class:`KernelEntry` (jaxpr-traced passes:
                   lane-contract, vmem-budget, host-sync)
* ``PURITY_PINS``  name -> builder of jaxpr-identity variants
                   (purity-pin pass; ONE home for the scattered
                   "knob off => identical program" test pins)
* ``MESH_CONFIGS`` (f_log, n_shards) records for the hist_scatter
                   static precondition (lane-contract pass)

This module stays import-light on purpose: kernel modules import it at
import time, so anything heavy here would cycle.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

# builder() -> (fn, args): fn traces with jax.make_jaxpr(fn)(*args);
# args are jax.ShapeDtypeStruct (abstract — nothing executes)
Builder = Callable[[], Tuple[Callable, Tuple[Any, ...]]]


@dataclass
class KernelEntry:
    """One registered analyzable entrypoint."""
    name: str
    kind: str                  # partition / hist / stream / fused /
                               # find / grow
    builder: Builder
    module: str = ""
    note: str = ""
    fixture: bool = False
    # argnums the entrypoint CLAIMS are donated (jit donate_argnums on
    # flat array args).  The hbm-budget pass audits the claim against
    # the LOWERED program: a declared argnum without a
    # ``tf.aliasing_output`` attribute is a DONATION_DROPPED finding —
    # the buffer is double-allocated every call (ISSUE 9)
    donate: Tuple[int, ...] = ()
    _traced: Any = field(default=None, repr=False)
    _lowered_text: Any = field(default=None, repr=False)

    def trace(self):
        """Cached ``jax.make_jaxpr`` of the entrypoint over its
        abstract args.  Trace-only: ShapeDtypeStruct args cannot be
        executed, so a pass that accidentally tried to run device code
        would fail loudly here."""
        if self._traced is None:
            import jax
            fn, args = self.builder()
            self._traced = jax.make_jaxpr(fn)(*args)
        return self._traced

    def lowered_info(self):
        """Cached ``(StableHLO text, original abstract args, kept
        argnums)`` of the entrypoint (trace + lower — still nothing
        compiles or executes; ``backend_compile`` is never reached).
        The lowered module is where jax records its ACTUAL
        buffer-aliasing decisions (``tf.aliasing_output`` arg
        attributes): a donation that cannot be honored (no
        shape/dtype-matching output) is silently dropped at this
        stage, which is exactly what the hbm-budget pass audits.
        ``kept`` maps the PRUNED lowered signature back to original
        argnums (jit drops unused args); None when the lowering does
        not expose ``kept_var_idx`` — the pass then falls back to
        order-preserving type alignment."""
        if self._lowered_text is None:
            import warnings

            import jax
            fn, args = self.builder()
            if not hasattr(fn, "lower"):
                fn = jax.jit(fn, donate_argnums=self.donate)
            with warnings.catch_warnings():
                # dropped donations warn at lowering; the pass reports
                # them as findings instead
                warnings.simplefilter("ignore")
                lowered = fn.lower(*args)
            kept = None
            try:
                kv = lowered._lowering.compile_args.get("kept_var_idx")
                if kv is not None:
                    kept = tuple(sorted(int(i) for i in kv))
            except Exception:   # private API — alignment falls back
                kept = None
            self._lowered_text = (lowered.as_text(), tuple(args), kept)
        return self._lowered_text


@dataclass
class MeshConfig:
    """A (f_log, n_shards) data-parallel histogram-merge shape to check
    against the reduce-scatter precondition at ANALYSIS time (the
    runtime fallback in ops/grow.py only warns once per shape)."""
    f_log: int
    n_shards: int
    source: str = ""
    fixture: bool = False


KERNELS: Dict[str, KernelEntry] = {}
PURITY_PINS: Dict[str, Callable] = {}
MESH_CONFIGS: List[MeshConfig] = []

_collected = False


def register_kernel(name: str, *, kind: str, note: str = "",
                    donate: Tuple[int, ...] = ()):
    """Decorator for kernel modules: registers ``builder`` under
    ``name``.  The builder runs lazily (first trace), so registration
    costs nothing at import time.  ``donate`` declares the argnums the
    entrypoint's jit donates (flat array args) — the hbm-budget pass
    then audits that every declared donation actually aliases an
    output in the lowered program."""
    def deco(builder: Builder) -> Builder:
        KERNELS[name] = KernelEntry(
            name=name, kind=kind, builder=builder,
            module=getattr(builder, "__module__", ""), note=note,
            donate=tuple(donate))
        return builder
    return deco


def register_purity_pin(name: str):
    """Decorator: ``builder() -> [(variant_name, fn, args), ...]``.
    The purity-pin pass traces every variant and requires identical
    jaxpr digests — the registered form of the "knob off => identical
    program" invariant."""
    def deco(builder: Callable) -> Callable:
        PURITY_PINS[name] = builder
        return builder
    return deco


def register_mesh_config(f_log: int, n_shards: int, source: str = "",
                         fixture: bool = False) -> None:
    MESH_CONFIGS.append(MeshConfig(int(f_log), int(n_shards),
                                   source=source, fixture=fixture))


def collect(force: bool = False) -> Dict[str, KernelEntry]:
    """Import every module that carries registration hooks; returns
    the kernel table.  Idempotent."""
    global _collected
    if _collected and not force:
        return KERNELS
    import importlib
    for mod in (
        "lightgbm_tpu.ops.pallas.partition_kernel",
        "lightgbm_tpu.ops.pallas.partition_kernel2",
        "lightgbm_tpu.ops.pallas.partition_kernel3",
        "lightgbm_tpu.ops.pallas.hist_kernel",
        "lightgbm_tpu.ops.pallas.hist_kernel2",
        "lightgbm_tpu.ops.pallas.fused_split",
        "lightgbm_tpu.ops.pallas.stream_grad",
        "lightgbm_tpu.ops.pallas.apply_find",
        "lightgbm_tpu.ops.pallas.serve_kernel",
        "lightgbm_tpu.analysis.entries",
    ):
        importlib.import_module(mod)
    # Tests and tools/tpu_smoke.py drop the library from ``sys.modules``
    # to re-read its knobs.  A caller bound to this module from before
    # such a purge (a test file's top-level import) then asks a
    # registry the hooks above did not register with: they ran, or had
    # run, against the one ``sys.modules`` holds now.  Take that one's.
    import sys
    live = sys.modules.get(__name__)
    if live is not None and live.KERNELS is not KERNELS:
        live.collect()
        KERNELS.update(live.KERNELS)
        PURITY_PINS.update(live.PURITY_PINS)
        MESH_CONFIGS[:] = list(live.MESH_CONFIGS)
    _collected = True
    return KERNELS


# ---------------------------------------------------------------------
# shared abstract-arg helpers for the registration hooks
# ---------------------------------------------------------------------
def sds(shape, dtype):
    """ShapeDtypeStruct shorthand (kept here so hooks stay one-liners
    and provably abstract)."""
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def partition_args(n: int, C: int, sel_words: int = 0):
    """(sel, rows, scratch) abstract args shared by every single-scan
    partition contract.  ``sel_words`` appends that many categorical
    bitset membership words to the 8-slot split descriptor (ISSUE 16)."""
    import jax.numpy as jnp
    from ..ops.pallas.layout import comb_shape
    shape = comb_shape(n, C)         # the comb is plane-major
    return (sds((8 + sel_words,), jnp.int32), sds(shape, jnp.float32),
            sds(shape, jnp.float32))
