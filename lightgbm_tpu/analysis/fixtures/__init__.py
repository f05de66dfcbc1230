"""Red-team fixture set: one SEEDED violation per analyzer pass.

Each fixture injects a deliberately-broken artifact into a normal
analyzer run (``--fixture NAME`` on the CLI, ``fixtures=[...]`` via
``run_analysis``): a traceable entrypoint with a bad memref geometry,
an AST file with a broken DMA protocol, a purity pin whose knob leaks.
The run must then FAIL — ci_tier1.sh leg 6 and tests/test_analysis.py
pin that each pass actually detects its seeded violation (an analyzer
that silently goes blind is worse than none).  Fixture findings are
never allowlistable.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List

from ..registry import KernelEntry, MeshConfig

_DIR = os.path.dirname(os.path.abspath(__file__))


@dataclass
class FixtureBundle:
    entries: List[KernelEntry] = field(default_factory=list)
    pins: Dict[str, object] = field(default_factory=dict)
    ast_files: List[str] = field(default_factory=list)
    mesh: List[MeshConfig] = field(default_factory=list)
    # routing pass (ISSUE 10): injected golden-matrix cells
    # [(key, encoded_cell)] and same-shape-bucket retrace pins
    routing_cells: List[tuple] = field(default_factory=list)
    retrace_pins: Dict[str, object] = field(default_factory=dict)
    # dma-race page-schedule audit (ISSUE 15): injected page-DMA
    # schedules [(name, events, n_pages)]
    page_schedules: List[tuple] = field(default_factory=list)


def _entry(name: str, kind: str, builder, donate=()) -> KernelEntry:
    return KernelEntry(name=name, kind=kind, builder=builder,
                       module=__name__, fixture=True,
                       donate=tuple(donate))


def load(name: str) -> FixtureBundle:
    """Build the named fixture bundle (see FIXTURES for the set)."""
    try:
        maker = FIXTURES[name]
    except KeyError:
        raise ValueError(
            f"unknown fixture {name!r}; known: {sorted(FIXTURES)}")
    return maker()


# ---------------------------------------------------------------------
# lane-contract: a kernel presenting a 64-lane HBM memref (the
# BENCH_r03 regression class, reconstructed)
# ---------------------------------------------------------------------
def _bad_lane() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from ...ops.pallas.partition_kernel import _HBM

        def kernel(x_hbm, o_hbm, v, sem):
            cp = pltpu.make_async_copy(x_hbm.at[pl.ds(0, 8)], v, sem)
            cp.start()
            cp.wait()
            cpo = pltpu.make_async_copy(v, o_hbm.at[pl.ds(0, 8)], sem)
            cpo.start()
            cpo.wait()

        n, c = 256, 64    # 64-lane lines: the seeded violation

        def fn(x):
            return pl.pallas_call(
                kernel,
                in_specs=[pl.BlockSpec(memory_space=_HBM)],
                out_specs=pl.BlockSpec(memory_space=_HBM),
                out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
                scratch_shapes=[pltpu.VMEM((8, c), jnp.float32),
                                pltpu.SemaphoreType.DMA],
            )(x)

        return fn, (jax.ShapeDtypeStruct((n, c), jnp.float32),)

    return FixtureBundle(entries=[_entry("fixture_bad_lane",
                                         "partition", builder)])


# ---------------------------------------------------------------------
# vmem-budget: a resident accumulator larger than physical VMEM
# ---------------------------------------------------------------------
def _bad_vmem() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc):
            acc[...] = jnp.zeros_like(acc)
            o_ref[...] = x_ref[...]

        def fn(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
                # 8192 x 4096 f32 = 128 MiB resident scratch
                scratch_shapes=[pltpu.VMEM((8192, 4096), jnp.float32)],
            )(x)

        return fn, (jax.ShapeDtypeStruct((32, 128), jnp.float32),)

    return FixtureBundle(entries=[_entry("fixture_bad_vmem", "hist",
                                         builder)])


# ---------------------------------------------------------------------
# dma-race / host-sync: AST fixture files (parsed, never imported)
# ---------------------------------------------------------------------
def _bad_dma() -> FixtureBundle:
    return FixtureBundle(
        ast_files=[os.path.join(_DIR, "bad_dma_ast.py")])


def _bad_host() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        import numpy as np

        def fn(x):
            # host round-trip inside the traced program
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2.0,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y + 1.0

        return fn, (jax.ShapeDtypeStruct((8, 128), jnp.float32),)

    return FixtureBundle(
        entries=[_entry("fixture_bad_host", "grow", builder)],
        ast_files=[os.path.join(_DIR, "bad_host_ast.py")])


# ---------------------------------------------------------------------
# hbm-budget donation audit: a jit that CLAIMS to donate its big
# carried buffer, but whose output shapes let jax silently drop the
# donation (no shape/dtype-matching output) — the buffer is then
# double-allocated every call.  The ISSUE-9 red team: the audit must
# catch the dropped alias in the lowered program.
# ---------------------------------------------------------------------
def _bad_donation() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp

        # the "carry" (256, 128) is donated but only a (128,) reduction
        # is returned — nothing can alias, jax drops the donation
        fn = jax.jit(lambda carry, x: (carry.sum(axis=0) + x,),
                     donate_argnums=(0,))
        return fn, (jax.ShapeDtypeStruct((256, 128), jnp.float32),
                    jax.ShapeDtypeStruct((128,), jnp.float32))

    return FixtureBundle(entries=[_entry("fixture_bad_donation",
                                         "grow", builder,
                                         donate=(0,))])


# ---------------------------------------------------------------------
# purity-pin: a knob that leaks into the "off" program
# ---------------------------------------------------------------------
def _bad_purity() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        args = (jax.ShapeDtypeStruct((8, 128), jnp.float32),)

        def off(x):
            return x * 2.0

        def leaky_off(x):
            return x * 2.0 + 0.0 * jnp.sum(x)   # the leak

        return [("off", off, args), ("knob-off-leaky", leaky_off, args)]

    return FixtureBundle(pins={"fixture-bad-purity": builder})


# ---------------------------------------------------------------------
# lane-contract mesh precondition: a config that hits the psum fallback
# ---------------------------------------------------------------------
def _bad_mesh() -> FixtureBundle:
    return FixtureBundle(mesh=[MeshConfig(
        f_log=10, n_shards=8, source="fixture", fixture=True)])


# ---------------------------------------------------------------------
# routing matrix: a fast-path-eligible cell routed to row_order with
# NO named fallback rule (the ISSUE-10 red team: an analyzer that
# cannot see an unjustified 25x loss is blind to ROADMAP item 4)
# ---------------------------------------------------------------------
def _bad_route() -> FixtureBundle:
    key = ("learner=serial;shards=1;be=tpu;efb=0;u8=1;over=0;"
           "fdiv=1;dp=0;cegb=0;cat=0;bag=0;lin=0;boost=gbdt;"
           "obj=binary;k=1;forced=0;mono=0;cegbc=0;phys=auto;"
           "stream=auto;part=permute;fused=1;scat=1;"
           "ob=0;pg=auto;fixture=bad_route")
    cell = ("path=row_order;scheme=none;fused=0;merge=none;"
            "paged=0;why=-;merge_why=-;paged_why=-;"
            "prog=row_order|none|fused0|serial|shards1|none|"
            "dp0|cegb0|cat0|efb0|u81|paged0")
    return FixtureBundle(routing_cells=[(key, cell)])


# ---------------------------------------------------------------------
# routing matrix: an UNJUSTIFIED over-wide EFB fallback (ISSUE 12).
# efb_overwide is the one shape under which a bundled config may still
# lose the physical path after the efb_bundle graduation — a cell that
# claims the rule while its key says the unbundled layout FITS (ew=0)
# quietly re-opens the deleted 0.04x fallback class for every bundled
# dataset.  The routing pass must reject it
# (ROUTING_EFB_OVERWIDE_UNJUSTIFIED).
# ---------------------------------------------------------------------
def _efb_overwide() -> FixtureBundle:
    key = ("learner=serial;shards=1;be=tpu;efb=1;u8=1;over=0;"
           "ew=0;fdiv=1;dp=0;cegb=0;cat=0;bag=0;lin=0;boost=gbdt;"
           "obj=binary;k=1;forced=0;mono=0;cegbc=0;phys=auto;"
           "stream=auto;part=permute;fused=1;scat=1;"
           "ob=0;pg=auto;fixture=efb_overwide")
    cell = ("path=row_order;scheme=none;fused=0;merge=none;"
            "paged=0;why=efb_overwide;merge_why=-;"
            "paged_why=-;"
            "prog=row_order|none|fused0|serial|shards1|none|"
            "dp0|cegb0|cat0|efb1|u81|paged0")
    return FixtureBundle(routing_cells=[(key, cell)])


# ---------------------------------------------------------------------
# routing matrix: an UNJUSTIFIED over-wide dense fallback.
# Every comb kernel now stages up to sixteen planes; a cell that blames
# comb_overwide while its key lacks the shape fact (cw=1) sends a dense
# table the kernels can build to the 0.04x row_order path.  The routing
# pass must reject it (ROUTING_COMB_OVERWIDE_UNJUSTIFIED).
# ---------------------------------------------------------------------
def _comb_overwide() -> FixtureBundle:
    key = ("learner=serial;shards=1;be=tpu;efb=0;u8=1;over=0;"
           "ew=0;fdiv=1;dp=0;cegb=0;cat=0;bag=0;lin=0;boost=gbdt;"
           "obj=binary;k=1;forced=0;mono=0;cegbc=0;phys=auto;"
           "stream=auto;part=permute;fused=1;scat=1;"
           "ob=0;pg=auto;fixture=comb_overwide")
    cell = ("path=row_order;scheme=none;fused=0;merge=none;"
            "paged=0;why=comb_overwide;merge_why=-;"
            "paged_why=-;"
            "prog=row_order|none|fused0|serial|shards1|none|"
            "dp0|cegb0|cat0|efb0|u81|paged0")
    return FixtureBundle(routing_cells=[(key, cell)])


# ---------------------------------------------------------------------
# lane-contract cat bitset (ISSUE 16): an oversized/misaligned bitset
# memref.  The graduated cat-subset path carries the per-node
# membership bitset as i32 SMEM words appended to sel (8 + W words,
# W = ceil(padded_bins/32) <= layout.CAT_BITSET_WORDS) — Mosaic lays
# SMEM scalars out itself, so no lane rule applies.  The seeded
# violation parks the bitsets in HBM instead, as (n_nodes, 8 + W) i32
# lines: a 16-lane minor dim, so every dynamic node-offset DMA fails
# the 'aligned to tiling (128)' proof on chip (the BENCH_r03 class,
# now wearing categorical clothes).  The lane-contract pass must flag
# it — an analyzer blind to this would wave through the obvious
# "optimization" of moving the bitset side table off SMEM.
# ---------------------------------------------------------------------
def _bad_cat() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from ...ops.pallas.layout import CAT_BITSET_WORDS
        from ...ops.pallas.partition_kernel import _HBM, SEL_MEMBER

        def kernel(b_hbm, o_hbm, v, sem):
            cp = pltpu.make_async_copy(b_hbm.at[pl.ds(0, 8)], v, sem)
            cp.start()
            cp.wait()
            cpo = pltpu.make_async_copy(v, o_hbm.at[pl.ds(0, 8)], sem)
            cpo.start()
            cpo.wait()

        # (n_nodes, 8 + 8) i32: the misaligned bitset side table
        n, w = 256, SEL_MEMBER + CAT_BITSET_WORDS

        def fn(b):
            return pl.pallas_call(
                kernel,
                in_specs=[pl.BlockSpec(memory_space=_HBM)],
                out_specs=pl.BlockSpec(memory_space=_HBM),
                out_shape=jax.ShapeDtypeStruct((n, w), jnp.int32),
                scratch_shapes=[pltpu.VMEM((8, w), jnp.int32),
                                pltpu.SemaphoreType.DMA],
            )(b)

        return fn, (jax.ShapeDtypeStruct((n, w), jnp.int32),)

    return FixtureBundle(entries=[_entry("fixture_bad_cat",
                                         "partition", builder)])


# ---------------------------------------------------------------------
# lane-contract serve kernel (ISSUE 18): the serving traversal's node
# arrays parked in 64-lane HBM lines.  The real kernel stacks
# [T, ni_pad] with ni_pad lane-padded (serve/model.py) and DMAs whole
# rows HBM->VMEM at grid step 0; the "obvious" memory saving of
# packing nodes at their true count breaks the minor-dim tiling proof
# on every forest DMA.  The lane-contract pass must flag it — the
# BENCH_r03 class wearing serving clothes.
# ---------------------------------------------------------------------
def _bad_serve_kernel() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from ...ops.pallas.serve_kernel import _HBM

        def kernel(sf_hbm, o_hbm, v, sem):
            cp = pltpu.make_async_copy(sf_hbm, v, sem)
            cp.start()
            cp.wait()
            cpo = pltpu.make_async_copy(v, o_hbm, sem)
            cpo.start()
            cpo.wait()

        # (trees, 64) i32 node lines: the seeded violation — the true
        # inner-node count kept un-padded instead of serve/model.py's
        # _pad_to_lane(ni_max, LANE)
        t, ni = 64, 64

        def fn(sf):
            return pl.pallas_call(
                kernel,
                in_specs=[pl.BlockSpec(memory_space=_HBM)],
                out_specs=pl.BlockSpec(memory_space=_HBM),
                out_shape=jax.ShapeDtypeStruct((t, ni), jnp.int32),
                scratch_shapes=[pltpu.VMEM((t, ni), jnp.int32),
                                pltpu.SemaphoreType.DMA],
            )(sf)

        return fn, (jax.ShapeDtypeStruct((t, ni), jnp.int32),)

    return FixtureBundle(entries=[_entry("fixture_bad_serve_kernel",
                                         "serve", builder)])


# ---------------------------------------------------------------------
# recompile audit: a shape-dependent constant baked into a jitted
# body — two batch sizes inside ONE serving bucket compile different
# programs, breaking the bucketed-batch contract
# ---------------------------------------------------------------------
def _bad_retrace() -> FixtureBundle:
    def builder():
        # the clean pin's builder with the seeded violation flipped
        # on: the TRUE row count is baked in as a trace-time python
        # constant, so the validity mask is a different const array
        # per batch size and every size in the bucket traces its own
        # program (one builder for pin + fixture — the pin guards the
        # very code the red team breaks)
        from ..passes.routing import bucket_pad_variants
        return bucket_pad_variants(bake_constant=True)

    return FixtureBundle(retrace_pins={"fixture-bad-retrace": builder})


# ---------------------------------------------------------------------
# batched multiclass red team (ISSUE 19), two seeded violations:
#
# 1. lane-contract: a "batched" K-grid grow kernel whose per-class
#    histogram slice is carried at 64 lanes — the tempting [K, ..., 64]
#    layout that halves the per-class slice to fit two classes per
#    register row.  Every ref is a real memref on chip; a 64-lane
#    minor is a masked half-VREG on every touch (LANE_MINOR_NOT_128).
# 2. routing matrix: a multiclass cell (k=multi) riding the physical
#    fast path that still trains serial-K (mcb=0) with NO named
#    mc_batch rule — the unjustified K-dispatch floor the routing
#    audit must reject (ROUTING_UNJUSTIFIED_FALLBACK).
# ---------------------------------------------------------------------
def _bad_mc_batch() -> FixtureBundle:
    def builder():
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        from ...ops.pallas.partition_kernel import _HBM

        k, f, b = 4, 16, 64   # 64-lane per-class slice: the violation

        def kernel(h_hbm, o_hbm, v, sem):
            i = pl.program_id(0)
            cp = pltpu.make_async_copy(h_hbm.at[i], v, sem)
            cp.start()
            cp.wait()
            cpo = pltpu.make_async_copy(v, o_hbm.at[i], sem)
            cpo.start()
            cpo.wait()

        def fn(h):
            return pl.pallas_call(
                kernel,
                grid=(k,),
                in_specs=[pl.BlockSpec(memory_space=_HBM)],
                out_specs=pl.BlockSpec(memory_space=_HBM),
                out_shape=jax.ShapeDtypeStruct((k, f, b), jnp.float32),
                scratch_shapes=[pltpu.VMEM((f, b), jnp.float32),
                                pltpu.SemaphoreType.DMA],
            )(h)

        return fn, (jax.ShapeDtypeStruct((k, f, b), jnp.float32),)

    key = ("learner=serial;shards=1;be=tpu;efb=0;u8=1;over=0;"
           "ew=0;fdiv=1;dp=0;cegb=0;cat=0;bag=0;lin=0;boost=gbdt;"
           "obj=other;k=multi;forced=0;mono=0;cegbc=0;phys=auto;"
           "stream=auto;part=permute;fused=1;scat=1;"
           "ob=0;pg=auto;mcb=auto;fixture=bad_mc_batch")
    cell = ("path=physical;scheme=permute;fused=1;merge=none;"
            "paged=0;mcb=0;why=-;merge_why=-;paged_why=-;"
            "mcb_why=-;"
            "prog=physical|permute|fused1|serial|shards1|none|"
            "dp0|cegb0|cat0|efb0|u81|paged0|mcb0")
    return FixtureBundle(
        entries=[_entry("fixture_bad_mc_batch", "hist", builder)],
        routing_cells=[(key, cell)])


# ---------------------------------------------------------------------
# dma-race page-schedule audit (ISSUE 15): a WRONG double-buffer
# schedule — the compute consumes each page right after issuing its
# transfer, without waiting (on chip: the kernels read a page buffer
# the host DMA engine is still filling).  The pass must fail it.
# ---------------------------------------------------------------------
def _bad_page() -> FixtureBundle:
    from ...ops import paged
    n_pages = 4
    events = []
    for p in range(n_pages):
        buf = p % 2
        events.append((paged.DMA_IN, p, buf))
        # the seeded bug: no DMA_WAIT — compute reads the in-flight page
        events.append((paged.COMPUTE, p, buf))
    return FixtureBundle(
        page_schedules=[("fixture_bad_page", events, n_pages)])


FIXTURES = {
    "bad_cat": _bad_cat,
    "bad_lane": _bad_lane,
    "bad_page": _bad_page,
    "bad_vmem": _bad_vmem,
    "bad_donation": _bad_donation,
    "bad_dma": _bad_dma,
    "bad_host": _bad_host,
    "bad_purity": _bad_purity,
    "bad_mc_batch": _bad_mc_batch,
    "bad_mesh": _bad_mesh,
    "bad_route": _bad_route,
    "bad_retrace": _bad_retrace,
    "bad_serve_kernel": _bad_serve_kernel,
    "efb_overwide": _efb_overwide,
    "comb_overwide": _comb_overwide,
}
