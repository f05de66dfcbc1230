"""Static kernel-contract analyzer (ISSUE 7 tentpole).

Every kernel lever since round 3 shipped with hand-grown runtime
guards — the 128-lane ``check_lane_width`` contract, the
tracer-live jaxpr-identity pin, the cost model's byte contracts —
because a bad BlockSpec or an unpaired DMA wait only surfaces as a
Mosaic error on the next chip run (the BENCH_r03 64-wide-slice
regression class).  This package is the compile-time equivalent of the
reference tree's invariant checks + CI sanitizers (SURVEY layers 0-1):
a pass pipeline that

* traces every REGISTERED grow/hist/partition/stream/fused kernel
  entrypoint to a jaxpr (``jax.make_jaxpr`` over abstract
  ``ShapeDtypeStruct`` args — shapes only, nothing executes, runs
  under ``JAX_PLATFORMS=cpu``) and walks it, and
* parses the ``ops/pallas/*.py`` kernel bodies via ``ast``,

then proves the kernel contracts BEFORE anything is dispatched:

``lane-contract``   every HBM-resident ref a kernel DMA-slices obeys
                    the 128-lane tiling rule of ``ops/pallas/layout.py``
                    (whole-program: the jaxpr's memref shapes are
                    checked, not just builders that remembered to call
                    ``check_lane_width``) + the hist_scatter
                    ``f_log % n_shards`` mesh precondition.
``vmem-budget``     per-kernel VMEM footprints (scratch shapes +
                    double-buffered BlockSpec blocks) against the
                    per-generation budget in ``obs/costmodel.py``.
``dma-race``        every ``make_async_copy``/``.start()`` paired with
                    a ``.wait()``; no reads of an in-flight copy's
                    destination; no SMEM cursor writes aliasing a
                    constructed-but-unstarted copy.
``host-sync``       no callback/host-pull primitives in the traced hot
                    path; no ``.item()``/``np.asarray`` in kernel
                    bodies (the ``profile_lib`` in-jit host-pull
                    methodology, enforced).
``purity-pin``      registered "knob off => jaxpr digest identical"
                    invariants (one home for the scattered per-test
                    pins).

CLI: ``python -m lightgbm_tpu.analysis [--strict] [--json]``.
Findings schema: ``lightgbm_tpu/analysis/v1`` (``findings.SCHEMA``).
Allowlist: ``analysis/allowlist.json`` — every entry NEEDS a
non-empty justification string.  Red-team fixtures (one seeded
violation per pass) live in ``analysis/fixtures/`` and are injected
with ``--fixture``; ci_tier1.sh leg 6 pins that a clean run exits 0
and that the lane/DMA fixtures each exit nonzero.
"""
from .findings import SCHEMA, Finding  # noqa: F401
from .run import PASS_NAMES, run_analysis  # noqa: F401
