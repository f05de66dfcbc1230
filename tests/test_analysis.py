"""Static kernel-contract analyzer (ISSUE 7): seeded-violation
fixtures per pass, clean baseline over the real kernels, allowlist
round trip, JSON schema pin, and the trace-only regression (the
analyzer never executes device code).
"""
import json

import pytest

from lightgbm_tpu.analysis import run_analysis
from lightgbm_tpu.analysis.allowlist import (ALLOWLIST_SCHEMA,
                                             AllowlistError)
from lightgbm_tpu.analysis.findings import SCHEMA
from lightgbm_tpu.analysis.run import PASS_NAMES


def _codes(report, failing_only=True):
    fs = report.failing() if failing_only else report.findings
    return {f.code for f in fs}


# ---------------------------------------------------------------------
# red-team fixture set: every pass must detect its seeded violation
# ---------------------------------------------------------------------
def test_fixture_lane_contract():
    rep = run_analysis(passes=["lane-contract"], fixtures=["bad_lane"])
    hits = [f for f in rep.failing() if f.code == "LANE_MINOR_NOT_128"]
    assert hits, "seeded 64-lane HBM memref was not flagged"
    assert all(f.fixture for f in hits)
    assert "fixture_bad_lane" in hits[0].where


def test_fixture_cat_bitset_lane_contract():
    """ISSUE 16 red team: per-node cat bitsets parked in HBM as
    16-lane i32 lines (instead of SMEM sel words) must trip the lane
    rule — the obvious 'optimization' of an HBM bitset side table is
    exactly the BENCH_r03 misaligned-DMA class."""
    rep = run_analysis(passes=["lane-contract"], fixtures=["bad_cat"])
    hits = [f for f in rep.failing() if f.code == "LANE_MINOR_NOT_128"]
    assert hits, "seeded misaligned HBM bitset memref was not flagged"
    assert all(f.fixture for f in hits)
    assert "fixture_bad_cat" in hits[0].where


def test_fixture_serve_kernel():
    """ISSUE 18 red team: the serving forest staged through HBM as
    64-lane node lines (a 'compact' per-tree layout) must trip the
    lane rule — the serve kernel's VMEM scratch DMA would stride
    misaligned on every tree."""
    rep = run_analysis(passes=["lane-contract"],
                       fixtures=["bad_serve_kernel"])
    hits = [f for f in rep.failing() if f.code == "LANE_MINOR_NOT_128"]
    assert hits, "seeded 64-lane serve-forest memref was not flagged"
    assert all(f.fixture for f in hits)
    assert "fixture_bad_serve_kernel" in hits[0].where


def test_fixture_vmem_budget():
    rep = run_analysis(passes=["vmem-budget"], fixtures=["bad_vmem"])
    hits = [f for f in rep.failing() if f.code == "VMEM_OVER_BUDGET"]
    assert hits, "seeded 128 MiB VMEM scratch was not flagged"
    assert all(f.fixture for f in hits)


def test_fixture_dma_race():
    rep = run_analysis(passes=["dma-race"], fixtures=["bad_dma"])
    codes = _codes(rep)
    assert "DMA_UNPAIRED_START" in codes
    assert "DMA_READ_BEFORE_WAIT" in codes
    assert "DMA_CURSOR_ALIAS" in codes
    # the seeded file is the only source of findings — the real
    # kernels' deferred-wait schedules stay clean
    assert all(f.fixture for f in rep.failing())


def test_fixture_host_sync():
    rep = run_analysis(passes=["host-sync"], fixtures=["bad_host"])
    codes = _codes(rep)
    assert "HOST_CALLBACK_IN_TRACE" in codes   # jaxpr-level
    assert "HOST_PULL_IN_KERNEL" in codes      # AST-level
    assert all(f.fixture for f in rep.failing())


def test_fixture_purity_pin():
    rep = run_analysis(passes=["purity-pin"], fixtures=["bad_purity"])
    hits = [f for f in rep.failing() if f.code == "PURITY_DIVERGES"]
    assert hits, "seeded leaky knob was not flagged"
    assert all(f.fixture for f in hits)


def test_fixture_mesh_precondition():
    # hist_scatter precondition: f_log % n_shards != 0 is reported at
    # ANALYSIS time (strict promotes the warning to failing)
    rep = run_analysis(passes=["lane-contract"], fixtures=["bad_mesh"],
                       strict=True)
    hits = [f for f in rep.failing()
            if f.code == "HIST_SCATTER_FALLBACK"]
    assert hits and "f_log=10" in hits[0].where


def test_mesh_cli_config_checked():
    from lightgbm_tpu.analysis.passes.lane import check_hist_scatter
    assert check_hist_scatter(16, 8)
    assert check_hist_scatter(10, 1)
    assert not check_hist_scatter(10, 8)
    rep = run_analysis(passes=["lane-contract"], mesh=[(10, 8)],
                       strict=True)
    assert "HIST_SCATTER_FALLBACK" in _codes(rep)
    rep_ok = run_analysis(passes=["lane-contract"], mesh=[(16, 8)],
                          strict=True)
    assert "HIST_SCATTER_FALLBACK" not in _codes(rep_ok, False)


def test_every_pass_has_a_fixture():
    """The red-team set covers the whole pipeline: every pass detects
    at least one seeded violation above — this pins the NAME mapping
    so a renamed pass cannot silently orphan its fixture."""
    from lightgbm_tpu.analysis.fixtures import FIXTURES
    assert set(FIXTURES) == {"bad_lane", "bad_vmem", "bad_donation",
                             "bad_dma", "bad_host", "bad_purity",
                             "bad_mesh", "bad_route", "bad_retrace",
                             "efb_overwide", "comb_overwide",
                             "bad_page", "bad_cat",
                             "bad_serve_kernel", "bad_mc_batch"}
    assert set(PASS_NAMES) == {"lane-contract", "vmem-budget",
                               "hbm-budget", "dma-race", "host-sync",
                               "purity-pin", "routing"}


def test_dma_start_inside_nested_scope_is_paired():
    """A copy constructed at kernel-body scope but start()-ed inside a
    pl.when closure must count toward its semaphore (the real kernels'
    idiom) — and an undrained one must surface as DMA_UNPAIRED_START,
    not as a 'dead code' DMA_NEVER_STARTED."""
    import textwrap

    from lightgbm_tpu.analysis.astutil import ModuleAnalysis
    src = textwrap.dedent("""
        def kernel(x_hbm, v, sem):
            cp = pltpu.make_async_copy(x_hbm.at[pl.ds(0, 8)], v, sem)

            @pl.when(blk == 0)
            def _go():
                cp.start()
    """)
    mod = ModuleAnalysis("nested_probe.py", source=src)
    (rep,) = mod.dma_reports()
    assert rep.sem_starts == {"sem": 1}
    assert rep.sem_waits == {}
    assert rep.never_started == []


def test_duplicate_kernel_body_names_all_scanned():
    """Two kernel wrappers sharing one simple name (``def kern`` in
    two builders of one module) must BOTH be scanned — a host pull in
    the second def cannot hide behind the first."""
    import textwrap

    from lightgbm_tpu.analysis.astutil import ModuleAnalysis
    src = textwrap.dedent("""
        def build1(x):
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:]
            return pl.pallas_call(kern, out_shape=s)(x)

        def build2(x):
            def kern(x_ref, o_ref):
                o_ref[:] = x_ref[:] * x_ref[0, 0].item()
            return pl.pallas_call(kern, out_shape=s)(x)
    """)
    mod = ModuleAnalysis("dup_probe.py", source=src)
    hits = mod.host_sync_hits()
    assert any(".item()" in what for _, _, what in hits), hits


# ---------------------------------------------------------------------
# clean baseline: the real kernels carry zero unallowlisted findings
# ---------------------------------------------------------------------
def test_clean_baseline_all_passes():
    rep = run_analysis(strict=True)
    assert rep.failing() == [], [f.to_json() for f in rep.failing()]
    # the run actually covered the registered surface
    assert len(rep.entries) >= 15
    assert set(rep.passes) == set(PASS_NAMES)


def test_registered_entries_trace_to_pallas_calls():
    """Coverage guard: the partition/hist/fused/stream registrations
    must actually expose pallas_call equations to the passes (an
    entry that silently traces to nothing would blind the analyzer)."""
    from lightgbm_tpu.analysis.jaxpr_tools import pallas_calls
    from lightgbm_tpu.analysis.run import build_context
    ctx = build_context()
    by_name = {e.name: e for e in ctx.entries}
    for name in ("partition_ss_permute", "hist_comb", "fused_split",
                 "stream_refresh", "apply_find"):
        calls = pallas_calls(by_name[name].trace())
        assert calls, f"{name} traced to no pallas_call"
        for c in calls:
            # every kernel-visible ref is classified
            assert all(r.space in ("smem", "vmem", "hbm", "semaphore")
                       for r in c.refs), (name, c.refs)


# ---------------------------------------------------------------------
# allowlist round trip
# ---------------------------------------------------------------------
def test_allowlist_roundtrip(tmp_path):
    path = tmp_path / "allow.json"
    path.write_text(json.dumps({
        "schema": ALLOWLIST_SCHEMA,
        "entries": [{"pass": "lane-contract",
                     "code": "HIST_SCATTER_FALLBACK",
                     "match": "f_log=10",
                     "justification": "test mesh is a known-slow "
                                      "probe shape"}],
    }))
    rep = run_analysis(passes=["lane-contract"], mesh=[(10, 8)],
                       allowlist_path=str(path), strict=True)
    hits = [f for f in rep.findings
            if f.code == "HIST_SCATTER_FALLBACK"]
    assert hits and hits[0].allowlisted
    assert "known-slow" in hits[0].justification
    assert rep.failing() == []
    # round trip: the emitted JSON carries the justification
    doc = rep.to_json()
    j = [f for f in doc["findings"]
         if f["code"] == "HIST_SCATTER_FALLBACK"][0]
    assert j["allowlisted"] is True and j["justification"]


def test_allowlist_requires_justification(tmp_path):
    path = tmp_path / "allow.json"
    path.write_text(json.dumps({
        "schema": ALLOWLIST_SCHEMA,
        "entries": [{"pass": "dma-race", "code": "DMA_UNPAIRED_START",
                     "match": "", "justification": "  "}],
    }))
    with pytest.raises(AllowlistError, match="justification"):
        run_analysis(passes=["dma-race"], allowlist_path=str(path))


def test_allowlist_unused_entry_is_flagged(tmp_path):
    path = tmp_path / "allow.json"
    path.write_text(json.dumps({
        "schema": ALLOWLIST_SCHEMA,
        "entries": [{"pass": "lane-contract",
                     "code": "LANE_MINOR_NOT_128",
                     "match": "no-such-entry",
                     "justification": "stale"}],
    }))
    rep = run_analysis(passes=["dma-race"], allowlist_path=str(path))
    assert "ALLOWLIST_UNUSED" in {f.code for f in rep.findings}


def test_allowlist_never_covers_fixtures(tmp_path):
    path = tmp_path / "allow.json"
    path.write_text(json.dumps({
        "schema": ALLOWLIST_SCHEMA,
        "entries": [{"pass": "vmem-budget", "code": "VMEM_OVER_BUDGET",
                     "match": "", "justification": "trying to blind "
                                                   "the red team"}],
    }))
    rep = run_analysis(passes=["vmem-budget"], fixtures=["bad_vmem"],
                       allowlist_path=str(path))
    hits = [f for f in rep.failing() if f.code == "VMEM_OVER_BUDGET"]
    assert hits, "fixture finding must not be allowlistable"


# ---------------------------------------------------------------------
# CLI: --json schema pin + exit codes
# ---------------------------------------------------------------------
def test_cli_json_schema_pin(capsys):
    from lightgbm_tpu.analysis.__main__ import main
    rc = main(["--json", "--passes", "dma-race"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["schema"] == SCHEMA == "lightgbm_tpu/analysis/v1"
    assert set(doc) == {"schema", "strict", "passes", "entries",
                        "findings", "summary"}
    assert set(doc["summary"]) == {"errors", "warnings", "allowlisted"}
    # finding rows carry the full pinned key set
    rc = main(["--json", "--passes", "dma-race", "--fixture",
               "bad_dma"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["findings"], "fixture run must emit findings"
    assert set(doc["findings"][0]) == {
        "pass_name", "code", "severity", "where", "message", "file",
        "line", "entry", "fixture", "allowlisted", "justification"}


def test_cli_exit_codes(capsys):
    from lightgbm_tpu.analysis.__main__ import main
    assert main(["--passes", "dma-race"]) == 0
    assert main(["--passes", "no-such-pass"]) == 2
    assert main(["--passes", "dma-race", "--fixture", "bad_dma"]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------
# purity pins: the registered invariants hold and live in ONE place
# ---------------------------------------------------------------------
def test_purity_pins_registered_and_hold():
    from lightgbm_tpu.analysis import registry
    registry.collect()
    assert {"grow-tracer-live", "grow-obs-lifecycle",
            "grow-numerics-off",
            "grow-pulse-off"} <= set(registry.PURITY_PINS)
    rep = run_analysis(passes=["purity-pin"], strict=True)
    assert rep.failing() == [], [f.to_json() for f in rep.failing()]


# ---------------------------------------------------------------------
# trace-only regression: the analyzer NEVER executes device code
# ---------------------------------------------------------------------
def test_analyzer_is_trace_only(monkeypatch):
    """Hard guarantee, not a convention: with XLA compilation disabled
    outright, the FULL pipeline (every pass, every registered entry,
    every purity pin) still completes — tracing abstract
    ShapeDtypeStruct args is all the analyzer ever does, which is why
    ci_tier1.sh leg 6 can gate kernel contracts on a CPU-only host."""
    from jax._src import compiler as jax_compiler

    def _boom(*a, **k):
        raise AssertionError(
            "analyzer attempted to compile/execute device code")

    monkeypatch.setattr(jax_compiler, "backend_compile", _boom)
    # force fresh traces: cached ClosedJaxprs from earlier tests would
    # weaken the guarantee
    from lightgbm_tpu.analysis import registry
    registry.collect()
    for e in registry.KERNELS.values():
        e._traced = None
    rep = run_analysis(strict=True)
    assert rep.failing() == []


def test_registered_mesh_configs_guard_padding():
    """analysis/entries.py registers the PADDED feature counts the
    data-parallel layout ships as mesh configs: all of them must pass
    the lane pass's hist_scatter precondition, so a padding regression
    becomes a HIST_SCATTER_FALLBACK finding in the clean --strict run
    (ISSUE 8 satellite)."""
    from lightgbm_tpu.analysis import registry
    from lightgbm_tpu.analysis.passes.lane import check_hist_scatter
    registry.collect()
    configs = [mc for mc in registry.MESH_CONFIGS if not mc.fixture]
    assert len(configs) >= 25, "padded mesh configs not registered"
    for mc in configs:
        assert check_hist_scatter(mc.f_log, mc.n_shards), (
            f"padded mesh config {mc} fails the reduce-scatter "
            "precondition")


def test_pack_permute_row_bookkeeping_is_lane_dense():
    """ISSUE 28's static pin on the waste itself.  A [R, 1] column is
    R / 8 vregs at 1 / 128 lane use: an operation on it costs what an
    operation on the whole [R, 128] block costs.  Before ISSUE 28 the
    permute compaction's unrolled rounds held 416 equations with such
    an operand (358 produced one) plus 124 that produced a full block,
    at R = 512, C = 128; the lane-dense bookkeeping has none, and
    produces a full block only at its edges (the split-column matvec's
    operand and the one MXU transpose's result slices).  Trace only."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.analysis.jaxpr_tools import walk_eqns
    from lightgbm_tpu.ops.pallas.partition_kernel3 import perm_pack_impl
    R, C = 512, 128
    pack = perm_pack_impl(R, C)

    def kern(sel_ref, x_ref, o_ref, n_ref):
        n_ref[0], n_ref[1], _ = pack(x_ref[...], sel_ref, sel_ref[1], 0,
                                     True, o_ref)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    jaxpr = jax.make_jaxpr(lambda sel, x: pl.pallas_call(
        kern, in_specs=[smem, vmem], out_specs=[vmem, smem],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((2,), jnp.int32)])(sel, x))(
        jnp.zeros((8,), jnp.int32), jnp.zeros((R, C), jnp.float32))

    def shapes(vs):
        return [tuple(getattr(getattr(v, "aval", None), "shape", ()))
                for v in vs]

    eqns = list(walk_eqns(jaxpr))
    columns = [e for e in eqns
               if (R, 1) in shapes(list(e.invars) + list(e.outvars))]
    blocks = [e for e in eqns
              if any(len(s) == 2 and s[0] == R for s in shapes(e.outvars))]
    assert len(eqns) > 1000, "the rounds are no longer unrolled here"
    assert not columns, [str(e.primitive) for e in columns][:8]
    assert len(blocks) <= 16, [str(e.primitive) for e in blocks]


@pytest.mark.parametrize("entry,scoped", [
    ("fused_split", True), ("partition_ss_permute", True),
    ("partition_ss_matmul", False)])
def test_vmem_budget_prices_run_scoped_buffers(entry, scoped):
    """The permute compaction allocates its routing word and staging
    blocks with ``pl.run_scoped`` inside the kernel (ISSUE 28): the
    vmem-budget pass has to price them as scratch, once (the two
    parity branches hold sibling scopes, not nested ones)."""
    import jax

    from lightgbm_tpu.analysis.jaxpr_tools import pallas_calls
    from lightgbm_tpu.analysis.passes.vmem import kernel_vmem_bytes
    from lightgbm_tpu.analysis.registry import collect
    from lightgbm_tpu.ops.pallas.partition_kernel2 import scan_block_rows
    fn, args = collect()[entry].builder()
    scan = pallas_calls(jax.make_jaxpr(fn)(*args))[0]
    # the registered builds take the block ops/grow.py would (ISSUE 37)
    rows = scan_block_rows(128, scheme="permute" if scoped else "matmul")
    block = rows * 128 * 4
    shapes = [r.shape for r in scan.vmem_refs(roles=("scratch",))]
    # the schedule's read and packed blocks, a slot pair each; the
    # compaction's scoped routing word and staging pair
    assert shapes.count((2, rows, 128)) == (3 if scoped else 2), shapes
    assert shapes.count((rows, 128)) == int(scoped), shapes
    blocked = sum(r.nbytes for r in scan.vmem_refs(roles=("in", "out")))
    assert kernel_vmem_bytes(scan) == (2 * blocked
                                       + block * (7 if scoped else 4))
