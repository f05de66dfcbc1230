"""HBM flight recorder (ISSUE 9): footprint-model equality against
the real grow jaxprs (planes x stream x mesh), the hbm-budget /
donation-audit pass, the page-schedule planner acceptance pair, the
``obs mem`` CLI pins + failure modes, the memory diff gate, and the
phase-granular residency sampling.
"""
import io
import json
import os
import contextlib

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import costmodel, mem
from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.obs import tracer as obs_tracer

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _all_avals(traced):
    """Every aval in a traced program: top-level in/out vars plus every
    nested eqn's vars — where the loop-carried histogram arena lives."""
    out = []

    def walk(j):
        inner = getattr(j, "jaxpr", j)
        for v in (list(inner.invars) + list(inner.outvars)):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)
        for eqn in inner.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    out.append(aval)
            for p in eqn.params.values():
                subs = ([p] if hasattr(p, "eqns") or hasattr(p, "jaxpr")
                        else (p if isinstance(p, (tuple, list)) else []))
                for sub in subs:
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub)

    walk(traced)
    return out


def _aval_bytes(aval):
    return int(np.prod(aval.shape, dtype=np.int64)
               * np.dtype(aval.dtype).itemsize) if aval.shape \
        else np.dtype(aval.dtype).itemsize


def _build_grow(n, f, b, L, *, stream=False):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams
    kw = {}
    if stream:
        kw["stream"] = {"kind": "binary", "sigmoid": 1.0, "count": n}
    return make_grow_fn(SplitHyperParams(min_data_in_leaf=2),
                        num_leaves=L, padded_bins=b,
                        physical_bins=_sds((n, f), jnp.uint8), **kw)


def _grow_args(gp, n, f, b, stream):
    """The operands of ``gp._grow_p``, as shapes."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    args = [_sds(comb_shape(gp._n_alloc, gp._C), jnp.float32)] * 2
    args += [_sds((1,) if stream else (n,), jnp.float32)] * 3
    args += [_sds((f,), jnp.float32), _sds((f,), jnp.int32),
             _sds((f,), jnp.bool_), _sds((f,), jnp.bool_),
             _sds((), jnp.int32), _sds((), jnp.float32)]
    if stream and gp._root0_fn is not None:
        args.append(_sds((f, b, 2), jnp.float32))
    return args


# ---------------------------------------------------------------------
# footprint-model equality vs the real grow jaxprs (the acceptance
# criterion: exact bytes, one AND two comb planes, stream on/off, mesh)
# ---------------------------------------------------------------------
_F_PLANES = {1: 16, 2: 144, 16: 2000}   # feature columns -> comb planes


@pytest.mark.parametrize("planes", [1, 2, 16])
@pytest.mark.parametrize("stream", [False, True])
def test_footprint_equals_grow_jaxpr(planes, stream):
    import jax
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    n, f, b, L = 4096, _F_PLANES[planes], 32, 8
    gp = _build_grow(n, f, b, L, stream=stream)
    fp = costmodel.grow_footprint(
        rows=n, f_pad=f, padded_bins=b, num_leaves=L,
        stream=stream, fused=gp.fused, rows_padded=True)
    geo = fp["geometry"]
    assert geo["n_alloc"] == gp._n_alloc
    assert geo["C"] == gp._C == 128 * planes

    carry = stream and gp._root0_fn is not None
    traced = jax.make_jaxpr(gp._grow_p)(*_grow_args(gp, n, f, b, stream))
    invars = [v.aval for v in traced.jaxpr.invars]

    # comb / scratch: EXACT equality, shape (the model's lines x lanes,
    # stored plane-major) and bytes
    for idx, name in ((0, "comb"), (1, "scratch")):
        buf = fp["buffers"][name]
        assert comb_shape(*buf["shape"]) == tuple(invars[idx].shape), \
            name
        assert buf["bytes"] == _aval_bytes(invars[idx]), name
    if not stream:
        for idx, name in ((2, "grad"), (3, "hess"), (4, "inbag")):
            buf = fp["buffers"][name]
            assert buf["shape"] == tuple(invars[idx].shape), name
            assert buf["bytes"] == _aval_bytes(invars[idx]) \
                * buf["count"], name
    if carry:
        buf = fp["buffers"]["root_hist"]
        assert buf["shape"] == tuple(invars[-1].shape)
        assert buf["bytes"] == _aval_bytes(invars[-1])

    # histogram arena + leaf_id: found INSIDE the jaxpr with the exact
    # model shape (the [L, F, 4, B] chan4 pool)
    all_avals = {(tuple(a.shape), str(a.dtype))
                 for a in _all_avals(traced)}
    pool = fp["buffers"]["hist_pool"]
    assert (pool["shape"], "float32") in all_avals, \
        f"pool {pool['shape']} not in the traced grow program"
    lid = fp["buffers"]["leaf_id"]
    assert (lid["shape"], "int32") in all_avals


def test_footprint_equals_batched_mc_grow_jaxpr():
    """ISSUE-19 cell of the matrix: the batched multiclass grow is a
    scan-over-K INSIDE one jitted program, so the footprint model must
    price what that program actually allocates — grad/hess/leaf_id and
    the tree arrays stack to [K, ...], but the histogram arena stays
    the SINGLE [L, F, 4, B] pool (the scan body allocates it once and
    XLA reuses the buffer across classes; there is no [K, L, F, 4, B]
    arena to price)."""
    import jax
    import jax.numpy as jnp
    n, f, b, L, k = 4096, 16, 32, 8, 4
    gp = _build_grow(n, f, b, L)
    fp = costmodel.grow_footprint(
        rows=n, f_pad=f, padded_bins=b, num_leaves=L,
        stream=False, fused=gp.fused, rows_padded=True,
        num_class=k, mc_batched=True)
    geo = fp["geometry"]
    assert geo["num_class"] == k and geo["mc_batched"] is True
    assert geo["n_alloc"] == gp._n_alloc and geo["C"] == gp._C

    n_phys = gp._n_alloc
    args = [_sds((n_phys, gp._C), jnp.float32),
            _sds((n_phys, gp._C), jnp.float32),
            _sds((k, n), jnp.float32), _sds((k, n), jnp.float32),
            _sds((n,), jnp.float32), _sds((k, f), jnp.float32),
            _sds((f,), jnp.int32), _sds((f,), jnp.bool_),
            _sds((f,), jnp.bool_), _sds((k,), jnp.int32)]
    traced = jax.make_jaxpr(gp.batched_fn())(*args)
    invars = [v.aval for v in traced.jaxpr.invars]

    # comb/scratch thread the scan carry: ONE allocation, no [K] axis
    for idx, name in ((0, "comb"), (1, "scratch")):
        buf = fp["buffers"][name]
        assert buf["shape"] == tuple(invars[idx].shape), name
        assert buf["bytes"] == _aval_bytes(invars[idx]), name
    # the scanned xs: [K, n] grad/hess are the model's count=K vectors
    for idx, name in ((2, "grad"), (3, "hess")):
        buf = fp["buffers"][name]
        assert buf["count"] == k, name
        assert buf["bytes"] == _aval_bytes(invars[idx]), name

    all_avals = {(tuple(a.shape), str(a.dtype))
                 for a in _all_avals(traced)}
    # the stacked leaf_id output: [K, n] int32, priced count=K
    lid = fp["buffers"]["leaf_id"]
    assert lid["count"] == k
    assert lid["bytes"] == k * n * 4
    assert ((k, n), "int32") in all_avals
    # ONE histogram arena at the serial shape — and NO K-stacked arena
    pool = fp["buffers"]["hist_pool"]
    assert pool["shape"] == (L, f, 4, b)
    assert (pool["shape"], "float32") in all_avals, \
        f"pool {pool['shape']} not in the traced batched program"
    assert ((k,) + pool["shape"], "float32") not in all_avals, \
        "the traced scan materialised a [K, L, F, 4, B] arena — the " \
        "footprint model (and the VMEM story) assume it never exists"
    # tree arrays stack: K x the serial tree bytes
    ta = fp["buffers"]["tree_arrays"]
    serial = costmodel.grow_footprint(
        rows=n, f_pad=f, padded_bins=b, num_leaves=L, stream=False,
        fused=gp.fused, rows_padded=True)
    assert ta["count"] == k
    assert ta["bytes"] == k * serial["buffers"]["tree_arrays"]["bytes"]
    # the batch only ever ADDS footprint terms vs serial-K
    assert fp["peak_bytes"] > serial["peak_bytes"]


def _eqns(traced):
    """Every equation of a traced program, nested jaxprs included."""
    inner = getattr(traced, "jaxpr", traced)
    for eqn in inner.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else [p]):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _eqns(sub)


def _row_sized_lookups(traced, n):
    """(gathers of n results out of a table of at most 256 entries,
    scatters into an n-sized operand) of a traced program."""
    size = lambda v: int(np.prod(v.aval.shape, dtype=np.int64))
    gathers = [e for e in _eqns(traced) if e.primitive.name == "gather"
               and size(e.invars[0]) <= 256 and size(e.outvars[0]) == n]
    scatters = [e for e in _eqns(traced)
                if e.primitive.name.startswith("scatter")
                and size(e.invars[0]) == n]
    return gathers, scatters


def _stream_grow_jaxpr(n, f, b, L):
    import jax
    gp = _build_grow(n, f, b, L, stream=True)
    return jax.make_jaxpr(gp._grow_p)(*_grow_args(gp, n, f, b, True))


def _pull_score_jaxpr(n, f, b, L):
    import jax
    gp = _build_grow(n, f, b, L, stream=True)
    return jax.make_jaxpr(gp._pull_score_fn)(
        _grow_args(gp, n, f, b, True)[0])


def _tail_jaxpr(n, L):
    """The boosting loop's score-update program, traced on the
    operands its first call gets."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.models.gbdt import GBDT
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    seen = {}
    finish = GBDT._finish_tree_async

    def spy(self, ta, leaf_id, kidx, init_score):
        seen.setdefault("args", (self, ta, leaf_id))
        return finish(self, ta, leaf_id, kidx, init_score)

    GBDT._finish_tree_async = spy
    try:
        lgb.train({"objective": "binary", "num_leaves": L,
                   "verbosity": -1},
                  lgb.Dataset(x, label=(x[:, 0] > 0).astype(np.float32)),
                  num_boost_round=1)
    finally:
        GBDT._finish_tree_async = finish
    inner, ta, leaf_id = seen["args"]
    assert not inner.valid_sets and leaf_id.shape == (n,)
    return jax.make_jaxpr(inner._async_tail_fn())(
        ta, leaf_id, inner.train_score[0], (), (), jnp.float32(0.1),
        jnp.float32(0.0))


@pytest.mark.parametrize("program", ["stream_grow", "tail", "pull_score"])
@pytest.mark.parametrize("select_max", [256, 0])
def test_hand_off_reads_no_leaf_table_by_gather(program, select_max,
                                                monkeypatch):
    """ISSUE 33: after the last split the stream grow program and the
    score-update tail take per-row leaf ids / values by compares
    against the leaf-sized table (ops/leaf_lookup.py): no gather of n
    results out of a table of at most 256 entries.  ISSUE 39: and the
    stream grow program holds NO n-sized scatter - the un-permute to
    row order is ``pull_score``'s, the one such scatter, run when the
    train score is read.  With ``SELECT_MAX = 0`` the helper falls back
    to the lookups this replaced, which the same census must find:
    that is what shows it can see them."""
    from lightgbm_tpu.ops import leaf_lookup
    monkeypatch.setattr(leaf_lookup, "SELECT_MAX", select_max)
    n, L = 4096, 8
    traced = {"stream_grow": lambda: _stream_grow_jaxpr(n, 16, 32, L),
              "pull_score": lambda: _pull_score_jaxpr(n, 16, 32, L),
              "tail": lambda: _tail_jaxpr(n, L)}[program]()
    gathers, scatters = _row_sized_lookups(traced, n)
    if program == "pull_score":
        assert not gathers and len(scatters) == 1
        return
    if select_max == 0:
        # leaf_of_pos by repeat (a scatter-add into zeros[n], a gather)
        # and lv_row, or the tail's leaf_value[leaf_id]
        assert len(gathers) == (2 if program == "stream_grow" else 1)
        assert len(scatters) == (1 if program == "stream_grow" else 0)
        return
    assert not gathers, [str(e) for e in gathers]
    assert not scatters, [str(e) for e in scatters]


def test_page_schedule_scales_with_num_class():
    """K multiplies the per-class persistent vectors (grad/hess/score);
    the planner must see that — a budget the K=1 shape fits under must
    page (adapt) or refuse once K=8 multiplies the footprint over it.
    Paged multiclass trains serial-K (the mc_batch_paged routing
    rule), so the schedule itself prices mc_batched=False."""
    kw = dict(rows=4_000_000, f_pad=28, padded_bins=256,
              num_leaves=255, stream=False, fused=False, n_shards=1)
    p1 = costmodel.page_schedule(num_class=1, **kw)
    p8 = costmodel.page_schedule(num_class=8, **kw)
    assert p8["unpaged_peak_bytes"] > p1["unpaged_peak_bytes"]
    # a budget strictly between the two peaks: K=1 fits resident, K=8
    # must adapt by paging
    limit = (p1["unpaged_peak_bytes"] + p8["unpaged_peak_bytes"]) // 2
    f1 = costmodel.page_schedule(num_class=1, limit_bytes=limit, **kw)
    f8 = costmodel.page_schedule(num_class=8, limit_bytes=limit, **kw)
    assert f1["paged"] is False and f1["fits"] is True
    assert f8["paged"] is True
    assert f8["fits"] is True and f8["rows_per_page"] > 0
    # and a budget below even the fixed overhead REFUSES with the
    # structured error instead of planning an impossible schedule
    tiny = costmodel.page_schedule(num_class=8, limit_bytes=1 << 20,
                                   **kw)
    assert tiny["paged"] is True and tiny["fits"] is False
    assert "error" in tiny


@pytest.mark.parametrize("form", ["bundled", "unbundled"])
def test_footprint_equals_grow_jaxpr_efb(form):
    """EFB cells of the matrix: the comb and the histogram pool price at
    the width the engaged form allocates - the bundle columns where the
    bundles stay in the comb (ISSUE 36, the plain finder), the
    UNBUNDLED logical width under the unbundling ingest (ISSUE 12; here
    extra_trees) - while the persistent bin matrix prices at the
    bundled storage width either way.  Builds the SAME synthetic cells
    the analyzer registers (`grow_physical_efb`,
    `grow_physical_efb_unbundled`), so the parity guarantee covers the
    geometry the lane/vmem/hbm passes price."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.entries import efb_demo_geometry
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams

    bundle, geo = efb_demo_geometry()
    n, f_log, f_phys = geo["n"], geo["f_log"], geo["f_phys"]
    L = geo["num_leaves"]
    f_comb, b_comb = ((f_phys, geo["padded_bins"]) if form == "bundled"
                      else (f_log, geo["padded_bins_log"]))
    gp = make_grow_fn(SplitHyperParams(min_data_in_leaf=2,
                                       use_extra_trees=form == "unbundled"),
                      num_leaves=L, padded_bins=geo["padded_bins"],
                      padded_bins_log=geo["padded_bins_log"], bundle=bundle,
                      physical_bins=_sds((n, f_phys), jnp.uint8))
    assert gp._f_pad == f_comb
    fp = costmodel.grow_footprint(
        rows=n, f_pad=f_comb, padded_bins=b_comb, num_leaves=L,
        rows_padded=True, bins_cols=f_phys, bins_itemsize=1)
    geo = fp["geometry"]
    assert geo["n_alloc"] == gp._n_alloc
    assert geo["C"] == gp._C
    assert geo["bins_cols"] == f_phys
    assert fp["buffers"]["bins"]["shape"] == (n, f_phys)
    assert fp["buffers"]["bins"]["bytes"] == n * f_phys

    n_phys = gp._n_alloc
    args = [_sds((n_phys, gp._C), jnp.float32),
            _sds((n_phys, gp._C), jnp.float32)]
    args += [_sds((n,), jnp.float32)] * 3
    args += [_sds((f_log,), jnp.float32), _sds((f_log,), jnp.int32),
             _sds((f_log,), jnp.bool_), _sds((f_log,), jnp.bool_),
             _sds((), jnp.int32), _sds((), jnp.float32)]
    traced = jax.make_jaxpr(gp._grow_p)(*args)
    invars = [v.aval for v in traced.jaxpr.invars]
    for idx, name in ((0, "comb"), (1, "scratch")):
        buf = fp["buffers"][name]
        assert buf["shape"] == tuple(invars[idx].shape), name
        assert buf["bytes"] == _aval_bytes(invars[idx]), name
    # the histogram arena: [L, 8 bundle columns, 4, 48] or the LOGICAL
    # [L, 16, 4, 32] pool
    all_avals = {(tuple(a.shape), str(a.dtype))
                 for a in _all_avals(traced)}
    pool = fp["buffers"]["hist_pool"]
    assert pool["shape"] == (L, f_comb, 4, b_comb)
    assert (pool["shape"], "float32") in all_avals, \
        f"pool {pool['shape']} not in the traced EFB grow program"


def test_footprint_matches_mesh_pieces():
    """Mesh cell of the matrix: the per-shard layout constants the
    data-parallel grower receives (MeshPhysicalPieces) equal the model
    geometry at n_shards=2, at one AND two comb planes."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams
    n_global, b, L = 8192, 32, 8
    for planes, f in _F_PLANES.items():
        n_local = n_global // 2
        pieces = make_grow_fn(
            SplitHyperParams(min_data_in_leaf=2), num_leaves=L,
            padded_bins=b, axis_name="data",
            physical_bins=_sds((n_local, f), jnp.uint8))
        fp = costmodel.grow_footprint(
            rows=n_global, f_pad=f, padded_bins=b, num_leaves=L,
            n_shards=2, rows_padded=True)
        geo = fp["geometry"]
        assert geo["n_local"] == pieces.n_local == n_local
        assert geo["n_alloc"] == pieces.n_alloc
        assert geo["C"] == pieces.C == 128 * planes
        comb = fp["buffers"]["comb"]
        assert comb["shape"] == (pieces.n_alloc, pieces.C)


def test_footprint_msltr_comb_bytes_and_peak():
    """The model at the shape ``msltr-train-2m`` runs - 2,270,296 rows,
    137 features padded to 144, a line of two planes: 2,271,232 rows
    (whole 2,048-row blocks: ``grow.PHYS_ROW_PAD``) + 6,144 lines of
    slack = 2,277,376 lines x 1,024 B = 2.33e9 bytes a comb-sized
    array, comb + scratch 4.66e9 (PERF.md section 4)."""
    fp = costmodel.grow_footprint(rows=2_270_296, f_pad=144,
                                  padded_bins=256, num_leaves=255)
    geo = fp["geometry"]
    assert (geo["n_alloc"], geo["C"]) == (2_277_376, 256)
    comb = fp["buffers"]["comb"]
    assert comb["shape"] == (2_277_376, 256)
    assert comb["bytes"] == 2_277_376 * 1_024 == 2_332_033_024
    assert fp["buffers"]["scratch"]["bytes"] == comb["bytes"]
    # the peak is the max phase live-set
    assert fp["peak_bytes"] == max(fp["phase_live"].values())
    assert fp["peak_phase"] in fp["phase_live"]


def test_hbm_budget_knobs(monkeypatch):
    phys, gen = costmodel.hbm_generation_bytes("v5e")
    assert phys == 16 << 30 and gen == "v5e"
    # v5e usable budget is exactly the 15.75 GiB the chip reports
    assert costmodel.hbm_limit_bytes("v5e") == int(15.75 * 2**30)
    monkeypatch.setenv(costmodel.HBM_LIMIT_ENV, "2.5")
    assert costmodel.hbm_limit_bytes() == int(2.5 * 2**30)
    monkeypatch.delenv(costmodel.HBM_LIMIT_ENV)
    monkeypatch.setenv(costmodel.HBM_GEN_ENV, "v5p")
    assert costmodel.hbm_limit_bytes() \
        == int((96 << 30) * (1 - costmodel.HBM_RESERVE_FRACTION))
    monkeypatch.setenv(costmodel.HBM_GEN_ENV, "v99")
    with pytest.raises(ValueError, match="unknown TPU generation"):
        costmodel.hbm_generation_bytes()


# ---------------------------------------------------------------------
# page-schedule planner: the ROADMAP-5 acceptance pair
# ---------------------------------------------------------------------
def test_page_schedule_100m_acceptance():
    from lightgbm_tpu.analysis.passes import hbm as hbm_pass
    rows, f_pad = 100_000_000, 28
    # unpaged: over budget, flagged by the pass
    flagged = hbm_pass.check_geometry(rows, f_pad, 256)
    assert any(f.code == "HBM_GEOMETRY_OVER_BUDGET" for f in flagged)
    # the planner emits a schedule that fits...
    plan = costmodel.page_schedule(rows=rows, f_pad=f_pad,
                                   padded_bins=256, num_leaves=255)
    assert plan["paged"] and plan["fits"]
    assert plan["resident_bytes"] <= plan["limit_bytes"]
    assert plan["rows_per_page"] % 512 == 0
    assert plan["n_pages"] >= 2
    assert plan["dma_bytes_per_tree"] > 0
    assert plan["overhead_s_per_tree"] > 0
    # ...and the hbm-budget pass ACCEPTS the paged geometry
    ok = hbm_pass.check_geometry(rows, f_pad, 256,
                                 plan["rows_per_page"])
    assert ok == []
    # a deliberately oversized page is rejected
    too_big = hbm_pass.check_geometry(rows, f_pad, 256,
                                      plan["rows_per_page"] * 8)
    assert any(f.code == "HBM_PAGED_OVER_BUDGET" for f in too_big)


def test_page_schedule_small_shape_unpaged():
    plan = costmodel.page_schedule(rows=100_000, f_pad=28,
                                   padded_bins=256, num_leaves=255)
    assert plan["paged"] is False and plan["fits"] is True


def test_page_schedule_prices_stream_kind_layout():
    # the streaming layouts carry per-objective constant columns
    # (binary 13 extras, l2 15): at f_pad=114 that straddles the
    # 128-lane boundary, so a plan priced at the wrong kind would
    # fail make_grow_fn's geometry check instead of training
    kw = dict(rows=512 * 64, f_pad=114, padded_bins=256, num_leaves=31,
              stream=True, rows_per_page=512 * 8)
    plan_b = costmodel.page_schedule(stream_kind="binary", **kw)
    plan_l = costmodel.page_schedule(stream_kind="l2", **kw)
    assert plan_b["C"] == 128 and plan_l["C"] == 256
    fp = costmodel.grow_footprint(
        rows=512 * 64, f_pad=114, padded_bins=256, num_leaves=31,
        stream=True, stream_kind="l2")
    assert plan_l["C"] == fp["geometry"]["C"]


def test_page_schedule_force_pages_a_fitting_shape():
    # LGBM_TPU_PAGED=1 semantics: the plan must exist even when the
    # footprint fits the budget (the CI tiny-budget forced-paged leg)
    plan = costmodel.page_schedule(rows=100_000, f_pad=28,
                                   padded_bins=256, num_leaves=255,
                                   force=True)
    assert plan["paged"] and plan["fits"]
    assert plan["rows_per_page"] % 512 == 0
    # an explicit rows_per_page pages too, without force
    plan2 = costmodel.page_schedule(rows=100_000, f_pad=28,
                                    padded_bins=256, num_leaves=255,
                                    rows_per_page=512 * 16)
    assert plan2["paged"] and plan2["n_pages"] >= 2


# ---------------------------------------------------------------------
# paged live-sets vs the REAL per-page programs (ISSUE 15): the page
# buffer shapes in the PageStore's jitted window update/extract must
# equal the planner's page geometry byte-for-byte, and the engaged
# grow program must be the unpaged one (grow-paged-off purity pin's
# buffer-level counterpart)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("planes", [1, 2])
def test_paged_page_buffers_match_plan(planes):
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.paged import PageStore
    from lightgbm_tpu.ops.pallas.layout import (comb_operand_shape,
                                                comb_shape)
    n, f, b, L = 8192, _F_PLANES[planes], 32, 8
    rpp = 2048
    gp = _build_grow(n, f, b, L, stream=True)
    plan = costmodel.page_schedule(
        rows=n, f_pad=f, padded_bins=b, num_leaves=L,
        stream=True, rows_per_page=rpp)
    assert plan["paged"]
    store = PageStore(n_alloc=gp._n_alloc, C=gp._C,
                      rows_per_page=rpp)
    assert store.C == 128 * planes
    window = comb_shape(store.n_alloc, store.C)
    page = comb_operand_shape(store.page_lines, store.C)
    # engaged geometry == plan geometry
    assert store.page_lines == plan["page_lines"]
    assert store.n_pages == plan["n_pages"]
    assert plan["page_bytes"] == store.page_lines * store.C * 4
    assert plan["C"] == store.C and plan["n_alloc"] == store.n_alloc
    # the REAL paged jaxprs: window update consumes exactly one page
    # buffer of page_lines lines (a line range of every plane) + the
    # plane-major window; extract produces exactly one page buffer
    upd = jax.make_jaxpr(store._update_fn())(
        _sds(window, jnp.float32), _sds(page, jnp.float32),
        _sds((), jnp.int32), _sds((), jnp.int32))
    page_bytes = [
        _aval_bytes(a) for a in _all_avals(upd)
        if tuple(a.shape) == page and a.dtype == jnp.float32]
    assert page_bytes and all(bb == plan["page_bytes"]
                              for bb in page_bytes)
    window_avals = [a for a in _all_avals(upd)
                    if tuple(a.shape) == window
                    and a.dtype == jnp.float32]
    fp = costmodel.grow_footprint(
        rows=n, f_pad=f, padded_bins=b, num_leaves=L,
        stream=True, fused=gp.fused, rows_padded=True)
    assert window_avals and all(
        _aval_bytes(a) == fp["buffers"]["comb"]["bytes"]
        for a in window_avals)
    ext = jax.make_jaxpr(store._extract_fn())(
        _sds(window, jnp.float32), _sds((), jnp.int32))
    out_aval = ext.jaxpr.outvars[0].aval
    assert tuple(out_aval.shape) == page
    assert _aval_bytes(out_aval) == plan["page_bytes"]


# ---------------------------------------------------------------------
# hbm-budget pass: donation audit + residency
# ---------------------------------------------------------------------
def test_donation_audit_detects_dropped_donation():
    from lightgbm_tpu.analysis import run_analysis
    rep = run_analysis(passes=["hbm-budget"], fixtures=["bad_donation"])
    hits = [f for f in rep.failing() if f.code == "DONATION_DROPPED"]
    assert hits, "seeded dropped donation was not flagged"
    assert all(f.fixture for f in hits)
    assert "fixture_bad_donation" in hits[0].where


def test_real_grow_entries_donations_hold():
    """The real grow/stream entrypoints' declared donations all alias
    in the lowered program (the ISSUE-9 satellite fix: the fused-root
    carry is donated too)."""
    from lightgbm_tpu.analysis import run_analysis
    from lightgbm_tpu.analysis import registry
    registry.collect()
    assert registry.KERNELS["grow_physical"].donate == (0, 1)
    assert 11 in registry.KERNELS["grow_stream"].donate
    rep = run_analysis(passes=["hbm-budget"], strict=True,
                       entry_filter={"grow_physical", "grow_stream"})
    assert rep.failing() == [], [f.to_json() for f in rep.failing()]


def test_lowered_arg_alignment_survives_pruning():
    """jit prunes unused args from the lowered signature; the audit
    must map surviving args back to ORIGINAL argnums (the grow_stream
    carry is original argnum 11 but lowered %arg7)."""
    from lightgbm_tpu.analysis import registry
    from lightgbm_tpu.analysis.passes.hbm import (
        entry_residency_bytes, parse_main_signature)
    registry.collect()
    entry = registry.KERNELS["grow_stream"]
    text, orig_args, kept = entry.lowered_info()
    lowered_args, results = parse_main_signature(text)
    assert len(lowered_args) < len(orig_args), \
        "pruning assumption gone — revisit the alignment test"
    _, aliased = entry_residency_bytes(text, orig_args, kept=kept)
    assert {0, 1, 11} <= aliased
    # the exact kept_var_idx mapping is available on this jax, and the
    # type-alignment fallback agrees with it on the real entries
    assert kept is not None and len(kept) == len(lowered_args)
    _, aliased_fb = entry_residency_bytes(text, orig_args, kept=None)
    assert aliased_fb == aliased


def test_phase_hbm_purity_pin_registered_and_holds():
    from lightgbm_tpu.analysis import registry
    from lightgbm_tpu.analysis.passes import purity
    registry.collect()
    assert "grow-phase-hbm" in registry.PURITY_PINS
    findings = purity.check_pin(
        "grow-phase-hbm", registry.PURITY_PINS["grow-phase-hbm"])
    assert findings == [], [f.message for f in findings]


# ---------------------------------------------------------------------
# phase-granular residency sampling end to end
# ---------------------------------------------------------------------
def test_phase_hbm_timeline_sampled(tmp_path):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(600, 6)).astype(np.float32)
    y = (x[:, 0] + rng.logistic(size=600) * 0.3 > 0).astype(np.float32)
    obs_tracer.enable(None)
    try:
        ds = lgb.Dataset(x, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params={"objective": "binary",
                                  "num_leaves": 5, "verbosity": -1,
                                  "max_bin": 31}, train_set=ds)
        obs_ledger.reset()
        for i in range(2):
            bst.update()
            obs_ledger.sample(i)
        rows = obs_ledger.iterations
        assert len(rows) == 2
        for row in rows:
            pb = row.get("hbm_phase_bytes")
            assert pb, "no per-phase residency watermark sampled"
            assert {"BeforeTrain", "Tree::grow",
                    "UpdateScore"} <= set(pb)
            assert all(v > 0 for v in pb.values())
        # the per-phase instants ride the trace too
        inst = [e for e in obs_tracer.events
                if e.get("name") == "hbm_live_bytes"]
        assert inst and all("phase" in e["args"] for e in inst)
    finally:
        obs_tracer.disable()
        obs_tracer.reset()
        from lightgbm_tpu.obs import reset_run
        reset_run()


# ---------------------------------------------------------------------
# obs mem CLI: pinned table, join verdicts, failure modes
# ---------------------------------------------------------------------
def _run_cli(argv):
    from lightgbm_tpu.obs.report import main
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    return rc, buf.getvalue()


def test_obs_mem_pinned_fixture_table():
    rec_path = os.path.join(DATA, "synthetic_mem_record.json")
    rc, out = _run_cli(["mem", rec_path])
    assert rc == 0
    expected = open(os.path.join(DATA,
                                 "synthetic_mem_expected.txt")).read()
    # the pinned fixture renders with its repo-relative path
    assert out.replace(rec_path,
                       "tests/data/synthetic_mem_record.json") \
        == expected, ("obs mem table drifted — regenerate with "
                      "python -m lightgbm_tpu.obs.mem if intended")


def test_obs_mem_join_flags_measured_over_predicted(tmp_path):
    rec = json.load(open(os.path.join(DATA,
                                      "synthetic_mem_record.json")))
    for row in rec["ledger"]["iterations"]:
        row["hbm_peak_bytes"] = 10**9     # 1 GB >> predicted ~46 MB
    p = tmp_path / "over.json"
    p.write_text(json.dumps(rec))
    rc, out = _run_cli(["mem", str(p)])
    assert rc == 1
    assert "FINDING" in out and "exceeds the" in out
    # and the embedded block records the same verdict
    block = mem.memory_block(rec)
    assert "finding" in block


def test_obs_mem_failure_modes(tmp_path):
    # legacy multichip artifact: clear message, exit 2
    legacy = tmp_path / "MULTICHIP_r99.json"
    legacy.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True,
                                  "skipped": False, "tail": "dryrun ok"}))
    rc, out = _run_cli(["mem", str(legacy)])
    assert rc == 2 and "legacy multichip" in out
    # truncated JSON: exit 2, no traceback
    p = tmp_path / "trunc.json"
    p.write_text('{"schema": "lightgbm_tpu/bench/v3", "met')
    rc, out = _run_cli(["mem", str(p)])
    assert rc == 2 and "Traceback" not in out
    # record without a shape block: exit 2 with guidance
    p2 = tmp_path / "noshape.json"
    p2.write_text(json.dumps({"schema": "lightgbm_tpu/bench/v2",
                              "metric": "m", "value": 1.0}))
    rc, out = _run_cli(["mem", str(p2)])
    assert rc == 2 and "shape" in out
    # --plan without geometry: usage error
    rc, out = _run_cli(["mem", "--plan"])
    assert rc == 2


def test_obs_mem_bad_hbm_limit_exits_cleanly(monkeypatch):
    """A non-positive LGBM_TPU_HBM_LIMIT_GB is a configuration error:
    exit 2 with a message, never a ZeroDivisionError traceback."""
    monkeypatch.setenv(costmodel.HBM_LIMIT_ENV, "0")
    with pytest.raises(ValueError, match="not a usable HBM budget"):
        costmodel.hbm_limit_bytes()
    rc, out = _run_cli(["mem",
                        os.path.join(DATA,
                                     "synthetic_mem_record.json")])
    assert rc == 2 and "Traceback" not in out
    assert "HBM" in out


def test_obs_mem_plan_cli():
    rc, out = _run_cli(["mem", "--plan", "--rows", "100000000",
                        "--features", "28"])
    assert rc == 0
    assert "rows/page:" in out and "fits" in out
    assert "host<->HBM DMA" in out


# ---------------------------------------------------------------------
# memory block in bench records + the diff gate
# ---------------------------------------------------------------------
def test_memory_block_shape():
    rec = json.load(open(os.path.join(DATA,
                                      "synthetic_mem_record.json")))
    block = mem.memory_block(rec)
    assert block["schema"] == "lightgbm_tpu/mem/v1"
    pred = block["predicted"]
    assert pred["peak_bytes"] == max(pred["phase_live"].values())
    assert pred["buffers"]["comb"] == pred["buffers"]["scratch"]
    meas = block["measured"]
    assert meas["live_peak_bytes"] == 42_000_000
    assert meas["alloc_peak_bytes"] == 47_000_000
    assert "finding" not in block


def test_diff_gates_memory_peaks(tmp_path):
    from lightgbm_tpu.obs.regress import diff_records
    base = json.load(open(os.path.join(DATA,
                                       "synthetic_mem_record.json")))
    cand = json.loads(json.dumps(base))
    f, _ = diff_records(base, cand)
    assert [x for x in f if x["kind"] == "memory"] == []
    for row in cand["ledger"]["iterations"]:
        row["hbm_live_bytes"] *= 2
        row["hbm_peak_bytes"] *= 2
    cand["memory"] = mem.memory_block(cand)
    # 2x peaks: flagged under the wall tolerance
    findings, incomparable = diff_records(base, cand)
    mems = [x for x in findings if x["kind"] == "memory"
            and x["status"] == "regression"]
    assert mems, findings
    # an UNMEASURED baseline must not produce memory findings
    base2 = json.loads(json.dumps(base))
    base2.pop("memory", None)
    for row in base2["ledger"]["iterations"]:
        row.pop("hbm_live_bytes", None)
        row.pop("hbm_peak_bytes", None)
    findings2, _ = diff_records(base2, cand)
    assert [x for x in findings2 if x["kind"] == "memory"] == []
    # ...but the residency series DISAPPEARING from a traced candidate
    # is the sampling silently breaking — fails the gate, like the
    # mesh-telemetry loss class
    findings3, _ = diff_records(base, base2)
    lost = [x for x in findings3 if x["kind"] == "memory"
            and x["status"] == "regression"]
    assert lost and "disengaged" in lost[0]["note"]
