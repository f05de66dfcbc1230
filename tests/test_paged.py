"""Paged comb: larger-than-HBM training (ISSUE 15).

Pins the tentpole contracts off-chip:

* the double-buffered page schedule is clean under its own audit and
  the audit actually detects broken schedules (the dma-race pass's
  page-granularity rules);
* paged and unpaged training produce BYTE-IDENTICAL trees across the
  width x partition-scheme x fused x stream matrix, through the REAL
  scan/copyback kernels (LGBM_TPU_PART_INTERP=kernel);
* the engaged page geometry equals ``costmodel.page_schedule``'s plan;
* the routing model's paged dimension (engagement, named losses);
* ``LGBM_TPU_CKPT_AT_REFRESH=1`` kill+resume stays byte-identical and
  matches the reset-based cadence bit-for-bit.
"""
import os
import sys

import numpy as np
import pytest

# knobs any cell below may set; saved/restored around each fresh-import
# train (the tests/test_physical.py convention)
KNOBS = ("LGBM_TPU_PHYS", "LGBM_TPU_PART_INTERP", "LGBM_TPU_PARTITION",
         "LGBM_TPU_FUSED", "LGBM_TPU_STREAM",
         "LGBM_TPU_PAGED", "LGBM_TPU_PAGE_ROWS", "LGBM_TPU_HBM_LIMIT_GB",
         "LGBM_TPU_CKPT_DIR", "LGBM_TPU_CKPT_EVERY",
         "LGBM_TPU_CKPT_AT_REFRESH", "LGBM_TPU_CKPT_KEEP")


def _purge():
    for m in [k for k in list(sys.modules)
              if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]


def _train(env, n=1500, f=6, rounds=3, params=None):
    """Fresh-import train; returns (tree digests, routing_info,
    model_text, resumed_from, dataset geometry facts)."""
    saved = {k: os.environ.get(k) for k in set(KNOBS) | set(env)}
    for k in KNOBS:
        os.environ.pop(k, None)
    for k, v in env.items():
        os.environ[k] = v
    try:
        _purge()
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        y = (np.nan_to_num(x[:, 0])
             + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]) > 0).astype(
                 np.float32)
        p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        p.update(params or {})
        ds = lgb.Dataset(x, label=y, params={"max_bin": 255})
        bst = lgb.train(p, ds, num_boost_round=rounds)
        trees = [(int(t.num_leaves),
                  t.split_feature[:int(t.num_leaves) - 1].tolist(),
                  t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
                  np.asarray(t.leaf_value).tobytes())
                 for t in bst._models]
        dd = getattr(bst._inner, "dd", None)
        geo = (None if dd is None else
               {"n_pad": int(dd.n_pad),
                "phys_f_pad": int(dd.phys_f_pad),
                "phys_padded_bins": int(dd.phys_padded_bins)})
        return (trees, bst._inner.routing_info(),
                bst.model_to_string(), bst.resumed_from, geo)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


BASE_ENV = {"LGBM_TPU_PHYS": "interpret",
            "LGBM_TPU_PART_INTERP": "kernel"}


# ---------------------------------------------------------------------
# schedule + audit units (no jax)
# ---------------------------------------------------------------------
class TestSchedule:
    @pytest.mark.parametrize("n_pages", [1, 2, 3, 7, 10])
    @pytest.mark.parametrize("writeback", [False, True])
    def test_double_buffer_schedule_clean(self, n_pages, writeback):
        from lightgbm_tpu.ops.paged import (double_buffer_schedule,
                                            validate_schedule)
        ev = double_buffer_schedule(n_pages, writeback=writeback)
        assert validate_schedule(ev, n_pages) == []

    def test_schedule_overlaps_dma_with_compute(self):
        # the tentpole property: page p+1's transfer is IN FLIGHT when
        # page p computes
        from lightgbm_tpu.ops.paged import (COMPUTE, DMA_IN, DMA_WAIT,
                                            double_buffer_schedule)
        ev = double_buffer_schedule(4)
        for p in range(3):
            i_start = ev.index((DMA_IN, p + 1, (p + 1) % 2))
            i_comp = ev.index((COMPUTE, p, p % 2))
            i_wait = ev.index((DMA_WAIT, p + 1, (p + 1) % 2))
            assert i_start < i_comp < i_wait

    def test_audit_detects_missing_wait(self):
        from lightgbm_tpu.ops import paged
        ev = [e for e in paged.double_buffer_schedule(3)
              if e[0] != paged.DMA_WAIT]
        bad = paged.validate_schedule(ev, 3)
        assert any(v.startswith("PAGE_COMPUTE_NO_WAIT") for v in bad)
        assert any(v.startswith("PAGE_READ_INFLIGHT") for v in bad)

    def test_audit_detects_single_buffer_collapse(self):
        # both pages routed through buffer 0: the prefetch overwrites
        # the page being computed
        from lightgbm_tpu.ops import paged
        ev = [(paged.DMA_IN, 0, 0), (paged.DMA_WAIT, 0, 0),
              (paged.DMA_IN, 1, 0), (paged.COMPUTE, 0, 0),
              (paged.DMA_WAIT, 1, 0), (paged.COMPUTE, 1, 0)]
        bad = paged.validate_schedule(ev, 2)
        assert any(v.startswith("PAGE_READ_INFLIGHT") for v in bad)

    def test_audit_detects_serialized_dma(self):
        # wait immediately after start, compute after: correct but no
        # overlap — the ~29 s/tree of host DMA lands on the critical
        # path, which the audit flags
        from lightgbm_tpu.ops import paged
        ev = []
        for p in range(3):
            ev += [(paged.DMA_IN, p, p % 2), (paged.DMA_WAIT, p, p % 2),
                   (paged.COMPUTE, p, p % 2)]
        bad = paged.validate_schedule(ev, 3)
        assert any(v.startswith("PAGE_NO_OVERLAP") for v in bad)

    def test_audit_detects_writeback_race(self):
        # an inbound fill over a buffer whose write-back is still in
        # flight corrupts the host copy — the review-found race the
        # DMA_OUT_WAIT event exists to prevent
        from lightgbm_tpu.ops import paged
        ev = [e for e in paged.double_buffer_schedule(3, writeback=True)
              if e[0] != paged.DMA_OUT_WAIT]
        bad = paged.validate_schedule(ev, 3)
        assert any(v.startswith("PAGE_WRITEBACK_RACE") for v in bad)
        assert any(v.startswith("PAGE_WRITEBACK_UNDRAINED")
                   for v in bad)

    def test_audit_detects_missing_and_dup_pages(self):
        from lightgbm_tpu.ops import paged
        ev = [(paged.DMA_IN, 0, 0), (paged.DMA_WAIT, 0, 0),
              (paged.COMPUTE, 0, 0), (paged.COMPUTE, 0, 0)]
        bad = paged.validate_schedule(ev, 2)
        assert any(v.startswith("PAGE_MISSING") for v in bad)
        assert any(v.startswith("PAGE_DUP") for v in bad)

    def test_analyzer_dma_pass_covers_page_schedules(self):
        from lightgbm_tpu.analysis import run_analysis
        rep = run_analysis(passes=["dma-race"], strict=True)
        assert rep.failing() == [], [f.to_json() for f in rep.failing()]
        bad = run_analysis(passes=["dma-race"], fixtures=["bad_page"])
        hits = [f for f in bad.failing()
                if f.code.startswith("DMA_PAGE")]
        assert hits and all(f.fixture for f in hits)


# ---------------------------------------------------------------------
# PageStore round trip
# ---------------------------------------------------------------------
class TestPageStore:
    def test_window_round_trip_bit_exact(self):
        import jax.numpy as jnp

        from lightgbm_tpu.ops.grow import PHYS_ROW_SLACK
        from lightgbm_tpu.ops.paged import PageStore
        n_alloc = 3 * 1024 + PHYS_ROW_SLACK
        store = PageStore(n_alloc=n_alloc, C=128, rows_per_page=1024)
        assert store.n_pages == 3
        rng = np.random.default_rng(1)
        window = jnp.asarray(
            rng.normal(size=(n_alloc, 128)).astype(np.float32))
        ref = np.asarray(window)
        store.flush_window(window)
        out = np.asarray(store.fetch_window())
        assert np.array_equal(out, ref)

    def test_fetch_before_build_raises(self):
        from lightgbm_tpu.ops.grow import PHYS_ROW_SLACK
        from lightgbm_tpu.ops.paged import PageStore
        store = PageStore(n_alloc=1024 + PHYS_ROW_SLACK, C=128,
                          rows_per_page=512)
        with pytest.raises(RuntimeError):
            store.fetch_window()

    def test_plan_pages_refuses_unpaged_shape(self):
        from lightgbm_tpu.ops.paged import plan_pages
        with pytest.raises(ValueError):
            plan_pages(rows=4096, f_pad=16, padded_bins=256,
                       num_leaves=31, stream=True)


# ---------------------------------------------------------------------
# byte-identical trees: the acceptance matrix
# ---------------------------------------------------------------------
# (env, feature columns): 130 columns make a comb line of two 128-lane
# planes, so a page is the same line range of both
PARITY_CELLS = {
    "stream_permute_fused": ({}, 6),
    "stream_permute_unfused": ({"LGBM_TPU_FUSED": "0"}, 6),
    "stream_matmul_fused": ({"LGBM_TPU_PARTITION": "matmul"}, 6),
    "stream_two_plane_permute_fused": ({}, 130),
    "physical_permute_fused": ({"LGBM_TPU_STREAM": "0"}, 6),
    "physical_two_plane_permute_fused": ({"LGBM_TPU_STREAM": "0"}, 130),
}


class TestPagedParity:
    @pytest.mark.parametrize("cell", sorted(PARITY_CELLS))
    def test_paged_trees_byte_identical(self, cell):
        cell_env, f = PARITY_CELLS[cell]
        env = dict(BASE_ENV, **cell_env)
        t_ref, info_ref, _, _, _ = _train(env, f=f)
        assert not info_ref["paged"]
        t_pg, info_pg, _, _, _ = _train(
            dict(env, LGBM_TPU_PAGED="1", LGBM_TPU_PAGE_ROWS="512"), f=f)
        assert info_pg["paged"], info_pg
        assert info_pg["page_plan"]["n_pages"] >= 2
        assert info_pg["page_plan"]["engaged"]["C"] \
            == 128 * (1 + (f > 6)), "cell is vacuous"
        assert t_ref == t_pg, (
            f"{cell}: paged trees diverged from the unpaged run")

    def test_paged_l2_objective_byte_identical(self):
        # regression: the page plan must price the ENGAGED stream
        # kind's layout (l2 carries two more constant columns than
        # binary) — gbdt threads objective_kind into plan_pages
        env = dict(BASE_ENV)
        p = {"objective": "regression", "num_leaves": 7,
             "verbosity": -1}
        t_ref, info_ref, _, _, _ = _train(env, params=p)
        assert info_ref["path"] == "stream"
        t_pg, info_pg, _, _, _ = _train(
            dict(env, LGBM_TPU_PAGED="1", LGBM_TPU_PAGE_ROWS="512"),
            params=p)
        assert info_pg["paged"]
        assert t_ref == t_pg

    def test_over_budget_engages_paging_automatically(self):
        # a small HBM budget makes the footprint model say over-budget
        # (the unpaged comb+scratch alone exceed it at 32k rows): the
        # auto default must page with the PLANNER's geometry and still
        # match the big-budget (unpaged) run byte-for-byte — the
        # ISSUE-15 acceptance shape, scaled to CI (the interpret path
        # without kernel depth keeps the 32k-row matrix fast)
        env = {"LGBM_TPU_PHYS": "interpret"}
        t_ref, info_ref, _, _, _ = _train(env, n=32000, rounds=2)
        assert not info_ref["paged"]
        t_pg, info_pg, _, _, geo = _train(
            dict(env, LGBM_TPU_HBM_LIMIT_GB="0.012"), n=32000,
            rounds=2)
        assert info_pg["paged"], info_pg
        assert info_pg["page_plan"]["n_pages"] >= 2
        assert t_ref == t_pg
        # the engaged geometry equals the planner's plan over the SAME
        # shape facts (the runtime snapshot carries them)
        from lightgbm_tpu.obs.costmodel import page_schedule
        ref = page_schedule(
            rows=geo["n_pad"], f_pad=geo["phys_f_pad"],
            padded_bins=geo["phys_padded_bins"], num_leaves=7,
            stream=True, fused=True,
            limit_bytes=int(0.012 * 2**30))
        assert ref["paged"] and ref["fits"]
        plan = info_pg["page_plan"]
        eng = plan["engaged"]
        for k in ("rows_per_page", "n_pages", "page_bytes",
                  "page_lines", "C"):
            assert eng[k] == ref[k], (k, eng[k], ref[k])
        assert plan["rows_per_page"] == ref["rows_per_page"]
        assert plan["dma_bytes_per_tree"] == ref["dma_bytes_per_tree"]
        # the double-buffered sweeps actually ran (fetch+flush per
        # tree, plus the init flush)
        assert eng["stats"]["cycles"] >= 2
        assert eng["stats"]["dma_bytes"] > 0


# ---------------------------------------------------------------------
# routing: the paged dimension
# ---------------------------------------------------------------------
class TestPagedRouting:
    def test_decide_paged_cells(self):
        from lightgbm_tpu.ops.routing import RouteInputs, decide
        tpu = dict(backend="tpu")
        d = decide(RouteInputs(over_budget=True, **tpu))
        assert d.paged and d.path == "stream"
        assert "paged1" in d.program_key
        d = decide(RouteInputs(**tpu))
        assert not d.paged and "paged0" in d.program_key
        d = decide(RouteInputs(paged_env="1", **tpu))
        assert d.paged
        d = decide(RouteInputs(over_budget=True, paged_env="0", **tpu))
        assert not d.paged and d.paged_reasons == ("paged_env_off",)
        d = decide(RouteInputs(over_budget=True, learner="data",
                               n_shards=8, **tpu))
        assert not d.paged
        assert d.paged_reasons == ("paged_mesh_unwired",)
        d = decide(RouteInputs(over_budget=True, gpu_use_dp=True, **tpu))
        assert not d.paged and d.path == "row_order"
        assert d.paged_reasons == ("paged_requires_physical",)

    def test_over_budget_priced_at_engaged_geometry(self, monkeypatch):
        # review regression: over_budget must be priced at the FINAL
        # engaged fused geometry, not the provisional decision's
        # defaults — a budget landing between the fused and unfused
        # peaks of a fused-unsupported shape would otherwise make
        # routing promise a paging the planner then refuses (crash)
        from lightgbm_tpu.obs import costmodel
        from lightgbm_tpu.ops import routing
        from lightgbm_tpu.ops.paged import plan_pages
        from lightgbm_tpu.ops.pallas.fused_split import fused_supported
        fp_shape, b = 10, 64
        assert not fused_supported(fp_shape, b, 128)
        kw = dict(rows=102400, f_pad=fp_shape, padded_bins=b,
                  num_leaves=31, stream=True, stream_kind="l2")
        peak_f = costmodel.grow_footprint(fused=True, **kw)["peak_bytes"]
        peak_u = costmodel.grow_footprint(fused=False,
                                          **kw)["peak_bytes"]
        assert peak_u < peak_f
        band = (peak_u + peak_f) // 2
        monkeypatch.setenv("LGBM_TPU_HBM_LIMIT_GB", str(band / 2**30))
        r = routing.resolve_layout(
            routing.RouteInputs(backend="tpu"), f_pad=fp_shape,
            padded_bins=b, rows=102400, num_leaves=31)
        d = routing.decide(r)
        assert not r.fused_ok and not d.fused
        # the engaged (unfused) peak fits the band limit: consistently
        # resident — no paged promise the planner would refuse
        assert not r.over_budget and not d.paged
        # and just below the unfused peak the promise IS honorable
        monkeypatch.setenv("LGBM_TPU_HBM_LIMIT_GB",
                           str((peak_u - 1) / 2**30))
        r2 = routing.resolve_layout(
            routing.RouteInputs(backend="tpu"), f_pad=fp_shape,
            padded_bins=b, rows=102400, num_leaves=31)
        d2 = routing.decide(r2)
        assert r2.over_budget and d2.paged
        plan = plan_pages(rows=102400, f_pad=fp_shape, padded_bins=b,
                          num_leaves=31,
                          stream=d2.path == "stream", fused=d2.fused,
                          stream_kind="l2")
        assert plan["paged"] and plan["fits"]

    def test_paged_digest_distinct(self):
        from lightgbm_tpu.ops.routing import RouteInputs, decide
        a = decide(RouteInputs(backend="tpu"))
        b = decide(RouteInputs(backend="tpu", paged_env="1"))
        assert a.digest() != b.digest()

    def test_matrix_has_paged_cells_all_justified(self):
        import json
        from lightgbm_tpu.analysis.passes.routing import matrix_path
        doc = json.load(open(matrix_path()))
        assert doc["summary"]["paged_cells"] > 0
        # every over-budget resident cell names its paged loss (the
        # ROUTING_PAGED_UNJUSTIFIED audit holds over the checked-in
        # golden)
        from lightgbm_tpu.ops.routing import decode_cell
        for key, enc in doc["cells"].items():
            kf = dict(part.partition("=")[::2]
                      for part in key.split(";"))
            c = decode_cell(enc)
            if (kf.get("ob") == "1"
                    and c["path"] in ("physical", "stream")
                    and not c["paged"]):
                assert c["paged_reasons"], key

    def test_paged_mesh_loss_is_loud(self):
        from lightgbm_tpu.obs.counters import events
        from lightgbm_tpu.ops.routing import (RouteInputs, decide,
                                              report_fallbacks)
        import lightgbm_tpu.obs as obs
        obs.reset_run()
        d = decide(RouteInputs(over_budget=True, learner="data",
                               n_shards=8, backend="tpu"))
        report_fallbacks(d)
        assert events.totals().get(
            "routing_fallback_paged_mesh_unwired", 0) == 1


# ---------------------------------------------------------------------
# LGBM_TPU_CKPT_AT_REFRESH=1 (satellite): in-place re-anchor at the
# stream refresh boundary, byte-identical like the reset cadence
# ---------------------------------------------------------------------
CKPT_PARAMS = {"num_leaves": 15, "learning_rate": 0.2, "max_bin": 31,
               "min_data_in_leaf": 5, "feature_fraction": 0.8}


class TestCkptAtRefresh:
    def _env(self, d, **extra):
        return dict({"LGBM_TPU_PHYS": "interpret",
                     "LGBM_TPU_CKPT_DIR": str(d),
                     "LGBM_TPU_CKPT_EVERY": "2"}, **extra)

    def test_inplace_matches_reset_cadence(self, tmp_path):
        _, info, ref, _, _ = _train(self._env(tmp_path / "a"),
                                    n=600, rounds=6,
                                    params=CKPT_PARAMS)
        assert info["path"] == "stream"
        _, _, txt, _, _ = _train(
            self._env(tmp_path / "b", LGBM_TPU_CKPT_AT_REFRESH="1"),
            n=600, rounds=6, params=CKPT_PARAMS)
        assert txt == ref

    def test_kill_resume_byte_identical(self, tmp_path):
        envr = self._env(tmp_path / "ref", LGBM_TPU_CKPT_AT_REFRESH="1")
        _, _, ref, _, _ = _train(envr, n=600, rounds=6,
                                 params=CKPT_PARAMS)
        envk = self._env(tmp_path / "kill",
                         LGBM_TPU_CKPT_AT_REFRESH="1")
        _train(envk, n=600, rounds=3, params=CKPT_PARAMS)
        _, _, txt, resumed, _ = _train(envk, n=600, rounds=6,
                                       params=CKPT_PARAMS)
        assert resumed == 2
        assert txt == ref

    def test_kill_resume_paged_at_refresh(self, tmp_path):
        # the composed cell: paged comb x in-place re-anchor (the
        # checkpoint layer re-anchors the PER-PAGE permutations too)
        extra = {"LGBM_TPU_CKPT_AT_REFRESH": "1", "LGBM_TPU_PAGED": "1",
                 "LGBM_TPU_PAGE_ROWS": "512"}
        envr = self._env(tmp_path / "ref", **extra)
        _, info, ref, _, _ = _train(envr, n=600, rounds=6,
                                    params=CKPT_PARAMS)
        assert info["paged"]
        envk = self._env(tmp_path / "kill", **extra)
        _train(envk, n=600, rounds=3, params=CKPT_PARAMS)
        _, _, txt, resumed, _ = _train(envk, n=600, rounds=6,
                                       params=CKPT_PARAMS)
        assert resumed == 2
        assert txt == ref

    def test_at_refresh_off_stream_falls_back_to_reset(self, tmp_path):
        # non-stream physical: reanchor_inplace returns False and the
        # reset path keeps the existing contract — the knob must be a
        # no-op there, not a divergence
        _, info, ref, _, _ = _train(
            self._env(tmp_path / "a", LGBM_TPU_STREAM="0"), n=600,
            rounds=4, params=CKPT_PARAMS)
        assert info["path"] == "physical"
        _, info2, txt, _, _ = _train(
            self._env(tmp_path / "b", LGBM_TPU_STREAM="0",
                      LGBM_TPU_CKPT_AT_REFRESH="1"), n=600, rounds=4,
            params=CKPT_PARAMS)
        assert info2["path"] == "physical"
        assert txt == ref


@pytest.mark.parametrize("C", [128, 256])
def test_page_store_round_trips_a_plane_major_window(C):
    """A page is the same line range of every 128-lane plane of the
    comb (ops/pallas/layout.py): flushing a window to host pages and
    fetching it back is the identity at one plane and at two."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import PHYS_ROW_SLACK
    from lightgbm_tpu.ops.paged import PageStore
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    n_alloc = 4096 + PHYS_ROW_SLACK
    store = PageStore(n_alloc=n_alloc, C=C, rows_per_page=1024)
    shape = comb_shape(n_alloc, C)
    window = jnp.arange(shape[0] * shape[1], dtype=jnp.float32).reshape(shape)
    store.flush_window(window)
    assert store._pages[0].shape[-2:] == (store.page_lines, 128)
    np.testing.assert_array_equal(np.asarray(store.fetch_window()),
                                  np.asarray(window))
