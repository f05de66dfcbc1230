"""ISSUE 5 + 6: run ledger, cost model, perf-regression gate, and
device-time kernel attribution.

Covers the tentpole contracts and satellites:

* cost-model EXACTNESS: the partition / histogram byte predictions in
  ``obs/costmodel.py`` equal the kernel-contract bytes derived
  independently from the row-movement oracle (the same oracle
  ``tests/test_partition_perm.py`` pins), at one AND two comb planes, with
  the real kernels run through the Pallas interpreter;
* the regression gate: self-diff exact-clean, thresholded walls,
  exact counters, knob-mismatch refusal, median-of-k noise immunity,
  per-kernel device-time thresholds (ISSUE 6);
* report / diff CLI robustness on empty, truncated and mixed-schema
  inputs (no crashes, clear messages — S3);
* counter/event lifecycle: reset between ``lgb.train`` calls,
  warn-once caches reset with them, thread-safe recording (S2);
* the run ledger: per-iteration sampling via TraceCallback, mesh
  collective records with shard skew, bench/v3 provenance;
* xplane attribution (ISSUE 6): the pure-python decoder round-trips
  the in-repo encoder (and the TF proto when installed), the kernel
  classifier maps Mosaic/XLA names onto cost-model entries, the
  checked-in synthetic fixture drives decoder -> classifier -> phase
  join -> ``obs attr`` table deterministically, and the tracer's
  TraceAnnotation mirroring stays off without a capture.
"""
import json
import os
import threading

import numpy as np
import pytest

from lightgbm_tpu.obs import costmodel, regress, xattr
from lightgbm_tpu.obs.report import main as report_main

DATA_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data")


def _cur():
    """The CURRENT library generation.  test_fused.py / test_physical.py
    purge and re-import lightgbm_tpu mid-session; the state-bearing obs
    tests must bind to the generation that training will actually use
    (module-level bindings taken at collection time would assert on a
    dead generation's counter/ledger stores).  costmodel / regress /
    report above are pure functions — staleness is harmless there."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    return lgb, obs


@pytest.fixture(autouse=True)
def _clean_obs():
    """Each test starts and ends with the obs state off and empty."""
    lgb, obs = _cur()
    obs.tracer.disable()
    obs.tracer.close()
    obs.tracer.reset()
    obs.reset_run()
    yield
    lgb, obs = _cur()
    obs.tracer.disable()
    obs.tracer.close()
    obs.tracer.reset()
    obs.reset_run()


def _make_problem(n=1200, f=6, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] - 0.7 * x[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(
        np.float32)
    return x, y


# ---------------------------------------------------------------------
# cost model: kernel-contract exactness (S6)
# ---------------------------------------------------------------------
class TestCostModelExactness:
    """Predicted bytes must EQUAL the bytes the kernel contract moves,
    derived independently from the partition oracle: the scan reads
    and writes every row in the window once, the copyback re-reads and
    re-writes the right segment, and every logical row touch moves
    one line: C * itemsize bytes."""

    def test_partition_bytes_match_kernel_contract(self):
        import jax.numpy as jnp

        from lightgbm_tpu.ops.pallas.layout import LANE
        from lightgbm_tpu.ops.pallas.partition_kernel import (SEL_CNT,
                                                              SEL_S0)
        from lightgbm_tpu.ops.pallas.partition_kernel3 import \
            make_partition_perm

        R, C, SIZE = 128, 128, 1024
        N = SIZE + 3 * R + 4096
        rng = np.random.default_rng(0)
        rows = np.zeros((N, C), np.float32)
        rows[:, :8] = rng.integers(0, 64, size=(N, 8))
        pm = make_partition_perm(N, C, R=R, size=SIZE, interpret=True,
                                 interpret_kernel=True)
        for s0, cnt, feat, sbin in ((64, 900, 3, 20), (0, 1024, 0, 31),
                                    (7, 777, 7, 0), (300, 512, 1, 63)):
            sel = np.zeros((8,), np.int32)
            sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = (s0, cnt, feat,
                                                         sbin)
            sel[6] = -1
            _, _, nl = pm(jnp.asarray(sel), jnp.asarray(rows),
                          jnp.zeros((N, C), jnp.float32))
            nl = int(nl)
            # oracle agreement (ties this to the kernel contract the
            # partition tests pin)
            assert nl == int((rows[s0:s0 + cnt, feat] <= sbin).sum())
            # independent touch count: scan read + scan write of every
            # window row, copyback read + write of the right segment
            touches = cnt + cnt + 2 * (cnt - nl)
            contract_bytes = touches * LANE * 4
            assert costmodel.partition_split_bytes(
                cnt, nl) == contract_bytes

    def test_partition_bytes_two_plane_match_kernel_contract(self):
        """A comb line of two 128-lane planes: the same touches, each
        one row DMA a plane - twice the bytes of a one-plane line."""
        import jax.numpy as jnp

        from lightgbm_tpu.ops.pallas.layout import LANE, to_planes
        from lightgbm_tpu.ops.pallas.partition_kernel import (SEL_CNT,
                                                              SEL_S0)
        from lightgbm_tpu.ops.pallas.partition_kernel3 import \
            make_partition_perm

        R, C, SIZE = 128, 2 * LANE, 1024
        N = SIZE + 3 * R + 4096
        rng = np.random.default_rng(2)
        rows = np.zeros((N, C), np.float32)
        rows[:, 130:138] = rng.integers(0, 32, size=(N, 8))
        comb = to_planes(jnp.asarray(rows))
        part = make_partition_perm(N, C, R=R, size=SIZE, interpret=True,
                                   interpret_kernel=True)
        for s0, cnt, feat, sbin in ((64, 400, 133, 15),
                                    (65, 401, 133, 15),
                                    (17, 511, 137, 30)):
            sel = np.zeros((8,), np.int32)
            sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = (s0, cnt, feat,
                                                         sbin)
            sel[6] = -1
            _, _, nl = part(jnp.asarray(sel), comb, jnp.zeros_like(comb))
            nl = int(nl)
            assert nl == int((rows[s0:s0 + cnt, feat] <= sbin).sum())
            touches = 2 * cnt + 2 * (cnt - nl)
            contract_bytes = touches * C * 4
            assert costmodel.partition_split_bytes(
                cnt, nl, c_phys=C) == contract_bytes
            assert costmodel.partition_split_bytes(cnt, nl, c_phys=C) \
                == 2 * costmodel.partition_split_bytes(cnt, nl)

    def test_hist_bytes_match_kernel_contract(self):
        """The comb-direct histogram build reads each window row once
        and writes one [f_pad, padded_bins, 2] f32 histogram."""
        import jax.numpy as jnp

        from lightgbm_tpu.ops.pallas.hist_kernel2 import \
            build_histogram_comb
        from lightgbm_tpu.ops.pallas.layout import LANE

        n_alloc, f_pad, padded_bins, cnt = 2048 + 512, 16, 64, 900
        rng = np.random.default_rng(0)
        logical = np.zeros((n_alloc, LANE // 2), np.float32)
        logical[:, :f_pad] = rng.integers(0, 64, size=(n_alloc, f_pad))
        wide = np.zeros((n_alloc, LANE), np.float32)
        wide[:, :LANE // 2] = logical
        h1 = build_histogram_comb(
            jnp.asarray(wide), jnp.int32(0), jnp.int32(0),
            jnp.int32(cnt), f_pad=f_pad, size=2048,
            padded_bins=padded_bins, rows_per_block=256, interpret=True)
        # the histogram write the contract prices is exactly the kernel
        # output buffer
        assert costmodel.hist_out_bytes(f_pad, padded_bins) \
            == h1.size * h1.dtype.itemsize
        contract_bytes = cnt * LANE * 4 + h1.size * h1.dtype.itemsize
        assert costmodel.hist_build_bytes(
            cnt, f_pad=f_pad, padded_bins=padded_bins) == contract_bytes
        # fused = partition + the scan's ONE histogram write, nothing
        # else (the deleted child re-read is the fusion win) - and a
        # whole build of the smaller child where the scan was told the
        # other side
        nl = 400
        part = costmodel.partition_split_bytes(cnt, nl)
        hw = costmodel.hist_out_bytes(f_pad, padded_bins)
        kw = dict(f_pad=f_pad, padded_bins=padded_bins)
        assert costmodel.fused_split_bytes(cnt, nl, **kw) == part + hw
        assert costmodel.fused_split_bytes(cnt, nl, rehist_rows=nl, **kw) \
            == part + hw + costmodel.hist_build_bytes(nl, **kw)
        # ... which is the unfused pair's traffic plus the scan's write
        assert costmodel.fused_split_bytes(cnt, nl, rehist_rows=nl, **kw) \
            == costmodel.unfused_split_bytes(cnt, nl, **kw) + hw

    def test_cat_bitset_sel_bytes_match_kernel_contract(self):
        """ISSUE 16: the split descriptor's categorical bitset
        extension.  The words/bytes contracts must EQUAL the serving
        packer's buffer and the extended sel operand the interpreted
        kernel body actually decodes — and the kernel's left count
        must equal the membership oracle."""
        import jax.numpy as jnp

        from lightgbm_tpu.ops.pallas.layout import (CAT_BITSET_WORDS,
                                                    cat_bitset_fit)
        from lightgbm_tpu.ops.pallas.partition_kernel import (SEL_CAT,
                                                              SEL_CNT,
                                                              SEL_MEMBER,
                                                              SEL_NANB,
                                                              SEL_S0)
        from lightgbm_tpu.ops.pallas.partition_kernel3 import \
            make_partition_perm
        from lightgbm_tpu.ops.predict import _members_to_words

        # formula pins + the layout budget linkage (rule cat_overwide)
        assert costmodel.cat_bitset_words(256) == CAT_BITSET_WORDS
        assert cat_bitset_fit(32 * CAT_BITSET_WORDS)
        assert not cat_bitset_fit(32 * CAT_BITSET_WORDS + 1)
        assert costmodel.partition_sel_bytes() == 8 * 4
        with pytest.raises(ValueError):
            costmodel.cat_bitset_words(0)
        # the contract equals the serving packer's buffer, bin by bin
        for bins in (1, 31, 32, 33, 255, 256):
            members = np.zeros((1, bins), np.float32)
            members[0, ::3] = 1.0
            words = np.asarray(_members_to_words(jnp.asarray(members)))
            assert words.shape[1] == costmodel.cat_bitset_words(bins)
            assert words.nbytes == costmodel.cat_bitset_bytes(bins)
        # ... and the extended sel operand the kernel decodes
        b = 64
        R, C, SIZE = 128, 128, 1024
        N = SIZE + 3 * R + 4096
        rng = np.random.default_rng(5)
        rows = np.zeros((N, C), np.float32)
        rows[:, :8] = rng.integers(0, b, size=(N, 8))
        member = np.zeros((1, b), np.float32)
        member[0, rng.choice(b, size=20, replace=False)] = 1.0
        wsel = np.asarray(_members_to_words(jnp.asarray(member))[0])
        pm = make_partition_perm(N, C, R=R, size=SIZE, interpret=True,
                                 interpret_kernel=True)
        s0, cnt, feat = 64, 900, 3
        sel = np.zeros((SEL_MEMBER + wsel.size,), np.int32)
        sel[SEL_S0], sel[SEL_CNT], sel[2] = s0, cnt, feat
        sel[SEL_CAT] = 1
        sel[SEL_NANB] = -1
        sel[SEL_MEMBER:] = wsel
        assert sel.nbytes == costmodel.partition_sel_bytes(b, cat=True)
        _, _, nl = pm(jnp.asarray(sel), jnp.asarray(rows),
                      jnp.zeros((N, C), jnp.float32))
        cols = rows[s0:s0 + cnt, feat].astype(np.int64)
        assert int(nl) == int(member[0, cols].sum())

    def test_phase_model_and_roofline(self):
        rec = {
            "schema": "lightgbm_tpu/bench/v3",
            "counters": {"splits": 10, "rows_partitioned": 50_000,
                         "rows_histogrammed": 40_000,
                         "fused_splits": 10},
            "shape": {"rows": 10_000, "f_pad": 32, "padded_bins": 256,
                      "trees": 2, "stream": True},
            "knobs": {"partition": "permute", "fused": True},
            "phases": {"Tree::grow": {"total_s": 0.01, "count": 2,
                                      "mean_s": 0.005}},
        }
        model = costmodel.phase_model(rec)
        lrb = costmodel.logical_row_bytes()
        # the whole-loop counter totals land on Tree::grow, the one
        # span whose measured wall covers every split; the root-scale
        # sampled Split / ConstructHistogram probes are gone (ISSUE 27)
        assert "Split" not in model and "ConstructHistogram" not in model
        assert model["Tree::grow"]["bytes_lo"] >= 2 * 50_000 * lrb
        assert model["Tree::grow"]["bytes_hi"] >= 4 * 50_000 * lrb
        assert "Boosting" in model
        # only the partition copyback is data-dependent: bytes sits at
        # the midpoint of the lo/hi bounds
        m = model["Tree::grow"]
        assert m["bytes"] == pytest.approx(
            (m["bytes_lo"] + m["bytes_hi"]) / 2)
        # unfused vs fused, mirroring the per-split contracts: the
        # smaller-child re-read comes back (rows_hist 40k vs the 20k
        # root passes); one histogram write per split either way
        unfused = dict(rec, knobs={"partition": "permute",
                                   "fused": False})
        mu = costmodel.phase_model(unfused)
        hw = costmodel.hist_out_bytes(32, 256)
        assert mu["Tree::grow"]["bytes"] - model["Tree::grow"]["bytes"] \
            == (40_000 - 20_000) * lrb
        # ... less what the fused route read and wrote again for the
        # splits whose record named the larger child
        missed = dict(rec, counters=dict(rec["counters"],
                                         side_miss_splits=2,
                                         rows_rehistogrammed=3_000))
        assert costmodel.phase_model(missed)["Tree::grow"]["bytes"] \
            - model["Tree::grow"]["bytes"] == 3_000 * lrb + 2 * hw
        km = costmodel.kernel_model(missed)
        assert km["fused_split"]["bytes_lo"] == 2 * 50_000 * lrb + 10 * hw
        assert km["hist_build"]["bytes"] == 3_000 * lrb + 2 * hw
        rows = costmodel.roofline_table(rec, peak_bw_gbps=819,
                                        peak_tflops=197)
        grow = next(r for r in rows if r["phase"] == "Tree::grow")
        assert grow["gbps"] == pytest.approx(
            model["Tree::grow"]["bytes"] / 0.01 / 1e9)
        assert 0 < grow["bw_util"] < 1
        # untraced / pre-v3 records get a clear error, not a KeyError
        with pytest.raises(costmodel.RecordModelError,
                           match="TRACED bench/v3"):
            costmodel.phase_model({"schema": "lightgbm_tpu/bench/v2"})


# ---------------------------------------------------------------------
# regression gate (tentpole 3)
# ---------------------------------------------------------------------
def _rec(value=10.0, phases=None, counters_d=None, knobs=None,
         events_d=None, ledger_iters=None, schema="lightgbm_tpu/bench/v3"):
    rec = {"schema": schema, "metric": "iters", "value": value,
           "unit": "iters/sec", "backend": "cpu",
           "knobs": knobs or {"partition": "permute", "fused": True}}
    if phases is not None:
        rec["phases"] = phases
    if counters_d is not None:
        rec["counters"] = counters_d
    if events_d is not None:
        rec["events"] = events_d
    if ledger_iters is not None:
        rec["ledger"] = {"schema": "lightgbm_tpu/ledger/v1",
                         "iterations": ledger_iters}
    return rec


class TestDiff:
    def test_self_diff_clean(self):
        rec = _rec(phases={"Split": {"total_s": 1.0, "count": 5,
                                     "mean_s": 0.2}},
                   counters_d={"splits": 30.0})
        findings, incomp = regress.diff_records(rec, rec)
        assert not incomp
        assert regress.regressions(findings) == []

    def test_wall_regression_thresholded(self):
        a = _rec(phases={"Split": {"total_s": 1.0, "count": 5,
                                   "mean_s": 0.2}})
        # inside tolerance: not flagged
        b = _rec(phases={"Split": {"total_s": 1.1, "count": 5,
                                   "mean_s": 0.22}})
        f, _ = regress.diff_records(a, b, wall_tol=0.25)
        assert regress.regressions(f) == []
        # 2x: flagged
        c = _rec(phases={"Split": {"total_s": 2.0, "count": 5,
                                   "mean_s": 0.4}})
        f, _ = regress.diff_records(a, c, wall_tol=0.25)
        regs = regress.regressions(f)
        assert len(regs) == 1 and regs[0]["name"] == "Split"

    def test_tiny_walls_ignored(self):
        a = _rec(phases={"noise": {"total_s": 0.0004, "count": 1,
                                   "mean_s": 0.0004}})
        b = _rec(phases={"noise": {"total_s": 0.0009, "count": 1,
                                   "mean_s": 0.0009}})
        f, _ = regress.diff_records(a, b)
        assert regress.regressions(f) == []

    def test_metric_direction(self):
        # iters/sec: LOWER candidate is the regression
        f, _ = regress.diff_records(_rec(value=10.0), _rec(value=5.0))
        assert regress.regressions(f)
        f, _ = regress.diff_records(_rec(value=10.0), _rec(value=20.0))
        assert not regress.regressions(f)

    def test_counters_exact(self):
        a = _rec(counters_d={"splits": 30.0, "rows_partitioned": 900.0})
        b = _rec(counters_d={"splits": 30.0, "rows_partitioned": 901.0})
        f, _ = regress.diff_records(a, b)
        regs = regress.regressions(f)
        assert len(regs) == 1 and regs[0]["kind"] == "counter"
        # exact match passes even at tolerance 0
        f, _ = regress.diff_records(a, a, wall_tol=0.0)
        assert regress.regressions(f) == []

    def test_event_appearance_flagged(self):
        a = _rec()
        b = _rec(events_d={"hist_scatter_psum_fallback": 1})
        f, _ = regress.diff_records(a, b)
        regs = regress.regressions(f)
        assert len(regs) == 1 and regs[0]["kind"] == "event"

    def test_knob_mismatch_incomparable(self):
        a = _rec(knobs={"partition": "permute", "fused": True})
        b = _rec(knobs={"partition": "matmul", "fused": True})
        _, incomp = regress.diff_records(a, b)
        assert incomp and "partition" in incomp[0]
        _, incomp = regress.diff_records(a, b, check_knobs=False)
        assert not incomp

    def test_median_of_k_straggler_immunity(self):
        """One straggler iteration (GC pause / recompile) must not flag
        the trajectory; a median shift must.  Records mirror real
        traced bench/v3 artifacts: the summary ``phases`` block (whose
        TOTAL the straggler inflates 3x) rides alongside the ledger —
        the medians must supersede it, not merely accompany it."""
        def rec_of(iters):
            total = sum(r["phases"]["Split"] for r in iters)
            return _rec(
                ledger_iters=iters,
                phases={"Split": {"total_s": total,
                                  "count": len(iters),
                                  "mean_s": total / len(iters)}})

        base = [{"iteration": i, "wall_s": 0.1,
                 "phases": {"Split": 0.05}} for i in range(9)]
        strag = [dict(r, phases=dict(r["phases"])) for r in base]
        strag[4] = {"iteration": 4, "wall_s": 1.5,
                    "phases": {"Split": 1.0}}
        f, _ = regress.diff_records(rec_of(base), rec_of(strag))
        assert regress.regressions(f) == []
        shifted = [{"iteration": i, "wall_s": 0.25,
                    "phases": {"Split": 0.15}} for i in range(9)]
        f, _ = regress.diff_records(rec_of(base), rec_of(shifted))
        kinds = {r["kind"] for r in regress.regressions(f)}
        assert "trajectory" in kinds and "phase-median" in kinds

    def test_phase_presence_direction(self):
        """A phase APPEARING in the candidate (new slow path engaged)
        is the regression; a phase that disappeared is surfaced as
        'changed' but does not fail the gate."""
        a = _rec(phases={"Split": {"total_s": 1.0, "count": 1,
                                   "mean_s": 1.0}})
        b = _rec(phases={"Split": {"total_s": 1.0, "count": 1,
                                   "mean_s": 1.0},
                         "FallbackPath": {"total_s": 5.0, "count": 1,
                                          "mean_s": 5.0}})
        f, _ = regress.diff_records(a, b)
        regs = regress.regressions(f)
        assert [r["name"] for r in regs] == ["FallbackPath"]
        # reversed direction: phase eliminated -> no gate failure
        f, _ = regress.diff_records(b, a)
        assert regress.regressions(f) == []
        assert any(x["status"] == "changed" and x["name"] ==
                   "FallbackPath" for x in f)

    def test_v2_record_still_diffs(self):
        a = _rec(schema="lightgbm_tpu/bench/v2",
                 phases={"Split": {"total_s": 1.0, "count": 1,
                                   "mean_s": 1.0}})
        b = _rec(schema="lightgbm_tpu/bench/v3",
                 phases={"Split": {"total_s": 3.0, "count": 1,
                                   "mean_s": 3.0}})
        f, incomp = regress.diff_records(a, b)
        assert not incomp
        assert any(r["name"] == "Split"
                   for r in regress.regressions(f))


# ---------------------------------------------------------------------
# CLI robustness (S3)
# ---------------------------------------------------------------------
class TestCliRobustness:
    def test_report_empty_trace(self, tmp_path, capsys):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        assert report_main(["report", str(p)]) == 0
        out = capsys.readouterr().out
        assert "no metadata line" in out and "no events" in out

    def test_report_truncated_trace(self, tmp_path, capsys):
        p = tmp_path / "trunc.jsonl"
        p.write_text(json.dumps({"schema": "lightgbm_tpu/trace/v1",
                                 "ph": "M", "name": "trace_start"})
                     + "\n"
                     + json.dumps({"name": "Split", "ph": "X",
                                   "ts": 0, "dur": 5000.0, "pid": 1,
                                   "tid": 1, "args": {}}) + "\n"
                     + '{"name": "Boosting", "ph": "X", "ts": 1')
        assert report_main(["report", str(p)]) == 0
        out = capsys.readouterr().out
        assert "1 unparseable line(s) skipped" in out
        assert "Split" in out

    def test_report_missing_file(self, capsys):
        assert report_main(["report", "/nonexistent/x.jsonl"]) == 1
        assert "obs report:" in capsys.readouterr().out

    def test_bench_report_empty_and_garbage(self, tmp_path, capsys):
        empty = tmp_path / "empty.json"
        empty.write_text("")
        garbage = tmp_path / "trunc.json"
        garbage.write_text('{"schema": "lightgbm_tpu/bench/v3", "va')
        rc = report_main(["report", "--bench", str(empty),
                          str(garbage)])
        assert rc == 1
        out = capsys.readouterr().out
        assert "empty file" in out and "truncated" in out

    def test_bench_report_mixed_schema(self, tmp_path, capsys):
        v2 = tmp_path / "v2.json"
        v2.write_text(json.dumps({
            "schema": "lightgbm_tpu/bench/v2", "metric": "m",
            "value": 1.0, "unit": "iters/sec"}))
        v3 = tmp_path / "v3.json"
        v3.write_text(json.dumps({
            "schema": "lightgbm_tpu/bench/v3", "metric": "m",
            "value": 1.0, "unit": "iters/sec",
            "provenance": {"git_sha": "abc", "jax": "0.0",
                           "backend": "cpu", "device_kind": "cpu",
                           "n_devices": 1}}))
        unknown = tmp_path / "old.json"
        unknown.write_text(json.dumps({"metric": "m", "value": 2.0}))
        assert report_main(["report", "--bench", str(v2), str(v3),
                            str(unknown)]) == 0
        out = capsys.readouterr().out
        assert "no provenance block" in out          # v2 fallback
        assert "provenance: git abc" in out          # v3
        assert "unknown schema" in out               # pre-v2 warning

    def test_diff_cli_truncated_input(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        a.write_text(json.dumps(_rec()))
        b = tmp_path / "b.json"
        b.write_text('{"schema": ')
        assert report_main(["diff", str(a), str(b)]) == 2
        assert "truncated" in capsys.readouterr().out

    def test_diff_cli_clean_and_regression(self, tmp_path, capsys):
        a = tmp_path / "a.json"
        a.write_text(json.dumps(_rec(value=10.0)))
        b = tmp_path / "b.json"
        b.write_text(json.dumps(_rec(value=4.0)))
        assert report_main(["diff", str(a), str(a)]) == 0
        assert report_main(["diff", str(a), str(b)]) == 1
        out = capsys.readouterr().out
        assert "clean" in out and "regression(s) flagged" in out

    def test_roofline_header_matches_env_peaks(self, tmp_path, capsys,
                                               monkeypatch):
        """The printed roof must be the one utilization was computed
        against — flag, then env override, then the record's
        device_kind; an unknown kind is an error and a CPU record is
        "not measured", never a v5e default."""
        rec = {
            "schema": "lightgbm_tpu/bench/v3", "metric": "m",
            "value": 1.0, "unit": "iters/sec",
            "provenance": {"device_kind": "TPU v5 lite"},
            "counters": {"splits": 4, "rows_partitioned": 1000,
                         "rows_histogrammed": 800, "fused_splits": 4},
            "shape": {"rows": 500, "f_pad": 16, "padded_bins": 64,
                      "trees": 1},
            "knobs": {"partition": "permute", "fused": True},
            "phases": {"Split": {"total_s": 0.01, "count": 1,
                                 "mean_s": 0.01}}}
        p = tmp_path / "v3.json"

        def roofline(**over):
            p.write_text(json.dumps({**rec, **over}))
            rc = report_main(["report", "--bench", "--roofline", str(p)])
            return rc, capsys.readouterr().out

        rc, out = roofline()
        assert rc == 0 and "peak 819 GB/s, 197 TFLOPs" in out
        rc, out = roofline(provenance={"device_kind": "TPU v9"})
        assert rc == 1 and "device_kind 'TPU v9'" in out
        rc, out = roofline(provenance={"device_kind": "cpu",
                                       "backend": "cpu"})
        assert rc == 0 and "roofline: not measured" in out
        monkeypatch.setenv("LGBM_TPU_PEAK_BW_GBPS", "400")
        rc, out = roofline()
        assert rc == 0 and "peak 400 GB/s, 197 TFLOPs" in out

    def test_roofline_cli_on_untraced_record(self, tmp_path, capsys):
        p = tmp_path / "v2.json"
        p.write_text(json.dumps({
            "schema": "lightgbm_tpu/bench/v2", "metric": "m",
            "value": 1.0, "unit": "iters/sec"}))
        rc = report_main(["report", "--bench", "--roofline", str(p)])
        assert rc == 1
        assert "roofline:" in capsys.readouterr().out


# ---------------------------------------------------------------------
# lifecycle (S2)
# ---------------------------------------------------------------------
class TestLifecycle:
    def test_counters_reset_between_train_calls(self):
        lgb, obs = _cur()
        obs.tracer.enable(None)  # in-memory tracing: counters ride grow
        x, y = _make_problem()
        params = {"objective": "binary", "num_leaves": 6,
                  "verbosity": -1, "max_bin": 63}
        bst1 = lgb.train(params, lgb.Dataset(
            x, label=y, params={"max_bin": 63}), num_boost_round=2)
        bst1._inner._flush_pending()
        tot1 = obs.counters.totals()
        assert tot1["splits"] > 0
        n_tree1 = len(obs.counters.per_tree)
        bst2 = lgb.train(params, lgb.Dataset(
            x, label=y, params={"max_bin": 63}), num_boost_round=2)
        bst2._inner._flush_pending()
        # the second run's totals reflect ONLY its own trees — no
        # accumulation across lgb.train calls
        assert obs.counters.totals()["splits"] == tot1["splits"]
        assert len(obs.counters.per_tree) == n_tree1

    def test_events_and_warn_once_reset(self):
        _, obs = _cur()
        from lightgbm_tpu.ops import grow as grow_mod
        from lightgbm_tpu.ops import routing as routing_mod
        obs.events.record("stale_event")
        grow_mod._HIST_SCATTER_WARNED.add((28, 8))
        routing_mod._ROUTING_WARNED.add("gpu_use_dp")
        obs.reset_run()
        assert obs.events.totals() == {}
        assert not grow_mod._HIST_SCATTER_WARNED
        assert not routing_mod._ROUTING_WARNED

    def test_train_resets_events_and_warn_once(self):
        lgb, obs = _cur()
        from lightgbm_tpu.ops import grow as grow_mod
        obs.events.record("stale_event")
        grow_mod._HIST_SCATTER_WARNED.add((77, 8))
        x, y = _make_problem(n=400)
        lgb.train({"objective": "binary", "num_leaves": 4,
                   "verbosity": -1, "max_bin": 63},
                  lgb.Dataset(x, label=y, params={"max_bin": 63}),
                  num_boost_round=1)
        assert "stale_event" not in obs.events.totals()
        assert (77, 8) not in grow_mod._HIST_SCATTER_WARNED

    def test_thread_safe_recording(self):
        _, obs = _cur()
        n_threads, per_thread = 8, 200

        def hammer():
            for _ in range(per_thread):
                obs.events.record("e")
                obs.counters.record(np.asarray(
                    [1.0, 2.0, 3.0, 4.0]
                    + [0.0] * (len(obs.COUNTER_NAMES) - 4)))

        ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert obs.events.totals()["e"] == n_threads * per_thread
        assert obs.counters.totals()["splits"] == n_threads * per_thread
        assert len(obs.counters.per_tree) == n_threads * per_thread


# ---------------------------------------------------------------------
# run ledger (tentpole 1)
# ---------------------------------------------------------------------
class TestLedger:
    def test_trace_callback_samples_ledger(self):
        lgb, obs = _cur()
        obs.tracer.enable(None)  # pre-enabled: device counters ride grow
        x, y = _make_problem(n=600)
        cb = lgb.TraceCallback(logger=False)
        lgb.train({"objective": "binary", "num_leaves": 5,
                   "verbosity": -1, "max_bin": 63},
                  lgb.Dataset(x, label=y, params={"max_bin": 63}),
                  num_boost_round=3, callbacks=[cb])
        rows = obs.ledger.iterations
        assert [r["iteration"] for r in rows] == [0, 1, 2]
        # per-iteration counter DELTAS: each row carries its own tree's
        # splits, and the deltas sum to the cumulative totals
        assert sum(r["counters"].get("splits", 0) for r in rows) \
            == obs.counters.totals()["splits"] > 0
        assert rows[1]["wall_s"] is not None and rows[1]["wall_s"] > 0
        # phase deltas present once the tracer is live
        assert any("Tree::grow" in r.get("phases", {}) for r in rows)
        assert all(r.get("hbm_live_bytes", 0) > 0 for r in rows)
        rec = obs.ledger.to_record()
        assert rec["schema"] == "lightgbm_tpu/ledger/v1"
        assert len(rec["iterations"]) == 3
        json.dumps(rec)   # must be JSON-able as-is

    def test_mesh_collective_records(self):
        lgb, obs = _cur()
        obs.tracer.enable(None)
        x, y = _make_problem(n=1600, f=8)
        lgb.train({"objective": "binary", "num_leaves": 6,
                   "verbosity": -1, "max_bin": 63,
                   "tree_learner": "data"},
                  lgb.Dataset(x, label=y, params={"max_bin": 63}),
                  num_boost_round=2)
        colls = obs.ledger.collectives
        assert len(colls) >= 2    # one per grow dispatch
        c = colls[0]
        assert c["name"].startswith("DataParallelGrower::")
        assert c["bytes_moved"] > 0 and c["shards"] == 8
        # shard skew: per-shard in-bag rows (no bagging: max == min and
        # the 8 shards cover all padded rows)
        assert c["skew_max"] >= c["skew_min"] > 0
        assert c["wall_s"] > 0
        json.dumps(obs.ledger.to_record())

    def test_ledger_reset_and_delta_isolation(self):
        _, obs = _cur()
        obs.tracer.enable(None)
        with obs.tracer.span("phasey"):
            pass
        obs.ledger.sample(0)
        obs.events.record("late_event")
        row = obs.ledger.sample(1)
        # second sample sees only the DELTA (the new event, no stale
        # phase time)
        assert row.get("events") == {"late_event": 1}
        assert "phasey" not in row.get("phases", {})
        obs.ledger.reset()
        assert obs.ledger.iterations == []
        # reset() RE-SEEDS the baselines from the live tracer (which
        # reset_run deliberately leaves running): phase time spanned
        # BEFORE the reset must not bleed into the first sample after
        # it — only post-reset spans count
        with obs.tracer.span("pre_reset_span"):
            pass
        obs.ledger.reset()
        with obs.tracer.span("post_reset_span"):
            pass
        row = obs.ledger.sample(0)
        assert "pre_reset_span" not in row.get("phases", {})
        assert "post_reset_span" in row.get("phases", {})


def test_env_knob_docs_stay_in_sync():
    """config.ENV_KNOBS is the docs' source of truth for defaults that
    actually live at the env-reading sites — pin the ones owned by
    code this PR touches so retuning a default without regenerating
    docs/Parameters.md fails here instead of rotting silently."""
    from lightgbm_tpu.config import ENV_KNOBS
    # roofline peaks have no default: they come from the record's
    # device_kind, and an unknown kind is an error
    assert ENV_KNOBS["LGBM_TPU_PEAK_BW_GBPS"][0] == "auto"
    assert ENV_KNOBS["LGBM_TPU_PEAK_TFLOPS"][0] == "auto"
    assert costmodel.roofline_peak(
        {"provenance": {"device_kind": "TPU v5 lite"}}, "bw_gbps") == 819.0
    with pytest.raises(costmodel.RecordModelError, match="device_kind"):
        costmodel.roofline_peak(
            {"provenance": {"device_kind": "TPU v9"}}, "tflops")
    with pytest.raises(costmodel.RooflineNotMeasured):
        costmodel.roofline_peak({"backend": "cpu"}, "bw_gbps", 819.0)
    from lightgbm_tpu.obs.tracer import Tracer
    assert ENV_KNOBS["LGBM_TPU_TRACE_MAX_EVENTS"][0] == str(
        Tracer()._max_events)
    # and the generated table itself must be current: every knob has a
    # row in docs/Parameters.md
    params_md = open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "Parameters.md")).read()
    for knob in ENV_KNOBS:
        assert f"`{knob}`" in params_md, (
            f"{knob} missing from docs/Parameters.md — rerun "
            "tools/gen_parameter_docs.py")


# ---------------------------------------------------------------------
# xplane decoder + kernel attribution (ISSUE 6)
# ---------------------------------------------------------------------
class TestXplaneDecoder:
    def test_encode_decode_roundtrip(self):
        space = xattr.synthetic_xspace()
        data = xattr.encode_xspace(space)
        back = xattr.parse_xspace(data)
        assert [p.name for p in back.planes] \
            == [p.name for p in space.planes]
        assert back.hostnames == ["synthetic"]
        for p0, p1 in zip(space.planes, back.planes):
            assert p1.event_metadata == p0.event_metadata
            assert len(p1.lines) == len(p0.lines)
            for l0, l1 in zip(p0.lines, p1.lines):
                assert l1.name == l0.name
                assert l1.timestamp_ns == l0.timestamp_ns
                assert [(e.metadata_id, e.offset_ps, e.duration_ps)
                        for e in l1.events] \
                    == [(e.metadata_id, e.offset_ps, e.duration_ps)
                        for e in l0.events]

    def test_checked_in_fixture_is_current(self):
        """The committed fixture bytes and bench record must be exactly
        what the in-repo encoder produces — regenerate both with
        ``python -m lightgbm_tpu.obs.xattr`` after changing either."""
        with open(os.path.join(DATA_DIR, "synthetic.xplane.pb"),
                  "rb") as f:
            assert f.read() == xattr.encode_xspace(
                xattr.synthetic_xspace())
        with open(os.path.join(DATA_DIR, "synthetic_bench.json")) as f:
            assert json.load(f) == xattr.synthetic_bench_record()

    def test_truncated_bytes_raise_parse_error(self):
        data = xattr.encode_xspace(xattr.synthetic_xspace())
        for cut in (1, 7, 50, len(data) - 1):
            with pytest.raises(xattr.XplaneParseError):
                xattr.parse_xspace(data[:cut])
        with pytest.raises(xattr.XplaneParseError, match="empty"):
            xattr.load_xspace(os.devnull)

    def test_negative_and_large_varints(self):
        """int64 fields ride the wire as two's-complement uint64; the
        decoder must fold them back (and big ps durations survive)."""
        line = xattr.XLine(id=1, name="XLA Ops",
                           events=[xattr.XEvent(metadata_id=1,
                                                offset_ps=-5,
                                                duration_ps=1 << 40)])
        plane = xattr.XPlane(id=1, name="/device:TPU:0", lines=[line],
                             event_metadata={1: "k"})
        back = xattr.parse_xspace(xattr.encode_xspace(
            xattr.XSpace(planes=[plane])))
        ev = back.planes[0].lines[0].events[0]
        assert ev.offset_ps == -5 and ev.duration_ps == 1 << 40

    def test_tf_proto_roundtrip_when_installed(self):
        xplane_pb2 = pytest.importorskip(
            "tensorflow.tsl.profiler.protobuf.xplane_pb2")
        data = xattr.encode_xspace(xattr.synthetic_xspace())
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(data)     # our bytes parse as the real proto
        assert [p.name for p in xs.planes] \
            == ["/device:TPU:0", "/device:TPU:1", "/host:CPU"]
        assert xs.planes[0].event_metadata[1].name \
            == "_fused_scan_kernel"
        # and the real proto's serialization parses with our reader
        back = xattr.parse_xspace(xs.SerializeToString())
        assert [p.name for p in back.planes] \
            == [p.name for p in xs.planes]

    def test_classifier_order_traps(self):
        """The substring traps: fused_scan_kernel contains scan_kernel,
        refresh_hist_kernel contains hist_kernel, copyback contains
        neither — each must land on its own class."""
        cases = {
            "_serve_kernel": "serve_traverse",
            "_serve_traverse_block": "serve_traverse",
            "_fused_scan_kernel": "fused_split",
            "_scan_kernel": "partition_scan",
            "_partition_kernel": "partition_scan",
            "_copyback_kernel": "partition_copyback",
            "_hist2_comb_kernel": "hist_build",
            "_refresh_hist_kernel": "stream_refresh",
            "_init_kernel": "stream_refresh",
            "_apply_find_pool_kernel": "find_split",
            # the names the pallas_calls carry since PR 27, as the
            # device plane of a chip capture spells them
            "lgbm_split_scan.1": "fused_split",
            "lgbm_partition_scan.4": "partition_scan",
            "lgbm_copyback.1": "partition_copyback",
            "lgbm_hist.2": "hist_build",
            "lgbm_refresh": "stream_refresh",
            "lgbm_apply_find.3": "find_split",
            "all-reduce.17": "collective",
            "reduce-scatter.3": "collective",
            "dynamic-update-slice.8": "copy",
            "fusion.42": "other",
        }
        for name, want in cases.items():
            assert xattr.classify_kernel(name) == want, name

    def test_pprof_space_bytes(self):
        """The pprof reader (hbm_high_water_bytes fallback) sums the
        'space' sample-type column, gzipped or raw."""
        from lightgbm_tpu.obs.xattr import (_enc_bytes, _enc_int,
                                            _enc_varint)
        strings = ["", "alloc_objects", "space"]
        # two sample types: (count, space); samples carry packed values
        prof = b""
        for t in (1, 2):
            prof += _enc_bytes(1, _enc_int(1, t))
        for vals in ((3, 1000), (2, 256)):
            packed = b"".join(_enc_varint(v) for v in vals)
            prof += _enc_bytes(2, _enc_bytes(2, packed))
        for s in strings:
            prof += _enc_bytes(6, s.encode())
        assert xattr.parse_pprof_space_bytes(prof) == 1256
        import gzip
        assert xattr.parse_pprof_space_bytes(
            gzip.compress(prof)) == 1256


class TestKernelModel:
    def test_fused_stream_classes(self):
        rec = xattr.synthetic_bench_record()
        model = costmodel.kernel_model(rec)
        lrb = costmodel.logical_row_bytes()
        hw = costmodel.hist_out_bytes(32, 256)
        fs = model["fused_split"]
        # the scan writes ONE child's histogram a split (ISSUE 30) ...
        assert fs["bytes_lo"] == 2 * 200_000 * lrb + 30 * hw
        assert fs["bytes_hi"] == 4 * 200_000 * lrb + 30 * hw
        assert fs["bytes"] == pytest.approx(
            (fs["bytes_lo"] + fs["bytes_hi"]) / 2)
        # ... and no split of this record named the larger child;
        # fused root carry: root histograms ride the stream refresh
        assert model["hist_build"]["bytes"] == 0
        assert model["stream_refresh"]["bytes"] == \
            3 * costmodel.stream_refresh_bytes(
                10_000, root_hist=True, f_pad=32,
                padded_bins=256)
        assert "partition_scan" not in model
        assert "collective" not in model

    def test_unfused_classes_and_collectives(self):
        rec = xattr.synthetic_bench_record()
        rec["knobs"] = dict(rec["knobs"], fused=False)
        rec["shape"] = dict(rec["shape"], stream=False)
        rec["ledger"] = {"collectives": [{"name": "g", "bytes_moved":
                                         1000}, {"bytes_moved": 500}]}
        model = costmodel.kernel_model(rec)
        lrb = costmodel.logical_row_bytes()
        hw = costmodel.hist_out_bytes(32, 256)
        assert model["partition_scan"]["bytes"] == 2 * 200_000 * lrb
        cb = model["partition_copyback"]
        assert (cb["bytes_lo"], cb["bytes"], cb["bytes_hi"]) \
            == (0, 200_000 * lrb, 2 * 200_000 * lrb)
        assert model["hist_build"]["bytes"] == \
            150_000 * lrb + (3 + 30) * hw
        assert model["collective"]["bytes"] == 1500
        assert "fused_split" not in model and "stream_refresh" \
            not in model

    def test_untraced_record_clear_error(self):
        with pytest.raises(costmodel.RecordModelError,
                           match="TRACED bench/v3"):
            costmodel.kernel_model({"schema": "lightgbm_tpu/bench/v2"})


class TestDeviceAttr:
    def _fixture_block(self):
        space = xattr.parse_xspace(xattr.encode_xspace(
            xattr.synthetic_xspace()))
        return xattr.device_block("fixture", [space],
                                  rec=xattr.synthetic_bench_record())

    def test_device_block_join(self):
        block = self._fixture_block()
        assert block["schema"] == "lightgbm_tpu/device/v1"
        assert [p["plane"] for p in block["planes"]] \
            == ["/device:TPU:0", "/device:TPU:1"]
        # shard 1 runs 10% slower by construction: measured skew
        assert block["skew"]["ratio"] == pytest.approx(1.1)
        k = block["kernels"]
        assert k["fused_split"]["device_ms"] == pytest.approx(12.6)
        assert k["fused_split"]["count"] == 2
        assert k["stream_refresh"]["device_ms"] == pytest.approx(6.3)
        # phase join: shard planes run concurrently, so the host wall
        # is judged against the STRAGGLER plane's device time (plane 1
        # runs 10% slower by construction), never the cross-plane sum
        grow = block["phases"]["Tree::grow"]
        p1 = block["planes"][1]["kernels"]
        dev = sum(p1[c]["device_ms"] for c in
                  xattr.PHASE_KERNELS["Tree::grow"] if c in p1)
        assert grow["device_ms"] == pytest.approx(dev)
        assert dev == pytest.approx(11.275)
        assert grow["dispatch_overhead_ms"] == pytest.approx(
            50.0 - dev)
        boost = block["phases"]["Boosting"]
        assert boost["device_ms"] == pytest.approx(3.3)
        # host annotations surfaced from the host plane
        assert block["annotations"]["Tree::grow"]["count"] == 1
        json.dumps(block)    # embeds in bench/v3 records as-is

    def test_attr_cli_exact_fixture_table(self, capsys, monkeypatch):
        """decoder -> classifier -> cost-model join -> table, pinned
        byte-for-byte against the checked-in expected output (the CI
        attr leg runs the same comparison).  The expected file embeds
        the repo-relative fixture path, so run from the repo root."""
        monkeypatch.chdir(os.path.dirname(os.path.dirname(DATA_DIR)))
        rc = report_main([
            "attr", os.path.join("tests", "data",
                                 "synthetic.xplane.pb"),
            "--bench", os.path.join("tests", "data",
                                    "synthetic_bench.json"),
            "--roofline", "--no-tf"])
        assert rc == 0
        out = capsys.readouterr().out
        with open(os.path.join(DATA_DIR,
                               "synthetic_attr_expected.txt")) as f:
            assert out == f.read()

    def test_attr_cli_failure_modes(self, tmp_path, capsys):
        # missing path and empty capture dir: exit 2
        assert report_main(["attr", str(tmp_path / "nope")]) == 2
        empty = tmp_path / "empty"
        empty.mkdir()
        assert report_main(["attr", str(empty)]) == 2
        # no TPU/GPU plane: exit 1, annotations still surfaced
        host = tmp_path / "host.xplane.pb"
        host.write_bytes(xattr.encode_xspace(xattr.synthetic_xspace(
            device_planes=0)))
        assert report_main(["attr", str(host)]) == 1
        # truncated pb: exit 2
        trunc = tmp_path / "trunc.xplane.pb"
        trunc.write_bytes(xattr.encode_xspace(
            xattr.synthetic_xspace())[:60])
        assert report_main(["attr", str(trunc), "--no-tf"]) == 2
        out = capsys.readouterr().out
        assert "empty capture dir" in out
        assert "no TPU/GPU device plane" in out
        assert "truncated" in out
        # unreadable bench record: exit 2
        pb = os.path.join(DATA_DIR, "synthetic.xplane.pb")
        bad = tmp_path / "bad.json"
        bad.write_text("{")
        assert report_main(["attr", pb, "--bench", str(bad)]) == 2

    def test_diff_thresholds_device_kernels(self):
        def rec_with_device(fused_ms, extra_cls=None):
            kernels = {"fused_split": {"device_ms": fused_ms,
                                       "count": 2},
                       "hist_build": {"device_ms": 4.0, "count": 2}}
            if extra_cls:
                kernels[extra_cls] = {"device_ms": 8.0, "count": 1}
            return _rec(phases={}, counters_d={"splits": 30.0}) | {
                "device": {"schema": "lightgbm_tpu/device/v1",
                           "kernels": kernels}}

        a = rec_with_device(12.6)
        f, incomp = regress.diff_records(a, a)
        assert not incomp and regress.regressions(f) == []
        # 2x fused device time: flagged past the wall tolerance
        f, _ = regress.diff_records(a, rec_with_device(25.2))
        regs = regress.regressions(f)
        assert [r["kind"] for r in regs] == ["device-kernel"]
        assert regs[0]["name"] == "fused_split"
        # a kernel class APPEARING above the floor = new device work
        f, _ = regress.diff_records(a, rec_with_device(
            12.6, extra_cls="partition_scan"))
        regs = regress.regressions(f)
        assert [r["name"] for r in regs] == ["partition_scan"]
        # disappearing class surfaces as changed, does not fail
        f, _ = regress.diff_records(rec_with_device(
            12.6, extra_cls="partition_scan"), a)
        assert regress.regressions(f) == []
        assert any(x["status"] == "changed" for x in f)
        # sub-floor device times are scheduler noise, ignored
        f, _ = regress.diff_records(rec_with_device(0.0004),
                                    rec_with_device(0.0009))
        assert regress.regressions(f) == []
        # captured candidate vs UNCAPTURED baseline: the device axis
        # was never measured there — no findings, not "every kernel
        # is new"
        f, _ = regress.diff_records(
            _rec(phases={}, counters_d={"splits": 30.0}),
            rec_with_device(12.6))
        assert regress.regressions(f) == []

    def test_tracer_annotation_toggle_and_capture(self, tmp_path):
        """annotate() only mirrors spans while on; xplane_capture flips
        it around a real jax.profiler capture whose host-plane output
        the in-repo decoder must read back (CPU backend: no device
        plane, exit 1 path)."""
        _, obs = _cur()
        obs.tracer.enable(None)
        assert not obs.tracer.annotating
        obs.tracer.annotate(True)
        try:
            with obs.tracer.span("annotated_probe"):
                pass      # TraceAnnotation outside a session is a no-op
        finally:
            obs.tracer.annotate(False)
        assert not obs.tracer.annotating
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from profile_lib import xplane_capture
        cap = str(tmp_path / "cap")
        try:
            with xplane_capture(cap):
                assert obs.tracer.annotating
                with obs.tracer.span("under_capture"):
                    import jax.numpy as jnp
                    import jax
                    jax.block_until_ready(jnp.ones((8,)) + 1)
        except RuntimeError as e:  # pragma: no cover - profiler busy
            pytest.skip(f"jax profiler unavailable here: {e}")
        assert not obs.tracer.annotating
        import glob as g
        pbs = g.glob(os.path.join(cap, "**", "*.xplane.pb"),
                     recursive=True)
        if not pbs:  # pragma: no cover - profiler wrote no xplane
            pytest.skip("capture produced no xplane.pb on this backend")
        # a REAL jax-written xplane must decode with the pure-python
        # reader; CPU captures carry no TPU plane -> the exit-1 path
        rc = report_main(["attr", cap, "--no-tf"])
        assert rc in (0, 1)

    def test_hbm_high_water_companion(self):
        _, obs = _cur()
        import jax.numpy as jnp
        import jax
        keep = jax.block_until_ready(jnp.zeros((1024,)))
        assert keep.nbytes > 0
        peak = obs.hbm_high_water_bytes()
        assert peak is None or (isinstance(peak, int) and peak >= 0)
        row = obs.ledger.sample(0)
        assert row.get("hbm_live_bytes", 0) > 0
        # hbm_peak_bytes present iff the backend reports a watermark
        if peak is not None:
            assert row.get("hbm_peak_bytes", 0) >= 0


def test_provenance_header_and_bench_v3():
    _, obs = _cur()
    prov = obs.provenance()
    for key in ("git_sha", "jax", "backend", "python"):
        assert key in prov, key
    assert "hostname" not in prov and "node" not in prov
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from profile_lib import BENCH_SCHEMA, bench_record
    assert BENCH_SCHEMA == "lightgbm_tpu/bench/v3"
    rec = bench_record("m", 1.0, "iters/sec")
    assert rec["schema"] == BENCH_SCHEMA
    assert rec["provenance"]["git_sha"] == prov["git_sha"]
    json.dumps(rec)


# ---------------------------------------------------------------------
# mesh flight recorder (ISSUE 8): per-shard ledger rows, skew series,
# measured-vs-predicted ICI join, multichip diff gates
# ---------------------------------------------------------------------
class TestMeshFlightRecorder:
    def _train_mesh(self, n=1600, f=8, rounds=2, leaves=8,
                    max_bin=63):
        """Traced data-parallel training on the 8-CPU mesh; returns
        (booster, collectives, mesh_summary, n_rows)."""
        lgb, obs = _cur()
        obs.tracer.enable(None)
        x, y = _make_problem(n=n, f=f)
        ds = lgb.Dataset(x, label=y, params={"max_bin": max_bin})
        bst = lgb.Booster(params={
            "objective": "binary", "num_leaves": leaves,
            "verbosity": -1, "max_bin": max_bin,
            "tree_learner": "data"},
            train_set=ds)
        for _ in range(rounds):
            bst.update()
        bst._inner._flush_pending()
        return (bst, obs.ledger.collectives, obs.ledger.mesh_summary(),
                n)

    def _check_per_shard(self, bst, colls, mesh, n, leaves):
        """The per-shard equivalence contract: every dispatch keys all
        8 shards, the per-shard in-bag rows sum to the SERIAL path's
        in-bag total (no bagging: every real row, padding excluded),
        and bytes_moved equals the collective contract recomputed
        independently from the layout."""
        from lightgbm_tpu.obs.costmodel import (collective_bytes,
                                                hist_out_bytes)
        grower = bst._inner.grow
        assert grower.hist_scatter
        assert len(colls) >= 1    # one row per grow dispatch
        f_pad = (grower._pieces.f_pad if grower.physical
                 else int(bst._inner.dd.bins.shape[1]))
        expect = collective_bytes(
            "psum_scatter", hist_out_bytes(f_pad,
                                           bst._inner.dd.padded_bins),
            8) * leaves
        for c in colls:
            rows = c["per_shard"]["inbag_rows"]
            assert len(rows) == 8 and len(c["per_shard"]["bytes"]) == 8
            # in-bag rows across shards == the serial-path in-bag
            # count: all n real rows (shard padding carries inbag=0)
            assert sum(rows) == pytest.approx(n)
            assert c["bytes_moved"] == expect
            assert c["per_shard"]["bytes"] == [expect] * 8
        assert mesh["shards"] == 8
        assert mesh["dispatches"] == len(colls)
        assert sum(mesh["per_shard"]["inbag_rows"]) \
            == pytest.approx(n * len(colls))
        assert mesh["bytes_moved_total"] == expect * len(colls)
        assert len(mesh["skew_series"]) == len(colls)

    def test_per_shard_ledger_equivalence(self):
        bst, colls, mesh, n = self._train_mesh()
        self._check_per_shard(bst, colls, mesh, n, leaves=8)

    def test_per_shard_ledger_equivalence_two_plane(self, monkeypatch):
        """Same contract through the physical mesh path at a comb line
        of two 128-lane planes (128 feature columns + the 6 value /
        row-id ones): the collective bytes are histogram payloads of
        the 128 columns, whatever the line holds beside them."""
        monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
        # 16384 rows = 8 shards x one full PHYS_ROW_PAD block of 2,048:
        # every shard holds real rows, so the skew series is defined
        # (an emptier n leaves whole shards as padding — in-bag 0 —
        # and the ratio honestly degenerates to None)
        bst, colls, mesh, n = self._train_mesh(n=16384, f=128, rounds=1,
                                               max_bin=15)
        assert bst._inner.grow.physical
        assert bst._inner.grow._pieces.C == 256
        self._check_per_shard(bst, colls, mesh, n, leaves=8)

    def test_ledger_mesh_summary_skew_series(self):
        """mesh_summary aggregates per-dispatch rows into per-shard
        totals and a skew time SERIES — a straggler that appears in
        dispatch 2 is a step in the series, not an averaged scalar."""
        _, obs = _cur()
        led = obs.RunLedger()
        led.record_collective("X::psum", bytes_moved=100, shards=2,
                              per_shard_rows=[10.0, 10.0],
                              per_shard_bytes=[100, 100])
        led.record_collective("X::psum", bytes_moved=100, shards=2,
                              per_shard_rows=[20.0, 10.0],
                              per_shard_bytes=[100, 100])
        m = led.mesh_summary()
        assert m["dispatches"] == 2 and m["shards"] == 2
        assert m["per_shard"]["inbag_rows"] == [30.0, 20.0]
        assert m["per_shard"]["bytes"] == [200, 200]
        assert m["skew_series"] == [1.0, 2.0]
        assert m["skew_max_ratio"] == 2.0
        # stored median uses the SAME convention as the diff gate's
        # _median (averaged middle pair) — what the report prints is
        # what obs diff thresholds
        assert m["skew_median_ratio"] == regress._median([1.0, 2.0]) \
            == 1.5
        rec = led.to_record()
        assert rec["mesh"] == m
        json.dumps(rec)
        # derived scalar view stays consistent with the series
        assert led.collectives[1]["skew_max"] == 20.0
        assert led.collectives[1]["skew_min"] == 10.0

    def test_diff_shard_count_mismatch_exit2(self, tmp_path, capsys):
        import copy
        a = xattr.synthetic_multichip_record()
        b = copy.deepcopy(a)
        b["multichip"]["n_shards"] = 16
        b["ledger"]["mesh"]["shards"] = 16
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        assert report_main(["diff", str(pa), str(pb)]) == 2
        assert "shard-count mismatch" in capsys.readouterr().out

    def test_diff_flags_skew_and_byte_mutations(self, tmp_path):
        import copy
        a = xattr.synthetic_multichip_record()
        skew = copy.deepcopy(a)
        mesh = skew["ledger"]["mesh"]
        mesh["skew_series"] = [2.0] * len(mesh["skew_series"])
        mesh["skew_max_ratio"] = mesh["skew_median_ratio"] = 2.0
        f, incomp = regress.diff_records(a, skew)
        assert not incomp
        assert [r["name"] for r in regress.regressions(f)] \
            == ["shard_skew_ratio(median)"]
        byt = copy.deepcopy(a)
        byt["ledger"]["collectives"][0]["bytes_moved"] += 1
        byt["ledger"]["mesh"]["bytes_moved_total"] += 1
        f, incomp = regress.diff_records(a, byt)
        assert not incomp
        assert [r["name"] for r in regress.regressions(f)] \
            == ["collective_bytes"]
        # and the clean self-diff stays clean
        f, incomp = regress.diff_records(a, a)
        assert not incomp and regress.regressions(f) == []
        # mesh telemetry DISAPPEARING from the candidate is the loss
        # the flight recorder exists to catch — it must fail the
        # gate, not read as a clean diff
        gone = copy.deepcopy(a)
        del gone["ledger"]["collectives"]
        del gone["ledger"]["mesh"]
        del gone["multichip"]
        f, incomp = regress.diff_records(a, gone)
        assert not incomp
        assert any(r["kind"] == "mesh" and r["name"] == "collectives"
                   for r in regress.regressions(f))

    def test_legacy_multichip_reader_fallback(self, tmp_path, capsys):
        """Old MULTICHIP_r*.json dryrun artifacts ({n_devices, rc, ok,
        tail}) are recognized everywhere with a clear pointer to
        tools/multichip_probe.py — report exits 0 with the message,
        diff refuses with exit 2, never a traceback."""
        legacy = {"n_devices": 8, "rc": 0, "ok": True,
                  "skipped": False, "tail": "dryrun ok"}
        p = tmp_path / "MULTICHIP_r99.json"
        p.write_text(json.dumps(legacy))
        rec = regress.load_record(str(p))
        assert rec.get("_legacy_multichip")
        assert report_main(["report", "--bench", str(p)]) == 0
        out = capsys.readouterr().out
        assert "legacy multichip dryrun" in out
        assert "multichip_probe" in out
        mc = tmp_path / "mc.json"
        mc.write_text(json.dumps(xattr.synthetic_multichip_record()))
        assert report_main(["diff", str(p), str(mc)]) == 2
        out = capsys.readouterr().out
        assert "legacy multichip" in out and "Traceback" not in out


class TestCollectivesValidation:
    """obs collectives: xstat decode, collective extraction, and the
    exact measured-vs-predicted join (ISSUE 8 tentpole 2)."""

    def test_mesh_fixture_is_current(self):
        """Committed mesh fixture bytes + bench record must match the
        in-repo encoder — regenerate with
        ``python -m lightgbm_tpu.obs.xattr``."""
        with open(os.path.join(DATA_DIR, "synthetic_mesh.xplane.pb"),
                  "rb") as f:
            assert f.read() == xattr.encode_xspace(
                xattr.synthetic_mesh_xspace())
        with open(os.path.join(DATA_DIR,
                               "synthetic_mesh_bench.json")) as f:
            assert json.load(f) == xattr.synthetic_multichip_record()

    def test_stat_roundtrip_int_and_double(self):
        ev = xattr.XEvent(metadata_id=1, duration_ps=10,
                          stats={1: 215040.0, 2: 1.5})
        line = xattr.XLine(id=1, name="XLA Ops", events=[ev])
        plane = xattr.XPlane(id=1, name="/device:TPU:0",
                             lines=[line],
                             event_metadata={1: "all-reduce.1"},
                             stat_metadata={1: "bytes_accessed",
                                            2: "duty_cycle"})
        back = xattr.parse_xspace(xattr.encode_xspace(
            xattr.XSpace(planes=[plane])))
        bev = back.planes[0].lines[0].events[0]
        assert bev.stats[1] == 215040.0          # int64 varint path
        assert bev.stats[2] == pytest.approx(1.5)  # double fixed64 path
        assert xattr.event_bytes(back.planes[0], bev) == 215040

    def test_plane_collective_events(self):
        space = xattr.parse_xspace(xattr.encode_xspace(
            xattr.synthetic_mesh_xspace()))
        evs = xattr.plane_collective_events(space.planes[0])
        assert [e["name"] for e in evs] \
            == ["all-reduce.3", "reduce-scatter.11"]
        ar, rs = evs
        assert ar["bytes"] is None      # no bytes stat on the capture
        assert rs["count"] == 2
        assert rs["bytes"] == 2 * xattr.MESH_DISPATCH_BYTES
        # the fusion event is not a collective
        assert all("fusion" not in e["name"] for e in evs)

    def test_collectives_block_exact_join(self):
        from lightgbm_tpu.obs.collectives import collectives_block
        space = xattr.synthetic_mesh_xspace()
        rec = xattr.synthetic_multichip_record()
        block = collectives_block("fix", [space], rec=rec)
        assert len(block["planes"]) == 8
        assert block["predicted"]["dispatches"] == 2
        assert all(j["status"] == "exact" for j in block["join"])
        json.dumps(block)

    def test_collectives_cli_exact_fixture_table(self, capsys,
                                                 monkeypatch):
        """Pinned byte-for-byte like the attr table (the CI mesh-obs
        leg runs the same comparison)."""
        monkeypatch.chdir(os.path.dirname(os.path.dirname(DATA_DIR)))
        rc = report_main([
            "collectives",
            os.path.join("tests", "data", "synthetic_mesh.xplane.pb"),
            "--bench", os.path.join("tests", "data",
                                    "synthetic_mesh_bench.json"),
            "--no-tf"])
        assert rc == 0
        out = capsys.readouterr().out
        with open(os.path.join(
                DATA_DIR, "synthetic_collectives_expected.txt")) as f:
            assert out == f.read()

    def test_collectives_cli_mismatch_flagged(self, tmp_path, capsys):
        """One mutated predicted byte => MISMATCH row + exit 1 (the
        exact-or-flagged contract)."""
        rec = xattr.synthetic_multichip_record()
        rec["ledger"]["collectives"][0]["bytes_moved"] += 1
        p = tmp_path / "mut.json"
        p.write_text(json.dumps(rec))
        rc = report_main([
            "collectives",
            os.path.join(DATA_DIR, "synthetic_mesh.xplane.pb"),
            "--bench", str(p), "--no-tf"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "MISMATCH" in out and "-1 B" in out

    def test_collectives_cli_failure_modes(self, tmp_path, capsys):
        # missing capture: exit 2
        assert report_main(["collectives",
                            str(tmp_path / "nope")]) == 2
        # host-only capture: exit 1
        host = tmp_path / "host.xplane.pb"
        host.write_bytes(xattr.encode_xspace(xattr.synthetic_xspace(
            device_planes=0)))
        assert report_main(["collectives", str(host), "--no-tf"]) == 1
        # device capture + bench record WITHOUT ledger rows: exit 1
        # with "nothing to validate"
        norec = tmp_path / "norec.json"
        norec.write_text(json.dumps(xattr.synthetic_bench_record()))
        assert report_main([
            "collectives",
            os.path.join(DATA_DIR, "synthetic_mesh.xplane.pb"),
            "--bench", str(norec), "--no-tf"]) == 1
        # legacy multichip bench: exit 2 (no ledger to join)
        legacy = tmp_path / "legacy.json"
        legacy.write_text(json.dumps({"n_devices": 8, "rc": 0,
                                      "ok": True, "tail": ""}))
        assert report_main([
            "collectives",
            os.path.join(DATA_DIR, "synthetic_mesh.xplane.pb"),
            "--bench", str(legacy), "--no-tf"]) == 2
        out = capsys.readouterr().out
        assert "Traceback" not in out
        # measured-only mode (no --bench): exit 0 on the mesh capture
        assert report_main([
            "collectives",
            os.path.join(DATA_DIR, "synthetic_mesh.xplane.pb"),
            "--no-tf"]) == 0

    def test_attr_straggler_root_cause_block(self):
        """device_block on a mesh capture names the slow shard and
        ranks per-kernel-class deltas vs the fastest plane (tentpole
        3: which shard, which phase, which kernel class)."""
        space = xattr.synthetic_mesh_xspace()
        block = xattr.device_block("fix", [space])
        strag = block["straggler"]
        assert strag["plane"] == "/device:TPU:3"    # 30% slower
        assert strag["causes"][0]["kernel"] == "other"
        coll = [c for c in strag["causes"]
                if c["kernel"] == "collective"]
        assert coll and coll[0]["phase"] == "Tree::grow"
        # the 2-plane synthetic fixture names fused_split under
        # Tree::grow as the top cause
        block2 = xattr.device_block("fix", [xattr.synthetic_xspace()])
        s2 = block2["straggler"]
        assert s2["plane"] == "/device:TPU:1"
        assert s2["causes"][0]["kernel"] == "fused_split"
        assert s2["causes"][0]["phase"] == "Tree::grow"


class TestCollectivesEdgeCases:
    """Review-hardening (ISSUE 8): partial stats coverage is surfaced
    not penalized, idle planes don't fail the gate, balanced captures
    render no straggler."""

    def test_idle_plane_does_not_fail_gate(self, tmp_path, capsys):
        import copy
        mesh = xattr.synthetic_mesh_xspace()
        idle = copy.deepcopy(mesh.planes[0])
        idle.id, idle.name = 99, "/device:TPU:8"
        idle.lines[0].events = [
            e for e in idle.lines[0].events
            if xattr.classify_kernel(
                idle.event_metadata.get(e.metadata_id, ""))
            != "collective"]
        mesh.planes.append(idle)
        pb = tmp_path / "mesh9.xplane.pb"
        pb.write_bytes(xattr.encode_xspace(mesh))
        rc = report_main([
            "collectives", str(pb),
            "--bench", os.path.join(DATA_DIR,
                                    "synthetic_mesh_bench.json"),
            "--no-tf"])
        assert rc == 0          # 8 exact shard planes + 1 idle plane
        out = capsys.readouterr().out
        assert "idle plane(s)" in out
        assert "all 8 shard plane(s) match" in out

    def test_partial_stats_coverage_surfaced(self):
        from lightgbm_tpu.obs.collectives import collectives_block
        block = collectives_block(
            "fix", [xattr.synthetic_mesh_xspace()],
            rec=xattr.synthetic_multichip_record())
        p = block["planes"][0]
        # the all-reduce carries no bytes stat, the reduce-scatter
        # does: coverage is 1/2 ops but the verdict stays exact
        assert (p["ops_with_bytes"], p["ops_total"]) == (1, 2)
        assert block["join"][0]["status"] == "exact"

    def test_balanced_capture_suppresses_straggler(self):
        import copy
        space = xattr.synthetic_xspace(device_planes=1)
        p2 = copy.deepcopy(space.planes[0])
        p2.id, p2.name = 2, "/device:TPU:1"
        space.planes.insert(1, p2)
        block = xattr.device_block("x", [space])
        assert block["skew"]["ratio"] == 1.0
        assert "straggler" not in block
        # skewed captures still root-cause (the 10%-slower fixture)
        assert "straggler" in xattr.device_block(
            "x", [xattr.synthetic_xspace()])


def test_report_tolerates_truncated_mesh_and_straggler_blocks(
        tmp_path, capsys):
    """S3 contract: a hand-edited/truncated multichip record (mesh
    block with a series but no derived ratios, straggler block missing
    keys) renders partially — one clear line, exit 0, no traceback."""
    rec = xattr.synthetic_multichip_record()
    rec["ledger"]["mesh"] = {"shards": 8, "dispatches": 2,
                             "skew_series": [1.0]}
    rec["device"] = {"schema": "lightgbm_tpu/device/v1",
                     "kernels": {"fused_split": {"device_ms": 1.0,
                                                 "count": 1}},
                     "planes": [{"plane": "p", "total_device_ms": 1.0,
                                 "kernels": {}}],
                     "straggler": {"plane": "/device:TPU:1",
                                   "causes": [{"kernel": "x"}]}}
    p = tmp_path / "trunc_mesh.json"
    p.write_text(json.dumps(rec))
    assert report_main(["report", "--bench", str(p)]) == 0
    out = capsys.readouterr().out
    assert "Traceback" not in out
    assert "mesh: 8 shard(s)" in out
    assert "straggler /device:TPU:1" in out
