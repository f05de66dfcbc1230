"""Serving-engine parity + contract suite (ISSUE 14).

The compiled forest engine (``lightgbm_tpu/serve``) must agree with the
host reference walk (``models/tree.py Tree.predict_leaf`` /
``Booster.predict``) EXACTLY on leaf indices and within f32-ulp bounds
on summed scores, across the full matrix: boosters trained at one and
two comb planes, EFB/one-hot datasets, categorical (one-hot and sorted-
subset bitset) splits, NaN/missing rows, multiclass K>1, iteration
slices, and the empty/1-row/bucket-boundary batch shapes. Plus the
bucketed-dispatch retrace pin (same bucket => one program; novel bucket
=> exactly one compile) and the predict-side routing rules."""
import os

import numpy as np
import pytest

from conftest import restore_env_knobs, save_env_knobs

SERVE_KNOBS = ("LGBM_TPU_SERVE", "LGBM_TPU_SERVE_BUCKETS",
               "LGBM_TPU_SERVE_QUEUE")


@pytest.fixture
def serve_env():
    saved = save_env_knobs(SERVE_KNOBS)
    os.environ["LGBM_TPU_SERVE"] = "1"
    yield
    restore_env_knobs(saved)


def _train(x, y, params, n_iter=8, ds_params=None, **ds_kw):
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(x, label=y, params=ds_params or {}, **ds_kw)
    bst = lgb.Booster(params={"verbosity": -1, **params}, train_set=ds)
    for _ in range(n_iter):
        bst.update()
    return bst


def _host_leaves(bst, xq):
    return np.stack([t.predict_leaf(np.asarray(xq, np.float64))
                     for t in bst._models], axis=1)


def _host_raw(bst, xq):
    k = bst._k
    raw = np.zeros((k, xq.shape[0]))
    for i, t in enumerate(bst._models):
        raw[i % k] += t.predict(np.asarray(xq, np.float64))
    return raw


def _engine(bst, **kw):
    from lightgbm_tpu.serve import ServingEngine, ServingModel
    return ServingEngine(ServingModel.from_booster(bst), **kw)


def _assert_parity(bst, xq, *, score_tol_ulps=64):
    """Exact leaf indices; score agreement bounded by a few f32 ulps
    per accumulated tree (the engine sums in f32, the host in f64)."""
    eng = _engine(bst)
    leaves = eng.predict_leaves(np.asarray(xq, np.float32))
    host_l = _host_leaves(bst, xq)
    np.testing.assert_array_equal(leaves, host_l)
    scores = eng.predict(np.asarray(xq, np.float32)).T  # [k, n]
    host_r = _host_raw(bst, xq)
    scale = np.maximum(np.abs(host_r), 1.0)
    tol = score_tol_ulps * len(bst._models) * np.finfo(np.float32).eps
    assert np.all(np.abs(scores - host_r) <= tol * scale), \
        float(np.abs(scores - host_r).max())
    return eng


def _higgs(n, f=12, seed=0, nan_frac=0.0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    if nan_frac:
        x[rng.random((n, f)) < nan_frac] = np.nan
    y = (np.nan_to_num(x[:, 0]) - np.nan_to_num(x[:, 1])
         + 0.5 * np.nan_to_num(x[:, 2]) * np.nan_to_num(x[:, 3])
         + rng.logistic(size=n) * 0.3 > 0).astype(np.float32)
    return x, y


# ---------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------
class TestParity:
    def test_dense_binary(self):
        x, y = _higgs(3000)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 31})
        xq, _ = _higgs(700, seed=5)
        _assert_parity(bst, xq)

    def test_nan_and_missing(self):
        x, y = _higgs(3000, nan_frac=0.08)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 31})
        xq, _ = _higgs(500, seed=9, nan_frac=0.2)
        xq[0] = np.nan                      # all-missing row
        _assert_parity(bst, xq)

    def test_zero_as_missing(self):
        x, y = _higgs(2500)
        x[x < 0.3] = 0.0                    # sparse-ish with real zeros
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15,
                            "zero_as_missing": True},
                     ds_params={"zero_as_missing": True})
        xq, _ = _higgs(400, seed=3)
        xq[xq < 0.2] = 0.0
        xq[:17, 0] = np.nan                 # NaN joins the zero bin
        _assert_parity(bst, xq)

    @pytest.mark.parametrize("planes", [1, 2])
    def test_physical_trained_boosters(self, planes):
        # boosters trained on the physical path (its interpret form on
        # the CPU) must serve as any other, at a comb line of one plane
        # and of two (130 features): the comb is the TRAINING layout,
        # the finalized trees and the serving quantizer never see it
        saved = save_env_knobs()
        os.environ["LGBM_TPU_PHYS"] = "interpret"
        f = 8 if planes == 1 else 130
        try:
            x, y = _higgs(1024, f=f, seed=11)
            bst = _train(x, y, {"objective": "binary",
                                "num_leaves": 8}, n_iter=4)
            assert bst._inner.grow._C == 128 * planes
            xq, _ = _higgs(300, f=f, seed=12)
            _assert_parity(bst, xq)
        finally:
            restore_env_knobs(saved)

    def test_efb_onehot(self):
        # EFB-bundled dataset: the serving quantizer works per LOGICAL
        # feature, so bundling must be invisible to the compiled walk
        rng = np.random.default_rng(2)
        n, n_onehot = 2500, 24
        dense, y = _higgs(n, f=6, seed=2)
        c = rng.integers(0, n_onehot, size=n)
        onehot = np.zeros((n, n_onehot), np.float32)
        onehot[np.arange(n), c] = 1.0
        x = np.hstack([onehot, dense])
        bst = _train(x, y, {"objective": "binary", "num_leaves": 31,
                            "enable_bundle": True},
                     ds_params={"enable_bundle": True})
        cq = rng.integers(0, n_onehot, size=400)
        oq = np.zeros((400, n_onehot), np.float32)
        oq[np.arange(400), cq] = 1.0
        xq = np.hstack([oq, _higgs(400, f=6, seed=21)[0]])
        _assert_parity(bst, xq)

    @pytest.mark.parametrize("onehot_cap", [64, 4])
    def test_categorical(self, onehot_cap):
        # onehot_cap=64: every cat split is one-hot; =4: sorted-subset
        # bitset splits (Tree::CategoricalDecision raw bitsets)
        rng = np.random.default_rng(4)
        n = 3000
        xc = rng.integers(0, 37, size=n).astype(np.float64)
        xc2 = rng.integers(0, 9, size=n).astype(np.float64)
        xn = rng.normal(size=(n, 4))
        x = np.column_stack([xc, xc2, xn])
        y = ((xc % 3 == 0) | (xn[:, 0] > 0.6)).astype(np.float32)
        p = {"objective": "binary", "num_leaves": 31,
             "max_cat_to_onehot": onehot_cap}
        bst = _train(x, y, p, ds_params=dict(p),
                     categorical_feature=[0, 1])
        # queries include unseen, rare, negative and NaN categories
        xq = np.column_stack([
            rng.integers(-3, 60, size=600).astype(np.float64),
            rng.integers(0, 12, size=600).astype(np.float64),
            rng.normal(size=(600, 4))])
        xq[rng.random(xq.shape) < 0.04] = np.nan
        _assert_parity(bst, xq)

    def test_multiclass(self):
        rng = np.random.default_rng(6)
        x = rng.normal(size=(2500, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=2500).astype(np.float64)
        bst = _train(x, y, {"objective": "multiclass", "num_class": 4,
                            "num_leaves": 15}, n_iter=5)
        xq = rng.normal(size=(333, 8)).astype(np.float32)
        eng = _assert_parity(bst, xq)
        assert eng.model.num_class == 4

    def test_iteration_slices(self):
        from lightgbm_tpu.serve import ServingEngine, ServingModel
        x, y = _higgs(2000)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15},
                     n_iter=10)
        xq, _ = _higgs(200, seed=8)
        for start, end in ((0, 10), (2, 7), (5, 10), (0, 1)):
            sm = ServingModel.from_booster(bst, start_iteration=start,
                                           end_iteration=end)
            eng = ServingEngine(sm)
            host = np.zeros(200)
            for t in bst._models[start:end]:
                host += t.predict(np.asarray(xq, np.float64))
            got = eng.predict(xq)[:, 0]
            assert np.allclose(got, host, rtol=1e-5, atol=1e-6)

    def test_batch_shapes(self):
        x, y = _higgs(1500)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15},
                     n_iter=4)
        eng = _engine(bst, bucket_min=16, bucket_max=64)
        host = _host_raw(bst, x)[0]
        # empty, 1 row, bucket-1, bucket, bucket+1, multiple chunks
        for n in (0, 1, 15, 16, 17, 63, 64, 65, 200):
            got = eng.predict(x[:n])[:, 0]
            assert got.shape == (n,)
            if n:
                assert np.allclose(got, host[:n], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------
# bucketed-dispatch retrace contract + donation pool
# ---------------------------------------------------------------------
class TestBuckets:
    def test_same_bucket_never_retraces(self):
        x, y = _higgs(1200)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15},
                     n_iter=3)
        eng = _engine(bst)
        eng.predict(x[:400])                    # bucket 512
        p1 = eng.stats()["programs"]
        for n in (300, 257, 512, 400):          # all bucket 512
            eng.predict(x[:n])
        assert eng.stats()["programs"] == p1, \
            "a same-bucket batch size retraced"
        eng.predict(x[:40])                     # novel bucket 64
        assert eng.stats()["programs"] == p1 + 1, \
            "a novel bucket must compile exactly one program"
        assert eng.stats()["buckets"] == [64, 512]

    def test_bucket_policy_env(self):
        saved = save_env_knobs(SERVE_KNOBS)
        os.environ["LGBM_TPU_SERVE_BUCKETS"] = "32:128"
        try:
            x, y = _higgs(900)
            bst = _train(x, y, {"objective": "binary",
                                "num_leaves": 8}, n_iter=2)
            eng = _engine(bst)
            assert eng.bucket_for(1) == 32
            assert eng.bucket_for(129) == 128   # chunks above the cap
            out = eng.predict(x[:300])          # 3 chunks of <=128
            assert out.shape == (300, 1)
        finally:
            restore_env_knobs(saved)

    def test_donated_buffer_pool_reuse(self):
        x, y = _higgs(800)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=2)
        eng = _engine(bst)
        for _ in range(4):
            eng.predict(x[:256])
        # steady state: the per-bucket pool holds the rotated buffers
        # (bounded, not one fresh allocation per dispatch)
        assert sum(len(v) for v in eng._pool.values()) <= 3
        assert eng.dispatches == 4

    def test_queue_double_buffering(self):
        from lightgbm_tpu.serve import ServingQueue
        x, y = _higgs(600)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=2)
        eng = _engine(bst)
        host = _host_raw(bst, x)[0]
        q = ServingQueue(eng, depth=2)
        outs = []
        for s in range(0, 320, 32):
            q.submit(x[s:s + 32])
            assert len(q._inflight) <= 2
        for o in q.drain():
            outs.append(o)
        got = np.concatenate([o[:, 0] for o in outs])
        assert np.allclose(got, host[:320], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------
# predict-side routing
# ---------------------------------------------------------------------
class TestPredictRouting:
    def test_booster_predict_engages_compiled(self, serve_env):
        import lightgbm_tpu as lgb
        x, y = _higgs(1000)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15},
                     n_iter=4)
        xq, _ = _higgs(300, seed=7)
        served = bst.predict(xq)
        os.environ["LGBM_TPU_SERVE"] = "0"
        host = bst.predict(xq)
        assert np.allclose(served, host, rtol=1e-5, atol=1e-6)
        # raw_score path too
        os.environ["LGBM_TPU_SERVE"] = "1"
        served_raw = bst.predict(xq, raw_score=True)
        os.environ["LGBM_TPU_SERVE"] = "0"
        host_raw = bst.predict(xq, raw_score=True)
        assert np.allclose(served_raw, host_raw, rtol=1e-5, atol=1e-6)
        # the engine cache engaged and routing_info reports the digest
        assert bst.__dict__.get("_serve_engines")
        info = bst._inner.routing_info()
        assert info["serving"]["digest"]
        assert isinstance(lgb.Booster, type)

    def test_rules_decide(self):
        from lightgbm_tpu.ops import routing as R
        base = dict(backend="tpu", serve_env="auto")
        assert R.predict_decide(R.PredictInputs(**base)).path == \
            "compiled"
        d = R.predict_decide(R.PredictInputs(**base, pred_contrib=True))
        assert d.path == "host" and "predict_contrib" in d.reasons
        d = R.predict_decide(R.PredictInputs(backend="cpu",
                                             serve_env="auto"))
        assert d.path == "host" and "serve_backend_auto" in d.reasons
        d = R.predict_decide(R.PredictInputs(backend="cpu",
                                             serve_env="1"))
        assert d.path == "compiled"
        d = R.predict_decide(R.PredictInputs(backend="tpu",
                                             serve_env="0"))
        assert d.path == "host"  # env off wins

    def test_kernel_rules_decide(self):
        """ISSUE 18: the serve_kernel dimension — engagement needs the
        compiled path AND no serve_kernel rule firing."""
        from lightgbm_tpu.ops import routing as R
        # default knobs on the TPU backend: compiled, XLA gather walk
        # (the v5e compiler refuses the Pallas kernel, PR 22)
        d = R.predict_decide(R.PredictInputs(backend="tpu",
                                             serve_env="auto"))
        assert d.path == "compiled" and not d.kernel
        assert d.kernel_reasons == ("serve_kernel_backend_auto",)
        # only the explicit ask engages the kernel, on TPU alone
        d = R.predict_decide(R.PredictInputs(
            backend="tpu", serve_env="auto", serve_kernel_env="1"))
        assert d.path == "compiled" and d.kernel and d.kernel_requested
        # VMEM-overwide forest: compiled path stays, kernel drops loud
        d = R.predict_decide(R.PredictInputs(
            backend="tpu", serve_env="auto", serve_kernel_env="1",
            forest_overwide=True))
        assert d.path == "compiled" and not d.kernel
        assert d.kernel_reasons == ("serve_forest_overwide",)
        # kernel env off: quiet
        d = R.predict_decide(R.PredictInputs(
            backend="tpu", serve_env="auto", serve_kernel_env="0"))
        assert d.path == "compiled" and not d.kernel
        assert d.kernel_reasons == ("serve_kernel_env_off",)
        # off-TPU backend under auto: quiet gather walk...
        d = R.predict_decide(R.PredictInputs(
            backend="cpu", serve_env="1"))
        assert d.path == "compiled" and not d.kernel
        assert "serve_kernel_backend_auto" in d.kernel_reasons
        # ...but the interpret seam engages anywhere
        d = R.predict_decide(R.PredictInputs(
            backend="cpu", serve_env="1",
            serve_kernel_env="interpret"))
        assert d.path == "compiled" and d.kernel
        # a host-routed predict never claims the kernel
        d = R.predict_decide(R.PredictInputs(
            backend="tpu", serve_env="0"))
        assert d.path == "host" and not d.kernel

    def test_loud_fallback_events(self, serve_env):
        from lightgbm_tpu.obs.counters import events
        x, y = _higgs(800)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=2)
        before = events.totals().get(
            "routing_fallback_predict_early_stop", 0)
        bst.predict(x[:50], pred_early_stop=True)
        assert events.totals().get(
            "routing_fallback_predict_early_stop", 0) == before + 1
        before = events.totals().get(
            "routing_fallback_predict_leaf_index", 0)
        bst.predict(x[:50], pred_leaf=True)
        assert events.totals().get(
            "routing_fallback_predict_leaf_index", 0) == before + 1

    def test_loaded_model_serves_compiled(self, serve_env):
        """ISSUE 18 / ROADMAP 2d: a booster loaded from model text
        serves COMPILED — the stack derives an exact quantizer from
        the trees' own thresholds, and the retired
        predict_loaded_model rule no longer exists."""
        import lightgbm_tpu as lgb
        from lightgbm_tpu.ops import routing as R
        x, y = _higgs(800)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=3)
        loaded = lgb.Booster(model_str=bst.model_to_string())
        assert "predict_loaded_model" not in R.PREDICT_RULE_BY_NAME
        got = loaded.predict(x[:100])
        # the compiled engine cache engaged on the LOADED booster
        assert loaded.__dict__.get("_serve_engines")
        os.environ["LGBM_TPU_SERVE"] = "0"
        host = bst.predict(x[:100])
        assert np.allclose(got, host, rtol=1e-6, atol=1e-7)

    def test_from_booster_accepts_loaded(self):
        """The derived-quantizer stack must be leaf-index EXACT vs the
        trained stack (same trees, f32-floored thresholds both
        sides)."""
        import lightgbm_tpu as lgb
        from lightgbm_tpu.serve import ServingModel
        x, y = _higgs(500)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=2)
        loaded = lgb.Booster(model_str=bst.model_to_string())
        sm = ServingModel.from_booster(loaded)
        assert sm.digest
        from lightgbm_tpu.serve import ServingEngine
        eng = ServingEngine(sm)
        lv = eng.predict_leaves(x[:200])
        host = np.stack(
            [t.predict_leaf(np.asarray(x[:200], np.float64))
             for t in bst._models], axis=1)
        assert (lv == host).all()

    def test_matrix_carries_predict_cells(self):
        import json

        from lightgbm_tpu.ops import routing as R
        doc = json.load(open(R.default_matrix_path()))
        pcells = doc.get("predict_cells") or {}
        assert len(pcells) == len(R.enumerate_predict_inputs())
        # every host cell names at least one live rule
        for key, enc in pcells.items():
            fields = dict(p.partition("=")[::2]
                          for p in enc.split(";"))
            if fields["path"] == "host":
                why = fields["why"].split("+")
                assert why and all(
                    r in R.PREDICT_RULE_BY_NAME for r in why), key


# ---------------------------------------------------------------------
# model identity
# ---------------------------------------------------------------------
class TestDigest:
    def test_digest_deterministic_and_distinct(self):
        from lightgbm_tpu.serve import ServingModel
        x, y = _higgs(1000)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 15},
                     n_iter=4)
        a = ServingModel.from_booster(bst)
        b = ServingModel.from_booster(bst)
        assert a.digest == b.digest
        sliced = ServingModel.from_booster(bst, end_iteration=2)
        assert sliced.digest != a.digest
        bst2 = _train(x, y, {"objective": "binary", "num_leaves": 15},
                      n_iter=5)
        assert ServingModel.from_booster(bst2).digest != a.digest

    def test_densify_event_and_warn_once(self):
        scipy_sparse = pytest.importorskip("scipy.sparse")
        from lightgbm_tpu.obs.counters import events
        x, y = _higgs(600)
        bst = _train(x, y, {"objective": "binary", "num_leaves": 8},
                     n_iter=2)
        before = events.totals().get("predict_densify", 0)
        sp = scipy_sparse.csr_matrix(np.nan_to_num(x[:100]))
        a = bst.predict(sp)
        b = bst.predict(np.nan_to_num(x[:100]))
        assert np.allclose(a, b)
        assert events.totals().get("predict_densify", 0) > before


# ---------------------------------------------------------------------
# serving flight recorder (ISSUE 17)
# ---------------------------------------------------------------------
def _flight_mod():
    from lightgbm_tpu.serve import flight
    return flight


@pytest.fixture
def flight_env():
    """Knob isolation + a fresh process recorder around every flight
    test (the recorder is process-global by design)."""
    saved = save_env_knobs()
    _flight_mod()._reset()
    yield
    restore_env_knobs(saved)
    _flight_mod()._reset()


def _tiny_booster(n=600, f=8, leaves=8, n_iter=3, seed=0):
    x, y = _higgs(n, f=f, seed=seed)
    return _train(x, y, {"objective": "binary", "num_leaves": leaves},
                  n_iter=n_iter), x


class TestFlightPurity:
    def test_metrics_off_identical_program_zero_recorder(self,
                                                         flight_env):
        # off: no recorder object exists, the engine binding is None
        # (the single `is None` branch per dispatch), and serving
        # allocates nothing recorder-related
        flight = _flight_mod()
        os.environ["LGBM_TPU_SERVE_METRICS"] = "off"
        bst, x = _tiny_booster()
        eng_off = _engine(bst)
        assert eng_off._flight is None
        eng_off.predict(x[:100].astype(np.float32))
        assert flight._RECORDER is None
        # on: the jitted serving entry is the IDENTICAL object (cached
        # per (n_steps, digest)) — byte-identical compiled program by
        # construction, metrics can only differ host-side
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        eng_on = _engine(bst)
        assert eng_on._flight is not None
        assert eng_on._fn is eng_off._fn
        assert eng_on._leaf_fn is eng_off._leaf_fn

    def test_metrics_on_never_enters_a_trace(self, flight_env):
        # the stats()["programs"] pin: with the recorder live, warmed
        # buckets never recompile — telemetry cannot cause a retrace
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        bst, x = _tiny_booster()
        eng = _engine(bst)
        xf = x.astype(np.float32)
        eng.predict(xf[:64])
        eng.predict(xf[:600])
        eng.mark_warm()
        warm = eng.stats()["programs"]
        queue = _serving_queue(eng, depth=2)
        for i in range(12):
            queue.submit(xf[i * 37:i * 37 + 40])
        queue.drain()
        eng.predict(xf[:600])
        eng.predict(xf[:50])
        st = eng.stats()
        assert st["programs"] == warm
        assert st["retraces_after_warmup"] == 0
        assert eng._flight.snapshot(), "recorder observed nothing"

    def test_retrace_after_warmup_counted_and_evented(self,
                                                      flight_env):
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        os.environ["LGBM_TPU_SERVE_BUCKETS"] = "16:4096"
        bst, x = _tiny_booster()
        eng = _engine(bst)
        xf = x.astype(np.float32)
        eng.collect(eng.dispatch(xf[:16]))
        eng.mark_warm()
        eng.collect(eng.dispatch(xf[:300]))   # novel bucket post-warm
        assert eng.stats()["retraces_after_warmup"] == 1
        eng._flight.flush()
        recs = eng._flight.snapshot()
        ev = {}
        for r in recs:
            for k, v in r["events"].items():
                ev[k] = ev.get(k, 0) + v
        assert ev.get("serve_retrace_after_warmup") == 1


def _serving_queue(engine, depth=None):
    from lightgbm_tpu.serve import ServingQueue
    return ServingQueue(engine, depth=depth)


class TestLatencyHistogram:
    def test_percentiles_parity_with_sample_list(self):
        # satellite: histogram-derived p50/p99 must stay comparable to
        # the sample-list numbers prior bench records carried — within
        # one log bucket (< the perf gate's 25% wall tolerance)
        from lightgbm_tpu.serve.flight import LatencyHistogram
        rng = np.random.default_rng(42)
        lat = rng.lognormal(mean=np.log(2e-3), sigma=0.6, size=800)
        h = LatencyHistogram()
        for s in lat:
            h.add(float(s))
        for q in (50.0, 99.0, 99.9):
            exact = float(np.percentile(lat, q))
            est = h.percentile_s(q)
            assert abs(est - exact) / exact < 0.25, (q, exact, est)

    def test_merge_matches_union(self):
        from lightgbm_tpu.serve.flight import LatencyHistogram
        rng = np.random.default_rng(7)
        a = rng.lognormal(np.log(1e-3), 0.5, 300)
        b = rng.lognormal(np.log(8e-3), 0.5, 300)
        ha, hb, hu = (LatencyHistogram() for _ in range(3))
        for s in a:
            ha.add(float(s))
        for s in b:
            hb.add(float(s))
        for s in np.concatenate([a, b]):
            hu.add(float(s))
        ha.merge(hb)
        assert ha.counts == hu.counts and ha.count == hu.count
        # wire form round-trips exactly
        rt = LatencyHistogram.from_sparse(ha.to_sparse())
        assert rt.counts == ha.counts

    def test_bucket_index_monotone_and_clamped(self):
        from lightgbm_tpu.serve import flight as fl
        idx = [fl.bucket_index(s) for s in
               (0.0, 1e-7, 1e-6, 1e-4, 1e-2, 1.0, 100.0, 1e6)]
        assert idx == sorted(idx)
        assert idx[0] == 0 and idx[-1] == fl.HIST_BUCKETS - 1
        assert fl.percentile_from_counts([0] * fl.HIST_BUCKETS,
                                         99.0) == 0.0

    def test_queue_records_latency_at_source(self, flight_env):
        # metrics OFF: the queue still measures (the bench's numbers
        # come from here now), recorder stays absent
        os.environ["LGBM_TPU_SERVE_METRICS"] = "off"
        bst, x = _tiny_booster()
        eng = _engine(bst)
        queue = _serving_queue(eng, depth=2)
        xf = x.astype(np.float32)
        n = 10
        for i in range(n):
            queue.submit(xf[i * 8:i * 8 + 8])
        queue.drain()
        lat = queue.latency_percentiles()
        assert lat["count"] == n
        assert 0 < lat["p50_ms"] <= lat["p99_ms"] <= lat["p999_ms"]
        snap = queue.latency_snapshot()
        assert sum(sum(c) for c in snap.values()) == n


class TestFlightWindows:
    def _recorder(self, t, window_s=5.0, **kw):
        from lightgbm_tpu.serve.flight import ServingFlightRecorder
        return ServingFlightRecorder(window_s=window_s,
                                     clock=lambda: t[0], **kw)

    GEOM = {"trees": 8, "levels": 4, "features": 8, "num_class": 1}

    def test_digest_change_rotates_never_merges(self):
        t = [100.0]
        rec = self._recorder(t)
        rec.on_dispatch("aaaa", 64, 60, novel=False, warm=True,
                        geom=self.GEOM)
        t[0] += 1.0
        rec.on_dispatch("bbbb", 64, 64, novel=False, warm=True,
                        geom=self.GEOM)   # hot swap: closes 'aaaa'
        rec.flush()
        recs = rec.snapshot()
        assert [r["digest"] for r in recs] == ["aaaa", "bbbb"]
        assert recs[0]["dispatches"] == 1
        assert recs[0]["padding_waste_bytes"] > 0
        assert recs[1]["padding_waste_bytes"] == 0

    def test_cadence_rotation_and_seq(self):
        t = [0.0]
        rec = self._recorder(t, window_s=2.0)
        for _ in range(5):
            rec.on_dispatch("aaaa", 64, 64, novel=False, warm=True,
                            geom=self.GEOM)
            t[0] += 1.0
        rec.flush()
        recs = rec.snapshot()
        assert len(recs) >= 2
        assert [r["seq"] for r in recs] == sorted(
            r["seq"] for r in recs)
        assert sum(r["dispatches"] for r in recs) == 5
        assert all(r["digest"] == "aaaa" for r in recs)

    def test_jsonl_emission_atomic(self, tmp_path):
        import json as _json
        t = [0.0]
        rec = self._recorder(t, emit_dir=str(tmp_path))
        for i in range(3):
            rec.on_dispatch("cccc", 32, 30, novel=(i == 0),
                            warm=False, geom=self.GEOM)
            rec.observe_latency("cccc", 32, 0.002)
            t[0] += 1.0
        rec.flush()
        files = [f for f in os.listdir(tmp_path)
                 if f.endswith(".jsonl")]
        assert len(files) == 1 and "servemetrics" in files[0]
        assert not any(f.endswith(".tmp") for f in os.listdir(tmp_path))
        lines = [_json.loads(l) for l in
                 open(tmp_path / files[0]) if l.strip()]
        assert lines and all(
            r["schema"] == "lightgbm_tpu/servemetrics/v1"
            for r in lines)
        # the reader consumes what the recorder wrote
        from lightgbm_tpu.obs.servemetrics import load_windows
        windows, problems = load_windows([str(tmp_path)])
        assert len(windows) == len(lines) and not problems

    def test_mid_stream_rebuild_segments_by_digest(self, flight_env):
        # a rebuilt engine (new digest) mid-stream: the shared process
        # recorder rotates at the boundary; the reader yields two
        # segments, never one merged stream
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        bst1, x1 = _tiny_booster(seed=0)
        bst2, _ = _tiny_booster(n=700, seed=99, n_iter=4)
        e1, e2 = _engine(bst1), _engine(bst2)
        assert e1.model.digest != e2.model.digest
        assert e1._flight is e2._flight
        xf = x1.astype(np.float32)
        e1.collect(e1.dispatch(xf[:32]))
        e1.collect(e1.dispatch(xf[:32]))
        e2.collect(e2.dispatch(xf[:16]))
        e1._flight.flush()
        recs = e1._flight.snapshot()
        digests = [r["digest"] for r in recs]
        assert e1.model.digest in digests
        assert e2.model.digest in digests
        from lightgbm_tpu.obs.servemetrics import segment_windows
        segs = segment_windows(recs)
        assert len(segs) == 2
        assert {s["digest"] for s in segs} == {e1.model.digest,
                                               e2.model.digest}


class TestQueueSaturation:
    def test_depth_sampled_at_cap_when_full(self, flight_env):
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        bst, x = _tiny_booster()
        eng = _engine(bst)
        queue = _serving_queue(eng, depth=2)
        xf = x.astype(np.float32)
        for i in range(6):
            queue.submit(xf[i * 8:i * 8 + 8])
        queue.drain()
        eng._flight.flush()
        recs = eng._flight.snapshot()
        q = {"samples": 0, "depth_max": 0, "depth_cap": 0}
        for r in recs:
            q["samples"] += r["queue"]["samples"]
            q["depth_max"] = max(q["depth_max"],
                                 r["queue"]["depth_max"])
            q["depth_cap"] = max(q["depth_cap"],
                                 r["queue"]["depth_cap"])
        assert q["samples"] == 6
        # saturation is visible: occupancy sampled BEFORE the block
        # reaches the cap once submits outrun completions
        assert q["depth_max"] == 2 == q["depth_cap"]

    def test_tickets_monotone_while_draining(self, flight_env):
        os.environ["LGBM_TPU_SERVE_METRICS"] = "mem"
        bst, x = _tiny_booster()
        eng = _engine(bst)
        queue = _serving_queue(eng, depth=2)
        xf = x.astype(np.float32)
        tickets, results = [], 0
        for i in range(9):
            tickets.append(queue.submit(xf[i * 4:i * 4 + 4]))
            if i % 3 == 2:       # drain concurrently with submits
                queue.result()
                results += 1
        results += len(queue.drain())
        assert tickets == sorted(tickets) == list(range(9))
        assert results == 9
        lat = queue.latency_percentiles()
        assert lat["count"] == 9


class TestServeCLIContract:
    DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data")

    def test_pinned_fixture_table_exit_1(self, capsys):
        from lightgbm_tpu.obs import findings as F
        from lightgbm_tpu.obs.servemetrics import run_serve
        fx = os.path.join(self.DATA, "servemetrics_r01.jsonl")
        rc = run_serve([fx])
        out = capsys.readouterr().out
        with open(os.path.join(self.DATA,
                               "servemetrics_expected.txt")) as f:
            expected = f.read()
        assert out == expected, \
            ("obs serve table drifted from tests/data/"
             "servemetrics_expected.txt — regenerate with python -m "
             "lightgbm_tpu.obs.servemetrics if intended")
        assert rc == F.EXIT_FINDINGS   # the injected retrace

    def test_fixture_windows_current(self):
        import json as _json
        from lightgbm_tpu.obs.servemetrics import \
            synthetic_serve_windows
        fx = os.path.join(self.DATA, "servemetrics_r01.jsonl")
        on_disk = [_json.loads(l) for l in open(fx) if l.strip()]
        assert on_disk == synthetic_serve_windows(), \
            ("checked-in servemetrics fixture drifted from its "
             "generator — regenerate with python -m "
             "lightgbm_tpu.obs.servemetrics")

    def test_truncated_and_legacy_exit_2(self, tmp_path, capsys):
        from lightgbm_tpu.obs.servemetrics import run_serve
        trunc = tmp_path / "trunc.jsonl"
        trunc.write_text('{"schema": "lightgbm_tpu/servemet')
        rc = run_serve([str(trunc)])
        out = capsys.readouterr().out
        assert rc == 2 and "Traceback" not in out
        legacy = tmp_path / "legacy.jsonl"
        legacy.write_text('{"schema": "lightgbm_tpu/serving/v1"}\n')
        rc = run_serve([str(legacy)])
        out = capsys.readouterr().out
        assert rc == 2 and "re-capture" in out
        rc = run_serve([str(tmp_path / "nope.jsonl")])
        assert rc == 2
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        rc = run_serve([str(empty)])
        assert rc == 2

    def test_slo_findings_gate(self, tmp_path, capsys):
        import json as _json
        from lightgbm_tpu.obs import findings as F
        from lightgbm_tpu.obs.servemetrics import (
            synthetic_serve_windows, run_serve)
        # only the clean segment: no retrace, exit 0 by default
        clean = [w for w in synthetic_serve_windows()
                 if w["digest"] == "abcdef012345"]
        p = tmp_path / "clean.jsonl"
        p.write_text("".join(_json.dumps(w) + "\n" for w in clean))
        assert run_serve([str(p)]) == F.EXIT_CLEAN
        capsys.readouterr()
        # a tight SLO flips the same input to exit 1
        assert run_serve([str(p)], slo_p99_ms=0.5) == F.EXIT_FINDINGS
        out = capsys.readouterr().out
        assert "SLO_P99" in out
        assert run_serve([str(p)],
                         max_pad_waste=0.05) == F.EXIT_FINDINGS
        out = capsys.readouterr().out
        assert "PAD_WASTE" in out


class TestServingGateP999:
    def _rec(self, **sv):
        base = {"schema": "lightgbm_tpu/bench/v3", "metric": "m",
                "value": 1.0, "unit": "rows/sec", "backend": "cpu",
                "serving": {"digest": "aaaa", "p99_ms": 1.0,
                            "p999_ms": 2.0, "bulk_rows_per_sec": 1e6,
                            "padding_waste_ratio": 0.10,
                            "retraces_after_warmup": 0}}
        rec = json_roundtrip(base)
        rec["serving"].update(sv)
        return rec

    def test_injected_p999_regression_flagged(self):
        from lightgbm_tpu.obs.regress import diff_records, regressions
        a = self._rec()
        f, inc = diff_records(a, self._rec())
        assert not inc and not regressions(f)   # self-diff clean
        f, inc = diff_records(a, self._rec(p999_ms=4.0))
        regs = regressions(f)
        assert [r["name"] for r in regs] == ["p999_latency"]

    def test_padding_waste_gates_like_walls(self):
        from lightgbm_tpu.obs.regress import diff_records, regressions
        a = self._rec()
        f, _ = diff_records(a, self._rec(padding_waste_ratio=0.30))
        assert any(r["name"] == "padding_waste_ratio"
                   for r in regressions(f))
        # below the 1% floor both ways: rounding noise, not gated
        f, _ = diff_records(self._rec(padding_waste_ratio=0.001),
                            self._rec(padding_waste_ratio=0.009))
        assert not any(r["name"] == "padding_waste_ratio"
                       for r in regressions(f))

    def test_digest_mismatch_stays_incomparable(self):
        from lightgbm_tpu.obs.regress import diff_records, regressions
        f, inc = diff_records(self._rec(),
                              self._rec(digest="bbbb", p999_ms=40.0))
        assert inc and not any(r["name"] == "p999_latency"
                               for r in regressions(f))


def json_roundtrip(obj):
    import json as _json
    return _json.loads(_json.dumps(obj))
