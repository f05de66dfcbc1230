"""Fault-tolerant training (ISSUE 13): deterministic checkpoint/
resume, fault-injection harness, numerical guardrails.

The hard contract under test: kill-at-iteration-i + resume grows
BYTE-IDENTICAL trees vs the uninterrupted run — pinned across
one and two comb planes x serial/8-shard mesh, at every K boundary, under
bagging + feature-fraction RNG state and under GOSS.  A resume whose
config fingerprint or engaged routing digest disagrees REFUSES with a
structured finding (exit 2), a torn/corrupt checkpoint surfaces as
CheckpointError (never a garbage resume), and every injected fault
class classifies into the faultreport/v1 table.  The checked-in golden
checkpoint ``tests/data/ckpt_r01`` pins the on-disk format byte-for-
byte (regenerate: ``python -m lightgbm_tpu.resilience``).
"""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FIXTURE = os.path.join(ROOT, "tests", "data", "ckpt_r01")
FIXTURE_FILES = ("LATEST", "ckpt_000004/manifest.json",
                 "ckpt_000004/model.txt", "ckpt_000004/score.npy")

# every knob a resilience train may set, saved/restored around each
# fresh-import train (the ci fallback legs export knob overrides for
# the whole pytest process — see conftest.restore_env_knobs)
RES_KNOBS = ("LGBM_TPU_CKPT_DIR", "LGBM_TPU_CKPT_EVERY",
             "LGBM_TPU_CKPT_KEEP", "LGBM_TPU_FAULT",
             "LGBM_TPU_FAULT_RETRIES", "LGBM_TPU_NUMERICS",
             "LGBM_TPU_PHYS",
             "LGBM_TPU_PART_INTERP", "LGBM_TPU_HIST_SCATTER")

# deterministic base config: feature_fraction + mid-cycle bagging keep
# the stateful host RNG streams live, so every kill/resume cell below
# also round-trips PCG64 state
BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.2,
        "max_bin": 31, "min_data_in_leaf": 5, "min_data_in_bin": 1,
        "feature_fraction": 0.8, "bagging_fraction": 0.8,
        "bagging_freq": 3, "verbosity": -1}


def _purge():
    for m in [k for k in list(sys.modules)
              if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]


def _data(n=600, f=6, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] - 0.5 * x[:, 1] + 0.25 * x[:, 2] * x[:, 3]
         + rng.logistic(size=n) * 0.3 > 0).astype(np.float32)
    return x, y


def _train(rounds, env=None, params=None, n=600, lr_schedule=None,
           fobj=None, callbacks=None, data_seed=3, f=6):
    """Fresh-import train (purge + reimport so env knobs re-resolve,
    the convention from tests/test_physical.py).  Returns
    (model_text, booster)."""
    env = dict(env or {})
    keys = set(RES_KNOBS) | set(env)
    saved = {k: os.environ.get(k) for k in keys}
    for k in RES_KNOBS:
        os.environ.pop(k, None)
    for k, v in env.items():
        os.environ[k] = v
    try:
        _purge()
        import lightgbm_tpu as lgb
        x, y = _data(n=n, f=f, seed=data_seed)
        p = dict(BASE)
        p.update(params or {})
        if fobj is not None:
            p["objective"] = fobj
        ds = lgb.Dataset(x, label=y, params=p)
        cbs = list(callbacks or [])
        if lr_schedule is not None:
            cbs.append(lgb.reset_parameter(learning_rate=lr_schedule))
        bst = lgb.train(p, ds, num_boost_round=rounds,
                        callbacks=cbs or None)
        return bst.model_to_string(), bst
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _ck_env(d, every=2, **extra):
    env = {"LGBM_TPU_CKPT_DIR": str(d),
           "LGBM_TPU_CKPT_EVERY": str(every)}
    env.update(extra)
    return env


# the ISSUE-13 acceptance matrix: one and two comb planes x
# serial/8-shard mesh (plus the default row_order cell); (env, params,
# feature columns).  124 columns + the 6 value / row-id columns is the
# smallest width whose comb line crosses into a second 128-lane plane.
_PHYS = {"LGBM_TPU_PHYS": "interpret"}
CELLS = {
    "row_order": ({}, {}, 6),
    "serial_one_plane": (_PHYS, {}, 6),
    "serial_two_plane": (_PHYS, {}, 124),
    "mesh_one_plane": (_PHYS, {"tree_learner": "data"}, 6),
    "mesh_two_plane": (_PHYS, {"tree_learner": "data"}, 124),
}


# ---------------------------------------------------------------------
# tentpole 1: kill + resume is byte-identical
# ---------------------------------------------------------------------
class TestKillResume:
    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_kill_resume_byte_identical(self, cell, tmp_path):
        env, params, f = CELLS[cell]
        rounds, kill_at = 6, 3
        ref, ref_bst = _train(rounds, env=_ck_env(tmp_path / "ref", 2,
                                                  **env),
                              params=params, f=f)
        if env:
            g = ref_bst._inner.grow
            c = getattr(g, "_C", None) or g._pieces.C
            assert c == 128 * (1 + (f > 6)), "cell is vacuous"
        ck = tmp_path / "kill"
        envk = _ck_env(ck, 2, **env)
        # the "kill": train only kill_at rounds — the process dies with
        # the last completed snapshot at the preceding K boundary,
        # exactly what SIGKILL mid-iteration leaves behind
        _train(kill_at, env=envk, params=params, f=f)
        txt, bst = _train(rounds, env=envk, params=params, f=f)
        assert bst.resumed_from == (kill_at // 2) * 2
        assert txt == ref, (f"{cell}: resume after kill@{kill_at} did "
                            "not reproduce the uninterrupted run")

    def test_kill_at_every_boundary(self, tmp_path):
        # kill at EVERY iteration around the K=2 cadence, including
        # before the first snapshot (resume then starts fresh) and
        # mid-bagging-cycle (freq=3: kills at 1,2,4,5 land mid-cycle)
        rounds = 6
        ref, _ = _train(rounds, env=_ck_env(tmp_path / "ref", 2))
        for kill_at in (1, 2, 3, 4, 5):
            ck = tmp_path / f"kill{kill_at}"
            envk = _ck_env(ck, 2)
            _train(kill_at, env=envk)
            txt, bst = _train(rounds, env=envk)
            assert bst.resumed_from == (kill_at // 2) * 2, kill_at
            assert txt == ref, f"kill@{kill_at} resume diverged"

    def test_goss_rng_roundtrip(self, tmp_path):
        # GOSS derives its sampling keys from seed x iteration and the
        # feature stream from the checkpointed PCG64 state — a resumed
        # run must keep drawing the same subsets
        params = {"boosting": "goss", "bagging_fraction": 1.0,
                  "bagging_freq": 0, "top_rate": 0.3,
                  "other_rate": 0.3}
        rounds = 6
        ref, _ = _train(rounds, env=_ck_env(tmp_path / "ref", 2),
                        params=params)
        envk = _ck_env(tmp_path / "kill", 2)
        _train(3, env=envk, params=params)
        txt, bst = _train(rounds, env=envk, params=params)
        assert bst.resumed_from == 2
        assert txt == ref

    def test_lr_schedule_resume_byte_identical(self, tmp_path):
        # reset_parameter mutates config.learning_rate IN PLACE each
        # iteration; the fingerprint is pinned at train start, so a
        # resume under an lr schedule must neither refuse nor diverge
        def sched(it):
            return 0.2 * (0.9 ** it)

        rounds = 6
        ref, _ = _train(rounds, env=_ck_env(tmp_path / "ref", 2),
                        lr_schedule=sched)
        envk = _ck_env(tmp_path / "kill", 2)
        _train(3, env=envk, lr_schedule=sched)
        txt, bst = _train(rounds, env=envk, lr_schedule=sched)
        assert bst.resumed_from == 2
        assert txt == ref

    def test_partial_multiclass_iteration_not_retried_in_place(
            self, tmp_path):
        # real NaN in CLASS 1's gradients only (custom objective):
        # class 0's tree is appended + scored before the sentinel
        # fires, so with no snapshot landed yet the engine must
        # degrade loudly — re-running the half-applied iteration
        # would duplicate class 0's tree
        calls = {"n": 0}

        def fobj(preds, ds):
            n = preds.shape[0]
            grad = (preds - 0.3).astype(np.float32)      # [n, K]
            hess = np.full_like(grad, 0.7)
            if calls["n"] == 1:                          # iteration 1
                grad[:2, 1] = np.nan
            calls["n"] += 1
            return grad, hess

        with pytest.raises(Exception) as ei:
            _train(6, env=_ck_env(tmp_path / "ck", 100,
                                  LGBM_TPU_NUMERICS="raise"),
                   params={"num_class": 3, "num_leaves": 7},
                   fobj=fobj)
        e = ei.value
        assert type(e).__name__ == "FaultError"
        assert e.report["class"] == "nan_gradients"
        assert e.report["recovered"] is False

    def test_unsupported_boosting_trains_unprotected(self, tmp_path):
        # dart carries per-iteration drop state the snapshot does not
        # capture: the engine warns once and trains WITHOUT checkpoints
        # instead of writing snapshots that could not resume
        ck = tmp_path / "ck"
        txt, bst = _train(3, env=_ck_env(ck, 1),
                          params={"boosting": "dart"})
        assert bst.num_trees() == 3
        assert not os.path.exists(os.path.join(str(ck), "LATEST"))


# ---------------------------------------------------------------------
# resume refusal: a checkpoint from a DIFFERENT run never continues
# ---------------------------------------------------------------------
class TestResumeRefusal:
    def test_config_fingerprint_mismatch_refuses(self, tmp_path):
        envk = _ck_env(tmp_path / "ck", 2)
        _train(3, env=envk)
        with pytest.raises(Exception) as ei:
            _train(6, env=envk, params={"num_leaves": 31})
        assert type(ei.value).__name__ == "ResumeRefused"
        assert ei.value.exit_code == 2
        assert ei.value.finding["code"] == "RESUME_CONFIG_MISMATCH"

    def test_routing_digest_mismatch_refuses(self, tmp_path):
        # same config, different engaged path: trees grown on the
        # physical comb are not a continuation of a row_order run
        # (obs diff incomparable-records semantics)
        envk = _ck_env(tmp_path / "ck", 2)
        _train(3, env=dict(envk, LGBM_TPU_PHYS="interpret"))
        with pytest.raises(Exception) as ei:
            _train(6, env=envk)
        assert type(ei.value).__name__ == "ResumeRefused"
        assert ei.value.exit_code == 2
        assert ei.value.finding["code"] == "RESUME_ROUTING_MISMATCH"

    def test_data_mismatch_refuses(self, tmp_path):
        # same config, same shape, DIFFERENT data (a refreshed
        # dataset reusing the checkpoint dir): the snapshot's forest
        # belongs to the old data — refuse instead of mixing two
        # datasets' trees into one model
        envk = _ck_env(tmp_path / "ck", 2)
        _train(3, env=envk)
        with pytest.raises(Exception) as ei:
            _train(6, env=envk, data_seed=4)
        assert type(ei.value).__name__ == "ResumeRefused"
        assert ei.value.exit_code == 2
        assert ei.value.finding["code"] == "RESUME_DATA_MISMATCH"

    def test_verbosity_is_fingerprint_exempt(self, tmp_path):
        # chattiness must not refuse a resume (the exempt list); the
        # model text's parameters dump still prints the new verbosity,
        # so compare the TREES (everything above the params section)
        envk = _ck_env(tmp_path / "ck", 2)
        ref, _ = _train(6, env=_ck_env(tmp_path / "ref", 2))
        _train(3, env=envk)
        txt, bst = _train(6, env=envk, params={"verbosity": 1})
        assert bst.resumed_from == 2

        def trees(t):
            return t.split("\nparameters")[0]

        assert trees(txt) == trees(ref)


# ---------------------------------------------------------------------
# corrupt checkpoints: CheckpointError (exit 2), never a garbage resume
# ---------------------------------------------------------------------
class TestCorruptCheckpoint:
    @pytest.fixture()
    def ckpt(self, tmp_path):
        d = str(tmp_path / "ck")
        _train(3, env=_ck_env(d, 2))
        from lightgbm_tpu.resilience import checkpoint as C
        path = C.latest(d)
        assert path is not None
        return C, d, path

    def test_valid_checkpoint_loads(self, ckpt):
        C, d, path = ckpt
        ck = C.load(path)
        assert ck.iteration == 2
        assert ck.manifest["schema"] == C.CKPT_SCHEMA

    def test_dangling_latest(self, ckpt):
        C, d, path = ckpt
        with open(os.path.join(d, "LATEST"), "w") as f:
            f.write("ckpt_999999\n")
        with pytest.raises(C.CheckpointError,
                           match="does not exist"):
            C.latest(d)

    def test_garbage_latest(self, ckpt):
        C, d, path = ckpt
        with open(os.path.join(d, "LATEST"), "w") as f:
            f.write("../../etc/passwd\n")
        with pytest.raises(C.CheckpointError,
                           match="not a\\s+checkpoint name"):
            C.latest(d)

    def test_truncated_manifest(self, ckpt):
        C, d, path = ckpt
        m = os.path.join(path, "manifest.json")
        with open(m) as f:
            text = f.read()
        with open(m, "w") as f:
            f.write(text[:len(text) // 2])
        with pytest.raises(C.CheckpointError, match="partial write"):
            C.load(path)

    def test_tampered_model_text(self, ckpt):
        C, d, path = ckpt
        m = os.path.join(path, "model.txt")
        with open(m, "a") as f:
            f.write("tamper\n")
        with pytest.raises(C.CheckpointError,
                           match="model.txt digest mismatch"):
            C.load(path)

    def test_bitrot_score(self, ckpt):
        C, d, path = ckpt
        s = os.path.join(path, "score.npy")
        raw = bytearray(open(s, "rb").read())
        raw[-1] ^= 0xFF
        with open(s, "wb") as f:
            f.write(raw)
        with pytest.raises(C.CheckpointError,
                           match="score digest mismatch"):
            C.load(path)

    def test_exceptions_carry_exit_2_and_finding(self, ckpt):
        C, d, path = ckpt
        err = C.CheckpointError("boom")
        assert err.exit_code == 2
        assert err.finding["code"] == "CKPT_CORRUPT"
        lines = C.render_refusal(err)
        assert any("CKPT_CORRUPT" in ln for ln in lines)

    def test_save_prunes_to_keep(self, tmp_path):
        d = str(tmp_path / "ck")
        _train(6, env=_ck_env(d, 1, LGBM_TPU_CKPT_KEEP="2"))
        names = sorted(n for n in os.listdir(d)
                       if n.startswith("ckpt_"))
        assert names == ["ckpt_000005", "ckpt_000006"]


# ---------------------------------------------------------------------
# tentpole 2: fault injection -> classification -> recovery
# ---------------------------------------------------------------------
class TestFaults:
    def test_parse_spec(self):
        from lightgbm_tpu.resilience import faults
        assert faults.parse_spec("oom@3") == ("oom", 3)
        assert faults.parse_spec(" DEATH@0 ") == ("death", 0)
        assert faults.parse_spec("") is None
        assert faults.parse_spec("off") is None
        for bad in ("oom", "oom@x", "oom@-1", "meteor@3"):
            with pytest.raises(ValueError):
                faults.parse_spec(bad)

    def test_classification_table(self):
        # injected/observed exception -> faultreport class (ordered,
        # first match wins — the doctor's BRINGUP_CLASSES pattern)
        from lightgbm_tpu.resilience import faults, numerics
        from lightgbm_tpu.resilience import checkpoint as C
        table = [
            (numerics.NumericalFault("grad/hess", 3, 7),
             "nan_gradients"),
            (C.CheckpointError("torn"), "checkpoint_corrupt"),
            (C.ResumeRefused("RESUME_CONFIG_MISMATCH", "fork"),
             "resume_refused"),
            (faults.SimulatedResourceExhausted(
                "RESOURCE_EXHAUSTED: out of memory while allocating"),
             "resource_exhausted"),
            (RuntimeError("RESOURCE_EXHAUSTED: 16.0G hbm"),
             "resource_exhausted"),
            (faults.SimulatedCollectiveTimeout(
                "DEADLINE_EXCEEDED: all-reduce timed out"),
             "collective_timeout"),
            (RuntimeError("barrier timed out waiting for shard 3"),
             "collective_timeout"),
            (ValueError("some anonymous explosion"), None),
        ]
        for exc, expected in table:
            assert faults.classify(exc) == expected, exc

    def test_fault_report_shape(self):
        from lightgbm_tpu.resilience import faults
        rep = faults.fault_report("resource_exhausted", iteration=7,
                                  error="OOM", recovered=True,
                                  attempt=1)
        assert rep["schema"] == "lightgbm_tpu/faultreport/v1"
        assert rep["class"] == "resource_exhausted"
        assert rep["recovered"] is True
        f = rep["finding"]
        assert f["code"] == "FAULT_RESOURCE_EXHAUSTED"
        assert f["severity"] == "warning"   # recovered = warning

    @pytest.mark.parametrize("fault,cls", [
        ("oom@3", "resource_exhausted"),
        ("hang@3", "collective_timeout"),
    ])
    def test_injected_fault_recovers_byte_identical(self, fault, cls,
                                                    tmp_path):
        # the fault fires mid-run, the engine classifies + resumes from
        # the last snapshot, and the FINAL model matches the fault-free
        # run byte for byte — recovery is invisible in the trees
        ref, _ = _train(6, env=_ck_env(tmp_path / "ref", 2))
        txt, bst = _train(6, env=_ck_env(tmp_path / "ck", 2,
                                         LGBM_TPU_FAULT=fault))
        from lightgbm_tpu.resilience import faults
        reports = faults.run_reports()
        assert [r["class"] for r in reports] == [cls]
        assert reports[0]["recovered"] is True
        assert bst.num_trees() == 6
        assert txt == ref

    def test_fault_without_checkpoint_degrades_loudly(self, tmp_path):
        with pytest.raises(Exception) as ei:
            _train(6, env={"LGBM_TPU_FAULT": "oom@3"})
        e = ei.value
        assert type(e).__name__ == "FaultError"
        assert e.exit_code == 1
        assert e.report["class"] == "resource_exhausted"
        assert e.report["recovered"] is False

    def test_retry_budget_exhausted_degrades(self, tmp_path):
        with pytest.raises(Exception) as ei:
            _train(6, env=_ck_env(tmp_path / "ck", 2,
                                  LGBM_TPU_FAULT="oom@3",
                                  LGBM_TPU_FAULT_RETRIES="0"))
        e = ei.value
        assert type(e).__name__ == "FaultError"
        assert e.report["class"] == "resource_exhausted"

    def test_unclassified_exception_propagates(self, tmp_path):
        # a plain bug in user code (callback/feval/fobj) is NOT a
        # device fault: the engine boundary must let it propagate
        # untouched — wrapping it into FaultError would mislabel it
        # and hide it from the caller's own except clauses
        def boom(env):
            if env.iteration == 2:
                raise KeyError("user callback bug")

        with pytest.raises(KeyError, match="user callback bug"):
            _train(6, env=_ck_env(tmp_path / "ck", 2),
                   callbacks=[boom])

    def test_retry_budget_resets_between_incidents(self, tmp_path):
        # the retry budget bounds CONSECUTIVE recovery attempts on one
        # incident, not the total transient faults a long run may
        # survive: two independent recoverable faults with
        # LGBM_TPU_FAULT_RETRIES=1 must both recover — and recovery
        # stays invisible in the trees
        fired = set()

        def flaky(env):
            if env.iteration in (2, 4) and env.iteration not in fired:
                fired.add(env.iteration)
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: transient allocation "
                    f"failure at iteration {env.iteration} (test)")

        ref, _ = _train(6, env=_ck_env(tmp_path / "ref", 1))
        txt, bst = _train(6, env=_ck_env(tmp_path / "ck", 1,
                                         LGBM_TPU_FAULT_RETRIES="1"),
                          callbacks=[flaky])
        from lightgbm_tpu.resilience import faults
        reports = faults.run_reports()
        assert ([r["class"] for r in reports]
                == ["resource_exhausted"] * 2)
        assert all(r["recovered"] for r in reports)
        assert bst.num_trees() == 6
        assert txt == ref

    def test_inplace_retry_rewinds_rng(self, tmp_path):
        # a recoverable fault BEFORE the first snapshot lands (cadence
        # 0 = resume-only) retries in place; the feature-fraction RNG
        # draw the dead attempt consumed must rewind, or the
        # "recovered" run silently trains different trees than the
        # fault-free one
        ref, _ = _train(4)
        txt, bst = _train(4, env=_ck_env(tmp_path / "ck", 0,
                                         LGBM_TPU_FAULT="nan@1",
                                         LGBM_TPU_NUMERICS="raise"))
        from lightgbm_tpu.resilience import faults
        reports = faults.run_reports()
        assert [r["class"] for r in reports] == ["nan_gradients"]
        assert reports[0]["recovered"] is True
        assert bst.num_trees() == 4
        assert txt == ref

    def test_death_class_kills_the_process(self, tmp_path):
        # SIGKILL-equivalent death: nothing survives except the
        # checkpoint directory (subprocess — the signal is real)
        import subprocess
        ck = str(tmp_path / "ck")
        code = (
            f"import sys; sys.path.insert(0, {ROOT!r})\n"
            "from tests.test_resilience import _train, _ck_env\n"
            f"_train(6, env=_ck_env({ck!r}, 2, "
            "LGBM_TPU_FAULT='death@3'))\n"
            "print('SURVIVED')\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=600, cwd=ROOT)
        assert proc.returncode == -9
        assert "SURVIVED" not in proc.stdout
        # the snapshot the next process resumes from is intact
        from lightgbm_tpu.resilience import checkpoint as C
        assert C.load(C.latest(ck)).iteration == 2


# ---------------------------------------------------------------------
# tentpole 3: numerical guardrails
# ---------------------------------------------------------------------
class TestNumerics:
    def test_invalid_policy_fails_loudly(self):
        with pytest.raises(ValueError, match="not a valid policy"):
            _train(1, env={"LGBM_TPU_NUMERICS": "yes please"})

    def test_raise_policy_classifies_nan(self, tmp_path):
        with pytest.raises(Exception) as ei:
            _train(4, env={"LGBM_TPU_FAULT": "nan@2",
                           "LGBM_TPU_NUMERICS": "raise"})
        e = ei.value
        assert type(e).__name__ == "FaultError"
        assert e.report["class"] == "nan_gradients"

    def test_raise_policy_recovers_with_checkpoint(self, tmp_path):
        ref, _ = _train(6, env=_ck_env(tmp_path / "ref", 2))
        txt, bst = _train(6, env=_ck_env(
            tmp_path / "ck", 2, LGBM_TPU_FAULT="nan@3",
            LGBM_TPU_NUMERICS="raise"))
        from lightgbm_tpu.resilience import faults
        assert [r["class"] for r in faults.run_reports()] \
            == ["nan_gradients"]
        assert txt == ref

    def test_skip_policy_drops_poisoned_tree(self):
        txt, bst = _train(4, env={"LGBM_TPU_FAULT": "nan@2",
                                  "LGBM_TPU_NUMERICS": "skip"})
        assert bst.num_trees() == 4
        # tree 2 degraded to a zero stump; its neighbours trained
        leaves = [int(t.num_leaves) for t in bst._models]
        assert leaves[2] == 1 and leaves[1] > 1 and leaves[3] > 1
        from lightgbm_tpu.obs import events
        assert events.totals().get("numerics_skip", 0) >= 1

    def test_clamp_policy_sanitizes_and_continues(self):
        x, _ = _data()
        txt, bst = _train(4, env={"LGBM_TPU_FAULT": "nan@2",
                                  "LGBM_TPU_NUMERICS": "clamp"})
        assert bst.num_trees() == 4
        assert all(int(t.num_leaves) > 1 for t in bst._models)
        assert np.isfinite(bst.predict(x)).all()

    def test_mesh_host_guard_classifies(self):
        # the mesh learners guard at the booster boundary (host_guard),
        # not in-grow — the classification must be identical
        with pytest.raises(Exception) as ei:
            _train(4, env={"LGBM_TPU_FAULT": "nan@2",
                           "LGBM_TPU_NUMERICS": "raise"},
                   params={"tree_learner": "data"})
        assert ei.value.report["class"] == "nan_gradients"

    def test_off_is_the_default_and_identical(self, tmp_path):
        # numerics=off must not perturb training at all (the analyzer
        # purity pin `grow-numerics-off` holds the jaxpr-level version
        # of this; here: end-to-end byte identity)
        ref, _ = _train(3)
        txt, _ = _train(3, env={"LGBM_TPU_NUMERICS": "off"})
        assert txt == ref

    def test_sanitize_fn(self):
        from lightgbm_tpu.resilience import numerics
        import jax.numpy as jnp
        g = jnp.asarray([np.nan, np.inf, -np.inf, 1.0], jnp.float32)
        h = jnp.asarray([2.0, np.nan, 3.0, -np.inf], jnp.float32)
        gs, hs = numerics.sanitize_fn()(g, h)
        assert np.isfinite(np.asarray(gs)).all()
        assert np.isfinite(np.asarray(hs)).all()
        assert float(gs[3]) == 1.0 and float(hs[2]) == 3.0
        assert int(numerics.count_bad_fn()(g, h)) == 5


# ---------------------------------------------------------------------
# golden fixture: the ckpt/v1 on-disk format is pinned byte-for-byte
# ---------------------------------------------------------------------
class TestGoldenFixture:
    def test_fixture_byte_current(self, tmp_path, monkeypatch):
        # the checked-in fixture must match its generator exactly (the
        # routing-matrix / xplane fixture convention) — a drifted
        # format silently un-pins every resume
        for k in RES_KNOBS:
            monkeypatch.delenv(k, raising=False)
        _purge()
        from lightgbm_tpu.resilience.__main__ import regen_fixture
        out = str(tmp_path / "regen")
        regen_fixture(out)
        for rel in FIXTURE_FILES:
            with open(os.path.join(FIXTURE, rel), "rb") as f:
                want = f.read()
            with open(os.path.join(out, rel), "rb") as f:
                got = f.read()
            assert got == want, \
                (f"tests/data/ckpt_r01/{rel} is stale — regenerate "
                 "with: python -m lightgbm_tpu.resilience")

    def test_fixture_resumes_byte_identical(self, tmp_path,
                                            monkeypatch):
        # resuming FROM the checked-in snapshot must keep growing the
        # exact trees the uninterrupted demo run grows — forever
        for k in RES_KNOBS:
            monkeypatch.delenv(k, raising=False)
        _purge()
        import lightgbm_tpu as lgb
        from lightgbm_tpu.resilience.__main__ import (demo_params,
                                                      demo_problem)
        x, y = demo_problem()
        p = demo_params()
        ds = lgb.Dataset(x, label=y, params=p)
        ref = lgb.train(p, ds, num_boost_round=6).model_to_string()
        ck = str(tmp_path / "ck")
        shutil.copytree(FIXTURE, ck)
        monkeypatch.setenv("LGBM_TPU_CKPT_DIR", ck)
        monkeypatch.setenv("LGBM_TPU_CKPT_EVERY", "0")  # resume-only
        _purge()
        import lightgbm_tpu as lgb2
        from lightgbm_tpu.resilience.__main__ import (
            demo_params as dp2, demo_problem as dpr2)
        x2, y2 = dpr2()
        p2 = dp2()
        ds2 = lgb2.Dataset(x2, label=y2, params=p2)
        bst = lgb2.train(p2, ds2, num_boost_round=6)
        assert bst.resumed_from == 4
        assert bst.model_to_string() == ref

    def test_manifest_is_valid_and_versioned(self):
        with open(os.path.join(FIXTURE, "ckpt_000004",
                               "manifest.json")) as f:
            m = json.load(f)
        assert m["schema"] == "lightgbm_tpu/ckpt/v1"
        assert m["iteration"] == 4
        assert m["rng_feature"]["bit_generator"] == "PCG64"
        assert m["rng_bagging"]["bit_generator"] == "PCG64"


# ---------------------------------------------------------------------
# policy plumbing
# ---------------------------------------------------------------------
class TestPolicy:
    def test_policy_from_env(self):
        from lightgbm_tpu.resilience import checkpoint as C
        assert C.policy_from_env({}).dir is None
        assert C.policy_from_env(
            {"LGBM_TPU_CKPT_DIR": "off"}).dir is None
        pol = C.policy_from_env({"LGBM_TPU_CKPT_DIR": "/tmp/x",
                                 "LGBM_TPU_CKPT_EVERY": "5",
                                 "LGBM_TPU_CKPT_KEEP": "3"})
        assert pol == C.CkptPolicy("/tmp/x", 5, 3)
        with pytest.raises(ValueError):
            C.policy_from_env({"LGBM_TPU_CKPT_DIR": "/tmp/x",
                               "LGBM_TPU_CKPT_EVERY": "often"})

    def test_knobs_registered(self):
        from lightgbm_tpu.config import ENV_KNOBS
        for k in ("LGBM_TPU_CKPT_DIR", "LGBM_TPU_CKPT_EVERY",
                  "LGBM_TPU_CKPT_KEEP", "LGBM_TPU_FAULT",
                  "LGBM_TPU_FAULT_RETRIES", "LGBM_TPU_NUMERICS"):
            assert k in ENV_KNOBS, k
