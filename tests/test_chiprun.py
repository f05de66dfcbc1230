"""Chip-run autopilot tests (ISSUE 11): environment doctor, shared
finding helper, declarative plan + resumable orchestrator, trend view.

The CPU container IS the test vehicle: the doctor must produce a CLEAN
verdict here (the same gate a chip run passes through), the checked-in
BENCH_r03 bring-up log must classify as the TPU-env-bringup class
forever (the regression that motivated ROADMAP item 1), and the full
checked-in plan must dry-run end to end with a complete journal.
"""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from lightgbm_tpu.obs import doctor  # noqa: E402
from lightgbm_tpu.obs import findings as F  # noqa: E402
from lightgbm_tpu.obs import trend  # noqa: E402
from lightgbm_tpu.obs.report import main as report_main  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "chip_run", os.path.join(ROOT, "tools", "chip_run.py"))
chip_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_run)

R03_LOG = os.path.join(ROOT, "tests", "data", "r03_env_failure.log")
DATA = os.path.join(ROOT, "tests", "data")


# ---------------------------------------------------------------------
# shared finding helper
# ---------------------------------------------------------------------
class TestFindings:
    def test_make_finding_shape(self):
        f = F.make_finding("backend", "X", "msg", severity="warning",
                           extra=1)
        assert f == {"layer": "backend", "code": "X",
                     "severity": "warning", "message": "msg",
                     "detail": {"extra": 1}}

    def test_bad_severity_rejected(self):
        with pytest.raises(ValueError):
            F.make_finding("l", "C", "m", severity="fatal")

    def test_exit_code(self):
        assert F.exit_code([]) == 0
        assert F.exit_code([F.make_finding("l", "C", "m",
                                           severity="info")]) == 0
        assert F.exit_code([F.make_finding("l", "C", "m")]) == 1

    def test_render_orders_errors_first(self):
        lines = F.render([
            F.make_finding("a", "I", "info", severity="info"),
            F.make_finding("b", "E", "err")])
        assert "ERROR" in lines[0] and "INFO" in lines[1]

    def test_guard_converts_exception_to_exit_2(self, capsys):
        @F.guard("obs test")
        def boom():
            raise RuntimeError("kaput")
        assert boom() == 2
        assert "obs test: RuntimeError: kaput" in \
            capsys.readouterr().out


# ---------------------------------------------------------------------
# doctor
# ---------------------------------------------------------------------
class TestDoctor:
    def test_cpu_clean_verdict(self):
        block = doctor.run_doctor(xplane_smoke=False)
        assert block["schema"] == "lightgbm_tpu/doctor/v1"
        assert block["backend"] == "cpu"
        assert block["verdict"] == "clean", block["findings"]
        assert F.exit_code(block["findings"]) == 0

    def test_cli_clean_on_cpu(self, capsys):
        assert report_main(["doctor", "--no-xplane-smoke"]) == 0
        assert "verdict CLEAN" in capsys.readouterr().out

    def test_r03_fixture_classifies_tpu_env_bringup(self):
        # THE regression pin: the log that killed BENCH_r03 must
        # classify as the env bring-up class, not the Mosaic noise the
        # dying run dragged along further down the same log
        with open(R03_LOG) as f:
            cls = doctor.classify_bringup_log(f.read())
        assert cls is not None
        assert cls["class"] == "tpu_env_bringup"
        assert "TPU_WORKER_HOSTNAMES" in cls["evidence"]

    def test_r03_fixture_fails_doctor_cli(self, capsys):
        rc = report_main(["doctor", "--log", R03_LOG,
                          "--no-xplane-smoke"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "BRINGUP_TPU_ENV_BRINGUP" in out
        assert "verdict FINDINGS" in out

    def test_log_failure_modes(self, tmp_path, capsys):
        assert report_main(["doctor", "--log", "/nonexistent/x.log",
                            "--no-xplane-smoke"]) == 2
        empty = tmp_path / "empty.log"
        empty.write_text("")
        [f] = doctor.check_log(str(empty))
        assert f["code"] == "LOG_EMPTY" and f["severity"] == "error"
        clean = tmp_path / "clean.log"
        clean.write_text("everything fine\n")
        [f] = doctor.check_log(str(clean))
        assert f["code"] == "LOG_UNCLASSIFIED"
        assert f["severity"] == "info"

    @pytest.mark.parametrize("text,expected", [
        ("could not determine TPU worker hostnames or IP addresses",
         "tpu_env_bringup"),
        ("libtpu.so: cannot open shared object file", "libtpu_missing"),
        ("RuntimeError: Unable to initialize backend 'tpu'",
         "libtpu_missing"),
        ("The TPU is already in use by process 1234", "device_busy"),
        ("Mosaic failed to compile TPU kernel: Slice shape along "
         "dimension 1 must be aligned to tiling (128), but is 64.",
         "mosaic_lane_tiling"),
        ("RESOURCE_EXHAUSTED: out of memory while allocating 16G",
         "hbm_oom"),
        ("worker killed by signal 9 during step 12", "preemption"),
        ("received termination notice: preparing to preempt",
         "preemption"),
        ("checkpoint corrupt: score digest mismatch (torn write)",
         "checkpoint_corrupt"),
        ("a perfectly healthy log line", None),
    ])
    def test_bringup_classes(self, text, expected):
        cls = doctor.classify_bringup_log(text)
        assert (cls["class"] if cls else None) == expected

    def test_classify_exception(self):
        cls = doctor.classify_exception(
            RuntimeError("Unable to initialize backend 'tpu'"))
        assert cls["class"] == "libtpu_missing"

    def test_mocked_env_failure_classes(self):
        # the r03 class, reproduced from env alone (no log needed)
        [f] = [x for x in doctor.check_tpu_env(
            "tpu", environ={"TPU_WORKER_ID": "0"})
            if x["severity"] == "error"]
        assert f["code"] == "TPU_ENV_INCOMPLETE"
        assert f["detail"]["bringup_class"] == "tpu_env_bringup"
        [f] = [x for x in doctor.check_tpu_env(
            "tpu", environ={"TPU_WORKER_HOSTNAMES": "host1:8470"})
            if x["severity"] == "error"]
        assert f["code"] == "TPU_WORKER_HOSTNAMES_INVALID"
        [f] = [x for x in doctor.check_tpu_env(
            "tpu", environ={"TPU_WORKER_HOSTNAMES": "a,b",
                            "TPU_WORKER_ID": "5"})
            if x["severity"] == "error"]
        assert f["code"] == "TPU_WORKER_ID_INCOHERENT"
        clean = doctor.check_tpu_env(
            "tpu", environ={"TPU_WORKER_HOSTNAMES": "10.0.0.1,10.0.0.2",
                            "TPU_WORKER_ID": "1"})
        assert all(x["severity"] == "info" for x in clean)

    def test_stray_tpu_env_on_cpu_is_warning_only(self):
        out = doctor.check_tpu_env(
            "cpu", environ={"TPU_WORKER_ID": "0"})
        assert [x["code"] for x in out] == ["TPU_ENV_STRAY"]
        assert out[0]["severity"] == "warning"

    def test_topology(self):
        [ok] = doctor.check_topology(8, (2, 4))
        assert ok["code"] == "TOPOLOGY_OK"
        [bad] = doctor.check_topology(8, (2, 8))
        assert bad["code"] == "TOPOLOGY_MISMATCH"
        assert bad["severity"] == "error"

    def test_xplane_smoke_on_cpu(self):
        out = doctor.check_xplane_smoke("cpu")
        assert [x["code"] for x in out] == ["XPLANE_OK"], out

    def test_disk_floor(self, tmp_path):
        [f] = doctor.check_disk(str(tmp_path),
                                environ={doctor.DISK_MIN_ENV: "0"})
        assert f["code"] == "DISK_OK"
        [f] = doctor.check_disk(str(tmp_path),
                                environ={doctor.DISK_MIN_ENV: "1e9"})
        assert f["code"] == "DISK_EXHAUSTED"
        assert f["severity"] == "error"

    def test_preflight_clean_on_cpu(self):
        pf = doctor.preflight()
        assert pf["verdict"] == "clean", pf["findings"]
        layers = {f["layer"] for f in pf["findings"]}
        # the cheap subset: no capture smoke before a bench capture
        assert "capture" not in layers
        assert {"backend", "libtpu", "tpu_env", "disk",
                "ckpt"} <= layers

    def test_ckpt_layer_off_by_default(self, monkeypatch):
        monkeypatch.delenv("LGBM_TPU_CKPT_DIR", raising=False)
        [f] = doctor.check_ckpt()
        assert f["code"] == "CKPT_OFF" and f["severity"] == "info"

    def test_ckpt_layer_empty_writable_dir(self, tmp_path,
                                           monkeypatch):
        d = str(tmp_path / "ck")
        monkeypatch.setenv("LGBM_TPU_CKPT_DIR", d)
        out = doctor.check_ckpt()
        codes = [f["code"] for f in out]
        assert "CKPT_DIR_EMPTY" in codes
        assert "DISK_OK" in codes
        # the disk finding is re-tagged into the ckpt layer
        assert all(f["layer"] == "ckpt" for f in out)
        assert all(f["severity"] == "info" for f in out)

    def test_ckpt_layer_corrupt_checkpoint_is_error(self, tmp_path,
                                                    monkeypatch):
        d = tmp_path / "ck"
        d.mkdir()
        (d / "LATEST").write_text("ckpt_000042\n")   # dangles
        monkeypatch.setenv("LGBM_TPU_CKPT_DIR", str(d))
        [f] = [x for x in doctor.check_ckpt()
               if x["severity"] == "error"]
        assert f["code"] == "CKPT_CORRUPT"
        assert f["detail"]["bringup_class"] == "checkpoint_corrupt"

    def test_ckpt_layer_invalid_policy_is_error(self, monkeypatch):
        monkeypatch.setenv("LGBM_TPU_CKPT_DIR", "/tmp/x")
        monkeypatch.setenv("LGBM_TPU_CKPT_EVERY", "often")
        [f] = doctor.check_ckpt()
        assert f["code"] == "CKPT_POLICY_INVALID"
        assert f["severity"] == "error"

    def test_failure_record_shape(self):
        rec = doctor.failure_record(
            "preflight", detail="boom",
            bringup_class="tpu_env_bringup",
            doctor_block={"schema": doctor.DOCTOR_SCHEMA,
                          "findings": []})
        assert rec["schema"] == "lightgbm_tpu/benchfail/v1"
        assert rec["stage"] == "preflight" and rec["ok"] is False
        assert rec["bringup_class"] == "tpu_env_bringup"
        assert rec["doctor"]["schema"] == doctor.DOCTOR_SCHEMA


# ---------------------------------------------------------------------
# plan schema
# ---------------------------------------------------------------------
class TestPlanSchema:
    def _plan(self):
        return chip_run.load_plan(chip_run.DEFAULT_PLAN)

    def test_checked_in_plan_round_trips(self):
        plan = self._plan()
        assert plan["schema"] == chip_run.PLAN_SCHEMA
        chip_run.validate_plan(plan)   # idempotent
        # encodes the whole round 6-13 checklist: doctor + smoke gates
        # + bench sweeps + joins + gate
        ids = [s["id"] for s in plan["steps"]]
        assert ids[0] == "doctor"
        for required in ("tpu_smoke", "bench_headline", "bench_traced",
                         "bench_xplane",
                         "bench_efb_bundled", "bench_efb_unbundled",
                         "bench_ckpt", "bench_paged",
                         "profile_partition", "attr_join", "mem_join",
                         "collectives_join", "perf_gate", "trend"):
            assert required in ids, f"plan lost step {required}"
        # the ISSUE-15 paged point must cap the budget so the shape
        # actually pages on one chip
        [pg] = [s for s in plan["steps"] if s["id"] == "bench_paged"]
        assert "LGBM_TPU_HBM_LIMIT_GB" in pg["env"]
        # the ISSUE-13 checkpoint-overhead point resumes via the env
        # knobs the resilience layer registers
        [ck] = [s for s in plan["steps"] if s["id"] == "bench_ckpt"]
        assert "--resume" in ck["cmd"]
        assert "LGBM_TPU_CKPT_DIR" in ck["env"]
        # the ISSUE-17 latency point must flight-record its windows and
        # the obs serve join must consume the same capture dir
        [sl] = [s for s in plan["steps"]
                if s["id"] == "bench_serve_latency"]
        assert "LGBM_TPU_SERVE_METRICS" in sl["env"]
        [sj] = [s for s in plan["steps"] if s["id"] == "serve_obs_join"]
        assert "serve" in sj["cmd"]
        assert "bench_serve_latency" in sj["needs"]

    def test_plan_digest_stable(self):
        plan = self._plan()
        assert chip_run.plan_digest(plan) == chip_run.plan_digest(
            json.loads(json.dumps(plan)))

    def test_step_digest_mode_sensitive(self):
        step = self._plan()["steps"][0]
        assert chip_run.step_digest(step, "dry") \
            != chip_run.step_digest(step, "real")
        assert chip_run.step_digest(step, "dry") \
            == chip_run.step_digest(json.loads(json.dumps(step)),
                                    "dry")

    @pytest.mark.parametrize("mutate,msg", [
        (lambda p: p.update(schema="nope"), "schema"),
        (lambda p: p.update(round=0), "round"),
        (lambda p: p.update(steps=[]), "steps"),
        (lambda p: p["steps"][0].update(bogus=1), "unknown field"),
        (lambda p: p["steps"].append(dict(p["steps"][0])),
         "duplicate"),
        (lambda p: p["steps"][0].update(cmd=[]), "cmd"),
        (lambda p: p["steps"][0].update(
            env={"LGBM_TPU_NO_SUCH_KNOB": "1"}), "registered knob"),
        (lambda p: p["steps"][0].update(needs=["later_step"]),
         "EARLIER"),
        (lambda p: p["steps"][0].update(requires_backend="quantum"),
         "requires_backend"),
        (lambda p: p["steps"][0].update(timeout_s=-1), "timeout"),
    ])
    def test_malformed_plans_rejected(self, mutate, msg):
        plan = json.loads(json.dumps(self._plan()))
        mutate(plan)
        with pytest.raises(ValueError, match=msg):
            chip_run.validate_plan(plan)


# ---------------------------------------------------------------------
# orchestrator: dry-run, resume, quarantine
# ---------------------------------------------------------------------
def _journal(run_dir):
    entries = []
    with open(os.path.join(run_dir, "journal.jsonl")) as f:
        for line in f:
            entries.append(json.loads(line))
    return entries


def _report(run_dir, rnd=None):
    if rnd is None:
        rnd = chip_run.load_plan(chip_run.DEFAULT_PLAN)["round"]
    with open(os.path.join(run_dir,
                           f"CHIPRUN_r{rnd:02d}.json")) as f:
        return json.load(f)


class TestChipRunDry:
    def test_dry_run_journal_complete(self, tmp_path):
        run_dir = str(tmp_path / "run")
        assert chip_run.main(["--dry-run", "--dir", run_dir]) == 0
        plan = chip_run.load_plan(chip_run.DEFAULT_PLAN)
        entries = _journal(run_dir)
        by_step = {e["step"]: e for e in entries if "step" in e}
        # EVERY plan step is journaled executed-or-validated with a
        # named reason (the acceptance criterion)
        for step in plan["steps"]:
            ent = by_step[step["id"]]
            assert ent["status"] in ("ok", "validated"), ent
            if ent["status"] != "ok":
                assert ent["reason"].startswith("dry-run"), ent
        # the doctor EXECUTED for real and its block is in the report
        assert by_step["doctor"]["status"] == "ok"
        rep = _report(run_dir)
        assert rep["schema"] == chip_run.REPORT_SCHEMA
        assert rep["gate"]["verdict"] == "dry-validated"
        assert rep["backend"] == "cpu"
        assert rep["doctor"]["schema"] == "lightgbm_tpu/doctor/v1"
        assert rep["doctor"]["verdict"] == "clean"
        assert len(rep["steps"]) == len(plan["steps"])

    def test_resume_skips_completed_steps(self, tmp_path):
        run_dir = str(tmp_path / "run")
        # killed run: halts after the doctor completes
        assert chip_run.main(["--dry-run", "--dir", run_dir,
                              "--halt-after", "doctor"]) == 0
        assert _report(run_dir)["gate"]["verdict"] == "halted"
        # resume: one MERGED journal, the doctor is skipped by digest
        # (exactly one executed entry), the rest completes
        assert chip_run.main(["--dry-run", "--dir", run_dir]) == 0
        entries = _journal(run_dir)
        doctor_entries = [e for e in entries
                          if e.get("step") == "doctor"]
        assert len(doctor_entries) == 1, \
            "resume re-executed the completed doctor step"
        headers = [e for e in entries
                   if e.get("schema") == chip_run.JOURNAL_SCHEMA]
        assert len(headers) == 2 and headers[1]["resumed"]
        rep = _report(run_dir)
        assert rep["gate"]["verdict"] == "dry-validated"
        assert rep["gate"]["cached"] >= 1
        doc_row = [s for s in rep["steps"] if s["id"] == "doctor"][0]
        assert doc_row.get("resumed") is True

    def test_halt_after_unknown_step_rejected(self, tmp_path, capsys):
        rc = chip_run.main(["--dry-run", "--dir",
                            str(tmp_path / "r"),
                            "--halt-after", "nope"])
        assert rc == 2
        assert "not a step id" in capsys.readouterr().out

    def test_unusable_plan_exits_2(self, tmp_path, capsys):
        bad = tmp_path / "plan.json"
        bad.write_text('{"schema": ')
        assert chip_run.main(["--plan", str(bad), "--dir",
                              str(tmp_path / "r")]) == 2
        assert "chip_run:" in capsys.readouterr().out


def _synth_plan(tmp_path, steps):
    plan = {"schema": chip_run.PLAN_SCHEMA, "round": 99,
            "defaults": {"timeout_s": 120, "retries": 0},
            "steps": steps}
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(plan))
    return str(p)


class TestChipRunQuarantine:
    def test_quarantined_step_degrades_not_kills(self, tmp_path):
        plan_path = _synth_plan(tmp_path, [
            {"id": "fail", "cmd": [sys.executable, "-c",
                                   "import sys; sys.exit(3)"],
             "retries": 1, "gate": True},
            {"id": "dep", "cmd": [sys.executable, "-c", "print('d')"],
             "needs": ["fail"]},
            {"id": "indep", "cmd": [sys.executable, "-c",
                                    "print('i')"]},
        ])
        run_dir = str(tmp_path / "run")
        rc = chip_run.main(["--plan", plan_path, "--dir", run_dir])
        assert rc == 1
        by_step = {e["step"]: e for e in _journal(run_dir)
                   if "step" in e}
        fail = by_step["fail"]
        assert fail["status"] == "quarantined"
        assert fail["attempts"] == 2          # retried once
        assert "exit 3" in fail["reason"]
        dep = by_step["dep"]
        assert dep["status"] == "skipped"
        assert "gated by fail" in dep["reason"]
        # one failing step degrades to a named finding: the
        # independent step still ran
        assert by_step["indep"]["status"] == "ok"
        rep = _report(run_dir, rnd=99)
        assert rep["gate"]["verdict"] == "fail"
        assert rep["gate"]["quarantined"] == ["fail"]
        assert rep["gate"]["skipped"] == ["dep"]
        codes = [f["code"] for f in rep["findings"]]
        assert "QUARANTINED_FAIL" in codes

    def test_resume_reruns_quarantined_and_skipped(self, tmp_path):
        flag = tmp_path / "now_pass"
        code = (f"import os, sys; "
                f"sys.exit(0 if os.path.exists({str(flag)!r}) else 3)")
        plan_path = _synth_plan(tmp_path, [
            {"id": "flaky", "cmd": [sys.executable, "-c", code]},
            {"id": "dep", "cmd": [sys.executable, "-c", "print(1)"],
             "needs": ["flaky"]},
        ])
        run_dir = str(tmp_path / "run")
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 1
        flag.write_text("")
        # resume: the quarantined step re-runs (failure is never
        # terminal), its skipped dependent re-evaluates and runs
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 0
        by_step = {}
        for e in _journal(run_dir):
            if "step" in e:
                by_step.setdefault(e["step"], []).append(e)
        assert [e["status"] for e in by_step["flaky"]] \
            == ["quarantined", "ok"]
        assert [e["status"] for e in by_step["dep"]] \
            == ["skipped", "ok"]

    def test_timeout_quarantines_and_keeps_partial_output(
            self, tmp_path):
        plan_path = _synth_plan(tmp_path, [
            {"id": "hang", "cmd": [
                sys.executable, "-u", "-c",
                "print('PARTIAL_PROGRESS'); "
                "import time; time.sleep(30)"],
             "timeout_s": 2},
        ])
        run_dir = str(tmp_path / "run")
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 1
        [hang] = [e for e in _journal(run_dir)
                  if e.get("step") == "hang"]
        assert hang["status"] == "quarantined"
        assert "timed out" in hang["reason"]
        # the partial child output is the debugging artifact for WHY
        # an expensive step hung — it must land in the step log
        with open(os.path.join(run_dir, "logs", "hang.log")) as f:
            assert "PARTIAL_PROGRESS" in f.read()

    def test_env_placeholders_resolve(self, tmp_path):
        # {dir} in a step's env values must resolve exactly like cmd
        # tokens (LGBM_TPU_XPLANE/TRACE point into the run dir)
        plan_path = _synth_plan(tmp_path, [
            {"id": "probe", "cmd": [
                sys.executable, "-c",
                "import os; open(os.environ['PROBE_OUT'], 'w')"
                ".write('x')"],
             "env": {"PROBE_OUT": "{dir}/probe.txt"}},
        ])
        run_dir = str(tmp_path / "run")
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 0
        assert os.path.exists(os.path.join(run_dir, "probe.txt"))

    def test_killed_bench_step_resumes_from_checkpoint(self, tmp_path):
        # ISSUE 13: a bench step SIGKILLed mid-training (the injected
        # death class) quarantines with the 'preemption' bring-up
        # class; the resumed chip_run re-runs it and the step picks
        # its training back up from the checkpoint the killed process
        # left behind — NOT from tree 0
        run_dir = str(tmp_path / "run")
        step = {
            "id": "bench_ckpt",
            "cmd": [sys.executable, "bench.py", "--smoke", "--rows",
                    "3000", "--iters", "6", "--leaves", "15",
                    "--resume", "--no-preflight", "--json",
                    "{dir}/bench_ckpt.json"],
            "env": {"LGBM_TPU_CKPT_DIR": "{dir}/ckpt",
                    "LGBM_TPU_CKPT_EVERY": "2",
                    "LGBM_TPU_FAULT": "death@4"},
            "artifact": "{dir}/bench_ckpt.json",
            "timeout_s": 600,
        }
        plan_path = _synth_plan(tmp_path, [step])
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 1
        [killed] = [e for e in _journal(run_dir)
                    if e.get("step") == "bench_ckpt"]
        assert killed["status"] == "quarantined"
        assert killed["rc"] == -9
        assert killed["bringup_class"] == "preemption"
        rep = _report(run_dir, rnd=99)
        [row] = rep["steps"]
        assert row["bringup_class"] == "preemption"
        [f] = [x for x in rep["findings"]
               if x["code"] == "QUARANTINED_BENCH_CKPT"]
        assert f["detail"]["bringup_class"] == "preemption"
        # the killed process left a verified checkpoint behind
        assert os.path.exists(os.path.join(run_dir, "ckpt", "LATEST"))
        # disarm the fault and resume the run: quarantined is never
        # terminal, so the step re-runs — and continues from the
        # snapshot (one merged journal records both attempts)
        step["env"] = {k: v for k, v in step["env"].items()
                       if k != "LGBM_TPU_FAULT"}
        plan_path = _synth_plan(tmp_path, [step])
        assert chip_run.main(["--plan", plan_path, "--dir",
                              run_dir]) == 0
        entries = [e for e in _journal(run_dir)
                   if e.get("step") == "bench_ckpt"]
        assert [e["status"] for e in entries] == ["quarantined", "ok"]
        with open(os.path.join(run_dir, "bench_ckpt.json")) as f:
            rec = json.load(f)
        # the record proves the resume: training continued from
        # iteration 4 (2 warmup + 2 timed before the kill), so the
        # step did not restart tree 0.  One post-resume update pays
        # the fresh process's jit compile OUTSIDE the timed window,
        # so 3 of the remaining 4 iterations are timed
        assert rec["ckpt"]["resumed_from"] == 4
        assert rec["ckpt"]["iters_timed"] == 3

    def test_real_run_with_skipped_gates_is_incomplete(self, tmp_path):
        # a REAL run on the wrong backend skips every capture gate and
        # produces zero records — that must NOT read as a passing run
        doctor_code = ("import json, os, sys; "
                       "json.dump({'backend': 'cpu'}, "
                       "open(sys.argv[1], 'w'))")
        plan_path = _synth_plan(tmp_path, [
            {"id": "doctor", "cmd": [sys.executable, "-c",
                                     doctor_code, "{dir}/doctor.json"],
             "gate": True, "artifact": "{dir}/doctor.json"},
            {"id": "smoke", "cmd": [sys.executable, "-c", "print(1)"],
             "needs": ["doctor"], "requires_backend": "tpu",
             "gate": True},
        ])
        run_dir = str(tmp_path / "run")
        rc = chip_run.main(["--plan", plan_path, "--dir", run_dir])
        assert rc == 1
        rep = _report(run_dir, rnd=99)
        assert rep["gate"]["verdict"] == "incomplete"
        codes = [f["code"] for f in rep["findings"]]
        assert "GATE_SKIPPED_SMOKE" in codes


# ---------------------------------------------------------------------
# trend view
# ---------------------------------------------------------------------
_TREND_FIXTURES = [os.path.join(DATA, name)
                   for name, _ in trend.synthetic_trend_records()]


class TestTrend:
    def test_pinned_table_over_synthetic_records(self, capsys):
        rc = trend.run_trend(list(_TREND_FIXTURES))
        out = capsys.readouterr().out
        with open(os.path.join(DATA, "trend_expected.txt")) as f:
            expected = f.read()
        assert out == expected, \
            ("trend table drifted from tests/data/trend_expected.txt "
             "— regenerate with python -m lightgbm_tpu.obs.trend if "
             "intended")
        # the fixture trajectory carries an injected drift: exit 1
        assert rc == 1

    def test_fixture_records_current(self):
        # the checked-in fixture records must match the generator (a
        # drifted fixture silently un-pins the table)
        for name, rec in trend.synthetic_trend_records():
            with open(os.path.join(DATA, name)) as f:
                assert json.load(f) == rec, f"{name} stale — " \
                    "regenerate with python -m lightgbm_tpu.obs.trend"

    def test_no_drift_without_regression(self, capsys):
        rc = trend.run_trend(_TREND_FIXTURES[:2])
        assert rc == 0
        assert "no drift" in capsys.readouterr().out

    def test_route_change_annotated_not_scored(self, tmp_path,
                                               capsys):
        _, a = trend.synthetic_trend_records()[1]
        b = json.loads(json.dumps(a))
        b["value"] = 1.0                       # huge drop, BUT
        b["routing"]["digest"] = "ffffffffffff"   # different path
        b["timestamp"] = "2026-07-02T00:00:00+00:00"
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        pa.write_text(json.dumps(a))
        pb.write_text(json.dumps(b))
        rc = trend.run_trend([str(pa), str(pb)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "route change" in out
        assert "METRIC_DRIFT" not in out

    def test_mid_trajectory_legacy_does_not_mask_drift(self, tmp_path,
                                                       capsys):
        # [v3 good, legacy v2, v3 drifted]: the legacy record in the
        # middle must not become the comparison base — the drift
        # between the v3 records around it is still flagged
        _, good = trend.synthetic_trend_records()[1]
        _, legacy = trend.synthetic_trend_records()[0]
        bad = json.loads(json.dumps(good))
        bad["value"] = 2.0
        bad["timestamp"] = "2026-07-03T00:00:00+00:00"
        legacy = dict(legacy,
                      timestamp="2026-06-15T00:00:00+00:00")
        paths = []
        for i, rec in enumerate((good, legacy, bad)):
            p = tmp_path / f"r{i}.json"
            p.write_text(json.dumps(rec))
            paths.append(str(p))
        rc = trend.run_trend(paths)
        out = capsys.readouterr().out
        assert rc == 1
        assert "METRIC_DRIFT" in out

    def test_legacy_recapture_pointer(self, capsys):
        trend.run_trend([_TREND_FIXTURES[0]])
        out = capsys.readouterr().out
        assert "legacy lightgbm_tpu/bench/v2" in out
        assert "re-capture" in out

    def test_directory_input(self, tmp_path, capsys):
        for src in _TREND_FIXTURES[:2]:
            with open(src) as f:
                (tmp_path / os.path.basename(src)).write_text(f.read())
        assert trend.run_trend([str(tmp_path)]) == 0
        assert "2 record(s)" in capsys.readouterr().out

    def test_unreadable_inputs(self, tmp_path, capsys):
        assert trend.run_trend(["/nonexistent/dir"]) == 2
        garbage = tmp_path / "g.json"
        garbage.write_text("{not json")
        assert trend.run_trend([str(garbage)]) == 2
        out = capsys.readouterr().out
        assert "Traceback" not in out

    def test_cli_routing(self, capsys):
        rc = report_main(["trend"] + list(_TREND_FIXTURES[:2]))
        assert rc == 0
        assert "bench trajectory" in capsys.readouterr().out
