"""BENCHMARK.json and the files it names say the same thing, and a cell,
a configuration or a per-layer metric is found by its file alone."""
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_has_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    layer = {m["name"]: m for m in spec["per_layer"]}
    for w in spec["workloads"]:
        cell = bench_run.load("workloads", w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert w["why"] == cell["why"] and len(w["why"]) <= 200
        conf = bench_run.load("configs", cell["config"])
        assert configs[w["config"]]["file"] == \
            f"benchmarks/configs/{w['config']}.json"
        assert conf["reduced"] == configs[w["config"]]["reduced"]
        assert conf["chips"] == cell["chips"]
        assert "setup_s" in cell["end_to_end"]
        assert set(cell["end_to_end"]) <= e2e
        assert os.path.isfile(os.path.join(
            BENCH, "kinds", cell["kind"] + ".py"))
        for name in cell["per_layer"]:
            assert name in layer, name
            where = layer[name].get("workloads")
            assert where is None or w["name"] in where


def test_end_to_end_entries_mirror_their_files(spec):
    for m in spec["end_to_end"]:
        f = bench_run.load("end_to_end", m["name"])
        for key in ("name", "unit", "better", "source"):
            assert f[key] == m[key], (m["name"], key)
        assert 0 < m["bound"] <= 0.1


def test_per_layer_entries_mirror_their_files(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        f = bench_run.load("layer_metrics", m["name"])
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert f[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        assert hasattr(bench_run.module("reducers", f["reducer"]), "reduce")


def test_names(spec):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[group]:
            assert NAME.match(entry["name"]), entry["name"]
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_no_tpu_is_exit_1_and_no_result_line(spec):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 1 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    import peaks
    assert peaks.of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks.of("TPU v9")
