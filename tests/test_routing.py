"""Routing model + golden matrix + runtime parity (ISSUE 10).

Three layers:

* unit: the declarative model (``ops/routing.py decide``) reproduces
  the documented path semantics cell by cell, and the config helpers
  (``config.env_knob``) behave;
* golden: the checked-in routing matrix
  (``lightgbm_tpu/analysis/routing_matrix.json``) matches a fresh
  enumeration byte-for-byte and every row_order cell is justified;
* runtime parity (the ISSUE acceptance): for sampled lattice cells a
  REAL CPU training engages exactly the path the matrix predicts —
  stream / physical / row_order, pack, scheme, merge — with the
  structured fallback events recorded.
"""
import json
import os
import sys

import numpy as np
import pytest

from conftest import restore_env_knobs as _restore_env
from conftest import save_env_knobs as _save_env

_MATRIX_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir,
    "lightgbm_tpu", "analysis", "routing_matrix.json")

# knobs every fresh train pins (None = unset): parity cells are keyed
# on the SHIPPING defaults, and an ambient CI export (e.g. the leg-2
# fallback knobs) must not silently reroute them
_BASE_ENV = {"LGBM_TPU_PHYS": None, "LGBM_TPU_STREAM": None,
             "LGBM_TPU_FUSED": None, "LGBM_TPU_PARTITION": None,
             "LGBM_TPU_PART_INTERP": None,
             "LGBM_TPU_HIST_SCATTER": None}


def _matrix():
    with open(_MATRIX_PATH) as fh:
        return json.load(fh)


def _fresh_train(env, params=None, n=600, f=5, rounds=1, data="dense"):
    """Train a tiny booster in a fresh library generation under
    ``env`` and return the engaged-path facts + routing decision."""
    saved = _save_env(tuple(_BASE_ENV))
    merged = dict(_BASE_ENV)
    merged.update(env or {})
    for k, v in merged.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs.counters import events
        rng = np.random.default_rng(0)
        p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        p.update(params or {})
        group = None
        if data in ("dense", "wide", "rank", "overwide"):
            # "wide": 130 feature columns, a comb line of two planes;
            # "overwide": 2,100, past the sixteen planes any comb
            # kernel stages
            x = rng.normal(size=(n, {"wide": 130, "overwide": 2100}.get(
                data, f))).astype(np.float32)
            y = (x[:, 0] + 0.5 * x[:, 1] > 0)
            if data == "rank":
                y = rng.integers(0, 5, size=n)
                group = [n // 20] * 20
        elif data == "cat":
            x = rng.normal(size=(n, f)).astype(np.float32)
            x[:, 0] = rng.integers(0, 12, size=n)
            y = (x[:, 1] > 0)
            p.setdefault("categorical_feature", "0")
        elif data in ("onehot", "wide_onehot"):
            # "wide_onehot": bundles whose UNBUNDLED width (140 + 3
            # columns) crosses into the second plane
            k = 24 if data == "onehot" else 140
            c = rng.integers(0, k, size=n)
            onehot = np.zeros((n, k), np.float32)
            onehot[np.arange(n), c] = 1.0
            dense = rng.normal(size=(n, 3)).astype(np.float32)
            x = np.hstack([onehot, dense])
            y = (c % 4 == 0)
            p.setdefault("max_bin", 31)
            p.setdefault("min_data_in_bin", 1)
        if p.get("objective") == "multiclass":
            y = rng.integers(0, p.get("num_class", 3), size=n)
        y = np.asarray(y, np.float32)
        bst = lgb.train(p, lgb.Dataset(x, label=y, group=group),
                        num_boost_round=rounds)
        inner = bst._inner
        grow = inner.grow
        stream = bool(getattr(inner, "_stream_grad", False))
        physical = (type(grow).__name__ == "_PhysicalGrow"
                    or bool(getattr(grow, "physical", False)))
        return {
            "routing": inner.routing_info(),
            "engaged_path": ("stream" if stream
                            else "physical" if physical
                            else "row_order"),
            "comb_C": getattr(grow, "_C", None),
            "grow_fused": getattr(grow, "fused", None),
            "hist_scatter": getattr(grow, "hist_scatter", None),
            "bundled": inner.dd.bundle is not None,
            "events": events.totals(),
        }
    finally:
        _restore_env(saved)
        for m in [k for k in list(sys.modules)
                  if k.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_matches_matrix(out):
    """The runtime decision's cell must exist in the golden matrix and
    predict the ENGAGED path/scheme/merge exactly."""
    from lightgbm_tpu.ops.routing import decode_cell
    r = out["routing"]
    assert r is not None, "no routing decision on the booster"
    cells = _matrix()["cells"]
    assert r["cell"] in cells, \
        f"runtime cell not in the golden matrix: {r['cell']}"
    cell = decode_cell(cells[r["cell"]])
    assert cell["path"] == r["path"] == out["engaged_path"], (
        cell, r, out["engaged_path"])
    assert r["pack"] == 1
    assert cell["scheme"] == r["scheme"]
    assert cell["merge"] == r["hist_merge"]
    assert cell["reasons"] == r["reasons"]
    if (out["engaged_path"] != "row_order"
            and out["grow_fused"] is not None):
        assert bool(out["grow_fused"]) == bool(r["fused"])


# ---------------------------------------------------------------------
# golden matrix currency + justification
# ---------------------------------------------------------------------
def test_matrix_is_current():
    """The checked-in golden equals a fresh enumeration BYTE-FOR-BYTE
    (the fixture-currency acceptance; regenerate with
    python -m lightgbm_tpu.ops.routing)."""
    from lightgbm_tpu.ops import routing
    with open(_MATRIX_PATH, "rb") as fh:
        golden = fh.read()
    assert golden == routing.canonical_bytes(routing.enumerate_matrix())


def test_every_row_order_cell_is_justified():
    from lightgbm_tpu.ops.routing import decode_cell
    doc = _matrix()
    n_row_order = 0
    for key, enc in doc["cells"].items():
        c = decode_cell(enc)
        if c["path"] == "row_order":
            n_row_order += 1
            assert c["reasons"], f"unjustified row_order cell: {key}"
        else:
            assert not c["reasons"] or c["path"] == "physical", key
    assert n_row_order > 0
    assert doc["summary"]["n_cells"] == len(doc["cells"])
    # the bench-priority ranking covers every loud fallback rule;
    # efb_bundle graduated in ISSUE 12, cat_subset in ISSUE 16 — only
    # the over-wide residues remain priced
    pri = {p["reason"] for p in doc["summary"]["bench_priority"]}
    assert {"efb_overwide", "non_u8_bins", "gpu_use_dp", "cegb_lazy",
            "cat_overwide", "n_pad_overflow", "comb_overwide"} == pri
    assert "efb_bundle" not in doc["summary"]["fallback_reasons"]
    assert "cat_subset" not in doc["summary"]["fallback_reasons"]


# ---------------------------------------------------------------------
# model unit semantics
# ---------------------------------------------------------------------
def test_decide_semantics():
    from lightgbm_tpu.ops.routing import RouteInputs, decide
    tpu = dict(backend="tpu")
    # shipping default on chip: l2 objective streams
    d = decide(RouteInputs(**tpu))
    assert (d.path, d.pack, d.scheme, d.fused) == \
        ("stream", 1, "permute", True)
    assert d.reasons == ()
    # config fallbacks are named
    d = decide(RouteInputs(gpu_use_dp=True, **tpu))
    assert d.path == "row_order" and d.reasons == ("gpu_use_dp",)
    # EFB GRADUATED (ISSUE 12): bundles alone no longer cost the fast
    # path — an l2-streamable bundled config streams
    d = decide(RouteInputs(efb_bundled=True, **tpu))
    assert d.path == "stream" and d.reasons == ()
    d = decide(RouteInputs(efb_bundled=True, cegb_lazy=True, **tpu))
    assert d.path == "row_order" and set(d.reasons) == {"cegb_lazy"}
    # ... except the over-wide bundle expansion, which falls back
    # loudly under the narrow shape rule
    d = decide(RouteInputs(efb_bundled=True, efb_overwide=True, **tpu))
    assert d.path == "row_order" and d.reasons == ("efb_overwide",)
    # the shape fact alone (no bundling) never fires the rule
    d = decide(RouteInputs(efb_overwide=True, **tpu))
    assert d.path == "stream"
    # a dense line no kernel stages falls back by its own name
    d = decide(RouteInputs(comb_overwide=True, **tpu))
    assert d.path == "row_order" and d.reasons == ("comb_overwide",)
    assert "cw=1" in d.cell and "cw=" not in decide(
        RouteInputs(**tpu)).cell
    # stream blockers leave the physical path engaged
    d = decide(RouteInputs(bagging=True, **tpu))
    assert d.path == "physical" and d.reasons == ("bagging_on",)
    d = decide(RouteInputs(objective_kind="other", multi_tree=True,
                           **tpu))
    assert d.path == "physical"
    assert set(d.reasons) == {"objective_not_streamable",
                              "multi_tree_iter"}
    # the scheme is the compaction knob's, on the physical path only
    d = decide(RouteInputs(partition_env="matmul", **tpu))
    assert (d.pack, d.scheme) == (1, "matmul")
    d = decide(RouteInputs(partition_env="matmul", gpu_use_dp=True,
                           **tpu))
    assert (d.pack, d.scheme) == (1, "none")
    # mesh merge rules
    d = decide(RouteInputs(learner="data", n_shards=8, **tpu))
    assert d.path == "physical" and d.hist_merge == "scatter"
    assert "mesh_stream_unwired" in d.reasons
    d = decide(RouteInputs(learner="data", n_shards=8,
                           f_log_shard_divisible=False, **tpu))
    assert d.hist_merge == "psum"
    assert d.merge_reasons == ("scatter_f_log_indivisible",)
    # env gates
    d = decide(RouteInputs(backend="cpu"))
    assert d.path == "row_order" and d.reasons == ("backend_not_tpu",)
    d = decide(RouteInputs(backend="cpu", phys_env="interpret"))
    assert d.path == "stream"
    d = decide(RouteInputs(phys_env="0", **tpu))
    assert d.path == "row_order" and d.reasons == ("phys_env_off",)
    # digests identify the ENGAGED path, not the reasons
    a = decide(RouteInputs(gpu_use_dp=True, **tpu))
    b = decide(RouteInputs(cegb_lazy=True, **tpu))
    assert a.digest() == b.digest()
    assert a.digest() != decide(RouteInputs(**tpu)).digest()


def test_cat_subset_graduated_semantics():
    """ISSUE 16: cat-subset configs ride the fast path; only the
    over-256-bin bitset corner still walks back, loudly, alongside
    the u16-bin rule it implies."""
    from lightgbm_tpu.ops.routing import RULES, RouteInputs, decide
    tpu = dict(backend="tpu")
    d = decide(RouteInputs(cat_subset=True, **tpu))
    assert (d.path, d.reasons) == ("stream", ())
    d = decide(RouteInputs(cat_subset=True, bagging=True, **tpu))
    assert d.path == "physical" and d.reasons == ("bagging_on",)
    d = decide(RouteInputs(cat_subset=True, bins_u8=False, **tpu))
    assert d.path == "row_order"
    assert set(d.reasons) == {"cat_overwide", "non_u8_bins"}
    # wide bins WITHOUT subset cats never fire the cat rule
    d = decide(RouteInputs(bins_u8=False, **tpu))
    assert set(d.reasons) == {"non_u8_bins"}
    # the graduated rules are gone from the rule table for good
    names = {r.name for r in RULES}
    assert {"cat_subset", "scatter_cat_subset"} & names == set()
    assert "cat_overwide" in names
    # and the scatter merge no longer walks back for cat configs
    d = decide(RouteInputs(cat_subset=True, learner="data", n_shards=8,
                           **tpu))
    assert d.hist_merge == "scatter" and d.merge_reasons == ()


def test_n_pad_overflow_boundary():
    """Satellite (ISSUE 16): the 2^24-row physical-mode ceiling.  The
    booster derives ``rows_over_limit`` per shard with the alloc slack
    subtracted (models/gbdt.py); pin the exact flip point shape-only
    through routing.decide — no training."""
    from lightgbm_tpu.ops.grow import PHYS_ROW_SLACK
    from lightgbm_tpu.ops.routing import RouteInputs, decide
    limit = (1 << 24) - PHYS_ROW_SLACK

    def facts(n_pad, n_shards):
        # the gbdt.py boundary expression, verbatim
        return dict(rows_over_limit=bool(n_pad // n_shards >= limit),
                    learner="serial" if n_shards == 1 else "data",
                    n_shards=n_shards, backend="tpu")

    for shards in (1, 8):
        under = decide(RouteInputs(**facts(shards * limit - 1, shards)))
        at = decide(RouteInputs(**facts(shards * limit, shards)))
        assert "n_pad_overflow" not in under.reasons, shards
        assert under.path in ("stream", "physical")
        assert at.path == "row_order", shards
        assert "n_pad_overflow" in at.reasons, shards


def test_encode_decode_roundtrip():
    from lightgbm_tpu.ops.routing import (RouteInputs, decide,
                                          decode_cell, encode_cell)
    d = decide(RouteInputs(gpu_use_dp=True, partition_env="matmul"))
    c = decode_cell(encode_cell(d))
    assert c["path"] == d.path and c["reasons"] == list(d.reasons)
    assert c["scheme"] == d.scheme
    assert c["program_key"] == d.program_key
    with pytest.raises(ValueError):
        decode_cell("not-a-cell")


def test_env_knob_helper():
    from lightgbm_tpu.config import env_knob
    assert env_knob("LGBM_TPU_PHYS", environ={}) == "auto"
    assert env_knob("LGBM_TPU_STREAM", environ={}) == "auto"
    assert env_knob("LGBM_TPU_PARTITION", environ={}) == "permute"
    assert env_knob("LGBM_TPU_PHYS",
                    environ={"LGBM_TPU_PHYS": "0"}) == "0"
    # empty string means unset, not "empty default"
    assert env_knob("LGBM_TPU_PHYS",
                    environ={"LGBM_TPU_PHYS": ""}) == "auto"
    with pytest.raises(KeyError):
        env_knob("LGBM_TPU_NO_SUCH_KNOB")


def test_report_fallbacks_events_and_warn_once():
    import lightgbm_tpu.ops.routing as routing
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs.counters import events
    obs.reset_run()
    d = routing.decide(routing.RouteInputs(gpu_use_dp=True,
                                           efb_bundled=True,
                                           efb_overwide=True))
    routing.report_fallbacks(d)
    routing.report_fallbacks(d)
    t = events.totals()
    # events count every occurrence; the log line is warn-once
    assert t["routing_fallback_gpu_use_dp"] == 2
    assert t["routing_fallback_efb_overwide"] == 2
    # the GRADUATED rule's event name must be gone for good
    assert "routing_fallback_efb_bundle" not in t
    assert {"gpu_use_dp", "efb_overwide"} <= routing._ROUTING_WARNED
    # env/backend fallbacks stay quiet
    obs.reset_run()
    assert routing._ROUTING_WARNED == set()
    routing.report_fallbacks(
        routing.decide(routing.RouteInputs(backend="cpu")))
    assert not events.totals()


# ---------------------------------------------------------------------
# obs diff: routing-path mismatch is incomparable (exit 2)
# ---------------------------------------------------------------------
def _rec(digest, path="physical"):
    return {"schema": "lightgbm_tpu/bench/v3", "metric": "m",
            "value": 1.0, "unit": "iters/sec",
            "routing": {"digest": digest, "path": path, "pack": 1,
                        "scheme": "permute", "hist_merge": "none"}}


def test_obs_diff_routing_mismatch(tmp_path):
    from lightgbm_tpu.obs.regress import diff_paths, diff_records
    finds, inc = diff_records(_rec("aaa"), _rec("bbb", "row_order"))
    assert any("routing-path mismatch" in m for m in inc), inc
    # same digest: comparable
    _, inc2 = diff_records(_rec("aaa"), _rec("aaa"))
    assert not inc2
    # one side missing the block (older record): still comparable
    old = _rec("aaa")
    del old["routing"]
    _, inc3 = diff_records(old, _rec("aaa"))
    assert not inc3
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(_rec("aaa")))
    b.write_text(json.dumps(_rec("bbb")))
    assert diff_paths(str(a), str(b)) == 2
    assert diff_paths(str(a), str(b), allow_knob_mismatch=True) == 0


# ---------------------------------------------------------------------
# analyzer pass: clean, fixtures detected, mutation detected
# ---------------------------------------------------------------------
def test_routing_pass_clean_strict():
    from lightgbm_tpu.analysis import run_analysis
    rep = run_analysis(passes=["routing"], strict=True)
    assert rep.failing() == [], [f.to_json() for f in rep.failing()]


def test_fixture_bad_route():
    from lightgbm_tpu.analysis import run_analysis
    rep = run_analysis(passes=["routing"], fixtures=["bad_route"])
    hits = [f for f in rep.failing()
            if f.code == "ROUTING_UNJUSTIFIED_FALLBACK"]
    assert hits and all(f.fixture for f in hits)


def test_fixture_comb_overwide():
    """A cell that blames comb_overwide without the shape
    fact in its key is refused by name."""
    from lightgbm_tpu.analysis import run_analysis
    rep = run_analysis(passes=["routing"], fixtures=["comb_overwide"])
    hits = [f for f in rep.failing()
            if f.code == "ROUTING_COMB_OVERWIDE_UNJUSTIFIED"]
    assert hits and all(f.fixture for f in hits)


def test_fixture_bad_retrace():
    from lightgbm_tpu.analysis import run_analysis
    rep = run_analysis(passes=["routing"], fixtures=["bad_retrace"])
    hits = [f for f in rep.failing() if f.code == "ROUTING_RETRACE"]
    assert hits and all(f.fixture for f in hits)
    assert "fixture-bad-retrace" in hits[0].where


def test_mutated_matrix_cell_fails(tmp_path):
    from lightgbm_tpu.analysis import run_analysis
    doc = _matrix()
    key = next(k for k, v in doc["cells"].items()
               if "path=stream" in v)
    doc["cells"][key] = (doc["cells"][key]
                         .replace("path=stream", "path=row_order"))
    p = tmp_path / "mut.json"
    p.write_text(json.dumps(doc))
    rep = run_analysis(passes=["routing"],
                       routing_matrix_path=str(p))
    codes = {f.code for f in rep.failing()}
    assert "ROUTING_MATRIX_STALE" in codes
    assert "ROUTING_UNJUSTIFIED_FALLBACK" in codes


# ---------------------------------------------------------------------
# runtime parity: the engaged path equals the matrix's prediction
# (the ISSUE-10 acceptance golden test)
# ---------------------------------------------------------------------
SERIAL_CELLS = [
    # (name, env, params, data, expected path, expected reasons subset)
    ("phys_env_off", {"LGBM_TPU_PHYS": "0"}, {}, "dense",
     "row_order", {"phys_env_off"}),
    ("stream_default", {"LGBM_TPU_PHYS": "interpret"}, {}, "dense",
     "stream", set()),
    ("stream_env_off", {"LGBM_TPU_PHYS": "interpret",
                        "LGBM_TPU_STREAM": "0"}, {}, "dense",
     "physical", {"stream_env_off"}),
    ("bagging", {"LGBM_TPU_PHYS": "interpret"},
     {"bagging_fraction": 0.7, "bagging_freq": 1}, "dense",
     "physical", {"bagging_on"}),
    ("multiclass", {"LGBM_TPU_PHYS": "interpret"},
     {"objective": "multiclass", "num_class": 3}, "dense",
     "physical", {"objective_not_streamable", "multi_tree_iter"}),
    ("gpu_use_dp", {"LGBM_TPU_PHYS": "interpret"},
     {"gpu_use_dp": True}, "dense", "row_order", {"gpu_use_dp"}),
    ("cegb_lazy", {"LGBM_TPU_PHYS": "interpret"},
     {"cegb_penalty_feature_lazy": [0.1, 0.1, 0.1, 0.1, 0.1]},
     "dense", "row_order", {"cegb_lazy"}),
    ("u16_bins", {"LGBM_TPU_PHYS": "interpret"},
     {"max_bin": 300, "min_data_in_bin": 1}, "dense",
     "row_order", {"non_u8_bins"}),
    # cat-subset GRADUATED (ISSUE 16): sorted-subset categorical
    # splits ride the fast path as bitset membership words; only the
    # over-256-bins corner still walks back (paired with non_u8_bins)
    ("cat_subset", {"LGBM_TPU_PHYS": "interpret"},
     {"max_cat_to_onehot": 4}, "cat", "stream", set()),
    ("cat_overwide", {"LGBM_TPU_PHYS": "interpret"},
     {"max_cat_to_onehot": 4, "max_bin": 300, "min_data_in_bin": 1},
     "cat", "row_order", {"cat_overwide", "non_u8_bins"}),
    # a dense table past MAX_COMB_COLS takes row_order by name
    ("comb_overwide", {"LGBM_TPU_PHYS": "interpret"}, {}, "overwide",
     "row_order", {"comb_overwide"}),
    # EFB GRADUATED (ISSUE 12): trained bundled cells now engage the
    # physical fast path (stream on a streamable objective), with the
    # env knobs still walking the bundled config down the same ladder
    # as any other config — three trained EFB cells pin the golden
    # matrix's post-graduation predictions
    ("efb_stream", {"LGBM_TPU_PHYS": "interpret"}, {}, "onehot",
     "stream", set()),
    ("efb_stream_off", {"LGBM_TPU_PHYS": "interpret",
                        "LGBM_TPU_STREAM": "0"}, {}, "onehot",
     "physical", {"stream_env_off"}),
    ("efb_phys_off", {"LGBM_TPU_PHYS": "0"}, {}, "onehot",
     "row_order", {"phys_env_off"}),
    # the route the ranking cell pins (benchmarks/configs/
    # msltr-lambdarank.json expect_route): physical, not streamed
    ("lambdarank", {"LGBM_TPU_PHYS": "interpret"},
     {"objective": "lambdarank"}, "rank",
     "physical", {"objective_not_streamable"}),
    # ... and its width: a comb line of two 128-lane planes, on both
    # routes and under EFB
    ("two_plane_stream", {"LGBM_TPU_PHYS": "interpret"}, {}, "wide",
     "stream", set()),
    ("two_plane_physical", {"LGBM_TPU_PHYS": "interpret",
                            "LGBM_TPU_STREAM": "0"}, {}, "wide",
     "physical", {"stream_env_off"}),
    # (extra_trees: a grow option the bundle-space finder does not
    # cover, so the bundles unbundle at ingest, into two planes)
    ("efb_two_plane", {"LGBM_TPU_PHYS": "interpret"},
     {"extra_trees": True}, "wide_onehot", "stream", set()),
    # ISSUE 36: with the plain finder the same table keeps its bundles
    # in the comb, one plane
    ("efb_kept_one_plane", {"LGBM_TPU_PHYS": "interpret"}, {},
     "wide_onehot", "stream", set()),
]


@pytest.mark.parametrize(
    "name,env,params,data,path,reasons",
    SERIAL_CELLS, ids=[c[0] for c in SERIAL_CELLS])
def test_runtime_parity_serial(name, env, params, data, path, reasons):
    out = _fresh_train(env, params, data=data)
    assert out["engaged_path"] == path, out["routing"]
    assert reasons <= set(out["routing"]["reasons"]), out["routing"]
    if data.endswith("onehot"):
        assert out["bundled"], "EFB did not engage; cell is vacuous"
    if data.endswith("onehot") and path != "row_order":
        kept = not params.get("extra_trees")
        assert out["routing"]["efb"] == ("bundled" if kept
                                         else "unbundled")
    if name == "efb_kept_one_plane":
        assert out["comb_C"] == 128, "the bundles did not stay bundled"
    elif data.startswith("wide"):
        assert out["comb_C"] == 256, "one plane; cell is vacuous"
    _assert_matches_matrix(out)
    # loud config fallbacks recorded as structured events
    for r in reasons & {"gpu_use_dp", "cegb_lazy", "non_u8_bins",
                        "cat_overwide", "efb_overwide", "comb_overwide"}:
        assert out["events"].get(f"routing_fallback_{r}", 0) >= 1, \
            (r, out["events"])
    # the graduated rules' warn-once paths are DEAD code — no run may
    # record their events again
    assert "routing_fallback_efb_bundle" not in out["events"]
    assert "routing_fallback_cat_subset" not in out["events"]


def test_runtime_parity_mesh_data_parallel():
    out = _fresh_train({"LGBM_TPU_PHYS": "interpret"},
                       params={"tree_learner": "data"}, n=1024)
    r = out["routing"]
    assert r["learner"] == "data" and r["n_shards"] == 8
    assert out["engaged_path"] == "physical"
    assert "mesh_stream_unwired" in r["reasons"]
    assert r["hist_merge"] == "scatter"
    assert out["hist_scatter"] is True
    _assert_matches_matrix(out)


def test_runtime_parity_efb_mesh():
    """Bundled data on the 8-shard physical mesh: fast path engaged,
    merge pinned to full-psum by the (still-standing) scatter_efb
    rule (ISSUE 12)."""
    out = _fresh_train({"LGBM_TPU_PHYS": "interpret"},
                       params={"tree_learner": "data"}, n=1024,
                       data="onehot")
    r = out["routing"]
    assert out["bundled"], "EFB did not engage; cell is vacuous"
    assert r["learner"] == "data" and r["n_shards"] == 8
    assert out["engaged_path"] == "physical"
    assert r["hist_merge"] == "psum"
    assert "scatter_efb" in r["merge_reasons"]
    assert out["hist_scatter"] is False
    _assert_matches_matrix(out)
