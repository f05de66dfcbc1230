"""Fused partition+histogram split kernel: fused vs unfused equivalence.

The compiled fused kernel (ops/pallas/fused_split.py) only lowers on
TPU; off-TPU the fused path runs its interpret/XLA reference composition
(the smaller child histogrammed from its contiguous range, sibling by
subtraction — what the compiled route hands on whether its scan named
the smaller child or it had to histogram it again, built from the exact
arithmetic the unfused path uses).
These tests pin the contract the compiled path must also satisfy (and
tools/tpu_smoke.py re-checks on the real chip): trained trees are
BIT-identical with LGBM_TPU_FUSED on and off.

The stream-mode root-histogram carry (the fused refresh building the
next tree's root histogram) rides the same knob and is covered by the
binary/regression configs below (stream engages for those by default).
"""
import os
import sys

import numpy as np
import pytest


def _purge():
    """Drop every cached lightgbm_tpu module so the next import re-reads
    the LGBM_TPU_* knobs (mirrors tools/tpu_smoke._purge_lgb_modules)."""
    for m in [k for k in list(sys.modules) if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]


from conftest import restore_env_knobs as _restore_env
from conftest import save_env_knobs as _save_env


def _tree_bytes(models):
    """What 'the same trees' compares: structure, thresholds and the
    leaf values' BYTES."""
    return [(int(t.num_leaves),
             t.split_feature[:int(t.num_leaves) - 1].tolist(),
             t.threshold_bin[:int(t.num_leaves) - 1].tolist(),
             np.asarray(t.leaf_value).tobytes()) for t in models]


def _fresh_train(fused, n=3000, f=6, rounds=4, objective="binary",
                 part_interp="", partition="", **params):
    saved = _save_env()
    os.environ["LGBM_TPU_PHYS"] = "interpret"
    os.environ["LGBM_TPU_FUSED"] = fused
    if part_interp:
        os.environ["LGBM_TPU_PART_INTERP"] = part_interp
    if partition:
        os.environ["LGBM_TPU_PARTITION"] = partition
    try:
        _purge()
        import lightgbm_tpu as lgb
        rng = np.random.default_rng(0)
        x = rng.normal(size=(n, f)).astype(np.float32)
        x[rng.random(x.shape) < 0.1] = np.nan
        y_raw = (np.nan_to_num(x[:, 0])
                 + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2]))
        y = ((y_raw > 0).astype(np.float32) if objective == "binary"
             else y_raw.astype(np.float32))
        p = {"objective": objective, "num_leaves": 15, "verbosity": -1}
        p.update(params)
        ds = lgb.Dataset(x, label=y)
        bst = lgb.train(p, ds, num_boost_round=rounds)
        return np.asarray(bst.predict(x)), _tree_bytes(bst._models)
    finally:
        _restore_env(saved)
        _purge()


@pytest.mark.parametrize("objective,params", [
    ("binary", {}),                                    # stream (binary)
    ("regression", {}),                                # stream (l2)
    ("binary", {"bagging_fraction": 0.7,
                "bagging_freq": 1}),                   # non-stream physical
    ("binary", {"monotone_constraints": [1, -1, 0, 0, 0, 0]}),
    ("regression", {"monotone_constraints": [1, -1, 0, 0, 0, 0],
                    "path_smooth": 2.0}),
])
def test_fused_bit_identical(objective, params):
    """Trees (splits, thresholds, leaf-value BYTES) and predictions must
    match exactly — the fused path reorganises kernel work, never
    arithmetic."""
    p0, t0 = _fresh_train("0", objective=objective, **params)
    p1, t1 = _fresh_train("1", objective=objective, **params)
    assert len(t0) == len(t1), f"tree counts differ: {len(t0)} != {len(t1)}"
    for i, (a, b) in enumerate(zip(t0, t1)):
        assert a[0] == b[0], f"tree {i}: num_leaves {a[0]} != {b[0]}"
        assert a[1] == b[1], f"tree {i}: split features differ"
        assert a[2] == b[2], f"tree {i}: thresholds differ"
        assert a[3] == b[3], f"tree {i}: leaf values differ bitwise"
    assert np.array_equal(p0, p1), "predictions differ"


@pytest.mark.parametrize("partition", ["permute", "matmul"])
def test_fused_bit_identical_kernel_interpret(partition):
    """Fused vs unfused through the REAL partition kernel bodies
    (LGBM_TPU_PART_INTERP=kernel: Pallas-interpreted scan + copyback,
    compiled row order) for both partition schemes — the deepest
    off-chip rendering of the fused-identity contract."""
    p0, t0 = _fresh_train("0", rounds=2, part_interp="kernel",
                          partition=partition)
    p1, t1 = _fresh_train("1", rounds=2, part_interp="kernel",
                          partition=partition)
    assert len(t0) == len(t1)
    for i, (a, b) in enumerate(zip(t0, t1)):
        assert a == b, f"tree {i} differs (partition={partition})"
    assert np.array_equal(p0, p1)


def test_fused_engaged_and_flagged():
    """The physical grower must report the fused path on (the tpu_smoke
    gate keys off the same attribute), and off under LGBM_TPU_FUSED=0."""
    for fused, expect in (("1", True), ("0", False)):
        saved = _save_env()
        os.environ["LGBM_TPU_PHYS"] = "interpret"
        os.environ["LGBM_TPU_FUSED"] = fused
        try:
            _purge()
            import lightgbm_tpu as lgb
            rng = np.random.default_rng(3)
            x = rng.normal(size=(1500, 4)).astype(np.float32)
            y = (x[:, 0] > 0).astype(np.float32)
            ds = lgb.Dataset(x, label=y)
            bst = lgb.train({"objective": "binary", "num_leaves": 7,
                             "verbosity": -1}, ds, num_boost_round=1)
            grower = bst._inner.grow
            assert getattr(grower, "fused", None) is expect, \
                (fused, type(grower).__name__)
        finally:
            _restore_env(saved)
            _purge()


# ---------------------------------------------------------------------
# ISSUE 30: the scan histograms ONE child, the one the finder's record
# (a hessian-derived left count) calls smaller; where the exact counts
# say otherwise the smaller child is histogrammed again from the comb.
# The grow program counts those splits and their rows, on every route.
# ---------------------------------------------------------------------
def _train_counted(fused, *, weighted, part_interp="", mesh=False,
                   n=3000, rounds=3, crossover=None):
    """Train l2 under LGBM_TPU_PHYS=interpret with a live tracer;
    returns (trees, counter totals, Tree::grow args, every tree's
    parent row counts, one a split).  ``weighted`` puts the hessian
    mass on the FEWER rows: 30% of the rows carry weight 20, the rest
    0.05, and the label steps where the weight does, so the record's
    left count is far off the rows' at the first splits.
    ``crossover`` (a ``pytest.MonkeyPatch`` and a row count) patches
    ``fused_split.hook_crossover_rows`` on the freshly imported
    library."""
    saved = _save_env()
    os.environ["LGBM_TPU_PHYS"] = "interpret"
    os.environ["LGBM_TPU_FUSED"] = fused
    if part_interp:
        os.environ["LGBM_TPU_PART_INTERP"] = part_interp
    try:
        _purge()
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import counters, tracer
        if crossover is not None:
            from lightgbm_tpu.ops.pallas import fused_split
            crossover[0].setattr(fused_split, "hook_crossover_rows",
                                 lambda ngroups: crossover[1])
        rng = np.random.default_rng(5)
        x = rng.normal(size=(n, 5)).astype(np.float32)
        heavy = x[:, 0] > 0.5
        y = (2.0 * heavy + 0.3 * x[:, 1]
             + 0.1 * rng.normal(size=n)).astype(np.float32)
        w = np.where(heavy, 20.0, 0.05).astype(np.float32)
        ds = lgb.Dataset(x, label=y, weight=w if weighted else None)
        p = {"objective": "regression", "num_leaves": 15,
             "min_data_in_leaf": 5, "verbosity": -1}
        if mesh:
            p.update({"tree_learner": "data", "max_bin": 31})
        tracer.enable(None)
        try:
            bst = lgb.train(p, ds, num_boost_round=rounds)
            bst._inner._flush_pending()
            tot = counters.totals()
            args = [e["args"] for e in tracer.events
                    if e["name"] == "Tree::grow"]
        finally:
            tracer.disable()
        return _tree_bytes(bst._inner.models), tot, args, [
            np.asarray(t.internal_count)[:int(t.num_leaves) - 1]
            for t in bst._inner.models]
    finally:
        _restore_env(saved)
        _purge()


# part_kernel runs every split through the Pallas interpreter: one tree
_VARIANTS = {"phys_interpret": {}, "part_kernel": {"part_interp": "kernel",
                                                   "rounds": 1},
             "mesh8": {"mesh": True, "rounds": 2}}
_UNFUSED = {}


def _unfused(variant, weighted, **kw):
    """The LGBM_TPU_FUSED=0 run of a variant, trained once a module."""
    key = (variant, weighted, *sorted(kw.items()))
    if key not in _UNFUSED:
        _UNFUSED[key] = _train_counted("0", weighted=weighted,
                                       **_VARIANTS[variant], **kw)
    return _UNFUSED[key]


@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_side_miss_rehistograms_to_the_unfused_trees(variant):
    """Hessian mass on the side with fewer rows: the record names the
    wrong child at some splits (``side_miss_splits > 0``), those
    children are histogrammed again, and the trees are byte-identical
    to LGBM_TPU_FUSED=0 - on one device, through the real partition
    kernel bodies, and over the 8-shard mesh."""
    t0, tot0, _, _ = _unfused(variant, True)
    t1, tot1, args, _ = _train_counted("1", weighted=True,
                                       **_VARIANTS[variant])
    assert t0 == t1
    assert tot1["side_miss_splits"] > 0
    # a missed split re-reads the SMALLER child: at most half its parent
    assert 0 < tot1["rows_rehistogrammed"] <= tot1["rows_partitioned"] / 2
    assert tot1["side_miss_splits"] <= tot1["splits"]
    # the unfused route predicts nothing
    assert tot0["side_miss_splits"] == tot0["rows_rehistogrammed"] == 0
    assert tot0["splits"] == tot1["splits"]
    # both ride the Tree::grow span, which is where the benchmark's
    # scan_side_miss reads them
    assert sum(a["side_miss_splits"] for a in args) \
        == tot1["side_miss_splits"]
    assert sum(a["rows_rehistogrammed"] for a in args) \
        == tot1["rows_rehistogrammed"]


# ---------------------------------------------------------------------
# ISSUE 35: the hook runs only at parents of up to
# ``fused_split.hook_crossover_rows`` rows (a shard); past it the scan is
# told no child and the smaller one is histogrammed from the comb.  The
# function is patched to 0 (never hook), to a row count that splits the
# tree's parents, and to HOOK_ALWAYS (the parent commit's program).
# ---------------------------------------------------------------------
# the mesh compares the GLOBAL parent with crossover x 8 shards
_MID = {"phys_interpret": 500, "part_kernel": 500, "mesh8": 60}
@pytest.mark.parametrize("cross", ["zero", "mid", "never"])
@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_hook_crossover_keeps_the_unfused_trees(variant, cross,
                                                monkeypatch):
    """Whatever the crossover, the trees are byte-identical to
    LGBM_TPU_FUSED=0 (off the chip both histograms are the reference
    arithmetic), a miss happens only where the hook ran, and at
    crossover 0 it never runs."""
    from lightgbm_tpu.ops.pallas.fused_split import HOOK_ALWAYS
    rows = {"zero": 0, "mid": _MID[variant], "never": HOOK_ALWAYS}[cross]
    t0, tot0, _, _ = _unfused(variant, True)
    t1, tot, args, _ = _train_counted("1", weighted=True,
                                   crossover=(monkeypatch, rows),
                                   **_VARIANTS[variant])
    assert t0 == t1
    assert tot["splits"] == tot0["splits"] > 0
    assert tot0["hook_splits"] == tot0["rows_hooked"] == 0
    assert tot["side_miss_splits"] <= tot["hook_splits"] <= tot["splits"]
    assert tot["rows_rehistogrammed"] <= tot["rows_hooked"] / 2
    if cross == "zero":
        assert tot["hook_splits"] == tot["rows_hooked"] == 0
        assert tot["side_miss_splits"] == tot["rows_rehistogrammed"] == 0
    elif cross == "mid":
        # the first splits' misses (test above) are past the crossover
        assert 0 < tot["hook_splits"] < tot["splits"]
    else:
        assert tot["hook_splits"] == tot["splits"]
        assert tot["side_miss_splits"] > 0
    # both ride the Tree::grow span: scan_rows_hooked reads them there
    assert sum(a["hook_splits"] for a in args) == tot["hook_splits"]
    assert sum(a["rows_hooked"] for a in args) == tot["rows_hooked"]


@pytest.mark.parametrize("variant", ["phys_interpret", "mesh8"])
def test_rows_hooked_is_the_hooked_parents_rows(variant, monkeypatch):
    """Constant hessians and a row count that needs no padding (the
    counter counts the rows the scan visits, a segment's padding rows
    among them): the model's internal counts ARE the parents' rows, so
    the counter can be checked split by split - on the mesh against
    the crossover times the shard count."""
    # whole PHYS_ROW_PAD blocks, a shard: 2 on one device, 1 on each of
    # 8, with the crossover grown as the rows are
    n, rows = ((16384, 4 * _MID[variant]) if variant == "mesh8"
               else (4096, _MID[variant]))
    trees, tot, _, parents = _train_counted(
        "1", weighted=False, n=n, crossover=(monkeypatch, rows),
        **_VARIANTS[variant])
    parents = np.concatenate(parents)
    hooked = parents[parents <= rows * (8 if variant == "mesh8" else 1)]
    assert 0 < len(hooked) < len(parents)
    assert tot["hook_splits"] == len(hooked)
    assert tot["rows_hooked"] == hooked.sum()
    assert tot["rows_partitioned"] == parents.sum()
    assert trees == _unfused(variant, False, n=n)[0]


def test_constant_hessians_never_miss():
    """Unweighted l2: every hessian is 1, the record's left count IS
    the row count - at a row count that needs no padding, whose rows
    the scan moves and no record counts - so the scan is always told
    the smaller child."""
    t0, _, _, _ = _train_counted("0", weighted=False, n=4096)
    t1, tot, _, _ = _train_counted("1", weighted=False, n=4096)
    assert t0 == t1 and tot["splits"] > 0
    assert tot["side_miss_splits"] == 0
    assert tot["rows_rehistogrammed"] == 0


@pytest.mark.parametrize("ngroups", [1, 2, 4, 11, 18, 64])
def test_hook_crossover_stays_inside_its_measurements(ngroups):
    """``hook_crossover_rows`` is linear in the group count BETWEEN the
    two geometries it was measured at and holds the nearer one's value
    outside them: a line through two points read 0 rows at one group
    (ISSUE 37), which no chip run had said."""
    from lightgbm_tpu.ops.pallas.fused_split import (_CROSSOVER_MEASURED,
                                                     hook_crossover_rows)
    (g0, (f0, r0)), (g1, (f1, r1)) = sorted(_CROSSOVER_MEASURED.items())
    ends = {g0: int(f0 * 1e3 / r0), g1: int(f1 * 1e3 / r1)}
    got = hook_crossover_rows(ngroups)
    assert got == ends[g0 if ngroups <= g0 else g1] \
        or (g0 < ngroups < g1
            and min(ends.values()) < got < max(ends.values()))


@pytest.mark.parametrize("side", ["left", "right"])
def test_fused_kernel_contract_interpret(side):
    """Kernel-level contract via the interpret builder: partition result
    matches make_partition_ss and the histogram of the child
    ``sel[SEL_SIDE]`` names equals the comb-direct histogram of that
    child's contiguous range."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    from lightgbm_tpu.ops.pallas.hist_kernel2 import build_histogram_comb
    from lightgbm_tpu.ops.pallas.partition_kernel import (SEL_CNT, SEL_S0,
                                                          SEL_SIDE)
    from lightgbm_tpu.ops.pallas.partition_kernel2 import make_partition_ss

    rng = np.random.default_rng(11)
    R, size, f_pad, b, C = 128, 1024, 32, 64, 128
    n = size + 3 * R + 2 * 2048
    rows = np.zeros((n, C), np.float32)
    rows[:, :f_pad] = rng.integers(0, b, size=(n, f_pad))
    rows[:, f_pad] = rng.normal(size=n).astype(np.float32)
    rows[:, f_pad + 1] = rng.random(size=n).astype(np.float32)
    # sel: split rows [s0, s0+cnt) on feature 3 at bin b//3
    s0, cnt = 64, 900
    sel = np.zeros((8,), np.int32)
    sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = s0, cnt, 3, b // 3
    sel[6] = -1                                    # no NaN bin
    sel[SEL_SIDE] = side == "left"
    sel_j = jnp.asarray(sel)
    rows_j = jnp.asarray(rows)
    scr_j = jnp.zeros_like(rows_j)

    fused = make_fused_split(n, C, f_pad=f_pad, padded_bins=b, R=R,
                             size=size, interpret=True)
    rows_f, _, nleft_f, h_side = fused(sel_j, rows_j, scr_j)

    part = make_partition_ss(n, C, R=R, size=size, interpret=True)
    rows_p, _, nleft_p = part(sel_j, rows_j, jnp.zeros_like(rows_j))
    assert 0 < int(nleft_f) == int(nleft_p) < cnt
    np.testing.assert_array_equal(np.asarray(rows_f), np.asarray(rows_p))

    lo, hi = ((s0, s0 + int(nleft_f)) if side == "left"
              else (s0 + int(nleft_f), s0 + cnt))
    h_ref = build_histogram_comb(
        rows_f, jnp.int32(lo), jnp.int32(0), jnp.int32(hi - lo),
        f_pad=f_pad, size=size, padded_bins=b, interpret=True)
    np.testing.assert_array_equal(np.asarray(h_side), np.asarray(h_ref))
    # ... which covers that child's rows exactly once (bf16 tolerance:
    # the histogram kernel multiplies values at bf16 operand precision;
    # this numpy reference is exact f32)
    seg = np.asarray(rows_f)[lo:hi]
    for feat in (0, 3, f_pad - 1):
        ref = np.zeros((b, 2), np.float32)
        for r in seg:
            ref[int(r[feat])] += r[f_pad:f_pad + 2]
        np.testing.assert_allclose(np.asarray(h_side)[feat], ref,
                                   rtol=4e-2, atol=4e-2)
