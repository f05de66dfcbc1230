"""Batched multiclass training (ISSUE 19): ONE compiled grow dispatch
per iteration grows all K class trees.

The batched path is a ``jax.lax.scan`` over the class axis INSIDE one
jitted program: the comb/scratch carry threads class k-1's final row
permutation into class k exactly like the serial loop does, so the
trees must be BYTE-identical to serial-K — same tree_seed schedule,
same feature-fraction RNG draws (active classes only, in class
order), same quantized-gain tie-breaks.  These tests pin that bar
across the routing matrix (partition scheme x fused x
serial/8-shard mesh, K in {3, 4}) through the REAL partition kernels
(``LGBM_TPU_PART_INTERP=kernel``), plus the two per-class semantics
the batch must not flatten:

* ``class_need_train`` gating — a class whose first-round tree is a
  stump stops training; its slot rides zeroed grad/hess and an
  all-zero feature mask through the scan (no RNG draw, comb carry
  untouched) while its siblings keep growing;
* per-class NumericsSkip — a poisoned class degrades to a zero stump
  WITHOUT dropping the sibling trees grown in the same dispatch.
"""
import os
import sys

import numpy as np
import pytest

_MC_ENV = ("LGBM_TPU_PHYS", "LGBM_TPU_PART_INTERP", "LGBM_TPU_PARTITION",
           "LGBM_TPU_FUSED", "LGBM_TPU_MC_BATCH",
           "LGBM_TPU_HIST_SCATTER", "LGBM_TPU_NUMERICS")


def _mc_data(k, n=1200, f=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    x[rng.random(x.shape) < 0.1] = np.nan
    sig = np.nan_to_num(x[:, 0]) + 0.5 * np.nan_to_num(x[:, 1] * x[:, 2])
    # balanced K-way label via signal quantiles: every class trains
    edges = np.quantile(sig, np.linspace(0, 1, k + 1)[1:-1])
    y = np.searchsorted(edges, sig).astype(np.float32)
    return x, y


def _digests(bst):
    out = []
    for t in bst._models:
        nl = int(t.num_leaves)
        out.append((nl, t.split_feature[:nl - 1].tolist(),
                    t.threshold_bin[:nl - 1].tolist(),
                    np.asarray(t.leaf_value[:nl]).tobytes()))
    return out


def _train_mc(mcb, k, partition="permute", fused="1",
              learner="serial", rounds=2, n=1200, fobj=None,
              numerics=None, **params):
    """One (knob-cell, K) multiclass run; returns (digests, engaged,
    event-totals, class_need_train)."""
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel",
           "LGBM_TPU_PARTITION": partition,
           "LGBM_TPU_FUSED": fused,
           "LGBM_TPU_MC_BATCH": mcb}
    if numerics is not None:
        env["LGBM_TPU_NUMERICS"] = numerics
    saved = {kk: os.environ.get(kk) for kk in _MC_ENV}
    for kk, v in env.items():
        os.environ[kk] = v
    try:
        for m in [kk for kk in list(sys.modules)
                  if kk.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import events
        x, y = _mc_data(k, n=n)
        p = {"objective": fobj if fobj is not None else "multiclass",
             "num_class": k, "num_leaves": 7, "verbosity": -1}
        p.update(params)
        ds = lgb.Dataset(x, label=y)
        bst = lgb.train(p, ds, num_boost_round=rounds)
        inner = bst._inner
        return (_digests(bst), bool(getattr(inner, "_mc_batched", False)),
                dict(events.totals()),
                list(getattr(inner, "_class_need_train", [])))
    finally:
        for kk, v in saved.items():
            if v is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = v
        for m in [kk for kk in list(sys.modules)
                  if kk.startswith("lightgbm_tpu")]:
            del sys.modules[m]


def _assert_parity(cell_b, cell_s, k, rounds):
    tb, engb, evb, _ = cell_b
    ts, engs, evs, _ = cell_s
    assert engb is True, "batched run did not engage the scan path"
    assert engs is False, "serial reference engaged the scan path"
    assert len(tb) == len(ts) == k * rounds
    for i, (a, b) in enumerate(zip(tb, ts)):
        assert a == b, (f"tree {i} (iter {i // k}, class {i % k}) "
                        f"differs between batched and serial-K")
    # the perf contract: ONE grow dispatch per iteration vs K
    assert evb.get("grow_dispatch", 0) == rounds, evb
    assert evs.get("grow_dispatch", 0) == rounds * k, evs


# ---------------------------------------------------------------------
# the parity matrix (byte-identical trees, batched vs serial-K)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("k,partition,fused,learner", [
    (3, "permute", "1", "serial"),
    (3, "matmul", "0", "serial"),
])
def test_batched_matches_serial(k, partition, fused, learner):
    kw = {}
    if learner == "data":
        kw = {"tree_learner": "data", "max_bin": 31,
              "min_data_in_leaf": 5}
    b = _train_mc("auto", k, partition, fused, learner, **kw)
    s = _train_mc("0", k, partition, fused, learner, **kw)
    _assert_parity(b, s, k, rounds=2)


@pytest.mark.slow
@pytest.mark.parametrize("k,partition,fused,learner", [
    (4, "permute", "1", "serial"),
    (4, "permute", "1", "data"),
    (3, "matmul", "1", "serial"),
    (4, "matmul", "0", "serial"),
    (3, "permute", "0", "data"),
    (3, "permute", "1", "data"),
])
def test_batched_matches_serial_full(k, partition, fused, learner):
    kw = {}
    if learner == "data":
        kw = {"tree_learner": "data", "max_bin": 31,
              "min_data_in_leaf": 5}
    b = _train_mc("auto", k, partition, fused, learner, **kw)
    s = _train_mc("0", k, partition, fused, learner, **kw)
    _assert_parity(b, s, k, rounds=2)


def test_feature_fraction_rng_alignment():
    # feature_fraction < 1 makes the per-class mask a REAL RNG draw;
    # the batch must consume draws in class order for active classes
    # only, or every downstream tree diverges
    b = _train_mc("auto", 3, feature_fraction=0.7)
    s = _train_mc("0", 3, feature_fraction=0.7)
    _assert_parity(b, s, 3, rounds=2)


# ---------------------------------------------------------------------
# per-class semantics through the batch
# ---------------------------------------------------------------------
def _make_fobj(k, n, poison_class=None, poison_iter=None,
               dead_class=None, seed=7):
    """Deterministic synthetic multiclass gradients; optionally NaN-
    poisons one class at one iteration, or zeroes one class outright
    (a first-round stump -> class_need_train gating)."""
    rng = np.random.default_rng(seed)
    g0 = rng.normal(size=(k, n)).astype(np.float32)
    h0 = rng.uniform(0.5, 1.5, size=(k, n)).astype(np.float32)
    state = {"it": 0}

    def fobj(preds, train_set):
        it = state["it"]
        state["it"] += 1
        g, h = g0.copy(), h0.copy()
        if dead_class is not None:
            g[dead_class] = 0.0
            h[dead_class] = 0.0
        if poison_class is not None and it == poison_iter:
            g[poison_class, ::3] = np.nan
        return g.reshape(-1), h.reshape(-1)

    return fobj


def test_class_need_train_stump_alignment():
    # class 2's gradients are identically zero: its first-round tree
    # is a stump, class_need_train[2] flips off, and every later
    # iteration appends a zero stump for it — from INSIDE the batched
    # dispatch, without perturbing the sibling classes' comb carry
    k, n, rounds = 3, 1200, 3
    kw = dict(rounds=rounds, n=n, min_data_in_leaf=5)
    b = _train_mc("auto", k, fobj=_make_fobj(k, n, dead_class=2), **kw)
    s = _train_mc("0", k, fobj=_make_fobj(k, n, dead_class=2), **kw)
    tb, engb, evb, needb = b
    ts, engs, evs, needs_ = s
    assert engb is True and engs is False
    assert tb == ts
    assert needb == needs_ == [True, True, False]
    for i in range(rounds):
        leaves = [tb[i * k + c][0] for c in range(k)]
        assert leaves[2] == 1, f"iter {i}: dead class grew {leaves[2]}"
        assert leaves[0] > 1 and leaves[1] > 1, leaves
    # gated stumps don't shrink the dispatch count: the batch still
    # launches once per iteration while ANY class needs training
    assert evb.get("grow_dispatch", 0) == rounds, evb


def test_per_class_numerics_skip():
    # NaN-poisoned class 1 at iteration 1 under the skip policy: its
    # tree degrades to a zero stump, the SIBLING trees grown by the
    # same dispatch survive, and training continues
    k, n, rounds = 3, 1200, 3
    kw = dict(rounds=rounds, n=n, numerics="skip", min_data_in_leaf=5)
    b = _train_mc("auto", k,
                  fobj=_make_fobj(k, n, poison_class=1, poison_iter=1),
                  **kw)
    s = _train_mc("0", k,
                  fobj=_make_fobj(k, n, poison_class=1, poison_iter=1),
                  **kw)
    tb, engb, evb, _ = b
    ts, engs, evs, _ = s
    assert engb is True and engs is False
    assert tb == ts
    assert len(tb) == k * rounds
    leaves = [t[0] for t in tb]
    it1 = leaves[k:2 * k]
    assert it1[1] == 1, f"poisoned class kept its splits: {it1}"
    assert it1[0] > 1 and it1[2] > 1, \
        f"siblings dropped with the poisoned class: {it1}"
    # neighbours in time also trained
    assert leaves[0] > 1 and leaves[2 * k] > 1, leaves
    assert evb.get("numerics_skip", 0) >= 1, evb
    assert evs.get("numerics_skip", 0) >= 1, evs


def test_env_knob_forces():
    # LGBM_TPU_MC_BATCH=1 forces the request on an eligible config;
    # =0 pins serial-K (the routing rule mc_batch_env_off)
    _, eng1, _, _ = _train_mc("1", 3, rounds=1, n=800)
    _, eng0, _, _ = _train_mc("0", 3, rounds=1, n=800)
    assert eng1 is True and eng0 is False


def test_binary_never_batches():
    # k=1 is not a batch: the flag must stay off and the dispatch
    # count unchanged for single-class objectives
    env = {"LGBM_TPU_PHYS": "interpret",
           "LGBM_TPU_PART_INTERP": "kernel",
           "LGBM_TPU_MC_BATCH": "1"}
    saved = {kk: os.environ.get(kk) for kk in _MC_ENV}
    for kk, v in env.items():
        os.environ[kk] = v
    try:
        for m in [kk for kk in list(sys.modules)
                  if kk.startswith("lightgbm_tpu")]:
            del sys.modules[m]
        import lightgbm_tpu as lgb
        from lightgbm_tpu.obs import events
        x, y = _mc_data(2, n=800)
        ds = lgb.Dataset(x, label=(y > 0).astype(np.float32))
        bst = lgb.train({"objective": "binary", "num_leaves": 7,
                         "verbosity": -1}, ds, num_boost_round=2)
        assert getattr(bst._inner, "_mc_batched", False) is False
        assert events.totals().get("grow_dispatch", 0) == 2
    finally:
        for kk, v in saved.items():
            if v is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = v
        for m in [kk for kk in list(sys.modules)
                  if kk.startswith("lightgbm_tpu")]:
            del sys.modules[m]
