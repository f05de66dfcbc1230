"""A job with validation sets: every tree is replayed over each valid
set's bins in the jitted score tail (under ``lgbm.valid``) and each
data set's metrics are evaluated once an iteration (``lgbm.eval``).

Held here on the CPU, on the stream route through its XLA emulation
(``LGBM_TPU_PHYS=interpret``), at a small size with a NaN-bearing
column and a categorical column past ``max_cat_to_onehot``, against
``benchmarks/reference_valid.py``: float64 numpy over the RAW valid
values that knows nothing of bins or the tail.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "benchmarks"))

import reference_valid  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.metric.metrics import AUCMetric, BinaryLoglossMetric  # noqa: E402
from lightgbm_tpu.models.gbdt import make_score_tail  # noqa: E402
from lightgbm_tpu.obs import tracer  # noqa: E402
from lightgbm_tpu.obs.counters import tree_depth  # noqa: E402
from lightgbm_tpu.obs.tracer import program_ops  # noqa: E402
from lightgbm_tpu.ops import predict  # noqa: E402

ROWS, COLS, LEVELS, TREES, LEAVES = 4096, 48, 12, 5, 15
PARAMS = {"objective": "binary", "num_leaves": LEAVES, "max_bin": 63,
          "verbosity": -1, "metric": "auc,binary_logloss"}


def _data(seed=7):
    rng = np.random.default_rng(seed)
    n = ROWS + 3072
    x = rng.normal(size=(n, COLS)).astype(np.float32)
    x[:, 1] = rng.integers(0, LEVELS, n)
    x[rng.random(n) < 0.15, 0] = np.nan
    effect = rng.normal(size=LEVELS)
    z = (np.nan_to_num(x[:, 0], nan=1.0) + effect[x[:, 1].astype(int)]
         + 0.5 * x[:, 2] * x[:, 3] + 0.5 * rng.normal(size=n))
    return x, (z > 0).astype(np.float32)


def _train(valid_slices, training_metric):
    """(booster, recorded evaluations, calls of each metric instance,
    the valid rows and labels by name); traced, so that the spans and
    program tables are there to read."""
    x, y = _data()
    dtrain = lgb.Dataset(x[:ROWS], y[:ROWS], categorical_feature=[1],
                         params={"max_bin": 63})
    names = sorted(valid_slices)
    valid = {k: (x[slice(*valid_slices[k])], y[slice(*valid_slices[k])])
             for k in names}
    calls = {}

    def counting(cls, attr):
        orig = getattr(cls, attr)

        def spy(self, *a, **k):
            calls[id(self)] = calls.get(id(self), 0) + 1
            return orig(self, *a, **k)
        return orig, spy

    patched = [(cls, attr) + counting(cls, attr) for cls, attr in
               ((AUCMetric, "device_program"), (BinaryLoglossMetric, "eval"))]
    for cls, attr, _, spy in patched:
        setattr(cls, attr, spy)
    tracer.reset()
    tracer.enable()
    recorded = {}
    try:
        bst = lgb.train(
            dict(PARAMS, is_training_metric=training_metric), dtrain, TREES,
            valid_sets=[lgb.Dataset(*valid[k], reference=dtrain)
                        for k in names],
            valid_names=names, callbacks=[lgb.record_evaluation(recorded)])
    finally:
        tracer.disable()
        for cls, attr, orig, _ in patched:
            setattr(cls, attr, orig)
    return bst, recorded, calls, valid


@pytest.fixture(scope="module")
def two_sets():
    saved = os.environ.get("LGBM_TPU_PHYS")
    os.environ["LGBM_TPU_PHYS"] = "interpret"
    try:
        out = _train({"a": (ROWS, ROWS + 2048), "b": (ROWS + 2048, None)},
                     training_metric=True)
        yield out + (list(tracer.events), dict(tracer._programs))
    finally:
        if saved is None:
            os.environ.pop("LGBM_TPU_PHYS", None)
        else:
            os.environ["LGBM_TPU_PHYS"] = saved
        tracer.reset()


def _reference(bst, xv):
    trees = reference_valid.parse_model(bst.model_to_string())
    return trees, reference_valid.scores_after(
        trees, reference_valid.leaves(trees, xv))


def test_the_job_takes_the_stream_route_with_nan_and_categorical_splits(
        two_sets):
    bst = two_sets[0]
    assert bst._inner._routing.path == "stream"
    trees = reference_valid.parse_model(bst.model_to_string())
    dt = np.concatenate([t.decision_type for t in trees])
    assert np.any(dt & 1)                               # categorical
    assert np.any(((dt >> 2) & 3 == reference_valid.MISSING_NAN)
                  & (dt & 1 == 0))                      # NaN-aware


@pytest.mark.parametrize("name", ["a", "b"])
def test_every_recorded_valid_auc_is_the_float64_midrank_auc(
        two_sets, name):
    bst, recorded, _, valid = two_sets[:4]
    xv, yv = valid[name]
    _, ref = _reference(bst, xv)
    got = recorded[name]["auc"]
    assert len(got) == TREES
    want = [reference_valid.auc(ref[i], yv) for i in range(TREES)]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("name", ["a", "b"])
def test_the_valid_score_is_the_reference_walk(two_sets, name):
    bst, _, _, valid = two_sets[:4]
    xv, _ = valid[name]
    _, ref = _reference(bst, xv)
    (vs,) = [v for v in bst._inner.valid_sets if v.name == name]
    score = np.asarray(vs.score)[0]
    assert score.shape == (len(xv),)
    off = np.abs(score - ref[-1])
    assert int(np.count_nonzero(~(off <= 1e-5))) == 0, off.max()


def _calls_per_set(bst, calls):
    inner = bst._inner
    sets = [("training", inner._train_metrics)] + [
        (vs.name, vs.metrics) for vs in inner.valid_sets]
    return {(ds, m.NAME): calls.get(id(m), 0) for ds, ms in sets for m in ms}


def test_each_metric_of_each_set_is_evaluated_once_an_iteration(two_sets):
    bst, recorded, calls = two_sets[:3]
    per = _calls_per_set(bst, calls)
    assert set(per) == {(ds, m) for ds in ("training", "a", "b")
                        for m in ("auc", "binary_logloss")}
    assert set(per.values()) == {TREES}
    assert sorted(recorded) == ["a", "b", "training"]


def test_one_valid_set_is_evaluated_once_an_iteration_too(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    bst, recorded, calls, _ = _train({"test": (ROWS, None)},
                                     training_metric=False)
    tracer.reset()
    per = _calls_per_set(bst, calls)
    assert per == {("test", "auc"): TREES, ("test", "binary_logloss"): TREES}
    assert list(recorded) == ["test"]


def test_a_valid_set_without_metrics_evaluates_nothing():
    x, y = _data()
    dtrain = lgb.Dataset(x[:ROWS], y[:ROWS])
    recorded = {}
    bst = lgb.train(dict(PARAMS, metric="None"), dtrain, 2,
                    valid_sets=[lgb.Dataset(x[ROWS:], y[ROWS:],
                                            reference=dtrain)],
                    callbacks=[lgb.record_evaluation(recorded)])
    assert recorded == {} and bst.eval_valid() == []


def test_one_data_set_at_a_time_is_the_same_program_on_the_same_score(
        two_sets):
    """``GBDT.eval(name)`` computes that set's metrics alone; the values
    are what every data set evaluated together gives, and what the
    metric's own program gives on the set's score, bit for bit."""
    bst = two_sets[0]
    inner = bst._inner
    every = inner.eval()
    assert every == inner.eval("training") + inner.eval("a") + \
        inner.eval("b")
    assert every == bst.eval_train() + bst.eval_valid()
    assert inner.eval("no such set") == []
    for vs in inner.valid_sets:
        (m,) = [m for m in vs.metrics if m.NAME == "auc"]
        want = m.eval_device(vs.score[0][:m.num_data])
        assert [r[1:] for r in inner.eval(vs.name) if r[1] == "auc"] == want


def test_the_spans_count_the_replay_and_the_tree_depth(two_sets):
    bst, _, _, valid, events = two_sets[:5]
    tails = [e["args"] for e in events if e["name"] == "UpdateScore::tail"]
    grows = [e["args"] for e in events if e["name"] == "Tree::grow"]
    assert len(tails) == len(grows) == TREES
    rows = sum(len(v[0]) for v in valid.values())
    assert all(a["valid_sets"] == 2 and a["valid_rows"] == rows
               and a["replay_matmul_rows"] == rows
               and a["replay_steps"] == 2 * (LEAVES - 1) for a in tails)
    text = bst.model_to_string()
    depths = []
    for t in reference_valid.parse_model(text):
        ni = t.num_leaves - 1
        depths.append(tree_depth(t.num_leaves, t.left_child[:ni],
                                 t.right_child[:ni]))
    assert [a["tree_depth"] for a in grows] == depths
    evals = [e["args"] for e in events if e["name"] == "Eval"]
    assert {(a["datasets"], a["metrics"]) for a in evals} == {(1, 2)}
    assert sum(e["name"] == "Eval::wait" for e in events) == len(evals)


def _random_tree(rng, leaves, ni, features, bins, *, words=0, cat=0.0,
                 chain=False):
    """A bin-space tree grown leaf-wise as the grower numbers it (node j
    splits a leaf: its left child keeps the leaf's id, its right child is
    leaf j + 1), on arrays padded to ``ni`` nodes past ``leaves - 1``
    with what the grower leaves there (zeros).  ``chain``: every split
    takes the newest leaf and almost every row goes right, so rows reach
    the bottom of a ``leaves - 1`` deep path."""
    lc, rc = np.zeros(ni, np.int32), np.zeros(ni, np.int32)
    home = {0: None}
    for j in range(leaves - 1):
        leaf = j if chain else int(rng.integers(0, j + 1))
        if home[leaf] is not None:
            (lc, rc)[home[leaf][1]][home[leaf][0]] = j
        lc[j], rc[j] = ~leaf, ~(j + 1)
        home[leaf], home[j + 1] = (j, 0), (j, 1)
    real = np.arange(ni) < leaves - 1
    sf = np.where(real, rng.integers(0, features, ni), 0)
    tb = np.where(real, rng.integers(0, 2 if chain else bins, ni), 0)
    return predict.DeviceTree(
        split_feature=jnp.asarray(sf, jnp.int32),
        threshold_bin=jnp.asarray(tb, jnp.int32),
        default_left=jnp.asarray(real & (rng.random(ni) < 0.5)),
        is_categorical=jnp.asarray(real & (rng.random(ni) < cat)),
        left_child=jnp.asarray(lc), right_child=jnp.asarray(rc),
        leaf_value=jnp.zeros(ni + 1, jnp.float32),
        num_leaves=jnp.int32(leaves),
        cat_words=jnp.asarray(rng.integers(-2**31, 2**31, (ni, words)),
                              jnp.int32))


# (leaves, padded inner nodes, rows, words, categorical share, chain,
#  EFB, block: 0 = the one predict_leaf_bins takes)
_REPLAY_CASES = {
    "nan_default_left": (31, 30, 700, 0, 0.0, False, False, 0),
    "categorical_bitsets": (31, 30, 700, 2, 0.5, False, False, 0),
    "one_hot_categoricals": (31, 30, 700, 0, 0.5, False, False, 0),
    "efb_feat_map": (31, 30, 700, 0, 0.3, False, True, 0),
    "stump": (1, 30, 700, 0, 0.0, False, False, 0),
    "fewer_leaves_than_padded": (9, 30, 700, 2, 0.3, False, False, 0),
    "chain_254_deep": (255, 254, 700, 0, 0.0, True, False, 0),
    "rows_not_a_multiple_of_the_block": (31, 30, 700, 2, 0.3, False,
                                         False, 256),
}


@pytest.mark.parametrize("case", sorted(_REPLAY_CASES))
def test_the_decision_matrix_replay_finds_the_walks_leaf_on_every_row(case):
    """Every row's leaf from the u8 route (two matmuls a block of rows)
    is the lock-step walk's.  Bins are drawn over every level, the NaN
    bin of the features that have one included, so both default
    directions, bitset words and one-hot equalities are hit."""
    leaves, ni, n, words, cat, chain, efb, block = _REPLAY_CASES[case]
    rng = np.random.default_rng(sorted(_REPLAY_CASES).index(case))
    levels = 64
    if efb:
        # 12 features of 8 bins, three a bundle column from bin 1 on
        fl, levels = 12, 8
        fmap = (jnp.arange(fl, dtype=jnp.int32) // 3,
                1 + 8 * (jnp.arange(fl, dtype=jnp.int32) % 3),
                jnp.asarray(rng.integers(0, 8, fl), jnp.int32))
        bins = rng.integers(0, 25, (n, 4))
    else:
        fl, fmap = 12, None
        bins = rng.integers(0, levels, (n, fl))
    num_bins = jnp.full(fl, levels, jnp.int32)
    has_nan = jnp.asarray(rng.random(fl) < 0.5)
    tree = _random_tree(rng, leaves, ni, fl, levels, words=words, cat=cat,
                        chain=chain)
    bins = jnp.asarray(bins, jnp.uint8)
    walk_tree = tree
    if leaves == 1:
        # a grown stump's zero children hold the walk at node 0 (leaf -1,
        # which the tail masks); its one leaf is the walk of the stump as
        # tree_to_device builds it, with no node
        walk_tree = tree._replace(split_feature=tree.split_feature[:0],
                                  cat_words=tree.cat_words[:0])
    walked = jax.jit(predict._walk_leaves)(walk_tree, bins, num_bins,
                                           has_nan, fmap)
    if block:
        got = jax.jit(predict._matmul_leaves, static_argnums=6)(
            tree, bins, num_bins, has_nan, fmap, None, block)
    else:
        assert predict.replay_block_rows(bins, ni) == n
        got = predict.predict_leaf_bins(tree, bins, num_bins, has_nan,
                                        feat_map=fmap)
    walked, got = np.asarray(walked), np.asarray(got)
    assert walked.min() >= 0 and walked.max() < leaves
    np.testing.assert_array_equal(got, walked)
    if chain:
        assert walked.max() == leaves - 1       # some row went all the way
    # the walk is what i32 bins (past 256 levels) still take
    assert predict.replay_block_rows(bins.astype(jnp.int32), ni) == 0


def test_tree_depth_counts_the_inner_nodes_of_the_longest_path():
    # root 0 -> (1, ~0); 1 -> (2, ~1); 2 -> (~2, ~3): a chain of three
    assert tree_depth(4, [1, 2, -3], [-1, -2, -4]) == 3
    # balanced, four leaves: 0 -> (1, 2), each of them over two leaves
    assert tree_depth(4, [1, -1, -3], [2, -2, -4]) == 2
    assert tree_depth(1, [], []) == 0


def test_the_replay_and_the_metric_are_in_the_program_tables(two_sets):
    programs = two_sets[5]
    score = programs["score"][1]
    assert "valid" in score and "score" in score
    for ds in ("training", "a", "b"):
        assert set(programs[f"eval:{ds}:auc"][1]) - {""} == {"eval"}


def _tail_ops(valid_sets):
    tail = make_score_tail(np.full(COLS, 64, np.int32),
                           np.zeros(COLS, bool))
    from lightgbm_tpu.ops.grow import TreeArrays
    ni, f32, i32 = LEAVES - 1, jnp.float32, jnp.int32
    s = jax.ShapeDtypeStruct
    ta = TreeArrays(s((ni,), i32), s((ni,), i32), s((ni,), f32),
                    s((ni,), jnp.bool_), s((ni,), jnp.bool_), s((ni,), i32),
                    s((ni,), i32), s((ni,), f32), s((ni,), f32),
                    s((ni,), f32), s((LEAVES,), f32), s((LEAVES,), f32),
                    s((LEAVES,), f32), s((), i32), s((1, 1), f32),
                    s((4,), i32))
    text = tail.lower(
        ta, None, None, tuple(s((512, COLS), jnp.uint8)
                              for _ in range(valid_sets)),
        tuple(s((512,), f32) for _ in range(valid_sets)), s((), f32),
        s((), f32)).compile().as_text()
    return text, program_ops(text)


def test_without_a_valid_set_the_tail_has_no_replay():
    """The program the cells without valid sets run: no op of it is
    ``valid``'s, and the scope's name is nowhere in its text."""
    text, ops = _tail_ops(0)
    assert "lgbm.valid" not in text and "valid" not in ops
    text, ops = _tail_ops(1)
    assert ops["valid"] and "lgbm.valid" in text


def test_the_reference_auc_is_the_pairwise_count_with_ties():
    s = np.array([0.1, 0.4, 0.35, 0.8, 0.35, 0.4, 0.1])
    y = np.array([0, 0, 1, 1, 1, 1, 0])
    pos, neg = s[y > 0], s[y == 0]
    pairs = sum(1.0 if p > q else 0.5 if p == q else 0.0
                for p in pos for q in neg)
    assert reference_valid.auc(s, y) == pytest.approx(
        pairs / (len(pos) * len(neg)), abs=1e-15)
    # in bfloat16 arithmetic it is near, not equal, at a larger size
    rng = np.random.default_rng(0)
    big = rng.normal(size=20000)
    yb = (rng.random(20000) < 1 / (1 + np.exp(-2 * big))).astype(int)
    gap = abs(reference_valid.auc_in_bf16(big, yb)
              - reference_valid.auc(big, yb))
    assert 1e-5 < gap < 5e-2
