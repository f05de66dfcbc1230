"""The plain references against the program on a tiny model."""
import numpy as np
import pytest

import higgs_data
import reference


@pytest.fixture(scope="module")
def tiny():
    import lightgbm_tpu as lgb
    x, y, logit = higgs_data.make_higgs_like(6000, seed=11)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
              "learning_rate": 0.1, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(x[:5000], label=y[:5000]),
                    num_boost_round=4)
    return bst, x, y, logit


def test_generator_is_a_function_of_the_seed():
    a = higgs_data.make_higgs_like(300_000, seed=2**31 + 7)
    b = higgs_data.make_higgs_like(300_000, seed=2**31 + 7)
    c = higgs_data.make_higgs_like(300_000, seed=2**31 + 8)
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    x, y, logit = a
    assert x.dtype == np.float32 and x.shape == (300_000, 28)
    assert 0.4 < y.mean() < 0.6
    # the noiseless logit is the ceiling: it beats its own noisy label
    assert reference.auc(logit, y) > 0.8


def test_walk_agrees_with_booster_predict(tiny):
    bst, x, _, _ = tiny
    trees = reference.parse_model(bst.model_to_string())
    assert len(trees) == 4 and trees[0].num_leaves == 15
    held = x[5000:]
    want = bst.predict(held, raw_score=True)
    assert np.max(np.abs(reference.walk(trees, held) - want)) < 1e-6
    two = bst.predict(held, raw_score=True, num_iteration=2)
    assert np.max(np.abs(reference.walk(trees[:2], held) - two)) < 1e-6


def test_depth():
    t = reference.TextTree(
        num_leaves=4, split_feature=np.array([0, 1, 2]),
        threshold=np.zeros(3), decision_type=np.zeros(3, np.int64),
        left_child=np.array([1, 2, -1]), right_child=np.array([-2, -3, -4]),
        leaf_value=np.arange(4.0), split_gain=np.ones(3))
    assert t.depth() == 3
    x = np.array([[-1.0, -1.0, -1.0], [-1.0, -1.0, 1.0], [1.0, 0.0, 0.0]])
    assert reference.walk([t], x).tolist() == [0.0, 3.0, 1.0]


def test_root_split_reference_matches_tree_0(tiny):
    bst, x, y, _ = tiny
    tree0 = reference.parse_model(bst.model_to_string())[0]
    r = reference.root_split(x[:5000], y[:5000], tree0, sample=5000)
    assert r.rel_err < 1e-4, r
    assert r.grid_ratio >= 0.99, r
    # a split somewhere else on another feature is not the root split
    worse = reference.TextTree(**{**tree0.__dict__})
    worse.split_feature = tree0.split_feature.copy()
    worse.split_feature[0] = (tree0.split_feature[0] + 1) % 28
    bad = reference.root_split(x[:5000], y[:5000], worse, sample=5000)
    assert not bad.ok(1e-3, 0.99)


def test_auc_with_ties_against_pair_counting():
    rng = np.random.default_rng(0)
    s = rng.integers(0, 5, size=200).astype(float)
    y = rng.integers(0, 2, size=200)
    pos, neg = s[y > 0], s[y == 0]
    pairs = ((pos[:, None] > neg[None, :]).sum()
             + 0.5 * (pos[:, None] == neg[None, :]).sum())
    assert reference.auc(s, y) == pytest.approx(pairs / (len(pos) * len(neg)))
