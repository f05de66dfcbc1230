"""The forest from the seed loads, routes compiled and is the model the
reference walks."""
import numpy as np
import pytest

import forest
import higgs_data
import reference


@pytest.fixture(scope="module")
def made():
    return forest.make_forest(2**31 + 3, trees=12, leaves=255)


def test_forest_shape_and_thresholds(made):
    text = made
    trees = reference.parse_model(text)
    assert len(trees) == 12
    assert all(t.num_leaves == 255 for t in trees)
    assert text == forest.make_forest(2**31 + 3, trees=12, leaves=255)
    bounds = forest.bin_upper_bounds(2**31 + 3)
    assert bounds.shape == (28, 254)
    for t in trees:
        for f, thr in zip(t.split_feature, t.threshold):
            assert thr in bounds[f]
            assert float(np.float32(thr)) == thr
    assert 8 <= max(t.depth() for t in trees) <= 40


def test_forest_text_loads_and_routes_compiled(made, monkeypatch):
    monkeypatch.setenv("LGBM_TPU_SERVE", "1")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import routing
    text = made
    bst = lgb.Booster(model_str=text)
    assert bst.num_trees() == 12
    d = bst._predict_route(routing, bst._models, pred_leaf=False,
                           pred_contrib=False, early_stop=False)
    assert d.path == "compiled", d
    x, _, _ = higgs_data.make_higgs_like(256, seed=5)
    served = bst.predict(x, raw_score=True)
    assert bst.serving_engine().stats()["dispatches"] == 1
    ref = reference.walk(reference.parse_model(text), x)
    assert np.max(np.abs(served - ref)) < 1e-6
