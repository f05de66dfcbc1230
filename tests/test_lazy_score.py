"""ISSUE 39: on the unpaged stream route the row-order train score is
read from the comb when somebody asks (``GBDT.train_score``, one run
of ``grow.pull_score()``: ``TrainScore::materialise``), not kept every
tree.  Held here, on the CPU through the route's XLA emulation
(``LGBM_TPU_PHYS=interpret``), a few thousand rows, binary and l2:

* what a read returns is the f32 chain the eager score tail specifies,
  ``score += f32(rate * leaf_value[leaf of the row])`` a tree, BIT FOR
  BIT, whenever it is read;
* every site that drops or rebuilds the comb reads it first;
* a numerics policy of ``raise`` / ``skip`` keeps the booster eager;
* a traced run's barriers never read it.

**The one-ulp case.**  The boosters that still keep the score every
tree (the paged comb here) add ``rate * leaf_value`` in a jitted tail
that XLA:CPU contracts into one fused multiply-add, a single rounding:
their score is within an ulp a tree of the chain, the trees equal.  The
comb's score - the one the gradients, and so the trees, are made from,
before this issue as after it - is the chain itself
(``test_the_kept_score_is_the_chain_within_an_fma``).
"""
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.obs import events, tracer

MATERIALISE = "TrainScore::materialise"
OBJECTIVES = ["binary", "regression"]
PAGED = {"LGBM_TPU_PAGED": "1", "LGBM_TPU_PAGE_ROWS": "2048"}
SKIP = {"LGBM_TPU_NUMERICS": "skip"}


@pytest.fixture(autouse=True)
def _stream_route(monkeypatch):
    for knob in ("LGBM_TPU_PAGED", "LGBM_TPU_PAGE_ROWS", "LGBM_TPU_NUMERICS",
                 "LGBM_TPU_STREAM", "LGBM_TPU_CKPT_DIR",
                 "LGBM_TPU_CKPT_EVERY", "LGBM_TPU_CKPT_AT_REFRESH"):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    events.reset()
    tracer.disable()
    tracer.reset()
    yield
    tracer.disable()
    tracer.reset()


@pytest.fixture
def grown(monkeypatch):
    """[(tree arrays, rate)] of every tree a booster finishes, and the
    booster's score when the first one was."""
    seen = {"trees": [], "start": None}
    finish = GBDT._finish_tree_async

    def spy(self, ta, leaf_id, kidx, init_score):
        if seen["start"] is None:
            seen["start"] = np.asarray(self._train_score)[0].copy()
        seen["trees"].append((ta, float(self.shrinkage_rate)))
        return finish(self, ta, leaf_id, kidx, init_score)

    monkeypatch.setattr(GBDT, "_finish_tree_async", spy)
    return seen


def _data(objective, n=3000, f=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    z = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.normal(size=n)
    y = (z > 0).astype(np.float32) if objective == "binary" \
        else z.astype(np.float32)
    return x, y


def _params(objective, **extra):
    return {"objective": objective, "num_leaves": 7, "verbosity": -1,
            "max_bin": 63, **extra}


def _dataset(objective, **kw):
    x, y = _data(objective, **kw)
    return lgb.Dataset(x, label=y, params={"max_bin": 63})


def _booster(objective, **extra):
    bst = lgb.Booster(_params(objective, **extra), _dataset(objective))
    inner = bst._inner
    assert inner._routing.path == "stream"
    return bst, inner


def _chain(inner, grown, upto=None, fused=False):
    """The eager tail's arithmetic, in numpy: two roundings a tree
    (``fused``: one, a fused multiply-add's)."""
    from lightgbm_tpu.ops.predict import (device_tree_from_arrays,
                                          predict_leaf_bins)
    score = grown["start"].copy()
    for ta, rate in grown["trees"][:upto]:
        if int(ta.num_leaves) <= 1:
            continue
        leaf = np.asarray(predict_leaf_bins(
            device_tree_from_arrays(ta), inner.dd.bins, inner.dd.num_bins,
            inner.dd.has_nan, feat_map=inner._fmap))
        out = np.asarray(ta.leaf_value)[leaf]
        if fused:       # the product of two f32 is exact in f64
            score = (score.astype(np.float64) + np.float64(np.float32(rate))
                     * out.astype(np.float64)).astype(np.float32)
        else:
            score = score + np.float32(rate) * out
    assert score.dtype == np.float32
    return score


def _bits(a):
    return np.asarray(a, np.float32).tobytes()


def _pulls():
    return events.totals().get(MATERIALISE, 0)


@pytest.mark.parametrize("trees", [1, 2, 7])
@pytest.mark.parametrize("objective", OBJECTIVES)
def test_the_score_read_from_the_comb_is_the_eager_tails_chain(
        objective, trees, grown):
    bst = lgb.train(_params(objective), _dataset(objective),
                    num_boost_round=trees)
    inner = bst._inner
    assert inner._routing.path == "stream" and inner._lazy_score
    assert inner._score_behind == trees and _pulls() == 0
    score = np.asarray(inner.train_score)
    assert score.shape == (1, inner.dd.n_pad) and score.dtype == np.float32
    assert _bits(score[0]) == _bits(_chain(inner, grown))
    assert inner._score_behind == 0 and _pulls() == 1
    # a second read is the cached array: nothing runs
    assert inner.train_score is inner.train_score and _pulls() == 1


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_the_kept_score_is_the_chain_within_an_fma(objective, grown,
                                                   monkeypatch):
    """The paged comb keeps ``leaf_id`` and the eager tail: the same
    trees, and a score that differs from the chain by the tail's fused
    multiply-add on XLA:CPU alone (module docstring)."""
    lazy = lgb.train(_params(objective), _dataset(objective),
                     num_boost_round=4)
    trees = {"start": grown["start"], "trees": grown["trees"][:4]}
    chain = _chain(lazy._inner, trees)
    assert _bits(lazy._inner.train_score) == _bits(chain[None])
    events.reset()
    for knob, value in PAGED.items():
        monkeypatch.setenv(knob, value)
    kept = lgb.train(_params(objective), _dataset(objective),
                     num_boost_round=4)
    assert kept._inner._routing.paged and not kept._inner._lazy_score
    assert kept._inner._score_behind == 0
    assert kept.model_to_string() == lazy.model_to_string()
    got = np.asarray(kept._inner.train_score)[0]
    assert _bits(got) == _bits(_chain(lazy._inner, trees, fused=True))
    assert _pulls() == 0                # kept, not read from the comb
    assert np.all(np.abs(got - chain) <= 4 * np.spacing(np.abs(chain)))


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_a_pull_mid_training_then_more_trees(objective, grown):
    bst, inner = _booster(objective)
    for _ in range(3):
        bst.update()
    mid = np.asarray(inner.train_score)[0].copy()
    assert _bits(mid) == _bits(_chain(inner, grown, upto=3))
    for _ in range(4):
        bst.update()
    assert inner._score_behind == 4 and _pulls() == 1
    assert _bits(np.asarray(inner.train_score)[0]) \
        == _bits(_chain(inner, grown))
    assert _pulls() == 2


def _rollback_then_train(objective, env, monkeypatch):
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    events.reset()
    bst, inner = _booster(objective)
    for _ in range(3):
        bst.update()
    behind = inner._score_behind
    bst.rollback_one_iter()
    rolled = _bits(inner._train_score)
    pulls = _pulls()
    for _ in range(2):
        bst.update()
    return (bst.model_to_string(), rolled, _bits(inner.train_score),
            behind, pulls)


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_rollback_with_unpulled_trees_then_training_on(objective,
                                                       monkeypatch):
    """The rollback reads the three un-pulled trees out of the comb
    BEFORE it drops it; scores and the trees grown after are those of
    the booster that pulls after every tree."""
    model, rolled, final, behind, pulls = _rollback_then_train(
        objective, {}, monkeypatch)
    assert behind == 3 and pulls == 1
    e_model, e_rolled, e_final, e_behind, e_pulls = _rollback_then_train(
        objective, SKIP, monkeypatch)
    assert e_behind == 0 and e_pulls == 3
    assert (model, rolled, final) == (e_model, e_rolled, e_final)


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_reset_parameter_changing_the_learning_rate(objective, grown):
    rates = [0.3, 0.2, 0.1, 0.05, 0.05]
    bst = lgb.train(_params(objective), _dataset(objective),
                    num_boost_round=len(rates),
                    callbacks=[lgb.reset_parameter(learning_rate=rates)])
    assert [r for _, r in grown["trees"]] == rates
    assert _pulls() == 0
    assert _bits(np.asarray(bst._inner.train_score)[0]) \
        == _bits(_chain(bst._inner, grown))


def _train_with_a_train_metric(objective, env, monkeypatch):
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    events.reset()
    ds = _dataset(objective)
    evals = {}
    bst = lgb.train(_params(objective), ds, num_boost_round=5,
                    valid_sets=[ds], valid_names=["training"],
                    callbacks=[lgb.record_evaluation(evals)])
    return evals, _pulls(), _bits(bst._inner.train_score)


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_a_train_set_metric_every_iteration_pulls_once_a_tree(
        objective, monkeypatch):
    evals, pulls, score = _train_with_a_train_metric(objective, {},
                                                     monkeypatch)
    assert pulls == 5
    values = next(iter(evals["training"].values()))
    assert len(values) == 5 and values[-1] < values[0]
    e_evals, e_pulls, e_score = _train_with_a_train_metric(
        objective, SKIP, monkeypatch)
    assert e_pulls == 5 and (evals, score) == (e_evals, e_score)


def _ckpt_train(objective, rounds, ckpt_dir, monkeypatch, **env):
    monkeypatch.setenv("LGBM_TPU_CKPT_DIR", str(ckpt_dir))
    monkeypatch.setenv("LGBM_TPU_CKPT_EVERY", "2")
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    bst = lgb.train(_params(objective, learning_rate=0.2),
                    _dataset(objective, n=1500), num_boost_round=rounds)
    assert bst._inner._routing.path == "stream" and bst._inner._lazy_score
    return bst


@pytest.mark.parametrize("at_refresh", ["0", "1"])
@pytest.mark.parametrize("objective", OBJECTIVES)
def test_checkpoint_then_resume(objective, at_refresh, tmp_path,
                                monkeypatch, grown):
    """A capture reads the comb's score, the re-anchor after it and
    the restore rebuild the comb from it: the resumed run grows the
    uninterrupted run's trees and holds its score, bit for bit."""
    env = {"LGBM_TPU_CKPT_AT_REFRESH": at_refresh}
    ref = _ckpt_train(objective, 6, tmp_path / "ref", monkeypatch, **env)
    _ckpt_train(objective, 3, tmp_path / "kill", monkeypatch, **env)
    saved = np.load(os.path.join(
        str(tmp_path / "kill"), "ckpt_000002", "score.npy"))
    resumed = _ckpt_train(objective, 6, tmp_path / "kill", monkeypatch,
                          **env)
    assert resumed.resumed_from == 2
    assert resumed.model_to_string() == ref.model_to_string()
    assert _bits(resumed._inner.train_score) == _bits(ref._inner.train_score)
    # what was saved is the score after two trees, not a stale cache
    assert _bits(saved) == _bits(_chain(ref._inner, grown, upto=2)[None])


def _train_with_a_valid_set(objective, env, monkeypatch):
    for knob, value in env.items():
        monkeypatch.setenv(knob, value)
    events.reset()
    train = _dataset(objective)
    xv, yv = _data(objective, n=700, seed=9)
    valid = lgb.Dataset(xv, label=yv, reference=train)
    evals = {}
    bst = lgb.train(_params(objective), train, num_boost_round=5,
                    valid_sets=[valid], valid_names=["held"],
                    callbacks=[lgb.record_evaluation(evals)])
    inner = bst._inner
    return (evals, _bits(inner.valid_sets[0].score), _pulls(),
            bst.model_to_string())


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_valid_set_scores_are_unchanged(objective, monkeypatch):
    evals, vscore, pulls, model = _train_with_a_valid_set(
        objective, {}, monkeypatch)
    assert pulls == 0                   # a valid metric reads no train score
    assert len(next(iter(evals["held"].values()))) == 5
    for env, e_pulls in ((SKIP, 5), (PAGED, 0)):
        got = _train_with_a_valid_set(objective, env, monkeypatch)
        assert got == (evals, vscore, e_pulls, model), env
        for knob in env:
            monkeypatch.delenv(knob)


@pytest.mark.parametrize("policy", ["raise", "skip"])
def test_a_numerics_sentinel_keeps_the_booster_eager(policy, grown,
                                                     monkeypatch):
    """``raise`` / ``skip`` need the last-good score when a tree is
    dropped, and the comb already holds the dropped tree's outputs: the
    booster pulls after every tree the sentinel let through, and a drop
    rebuilds the comb from that score."""
    # other tests of a worker purge and re-import the library: the
    # guard in ops/grow.py imports its sentinel when called (the newest
    # generation), the booster raises its own generation's fault
    from lightgbm_tpu.resilience import numerics
    fault = GBDT._train_one_tree.__globals__[
        "resilience_numerics"].NumericalFault
    monkeypatch.setenv("LGBM_TPU_NUMERICS", policy)
    bst, inner = _booster("binary")
    assert inner._lazy_score and inner._numerics == policy
    for n in (1, 2):
        bst.update()
        assert inner._score_behind == 0 and _pulls() == n
    good = _bits(inner._train_score)
    assert good == _bits(_chain(inner, grown)[None])
    count_bad = numerics.count_bad_fn()
    monkeypatch.setattr(numerics, "count_bad_fn",
                        lambda: lambda *a: count_bad(*a) + 1)
    if policy == "raise":
        with pytest.raises(fault):
            inner.train_one_iter()
    else:
        bst.update()
        assert inner.models[-1].num_leaves == 1
    assert _pulls() == 2 and _bits(inner.train_score) == good
    assert inner.grow._comb is None     # rebuilt from ``good`` next tree
    monkeypatch.setattr(numerics, "count_bad_fn", lambda: count_bad)
    bst.update()                        # (the dropped tree scored nothing)
    assert len(grown["trees"]) == 3
    assert _bits(inner.train_score) == _bits(_chain(inner, grown)[None])


def test_dropping_the_comb_with_trees_behind_is_refused():
    bst, inner = _booster("binary")
    bst.update()
    inner.grow.reset_stream()           # behind the booster's back
    with pytest.raises(RuntimeError, match="comb was dropped with 1"):
        inner.train_score


@pytest.mark.parametrize("objective", OBJECTIVES)
def test_a_traced_runs_barriers_never_read_the_score(objective):
    tracer.enable(None)
    bst = lgb.train(_params(objective), _dataset(objective),
                    num_boost_round=3)
    names = [e["name"] for e in tracer.events]
    assert names.count("Tree::grow::wait") == 3
    assert names.count("UpdateScore::wait") == 3
    assert MATERIALISE not in names and _pulls() == 0
    assert bst._inner._score_behind == 3
    assert not [e for e in tracer.events
                if e["name"] == "Program::ops"
                or e["args"].get("program") == "pull_score"]
    # the read is a span of its own, and says how far behind it was
    tracer.annotate(True)
    bst._inner.train_score
    tracer.annotate(False)
    spans = [e for e in tracer.events if e["name"] == MATERIALISE]
    assert [e["args"]["trees_behind"] for e in spans] == [3]
    assert names.count(MATERIALISE + "::wait") == 0
    assert [e["name"] for e in tracer.events].count(
        MATERIALISE + "::wait") == 1
    # and its program's table holds ops of ``leafrows`` alone
    tables = [e["args"] for e in tracer.events
              if e["name"] == "Program::ops"]
    pull = [t["ops"] for t in tables if t["program"] == "pull_score"]
    assert len(pull) == 1 and pull[0].get("leafrows")
    assert set(pull[0]) <= {"leafrows", ""}
    assert all(key.split()[0].startswith(("copy", "bitcast"))
               for key in pull[0].get("", []))


def test_pull_score_reads_the_comb_and_leaves_it(grown):
    """The program by itself: not donated, rows by their id bytes."""
    bst, inner = _booster("regression")
    for _ in range(2):
        bst.update()
    grow = inner.grow
    comb = grow._comb
    before = np.asarray(comb).copy()
    score = np.asarray(grow.pull_score())[0]
    assert grow._comb is comb and not comb.is_deleted()
    assert np.array_equal(np.asarray(comb), before)
    assert _bits(score) == _bits(_chain(inner, grown))
