"""The ranking cell's own pieces: the frozen generator, the fixed length
draw, the plain rank reference, the new reducer, and the cell's rehearsal."""
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import msltr_data
import reference_rank

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def test_the_length_draw_is_the_published_shape():
    train, held = msltr_data.query_lengths()
    assert len(train) == 18_919 and len(held) == 2_000
    assert (int(train.sum()), int(train.min()), int(train.max())) == (
        2_270_296, 1, 1_251)
    assert 119.9 < train.mean() < 120.1 and np.median(train) < 100
    assert int(held.min()) == 1 and held.max() <= 1_251
    # one fixed draw: frozen
    assert hashlib.sha256(train.tobytes() + held.tobytes()).hexdigest() == (
        "0b7a76b109d2a3f5145bd475cfe255bf99afe36e48332acf3447d993ca0d48bf")


def test_generator_is_frozen_and_a_function_of_the_seed():
    kw = dict(n_train=300, n_held=100, longest=200, total_rows=None)
    a = msltr_data.make_msltr_like(2**31 + 7, **kw)
    b = msltr_data.make_msltr_like(2**31 + 7, **kw)
    c = msltr_data.make_msltr_like(2**31 + 8, **kw)
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[3], c[3]) and np.array_equal(a[4], c[4])
    x, grade, oracle, train, held = a
    assert x.dtype == np.float32 and x.shape == (42_544, 137)
    # features, grades and lengths, frozen (the float64 oracle goes
    # through a BLAS product, whose last bit may differ between CPUs)
    h = hashlib.sha256()
    for part in (x, grade, train, held):
        h.update(np.ascontiguousarray(part).tobytes())
    assert h.hexdigest() == (
        "906675c6e8174fbdecebcda8803dbf4427867de72a30093da16a99df0c56a3c6")
    shares = np.bincount(grade.astype(np.int64), minlength=5) / len(grade)
    assert np.allclose(shares, msltr_data.GRADE_SHARES, atol=0.02)
    # column kinds: counts with few values, mostly-zero, constant in a query
    kind = msltr_data.task()["kind"]
    cnt = x[:, np.flatnonzero(kind == msltr_data.COUNT)[0]]
    assert cnt.min() >= 0 and len(np.unique(cnt)) <= 16
    assert (x[:, np.flatnonzero(kind == msltr_data.SPARSE)[0]] == 0).mean() > 0.7
    qcol = x[:train[0], np.flatnonzero(kind == msltr_data.QUERY)[0]]
    assert train[0] > 1 and len(np.unique(qcol)) == 1
    # the oracle ranks better than the constant score
    n = int(train.sum())
    qb = np.concatenate([[0], np.cumsum(held)])
    assert reference_rank.ndcg_at(10, oracle[n:], grade[n:], qb) > (
        reference_rank.ndcg_at(10, np.zeros(len(grade) - n), grade[n:], qb)
        + 0.3)


@pytest.mark.parametrize("norm", [True, False])
def test_the_numpy_form_is_the_loops(norm):
    rng = np.random.default_rng(4)
    sizes = np.array([1, 2, 7, 31, 90, 45])
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(sizes.sum())
    label = rng.integers(0, 5, n)
    score = rng.standard_normal(n).astype(np.float32)
    score[5:30] = 0.0
    a = reference_rank.lambdarank(score, label, qb, norm=norm, loops=True)
    b = reference_rank.lambdarank(score, label, qb, norm=norm)
    for p, q in zip(a, b):
        np.testing.assert_allclose(p, q, rtol=0, atol=1e-12)
    assert np.abs(a[0]).max() > 0
    # lambdas of a query sum to zero; hessians are not negative
    for q in range(len(sizes)):
        assert abs(a[0][qb[q]:qb[q + 1]].sum()) < 1e-12
    assert a[1].min() >= 0


def test_ndcg_handles_ties_in_index_order_and_empty_queries():
    # query 0: scores tied -> index order; query 1: all labels 0 -> 1.0
    label = np.array([0, 3, 1, 0, 0])
    qb = np.array([0, 3, 5])
    tied = reference_rank.ndcg_at(2, np.zeros(5), label, qb)
    gain = reference_rank.default_label_gain(3)
    d = reference_rank.discount(np.arange(3))
    dcg = gain[0] * d[0] + gain[3] * d[1]
    best = gain[3] * d[0] + gain[1] * d[1]
    assert tied == pytest.approx((dcg / best + 1.0) / 2.0)
    assert reference_rank.ndcg_at(
        2, np.array([0.0, 2.0, 1.0, 0.0, 0.0]), label, qb) == 1.0


def test_span_arg_ratio_reads_the_boosting_span():
    from run import module
    reduce = module("reducers", "span_arg_ratio").reduce
    spans = [
        {"name": "Boosting", "args": {"pairs_visited": 30, "pair_slots": 40}},
        {"name": "Boosting", "args": {"pairs_visited": 30, "pair_slots": 40}},
        {"name": "Tree::grow", "args": {"splits": 3}},
    ]
    kw = dict(span="Boosting", num="pairs_visited", den="pair_slots",
              scale=100.0)
    assert reduce({"spans": spans}, **kw) == 75.0
    # a program whose span carries no such args: nothing to read
    assert reduce({"spans": [{"name": "Boosting", "args": {}}]}, **kw) is None
    assert reduce({"spans": []}, **kw) is None


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "msltr-train-2m", "--seed", str(2**31 + 5),
         "--seconds", "5", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 3, out.stderr[-2000:]
    assert '"note": "gradient_check"' in out.stdout
    # auc_share reads a fixed number of trees: the window is too short
    # for them here, so the rest are trained after it
    notes = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith('{"note"')]
    top_up = next(n for n in notes if n["note"] == "top_up")
    assert top_up["trees_at_close"] < top_up["trees"] == top_up["auc_trees"]
    assert top_up["built"] == 0
    auc = next(n for n in notes if n["note"] == "auc")
    assert auc["trees"] == top_up["auc_trees"] and 0 < auc["auc_share"] < 100
    assert '"objective_not_streamable"' in out.stdout
    assert "rank_pair_fill" in out.stderr and "gradients_ms_per_iter" in \
        out.stderr
