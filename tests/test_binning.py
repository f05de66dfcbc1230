import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.binning import BinMapper, BinType, MissingType
from lightgbm_tpu.io.dataset_core import BinnedDataset


def test_simple_numerical_bins():
    vals = np.arange(100, dtype=np.float64)
    m = BinMapper.find_bin(vals, 100, max_bin=10, min_data_in_bin=1)
    assert 2 <= m.num_bins <= 10
    b = m.values_to_bins(vals)
    # monotone: larger value -> same or larger bin
    assert np.all(np.diff(b) >= 0)
    # roughly equal-count
    counts = np.bincount(b)
    assert counts.max() <= 3 * counts[counts > 0].min() + 20


def test_distinct_fewer_than_max_bin():
    vals = np.repeat([1.0, 2.0, 5.0], 30)
    m = BinMapper.find_bin(vals, 90, max_bin=255, min_data_in_bin=3)
    b = m.values_to_bins(np.array([1.0, 2.0, 5.0]))
    assert len(set(b.tolist())) == 3
    # boundaries at midpoints
    assert m.values_to_bins(np.array([1.4]))[0] == b[0]
    assert m.values_to_bins(np.array([1.6]))[0] == b[1]


def test_nan_bin():
    vals = np.concatenate([np.random.default_rng(0).normal(size=500),
                           [np.nan] * 50])
    m = BinMapper.find_bin(vals, 550, max_bin=63, min_data_in_bin=3)
    assert m.missing_type == MissingType.NAN
    assert m.values_to_bins(np.array([np.nan]))[0] == m.nan_bin
    assert m.has_nan_bin


def test_zero_as_missing():
    vals = np.concatenate([np.zeros(100), np.arange(1, 101)])
    m = BinMapper.find_bin(vals, 200, max_bin=63, zero_as_missing=True)
    assert m.missing_type == MissingType.ZERO
    assert m.values_to_bins(np.array([np.nan]))[0] == m.values_to_bins(np.array([0.0]))[0]


def test_zero_protected_bin():
    # sparse-style data: zeros should have a dedicated bin
    rng = np.random.default_rng(0)
    vals = np.where(rng.random(1000) < 0.7, 0.0, rng.normal(size=1000))
    m = BinMapper.find_bin(vals, 1000, max_bin=63)
    zb = m.values_to_bins(np.array([0.0]))[0]
    assert m.values_to_bins(np.array([0.5]))[0] != zb
    assert m.values_to_bins(np.array([-0.5]))[0] != zb


def test_categorical():
    rng = np.random.default_rng(0)
    vals = rng.choice([3, 7, 11], size=300).astype(np.float64)
    m = BinMapper.find_bin(vals, 300, max_bin=63, bin_type=BinType.CATEGORICAL)
    b = m.values_to_bins(np.array([3.0, 7.0, 11.0, 999.0, np.nan]))
    assert len(set(b[:3].tolist())) == 3
    assert b[3] == 0 and b[4] == 0  # unseen & NaN -> other bin


def test_bin_to_threshold_consistency():
    rng = np.random.default_rng(1)
    vals = rng.normal(size=2000)
    m = BinMapper.find_bin(vals, 2000, max_bin=63)
    x = rng.normal(size=500)
    bins = m.values_to_bins(x)
    for t in range(m.num_bins - 1 - m.has_nan_bin):
        thr = m.bin_to_threshold(t)
        np.testing.assert_array_equal(bins <= t, x <= thr)


def test_dataset_construct_and_cache(tmp_path):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 5))
    y = rng.normal(size=500).astype(np.float32)
    cfg = Config.from_params({"max_bin": 63})
    ds = BinnedDataset.construct(X, cfg, label=y, weight=np.ones(500))
    assert ds.bin_matrix.shape == (500, 5)
    p = str(tmp_path / "d.bin")
    ds.save_binary(p)
    ds2 = BinnedDataset.load_binary(p)
    np.testing.assert_array_equal(ds.bin_matrix, ds2.bin_matrix)
    np.testing.assert_array_equal(ds.metadata.label, ds2.metadata.label)


def test_subset():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(100, 3))
    cfg = Config()
    ds = BinnedDataset.construct(X, cfg, label=np.arange(100, dtype=np.float32))
    sub = ds.subset(np.array([5, 10, 20]))
    assert sub.num_data == 3
    np.testing.assert_array_equal(sub.metadata.label, [5, 10, 20])


def _greedy_loop(dv, counts, max_bin, total_cnt, min_data_in_bin):
    """The per-distinct-value loop of ``_greedy_find_boundaries`` when
    no value is big enough for a bin of its own: the oracle of the
    cumulative-count search that replaced it there."""
    bounds, cur = [], 0
    remaining_cnt, remaining_bins = total_cnt, max(max_bin, 1)
    mean_rest = remaining_cnt / remaining_bins
    lower = max(min_data_in_bin, 1)
    for i in range(len(dv) - 1):
        cur += counts[i]
        if cur >= max(lower, mean_rest):
            bounds.append((dv[i] + dv[i + 1]) / 2.0)
            remaining_cnt -= cur
            remaining_bins = max(remaining_bins - 1, 1)
            mean_rest = remaining_cnt / remaining_bins
            cur = 0
        if len(bounds) >= max_bin - 1:
            break
    return bounds


@pytest.mark.parametrize("kind", ["normal", "float32", "rounded",
                                  "repeats", "tiny"])
@pytest.mark.parametrize("max_bin,min_data_in_bin", [
    (63, 3), (255, 3), (15, 20), (2, 1)])
def test_greedy_bounds_without_big_values_match_the_loop(
        kind, max_bin, min_data_in_bin):
    """Where no sampled value holds a bin's worth of rows
    (continuous columns), the bounds come from a search on cumulative
    counts - the same floats the per-value loop gives, bit for bit."""
    from lightgbm_tpu.io.binning import _greedy_find_boundaries
    rng = np.random.default_rng(max_bin * 7 + min_data_in_bin)
    n = {"tiny": 300}.get(kind, 20000)
    v = {"normal": lambda: rng.normal(size=n),
         "float32": lambda: rng.normal(size=n).astype(np.float32),
         "rounded": lambda: np.round(rng.exponential(size=n), 3),
         "repeats": lambda: rng.integers(0, n // 5, size=n),
         "tiny": lambda: rng.normal(size=n)}[kind]().astype(np.float64)
    dv, cnt = np.unique(v, return_counts=True)
    assert len(dv) > max_bin and not (cnt >= n / max_bin).any()
    got = _greedy_find_boundaries(dv, cnt, max_bin, n, min_data_in_bin)
    assert got == _greedy_loop(dv, cnt, max_bin, n, min_data_in_bin)
    assert 0 < len(got) <= max_bin - 1
