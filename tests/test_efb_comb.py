"""EFB bundles KEPT in the comb (ISSUE 36).

On the physical route with the plain finder a bundled table keeps one
comb column a bundle: the histograms run over bundle columns, the split
finder works in bundle space (``split.find_best_split_segments``) and a
split on a bundled sub-feature reaches the partition as a membership set
of the bundle column's bins (``grow.bundled_split_members``).  Trees,
thresholds and predictions speak logical features, and - with
``max_conflict_rate=0`` - are the unbundled path's split for split.  The
leaf values are not that path's bytes (ISSUE 36 asked for equal model
text): the forms sum in different orders, and each is held here to the
float64 sums of its own leaves, the bundled comb the closest of the
three (a one-hot split's small child keeps the sum of its own rows; the
other forms give it a parent's totals minus the default bin's sum).

Everything here runs on the CPU at small sizes, the physical route
through ``LGBM_TPU_PHYS=interpret``; the plain reference is the
benchmark's ``reference_efb.py`` (float64 over RAW sparse values).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 20,
          "max_bin": 63, "learning_rate": 0.1, "verbosity": -1}


def onehot_table(n=3000, levels=(6, 7, 12, 9, 40), seed=7,
                 dense_flag=False):
    """Seeded one-hot fields + 2 numeric columns as a scipy CSR matrix,
    its label with logistic noise (so that no split is decided by
    rounding).  ``dense_flag`` adds a 0/1 column that is 1 on half of the
    rows: over EFB's sparse threshold, so it stays unbundled."""
    import scipy.sparse as sp
    rng = np.random.default_rng(seed)
    fields = [rng.integers(0, k, n) for k in levels]
    offs = np.concatenate([[0], np.cumsum(levels)[:-1]])
    n_hot = int(sum(levels))
    nums = np.stack([rng.normal(size=n), rng.gamma(2.0, size=n)], 1)
    cols = [f + o for f, o in zip(fields, offs)]
    vals = [np.ones(n)] * len(levels)
    cols += [np.full(n, n_hot), np.full(n, n_hot + 1)]
    vals += [nums[:, 0], nums[:, 1]]
    width = n_hot + 2
    logit = sum(rng.normal(0, 0.8, k)[f] for k, f in zip(levels, fields))
    logit = logit + 0.7 * nums[:, 0] - 0.3 * nums[:, 1]
    if dense_flag:
        flag = rng.random(n) < 0.5
        cols.append(np.full(n, width))
        vals.append(flag.astype(np.float64))
        logit = logit + 0.9 * flag
        width += 1
    y = (logit + rng.logistic(size=n) > 0).astype(np.float32)
    x = sp.csr_matrix(
        (np.stack(vals, 1).reshape(-1).astype(np.float32),
         (np.repeat(np.arange(n), len(cols)), np.stack(cols, 1).reshape(-1))),
        shape=(n, width))
    x.eliminate_zeros()
    return x, y


def train(monkeypatch, x, y, *, phys, rounds=3, **params):
    import lightgbm_tpu as lgb
    monkeypatch.setenv("LGBM_TPU_PHYS", phys)
    p = dict(PARAMS, **params)
    ds = lgb.Dataset(x, label=y, params=p)
    return lgb.train(p, ds, num_boost_round=rounds)


def structure(bst):
    out = []
    for t in bst._models:
        ni = int(t.num_leaves) - 1
        out.append((ni + 1, t.split_feature[:ni].tolist(),
                    t.threshold_bin[:ni].tolist(),
                    np.asarray(t.decision_type[:ni]).tolist()))
    return out


def tree0_off_float64(bst, x, y):
    """Largest distance of tree 0's leaf values from ``-lr G / H`` of
    the float64 sums over the rows the float64 walk over RAW values
    sends to each leaf."""
    import reference
    import reference_efb
    import reference_mesh
    tree0 = reference.parse_model(bst.model_to_string())[0]
    leaf = reference_efb.leaf_of_rows(tree0, reference_efb.Columns(x))
    y64 = np.asarray(y, np.float64)
    k = tree0.num_leaves
    sums = reference_mesh.LeafSums(
        count=np.bincount(leaf, minlength=k).astype(np.int64),
        sum_y=np.bincount(leaf, weights=y64, minlength=k),
        p=float(y64.mean()), leaf=leaf, y=y64)
    want = sums.leaf_values(PARAMS["learning_rate"])
    return float(np.max(np.abs(tree0.leaf_value - want)))


@pytest.fixture(scope="module")
def table():
    return onehot_table()


@pytest.fixture()
def three_forms(table, monkeypatch):
    x, y = table
    return {
        "bundled": train(monkeypatch, x, y, phys="interpret"),
        "unbundled": train(monkeypatch, x, y, phys="interpret",
                           enable_bundle=False),
        "row_order": train(monkeypatch, x, y, phys="0"),
    }


# (a) ------------------------------------------------------------------
def test_bundled_comb_gives_the_unbundled_and_the_row_order_trees(
        three_forms, table):
    r = {k: b._inner.routing_info() for k, b in three_forms.items()}
    assert (r["bundled"]["path"], r["bundled"]["efb"]) == ("stream",
                                                           "bundled")
    assert (r["unbundled"]["path"], r["unbundled"]["efb"]) == ("stream",
                                                               "none")
    assert (r["row_order"]["path"], r["row_order"]["efb"]) == ("row_order",
                                                               "expand")
    lay = three_forms["bundled"]._inner.layout_info()
    assert lay["logical_features"] == table[0].shape[1]
    assert lay["comb_cols"] == lay["phys_cols"] < 32
    assert lay["bundles"] >= 5 and lay["comb_line_bytes"] == 512
    # the same split at the same step of every tree, in all three
    want = structure(three_forms["bundled"])
    assert structure(three_forms["unbundled"]) == want
    assert structure(three_forms["row_order"]) == want
    # tree 0's leaf values, each form against the float64 sums of its
    # own leaves (off the chip a histogram bin is a sequential float32
    # scatter-add): the bundled comb reads 1.5e-6, the row_order
    # expansion 1.1e-5, the unbundled comb 3.2e-5
    x, y = table
    assert tree0_off_float64(three_forms["bundled"], x, y) < 5e-6
    assert tree0_off_float64(three_forms["row_order"], x, y) < 5e-5
    assert tree0_off_float64(three_forms["unbundled"], x, y) < 2e-4
    # three trees on, the forms' scores are as far apart as the least
    # exact of them is from its sums
    raw = {k: b.predict(x, raw_score=True) for k, b in three_forms.items()}
    for k in ("unbundled", "row_order"):
        np.testing.assert_allclose(raw["bundled"], raw[k], rtol=0,
                                   atol=2e-4)


# (b) ------------------------------------------------------------------
def test_tree_0_puts_every_row_in_the_leaf_of_the_float64_walk(
        table, monkeypatch):
    import reference
    import reference_efb
    x, y = table
    bst = train(monkeypatch, x, y, phys="interpret", rounds=1)
    scores = np.asarray(bst._inner.train_score)[0][:len(y)]
    tree0 = reference.parse_model(bst.model_to_string())[0]
    cols = reference_efb.Columns(x)
    leaf = reference_efb.leaf_of_rows(tree0, cols)
    assert tree0.num_leaves == PARAMS["num_leaves"]
    assert np.max(np.abs(scores - tree0.leaf_value[leaf])) < 1e-5
    root = reference_efb.root_gains(cols, y, tree0)
    assert root.ok(2e-2, 0.99), root


def test_member_counters_count_the_splits_on_bundled_columns(
        table, monkeypatch, tmp_path):
    from lightgbm_tpu.obs import tracer
    x, y = table
    tracer.enable(str(tmp_path / "t.jsonl"))
    try:
        bst = train(monkeypatch, x, y, phys="interpret", rounds=2)
        grows = [e["args"] for e in tracer.events
                 if e["name"] == "Tree::grow" and e["ph"] == "X"]
        setup = {e["name"]: e["args"] for e in tracer.events
                 if e["name"] in ("Dataset::bundle", "Train::layout")}
    finally:
        tracer.disable()
        tracer.reset()      # the events are the process's, not the test's
    info = bst._inner.dd.bundle
    for t, args in zip(bst._models, grows):
        ni = int(t.num_leaves) - 1
        on_bundle = info["is_bundled"][t.split_feature[:ni]]
        assert args["member_splits"] == on_bundle.sum() > 0
        assert args["rows_member"] == np.asarray(
            t.internal_count[:ni])[on_bundle].sum()
        assert args["rows_member"] <= args["rows_partitioned"]
    assert setup["Dataset::bundle"]["features_bundled"] == 74
    assert setup["Dataset::bundle"]["conflict_rows"] == 0
    assert setup["Train::layout"]["comb_line_bytes"] == 512
    assert setup["Train::layout"]["bundles"] == \
        setup["Dataset::bundle"]["bundles"]


# (c) ------------------------------------------------------------------
def _demo_bundle():
    """Columns: 0 an unbundled numeric with a NaN bin (6 bins), 1 an
    unbundled numeric (9 bins), 2 a bundle of three sub-features (3, 2
    and 4 bins; defaults 0, 1, 2), 3 a bundle of one 2-bin feature pair."""
    nb = np.array([6, 9, 3, 2, 4, 2, 2], np.int32)
    return {
        "feat_phys": np.array([0, 1, 2, 2, 2, 3, 3], np.int32),
        "feat_offset": np.array([0, 0, 1, 4, 6, 1, 3], np.int32),
        "feat_default": np.array([0, 0, 0, 1, 2, 0, 0], np.int32),
        "is_bundled": np.array([0, 0, 1, 1, 1, 1, 1], bool),
        "num_bins_log": nb,
        "has_nan": np.array([1, 0, 0, 0, 0, 0, 0], bool),
        "is_cat": np.zeros(7, bool),
    }


def _stored_value(bundle, j, lbin):
    """The bundle-column value of a row whose feature j sits at lbin and
    every other member of the column at its default."""
    if bundle["is_bundled"][j] and lbin == bundle["feat_default"][j]:
        return 0
    return int(bundle["feat_offset"][j]) + lbin


@pytest.mark.parametrize("feat", range(7))
def test_a_logical_split_becomes_the_right_set_of_bundle_bins(feat):
    """Every (feature, threshold, default direction) through the XLA
    reference partition: the rows that go left are the rows whose
    LOGICAL bin is <= the threshold (NaN-bin rows by default_left),
    whichever member of the column a row stores."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import bundled_split_members
    from lightgbm_tpu.ops.pallas.partition_kernel import \
        make_reference_partition
    from lightgbm_tpu.ops.predict import _members_to_words
    b, padded = _demo_bundle(), 256
    maps = tuple(jnp.asarray(b[k]) for k in (
        "feat_phys", "feat_offset", "feat_default", "is_bundled"))
    col = int(b["feat_phys"][feat])
    members = [j for j in range(7) if b["feat_phys"][j] == col]
    # one row for every bin of every member of the column, and the
    # all-default row of a bundle
    rows = [(j, k) for j in members for k in range(b["num_bins_log"][j])]
    stored = np.array([_stored_value(b, j, k) for j, k in rows])
    n, C = 16, 128
    comb = np.zeros((n, C), np.float32)
    comb[:len(rows), col] = stored
    comb[:, 100] = np.arange(n)          # a row id to follow the rows
    part = make_reference_partition(n, C)
    nbins = int(b["num_bins_log"][feat])
    has_nan = bool(b["has_nan"][feat])
    for sbin in range(nbins - 1 - has_nan):
        for dl in (False, True):
            c, in_bun, member = bundled_split_members(
                maps, jnp.int32(feat), jnp.int32(sbin), jnp.bool_(False),
                jnp.int32(nbins), padded)
            assert int(c) == col and bool(in_bun) == bool(
                b["is_bundled"][feat])
            sel = jnp.concatenate([jnp.asarray([
                0, len(rows), int(c), sbin, int(dl), int(in_bun),
                nbins - 1 if has_nan else -1, 0], jnp.int32),
                _members_to_words(
                    member.astype(jnp.float32)[None])[0]])
            out, _, nleft = part(sel, jnp.asarray(comb), jnp.asarray(comb))
            went_left = set(np.asarray(out)[:int(nleft), 100].astype(int))
            want = set()
            for i, (j, k) in enumerate(rows):
                # the row's logical bin of the SPLIT feature
                lb = k if j == feat else int(b["feat_default"][feat])
                if has_nan and lb == nbins - 1:
                    left = dl
                else:
                    left = lb <= sbin
                if left:
                    want.add(i)
            assert went_left == want, (feat, sbin, dl)


# (d) ------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(6))
def test_segment_finder_is_expand_plus_the_finder(seed):
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import (SplitHyperParams, find_best_split,
                                        find_best_split_segments,
                                        segment_maps, segment_weights)
    rng = np.random.default_rng(seed)
    b = _demo_bundle()
    b["is_cat"] = np.array([0, 1, 0, 0, 0, 0, 0], bool) if seed % 2 else \
        b["is_cat"]
    f_log, P, B = 7, 8, 16
    nb, phys, off = b["num_bins_log"], b["feat_phys"], b["feat_offset"]
    dfl, isb = b["feat_default"], b["is_bundled"]
    n = 600
    g = rng.normal(size=n).astype(np.float32)
    h = (np.abs(rng.normal(size=n)) + 0.1).astype(np.float32)
    logical = np.zeros((n, f_log), int)
    for j in (0, 1):
        logical[:, j] = rng.integers(0, nb[j], n)
    for col in (2, 3):
        mem = [j for j in range(f_log) if phys[j] == col]
        logical[:, mem] = dfl[mem]
        pick = rng.integers(-1, len(mem), n)     # at most one non-default
        for i, j in enumerate(mem):
            rows = np.flatnonzero(pick == i)
            other = [k for k in range(nb[j]) if k != dfl[j]]
            logical[rows, j] = rng.choice(other, len(rows))
    h_log = np.zeros((f_log, B, 2), np.float32)
    h_phy = np.zeros((P, B, 2), np.float32)
    for j in range(f_log):
        np.add.at(h_log[j, :, 0], logical[:, j], g)
        np.add.at(h_log[j, :, 1], logical[:, j], h)
        stored = np.where(isb[j] & (logical[:, j] == dfl[j]), -1,
                          off[j] + logical[:, j])
        keep = stored >= 0
        np.add.at(h_phy[phys[j], :, 0], stored[keep], g[keep])
        np.add.at(h_phy[phys[j], :, 1], stored[keep], h[keep])
    for col in (2, 3):          # bin 0: every member at its default
        h_phy[col, 0] = h_log[0].sum(0) - h_phy[col, 1:].sum(0)
    hp = SplitHyperParams(min_data_in_leaf=3)
    fmask = (rng.random(f_log) > 0.25).astype(np.float32)
    args = (float(g.sum()), float(h.sum()), float(n))
    want = find_best_split(
        jnp.asarray(h_log), *args, jnp.asarray(nb), jnp.asarray(b["has_nan"]),
        jnp.asarray(b["is_cat"]), jnp.asarray(fmask), jnp.asarray(True), hp)
    maps = segment_maps(b, P, B)
    pos_mask = jnp.take(jnp.concatenate([jnp.asarray(fmask), jnp.zeros(1)]),
                        jnp.asarray(maps["feat"]), mode="wrap")
    got = find_best_split_segments(
        jnp.asarray(h_phy), *args, maps, segment_weights(maps), pos_mask,
        jnp.asarray(True), hp)
    for name in ("feature", "threshold_bin", "default_left",
                 "is_categorical"):
        assert int(getattr(got, name)) == int(getattr(want, name)), name
    for name in ("gain", "left_sum_g", "left_sum_h", "left_count",
                 "left_output", "right_output"):
        np.testing.assert_allclose(getattr(got, name), getattr(want, name),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# (e) ------------------------------------------------------------------
def test_a_field_wider_than_a_bundle_and_a_dense_flag_beside_it(
        monkeypatch):
    x, y = onehot_table(n=4000, levels=(300, 6), seed=11, dense_flag=True)
    got = train(monkeypatch, x, y, phys="interpret", rounds=2,
                max_bin=255, min_data_in_leaf=10)
    ref = train(monkeypatch, x, y, phys="interpret", rounds=2,
                max_bin=255, min_data_in_leaf=10, enable_bundle=False)
    b = got._inner.dd.bundle
    assert got._inner.routing_info()["efb"] == "bundled"
    # 300 two-bin columns need three 255-bin bundle columns
    wide = np.unique(b["feat_phys"][:300])
    assert len(wide) >= 3 and b["is_bundled"][:300].all()
    flag = x.shape[1] - 1
    assert not b["is_bundled"][flag]       # over the sparse threshold
    assert got._inner.layout_info()["comb_line_bytes"] == 512
    assert structure(got) == structure(ref)
    # tree 0 against the float64 sums of its leaves: the bundled comb
    # reads 1.8e-5 (the cell's limit is 5e-5), the unbundled 4.5e-4
    assert tree0_off_float64(got, x, y) < 5e-5
    assert tree0_off_float64(ref, x, y) < 1e-3


def test_grow_build_refuses_the_form_the_layout_was_not_decided_for():
    """gbdt decides the EFB form once (``dd.comb_bundled``: the route,
    the footprint and ``phys_f_pad`` read it) and hands it down; a grow
    configuration that would build the other form raises instead of
    compiling a program the route does not describe."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.split import SplitHyperParams
    build = dict(num_leaves=8, padded_bins=16, padded_bins_log=16,
                 bundle=_demo_bundle(),
                 physical_bins=jax.ShapeDtypeStruct((4096, 8), jnp.uint8))
    plain = SplitHyperParams(min_data_in_leaf=2)
    with pytest.raises(ValueError, match="decided for bundled_comb=False"):
        make_grow_fn(plain, bundled_comb=False, **build)
    with pytest.raises(ValueError, match="decided for bundled_comb=True"):
        make_grow_fn(plain._replace(use_extra_trees=True),
                     bundled_comb=True, **build)


# (f) ------------------------------------------------------------------
def test_route_names_the_form_and_prices_the_width_it_allocates(
        monkeypatch):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.pallas.layout import MAX_COMB_COLS
    from lightgbm_tpu.ops.routing import RouteInputs, decide
    tpu = dict(backend="tpu")
    assert decide(RouteInputs(**tpu)).efb == "none"
    d = decide(RouteInputs(efb_bundled=True, efb_comb=True, **tpu))
    assert (d.path, d.efb) == ("stream", "bundled")
    u = decide(RouteInputs(efb_bundled=True, **tpu))
    assert (u.path, u.efb) == ("stream", "unbundled")
    assert d.digest() != u.digest() and d.to_json()["efb"] == "bundled"
    assert decide(RouteInputs(efb_bundled=True, efb_comb=True,
                              phys_env="0", **tpu)).efb == "expand"
    # a table whose logical width is past the comb's column budget and
    # whose bundle columns are a handful: the bundled comb takes it, the
    # unbundling ingest (here: extra_trees, which the bundle-space finder
    # does not cover) has to give it to row_order
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    x, y = onehot_table(n=4096, levels=(900, 900, 900), seed=3)
    p = dict(PARAMS, min_data_in_leaf=5, max_bin=15)
    ds = lgb.Dataset(x, label=y, params=p)
    ds.construct()
    assert ds._binned.num_features + 16 > MAX_COMB_COLS
    r = lgb.Booster(p, ds)._inner.routing_info()
    assert (r["path"], r["efb"], r["reasons"]) == ("stream", "bundled", [])
    r = lgb.Booster(dict(p, extra_trees=True), ds)._inner.routing_info()
    assert (r["path"], r["efb"]) == ("row_order", "expand")
    assert r["reasons"] == ["efb_overwide"]


# the sparse store behind it --------------------------------------------
def test_bundle_columns_from_stored_entries_are_the_dense_ones(table):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.bundle import build_physical_matrix
    from lightgbm_tpu.io.dataset_core import BinnedDataset
    x, y = table
    cfg = Config.from_params({"max_bin": 63})
    sparse = BinnedDataset.construct(x, cfg, label=y)
    dense = BinnedDataset.construct(np.asarray(x.todense()), cfg, label=y)
    assert sparse._bin_matrix is None and sparse.sparse_bins is not None
    assert (sparse.num_data, sparse.num_features) == dense.bin_matrix.shape
    info = sparse.bundle_info
    assert info is not None and info.any_bundled
    np.testing.assert_array_equal(info.feat_phys,
                                  dense.bundle_info.feat_phys)
    from_entries = sparse.physical_matrix(info)
    assert sparse._bin_matrix is None       # still not densified
    np.testing.assert_array_equal(
        from_entries, build_physical_matrix(dense.bin_matrix, info))
    np.testing.assert_array_equal(sparse.bin_matrix, dense.bin_matrix)
