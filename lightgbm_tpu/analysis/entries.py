"""Grow-level analyzer registrations (kernel-level hooks live in the
``ops/pallas/*.py`` modules themselves).

Registered here:

* ``grow_serial``   — the row-order grow program (the shapes the
  ISSUE-2 jaxpr pins trace), for host-sync coverage of the whole
  jitted tree-growth loop.
* ``grow_physical`` — the physical-partition grow core (off-TPU this
  traces the interpret reference path; the compiled kernel geometry is
  covered by the per-kernel registrations).
* purity pins ``grow-tracer-live`` and ``grow-obs-lifecycle`` — the
  registered home of the "telemetry on or off => identical program"
  invariant that used to live as ad-hoc string compares in
  tests/test_obs.py.
* mesh configs (ISSUE 8) — the PADDED feature counts the gbdt
  data-parallel path ships (``device_data.pad_features_to_shards``
  over a representative feature x shard matrix), registered so the
  lane pass proves ``f_log % n_shards == 0`` statically: a padding
  regression is a ``HIST_SCATTER_FALLBACK`` finding at analysis time,
  not a run-time warn-once.
"""
from __future__ import annotations

import functools

from .registry import (register_kernel, register_mesh_config,
                       register_purity_pin, sds)


def _grow_args(n: int, f: int):
    import jax.numpy as jnp
    return (sds((n, f), jnp.uint8), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((f,), jnp.float32), sds((f,), jnp.int32),
            sds((f,), jnp.bool_), sds((f,), jnp.bool_),
            sds((), jnp.int32))


def _hp():
    from ..ops.split import SplitHyperParams
    return SplitHyperParams(min_data_in_leaf=2)


@register_kernel("grow_serial", kind="grow",
                 note="row-order grow loop, telemetry off")
def _grow_serial():
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    fn = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    return fn, _grow_args(n, f)


@register_kernel("grow_physical", kind="grow", donate=(0, 1),
                 note="physical-partition grow core (interpret path "
                      "off-TPU); comb+scratch donation audited")
def _grow_physical():
    import jax.numpy as jnp
    from ..ops.grow import make_grow_fn
    n, f, b = 4096, 16, 32
    gp = make_grow_fn(_hp(), num_leaves=8, padded_bins=b,
                      physical_bins=sds((n, f), jnp.uint8))
    n_phys = gp._n_alloc
    args = (sds((n_phys, gp._C), jnp.float32),
            sds((n_phys, gp._C), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((f,), jnp.float32),
            sds((f,), jnp.int32), sds((f,), jnp.bool_),
            sds((f,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32))
    return gp._grow_p, args


@register_kernel("grow_physical_mc", kind="grow", donate=(0, 1),
                 note="batched multiclass grow: ONE scan-over-K "
                      "dispatch grows all K class trees (ISSUE 19); "
                      "comb carried through the scan, donation "
                      "audited on the threaded comb/scratch")
def _grow_physical_mc():
    import jax.numpy as jnp
    from ..ops.grow import make_grow_fn
    n, f, b, k = 4096, 16, 32, 4
    gp = make_grow_fn(_hp(), num_leaves=8, padded_bins=b,
                      physical_bins=sds((n, f), jnp.uint8))
    n_phys = gp._n_alloc
    args = (sds((n_phys, gp._C), jnp.float32),
            sds((n_phys, gp._C), jnp.float32),
            sds((k, n), jnp.float32), sds((k, n), jnp.float32),
            sds((n,), jnp.float32), sds((k, f), jnp.float32),
            sds((f,), jnp.int32), sds((f,), jnp.bool_),
            sds((f,), jnp.bool_), sds((k,), jnp.int32))
    return gp.batched_fn(), args


def efb_demo_geometry():
    """The ONE synthetic EFB lattice cell both the analyzer entry
    (``grow_physical_efb``) and the cost-model parity test
    (tests/test_mem.py) build, so the footprint-equals-jaxpr guarantee
    always covers the exact shape the lane/vmem/hbm passes price.
    Bundle map in the io/bundle.py layout: 4 unbundled 32-bin features
    in columns 0-3, then 3 bundles of 4 x 8-bin features (offsets 1,
    9, 17, 25 -> 33-bin stacked columns).  Returns (bundle, geometry
    kwargs for ``make_grow_fn``)."""
    import numpy as np
    f_log, f_phys = 16, 8          # 12 bundled features in 3 columns
    bundle = {
        "feat_phys": np.array([0, 1, 2, 3]
                              + [4 + j // 4 for j in range(12)],
                              np.int32),
        "feat_offset": np.array([0] * 4 + [1 + 8 * (j % 4)
                                           for j in range(12)],
                                np.int32),
        "feat_default": np.zeros(f_log, np.int32),
        "is_bundled": np.array([False] * 4 + [True] * 12),
        "num_bins_log": np.array([32] * 4 + [8] * 12, np.int32),
    }
    return bundle, dict(n=4096, f_log=f_log, f_phys=f_phys,
                        padded_bins=48, padded_bins_log=32,
                        num_leaves=8)


def _grow_physical_efb_form(hp, f_comb):
    """The physical grow over the demo bundle map, with the comb width
    the form has to engage."""
    import jax.numpy as jnp
    from ..ops.grow import make_grow_fn
    bundle, geo = efb_demo_geometry()
    n, f_log, f_phys = geo["n"], geo["f_log"], geo["f_phys"]
    gp = make_grow_fn(hp, num_leaves=geo["num_leaves"],
                      padded_bins=geo["padded_bins"],
                      padded_bins_log=geo["padded_bins_log"],
                      bundle=bundle,
                      physical_bins=sds((n, f_phys), jnp.uint8))
    assert gp._f_pad == {"bundled": f_phys, "unbundled": f_log}[f_comb], \
        gp._f_pad
    n_phys = gp._n_alloc
    args = (sds((n_phys, gp._C), jnp.float32),
            sds((n_phys, gp._C), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((f_log,), jnp.float32),
            sds((f_log,), jnp.int32), sds((f_log,), jnp.bool_),
            sds((f_log,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32))
    return gp._grow_p, args


@register_kernel("grow_physical_efb", kind="grow", donate=(0, 1),
                 note="physical grow over a BUNDLED dataset with the "
                      "plain finder (ISSUE 36): the comb keeps one "
                      "column a bundle, so the lane/vmem/hbm passes "
                      "price the bundled geometry, and the split "
                      "finder works in bundle space")
def _grow_physical_efb():
    return _grow_physical_efb_form(_hp(), "bundled")


@register_kernel("grow_physical_efb_unbundled", kind="grow", donate=(0, 1),
                 note="physical grow over a BUNDLED dataset with a grow "
                      "option the bundle-space finder does not cover "
                      "(ISSUE 12: the comb ingests the unbundled "
                      "logical width, and the passes price that)")
def _grow_physical_efb_unbundled():
    return _grow_physical_efb_form(_hp()._replace(use_extra_trees=True),
                                   "unbundled")


@register_kernel("grow_stream", kind="grow", donate=(0, 1, 11),
                 note="stream-mode physical grow with the fused root "
                      "carry; comb+scratch+root_hist donation audited "
                      "(the ISSUE-9 fix: an undonated carry double-"
                      "allocates every call)")
def _grow_stream():
    import jax.numpy as jnp
    from ..ops.grow import make_grow_fn
    n, f, b = 4096, 16, 32
    gp = make_grow_fn(
        _hp(), num_leaves=8, padded_bins=b,
        physical_bins=sds((n, f), jnp.uint8),
        stream={"kind": "binary", "sigmoid": 1.0, "count": n})
    n_phys = gp._n_alloc
    args = [sds((n_phys, gp._C), jnp.float32),
            sds((n_phys, gp._C), jnp.float32),
            sds((1,), jnp.float32), sds((1,), jnp.float32),
            sds((1,), jnp.float32), sds((f,), jnp.float32),
            sds((f,), jnp.int32), sds((f,), jnp.bool_),
            sds((f,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32)]
    if gp._root0_fn is not None:
        # fused root carry engaged (the shipping stream default): the
        # carried root histogram rides argnum 11 and must alias
        args.append(sds((f, b, 2), jnp.float32))
    else:
        # LGBM_TPU_FUSED=0: no carry argument exists — narrow the
        # declared donation so the audit checks what this build ships
        from .registry import KERNELS
        KERNELS["grow_stream"].donate = (0, 1)
    return gp._grow_p, tuple(args)


@register_kernel("paged_window_update", kind="paged", donate=(0,),
                 note="paged comb window assembly (ISSUE 15): one "
                      "page buffer lands into the donated grow-time "
                      "window (ops/paged.PageStore) — the per-page "
                      "program whose buffer shapes tests/test_mem.py "
                      "equality-checks against the planner's page "
                      "geometry")
def _paged_window_update():
    import jax.numpy as jnp

    from ..ops.paged import PageStore
    store = PageStore(n_alloc=4096 + 5120, C=128, rows_per_page=2048)
    fn = store._update_fn()
    return fn, (sds((store.n_alloc, store.C), jnp.float32),
                sds((store.page_lines, store.C), jnp.float32),
                sds((), jnp.int32), sds((), jnp.int32))


@register_kernel("paged_page_extract", kind="paged",
                 note="paged comb write-back slice (ISSUE 15): one "
                      "page buffer extracted from the window for the "
                      "host flush")
def _paged_page_extract():
    import jax.numpy as jnp

    from ..ops.paged import PageStore
    store = PageStore(n_alloc=4096 + 5120, C=128, rows_per_page=2048)
    fn = store._extract_fn()
    return fn, (sds((store.n_alloc, store.C), jnp.float32),
                sds((), jnp.int32))


@register_purity_pin("grow-paged-off")
def _pin_paged_off():
    """The paged comb is pure ORCHESTRATION: the grow program a paged
    build compiles must be identical to the unpaged build's — the
    kernels extend their grid over pages without being rewritten (the
    ISSUE-15 tentpole contract), so paging can never perturb the
    trained trees at the program level."""
    import jax.numpy as jnp

    from ..ops.grow import make_grow_fn
    n, f, b = 4096, 16, 32
    unpaged = make_grow_fn(
        _hp(), num_leaves=8, padded_bins=b,
        physical_bins=sds((n, f), jnp.uint8),
        stream={"kind": "binary", "sigmoid": 1.0, "count": n})
    paged = make_grow_fn(
        _hp(), num_leaves=8, padded_bins=b,
        physical_bins=sds((n, f), jnp.uint8),
        stream={"kind": "binary", "sigmoid": 1.0, "count": n},
        paged={"rows_per_page": 2048})
    n_phys = unpaged._n_alloc
    args = [sds((n_phys, unpaged._C), jnp.float32),
            sds((n_phys, unpaged._C), jnp.float32),
            sds((1,), jnp.float32), sds((1,), jnp.float32),
            sds((1,), jnp.float32), sds((f,), jnp.float32),
            sds((f,), jnp.int32), sds((f,), jnp.bool_),
            sds((f,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32)]
    if unpaged._root0_fn is not None:
        args.append(sds((f, b, 2), jnp.float32))
    args = tuple(args)
    # ISSUE 39: the paged build still returns the row-order ``leaf_id``
    # (its pages leave the device between trees, so its booster keeps
    # the train score every tree); the unpaged build returns None
    # there.  The pin holds everything else: the program that grows
    # the tree and refreshes the comb.
    return [("unpaged", _without_leaf_id(unpaged._grow_p, args), args),
            ("paged", _without_leaf_id(paged._grow_p, args), args)]


def _without_leaf_id(grow_p, args):
    """``grow_p`` less its second output (``leaf_id``) and the
    equations only that output needs."""
    import jax
    from jax._src.interpreters import partial_eval as pe
    # the program itself, out of the one ``pjit`` that wraps it: traced
    # again in line, a constant nothing reads any more is not in it
    (eqn,) = jax.make_jaxpr(grow_p)(*args).jaxpr.eqns
    inner = eqn.params["jaxpr"]
    out = jax.eval_shape(grow_p, *args)
    first = len(jax.tree.leaves(out[0]))
    width = len(jax.tree.leaves(out[1]))
    used = [not first <= i < first + width
            for i in range(len(inner.jaxpr.outvars))]
    jaxpr, _ = pe.dce_jaxpr(inner.jaxpr, used, instantiate=True)
    return functools.partial(jax.core.eval_jaxpr, jaxpr, inner.consts)


@register_purity_pin("grow-tracer-live")
def _pin_tracer_live():
    """Turning the tracer on changes no compiled program (ISSUE 27): a
    grow program built AND traced while the tracer is live must be the
    program of a build that never saw it.  The work counters come from
    the finished tree on the host (obs/counters.counters_from_tree),
    so nothing in ``make_grow_fn`` may look at the tracer."""
    from ..obs import tracer
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    args = _grow_args(n, f)
    off = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)

    def live(*a):
        was = tracer.enabled
        tracer.enable(None)
        try:
            return make_grow_fn(_hp(), num_leaves=8, padded_bins=b)(*a)
        finally:
            if not was:
                tracer.disable()
                tracer.reset()

    return [("tracer-off", off, args), ("tracer-live", live, args)]


@register_purity_pin("grow-obs-lifecycle")
def _pin_obs_lifecycle():
    """Exercising the obs tracer / ledger / reset lifecycle must not
    leak into a later counter-free grow build."""
    from .. import obs
    from ..obs import costmodel  # noqa: F401 (import hook)
    from ..obs import tracer
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    args = _grow_args(n, f)
    before = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    tracer.enable(None)
    with tracer.span("analysis-probe"):
        pass
    obs.ledger.sample(0)
    tracer.disable()
    tracer.reset()
    obs.reset_run()
    after = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    return [("before-obs", before, args), ("after-obs", after, args)]


@register_purity_pin("grow-pulse-off")
def _pin_pulse_off():
    """Exercising the pulse heartbeat lifecycle (ISSUE 20: a mem-mode
    emitter beating, evented and reset) must not leak into a later
    counter-free grow build — the proof that LGBM_TPU_PULSE=off
    compiles the identical program and a pulsed run's beats live
    strictly outside the traced jit."""
    import os

    from ..obs import pulse
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    args = _grow_args(n, f)
    before = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    prev = os.environ.get(pulse.PULSE_ENV)
    os.environ[pulse.PULSE_ENV] = "mem"
    try:
        em = pulse.emitter("analysis-probe")
        assert em is not None
        em.beat("probe::beat", iteration=0, total=2, force=True)
        em.beat("probe::beat", iteration=1, total=2, force=True)
        em.event("end", iteration=1)
    finally:
        if prev is None:
            os.environ.pop(pulse.PULSE_ENV, None)
        else:
            os.environ[pulse.PULSE_ENV] = prev
        pulse._reset()
    after = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    return [("before-pulse", before, args),
            ("after-pulse", after, args)]


@register_purity_pin("grow-numerics-off")
def _pin_numerics_off():
    """numerics="off" must compile the identical program to a build
    that never heard of the guardrails (the default): the ISSUE-13
    contract that LGBM_TPU_NUMERICS costs nothing unless asked for —
    the same shape as the PR-2 counters pin.  (clamp/raise/skip wrap
    the built callable OUTSIDE the grow jit, so the only way the knob
    could leak is make_grow_fn branching on it — exactly what this pin
    watches.)"""
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    args = _grow_args(n, f)
    off = make_grow_fn(_hp(), num_leaves=8, padded_bins=b,
                       numerics="off")
    default = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    return [("numerics=off", off, args), ("default", default, args)]


@register_purity_pin("grow-phase-hbm")
def _pin_phase_hbm():
    """The phase-granular HBM watermark sampling (ISSUE 9: gbdt's
    ``_sample_phase_hbm`` -> tracer instants + ledger
    ``record_phase_hbm``) is host-side only — exercising it must not
    leak into a later counter-free grow build (the jaxpr pin that used
    to cover the one-per-iteration instant, extended to the per-phase
    census)."""
    from .. import obs
    from ..obs import tracer
    from ..ops.grow import make_grow_fn
    n, f, b = 128, 8, 32
    args = _grow_args(n, f)
    before = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    tracer.enable(None)
    tracer.instant("hbm_live_bytes", phase="Tree::grow", bytes=0)
    obs.ledger.record_phase_hbm("Tree::grow", 0)
    obs.ledger.sample(0)
    tracer.disable()
    tracer.reset()
    obs.reset_run()
    after = make_grow_fn(_hp(), num_leaves=8, padded_bins=b)
    return [("before-mem-sampling", before, args),
            ("after-mem-sampling", after, args)]


# ---------------------------------------------------------------------
# mesh configs: the hist_scatter fast-path guard.  Register what the
# data-parallel layout ACTUALLY ships — pad_features_to_shards over the
# feature-count x shard-count x bin-width matrix — so check_hist_scatter
# (lane pass) fails the clean --strict run the day the padding helper
# stops guaranteeing divisibility.  Import-light: no jax needed.
# ---------------------------------------------------------------------
def _register_padded_mesh_configs() -> None:
    from ..ops.device_data import pad_features_to_shards
    from ..ops.histogram import (bins_per_feature_padded,
                                 feature_group_size)
    for f in (5, 10, 28, 100, 250):
        for shards in (2, 3, 4, 8, 16):
            for max_bin in (63, 255):
                g = feature_group_size(bins_per_feature_padded(max_bin))
                register_mesh_config(
                    pad_features_to_shards(f, g, shards), shards,
                    source=f"pad_features_to_shards(f={f}, group={g}, "
                           f"shards={shards})")


_register_padded_mesh_configs()


# ---------------------------------------------------------------------
# serving engine (ISSUE 14): the compiled-forest predict dispatch goes
# through the same lane/vmem/hbm/host-sync passes as the training
# kernels, and its donated score buffer through the donation audit
# ---------------------------------------------------------------------
def serve_forest_args(n: int = 256, t: int = 8, ni: int = 7,
                      nl: int = 8, f: int = 6, b: int = 16,
                      w: int = 2, k: int = 1, f_orig: int = 6):
    """Abstract args of one bucketed serving dispatch, in the flat
    ``ops.predict.forest_scores_flat`` order (score buffer last — the
    donated argnum the hbm pass audits)."""
    import jax.numpy as jnp
    return (sds((t, ni), jnp.int32),      # split_feature
            sds((t, ni), jnp.int32),      # threshold_bin
            sds((t, ni), jnp.bool_),      # default_left
            sds((t, ni), jnp.bool_),      # is_categorical
            sds((t, ni), jnp.int32),      # left_child
            sds((t, ni), jnp.int32),      # right_child
            sds((t, nl), jnp.float32),    # leaf_value
            sds((t,), jnp.int32),         # init_node
            sds((t, ni * w), jnp.int32),  # cat_words (flat, ISSUE 18)
            sds((t, ni), jnp.int32),      # cat_nbits
            sds((f,), jnp.int32),         # used_cols
            sds((f, b), jnp.float32),     # ub
            sds((f,), jnp.int32),         # default_bin
            sds((f,), jnp.int32),         # num_bins
            sds((f,), jnp.bool_),         # has_nan
            sds((f,), jnp.bool_),         # missing_zero
            sds((t, ni), jnp.int32),      # node_meta (packed word)
            sds((f,), jnp.bool_),         # cat_col (ISSUE 18)
            sds((n, f_orig), jnp.float32),  # raw rows
            sds((), jnp.int32),           # n_real (traced!)
            sds((n, k), jnp.float32))     # donated score buffer


@register_kernel("serve_forest", kind="serve", donate=(20,),
                 note="bucketed compiled-forest serving dispatch "
                      "(ISSUE 14): on-device raw->bin quantize + "
                      "level-synchronous forest walk + donated score "
                      "buffer (the argnum-20 aliasing is the PR-9 "
                      "donation contract; the packed per-node "
                      "metadata word is the round-17 headroom #1)")
def _serve_forest():
    import functools

    from ..ops.predict import forest_scores_flat
    fn = functools.partial(forest_scores_flat, n_steps=5)
    return fn, serve_forest_args()
