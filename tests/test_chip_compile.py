"""Compile-for-the-chip guard: the default training path's kernels at
the Higgs width and at the MS LTR width (137 features: a comb line of
two 128-lane planes), and the compiled serving walk, must get through the
v5e compiler (Mosaic + XLA:TPU) — and today's known refusals stay
pinned so the PR that fixes one has to flip its pin.

Nothing here runs on a device: the TPU compiler is installed with
libtpu and compiles for a DESCRIBED ``v5e:2x2`` chip under
``JAX_PLATFORMS=cpu``.  Interpret-mode parity (every other test of
these kernels) says nothing about lowering; this file is the off-chip
half of ``python chip_smoke.py``.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may hold libtpu, so nothing chip-related may
happen at import/collection time (every xdist worker imports this
file), and all compile cases live in this ONE file so they land on one
worker.
"""
from __future__ import annotations

import functools

import pytest

# Higgs-like 1M x 28 on the default physical+stream+fused route:
# 1,000,000 rows pad to whole 2,048-row blocks (grow.PHYS_ROW_PAD),
# plus PHYS_ROW_SLACK.  The rows a step of the scan moves are not
# written here: every builder below asks ``scan_block_rows`` as
# ops/grow.py does (``_scan_rows``), 2,048 at one plane and 1,024 at two
N_PAD, N_ALLOC, C, F_PAD, BINS, LEAVES = (
    1_001_472, 1_007_616, 128, 32, 256, 255)
HIGGS = (N_PAD, N_ALLOC, C, F_PAD)
# MS LTR, 2,270,296 x 137 on the non-stream physical route: 137 features
# pad to 144 columns, + 6 value / row-id columns = 150 lanes, C = 256.
# A [n, 256] f32 array is tiled (8, 128) in HBM and Mosaic refuses a row
# DMA at an arbitrary row offset into it; the comb is plane-major
# (ops/pallas/layout.py), which is what these cases hold
MSLTR = (2_271_232, 2_277_376, 256, 144)
# Epsilon, 400,000 x 2,000 dense on the stream route at 63 bins (64
# padded): 401,408 rows + 6,144 lines of slack, 2,000 bin columns + 13
# stream columns = 2,048 lanes, sixteen planes
EPSILON = (401_408, 407_552, 2048, 2000)


def _scan_rows(geom, scan: str = "permute") -> int:
    """The block ops/grow.py builds the scan at for this geometry."""
    from lightgbm_tpu.ops.pallas.fused_split import scan_block_rows
    return scan_block_rows(geom[2], scheme=scan)


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-device compile is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _part_args(n_alloc, c):
    """(sel, rows, scratch, grid_blocks) of the dynamic-grid scans."""
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import partition_args, sds
    return partition_args(n_alloc, c) + (sds((), jnp.int32),)


def _fused(scan: str, geom=HIGGS, raw_hist=False):
    """The fused scan, one kernel for the three states of
    ``sel[SEL_SIDE]`` (left, right, no child: ISSUE 35);
    ``raw_hist=True`` is the form ops/grow.py calls, the accumulator
    handed on as it is."""
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    _, n_alloc, c, f_pad = geom
    fn = make_fused_split(n_alloc, c, f_pad=f_pad, padded_bins=BINS,
                          R=_scan_rows(geom, scan), dynamic=True,
                          scan=scan, raw_hist=raw_hist)
    return fn, _part_args(n_alloc, c)


def _partition_perm(geom=HIGGS):
    from lightgbm_tpu.ops.pallas.partition_kernel3 import \
        make_partition_perm
    _, n_alloc, c, _ = geom
    return (make_partition_perm(n_alloc, c, dynamic=True,
                                R=_scan_rows(geom)),
            _part_args(n_alloc, c))


def _stream(which: str, geom=HIGGS):
    """The stream route's kernels; at the MS LTR width its 13 score /
    constant columns follow the 144 bin columns into the second plane
    (no cell runs that yet: a binary or l2 job of more than 109
    features would)."""
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.pallas.stream_grad import (N_CONSTS, make_init,
                                                     make_refresh,
                                                     stream_block_rows)
    n_pad, n_alloc, c, f_pad = geom
    kw = dict(kind="binary", sigmoid=1.0, f=f_pad, n_alloc=n_alloc,
              n_pad=n_pad, C=c, R=stream_block_rows(c))
    comb = sds(comb_shape(n_alloc, c), jnp.float32)
    if which == "init":
        return make_init(f_real=f_pad, **kw), (
            comb, sds((n_pad, f_pad), jnp.uint8),
            sds((2 + N_CONSTS["binary"], n_pad), jnp.float32))
    fn = make_refresh(root_hist=which == "refresh_root",
                      padded_bins=BINS, **kw)
    return fn, (comb, sds((1, n_pad), jnp.float32))


def _hist_comb_root(geom=HIGGS):
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.pallas.hist_kernel2 import build_histogram_comb
    from lightgbm_tpu.ops.pallas.layout import comb_planes, comb_shape
    n_pad, n_alloc, c, f_pad = geom
    fn = functools.partial(build_histogram_comb, f_pad=f_pad, size=n_pad,
                           padded_bins=BINS, planes=comb_planes(c))
    return fn, (sds(comb_shape(n_alloc, c), jnp.float32),) + (
        sds((), jnp.int32),) * 3


def _hist_comb_dyn(geom=HIGGS):
    """The dynamic-grid comb histogram: the unfused route's child pass,
    and the fused route's re-histogram of a split whose scan was told
    the other child (ops/grow.py; ISSUE 30)."""
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.pallas.hist_kernel2 import \
        build_histogram_comb_dyn
    from lightgbm_tpu.ops.pallas.layout import comb_planes, comb_shape
    _, n_alloc, c, f_pad = geom
    fn = functools.partial(build_histogram_comb_dyn, f_pad=f_pad,
                           padded_bins=BINS, planes=comb_planes(c))
    return fn, (sds(comb_shape(n_alloc, c), jnp.float32),) + (
        sds((), jnp.int32),) * 3


def _apply_find_pool(f: int):
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.pallas.apply_find import (_finder_args,
                                                    make_apply_find_pool)
    from lightgbm_tpu.ops.split import SplitHyperParams
    fn = make_apply_find_pool(SplitHyperParams(min_data_in_leaf=20),
                              L=LEAVES, f=f, b=BINS, max_depth=-1)
    return fn, _finder_args(LEAVES, f, BINS, ()) + (
        sds((LEAVES, f, 4, BINS), jnp.float32),)


def _serve_forest():
    """The XLA gather walk ``Booster.predict`` runs by default, over a
    100-tree x 255-leaf forest (a 4096-row bucket: XLA:TPU's compile
    time grows with the bucket, 2.5 s here against 45 s at the 65536
    cap, and the program is the same)."""
    from lightgbm_tpu.analysis.entries import serve_forest_args
    from lightgbm_tpu.ops.predict import forest_scores_flat
    fn = functools.partial(forest_scores_flat, n_steps=24)
    return fn, serve_forest_args(n=4096, t=100, ni=256, nl=256, f=28,
                                 b=256, w=0, k=1, f_orig=28)


def _registered(name: str):
    from lightgbm_tpu.analysis.registry import collect
    return collect()[name].builder()


# (builder, must the HLO hold a Mosaic kernel?)
COMPILES = {
    "fused_split_permute": (functools.partial(_fused, "permute"), True),
    "fused_split_matmul": (functools.partial(_fused, "matmul"), True),
    "partition_perm": (_partition_perm, True),
    "stream_init": (functools.partial(_stream, "init"), True),
    "stream_refresh": (functools.partial(_stream, "refresh"), True),
    "stream_refresh_root": (functools.partial(_stream, "refresh_root"),
                            True),
    "hist_comb_root": (_hist_comb_root, True),
    "apply_find_pool_f28": (functools.partial(_apply_find_pool, 28), True),
    "apply_find_pool_f32": (functools.partial(_apply_find_pool, 32), True),
    "serve_forest_100x255": (_serve_forest, False),
    "fused_split_permute_msltr": (
        functools.partial(_fused, "permute", MSLTR), True),
    "fused_split_matmul_msltr": (
        functools.partial(_fused, "matmul", MSLTR), True),
    "partition_perm_msltr": (
        functools.partial(_partition_perm, MSLTR), True),
    "hist_comb_root_msltr": (
        functools.partial(_hist_comb_root, MSLTR), True),
    "hist_comb_dyn": (_hist_comb_dyn, True),
    "hist_comb_dyn_msltr": (functools.partial(_hist_comb_dyn, MSLTR),
                            True),
    "stream_init_msltr": (functools.partial(_stream, "init", MSLTR),
                          True),
    "stream_refresh_root_msltr": (
        functools.partial(_stream, "refresh_root", MSLTR), True),
    "fused_split_permute_raw": (
        functools.partial(_fused, "permute", HIGGS, True), True),
    "fused_split_permute_raw_msltr": (
        functools.partial(_fused, "permute", MSLTR, True), True),
    # sixteen planes: the grow program holds the scan, the
    # copy-back, both histograms and the refresh (below); the init is
    # the one comb kernel outside it
    "stream_init_epsilon": (functools.partial(_stream, "init", EPSILON),
                            True),
}


def _compile(builder, one_chip):
    import jax
    fn, args = builder()
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
                 for a in args)
    return jax.jit(fn).lower(*args).compile()


@pytest.fixture(scope="module")
def compiled_text(one_chip, no_compile_cache):
    """``COMPILES[name]`` through the v5e compiler, as HLO text; each
    program is compiled once for all the tests that read it."""
    texts = {}

    def get(name):
        if name not in texts:
            texts[name] = _compile(COMPILES[name][0], one_chip).as_text()
        return texts[name]

    return get


@pytest.mark.parametrize("name", sorted(COMPILES))
def test_default_path_compiles_for_v5e(name, compiled_text):
    if COMPILES[name][1]:
        assert "tpu_custom_call" in compiled_text(name), (
            f"{name} compiled without a Mosaic kernel — an interpret-"
            "mode or XLA fallback slipped onto the chip path")


# The names the kernels of the ``higgs`` route carry into the compiled
# program (``pallas_call(name=...)``).  The profiler's ``XLA Ops`` line
# names an event by its HLO instruction, so these are what the per-layer
# metrics of ``benchmarks/`` are keyed on (``split_scan_ms_per_iter``
# reads ``lgbm_split_scan``); unnamed, the trace calls a kernel after
# whatever encloses it (``%body.23``), which moves with any refactor.
KERNEL_NAMES = {
    "lgbm_split_scan": "fused_split_permute",
    "lgbm_copyback": "fused_split_permute",
    "lgbm_refresh": "stream_refresh_root",
    "lgbm_hist": "hist_comb_root",
    "lgbm_apply_find": "apply_find_pool_f32",
}


@pytest.mark.parametrize("kernel", sorted(KERNEL_NAMES))
def test_kernel_names_reach_the_compiled_program(kernel, compiled_text):
    import re
    text = compiled_text(KERNEL_NAMES[kernel])
    named = re.findall(
        r"%(" + kernel + r")(?:\.\d+)? = [^\n]*custom-call\([^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", text)
    assert named, f"no Mosaic kernel named {kernel} in the compiled HLO"
    # and no Mosaic kernel of these programs is left to be named after
    # its surroundings
    every = re.findall(r"%([A-Za-z_][\w-]*?)(?:\.\d+)? = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert set(every) <= set(KERNEL_NAMES), every


@pytest.mark.parametrize("name,groups", [
    ("fused_split_permute", 4), ("fused_split_matmul", 4),
    ("fused_split_permute_msltr", 18), ("fused_split_matmul_msltr", 18),
    ("fused_split_permute_raw", 4), ("fused_split_permute_raw_msltr", 18)])
def test_the_fused_scan_accumulates_one_child(name, groups, compiled_text):
    """ISSUE 30: the scan's resident accumulator is ONE [groups, M, N]
    block - the child ``sel[SEL_SIDE]`` names - at the Higgs width (32
    columns, 4 groups of 8) and at 144 columns over two planes (18
    groups): 0.5 MB and 2.4 MB of VMEM where the two-sided hook held
    twice that."""
    import re
    scans = re.findall(r"%lgbm_split_scan(?:\.\d+)? = (\([^\n]*?\)) "
                       r"custom-call", compiled_text(name))
    assert len(scans) == 1, scans
    assert f"f32[{groups},128,256]" in scans[0]
    assert f"f32[2,{groups},128,256]" not in scans[0]


def _assert_one_scan_no_comb_copy(text, comb):
    """A compiled grow program's text: one ``lgbm_split_scan``, one
    conditional, and no copy of a ``comb``-shaped f32 array."""
    import re
    assert len(re.findall(r"%lgbm_split_scan(?:\.\d+)? = ", text)) == 1
    ops = re.findall(r"^\s*(?:ROOT )?%[\w.-]+ = (\S+) ([\w-]+)\(", text,
                     re.M)
    assert len([o for o in ops if o[1] == "conditional"]) == 1
    comb_sized = [o[1] for o in ops
                  if o[0].startswith(f"f32[{comb[0]},{comb[1]}]")]
    assert comb_sized and not [o for o in comb_sized if o.startswith("copy")]


# The whole grow programs, each through the v5e compiler once for all
# the tests that read it: (compiled text, temporary bytes, comb shape).
_GROW_PROGRAMS = {}
_PULL_PROGRAMS = {}


def _serial_grow_program(one_chip, n, f, stream, crossover=None,
                         scoped=True):
    """``make_grow_fn`` asks ``jax.default_backend()`` for its route;
    the builder answers for the described chip.  ``scoped=False``
    builds the program with the phases of ``obs/tracer.py`` taken out."""
    import contextlib
    import sys
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas import fused_split
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    key = (n, f, stream, crossover, scoped)
    if key in _GROW_PROGRAMS:
        return _GROW_PROGRAMS[key]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        if crossover is not None:
            patch.setattr(fused_split, "hook_crossover_rows",
                          lambda ngroups: crossover)
        if not scoped:
            patch.setattr(sys.modules["lightgbm_tpu.obs.tracer"],
                          "_phase_scope",
                          lambda name: contextlib.nullcontext())
        gp = make_grow_fn(
            SplitHyperParams(min_data_in_leaf=20), num_leaves=LEAVES,
            padded_bins=BINS, physical_bins=sds((n, f), jnp.uint8),
            **({"stream": {"kind": "binary", "sigmoid": 1.0, "count": n}}
               if stream else {}))
        assert gp.fused and (gp._root0_fn is not None) == stream
        # ISSUE 37: the scan of this program moves what the function
        # gives a comb of its width
        assert gp.scan_block_rows == _scan_rows((0, 0, gp._C, f)) == (
            2048 if gp._C == 128 else 1024)
        # ... and at one and two planes the comb histogram
        # is one tile of 2,048 rows, as before sixteen were built
        assert (gp.comb_planes, gp.hist_tiles, gp.hist_block_rows) == (
            gp._C // 128, 1, 2048)
        comb = comb_shape(gp._n_alloc, gp._C)
        rows = sds((1,) if stream else (n,), jnp.float32)
        args = [sds(comb, jnp.float32)] * 2 + [rows] * 3 + [
            sds((f,), jnp.float32), sds((f,), jnp.int32),
            sds((f,), jnp.bool_), sds((f,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32)]
        if stream:
            args.append(sds((f, BINS, 2), jnp.float32))
        compiled = gp._grow_p.lower(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args)).compile()
        assert gp.lazy_score == stream
        if stream:
            # ISSUE 39: the route's other program, which puts the
            # comb's scores in row order when the train score is read
            pull = gp._pull_score_fn.lower(jax.ShapeDtypeStruct(
                comb, jnp.float32, sharding=one_chip)).compile()
            _PULL_PROGRAMS[key] = (
                pull.as_text(), pull.memory_analysis().temp_size_in_bytes)
    out = _GROW_PROGRAMS[key] = (
        compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes,
        comb)
    return out


def _higgs_grow_program(one_chip, crossover=5000, scoped=True):
    """The ``higgs`` route at the ``higgs-train-10m`` shape (stream,
    fused, 255 leaves, 10.5M rows, one plane)."""
    return _serial_grow_program(one_chip, 10_500_096, F_PAD, True,
                                crossover, scoped)


def _msltr_grow_program(one_chip):
    """The non-stream route at the ``msltr-train-2m`` shape (fused, 255
    leaves, 2.27M rows, 144 columns over two planes, the XLA finder)."""
    return _serial_grow_program(one_chip, MSLTR[0], MSLTR[3], False)


@pytest.mark.parametrize("crossover", [0, 5000, None],
                         ids=["never_hook", "mid", "always_hook"])
def test_the_grow_program_compiles_with_one_scan_and_no_comb_copy(
        crossover, one_chip, no_compile_cache):
    """ISSUE 35: the WHOLE grow program of the ``higgs`` route at the
    ``higgs-train-10m`` shape (stream, fused, 255 leaves, 10.5M rows)
    through the v5e compiler, whatever the hook's crossover: one
    ``lgbm_split_scan`` (the kernel takes the third state, there is no
    second scan), the comb-direct ``lgbm_hist`` inside the one
    conditional, no comb-sized ``copy`` anywhere (the cond's branches
    only read the comb: the cell stands at 97% of the chip's memory)
    and temporaries far under one comb."""
    import re
    from lightgbm_tpu.ops.pallas import fused_split
    if crossover is None:
        crossover = fused_split.HOOK_ALWAYS
    text, temp_bytes, comb = _higgs_grow_program(one_chip, crossover)
    _assert_one_scan_no_comb_copy(text, comb)
    # the comb-direct histogram sits in a branch computation, not in
    # the loop body beside the scan
    hists = re.findall(r"%lgbm_hist(?:\.\d+)? = [^\n]*op_name=\"([^\"]*)\"",
                       text)
    assert len(hists) == 1 and re.search(
        r"/while/body/lgbm\.hist/cond/branch_1_fun/", hists[0])
    assert temp_bytes < comb[0] * comb[1] * 4 // 8


# ISSUE 38: the ops a capture's ``XLA Ops`` line can show, by the phase
# the program wrote them under (``obs/tracer.program_ops``, which is
# what a traced run's ``Program::ops`` event holds).  Under no phase
# are only ops the program did not write: what the compiler adds
# without metadata (copies and moves between memory spaces,
# broadcasts, the pieces it cuts a ``reduce-window`` into) and the
# cached lowering of ``jnp.cumsum`` (``op_name="reduce_window_sum"``).
# Counted, so that a scope lost in a later change shows here.
UNPHASED_AT_MOST = {"higgs": 50, "msltr": 120, "mesh": 130}
NEVER_UNPHASED = ("lgbm_", "while", "conditional", "sort", "scatter",
                  "all-reduce", "reduce-scatter", "all-gather",
                  "collective-permute", "pmin", "pmax", "psum")


def _phase_table(which, one_chip, topo):
    from lightgbm_tpu.obs.tracer import program_ops
    text = (_higgs_grow_program(one_chip)[0] if which == "higgs"
            else _msltr_grow_program(one_chip)[0] if which == "msltr"
            else _mesh_grow_program(topo)[0])
    return text, program_ops(text)


@pytest.mark.parametrize("which", ["higgs", "msltr", "mesh"])
def test_every_op_the_grow_program_wrote_has_a_phase(
        which, one_chip, topo, no_compile_cache):
    import re
    from lightgbm_tpu.obs.tracer import PHASES
    text, ops = _phase_table(which, one_chip, topo)
    # every instruction that carries the program's own op_name - at the
    # top level, in a loop body or a branch, inside a fusion - is under
    # a phase
    # (not the program's: ``cumsum``'s pieces, and what the partitioner
    # of the mesh program makes, named after the container alone or
    # after one instruction, ``.../shard_map/slice.395``: no path)
    written = [n for n in re.findall(
        r'op_name="jit\([^"/]*\)/(?:shard_map/)?([^"]+)"', text)
        if n != "reduce_window_sum" and (which != "mesh" or "/" in n)]
    assert len(written) > 500
    lost = [n for n in written if not re.match(
        r"(?:.*/)?lgbm\.(?:" + "|".join(PHASES) + r")(?:/|$)", n)]
    assert lost == []
    want = {"root", "hist", "find", "partition", "glue", "leafrows"}
    want |= {"refresh"} if which == "higgs" else set()
    want |= {"merge"} if which == "mesh" else set()
    assert set(ops) - {""} == want
    unphased = ops.get("", [])
    assert len(unphased) <= UNPHASED_AT_MOST[which], len(unphased)
    assert not [k for k in unphased if k.startswith(NEVER_UNPHASED)]
    # the kernels, by the phase they serve
    where = {k.split(".")[0].split(" ")[0]: ph for ph, keys in ops.items()
             for k in keys if k.startswith("lgbm_")}
    assert where["lgbm_split_scan"] == where["lgbm_copyback"] == "partition"
    if which == "higgs":
        assert where["lgbm_hist"] == "hist"
        assert where["lgbm_refresh"] == "refresh"
        assert where["lgbm_apply_find"] == "find"
    else:
        hists = sorted(ph for ph, keys in ops.items() for k in keys
                       if k.startswith("lgbm_hist"))
        assert hists == ["hist", "root"]
    if which == "mesh":
        crossing = {ph for ph, keys in ops.items() for k in keys
                    if k.startswith(("all-reduce", "reduce-scatter",
                                     "psum", "pmin", "pmax"))}
        assert crossing == {"merge"}


def _entry_outputs(text):
    """The result shapes of a compiled module's entry computation."""
    import re
    root = re.search(r"^\s*ROOT %[\w.-]+ = (.*?) [\w-]+\(",
                     text[text.index("\nENTRY "):], re.M).group(1)
    return re.sub(r"\{[^{}]*\}|/\*.*?\*/", "", root)


@pytest.mark.parametrize("which", ["higgs", "msltr", "mesh"])
def test_only_the_stream_program_stopped_undoing_the_permutation(
        which, one_chip, topo, no_compile_cache):
    """ISSUE 39: the stream grow program holds no sort, no scatter and
    no row-id decode under ``lgbm.leafrows`` - what is left there is
    the leaf of a position, whose shrunk output the refresh adds - and
    returns no ``[n]`` i32; the non-stream and mesh programs, whose
    gradients are made from ROW-order scores, undo the permutation as
    they did."""
    import re
    text, ops = _phase_table(which, one_chip, topo)
    n = {"higgs": 10_500_096, "msltr": MSLTR[0], "mesh": 5_251_072}[which]
    leafrows = [re.sub(r"[.\d]* .*", "", k) for k in ops["leafrows"]]
    lines = [l for l in text.splitlines() if "lgbm.leafrows" in l]
    unpermutes = [l for l in lines if re.search(
        r" (?:sort|scatter)\(|multiply_reduce_fusion", l)]
    if which == "higgs":
        assert not unpermutes
        assert not [k for k in leafrows if k in ("sort", "scatter")]
        assert f"s32[{n}]" not in _entry_outputs(text)
        assert len(ops["leafrows"]) <= 6
    else:
        assert unpermutes
        assert f"s32[{n}]" in _entry_outputs(text)


@pytest.mark.parametrize("which", ["higgs", "two_planes"])
def test_pull_score_compiles_for_v5e(which, one_chip, no_compile_cache):
    """ISSUE 39: the program a read of ``GBDT.train_score`` runs on the
    stream route, at the ``higgs-train-10m`` shape and at a comb of two
    planes (144 bin columns: the id bytes and the score terms ride the
    second): the comb goes in and is not donated, one ``[n]`` f32 comes
    out; every op it wrote is ``leafrows``'s, so a capture books it
    there and not under ``phase_unnamed_share``; one scatter, the sort
    XLA:TPU puts before it at 10.5M rows; no column slice (a ``[n, k]``
    array pads to 512 B a row); temporaries under a tenth of the comb."""
    import re
    from lightgbm_tpu.obs.tracer import program_ops
    n, f = (10_500_096, F_PAD) if which == "higgs" else (MSLTR[0], MSLTR[3])
    if which == "higgs":
        _higgs_grow_program(one_chip)
        comb = _GROW_PROGRAMS[(n, f, True, 5000, True)][2]
        grow_ops = program_ops(_GROW_PROGRAMS[(n, f, True, 5000, True)][0])
        text, temp_bytes = _PULL_PROGRAMS[(n, f, True, 5000, True)]
    else:
        _, _, comb = _serial_grow_program(one_chip, n, f, True)
        grow_ops = {}
        text, temp_bytes = _PULL_PROGRAMS[(n, f, True, None, True)]
    assert comb[1] == 128 and comb[0] % (1 + (which != "higgs")) == 0
    assert "input_output_alias" not in text.splitlines()[0]
    assert _entry_outputs(text) == f"f32[1,{n}]"
    ops = program_ops(text)
    assert set(ops) == {"leafrows", ""}
    # under no phase: the compiler's own moves between memory spaces
    assert all(k.startswith(("copy", "bitcast")) for k in ops[""])
    names = [re.sub(r"[.\d]* .*", "", k) for k in ops["leafrows"]]
    assert names.count("sort") <= 1 and "gather" not in names
    assert re.search(r" scatter\(", text)
    assert not re.search(rf"f32\[{comb[0]},\d+\]", text.replace(
        f"f32[{comb[0]},{comb[1]}]", ""))
    assert temp_bytes < comb[0] * comb[1] * 4 // 10
    # a capture names an op by instruction and shape: none of this
    # program's is an op of the grow program under another phase
    mine = {k: ph for ph, keys in ops.items() for k in keys}
    theirs = {k: ph for ph, keys in grow_ops.items() for k in keys}
    assert not {k for k in mine if theirs.get(k, mine[k]) != mine[k]}


def _without_names(text):
    """A compiled module's text without what a scope may touch: each
    instruction's ``metadata``, the stack-frame tables under the
    header, and the locations inside a Mosaic kernel's payload (the
    bytecode ``body``: a scope above a ``pallas_call`` is in the
    locations of the kernel's ops, PR 27)."""
    import re
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"[^"]*"', '"body":""', text)
    lines = text.splitlines()
    first = next(i for i, l in enumerate(lines)
                 if re.match(r"(ENTRY )?%", l))
    return [lines[0]] + lines[first:]


def test_the_scopes_add_names_and_nothing_else(one_chip, no_compile_cache):
    """ISSUE 38 (c): the ``higgs`` grow program compiled with the
    phases taken out (the helper's scope patched to a null context) is
    the same module: same instructions, same names and numbers, same
    schedule."""
    scoped = _higgs_grow_program(one_chip)
    bare = _higgs_grow_program(one_chip, scoped=False)
    assert "lgbm.partition" in scoped[0] and "lgbm." not in bare[0]
    assert _without_names(scoped[0]) == _without_names(bare[0])
    assert scoped[1:] == bare[1:]


def _mesh_grow_program(topo):
    """The data-parallel grow program of ``higgs-data4-train-21m``
    (5.25M rows a shard, four shards): (compiled text, comb lines and
    lanes a shard)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    from lightgbm_tpu.parallel.data_parallel import (DATA_AXIS,
                                                     DataParallelGrower)
    if "mesh" in _GROW_PROGRAMS:
        return _GROW_PROGRAMS["mesh"]
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    # 21,000,000 rows pad to whole 2,048-row blocks a shard
    shards, n_loc, f = len(topo.devices), 5_251_072, F_PAD
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        grower = DataParallelGrower(
            SplitHyperParams(min_data_in_leaf=20), num_leaves=LEAVES,
            padded_bins=BINS, mesh=mesh,
            physical_bins=sds((shards * n_loc, f), jnp.uint8))
        assert grower.fused and grower.hist_scatter
        # ISSUE 37: every shard's scan moves the block the function
        # gives this width, and a shard's comb stays inside what the
        # four-chip cell's ``correct`` allows: its 5,250,000 rows +
        # 8,192 lines
        assert grower.scan_block_rows == _scan_rows(
            (0, 0, grower._pieces.C, f)) == 2048
        assert grower._pieces.n_alloc <= 5_250_000 + 8_192
        lines, lanes = comb_shape(grower._pieces.n_alloc, grower._pieces.C)

        def arg(shape, dtype, *spec):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

        comb = arg((shards * lines, lanes), jnp.float32, DATA_AXIS, None)
        row = arg((shards * n_loc,), jnp.float32, DATA_AXIS)
        text = grower._sharded_core.lower(
            comb, comb, row, row, row, arg((f,), jnp.float32),
            arg((f,), jnp.int32), arg((f,), jnp.bool_),
            arg((f,), jnp.bool_), arg((), jnp.int32),
            arg((), jnp.float32)).compile().as_text()
    out = _GROW_PROGRAMS["mesh"] = (text, (lines, lanes))
    return out


def test_the_mesh_grow_program_adds_no_collective(topo, no_compile_cache):
    """ISSUE 35 on the mesh: the data-parallel grow program of
    ``higgs-data4-train-21m`` (5.25M rows a shard, four shards) through
    the v5e compiler.  Whether the hook runs is decided from the leaf
    record's replicated count, so the split still pays the collectives
    it paid before - per split one reduce-scatter of the histogram, ONE
    all-reduce for both row counts (a psum ahead of the scan could not
    share it), the election's pmin / pmax; the root pays its own - and
    none sits inside the conditional; one scan, no comb-sized copy."""
    import re
    text, comb = _mesh_grow_program(topo)
    _assert_one_scan_no_comb_copy(text, comb)
    # root + split: the parent commit's program reads the same counts
    assert text.count(" reduce-scatter(") == 2
    assert text.count(" all-reduce(") == 10
    branches = re.findall(r"branch_computations=\{([^}]*)\}", text)
    assert len(branches) == 1
    for name in branches[0].replace("%", "").split(", "):
        body = text[text.index(f"\n%{name} "):]
        body = body[:body.index("\n}\n")]
        assert "all-reduce" not in body and "reduce-scatter" not in body


def _hand_off(stream: bool, n: int):
    """The finalisation of ``ops/grow.py`` after the last split, at a
    cell's row count: off the stream route leaf ids by position from
    the segment table and the un-permute to row order by the comb's
    row ids; on it the shrunk leaf outputs by position, and no more."""
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.leaf_lookup import leaf_of_position

    def fn(seg, lv_leaf, ridx):
        leaf_of_pos, *lv_row = leaf_of_position(
            seg, n, (lv_leaf,) if stream else ())
        if stream:          # ISSUE 39: nothing is put in row order
            return tuple(lv_row)
        leaf_id = jnp.zeros((n,), jnp.int32).at[ridx].set(
            leaf_of_pos, mode="drop")
        return (leaf_id,)

    return fn, (sds((LEAVES, 2), jnp.int32), sds((LEAVES,), jnp.float32),
                sds((n,), jnp.int32))


@pytest.mark.parametrize("stream,n", [
    (True, 10_500_096), (True, HIGGS[0]), (False, MSLTR[0])],
    ids=["higgs_10m_stream", "higgs_1m_stream", "msltr_physical"])
def test_hand_off_compiles_to_compares_not_gathers(stream, n, one_chip,
                                                   no_compile_cache):
    """ISSUE 33: at 255 leaves the v5e program of the hand-off holds no
    gather (8 ns an element on this chip whatever the table's size);
    the select-sum is a reduce inside a fusion, its [L, n] operand never
    an array; the one sort is the scatter's (XLA:TPU sorts the (row id,
    leaf) pairs at 10.5M rows and not at 2.27M), so since ISSUE 39 the
    stream route has none; and the temporaries
    stay under three n-sized vectors, what the repeat + take form held
    (``higgs-train-10m`` runs 0.46e9 under the chip's memory)."""
    import re
    compiled = _compile(functools.partial(_hand_off, stream, n), one_chip)
    text = compiled.as_text()
    ops = re.findall(r"^\s*(?:ROOT )?%[\w.-]+ = (\S+) ([\w-]+)\(", text,
                     re.M)
    assert ops and not [o for o in ops if o[1] == "gather"]
    assert len([o for o in ops if o[1] == "sort"]) <= (0 if stream else 1)
    assert stream == (not [o for o in ops if o[1] == "scatter"]
                      and " scatter(" not in text)
    wide = [o for o in ops if o[0].startswith((f"s32[{LEAVES},{n}]",
                                               f"pred[{LEAVES},{n}]"))]
    assert wide and " reduce(" in text
    entry = text[text.index("\nENTRY "):]
    assert f"[{LEAVES},{n}]" not in entry
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 4 * n


def _expo_bundle(f_log_pad=704):
    """The bundle map of ``expo-onehot``'s shape: six one-hot fields of
    12 / 31 / 7 / 22 / 313 / 313 two-bin columns in ten bundle columns
    of at most 255 bins, and the two numeric columns unbundled."""
    import numpy as np
    phys = np.zeros(f_log_pad, np.int32)
    off = np.zeros(f_log_pad, np.int32)
    nb = np.zeros(f_log_pad, np.int32)
    bundled = np.zeros(f_log_pad, bool)
    j, col = 0, 2
    for levels in (12, 31, 7, 22, 313, 313):
        o = 1
        for _ in range(levels):
            if o + 2 > 255:
                col, o = col + 1, 1
            phys[j], off[j], nb[j], bundled[j] = col, o, 2, True
            o += 2
            j += 1
        col += 1
    phys[698:700], nb[698:700] = (0, 1), 255
    assert col == 12
    return {"feat_phys": phys, "feat_offset": off, "is_bundled": bundled,
            "feat_default": np.zeros(f_log_pad, np.int32),
            "num_bins_log": nb, "has_nan": np.zeros(f_log_pad, bool),
            "is_cat": np.zeros(f_log_pad, bool)}


def test_the_bundled_comb_grow_program_compiles_at_the_expo_shape(
        one_chip, no_compile_cache, monkeypatch):
    """ISSUE 36: the WHOLE grow program of ``expo-train-10m`` (stream,
    fused, 255 leaves, 10M rows, 700 logical columns in 12 bundle
    columns of one comb plane) through the v5e compiler.  The comb is
    Higgs's: 512 B a line, one ``lgbm_split_scan`` - told its split by
    the 8 + 8-word descriptor, the membership set riding it - the
    comb-direct ``lgbm_hist`` in the one conditional, no comb-sized
    copy; the finder is the XLA tail in bundle space (no
    ``lgbm_apply_find``, no ``[704, 256]`` logical histogram anywhere),
    and the pool is ``[255, 16, 4, 256]``."""
    import re
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    n, f, f_log = 10_000_384, 16, 704
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    gp = make_grow_fn(
        SplitHyperParams(min_data_in_leaf=20), num_leaves=LEAVES,
        padded_bins=BINS, padded_bins_log=BINS, bundle=_expo_bundle(f_log),
        physical_bins=sds((n, f), jnp.uint8),
        stream={"kind": "binary", "sigmoid": 1.0, "count": n})
    assert gp.fused and gp._f_pad == f and gp._C == 128
    assert gp._ingest is None           # nothing unbundles
    assert gp.scan_block_rows == 2048   # Higgs's comb, Higgs's block
    comb = comb_shape(gp._n_alloc, gp._C)
    args = [sds(comb, jnp.float32)] * 2 + [sds((1,), jnp.float32)] * 3 + [
        sds((f_log,), jnp.float32), sds((f_log,), jnp.int32),
        sds((f_log,), jnp.bool_), sds((f_log,), jnp.bool_),
        sds((), jnp.int32), sds((), jnp.float32),
        sds((f, BINS, 2), jnp.float32)]
    compiled = gp._grow_p.lower(*(
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
        for a in args)).compile()
    text = compiled.as_text()
    _assert_one_scan_no_comb_copy(text, comb)
    scan = re.search(r"%lgbm_split_scan(?:\.\d+)? = [^\n]*", text).group(0)
    assert "s32[16]{0}" in scan         # sel + 8 membership words
    assert "lgbm_apply_find" not in text
    assert f"f32[{LEAVES},{f},4,{BINS}]" in text
    assert not re.search(rf"f32\[(?:\d+,)*{f_log},{BINS}[,\]]", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < comb[0] * comb[1] * 4 // 8


def test_the_finder_at_the_msltr_width_is_the_xla_tail():
    """144 columns x 256 bins is past the Pallas finder's scoped-VMEM
    budget (apply_find.tail_supported), so that route's split finder is
    the XLA tail and ``lgbm_apply_find`` is not in its program; so is
    Epsilon's 2,000 x 64 (and 64 bins is under one 128-lane tile).
    Flip this pin in the PR that tiles the finder over features."""
    from lightgbm_tpu.ops.pallas.apply_find import tail_supported
    assert tail_supported(F_PAD, BINS)
    assert not tail_supported(MSLTR[3], BINS)
    assert not tail_supported(EPSILON[3], 64)


def test_the_grow_program_compiles_at_the_epsilon_shape(
        one_chip, no_compile_cache, record_property):
    """The WHOLE grow program of ``epsilon-train-400k``
    (401,408 x 2,000, 64 bins, stream binary, 255 leaves; a comb of
    sixteen planes, 8 KiB a line) through the v5e compiler, in seconds
    (the parent's did not finish in 900).  Unfused; the scan takes the
    block ``scan_block_rows`` gives 2,048 lanes (128 rows) and the
    copy-back 512; both comb histograms sweep sixteen one-plane tiles
    of 2,048 rows (a [16 x 8, 128, 256] accumulator, 8 groups of 16
    columns a tile, a bin split 8 x 8: whole MXU passes); the finder
    is the XLA tail (no ``lgbm_apply_find``) over the ``[255, 2000, 4, 64]`` pool; and
    the footprint model's comb, scratch and pool are the compiled
    program's."""
    import re
    import time
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import sds
    from lightgbm_tpu.obs import costmodel
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    n, _, c, f = EPSILON
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        gp = make_grow_fn(
            SplitHyperParams(min_data_in_leaf=1,
                             min_sum_hessian_in_leaf=100.0),
            num_leaves=LEAVES, padded_bins=64,
            physical_bins=sds((n, f), jnp.uint8),
            stream={"kind": "binary", "sigmoid": 1.0, "count": n})
        assert (gp._C, gp.comb_planes, gp.fused) == (c, 16, False)
        assert gp.scan_block_rows == _scan_rows(EPSILON) == 128
        assert (gp.hist_tiles, gp.hist_block_rows) == (16, 2048)
        comb = comb_shape(gp._n_alloc, gp._C)
        args = [sds(comb, jnp.float32)] * 2 + [sds((1,), jnp.float32)] * 3 + [
            sds((f,), jnp.float32), sds((f,), jnp.int32),
            sds((f,), jnp.bool_), sds((f,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32)]
        t0 = time.perf_counter()
        compiled = gp._grow_p.lower(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in args)).compile()
        compile_s = time.perf_counter() - t0
    record_property("compile_s", compile_s)
    print(f"epsilon grow program: lowered and compiled in {compile_s:.1f} s")
    assert compile_s < 120.0
    text = compiled.as_text()
    # the unfused scan, under a name of its own
    assert len(re.findall(r"%lgbm_partition_scan(?:\.\d+)? = ", text)) == 1
    assert "lgbm_split_scan" not in text
    assert len(re.findall(r"%lgbm_copyback(?:\.\d+)? = ", text)) == 1
    assert "lgbm_apply_find" not in text
    assert f"f32[{LEAVES},{f},4,64]" in text
    hists = re.findall(r"%lgbm_hist(?:\.\d+)? = (f32\[[\d,]+\])", text)
    assert hists == ["f32[128,128,256]"] * 2, hists
    fp = costmodel.grow_footprint(rows=400_000, f_pad=f, padded_bins=64,
                                  num_leaves=LEAVES, stream=True,
                                  fused=False)
    assert (fp["geometry"]["n_alloc"], fp["geometry"]["C"]) == (
        gp._n_alloc, c)
    buf = fp["buffers"]
    assert buf["comb"]["bytes"] == comb[0] * comb[1] * 4 == 3_338_665_984
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= buf["comb"]["bytes"] + buf[
        "scratch"]["bytes"]
    assert buf["hist_pool"]["bytes"] <= mem.temp_size_in_bytes < comb[
        0] * comb[1] * 4 // 4


def _score_tail_program(one_chip, valid_sets):
    """The score tail ``epsilon-valid-train-400k`` dispatches after every
    tree (255 leaves, 2,000 columns at 64 bins; on the stream route no
    train score), with that many ``[100,000, 2,000]`` u8 valid sets:
    (compiled text, its memory analysis)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.models.gbdt import make_score_tail
    from lightgbm_tpu.ops.grow import TreeArrays
    f, n, ni = EPSILON[3], 100_000, LEAVES - 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    ta = TreeArrays(s((ni,), i32), s((ni,), i32), s((ni,), f32),
                    s((ni,), b), s((ni,), b), s((ni,), i32), s((ni,), i32),
                    s((ni,), f32), s((ni,), f32), s((ni,), f32),
                    s((LEAVES,), f32), s((LEAVES,), f32), s((LEAVES,), f32),
                    s((), i32), s((1, 1), f32), s((4,), i32))
    tail = make_score_tail(np.full(f, 64, np.int32), np.zeros(f, bool))
    compiled = tail.lower(
        ta, None, None, (s((n, f), jnp.uint8),) * valid_sets,
        (s((n,), f32),) * valid_sets, s((), f32), s((), f32)).compile()
    return compiled.as_text(), compiled.memory_analysis()


def test_the_score_tail_compiles_with_an_epsilon_valid_set(
        one_chip, no_compile_cache):
    """The valid replay at ``epsilon-valid-train-400k``'s shape gets
    through the v5e compiler as the decision-matrix replay: under
    ``lgbm.valid`` one ``while`` over the u8 bins moves a block of B
    rows a trip (n // B trips, not one a node), its node-column and
    path matmuls are there at [B, 254] and [B, 255], no gather reads a
    row, nothing copies the matrix and the temporaries stay under 64
    MiB; without a valid set the tail has no op of the phase and the
    scope's name is not in its text."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.obs.tracer import program_ops
    from lightgbm_tpu.ops.predict import replay_block_rows
    bare, _ = _score_tail_program(one_chip, 0)
    assert "lgbm.valid" not in bare and "valid" not in program_ops(bare)
    text, mem = _score_tail_program(one_chip, 1)
    ops = program_ops(text)
    block = replay_block_rows(
        jax.ShapeDtypeStruct((100_000, 2_000), jnp.uint8), LEAVES - 1)
    assert 0 < block < 100_000
    loops = [k for k in ops["valid"] if k.startswith("while")
             and "u8[100000,2000]" in k]
    assert len(loops) == 1
    assert f"s32[{block}]" in " ".join(ops["valid"])  # a block's leaves
    dots = {m for line in text.splitlines() if "lgbm.valid" in line
            for m in re.findall(r"= (f32\[\d+,\d+\])\S* convolution\(",
                                line)}
    assert {f"f32[{block},254]", f"f32[{block},255]"} <= dots
    gathered = [int(np.prod([int(d) for d in dims.split(",")]))
                for dims in re.findall(r"= \w+\[([\d,]+)\]\S* gather\(", text)]
    assert max(gathered, default=0) <= LEAVES     # node tables only
    assert not [k for ph, keys in ops.items() if ph != "valid"
                for k in keys if k.startswith(("while", "gather"))]
    assert not re.search(r"\w+\[100000,2000\]", text.replace(
        "u8[100000,2000]", ""))
    assert mem.temp_size_in_bytes <= 64 << 20


# Off the default path, refused by the v5e compiler on jax 0.9.0 /
# libtpu 0.0.34 (PR 22).  serve_traverse: ``sf[gidx]`` gathers a flat
# VMEM vector by a [BR, T] index array ("Only 2D gather is supported").
# Opt-in only: LGBM_TPU_SERVE_KERNEL=1.  Flip the pin in the PR that
# fixes the kernel.
@pytest.mark.parametrize("name", ["serve_traverse"])
def test_known_refusals_stay_pinned(name, one_chip, no_compile_cache):
    with pytest.raises(Exception, match="Only 2D gather"):
        _compile(functools.partial(_registered, name), one_chip)
