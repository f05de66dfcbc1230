"""ISSUE 38: every device op of an iteration is traced under one phase
of the algorithm (``lgbm.<phase>``, ``obs/tracer.py`` PHASES), and a
capture's span file says what its instruction names mean
(``Program::ops``).  Coverage is held at the source, on the jaxprs of
the routes the chip takes - traced for it here, where nothing runs -;
``tests/test_chip_compile.py`` holds it in the compiled text."""
import contextlib
import importlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import tracer

# ``lightgbm_tpu.obs.tracer`` the attribute is the Tracer instance
tracer_mod = importlib.import_module("lightgbm_tpu.obs.tracer")
PHASES, PREFIX = tracer_mod.PHASES, tracer_mod.PHASE_PREFIX
phase, phased, next_phase = (tracer_mod.phase, tracer_mod.phased,
                             tracer_mod.next_phase)
# a program's containers: the phases are inside them
CONTAINERS = ("jit", "pjit", "shard_map")
COLLECTIVES = ("psum", "psum2", "psum_invariant", "pmax", "pmin",
               "reduce_scatter", "psum_scatter", "all_gather")


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.disable()
    tracer.reset()
    yield
    tracer.annotate(False)
    tracer.disable()
    tracer.reset()


def own_phases(eqn):
    return [p[len(PREFIX):] for p in str(eqn.source_info.name_stack).split("/")
            if p.startswith(PREFIX)]


def sub_jaxprs(eqn):
    for key, value in eqn.params.items():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                yield key, v


def walk(jaxpr, inherited=None, strict=True, out=None):
    """[(eqn, phase)] of a program: an equation is under the one phase
    of its own name stack, else under its enclosing equation's (a
    branch of a ``cond``, a library's nested ``jit``).  ``strict``: the
    program's own level and the split loop's body, where every
    equation must carry its phase itself."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name, own = eqn.primitive.name, own_phases(eqn)
        assert len(own) <= 1, (name, own)
        if name in CONTAINERS and inherited is None and not own:
            for _, sub in sub_jaxprs(eqn):
                walk(sub, None, True, out)
            continue
        assert own or not strict, f"{name} under no phase of its own"
        ph = own[0] if own else inherited
        assert ph in PHASES, (name, ph)
        out.append((eqn, ph))
        if name == "pallas_call":
            continue                    # a kernel is one op
        for key, sub in sub_jaxprs(eqn):
            walk(sub, ph, strict and name == "while"
                 and key == "body_jaxpr", out)
    return out


def kernels(walked):
    got = {}
    for eqn, ph in walked:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            got.setdefault(name, set()).add(ph)
    return got


N, F, B, L = 8192, 32, 256, 15


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _meta(f):
    return [_sds((f,), jnp.float32), _sds((f,), jnp.int32),
            _sds((f,), jnp.bool_), _sds((f,), jnp.bool_),
            _sds((), jnp.int32), _sds((), jnp.float32)]


def _serial(stream):
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    gp = make_grow_fn(
        SplitHyperParams(min_data_in_leaf=20), num_leaves=L, padded_bins=B,
        physical_bins=_sds((N, F), jnp.uint8),
        **({"stream": {"kind": "binary", "sigmoid": 1.0, "count": N}}
           if stream else {}))
    comb = _sds(comb_shape(gp._n_alloc, gp._C), jnp.float32)
    rows = _sds((1,) if stream else (N,), jnp.float32)
    args = [comb, comb, rows, rows, rows] + _meta(F)
    if stream:
        args.append(_sds((F, B, 2), jnp.float32))
    return gp._grow_p, args


def _mesh():
    from jax.sharding import Mesh
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    from lightgbm_tpu.parallel.data_parallel import (DATA_AXIS,
                                                     DataParallelGrower)
    shards = 4
    grower = DataParallelGrower(
        SplitHyperParams(min_data_in_leaf=20), num_leaves=L, padded_bins=B,
        mesh=Mesh(np.array(jax.devices()[:shards]), (DATA_AXIS,)),
        physical_bins=_sds((shards * N, F), jnp.uint8))
    lines, lanes = comb_shape(grower._pieces.n_alloc, grower._pieces.C)
    comb = _sds((shards * lines, lanes), jnp.float32)
    rows = _sds((shards * N,), jnp.float32)
    return grower._sharded_core, [comb, comb, rows, rows, rows] + _meta(F)


ROUTES = {"stream": lambda: _serial(True),
          "non_stream": lambda: _serial(False), "shard_map": _mesh}
# kernel -> phase; the one ``lgbm_hist`` of the stream route is the
# child's, off it the root histogram is a second one
KERNELS = {
    "stream": {"lgbm_split_scan": {"partition"},
               "lgbm_copyback": {"partition"}, "lgbm_hist": {"hist"},
               "lgbm_apply_find": {"find"}, "lgbm_refresh": {"refresh"}},
    "non_stream": {"lgbm_split_scan": {"partition"},
                   "lgbm_copyback": {"partition"},
                   "lgbm_hist": {"hist", "root"},
                   "lgbm_apply_find": {"find"}},
    "shard_map": {"lgbm_split_scan": {"partition"},
                  "lgbm_copyback": {"partition"},
                  "lgbm_hist": {"hist", "root"}},
}


@pytest.fixture
def as_the_chip(monkeypatch):
    """``make_grow_fn`` asks ``jax.default_backend()`` for its route;
    answer for the chip.  Tracing a kernel needs no device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_every_equation_of_the_grow_program_is_under_one_phase(
        route, as_the_chip):
    fn, args = ROUTES[route]()
    walked = walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert len(walked) > 500
    assert kernels(walked) == KERNELS[route]
    seen = {ph for _, ph in walked}
    want = {"root", "hist", "find", "partition", "glue", "leafrows"}
    want |= {"refresh"} if route == "stream" else set()
    want |= {"merge"} if route == "shard_map" else set()
    assert seen == want
    # whatever crosses the mesh is ``merge``'s, the root's own too
    crossing = {ph for eqn, ph in walked
                if eqn.primitive.name in COLLECTIVES}
    assert crossing == ({"merge"} if route == "shard_map" else set())
    # the loop is glue's, and so is what it does under no seam
    loops = [ph for eqn, ph in walked if eqn.primitive.name == "while"
             and own_phases(eqn)]
    assert loops == ["glue"]
    # ISSUE 39: the stream program does not undo the permutation (no
    # row-id decode, no scatter to row order, no [n] i32 result); the
    # routes whose gradients read ROW-order scores do, as before
    unpermute = {eqn.primitive.name for eqn, ph in walked
                 if ph == "leafrows" and eqn.primitive.name in (
                     "dot_general", "scatter", "sort")}
    row_ids = [v for v in jax.eval_shape(fn, *args)[1:2]
               if v is not None]
    if route == "stream":
        assert not unpermute and not row_ids
    else:
        assert unpermute == {"dot_general", "scatter"}
        assert row_ids[0].dtype == jnp.int32 and row_ids[0].ndim == 1


def test_pull_score_is_leafrows_and_reads_the_comb_alone(as_the_chip):
    """ISSUE 39: the program behind a read of ``GBDT.train_score`` on
    the stream route: one argument (the comb, not donated), every
    equation under ``leafrows``, one scatter."""
    from lightgbm_tpu.ops.grow import make_grow_fn
    from lightgbm_tpu.ops.pallas.layout import comb_shape
    from lightgbm_tpu.ops.split import SplitHyperParams
    gp = make_grow_fn(
        SplitHyperParams(min_data_in_leaf=20), num_leaves=L, padded_bins=B,
        physical_bins=_sds((N, F), jnp.uint8),
        stream={"kind": "binary", "sigmoid": 1.0, "count": N})
    assert gp.lazy_score
    comb = _sds(comb_shape(gp._n_alloc, gp._C), jnp.float32)
    traced = jax.make_jaxpr(gp._pull_score_fn)(comb)
    (pjit,) = traced.jaxpr.eqns
    assert not any(pjit.params["donated_invars"])
    walked = walk(traced.jaxpr)
    assert {ph for _, ph in walked} == {"leafrows"}
    names = [eqn.primitive.name for eqn, _ in walked]
    assert names.count("scatter") == 1 and names.count("dot_general") == 4
    assert [v.aval.shape for v in traced.jaxpr.outvars] == [(1, N)]
    # off the stream route, and on the paged comb, there is none
    for kw in ({}, {"stream": {"kind": "binary", "sigmoid": 1.0,
                               "count": N},
                    "paged": {"rows_per_page": 2048}}):
        other = make_grow_fn(
            SplitHyperParams(min_data_in_leaf=20), num_leaves=L,
            padded_bins=B, physical_bins=_sds((N, F), jnp.uint8), **kw)
        assert not other.lazy_score and other._pull_score_fn is None


@pytest.mark.parametrize("entry", ["grow_serial", "grow_physical",
                                   "grow_stream", "grow_physical_efb"])
def test_the_off_chip_routes_are_covered_too(entry):
    """The fixtures of ``tests/test_obs.py``: the row-order route and
    the physical routes' XLA emulation (bucket switch, ``hist_merge``),
    which the tier-1 suite runs."""
    from lightgbm_tpu.analysis import registry
    fn, args = registry.collect()[entry].builder()
    walked = walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert {"root", "hist", "find", "partition", "glue",
            "leafrows"} <= {ph for _, ph in walked}


def _booster(objective="binary", **extra):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(600, 5)).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.normal(size=600) > 0).astype(np.float32)
    ds = lgb.Dataset(x, label=y, params={"max_bin": 31}, **extra)
    return lgb.train({"objective": objective, "num_leaves": 5,
                      "verbosity": -1, "max_bin": 31}, ds,
                     num_boost_round=2)


def test_the_score_tail_and_the_gradients_have_their_phases():
    inner = _booster()._inner
    tail = inner._async_tail_fn()
    ta = inner._pending[-1][1]      # the last tree, not flushed yet
    walked = walk(jax.make_jaxpr(tail)(
        ta, jnp.zeros((inner._n_rows_host,), jnp.int32),
        inner.train_score[0], (), (), jnp.float32(0.1),
        jnp.float32(0.0)).jaxpr)
    assert walked and {ph for _, ph in walked} == {"score"}
    grad = inner._grad_fn
    walked = walk(jax.make_jaxpr(grad.func)(
        *grad.args, inner.train_score).jaxpr)
    assert walked and {ph for _, ph in walked} == {"gradients"}


# ---- the helpers ------------------------------------------------------
def _stacks(fn, *args):
    return [(e.primitive.name, str(e.source_info.name_stack))
            for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns]


def test_seams_cut_a_function_without_nesting():
    @phased
    def inner(x):               # called in line: moves the caller's
        next_phase("hist")      # cursor and hands it back
        return x * 3.0

    @phase("merge")             # a decorator too
    def crossing(x):
        return x - 1.0

    @phased
    def f(x):
        next_phase("root")
        x = x + 1.0
        next_phase("partition")
        x = inner(x) + 2.0
        with phase("find"):     # sets ``partition`` aside, not under it
            x = crossing(jnp.sin(x))
        return jnp.cos(x)

    assert _stacks(f, jnp.ones(3)) == [
        ("add", "lgbm.root"), ("mul", "lgbm.hist"),
        ("add", "lgbm.partition"), ("sin", "lgbm.find"),
        ("sub", "lgbm.merge"), ("cos", "lgbm.partition")]
    # and nothing is left open behind it
    assert _stacks(lambda x: x + 1.0, jnp.ones(3)) == [("add", "")]


def test_a_nested_jaxpr_has_its_own_cursor():
    @phased
    def body(c):
        next_phase("partition")
        c = c + 1.0
        next_phase("hist")
        return c * 2.0

    def bare(c):                # no cursor at this level: a plain scope
        with phase("find"):
            return c - 1.0

    @phased
    def f(x):
        next_phase("glue")
        x = jax.lax.while_loop(lambda c: c.sum() < 9.0, body, x)
        x = jax.lax.cond(x[0] > 0, bare, bare, x)
        return x + 5.0

    jaxpr = jax.make_jaxpr(f)(jnp.ones(3)).jaxpr
    assert [(e.primitive.name, ph) for e, ph in walk(jaxpr)
            if e.primitive.name in ("while", "add", "mul", "sub",
                                    "cond")] == [
        ("while", "glue"), ("add", "partition"), ("mul", "hist"),
        ("cond", "glue"), ("sub", "find"), ("sub", "find"),
        ("add", "glue")]
    with pytest.raises(ValueError, match="no phase"):
        jax.make_jaxpr(lambda x: phase("split").__enter__())(1.0)
    with pytest.raises(RuntimeError, match="outside a phased"):
        jax.make_jaxpr(lambda x: next_phase("glue"))(1.0)


def test_an_error_leaves_no_phase_open():
    @phased
    def f(x):
        next_phase("root")
        raise KeyError("boom")

    with pytest.raises(KeyError):
        jax.make_jaxpr(f)(1.0)
    assert _stacks(lambda x: x + 1.0, jnp.ones(3)) == [("add", "")]


# ---- Program::ops -------------------------------------------------------
HLO = '''HloModule jit_f, is_scheduled=true

FileNames
1 "x.py"

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inside.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(f)/lgbm.root/add"}
}

%branch_a (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %lgbm_hist.3 = f32[8]{0:T(128)} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/lgbm.glue/while/body/lgbm.hist/cond/branch_1_fun/pallas_call"}
}

%branch_b (p.2: f32[8]) -> f32[8] {
  ROOT %p.2 = f32[8]{0} parameter(0)
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.9 = f32[8]{0:T(128)S(1)} fusion(%gte), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/lgbm.glue/while/body/lgbm.partition/add" stack_frame_id=4}
  %copy.3 = f32[8]{0:T(128)} copy(%fusion.9)
  %conditional.1 = f32[8]{0} conditional(%c, %copy.3, %copy.3), branch_computations={%branch_a, %branch_b}, metadata={op_name="jit(f)/lgbm.glue/while/body/lgbm.hist/cond"}
  %rw = f32[8]{0} reduce-window(%conditional.1), to_apply=%fused_computation.1, metadata={op_name="reduce_window_sum"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%i, %rw)
}

%cond (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%a, %b), direction=LT, metadata={op_name="jit(f)/lgbm.glue/while/cond/lt"}
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.11 = (s32[]{:T(128)}, f32[8]{0:T(128)}, /*index=2*/f32[8]{0}) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(f)/lgbm.glue/while"}
  ROOT %sort = (s32[8]{0}, s32[8]{0}) sort(%a, %b), dimensions={0}, to_apply=%fused_computation.1, metadata={op_name="jit(f)/lgbm.leafrows/scatter"}
}
'''


def test_program_ops_reads_the_phase_of_every_op_a_capture_can_show():
    assert tracer_mod.program_ops(HLO) == {
        "glue": ["while.11 (s32[], f32[8], f32[8])", "lt.1 pred[]"],
        "leafrows": ["sort (s32[8], s32[8])"],
        # the innermost phase is the op's; a fusion's insides are not ops
        "partition": ["fusion.9 f32[8]"],
        "hist": ["conditional.1 f32[8]", "lgbm_hist.3 f32[8]"],
        # made by the compiler, or by a library's own lowering
        "": ["copy.3 f32[8]", "rw f32[8]"]}


def test_the_benchmarks_reducer_reads_an_event_as_the_table_names_it():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                        "reducers", "device_phase_self_per.py")
    spec = importlib.util.spec_from_file_location("_phase_reducer", path)
    reducer = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reducer)
    shape = ("(s32[]{:T(128)}, f32[255,32,4,256]{3,2,1,0:T(8,128)}, "
             "/*index=2*/f32[10505216,128]{1,0:T(8,128)S(1)})")
    event = f"%while.27 = {shape} while({shape} %tuple.9), condition=%c"
    assert reducer.op_key(event) == tracer_mod.op_key("%while.27", shape) \
        == "while.27 (s32[], f32[255,32,4,256], f32[10505216,128])"


class _Capture:
    """A callback that opens a capture after ``at`` iterations and
    closes it one later, as a benchmark's does (no profiler is needed
    for what the tracer writes)."""

    def __init__(self, at):
        self.at = at

    def __call__(self, env):
        if env.iteration + 1 == self.at:
            tracer.annotate(True)
        elif env.iteration + 1 == self.at + 1:
            tracer.annotate(False)


def _tables():
    return [e for e in tracer.events if e["name"] == "Program::ops"]


def test_program_ops_is_written_once_a_capture_and_inside_it(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_PHYS", "interpret")
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2048, 6)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 6, "verbosity": -1,
              "max_bin": 31}
    # off: nothing is kept and nothing is written, capture or not
    lgb.train(params, lgb.Dataset(x, label=y, params={"max_bin": 31}),
              num_boost_round=3, callbacks=[_Capture(1)])
    assert tracer.events == [] and tracer._programs == {}
    tracer.annotate(False)

    tracer.enable(None)
    lgb.train(params, lgb.Dataset(x, label=y, params={"max_bin": 31}),
              num_boost_round=6, callbacks=[_Capture(2), _Capture(4)])
    tables = _tables()
    # two captures, each with one table a program of the iteration
    programs = sorted(e["args"]["program"] for e in tables)
    assert programs and len(programs) == 2 * len(set(programs))
    assert {"grow", "score"} <= set(programs)
    for e in tables:
        assert e["dur"] == 0.0 and e["args"]["parent"] == "Callbacks"
        ops = e["args"]["ops"]
        assert set(ops) <= set(PHASES) | {""}
        assert sum(map(len, ops.values())) > 0
    grow = [e for e in tables if e["args"]["program"] == "grow"]
    assert grow[0]["args"]["ops"] == grow[1]["args"]["ops"]
    assert {"root", "partition", "hist", "glue"} <= set(
        grow[0]["args"]["ops"])
    # the tables were made once, in the first iteration the tracer saw,
    # and nothing was lowered or compiled for them: the dispatch's own
    # trace and executable were found in JAX's caches
    made = [e for e in tracer.events if e["name"] == "Program::table"]
    assert sorted(e["args"]["program"] for e in made) == sorted(
        set(programs))
    assert all(e["ts"] < tables[0]["ts"] for e in made)
    assert not [e for e in tracer.events
                if e["name"] in ("jax::lower", "jax::backend_compile")
                and e["args"].get("parent") == "Program::table"]
    assert not [e for e in tracer.events
                if e["name"] == "Program::ops::failed"]


def test_a_program_described_in_a_capture_is_written_at_once():
    @jax.jit
    @phase("score")
    def f(x):
        return x * 2.0

    x = jnp.ones(8)
    f(x)
    tracer.program("score", f, x)       # off
    assert tracer._programs == {} and tracer.events == []
    tracer.enable(None)
    tracer.annotate(True)
    tracer.program("score", f, x)
    tracer.program("score", f, x)       # known: nothing again
    tracer.annotate(False)
    tables = _tables()
    assert len(tables) == 1
    assert sum(map(len, tables[0]["args"]["ops"].get("score", []))) > 0


_CACHE_SCRIPT = '''
import sys
import jax, jax.numpy as jnp
from lightgbm_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from lightgbm_tpu.obs.tracer import phase

@jax.jit
@phase(sys.argv[1])
def step(x):
    return jnp.sin(x) * 2.0

x = jnp.arange(64.0)
step(x)
print(step.lower(x).compile().as_text().count("lgbm." + sys.argv[1]))
'''


def test_a_cached_program_comes_back_with_the_scopes_it_was_built_with(
        tmp_path):
    """The persistent cache's key holds the instructions' metadata
    (``enable_compile_cache``): a program that differs from a cached
    one only in its phase is built, not fetched with the other's
    names - which is what ``Program::ops`` would then say."""
    import subprocess
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    env.pop("XLA_FLAGS", None)

    def run(name):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, name], env=env,
            capture_output=True, text=True, timeout=300,
            cwd=os.path.join(os.path.dirname(__file__), os.pardir))
        assert out.returncode == 0, out.stderr[-2000:]
        return int(out.stdout.strip().splitlines()[-1])

    assert run("score") > 0
    entries = len(os.listdir(tmp_path / "cache"))
    assert entries > 0
    assert run("gradients") > 0             # not the cached ``score``
    assert len(os.listdir(tmp_path / "cache")) > entries
    assert run("score") > 0
