"""routing pass: static routing-matrix audit + recompile audit
(ISSUE 10).

Two halves, both CPU-only and trace-only:

* **routing matrix** — a fresh enumeration of the config x env-knob x
  shape lattice (``ops/routing.py enumerate_matrix``) must match the
  checked-in golden byte-for-byte
  (``lightgbm_tpu/analysis/routing_matrix.json``): any silent routing
  change is a ``ROUTING_MATRIX_STALE`` finding.  Every checked-in
  row_order cell must carry at least one named fallback rule — a
  fast-path-eligible config routed to the 0.04x path with no
  justification (``ROUTING_UNJUSTIFIED_FALLBACK``) is either a model
  regression or a hand-mutated golden (the ``bad_route`` red team).
* **recompile audit** — representative lattice cells are built through
  the REAL ``make_grow_fn`` and traced with ``jax.make_jaxpr`` over
  abstract args (nothing executes): two independent builds of the same
  cell must digest identically (the compile set is a function of the
  program key, not of build order — ``ROUTING_PROGRAM_DIVERGES``);
  flipping a knob the routing model declares irrelevant for a cell
  must not change its digest (``ROUTING_KNOB_LEAKS``, generalizing the
  PR-7 purity pins); donations declared on the cell
  must survive in the lowered program (``ROUTING_DONATION_DROPPED``);
  and registered retrace pins — variants that share one shape bucket
  by contract, the ISSUE-2 serving engine's bucketed-batch design —
  must digest identically (``ROUTING_RETRACE``; a shape-dependent
  constant baked into a jitted body is the ``bad_retrace`` red team).

Digests hash the jaxpr text AND its consts bytes: a baked-in constant
array changes the consts even when the printed equation graph is
unchanged, which is exactly the retrace class this pass pins.
"""
from __future__ import annotations

import hashlib
import json
import os
import warnings
from contextlib import contextmanager
from typing import List

from ..findings import Finding, SEV_ERROR

PASS_NAME = "routing"


def matrix_path() -> str:
    from ...ops.routing import default_matrix_path
    return default_matrix_path()


def jaxpr_digest(fn, args) -> str:
    """sha256 over the traced program text + consts bytes."""
    import jax
    import numpy as np
    closed = jax.make_jaxpr(fn)(*args)
    h = hashlib.sha256(str(closed).encode())
    for c in closed.consts:
        try:
            h.update(np.asarray(c).tobytes())
        except Exception:
            h.update(repr(c).encode())
    return h.hexdigest()


@contextmanager
def _env(overrides: dict):
    """Temporarily set/unset environment knobs around a build."""
    saved = {k: os.environ.get(k) for k in overrides}
    try:
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------
# retrace pins: variants that SHARE one shape bucket by contract and
# must therefore trace to the identical program (the ISSUE-2 serving
# engine's bucketed-batch design is written against this check)
# ---------------------------------------------------------------------
def bucket_pad_variants(bake_constant: bool):
    """Two batch sizes (100 and 200 rows) padded into ONE serving
    bucket — the shared builder behind the clean retrace pin AND the
    ``bad_retrace`` fixture, so the pin genuinely guards this builder:

    * ``bake_constant=False`` (the pin): the true row count rides as a
      TRACED scalar and the body derives everything from traced
      operands, so both variants MUST compile the identical program —
      if an edit makes the body consume ``n_real`` at trace time, the
      clean pin fails, not just the red team;
    * ``bake_constant=True`` (the fixture): the row count is baked in
      as a trace-time constant, so the validity mask becomes a
      different const array per batch size and the digests diverge."""
    import jax.numpy as jnp

    from ..registry import sds
    BUCKET = 256

    def mk(n_real):
        if bake_constant:
            def fn(xpad):
                mask = (jnp.arange(BUCKET) < n_real).astype(
                    jnp.float32)
                return jnp.sum(xpad * mask[:, None])

            return fn, (sds((BUCKET, 8), jnp.float32),)

        def fn(xpad, n):
            # positions derived from the traced operand (no eager
            # constant computation: the pass must stay trace-only)
            pos = jnp.cumsum(jnp.ones_like(xpad[:, :1]), axis=0)
            mask = (pos <= n.astype(xpad.dtype)).astype(xpad.dtype)
            return jnp.sum(xpad * mask)

        return fn, (sds((BUCKET, 8), jnp.float32), sds((), jnp.int32))

    a, b = mk(100), mk(200)
    return [("rows=100", a[0], a[1]), ("rows=200", b[0], b[1])]


def _pin_serving_bucket_pad():
    return bucket_pad_variants(bake_constant=False)


def _pin_serve_forest_bucket():
    """The REAL serving kernel under the REAL bucket policy (ISSUE
    14): two runtime batch sizes that share one power-of-two bucket
    must trace the identical ``forest_scores`` program — the true row
    count rides as a traced scalar, bucket padding happens OUTSIDE the
    jit, and the bucket geometry is the only shape the program sees.
    If the bucket policy ever splits these sizes, or an edit bakes the
    real count into the body, this pin fails on CPU before any serving
    fleet retraces."""
    import functools

    from ...config import ENV_KNOBS
    from ...ops.predict import forest_scores_flat
    from ...serve.engine import bucket_for
    from ..entries import serve_forest_args
    # the SHIPPING bucket policy (the ENV_KNOBS default, not the live
    # env: pins must stay deterministic) — if the default ever moves,
    # the pin traces the new geometry automatically
    lo, hi = (int(v) for v in
              ENV_KNOBS["LGBM_TPU_SERVE_BUCKETS"][0].split(":"))
    variants = []
    for n_real in (130, 200):
        bucket = bucket_for(n_real, lo, hi)
        fn = functools.partial(forest_scores_flat, n_steps=5)
        variants.append((f"rows={n_real}", fn,
                         serve_forest_args(n=bucket)))
    return variants


RETRACE_PINS = {"serving-bucket-pad": _pin_serving_bucket_pad,
                "serving-forest-bucket": _pin_serve_forest_bucket}


# ---------------------------------------------------------------------
# matrix audit
# ---------------------------------------------------------------------
def _check_matrix(ctx) -> List[Finding]:
    from ...ops import routing as model
    out: List[Finding] = []
    path = getattr(ctx, "routing_matrix_path", None) or matrix_path()
    rel = os.path.relpath(path, os.getcwd()) if os.path.isabs(path) \
        else path
    fresh_bytes = model.canonical_bytes(model.enumerate_matrix())
    golden, golden_bytes = None, b""
    try:
        with open(path, "rb") as fh:
            golden_bytes = fh.read()
        golden = json.loads(golden_bytes.decode())
    except FileNotFoundError:
        out.append(Finding(
            pass_name=PASS_NAME, code="ROUTING_MATRIX_MISSING",
            severity=SEV_ERROR, where=f"file:{rel}",
            message=("checked-in golden routing matrix not found — "
                     "regenerate with python -m "
                     "lightgbm_tpu.ops.routing")))
    except (ValueError, OSError) as e:
        out.append(Finding(
            pass_name=PASS_NAME, code="ROUTING_MATRIX_UNREADABLE",
            severity=SEV_ERROR, where=f"file:{rel}",
            message=f"golden routing matrix unreadable: {e}"))
    if golden is not None and golden_bytes != fresh_bytes:
        fresh_cells = json.loads(fresh_bytes.decode())["cells"]
        gold_cells = dict(golden.get("cells") or {})
        changed = sorted(k for k in (set(fresh_cells) & set(gold_cells))
                         if fresh_cells[k] != gold_cells[k])
        added = sorted(set(fresh_cells) - set(gold_cells))
        removed = sorted(set(gold_cells) - set(fresh_cells))
        sample = (changed or added or removed)[:3]
        out.append(Finding(
            pass_name=PASS_NAME, code="ROUTING_MATRIX_STALE",
            severity=SEV_ERROR, where=f"file:{rel}",
            message=(
                f"golden matrix differs from a fresh enumeration "
                f"({len(changed)} cell(s) changed, {len(added)} new, "
                f"{len(removed)} removed"
                + (f"; e.g. {sample}" if sample else "")
                + ") — a routing rule changed without regenerating "
                "the golden (python -m lightgbm_tpu.ops.routing) or "
                "the golden was hand-edited")))
    # justification audit over the CHECKED-IN cells (so a hand-mutated
    # golden fails even when its bytes happen to parse) plus any
    # fixture-injected cells
    cells = dict((golden or {}).get("cells") or {})
    fixture_keys = set()
    for key, enc in getattr(ctx, "routing_cells", []):
        cells[key] = enc
        fixture_keys.add(key)
    for key in sorted(cells):
        try:
            c = model.decode_cell(cells[key])
        except (ValueError, KeyError) as e:
            out.append(Finding(
                pass_name=PASS_NAME, code="ROUTING_CELL_UNPARSEABLE",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=f"golden cell does not parse: {e}",
                fixture=key in fixture_keys))
            continue
        if c["path"] == "row_order" and not c["reasons"]:
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "cell routes a fast-path-eligible config to the "
                    "0.04x row_order path with NO named fallback rule "
                    "— either a routing-model regression or a mutated "
                    "golden matrix"),
                fixture=key in fixture_keys))
        # efb_overwide is a PURE SHAPE rule (ISSUE 12): it may only
        # justify a fallback on a cell whose key carries the over-wide
        # shape fact (ew=1).  A cell claiming it without the fact is a
        # smuggled re-opening of the graduated efb_bundle class — the
        # efb_overwide red-team fixture seeds exactly this.
        if ("efb_overwide" in c["reasons"]
                and "ew=1" not in key.split(";")):
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_EFB_OVERWIDE_UNJUSTIFIED",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "cell blames efb_overwide for a row_order fallback "
                    "but its key says the unbundled layout FITS the "
                    "comb column budget (ew=0) — bundled configs that "
                    "fit must ride the physical fast path (the ISSUE-12 "
                    "graduation); this cell re-opens the deleted "
                    "efb_bundle class under a new name"),
                fixture=key in fixture_keys))
        # comb_overwide is a pure shape rule too: only a
        # cell whose key carries the fact (cw=1) may blame it
        if ("comb_overwide" in c["reasons"]
                and "cw=1" not in key.split(";")):
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_COMB_OVERWIDE_UNJUSTIFIED",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "cell blames comb_overwide for a row_order fallback "
                    "but its key says every comb kernel stages the line "
                    "(no cw=1) - a dense table up to sixteen planes must "
                    "ride the physical fast path"),
                fixture=key in fixture_keys))
        # paged audit (ISSUE 15): an over-budget cell (ob=1) whose
        # engaged path holds the comb HBM-resident must either page or
        # name the paged rule that cost it — a resident over-budget
        # cell with no reason is an on-chip OOM the model stopped
        # seeing
        kf = dict(part.partition("=")[::2] for part in key.split(";"))
        if (kf.get("ob") == "1"
                and c["path"] in ("physical", "stream")
                and not c.get("paged")
                and not c.get("paged_reasons")):
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_PAGED_UNJUSTIFIED",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "cell keeps an over-budget shape (ob=1) fully "
                    "HBM-resident with NO named paged rule — the "
                    "shape OOMs on chip; either the paged routing "
                    "regressed or the golden matrix was mutated"),
                fixture=key in fixture_keys))
        # multiclass batch audit (ISSUE 19): a multiclass cell (k=multi)
        # on the physical fast path that still trains serial-K must
        # name the mc_batch rule that cost it the ONE-dispatch grow —
        # an unjustified serial cell silently pays K compiled dispatch
        # floors per iteration
        if (kf.get("k") == "multi"
                and c["path"] == "physical"
                and not c.get("mc_batched")
                and not c.get("mc_batch_reasons")):
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "multiclass cell rides the physical fast path but "
                    "trains its K class trees as K serial grow "
                    "dispatches with NO named mc_batch rule — either "
                    "the batched-multiclass routing regressed or the "
                    "golden matrix was mutated"),
                fixture=key in fixture_keys))
    # predict-side cells (ISSUE 14): every checked-in host-walk cell
    # must name the rule that cost it the compiled serving path, and
    # the named rules must exist in the live PREDICT_RULES table
    pcells = dict((golden or {}).get("predict_cells") or {})
    for key, enc in getattr(ctx, "routing_predict_cells", []):
        pcells[key] = enc
        fixture_keys.add(key)
    for key in sorted(pcells):
        enc = pcells[key]
        try:
            fields = dict(part.partition("=")[::2]
                          for part in enc.split(";"))
            ppath = fields["path"]
            preasons = ([] if fields.get("why", "-") == "-"
                        else fields["why"].split("+"))
            pkernel = bool(int(fields.get("kernel", 0)))
            kreasons = ([] if fields.get("kwhy", "-") == "-"
                        else fields["kwhy"].split("+"))
        except (ValueError, KeyError) as e:
            out.append(Finding(
                pass_name=PASS_NAME, code="ROUTING_CELL_UNPARSEABLE",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=f"golden predict cell does not parse: {e}",
                fixture=key in fixture_keys))
            continue
        if ppath == "host" and not preasons:
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "predict cell routes a compile-eligible predict "
                    "to the host reference walk with NO named rule — "
                    "either a predict_decide regression or a mutated "
                    "golden matrix"),
                fixture=key in fixture_keys))
        unknown = [r for r in preasons + kreasons
                   if r not in model.PREDICT_RULE_BY_NAME]
        if unknown:
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    f"predict cell names rule(s) {unknown} that do "
                    "not exist in ops/routing.py PREDICT_RULES — a "
                    "deleted rule left stale justifications behind"),
                fixture=key in fixture_keys))
        # serve_kernel audit (ISSUE 18): a compiled cell that runs the
        # gather walk instead of the VMEM kernel must name the kernel
        # rule that cost it — and serve_forest_overwide is a PURE
        # SHAPE rule, valid only on cells whose key carries the
        # over-wide forest fact (ow=1).  This is the static proof of
        # the ~2MB engagement rule: a fitting forest whose kernel was
        # asked for (LGBM_TPU_SERVE_KERNEL=1 on the TPU backend, or
        # the interpret seam) MUST ride the kernel.
        if ppath == "compiled" and not pkernel and not kreasons:
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "predict cell serves a kernel-eligible compiled "
                    "predict through the XLA gather walk with NO "
                    "named serve_kernel rule — either a "
                    "predict_decide regression or a mutated golden "
                    "matrix"),
                fixture=key in fixture_keys))
        if ("serve_forest_overwide" in kreasons
                and "ow=1" not in key.split(";")):
            out.append(Finding(
                pass_name=PASS_NAME,
                code="ROUTING_UNJUSTIFIED_FALLBACK",
                severity=SEV_ERROR, where=f"cell:{key}",
                message=(
                    "predict cell blames serve_forest_overwide but "
                    "its key says the stacked forest FITS the VMEM "
                    "scratch cap (ow=0) — fitting forests must ride "
                    "the Pallas traversal kernel on the compiled "
                    "path (the ISSUE-18 engagement rule)"),
                fixture=key in fixture_keys))
    return out


# ---------------------------------------------------------------------
# recompile audit
# ---------------------------------------------------------------------
def _phys_build(f_pad: int, env: dict = None):
    """Build the physical grow program for one lattice cell at a small
    shape; returns ``(grow_wrapper, abstract_args)``."""
    import jax.numpy as jnp

    from ...ops.grow import make_grow_fn
    from ...ops.split import SplitHyperParams
    from ..registry import sds
    n, b = 4096, 32
    hp = SplitHyperParams(min_data_in_leaf=2)
    with _env(env or {}):
        gp = make_grow_fn(hp, num_leaves=8, padded_bins=b,
                          physical_bins=sds((n, f_pad), jnp.uint8))
    n_phys = gp._n_alloc
    args = (sds((n_phys, gp._C), jnp.float32),
            sds((n_phys, gp._C), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((f_pad,), jnp.float32),
            sds((f_pad,), jnp.int32), sds((f_pad,), jnp.bool_),
            sds((f_pad,), jnp.bool_), sds((), jnp.int32),
            sds((), jnp.float32))
    return gp, args


def _serial_build(env: dict = None):
    import jax.numpy as jnp

    from ...ops.grow import make_grow_fn
    from ...ops.split import SplitHyperParams
    from ..registry import sds
    n, f, b = 128, 8, 32
    hp = SplitHyperParams(min_data_in_leaf=2)
    with _env(env or {}):
        fn = make_grow_fn(hp, num_leaves=8, padded_bins=b)
    args = (sds((n, f), jnp.uint8), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((f,), jnp.float32), sds((f,), jnp.int32),
            sds((f,), jnp.bool_), sds((f,), jnp.bool_),
            sds((), jnp.int32))
    return fn, args


# knobs to UNSET for every audited build: the audit pins the shipping
# cells, and an exported sweep knob would silently re-route them
_CLEAN = {"LGBM_TPU_STREAM": None, "LGBM_TPU_PHYS": None,
          "LGBM_TPU_HIST_SCATTER": None}


def _audit_recompile(ctx) -> List[Finding]:
    out: List[Finding] = []

    def finding(code, where, message):
        out.append(Finding(pass_name=PASS_NAME, code=code,
                           severity=SEV_ERROR, where=where,
                           message=message))

    # 1. determinism: two independent builds of one cell, one program
    try:
        gp_a, args_a = _phys_build(16, dict(_CLEAN))
        gp_b, args_b = _phys_build(16, dict(_CLEAN))
        d_a = jaxpr_digest(gp_a._grow_p, args_a)
        d_b = jaxpr_digest(gp_b._grow_p, args_b)
        if d_a != d_b:
            finding(
                "ROUTING_PROGRAM_DIVERGES",
                "cell:physical/permute",
                f"two independent builds of the same lattice cell "
                f"trace to DIFFERENT programs ({d_a[:12]} != "
                f"{d_b[:12]}): the compile set is not a function of "
                f"the program key, so every rebuild recompiles")
    except Exception as e:
        finding("ROUTING_AUDIT_FAILED", "cell:physical/permute",
                f"recompile audit build raised: "
                f"{type(e).__name__}: {e}")
        d_a = None

    # 2. irrelevant-knob flips: the routing model says these knobs do
    # not change the engaged program of the flipped cell, so the
    # digest must not move (the purity-pin idea generalized to the
    # routing lattice)
    flips = [
        ("physical", "LGBM_TPU_HIST_SCATTER", "0",
         lambda: _phys_build(16, dict(_CLEAN,
                                      LGBM_TPU_HIST_SCATTER="0")),
         lambda: (gp_a, args_a) if d_a is not None
         else _phys_build(16, dict(_CLEAN))),
        ("serial/row_order", "LGBM_TPU_STREAM", "0",
         lambda: _serial_build(dict(_CLEAN, LGBM_TPU_STREAM="0")),
         lambda: _serial_build(dict(_CLEAN))),
    ]
    for label, knob, val, build_flip, build_base in flips:
        try:
            base_fn, base_args = build_base()
            flip_fn, flip_args = build_flip()
            base_fn = getattr(base_fn, "_grow_p", base_fn)
            flip_fn = getattr(flip_fn, "_grow_p", flip_fn)
            if jaxpr_digest(base_fn, base_args) != \
                    jaxpr_digest(flip_fn, flip_args):
                finding(
                    "ROUTING_KNOB_LEAKS", f"cell:{label} knob:{knob}",
                    f"{knob}={val} changes the traced program of a "
                    f"cell the routing matrix marks insensitive to it "
                    f"— an irrelevant knob flip would recompile (and "
                    f"invalidate) the cached fast-path program")
        except Exception as e:
            finding("ROUTING_AUDIT_FAILED", f"cell:{label} knob:{knob}",
                    f"knob-flip audit raised: {type(e).__name__}: {e}")

    # 3. donations survive at a second width (64 feature columns):
    # the declared comb/scratch aliases must appear in the LOWERED
    # program (lowering only; backend_compile is never reached)
    try:
        wide, w_args = _phys_build(64, dict(_CLEAN))
        from .hbm import entry_residency_bytes
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lowered = wide._grow_p.lower(*w_args)
        kept = None
        try:
            kv = lowered._lowering.compile_args.get("kept_var_idx")
            if kv is not None:
                kept = tuple(sorted(int(i) for i in kv))
        except Exception:
            kept = None
        _, aliased = entry_residency_bytes(
            lowered.as_text(), w_args, kept=kept)
        for argnum in (0, 1):
            if argnum not in aliased:
                finding(
                    "ROUTING_DONATION_DROPPED",
                    f"cell:physical/wide arg:{argnum}",
                    f"the comb/scratch donation (argnum {argnum}) "
                    f"was dropped in the lowered program of this "
                    f"lattice cell — every grow call double-"
                    f"allocates the buffer")
    except Exception as e:
        finding("ROUTING_AUDIT_FAILED", "cell:physical/wide",
                f"donation audit raised: {type(e).__name__}: {e}")
    return out


def _check_retrace_pins(ctx) -> List[Finding]:
    out: List[Finding] = []
    pins = dict(RETRACE_PINS)
    fixture_pins = dict(getattr(ctx, "retrace_pins", {}))
    pins.update(fixture_pins)
    for name in sorted(pins):
        is_fixture = name in fixture_pins
        try:
            variants = pins[name]()
            digests = [(vname, jaxpr_digest(fn, args))
                       for vname, fn, args in variants]
        except Exception as e:
            out.append(Finding(
                pass_name=PASS_NAME, code="ROUTING_PIN_BUILD_FAILED",
                severity=SEV_ERROR, where=f"retrace-pin:{name}",
                message=(f"retrace pin builder raised: "
                         f"{type(e).__name__}: {e}"),
                fixture=is_fixture))
            continue
        base_name, base = digests[0]
        for vname, d in digests[1:]:
            if d != base:
                out.append(Finding(
                    pass_name=PASS_NAME, code="ROUTING_RETRACE",
                    severity=SEV_ERROR,
                    where=f"retrace-pin:{name} variant:{vname}",
                    message=(
                        f"variant {vname!r} traces a DIFFERENT "
                        f"program than {base_name!r} ({d[:12]} != "
                        f"{base[:12]}) inside ONE shape bucket: a "
                        f"shape-dependent constant is baked into the "
                        f"jitted body, so every batch size recompiles "
                        f"— the bucketed-batch contract the serving "
                        f"engine is written against is broken"),
                    fixture=is_fixture))
    return out


def run(ctx) -> List[Finding]:
    out = _check_matrix(ctx)
    out.extend(_audit_recompile(ctx))
    out.extend(_check_retrace_pins(ctx))
    return out
