"""Pallas TPU kernel: VMEM-resident level-synchronous forest traversal
(ISSUE 18, the serving hot-path graduation).

Reference analog: the CUDA prediction path keeps the tree arrays in
shared/L2 and walks all rows per block (src/treelearner/cuda's
prediction kernels); the XLA gather walk we ship since ISSUE 14
(``ops/predict._forest_walk``) re-streams the ``[T, ni_pad]`` node
arrays from HBM on EVERY level of every dispatch —
``costmodel.serving_traversal_bytes`` prices it at ~28 B per
(row, tree, level).  This kernel inverts the loop's memory shape:

* the ENTIRE stacked forest — threshold bins, left/right pointers, the
  packed node-meta word, the flat cat bitset words + bit counts, and
  the leaf table — is DMA'd HBM->VMEM **once per dispatch** (grid step
  0; VMEM scratch persists across the sequential TPU grid), so every
  traversal level after that reads VMEM, not HBM;
* row blocks stream through double-buffered VMEM tiles via the normal
  Pallas block pipeline: the ONE ``[BR, F]`` i32 matrix
  (``ops.predict.quantize_rows_kernel`` — quantized bins on numerical
  columns, int-truncated raw values on categorical columns) in,
  per-class scores out;
* the donated score buffer is preserved through an explicit
  ``input_output_aliases`` entry, so steady-state dispatches allocate
  nothing (the PR-9 donation contract, audited by the analyzer's
  hbm-budget pass on the interpret entry).

``costmodel.serving_kernel_bytes`` prices exactly this contract
(forest bytes once + row bytes once, no per-level term) and the kernel
only engages when ``layout.serve_forest_fit`` holds — the stacked
forest fits ``layout.SERVE_FOREST_VMEM_CAP`` (over-wide forests take
the loud ``serve_forest_overwide`` routing fallback to the XLA gather
walk; ops/routing.py).

Traversal-semantics deltas vs the gather walk, both baked at stack
time by ``serve/model.py``:

* no ``init_node`` in VMEM — every tree starts at node 0, and a
  single-leaf tree's node-0 children are both ``~0`` so one step parks
  it on leaf 0 (the gather walk keeps ``init_node = -1`` instead);
* no ``is_categorical`` array — node-meta bit 2 carries the flag;
* no raw-value re-gather per level — categorical columns of the input
  matrix already hold the int-truncated raw values.

Leaf-index-EXACT parity against the gather walk and the host walk is
pinned off-chip by tests/test_serve_kernel.py through the Pallas
interpreter (``LGBM_TPU_SERVE_INTERP=kernel``), the same proof seam
as ``LGBM_TPU_PART_INTERP``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the unblocked HBM memory space of manually-DMA'd refs
_HBM = pltpu.HBM

# default row-block height of the streamed input tile; buckets are
# pow2 >= 64 so any bucket either divides it or equals BR after the
# min() in make_serve_traverse
_BLOCK_ROWS = 512


def _traverse_block(bins, scratch, *, T: int, NI: int, W: int,
                    n_steps: int):
    """[BR, F] i32 block -> [BR, T] leaf indices, reading ONLY the
    VMEM-resident forest values in ``scratch`` (flat i32 vectors).
    The level loop is the same lock-step node-pointer chase as
    ``ops.predict._forest_walk``, minus the per-level HBM gathers."""
    sf, tb, lc, rc, nm, cw, nb = scratch
    br = bins.shape[0]
    tri = jax.lax.broadcasted_iota(jnp.int32, (br, T), 1)

    def body(_, node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        gidx = tri * NI + nd                               # [BR, T]
        feat = sf[gidx]
        b = jnp.take_along_axis(bins, feat, axis=1)
        meta = nm[gidx]
        at_nan = ((meta & 2) > 0) & (b == (meta >> 3))
        go_num = ((b <= tb[gidx]) & ~at_nan) | (at_nan
                                                & ((meta & 1) > 0))
        if W > 0:
            # raw-value bitset membership: categorical columns of the
            # input matrix carry int-truncated raw values (NaN/inf ->
            # -1, rejected by the range check like the host walk)
            ok = (b >= 0) & (b < nb[gidx])
            ivc = jnp.clip(b, 0, W * 32 - 1)
            word = cw[gidx * W + ivc // 32]
            go_cat = ok & (((word >> (ivc % 32)) & 1) > 0)
            go_left = jnp.where((meta & 4) > 0, go_cat, go_num)
        else:
            go_left = go_num
        nxt = jnp.where(go_left, lc[gidx], rc[gidx])
        return jnp.where(active, nxt, node)

    node = jnp.zeros((br, T), jnp.int32)
    if n_steps > 0:
        node = jax.lax.fori_loop(0, n_steps, body, node)
    return ~jnp.minimum(node, -1)


def _serve_kernel(n_real_ref, *refs, T: int, NI: int, NL: int, W: int,
                  K: int, n_steps: int, leaves: bool):
    """One grid step: land the forest in VMEM scratch (step 0 only —
    scratch persists across the sequential grid), then traverse one
    row block."""
    # forest HBM operands: sf, tb, lc, rc, nm [, cw, nb] [, lv] — the
    # scratch_shapes list mirrors this order exactly, so the landing
    # loop below is a plain zip
    nf = 5 + (2 if W > 0 else 0) + (0 if leaves else 1)
    forest_in = refs[:nf]
    if leaves:
        bins_ref, out_ref = refs[nf], refs[nf + 1]
        scratch_refs, sem = refs[nf + 2:-1], refs[-1]
    else:
        bins_ref, _buf_ref, out_ref = (refs[nf], refs[nf + 1],
                                       refs[nf + 2])
        scratch_refs, sem = refs[nf + 3:-1], refs[-1]

    @pl.when(pl.program_id(0) == 0)
    def _land_forest():
        # the whole forest, HBM -> VMEM, once per dispatch — the
        # "forest bytes once" term of costmodel.serving_kernel_bytes
        for src, dst in zip(forest_in, scratch_refs):
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()

    vsf, vtb, vlc, vrc, vnm = scratch_refs[:5]
    if W > 0:
        vcw, vnb = scratch_refs[5:7]
        cw, nb = vcw[:].reshape(-1), vnb[:].reshape(-1)
    else:
        cw = nb = None
    scratch = (vsf[:].reshape(-1), vtb[:].reshape(-1),
               vlc[:].reshape(-1), vrc[:].reshape(-1),
               vnm[:].reshape(-1), cw, nb)

    br = bins_ref.shape[0]
    leaf = _traverse_block(bins_ref[:], scratch, T=T, NI=NI, W=W,
                           n_steps=n_steps)
    rows = (pl.program_id(0) * br
            + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0))
    live = rows < n_real_ref[0]
    if leaves:
        out_ref[:] = jnp.where(live, leaf, 0)
    else:
        vlv = scratch_refs[-1]
        tri = jax.lax.broadcasted_iota(jnp.int32, (br, T), 1)
        # upcast right after the read: the leaf table may be bf16
        # (LGBM_TPU_SERVE_LEAF_BF16) but scores accumulate f32
        vals = vlv[:].reshape(-1)[tri * NL + leaf].astype(jnp.float32)
        per_class = vals.reshape(br, T // max(K, 1), K).sum(axis=1)
        out_ref[:] = jnp.where(live, per_class, 0.0)


def make_serve_traverse(*, n: int, trees: int, ni_pad: int,
                        nl_pad: int, cat_words_w: int, n_feat: int,
                        num_class: int, n_steps: int,
                        leaf_dtype=jnp.float32,
                        block_rows: int = _BLOCK_ROWS,
                        leaves: bool = False,
                        interpret: bool = False):
    """Build the VMEM-resident traversal for one (bucket, forest
    geometry) cell.

    Scores form: ``fn(sf, tb, lc, rc, nm[, cw, nb], lv, bins, n_real,
    buf) -> [n, K] f32`` with ``buf`` aliased to the output (the
    donated score buffer).  ``leaves=True`` drops ``lv``/``buf`` and
    returns ``[n, T]`` i32 leaf indices (the parity probe).  ``bins``
    is the single [n, F] i32 matrix from
    ``ops.predict.quantize_rows_kernel``; ``n_real`` rides as i32[1]
    SMEM (a traced value — the bucket's program must not retrace per
    batch size; the ROUTING_RETRACE contract)."""
    from .layout import check_lane_width
    check_lane_width(ni_pad, jnp.int32)
    check_lane_width(nl_pad, jnp.int32)
    t, ni, nl, w, f, k = (int(trees), int(ni_pad), int(nl_pad),
                          int(cat_words_w), int(n_feat),
                          int(num_class))
    br = min(int(block_rows), int(n))
    if n % br:
        raise ValueError(
            f"bucket rows {n} must be a multiple of the row block "
            f"{br} (buckets are pow2, so this only fires on a "
            f"mis-built dispatch)")
    kern = functools.partial(_serve_kernel, T=t, NI=ni, NL=nl, W=w,
                             K=k, n_steps=int(n_steps), leaves=leaves)

    hbm = pl.BlockSpec(memory_space=_HBM)
    nf = 5 + (2 if w > 0 else 0) + (0 if leaves else 1)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]    # n_real
    in_specs += [hbm] * nf                                # forest
    in_specs += [pl.BlockSpec((br, f), lambda i: (i, 0))]  # bins
    scratch = [pltpu.VMEM((t, ni), jnp.int32)] * 5
    if w > 0:
        scratch += [pltpu.VMEM((t, ni * w), jnp.int32),
                    pltpu.VMEM((t, ni), jnp.int32)]
    aliases = {}
    if leaves:
        out_specs = pl.BlockSpec((br, t), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((n, t), jnp.int32)
    else:
        scratch += [pltpu.VMEM((t, nl), jnp.dtype(leaf_dtype))]
        in_specs += [pl.BlockSpec((br, k), lambda i: (i, 0))]  # buf
        out_specs = pl.BlockSpec((br, k), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((n, k), jnp.float32)
        # the donated score buffer: last input -> the one output
        aliases = {len(in_specs) - 1: 0}
    scratch += [pltpu.SemaphoreType.DMA]

    call = pl.pallas_call(
        kern,
        grid=(n // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        interpret=interpret,
    )

    if leaves:
        def fn(sf, tb, lc, rc, nm, *rest):
            *cat, bins, n_real = rest
            return call(n_real, sf, tb, lc, rc, nm, *cat, bins)
    else:
        def fn(sf, tb, lc, rc, nm, *rest):
            *cat, lv, bins, n_real, buf = rest
            return call(n_real, sf, tb, lc, rc, nm, *cat, lv, bins,
                        buf)
    return fn


def forest_kernel_args(forest, *, leaves: bool = False):
    """The positional forest operands of a built traversal, in
    ``make_serve_traverse`` order — the ONE place the engine and the
    parity tests unpack a :class:`~lightgbm_tpu.ops.predict
    .ServingForest` for the kernel (the stacking-order contract)."""
    t, ni = forest.split_feature.shape
    w = forest.cat_words.shape[1] // max(int(ni), 1)
    args = [forest.split_feature, forest.threshold_bin,
            forest.left_child, forest.right_child, forest.node_meta]
    if w > 0:
        args += [forest.cat_words, forest.cat_nbits]
    if not leaves:
        args += [forest.leaf_value]
    return tuple(args)


# ---- static-analysis registration (lightgbm_tpu/analysis, ISSUE 7) ----
from ...analysis.registry import register_kernel, sds


def _demo_geometry():
    """The max-fit forest cell: the LARGEST geometry the
    ``serve_forest_overwide`` rule admits under the 4 MiB cap
    (layout.serve_forest_vmem_bytes(500, 256, 256) = 3 MiB), so the
    analyzer's vmem-budget pass proves the "~2 MB-class forests fit"
    engagement rule statically — a cap regression becomes a
    VMEM_OVERSUBSCRIBED finding, not a Mosaic error on chip."""
    return dict(n=1024, trees=500, ni_pad=256, nl_pad=256,
                cat_words_w=0, n_feat=32, num_class=1, n_steps=9)


def _demo_args(geo, *, leaves: bool = False):
    import jax.numpy as jnp
    t, ni, nl = geo["trees"], geo["ni_pad"], geo["nl_pad"]
    args = [sds((t, ni), jnp.int32)] * 2 + \
           [sds((t, ni), jnp.int32)] * 2 + [sds((t, ni), jnp.int32)]
    if geo["cat_words_w"] > 0:
        args += [sds((t, ni * geo["cat_words_w"]), jnp.int32),
                 sds((t, ni), jnp.int32)]
    if not leaves:
        args += [sds((t, nl), jnp.float32)]
    args += [sds((geo["n"], geo["n_feat"]), jnp.int32),
             sds((1,), jnp.int32)]
    if not leaves:
        args += [sds((geo["n"], geo["num_class"]), jnp.float32)]
    return tuple(args)


@register_kernel("serve_traverse", kind="serve",
                 note="VMEM-resident serving traversal (ISSUE 18) at "
                      "the max-fit forest geometry: the whole forest "
                      "lands in VMEM scratch once per dispatch, row "
                      "blocks pipeline through double-buffered tiles "
                      "— the vmem-budget pass prices the resident set "
                      "the serve_forest_overwide rule admits")
def _serve_traverse():
    geo = _demo_geometry()
    return make_serve_traverse(**geo), _demo_args(geo)


@register_kernel("serve_traverse_interp", kind="serve", donate=(8,),
                 note="interpret-mode build of serve_traverse (the "
                      "LGBM_TPU_SERVE_INTERP=kernel proof seam): "
                      "lowers off-TPU, so the hbm-budget pass audits "
                      "the donated score buffer's aliasing through "
                      "the pallas_call (argnum 8 = buf)")
def _serve_traverse_interp():
    geo = _demo_geometry()
    return (make_serve_traverse(**geo, interpret=True),
            _demo_args(geo))
