"""Gradient/hessian histogram construction on TPU.

Reference analog: the CUDA histogram kernel
(src/treelearner/cuda/cuda_histogram_constructor.cu:18-126) which uses
shared-memory atomicAdd per (feature, bin).  TPUs have no fast scatter-atomics,
so the op is re-expressed for the MXU as a **split-bin one-hot matmul**:

    bin = hi * lo_n + lo        (hi in [0, B_hi), lo in [0, lo_n), B_hi * lo_n = B)
    hist[f, hi, lo, c] = sum_r onehot_hi[r, f, hi] * onehot_lo[r, f, lo] * val[r, c]

Features are packed in groups of ``G`` (``feature_group_size``) so the matmul
operands are ``[R, G * B_hi]`` x ``[R, G * lo_n * C]``, contraction over rows.
The Pallas kernels (ops/pallas/hist_kernel2.hist_geometry) take the split from
the bin count: ``lo_n = B / 8`` up to 128 bins (``B_hi = 8``, ``G = 16``) and
``lo_n = 16`` past it, so ``G * B_hi == 128`` — a full MXU tile on the M axis —
up to 128 bins and at 256 (at most 128 between them).  The XLA matmul
formulation below (no chip route runs it) keeps ``lo_n = 16``, whose M axis is
``G * B/16``: 128 at 256 bins, less below 128.  Cross-feature blocks of the
``[M, G*lo_n*C]`` product are garbage and discarded (the diagonal g==g' blocks
are the per-feature histograms); this costs a factor ``G`` of extra FLOPs but
turns an un-TPU-friendly scatter into dense matmuls, which wins by orders of
magnitude.  Rows are streamed in blocks with ``lax.scan`` to bound the one-hot
intermediates: per block they are ``R * F_pad * (B/16) / G`` floats for the hi
one-hot and ``R * F_pad * 16 * C / G * G = R * F_pad * 16 * C`` for the
lo-times-values tensor — ~50 MB per 4096-row block at F_pad=128, C=3 if XLA
materialises them un-fused.  Tune ``rows_per_block`` down on small-memory
devices; the Pallas kernel (ops/pallas) builds the one-hots in VMEM and has no
such intermediate.

Channels: c = (grad, hess, count).  Masking (leaf membership, bagging) is
folded into the values, so a histogram over any row subset is a full-rate
dense pass — the reference's smaller-leaf + subtraction trick
(serial_tree_learner.cpp:287-327) is applied by the caller at the
[F, B, 3]-array level.

Precision: the reference accumulates double histograms (bin.h:32) or fp32 on
GPU (gpu_use_dp).  Here one-hots are exact in any dtype; values are f32 and
accumulation is f32 (``gpu_use_dp=True`` upgrades accumulation to f64).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def bins_per_feature_padded(max_num_bins: int) -> int:
    """Pad per-feature bin count to a multiple of 16 (the split-bin
    decomposition: a whole ``lo_n`` at every split)."""
    b = max(int(max_num_bins), 16)
    return int(np.ceil(b / 16) * 16)


def feature_group_size(padded_bins: int) -> int:
    """Features per matmul group: G * (B/16) <= 128 (at most one MXU tile
    on the M axis of the XLA matmul's 16-wide split), with G capped at 16 to
    bound the Pallas kernel's unrolled one-hot construction: 16 up to 128
    bins, where the Pallas kernels split a bin 8 ways on hi and fill the M
    axis (hist_kernel2.hist_geometry).  The XLA matmul impl and the Pallas
    kernel share this value so the dataset's feature padding satisfies
    both."""
    b_hi = max(padded_bins // 16, 1)
    return max(min(128 // b_hi, 16), 1)


def default_histogram_impl() -> str:
    """The v2 Pallas kernel on TPU (matmul-expanded one-hots in VMEM,
    measured ~2x the XLA nibble matmul inside the grow loop at 16k-row
    buckets and ~4x at 1M rows on v5e — the XLA path materialises ~200
    one-hot bytes per (row, feature) through HBM); scatter-add elsewhere
    (XLA CPU/GPU lower scatter natively, and the nibble matmul's
    garbage-FLOP factor has no MXU to hide in).  Override with the
    ``LGBM_TPU_HIST_IMPL`` env var (pallas2 | pallas | matmul | scatter)."""
    import os
    forced = os.environ.get("LGBM_TPU_HIST_IMPL", "")
    if forced:
        return forced
    return "pallas2" if jax.default_backend() == "tpu" else "scatter"


@functools.partial(jax.jit, static_argnames=("padded_bins", "rows_per_block",
                                             "use_dp", "impl"))
def build_histogram(
    bins: jnp.ndarray,      # [n, F_pad] uint8/int32, values < padded_bins
    values: jnp.ndarray,    # [n, C] f32 (grad, hess, count-indicator), masked
    *,
    padded_bins: int,
    rows_per_block: int = 16384,
    use_dp: bool = False,
    impl: str = "",
) -> jnp.ndarray:
    """Returns hist [F_pad, padded_bins, C] f32 (f64 accumulate if use_dp)."""
    if not impl:
        impl = default_histogram_impl()
    if impl == "scatter":
        return _build_histogram_scatter(bins, values, padded_bins, use_dp)
    if impl in ("pallas2", "pallas2_interpret"):
        if use_dp:
            # kernel multiplies in bf16 / accumulates f32; honor gpu_use_dp
            # by routing to the XLA matmul path (f64-capable under x64)
            import warnings
            warnings.warn(
                "gpu_use_dp: pallas2 histogram kernel is bf16/f32-only; "
                "falling back to the XLA matmul implementation.",
                stacklevel=2)
        else:
            from .pallas.hist_kernel2 import build_histogram_pallas2
            return build_histogram_pallas2(
                bins, values, padded_bins=padded_bins,
                rows_per_block=min(rows_per_block, 2048),
                interpret=(impl == "pallas2_interpret"
                           or jax.default_backend() != "tpu"))
    if impl in ("pallas", "pallas_interpret"):
        if use_dp:
            # the Pallas kernel accumulates f32 only; honor gpu_use_dp by
            # routing to the XLA matmul path (which supports f64 under x64)
            import warnings
            warnings.warn(
                "gpu_use_dp: pallas histogram kernel is float32-only; "
                "falling back to the XLA matmul implementation.",
                stacklevel=2)
        else:
            from .pallas.hist_kernel import build_histogram_pallas
            return build_histogram_pallas(
                bins, values, padded_bins=padded_bins,
                rows_per_block=min(rows_per_block, 1024),
                interpret=(impl == "pallas_interpret"
                           or jax.default_backend() != "tpu"))
    n, f_pad = bins.shape
    c = values.shape[1]
    b = padded_bins
    b_hi = b // 16
    g = feature_group_size(b)
    assert f_pad % g == 0, (f_pad, g)
    ngroups = f_pad // g

    nblocks = -(-n // rows_per_block)
    n_padded = nblocks * rows_per_block
    if n_padded != n:
        bins = jnp.pad(bins, ((0, n_padded - n), (0, 0)))
        values = jnp.pad(values, ((0, n_padded - n), (0, 0)))

    bins = bins.astype(jnp.int32).reshape(nblocks, rows_per_block, f_pad)
    values = values.reshape(nblocks, rows_per_block, c)
    if use_dp and not jax.config.jax_enable_x64:
        # jnp silently downcasts f64 -> f32 without x64 mode; surface it
        # instead of pretending the flag worked (reference gpu_use_dp doubles)
        import warnings
        warnings.warn(
            "gpu_use_dp requested but JAX x64 mode is disabled; histogram "
            "accumulation stays in float32. Set JAX_ENABLE_X64=1 for true "
            "double-precision histograms.", stacklevel=2)
    acc_dtype = jnp.float64 if use_dp else jnp.float32

    def block(carry, operand):
        bins_blk, vals_blk = operand  # [R, F_pad], [R, C]
        hi = bins_blk // 16
        lo = bins_blk % 16
        # [R, ngroups, G*B_hi] with G*B_hi == 128
        oh_hi = jax.nn.one_hot(hi, b_hi, dtype=jnp.float32)
        oh_hi = oh_hi.reshape(rows_per_block, ngroups, g * b_hi)
        # [R, ngroups, G*16*C]
        oh_lo = jax.nn.one_hot(lo, 16, dtype=jnp.float32)
        lo_val = oh_lo[..., None] * vals_blk[:, None, None, :]
        lo_val = lo_val.reshape(rows_per_block, ngroups, g * 16 * c)
        # contraction over rows; one batched matmul per feature group
        prod = jax.lax.dot_general(
            oh_hi, lo_val,
            dimension_numbers=(((0,), (0,)), ((1,), (1,))),
            preferred_element_type=jnp.float32,
        )  # [ngroups, G*B_hi, G*16*C]
        prod = prod.reshape(ngroups, g, b_hi, g, 16, c)
        # keep only the diagonal (same-feature) blocks
        diag = jnp.diagonal(prod, axis1=1, axis2=3)  # [ngroups, B_hi, 16, C, G]
        diag = jnp.moveaxis(diag, -1, 1)             # [ngroups, G, B_hi, 16, C]
        return carry + diag.reshape(f_pad, b, c).astype(acc_dtype), None

    init = jnp.zeros((f_pad, b, c), dtype=acc_dtype)
    hist, _ = jax.lax.scan(block, init, (bins, values))
    return hist.astype(jnp.float32)


def _build_histogram_scatter(bins, values, padded_bins, use_dp) -> jnp.ndarray:
    """Scatter-add formulation (the reference CPU hot loop
    dense_bin.hpp:98-140, one add per (row, feature)).  Used off-TPU."""
    n, f_pad = bins.shape
    c = values.shape[1]
    b = padded_bins
    acc_dtype = jnp.float64 if (use_dp and jax.config.jax_enable_x64) else jnp.float32
    offsets = (jnp.arange(f_pad, dtype=jnp.int32) * b)[None, :]
    idx = (bins.astype(jnp.int32) + offsets).reshape(-1)
    upd = jnp.broadcast_to(values[:, None, :], (n, f_pad, c)).reshape(-1, c)
    hist = jnp.zeros((f_pad * b, c), acc_dtype).at[idx].add(
        upd.astype(acc_dtype))
    return hist.reshape(f_pad, b, c).astype(jnp.float32)


def subtract_histogram(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """The reference's histogram subtraction trick
    (serial_tree_learner.cpp:428 ``Subtract``): sibling = parent - child.
    A trivial vector op on TPU."""
    return parent - child
