"""Per-split fixed-cost floor: separate partition+hist pair vs fused.

Reproduces the ISSUE-1 claim that fusing the single-scan partition with
the child-histogram accumulation cuts the per-split floor at small
leaves (~120 us for the pair at 1k rows; docs/PERF_NOTES.md "Next
levers" #3).  Each variant runs ONE split of an L-row leaf per
iteration of an in-jit fori_loop whose accumulator depends on the
kernel outputs (nleft + histogram sum), barriered by a HOST VALUE PULL
(PERF_NOTES "round 3b" methodology).

  pair   — make_partition_ss + build_histogram_comb_dyn of the smaller
           child: the unfused production path's two pallas_call entries
  fused  — what ops/grow.py runs at a split whose record named the
           smaller child (ISSUE 30): make_fused_split, one scan that
           histograms that child from the VMEM-resident blocks, and a
           lax.cond that has nothing to do
  miss   — the same at a split whose record named the LARGER child:
           the cond histograms the smaller one from the comb

Env: LS=1024,4096 (leaf-row sweep), REPS=1000 (in-jit splits per
timing; keep >= 1000 or the ~20-50 ms dispatch floor pollutes the
division), R=512 (partition block rows).  Off-TPU the kernels run in
interpret mode with tiny REPS — a functional check only, not a timing.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from profile_lib import bench_chain

import numpy as np
import jax
import jax.numpy as jnp

F_PAD = 32          # 28 Higgs-like features padded to the group size
B = 256             # 255 bins + pad
C = 128             # physical comb lane width (f_pad + extras -> 128)
HIST_RPB = 2048


def make_leaf(n_alloc: int, L: int, seed: int = 0):
    """Comb-layout leaf: bins at cols [0, F_PAD), (g, h) at
    [F_PAD, F_PAD+2), rows [0, L) valid."""
    rng = np.random.default_rng(seed)
    comb = np.zeros((n_alloc, C), np.float32)
    comb[:L, :F_PAD] = rng.integers(0, B, size=(L, F_PAD))
    comb[:L, F_PAD:F_PAD + 2] = rng.normal(size=(L, 2))
    comb[:L, F_PAD + 1] = np.abs(comb[:L, F_PAD + 1]) + 0.1
    return jnp.asarray(comb), jnp.zeros((n_alloc, C), jnp.float32)


def build(var: str, L: int, R: int, interpret: bool, small_left: bool):
    from lightgbm_tpu.ops.pallas.partition_kernel2 import make_partition_ss
    from lightgbm_tpu.ops.pallas.partition_kernel3 import \
        make_partition_perm
    from lightgbm_tpu.ops.pallas.hist_kernel2 import \
        build_histogram_comb_dyn
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split

    # measure the SHIPPING partition packing by default (permute);
    # LGBM_TPU_PARTITION=matmul A/Bs the one-hot scheme
    scheme = os.environ.get("LGBM_TPU_PARTITION", "permute")
    if scheme not in ("permute", "matmul"):
        raise ValueError(f"LGBM_TPU_PARTITION={scheme!r} "
                         "(want permute|matmul)")
    n_alloc = L + 2 * R + 2 * HIST_RPB
    # sel: [s0, cnt, feat, split_bin, default_left, is_cat, nan_bin, 0]
    sel = jnp.asarray([0, L, 3, B // 2, 1, 0, -1, 0], jnp.int32)
    nb = jnp.maximum(-(-jnp.int32(L) // R), 1)

    def hist_child(comb, nleft):
        small_left = nleft * 2 <= L
        return build_histogram_comb_dyn(
            comb, jnp.where(small_left, 0, nleft), jnp.int32(0),
            jnp.where(small_left, nleft, L - nleft), f_pad=F_PAD,
            padded_bins=B, rows_per_block=min(HIST_RPB, L),
            interpret=interpret)

    if var in ("fused", "miss"):
        from lightgbm_tpu.ops.pallas.partition_kernel import SEL_SIDE
        fused = make_fused_split(n_alloc, C, f_pad=F_PAD, padded_bins=B,
                                 R=R, size=L if interpret else 0,
                                 dynamic=True, interpret=interpret,
                                 scan=scheme)

        # the record's side: the leaf is re-split on the same column
        # every time, so the smaller child is known beforehand
        side = small_left if var == "fused" else not small_left
        sel = sel.at[SEL_SIDE].set(int(side))

        def split(comb, scratch):
            comb, scratch, nleft, h_side = fused(sel, comb, scratch, nb)
            h = jax.lax.cond((nleft * 2 <= L) != side,
                             lambda c, _: hist_child(c, nleft),
                             lambda _, h_: h_, comb, h_side)
            return comb, scratch, nleft.astype(jnp.float32) + jnp.sum(h)
    else:
        mk = (make_partition_perm if scheme == "permute"
              else make_partition_ss)
        part = mk(n_alloc, C, R=R,
                  size=L if interpret else 0,
                  dtype=jnp.float32, dynamic=True,
                  interpret=interpret)

        def split(comb, scratch):
            comb, scratch, nleft = part(sel, comb, scratch, nb)
            h = hist_child(comb, nleft)
            return comb, scratch, nleft.astype(jnp.float32) + jnp.sum(h)

    return split


def main():
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    R = int(os.environ.get("R", 512))
    reps = int(os.environ.get("REPS", 1000 if on_tpu else 2))
    sizes = [int(s) for s in os.environ.get("LS", "1024,4096").split(",")]
    if not on_tpu:
        print(f"[profile_fused] backend={jax.default_backend()}: "
              "interpret-mode functional check, timings meaningless")

    for L in sizes:
        base = {}
        for var in ("pair", "fused", "miss"):
            n_alloc = L + 2 * R + 2 * HIST_RPB
            comb, scratch = make_leaf(n_alloc, L)
            n_left = int((np.asarray(comb[:L, 3]) <= B // 2).sum())
            split = build(var, L, R, interpret, n_left * 2 <= L)

            dt, _ = bench_chain(split, comb, scratch, reps=reps)
            base[var] = dt
            print(f"L={L:6d} {var:5s}: {dt*1e6:8.1f} us/split  "
                  f"({dt/L*1e9:6.2f} ns/row)", flush=True)
        for var in ("fused", "miss"):
            red = 100.0 * (1.0 - base[var] / base["pair"])
            print(f"L={L:6d} {var} vs pair: {red:+.1f}% floor reduction",
                  flush=True)


if __name__ == "__main__":
    main()
