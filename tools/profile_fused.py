"""Per-split cost of the ways to the smaller child's histogram: the
separate partition+hist pair, the fused scan's hook, and the fused scan
with its hook skipped + the comb-direct kernel.

Reproduces the ISSUE-1 claim that fusing the single-scan partition with
the child-histogram accumulation cuts the per-split floor at small
leaves (~120 us for the pair at 1k rows; docs/PERF_NOTES.md "Next
levers" #3), and makes the table ``fused_split.hook_crossover_rows``
takes its constants from (ISSUE 35).  Each variant runs ONE split of an
L-row leaf per iteration of an in-jit fori_loop whose accumulator
depends on the kernel outputs (nleft + histogram sum), barriered by a
HOST VALUE PULL (PERF_NOTES "round 3b" methodology).  The split is
close to even, so the smaller child is half the parent: the worst case
of every variant that reads the child's rows again.

  pair   — make_partition_ss + build_histogram_comb_dyn of the smaller
           child: the unfused production path's two pallas_call entries
  fused  — what ops/grow.py runs at a split under the crossover whose
           record named the smaller child (ISSUE 30): make_fused_split,
           one scan that histograms that child from the VMEM-resident
           blocks, and a lax.cond that has nothing to do
  miss   — the same at a split whose record named the LARGER child:
           the cond histograms the smaller one from the comb
  direct — what ops/grow.py runs at a split past the crossover (ISSUE
           35): the same kernel told SIDE_NONE, its hook skipped, and
           the cond histograms the smaller child from the comb
  scan   — ``direct`` without the histogram: the scan with its hook
           skipped and the copy-back, nothing else.  What a grid step
           costs is read from this one (ISSUE 37)

Env: WIDTH=32,144 (feature columns: 32 on one 128-lane plane, the
``higgs`` layout; 144 on two, ``msltr-lambdarank``'s), LS=1024,4096
(leaf-row sweep), REPS (in-jit splits per timing; default 4e8 / L
clipped to [100, 4000]: keep the timed loop well over the ~20-50 ms
dispatch floor), R=512,1024,2048 (partition block rows: a sweep),
VARS=pair,fused,miss,direct,scan (the variants to run).  The last
lines fit fixed + rows x slope to ``fused`` and ``direct`` over the
leaf sweep and print the crossover at each R, and - over an R sweep -
fit ``step_us = F + R x c`` to the row slopes of ``scan`` (``direct``
where ``scan`` was not run): F is what a grid step costs whatever it
moves, c what a row costs.  A kernel the chip's compiler refuses (a
block past the scoped VMEM) prints ``does not compile`` and the sweep
goes on.  Off-TPU the kernels run in interpret mode with tiny REPS — a
functional check only, not a timing.

Read on the v5e (PERF.md, Findings, PR 37: the table and the chip call)
before ``fused_split.scan_block_rows`` took R from the comb's width.
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

B = 256             # 255 bins + pad
HIST_RPB = 2048
FEAT = 3            # the split column


def make_leaf(n_alloc: int, L: int, f_pad: int, seed: int = 0):
    """Plane-major comb holding one leaf: bins at cols [0, f_pad),
    (g, h) at [f_pad, f_pad + 2), rows [0, L) valid; made on the
    device a plane at a time (a 4M-row leaf of two planes is 4.3e9
    bytes, and its scratch as much again).  Returns (comb, scratch,
    rows of the leaf that go left)."""
    from lightgbm_tpu.ops.pallas.layout import LANE, comb_layout
    C = comb_layout(f_pad + 6)
    live = (jnp.arange(n_alloc) < L)[:, None]
    n_left, planes = 0, []
    for p, key in enumerate(jax.random.split(jax.random.PRNGKey(seed),
                                             C // LANE)):
        kb, kv = jax.random.split(key)
        lo = p * LANE
        nb = min(max(f_pad - lo, 0), LANE)       # bin columns here
        cols = [jax.random.randint(kb, (n_alloc, nb), 0, B).astype(
            jnp.float32)]
        if lo <= FEAT < lo + nb:
            n_left = int(jnp.sum(
                live[:, 0] & (cols[0][:, FEAT - lo] <= B // 2)))
        if lo <= f_pad < lo + LANE:              # the (g, h) pair
            v = jax.random.normal(kv, (n_alloc, 2))
            cols.append(v.at[:, 1].set(jnp.abs(v[:, 1]) + 0.1))
        width = sum(c.shape[1] for c in cols)
        cols.append(jnp.zeros((n_alloc, LANE - width), jnp.float32))
        planes.append(jnp.where(live, jnp.concatenate(cols, axis=1), 0.0))
    comb = jnp.concatenate(planes, axis=0)
    del planes, cols
    return comb, jnp.zeros_like(comb), n_left


def build(var: str, L: int, R: int, interpret: bool, small_left: bool,
          f_pad: int):
    from lightgbm_tpu.ops.pallas.partition_kernel2 import make_partition_ss
    from lightgbm_tpu.ops.pallas.partition_kernel3 import \
        make_partition_perm
    from lightgbm_tpu.ops.pallas.hist_kernel2 import \
        build_histogram_comb_dyn
    from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
    from lightgbm_tpu.ops.pallas.layout import comb_layout, comb_planes
    from lightgbm_tpu.ops.pallas.partition_kernel import (
        SEL_SIDE, SIDE_LEFT, SIDE_NONE, SIDE_RIGHT)
    C = comb_layout(f_pad + 6)

    # measure the SHIPPING partition packing by default (permute);
    # LGBM_TPU_PARTITION=matmul A/Bs the one-hot scheme
    scheme = os.environ.get("LGBM_TPU_PARTITION", "permute")
    if scheme not in ("permute", "matmul"):
        raise ValueError(f"LGBM_TPU_PARTITION={scheme!r} "
                         "(want permute|matmul)")
    n_alloc = L + 2 * R + 2 * HIST_RPB
    # sel: [s0, cnt, feat, split_bin, default_left, is_cat, nan_bin, 0]
    sel = jnp.asarray([0, L, FEAT, B // 2, 1, 0, -1, 0], jnp.int32)
    nb = jnp.maximum(-(-jnp.int32(L) // R), 1)

    def hist_child(comb, nleft):
        small_left = nleft * 2 <= L
        return build_histogram_comb_dyn(
            comb, jnp.where(small_left, 0, nleft), jnp.int32(0),
            jnp.where(small_left, nleft, L - nleft), f_pad=f_pad,
            padded_bins=B, rows_per_block=min(HIST_RPB, L),
            interpret=interpret, planes=comb_planes(C))

    if var in ("fused", "miss", "direct", "scan"):
        from lightgbm_tpu.ops.pallas.fused_split import hook_histogram
        # the compiled kernel hands on its raw accumulator, as in
        # ops/grow.py: the extraction is paid in the branch that reads it
        fused = make_fused_split(n_alloc, C, f_pad=f_pad, padded_bins=B,
                                 R=R, size=L if interpret else 0,
                                 dynamic=True, interpret=interpret,
                                 scan=scheme, raw_hist=not interpret)
        extract = (lambda h: h) if interpret else (
            lambda acc: hook_histogram(acc, f_pad, B))

        # the record's side: the leaf is re-split on the same column
        # every time, so the smaller child is known beforehand
        side = small_left if var == "fused" else not small_left
        direct = var in ("direct", "scan")
        sel = sel.at[SEL_SIDE].set(
            SIDE_NONE if direct else SIDE_LEFT if side else SIDE_RIGHT)

        def split(comb, scratch):
            comb, scratch, nleft, h_side = fused(sel, comb, scratch, nb)
            if var == "scan":
                # the accumulator is all zeros; summing it keeps the
                # kernel's fourth output alive at no row-sized cost
                return (comb, scratch,
                        nleft.astype(jnp.float32) + jnp.sum(h_side))
            h = jax.lax.cond(direct | ((nleft * 2 <= L) != side),
                             lambda c, _: hist_child(c, nleft),
                             lambda _, h_: extract(h_), comb, h_side)
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


def _fit(points):
    """Least squares of secs = fixed + rows x slope over (rows, secs)."""
    x = np.array([p[0] for p in points], np.float64)
    y = np.array([p[1] for p in points], np.float64)
    slope, fixed = np.polyfit(x, y, 1, w=1.0 / y)   # relative residuals
    return fixed, slope


def _step_fit(slopes):
    """Least squares of ns_per_row = F / R + c over {R: ns a row}:
    (F in us a step, c in ns a row)."""
    inv = np.array([1.0 / r for r in slopes], np.float64)
    y = np.array(list(slopes.values()), np.float64)
    f_ns, c = np.polyfit(inv, y, 1)
    return f_ns * 1e-3, c


def main():
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    blocks = [int(r) for r in os.environ.get("R", "512").split(",")]
    sizes = [int(s) for s in os.environ.get("LS", "1024,4096").split(",")]
    widths = [int(w) for w in os.environ.get("WIDTH", "32").split(",")]
    variants = os.environ.get("VARS", "pair,fused,miss,direct").split(",")
    if not on_tpu:
        print(f"[profile_fused] backend={jax.default_backend()}: "
              "interpret-mode functional check, timings meaningless")

    for f_pad in widths:
        slopes = {}             # R -> ns a parent row of the scan
        for R in blocks:
            curves, refused = {}, False
            for L in sizes:
                if refused:         # no size of this kernel will build
                    break
                reps = int(os.environ.get(
                    "REPS",
                    min(max(int(4e8 / L), 100), 4000) if on_tpu else 2))
                base = {}
                for var in variants:
                    n_alloc = L + 2 * R + 2 * HIST_RPB
                    comb, scratch, n_left = make_leaf(n_alloc, L, f_pad)
                    split = build(var, L, R, interpret, n_left * 2 <= L,
                                  f_pad)
                    try:
                        dt, _ = bench_chain(split, comb, scratch, reps=reps)
                    except Exception as e:      # the compiler's refusal
                        print(f"W={f_pad:3d} R={R:4d} L={L:7d} {var:6s}: "
                              f"does not compile ({type(e).__name__}: "
                              f"{str(e)[:200]!r})", flush=True)
                        refused = True
                        break
                    finally:
                        del comb, scratch
                    base[var] = dt
                    curves.setdefault(var, []).append((L, dt))
                    print(f"W={f_pad:3d} R={R:4d} L={L:7d} {var:6s}: "
                          f"{dt*1e6:9.1f} us/split  ({dt/L*1e9:6.2f} ns/row)"
                          f"  reps={reps}", flush=True)
                for var in ("fused", "miss", "direct"):
                    if var in base and "pair" in base:
                        red = 100.0 * (1.0 - base[var] / base["pair"])
                        print(f"W={f_pad:3d} R={R:4d} L={L:7d} {var} vs "
                              f"pair: {red:+.1f}% floor reduction",
                              flush=True)
            fits = {v: _fit(c) for v, c in curves.items() if len(c) >= 2}
            for v, (fx, sl) in fits.items():
                print(f"W={f_pad:3d} R={R:4d} fit: {v} {fx*1e6:.2f} us + "
                      f"{sl*1e9:.3f} ns/row = {sl*1e9*R*1e-3:.3f} us a "
                      f"{R}-row step", flush=True)
            if "fused" in fits and "direct" in fits:
                (fh, sh), (fd, sd) = fits["fused"], fits["direct"]
                gap_us, gap_ns = (fd - fh) * 1e6, (sh - sd) * 1e9
                cross = gap_us * 1e3 / gap_ns if gap_ns > 0 else float("inf")
                print(f"W={f_pad:3d} R={R:4d} direct's fixed gap "
                      f"{gap_us:.2f} us, the hook's row gap {gap_ns:.3f} "
                      f"ns: crossover at {cross:.0f} rows", flush=True)
            of = "scan" if "scan" in fits else "direct"
            if of in fits:
                slopes[R] = fits[of][1] * 1e9
        if len(slopes) >= 2:
            F, c = _step_fit(slopes)
            print(f"W={f_pad:3d} step fit over R={sorted(slopes)} from "
                  f"`{of}`: step_us = {F:.3f} + R x {c:.4f}e-3  (F us a "
                  f"grid step; c ns a parent row, the copy-back's "
                  + ("" if of == "scan" else "and the child histogram's ")
                  + "share included)", flush=True)


if __name__ == "__main__":
    main()
