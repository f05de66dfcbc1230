"""Partition-kernel sweep: scheme x R x dtype (ISSUE 3).

Measures the single-scan partition's per-row cost for every
combination of

  * scheme:  permute (butterfly routing, O(log R)/row)  vs  matmul
             ([R, R] one-hot contraction, O(R)/row)
  * R:       block rows (the round-3b sweep put the matmul scheme's
             knee at 512; the permute scheme's block is
             partition_kernel2.scan_block_rows)
  * dtype:   f32, plus a bf16 attempt that documents the Mosaic
             (8,128)x2 dynamic-offset blocker instead of crashing.

Each record carries the DMA-bytes accounting (dma_bytes_per_logical_row
= line bytes x ~4 moves: scan read + rows/scratch writes + copyback).

Methodology: ``profile_lib.bench_chain`` — the IN-JIT fori_loop chain
whose accumulator depends on each call's ``nleft`` output, barriered by
a host value pull (docs/PERF_NOTES.md round-3b).  Each step re-partitions the
full range in place (carried rows/scratch donated), so secs/step over
``cnt`` rows is directly comparable to the 10.8 ns/row matmul baseline.

Run on chip:  ``REPS=1000 ROWS=1048576 python tools/profile_partition.py``
Off chip:     ``python tools/profile_partition.py --smoke`` (Pallas
interpreter, correctness-plumbing only — timings meaningless).
Emits one ``profile_lib.bench_record`` JSON line per point.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

import numpy as np
import jax
import jax.numpy as jnp

from profile_lib import bench_chain, bench_record
from lightgbm_tpu.ops.pallas.layout import LANE
from lightgbm_tpu.ops.pallas.partition_kernel import SEL_S0, SEL_CNT
from lightgbm_tpu.ops.pallas.partition_kernel2 import make_partition_ss
from lightgbm_tpu.ops.pallas.partition_kernel3 import make_partition_perm

C = 128


def _rows(n_alloc, dtype, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.zeros((n_alloc, LANE), np.float32)
    rows[:, :16] = rng.integers(0, 256, size=(n_alloc, 16))
    return jnp.asarray(rows).astype(dtype)


def run_point(scheme, r, dtype, n_cnt, interpret, reps):
    n_alloc = n_cnt + 2 * r + 2 * 2048
    kw = dict(R=r, size=n_cnt, dtype=dtype)
    if interpret:
        kw.update(interpret=True, interpret_kernel=True)
    mk = make_partition_perm if scheme == "permute" else make_partition_ss
    part = mk(n_alloc, C, **kw)
    rows = _rows(n_alloc, dtype)
    scratch = jnp.zeros_like(rows)
    sel = np.zeros((8,), np.int32)
    sel[SEL_S0], sel[SEL_CNT], sel[2], sel[3] = 0, n_cnt, 3, 127
    sel[6] = -1
    sel_j = jnp.asarray(sel)

    def step(rows_c, scratch_c):
        rows_n, scratch_n, nleft = part(sel_j, rows_c, scratch_c)
        return rows_n, scratch_n, nleft

    dt, _ = bench_chain(step, rows, scratch, reps=reps)
    return dt


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="Pallas interpreter, tiny shapes (plumbing "
                         "check on CPU; timings meaningless)")
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("ROWS", "1048576")))
    ap.add_argument("--reps", type=int,
                    default=int(os.environ.get("REPS", "1000")))
    ap.add_argument("--rs", default=os.environ.get("RS", "256,512,1024"),
                    help="comma-separated R candidates")
    args = ap.parse_args()

    interpret = args.smoke or jax.default_backend() != "tpu"
    n_cnt = 4096 if interpret else args.rows
    reps = 2 if interpret else args.reps
    rs = [int(x) for x in args.rs.split(",")]

    dtype = jnp.float32
    for r in rs:
        for scheme in ("matmul", "permute"):
            try:
                dt = run_point(scheme, r, dtype, n_cnt, interpret, reps)
            except Exception as e:  # noqa: BLE001 — sweep must finish
                print(json.dumps(bench_record(
                    f"partition_{scheme}_R{r}", -1.0,
                    "ns/row", error=f"{type(e).__name__}: {e}"[:200])))
                continue
            line_bytes = LANE * jnp.dtype(dtype).itemsize
            print(json.dumps(bench_record(
                f"partition_{scheme}_R{r}",
                round(dt / n_cnt * 1e9, 3), "ns/row",
                rows=n_cnt, reps=reps, secs_per_step=round(dt, 6),
                interpret=interpret,
                # bytes each row moves per line touch; the
                # scan/copyback touch every partitioned row ~4x (read,
                # rows+scratch writes, copyback), so total partition
                # DMA per row ~= 4x this
                dma_bytes_per_logical_row=line_bytes,
                dma_bytes_per_row_total=4 * line_bytes)))
    # bf16 storage: expected to fail Mosaic's (8,128)x2 dynamic-offset
    # tiling proof today (PERF_NOTES lever #1) — record the outcome so
    # the next chip run documents whether the restriction lifted
    if not interpret:
        try:
            dt = run_point("permute", rs[0], jnp.bfloat16, n_cnt,
                           False, reps)
            print(json.dumps(bench_record(
                f"partition_permute_R{rs[0]}_bf16",
                round(dt / n_cnt * 1e9, 3), "ns/row", rows=n_cnt)))
        except Exception as e:  # noqa: BLE001
            # SAME metric key as the success branch so blocked /
            # unblocked outcomes pair across chip runs in obs report
            print(json.dumps(bench_record(
                f"partition_permute_R{rs[0]}_bf16", -1.0,
                "ns/row", blocked=f"{type(e).__name__}: {e}"[:200])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
