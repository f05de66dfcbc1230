"""Static VLIW-bundle census of a Pallas kernel, off the chip.

The TPU compiler that ships with libtpu compiles for a DESCRIBED
``v5e:2x2`` under ``JAX_PLATFORMS=cpu`` (tests/test_chip_compile.py)
and, asked to, dumps the kernel's final schedule: one line a VLIW
bundle, and a table of how many slots of each unit (MXU, XLU, VALU,
vector load / store, spills among them) every bundle uses.  Bundles are
not cycles - stalls and DMA waits come on top - but a kernel bound by
what it computes in VMEM tracks them: ``lgbm_split_scan`` read 22.1k
bundles (both parity bodies) at 9.7 us a step and 11.7k at 5.9 us
(PERF.md, Findings, PR 28), and the spill stores (one store slot a
bundle) show where a [R, 1] column costs a whole block.  No chip time.

    python tools/bundle_census.py fused_permute [--ops 24]
    python tools/bundle_census.py part_perm --R 1024
    python tools/bundle_census.py fused_permute --R 1024 --cols 144
    python tools/bundle_census.py registry:fused_split_cat

Read on this tree (ISSUE 37; one step's body, spill stores in
brackets), so the next writer need not re-derive them:

    kernel          columns   R = 512         1,024            2,048
    part_perm       32        1,564 (209)     3,060 (771)      6,073 (1,672)
    fused_permute   32        5,119 (835)     9,565 (2,168)    18,594 (5,506)
    fused_permute   144       15,387 (2,851)  30,127 (5,518)   58,809 (13,200)

(At the parent the scan held a body a parity and read 3,003 / 6,009 /
12,006, 9,970 / 18,842 / 36,935 and 29,998 / 59,473 / 116,969.)  Twice
the rows, twice the bundles, within 2% in the unfused scan: the
compaction has no fixed part and the butterfly's O(log R) rounds add
nothing that shows, so what a grid step costs beyond its rows is in no
bundle (the chip reads 1.4 us of it: PERF.md, Findings, PR 37).  The
two-plane kernel at R = 2,048 is refused: its stack is 18.39 MiB and a
kernel that asks for nothing gets 16 (the compiler's own message names
both; partition_kernel2._SCAN_LINES holds ten such readings, taken by
lowering ``vmem_limit_bytes`` until the compiler refuses).

Kernels: ``fused_permute`` / ``fused_matmul`` (the ``higgs`` route's
fused scan at the Higgs width), ``part_perm`` / ``part_matmul`` (the
unfused scans), or ``registry:<name>`` for any entry of the static
analyzer's registry.  The compile runs in a child process: libtpu's
dumper aborts after the last kernel file is written (it looks for a
report template that is not shipped), and only one process may hold
libtpu at a time.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
N_ALLOC, BINS = 1_007_616, 256      # Higgs 1M, as the chip-compile tests


def _builder(kernel: str, R: int, f_pad: int):
    import jax.numpy as jnp
    from lightgbm_tpu.analysis.registry import collect, partition_args, sds
    from lightgbm_tpu.ops.pallas.layout import comb_layout
    if kernel.startswith("registry:"):
        return collect()[kernel.split(":", 1)[1]].builder()
    C = comb_layout(f_pad + 6)
    args = partition_args(N_ALLOC, C) + (sds((), jnp.int32),)
    if kernel.startswith("fused_"):
        from lightgbm_tpu.ops.pallas.fused_split import make_fused_split
        return make_fused_split(
            N_ALLOC, C, f_pad=f_pad, padded_bins=BINS, R=R, dynamic=True,
            scan=kernel.split("_", 1)[1]), args
    if kernel == "part_perm":
        from lightgbm_tpu.ops.pallas.partition_kernel3 import \
            make_partition_perm as make
    elif kernel == "part_matmul":
        from lightgbm_tpu.ops.pallas.partition_kernel2 import \
            make_partition_ss as make
    else:
        raise SystemExit(f"unknown kernel {kernel!r}")
    return make(N_ALLOC, C, R=R, dynamic=True), args


def _child(kernel: str, R: int, f_pad: int) -> None:
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    fn, args = _builder(kernel, R, f_pad)
    args = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
                 for a in args)
    jax.jit(fn).lower(*args).compile()     # may abort in the dumper


def _census(dump: str, n_ops: int) -> int:
    """Print one line a Mosaic kernel found in ``dump``; the count."""
    seen = set()
    for f in sorted(glob.glob(os.path.join(
            dump, "*-final_hlo-static-per-bundle-utilization.txt"))):
        name = re.sub(r"^\d+-(.*?)-\d+-final_hlo.*$", r"\1",
                      os.path.basename(f))
        def sibling(tag):
            return glob.glob(re.sub(
                r"-\d+-final_hlo-static-per-bundle-utilization", tag, f))
        bundles = sibling("-*-final_bundles")
        sched = sibling("-*-schedule-analysis_final_bundles")
        if (name in seen or name == "TLP" or not bundles or not sched
                or "tpu_custom_call" not in open(sched[0]).read()):
            continue                      # an XLA fusion, not a kernel
        seen.add(name)
        text = open(bundles[0]).read()
        lines = open(f).read().splitlines()
        units = [u.strip() for u in lines[1].split(",")]
        rows = [list(map(int, ln.split()))
                for ln in lines[lines.index("== UTILIZATION:") + 1:]
                if ln[:1].isdigit()]
        total = [sum(r[j] for r in rows) for j in range(len(units))]
        print(f"{name}: {len(rows)} bundles  " + "  ".join(
            f"{u}={t}" for u, t in zip(units, total)))
        if n_ops:
            ops = collections.Counter()
            for m in re.finditer(r"\{(.*)\}\s*$", text, re.M):
                for ins in m.group(1).split(";;"):
                    op = re.match(r"\s*(?:%\S+\s*=\s*)?([a-zA-Z_][\w.]*)",
                                  ins)
                    if op:
                        ops[re.sub(r"\.(xlu|mxu)\d", r".\1",
                                   op.group(1))] += 1
            print("  " + "  ".join(f"{k}:{v}"
                                   for k, v in ops.most_common(n_ops)))
    return len(seen)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kernel")
    ap.add_argument("--R", type=int, default=512, help="block rows")
    ap.add_argument("--cols", type=int, default=32,
                    help="feature columns of the comb (32: one 128-lane "
                         "plane, the Higgs width; 144: two, MS LTR's)")
    ap.add_argument("--ops", type=int, default=0,
                    help="also print the N commonest opcodes")
    ap.add_argument("--keep", help="keep the dump in this directory")
    a = ap.parse_args()
    if os.environ.get("_BUNDLE_CENSUS_CHILD"):
        _child(a.kernel, a.R, a.cols)
        return 0
    dump = a.keep or tempfile.mkdtemp(prefix="bundle_census_")
    env = dict(os.environ, _BUNDLE_CENSUS_CHILD="1", JAX_PLATFORMS="cpu",
               TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                "--xla_jf_dump_llo_text=true")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)]
                       + sys.argv[1:], env=env, capture_output=True,
                       text=True)
    found = _census(dump, a.ops)
    if not a.keep:
        shutil.rmtree(dump, ignore_errors=True)
    if not found:      # the compile itself failed: say what it said
        sys.stderr.write((p.stdout + p.stderr)[-4000:])
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
