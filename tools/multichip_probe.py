#!/usr/bin/env python
"""Multichip flight-recorder probe: a TRACED data-parallel training run
on an n-device mesh, consolidated into one multichip bench/v3 record
(ISSUE 8 tentpole 4).

Replaces the informal dryrun scripts behind ``MULTICHIP_r*.json``
(``__graft_entry__.dryrun_multichip`` ran one step and recorded only
{n_devices, rc, ok, tail}; ``bench.py mesh_probe`` reported a bare
iters/sec): this probe trains real trees through the mesh learner with
the obs tracer live, so the record carries everything the perf gate
needs —

* the bench/v3 envelope (provenance, metric, knobs, shape block);
* the per-iteration run-ledger trajectory with one collective row per
  grow dispatch, each keyed by shard id (per-shard in-bag rows,
  per-shard analytical ICI bytes), aggregated into the ledger ``mesh``
  block's skew time series;
* a schema-additive ``multichip`` block
  (``lightgbm_tpu/multichip/v1``): mesh geometry (axes, shard count,
  device kind), the engaged learner flags (physical / hist_scatter),
  and the obs event totals (fallback events are visible in
  the artifact, not just the log).

``obs diff`` / ``tools/perf_gate.py`` compare two such records with
the mesh rules: shard-count mismatch = incomparable (exit 2),
collective bytes exact, shard-skew ratio thresholded.  Legacy
``MULTICHIP_r*.json`` artifacts are recognized by both readers with a
pointer back to this tool.

Self-provisioning: without n jax devices (single-chip host, CPU
container) the probe re-execs itself under a virtual n-device CPU
platform — the ``tests/conftest.py`` / ``dryrun_multichip`` recipe —
so CI's mesh-obs leg runs anywhere.

Usage:
    python tools/multichip_probe.py --json MC.json          # 8-way CPU
    python tools/multichip_probe.py --devices 16 --learner data
    python tools/perf_gate.py MC_BASELINE.json MC.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))

RECORD_MARK = "MULTICHIP_RECORD:"


def probe_record(n_devices: int, *, learner: str = "data",
                 rows: int = 12000, iters: int = 4, leaves: int = 15,
                 warmup: int = 2) -> dict:
    """Run the traced mesh training in THIS process (which must hold
    ``n_devices`` jax devices) and return the multichip record."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import counters as obs_counters
    from lightgbm_tpu.obs import events as obs_events
    from lightgbm_tpu.obs import ledger as obs_ledger
    from lightgbm_tpu.obs import tracer as obs_tracer
    from lightgbm_tpu.obs.metrics import MULTICHIP_SCHEMA
    from lightgbm_tpu.parallel.mesh import mesh_desc

    if not obs_tracer.enabled:
        obs_tracer.enable(None)   # in-memory: the record needs phases

    rng = np.random.default_rng(11)
    f = 20
    x = rng.normal(size=(rows, f)).astype(np.float32)
    y = (x[:, 0] - 0.6 * x[:, 1] + 0.4 * x[:, 2] * x[:, 3]
         + rng.logistic(size=rows) * 0.5 > 0).astype(np.float32)
    params = {
        "objective": "binary",
        "num_leaves": leaves,
        "learning_rate": 0.15,
        "verbosity": -1,
        "max_bin": 63,
        "tree_learner": learner,
    }
    train = lgb.Dataset(x, label=y, params={"max_bin": 63})
    bst = lgb.Booster(params=params, train_set=train)
    grower = bst._inner.grow

    def sync():
        import jax.numpy as jnp
        return float(jnp.sum(bst._inner.train_score))

    for _ in range(warmup):
        bst.update()
    bst._inner._flush_pending()
    sync()
    obs_tracer.reset()
    obs_counters.reset()
    obs_ledger.reset()
    ev0 = obs_events.totals()

    t0 = time.perf_counter()
    t_prev = t0
    for i in range(iters):
        bst.update()
        t_now = time.perf_counter()
        obs_ledger.sample(i, wall_s=t_now - t_prev)
        t_prev = t_now
    sync()
    elapsed = time.perf_counter() - t0

    from profile_lib import bench_record
    mesh = getattr(grower, "mesh", None)
    n_shards = int(getattr(grower, "num_shards", 0)
                   or getattr(grower, "num_col_shards", 1)
                   * max(getattr(grower, "num_row_shards", 1), 1))
    if n_shards != n_devices:
        # a host with MORE devices than requested meshes them all
        # (build_mesh defaults every device onto the data axis): label
        # the record by what actually ran, never by what was asked
        print(f"[multichip_probe] note: requested {n_devices} devices "
              f"but the mesh engaged {n_shards} shard(s); the record "
              "is labeled with the engaged count", file=sys.stderr)
    rec = bench_record(
        f"multichip_iters_per_sec_{learner}{n_shards}",
        round(iters / elapsed, 4), "iters/sec",
        rows=rows, iters=iters, leaves=leaves,
        knobs={
            "partition": os.environ.get("LGBM_TPU_PARTITION",
                                        "permute"),
            "fused": os.environ.get("LGBM_TPU_FUSED", "1") != "0",
            "tree_learner": learner,
        })
    inner = bst._inner
    rec["shape"] = {
        "rows": rows,
        "features": f,
        # engaged-path widths (identity here — dense probe data never
        # bundles; phys_* keeps the block honest if that changes)
        "f_pad": int(inner.dd.phys_f_pad),
        "padded_bins": int(inner.dd.phys_padded_bins),
        "bins_cols": int(inner.dd.bins.shape[1]),
        "bins_itemsize": int(inner.dd.bins.dtype.itemsize),
        "trees": iters,
        "stream": bool(getattr(inner, "_stream_grad", False)),
    }
    # engaged routing cell + digest (ISSUE 10): shard-count AND
    # path mismatches both make records incomparable in obs diff
    routing = inner.routing_info()
    if routing is not None:
        rec["routing"] = routing
    rec["traced"] = True
    rec["phases"] = obs_tracer.summary()
    rec["counters"] = obs_counters.totals()
    rec["ledger"] = obs_ledger.to_record()
    ev = {k: v - ev0.get(k, 0) for k, v in obs_events.totals().items()
          if v - ev0.get(k, 0) > 0}
    if ev:
        rec["events"] = ev
    rec["multichip"] = {
        "schema": MULTICHIP_SCHEMA,
        "mesh": (mesh_desc(mesh) if mesh is not None
                 else {"axes": {}, "n_devices": n_shards,
                       "n_shards": n_shards, "device_kind": "unknown"}),
        "n_shards": n_shards,
        "learner": learner,
        "physical": bool(getattr(grower, "physical", False)),
        "hist_scatter": bool(getattr(grower, "hist_scatter", False)),
        "events": obs_events.totals(),
    }
    return rec


def _reexec_on_cpu_mesh(n_devices: int, argv: list) -> dict:
    """Re-run this script under a virtual n-device CPU platform and
    read the record back off its stdout (the dryrun_multichip /
    conftest self-provisioning recipe)."""
    from lightgbm_tpu.utils.cpu_mesh import cpu_mesh_env
    here = os.path.abspath(__file__)
    env = cpu_mesh_env(n_devices)
    proc = subprocess.run(
        [sys.executable, here, "--inner"] + argv,
        env=env, capture_output=True, text=True, timeout=1200,
        cwd=os.path.dirname(os.path.dirname(here)))
    for line in proc.stdout.splitlines():
        if line.startswith(RECORD_MARK):
            return json.loads(line[len(RECORD_MARK):])
    sys.stderr.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    raise RuntimeError(
        f"multichip probe subprocess emitted no record "
        f"(rc={proc.returncode})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="traced mesh training -> multichip bench/v3 record")
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size (default 8; CPU-virtualized when "
                         "this host has fewer jax devices)")
    ap.add_argument("--learner", default="data",
                    choices=("data", "voting", "feature"),
                    help="tree_learner to probe (default data)")
    ap.add_argument("--rows", type=int, default=12000)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--leaves", type=int, default=15)
    ap.add_argument("--json", default="",
                    help="write the record to this path "
                         "(MULTICHIP_r*.json round artifact)")
    ap.add_argument("--inner", action="store_true",
                    help=argparse.SUPPRESS)   # subprocess re-entry
    args = ap.parse_args(argv)

    passthrough = ["--devices", str(args.devices),
                   "--learner", args.learner,
                   "--rows", str(args.rows),
                   "--iters", str(args.iters),
                   "--leaves", str(args.leaves)]

    if args.inner:
        # subprocess re-entry: pin the virtual CPU mesh BEFORE any
        # lightgbm_tpu/jax import (the conftest.py recipe — load
        # cpu_mesh by path so the package __init__ doesn't run first)
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_cpu_mesh", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(
                    __file__))), "lightgbm_tpu", "utils",
                "cpu_mesh.py"))
        cpu_mesh = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cpu_mesh)
        cpu_mesh.force_cpu_devices(args.devices)
        rec = probe_record(args.devices, learner=args.learner,
                           rows=args.rows, iters=args.iters,
                           leaves=args.leaves)
        print(RECORD_MARK + json.dumps(rec))
        return 0

    import jax
    if len(jax.devices()) >= args.devices:
        rec = probe_record(args.devices, learner=args.learner,
                           rows=args.rows, iters=args.iters,
                           leaves=args.leaves)
    else:
        rec = _reexec_on_cpu_mesh(args.devices, passthrough)

    print(json.dumps(rec))
    if args.json:
        from profile_lib import write_bench_record
        write_bench_record(args.json, rec)
        print(f"multichip record -> {args.json}", file=sys.stderr)
    mc = rec.get("multichip", {})
    print(f"[multichip_probe] {args.learner} learner over "
          f"{mc.get('n_shards')} shard(s): {rec.get('value')} "
          f"iters/sec, physical={mc.get('physical')}, "
          f"hist_scatter={mc.get('hist_scatter')}, "
          f"{len((rec.get('ledger') or {}).get('collectives', []))} "
          "collective row(s)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
