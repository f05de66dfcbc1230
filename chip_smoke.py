"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: train + serve
    python chip_smoke.py --chips 4    four chips: data-parallel train
                                      against serial, nothing else
    python chip_smoke.py --rehearse   the same control flow on the CPU at
                                      a tiny size (interpret-mode kernels);
                                      never prints the ok line, exits 3

One process, the normal entry points (``lgb.train``, ``Booster.predict``),
data from ``--seed``.  There is no fallback: without a TPU the script
says why and exits 1 before any data is made, and a failed phase is an
uncaught exception.  The per-phase JSON lines are SMOKE numbers (13
iterations, one run) — not benchmark results.  The last line of a
passing run is the driver's contract:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Nothing here needs ``src/native/libtgb_native.so`` (git-ignored): the
binning falls back to numpy where the library is absent and cannot be
built.  This is the gate for any change under ``lightgbm_tpu/ops/pallas/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# (train rows, held-out rows, held-out rows the host tree walk re-scores)
CHIP_ROWS = (1_000_000, 100_000, 10_000)
REHEARSAL_ROWS = (50_000, 5_000, 1_000)
WARMUP_ITERS, TIMED_ITERS = 3, 10
# Held-out AUC after 13 iterations.  The CPU rehearsal of the same
# generator at 50k training rows (--rehearse, seed 0) reaches 0.7973;
# a model that learned nothing scores 0.5.
AUC_FLOOR = 0.77
# the held-out set is evaluated after every iteration (the default
# metric_freq), so the metric's own compile lands in the warm-up
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "learning_rate": 0.1, "metric": "auc", "verbosity": -1}


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def host_pull(bst) -> float:
    """Execution barrier: the train scores, summed, on the host."""
    import jax.numpy as jnp
    return float(jnp.sum(bst._inner.train_score))


def train(lgb, x, y, xv, yv, extra_params=None):
    """``lgb.train`` for 3 warm-up + 10 timed iterations.  Returns
    (booster, record): the clock starts after the warm-up's host pull and
    stops after the last iteration's.  Every iteration also replays the
    new tree over the held-out rows and evaluates them, as ``valid_sets``
    asks; as of PR 22 that, not the tree, is most of an iteration on the
    chip (PERF.md, ROADMAP A10)."""
    params = {**PARAMS, **(extra_params or {})}
    t0 = time.perf_counter()
    dtrain = lgb.Dataset(x, label=y, params={"max_bin": PARAMS["max_bin"]})
    dvalid = lgb.Dataset(xv, label=yv, reference=dtrain)
    dtrain.construct()
    dvalid.construct()
    marks = {"start": time.perf_counter()}

    def clock(env):
        if env.iteration + 1 in (WARMUP_ITERS, WARMUP_ITERS + TIMED_ITERS):
            host_pull(env.model)
            marks[env.iteration + 1] = time.perf_counter()

    evals = {}
    bst = lgb.train(params, dtrain,
                    num_boost_round=WARMUP_ITERS + TIMED_ITERS,
                    valid_sets=[dvalid],
                    callbacks=[lgb.record_evaluation(evals), clock])
    warm, end = marks[WARMUP_ITERS], marks[WARMUP_ITERS + TIMED_ITERS]
    return bst, {
        "bin_s": round(marks["start"] - t0, 3),
        # first three iterations: device layout + every compile
        "compile_s": round(warm - marks["start"], 3),
        "iters_per_sec": TIMED_ITERS / (end - warm),
        "auc": float(evals["valid_0"]["auc"][-1]),
        "num_trees": bst.num_trees(),
    }


def check_no_fallback_events(events0, obs_events) -> None:
    fell = sorted(k for k, v in obs_events.totals().items()
                  if k.startswith("routing_fallback_")
                  and v != events0.get(k, 0))
    if fell:
        raise AssertionError(f"routing fallbacks fired: {fell}")


def check_auc(auc: float) -> None:
    if not auc >= AUC_FLOOR:
        raise AssertionError(
            f"held-out AUC {auc:.4f} is under the {AUC_FLOOR} floor")


def tree_structure(bst, n_trees: int):
    return [(t.num_leaves, t.split_feature[:t.num_leaves - 1].tolist(),
             t.threshold_bin[:t.num_leaves - 1].tolist())
            for t in bst._models[:n_trees]]


def phase_train(lgb, data, device_kind: str):
    from lightgbm_tpu.obs import events as obs_events
    events0 = obs_events.totals()
    bst, rec = train(lgb, *data)
    r = bst._inner._routing
    print(f"routing digest {r.digest()}: {json.dumps(r.to_json())}",
          flush=True)
    got = (r.path, r.fused, r.scheme, r.pack, r.learner, r.reasons)
    want = ("stream", True, "permute", 1, "serial", ())
    if got != want:
        raise AssertionError(f"train route {got}, expected {want}")
    check_no_fallback_events(events0, obs_events)
    check_auc(rec["auc"])
    emit(phase="train", smoke=True, **rec, route=r.digest(),
         rows=len(data[1]), held_out_rows=len(data[3]),
         device_kind=device_kind)
    return bst


def phase_serve(bst, xv, ref_rows: int, device_kind: str) -> None:
    import numpy as np

    from lightgbm_tpu.ops import routing
    d = bst._predict_route(routing, bst._models, pred_leaf=False,
                           pred_contrib=False, early_stop=False)
    if (d.path, d.kernel) != ("compiled", False):
        raise AssertionError(
            f"predict route path={d.path} kernel={d.kernel} "
            f"({d.reasons}, {d.kernel_reasons}); expected the compiled "
            "XLA gather walk")
    walls = []
    for _ in ("cold", "warm"):
        t0 = time.perf_counter()
        raw = bst.predict(xv, raw_score=True)
        walls.append(time.perf_counter() - t0)
    stats = bst.serving_engine().stats()
    if stats["kernel"] != "" or not stats["dispatches"]:
        raise AssertionError(f"serving engine did not dispatch the "
                             f"gather walk: {stats}")
    # the plain reference: the host tree walk (what LGBM_TPU_SERVE=0
    # runs), called directly
    x_ref = np.asarray(xv[:ref_rows], np.float64)
    ref = sum(t.predict(x_ref) for t in bst._models)
    diff = float(np.max(np.abs(raw[:ref_rows] - ref)))
    if raw.shape != (len(xv),) or not np.all(np.isfinite(raw)) \
            or not diff <= 1e-5:
        raise AssertionError(
            f"compiled predict disagrees with the host tree walk: shape "
            f"{raw.shape}, max abs diff {diff} over {ref_rows} rows")
    emit(phase="serve", smoke=True, rows=len(xv),
         cold_s=round(walls[0], 3), rows_per_sec=len(xv) / walls[1],
         max_abs_diff_vs_host_walk=diff, programs=stats["programs"],
         device_kind=device_kind)


def phase_four_chips(lgb, data, device_kind: str) -> None:
    """``tree_learner=data`` over the four-chip mesh against serial
    training on ``jax.devices()[0]``, same data, same process."""
    import jax

    from lightgbm_tpu.obs import events as obs_events
    events0 = obs_events.totals()
    mesh_bst, mesh_rec = train(lgb, *data, {"tree_learner": "data"})
    r = mesh_bst._inner._routing
    print(f"routing digest {r.digest()}: {json.dumps(r.to_json())}",
          flush=True)
    # benchmarks/configs/higgs-data4.json's expect_route
    got = (r.learner, r.n_shards, r.path, r.hist_merge, r.reasons)
    want = ("data", 4, "physical", "scatter", ("mesh_stream_unwired",))
    if got != want:
        raise AssertionError(f"mesh route {got}, expected {want}")
    check_no_fallback_events(events0, obs_events)
    # what a traced run's Tree::grow span says of the mesh, for the
    # last tree: the grow program's own per-shard row counts among it
    emit(phase="mesh_tree", smoke=True,
         **mesh_bst._inner.grow.tree_span_args(
             mesh_bst._models[-1].num_leaves - 1))
    # code that has only seen one chip may put everything on device 0
    shards = mesh_bst._inner.grow._comb.addressable_shards
    rows = {str(s.device): s.data.shape[0] for s in shards}
    share = len(data[1]) / 4
    if sorted(rows) != sorted(str(d) for d in jax.devices()) or any(
            not share <= n <= share + 8192 for n in rows.values()):
        raise AssertionError(
            f"comb is not ~{share:.0f} rows on each of 4 devices: {rows}")
    check_auc(mesh_rec["auc"])
    emit(phase="train_data_parallel", smoke=True, **mesh_rec,
         route=r.digest(), rows=len(data[1]), shard_rows=rows,
         device_kind=device_kind)

    ser_bst, ser_rec = train(lgb, *data)
    if ser_bst._inner._routing.learner != "serial":
        raise AssertionError("the comparison did not train serial")
    emit(phase="train_serial", smoke=True, **ser_rec,
         route=ser_bst._inner._routing.digest(), rows=len(data[1]),
         device=str(jax.devices()[0]), device_kind=device_kind)
    same = tree_structure(mesh_bst, 3) == tree_structure(ser_bst, 3)
    auc_gap = abs(mesh_rec["auc"] - ser_rec["auc"])
    if not (same or auc_gap <= 1e-3):
        raise AssertionError(
            f"data-parallel and serial disagree: first 3 trees differ "
            f"and held-out AUC differs by {auc_gap}")
    emit(phase="compare", smoke=True, first_3_trees_identical=same,
         auc_gap=auc_gap)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        # the chip's routes on the CPU: Pallas kernels in interpret mode,
        # compiled serving on a non-TPU backend
        os.environ["LGBM_TPU_PHYS"] = "interpret"
        os.environ["LGBM_TPU_SERVE"] = "1"
    n_train, n_held, n_ref = REHEARSAL_ROWS if args.rehearse else CHIP_ROWS

    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {device}); there "
              "is no CPU fallback — see --rehearse", file=sys.stderr)
        return 1
    if len(dev) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly that many "
              f"devices, JAX reports {len(dev)}", file=sys.stderr)
        return 1

    import bench
    import lightgbm_tpu as lgb

    # ONE draw: the generator's hidden weights come from the seed, so
    # train and held-out rows must share it
    x, y = bench.make_higgs_like(n_train + n_held, seed=args.seed)
    data = (x[:n_train], y[:n_train], x[n_train:], y[n_train:])
    if args.chips == 4:
        phase_four_chips(lgb, data, device["kind"])
    else:
        bst = phase_train(lgb, data, device["kind"])
        phase_serve(bst, data[2], n_ref, device["kind"])
    if args.rehearse:
        print("chip_smoke: rehearsal on "
              f"{device['platform']} finished; not a chip run",
              file=sys.stderr)
        return 3
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
