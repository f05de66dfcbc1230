#!/usr/bin/env bash
# Tier-1 CI with the fallback-path leg (ISSUE 3 satellite).
#
# Leg 1 runs the ROADMAP tier-1 command verbatim (default shipping
# knobs: fused split kernel on, permute partition packing).
# Leg 2 re-runs the partition-sensitive suites with the FALLBACK knobs
# (LGBM_TPU_FUSED=0, LGBM_TPU_PARTITION=matmul) so the bisection paths
# cannot silently rot: the matmul packing and the separate
# partition/histogram kernel pair stay trained-and-equivalent even
# though the defaults no longer exercise them.
# (Leg 3 went with the two-rows-a-line comb layout it tested; the leg
# numbers below keep their names.)
# Leg 4 (obs, ISSUE 5) captures a 2-iteration traced bench record and
# runs the perf-regression gate against it: the self-diff must pass
# exactly (counters exact, walls identical), and a synthetically
# injected 2x phase regression MUST be flagged — proving the gate that
# will judge the next chip run actually detects regressions.
# Leg 5 (attr, ISSUE 6) pins device-time kernel attribution: `obs attr`
# on the checked-in synthetic xplane fixture must produce the EXACT
# per-kernel device-time/bytes/GB-s table (pure-python decoder, zero
# optional deps), and the defined failure modes — empty capture dir,
# capture with no TPU plane, truncated .pb — must exit 2/1/2 with a
# clear message, never a traceback.
# Leg 6 (lint, ISSUE 7) runs the static kernel-contract analyzer
# (python -m lightgbm_tpu.analysis): a clean --strict run over every
# registered kernel entrypoint must exit 0, and the red-team fixtures
# (an injected 64-lane lane-contract violation, an injected unpaired
# DMA start) must each exit NONZERO — the analyzer that gates the
# next chip run's kernels is itself gated against going blind.
# Trace-only: the leg needs no device and runs under JAX_PLATFORMS=cpu.
# Leg 7 (mesh-obs, ISSUE 8) exercises the mesh flight recorder: a
# traced 8-CPU-mesh training via tools/multichip_probe.py must produce
# a multichip bench/v3 record (per-shard ledger rows, skew series,
# multichip block) whose self-diff passes, while an injected 2x
# per-shard skew and a mutated collective byte count are each flagged
# by tools/perf_gate.py; legacy MULTICHIP_r*.json artifacts must be
# read with a clear fallback message, and the pinned `obs collectives`
# fixture table (measured-vs-predicted ICI join) must match exactly.
#
# Leg 8 (mem, ISSUE 9) exercises the HBM flight recorder: a traced
# bench record must carry the memory block (predicted footprint +
# measured residency peaks) and pass `obs mem` cleanly; the pinned
# `obs mem` table on the checked-in fixture record must match exactly;
# an injected 2x residency-peak regression MUST fail tools/perf_gate.py
# and the dropped-donation red-team fixture MUST fail the analyzer's
# hbm-budget pass; the 100M-row geometry must be flagged unpaged and
# accepted with the planner's page schedule; legacy records degrade
# with a clear message, never a traceback.
#
# Leg 9 (routing, ISSUE 10) pins the program-space auditor: a clean
# `--passes routing --strict` run over the full config x env-knob x
# shape lattice must exit 0 (golden routing matrix current, every
# row_order cell justified, recompile audit green), the red-team
# fixtures bad_route (fast-path-eligible cell routed to row_order
# with no reason) and bad_retrace (shape-dependent constant baked
# into a jitted body) must each exit NONZERO, a hand-mutated golden
# matrix cell must fail, and `obs diff` on two records with
# mismatched routing digests must exit 2 (incomparable).
#
# Leg 10 (chiprun, ISSUE 11) pins the chip-run autopilot: `obs
# doctor` must exit 0 with a CLEAN verdict on the CPU backend while
# the checked-in BENCH_r03 bring-up log fixture must FAIL it,
# classified as the TPU-env-bringup class (the regression that
# motivated ROADMAP item 1); `chip_run.py --dry-run` must execute the
# full checked-in plan end to end (every step journaled
# executed-or-validated with a named reason, consolidated report
# written, exit 0); a killed-then-resumed dry run must produce ONE
# merged journal with the completed doctor step skipped by digest;
# and the pinned `obs trend` table over the synthetic trajectory
# fixtures must match exactly (exit 1: the fixture carries an
# injected drift the view must flag).
#
# Leg 11 (efb, ISSUE 12) pins the EFB graduation: a clean strict
# routing run over the REGENERATED matrix (the efb_bundle rule is
# deleted — bundled columns unbundle onto the physical fast path at
# comb ingest), the bundled-vs-unbundled bit-parity matrix
# (tests/test_efb_physical.py: byte-identical trees across width x
# serial/mesh through the real kernel bodies), a hand-mutated EFB
# matrix cell must fail at cell level, and the efb_overwide red-team
# fixture (the over-wide rule claimed without the over-wide shape
# fact) must fail — re-opening the graduated 0.04x class silently is
# un-reintroducible.
#
# Leg 12 (faults, ISSUE 13) pins fault-tolerant training on CPU: a
# clean run writes ckpt/v1 snapshots and a second invocation resumes
# them; each injected fault class (death = real SIGKILL, NaN-poisoned
# gradients, simulated RESOURCE_EXHAUSTED, simulated collective
# timeout) must classify into its faultreport/v1 class and either
# recover from the last checkpoint (exit 0, the death class by the
# NEXT process resuming) or degrade loudly (exit 1 classified, exit 2
# for a corrupt checkpoint) — never a raw traceback.
#
# Leg 16 (serve-obs, ISSUE 17) pins the serving flight recorder: the
# obs serve table over the checked-in synthetic servemetrics fixture
# is byte-exact (exit 1 on its injected retrace), a fresh
# LGBM_TPU_SERVE_METRICS bench run emits a clean digest-segmented
# window stream (0 retraces => exit 0) and the bench record carries
# the p999/padding-waste fields, the perf gate passes a self-diff but
# fails an injected 2x p999 tail, and truncated/legacy JSONL exits 2
# with no traceback.
#
# Leg 17 (serve-kernel, ISSUE 18) pins the VMEM-resident Pallas
# serving traversal: the kernel parity suite runs with the interpret
# seam FORCED (LGBM_TPU_SERVE_INTERP=kernel — leaf-index-exact vs
# both the gather walk and the host reference, retrace pin, donation
# aliasing, serving_kernel_bytes equality), the analyzer stays
# --strict over the registered serve_traverse entry (lane/vmem/hbm
# donation + the predict-cell kernel audit), the bad_serve_kernel
# red-team fixture (64-lane HBM node lines) MUST fail lane-contract,
# and a golden predict cell hand-mutated to kernel=0 with no
# justifying rule MUST fail the routing pass at cell level.
#
# Leg 18 (multiclass, ISSUE 19) pins the batched multiclass grow
# path: the parity suite runs with its slow cells FORCED (batched
# trees byte-identical to serial-K across partition/fused/
# learner cells, feature-fraction RNG alignment, class_need_train
# gating, per-class NumericsSkip), the analyzer stays --strict over
# the registered grow_physical_mc entry, the bad_mc_batch red-team
# fixture (64-lane per-class HBM hist slices + a serial-K multi
# cell) MUST fail both lane-contract and routing, a golden multi
# cell hand-mutated to mcb=0 with no justifying mc_batch rule MUST
# fail the routing pass at cell level, and the obs ledger must show
# exactly ONE grow dispatch per iteration at K=4 (vs K per
# iteration with the knob off).
#
# Leg 19 (pulse, ISSUE 20) pins the live pulse telemetry path: the
# checked-in multi-role fixture (tests/data/pulse_r01) renders
# byte-exactly through both obs watch (all four finding classes at
# the pinned clock, exit 1) and obs timeline (7 sources merged into
# one monotonic view, exit 0), a fresh pulse-on training run streams
# heartbeats plus a terminal end event and watches CLEAN under the
# default thresholds, an injected mid-training hang
# (LGBM_TPU_FAULT=hang@3, unrecoverable) leaves a silent tail that
# MUST be flagged STALLED with the same collective_timeout class
# faults.py assigns the hang, and a stream truncated by a foreign
# writer is a named exit-2 usage error with no traceback.
#
# Usage: bash tools/ci_tier1.sh            (all legs)
#        bash tools/ci_tier1.sh --fallback (leg 2 only, ~2 min)
#        bash tools/ci_tier1.sh --obs      (leg 4 only, ~1 min)
#        bash tools/ci_tier1.sh --attr     (leg 5 only, ~10 s)
#        bash tools/ci_tier1.sh --lint     (leg 6 only, ~30 s)
#        bash tools/ci_tier1.sh --mesh-obs (leg 7 only, ~2 min)
#        bash tools/ci_tier1.sh --mem      (leg 8 only, ~1 min)
#        bash tools/ci_tier1.sh --routing  (leg 9 only, ~1 min)
#        bash tools/ci_tier1.sh --chiprun  (leg 10 only, ~1 min)
#        bash tools/ci_tier1.sh --efb      (leg 11 only, ~2 min)
#        bash tools/ci_tier1.sh --faults   (leg 12 only, ~2 min)
#        bash tools/ci_tier1.sh --serve    (leg 13 only, ~2 min)
#        bash tools/ci_tier1.sh --paged    (leg 14 only, ~3 min)
#        bash tools/ci_tier1.sh --cat      (leg 15 only, ~8 min)
#        bash tools/ci_tier1.sh --serve-obs (leg 16 only, ~2 min)
#        bash tools/ci_tier1.sh --serve-kernel (leg 17 only, ~2 min)
#        bash tools/ci_tier1.sh --multiclass (leg 18 only, ~4 min)
#        bash tools/ci_tier1.sh --pulse    (leg 19 only, ~2 min)
set -o pipefail
cd "$(dirname "$0")/.."

fallback_leg() {
    echo "=== tier-1 leg 2: fallback paths (LGBM_TPU_FUSED=0" \
         "LGBM_TPU_PARTITION=matmul) ==="
    env -u LGBM_TPU_PART_INTERP \
        JAX_PLATFORMS=cpu LGBM_TPU_FUSED=0 LGBM_TPU_PARTITION=matmul \
        timeout -k 10 600 python -m pytest \
        tests/test_fused.py tests/test_physical.py \
        tests/test_partition_perm.py \
        -q -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
}

obs_leg() {
    echo "=== tier-1 leg 4: obs ledger + perf-regression gate ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # 2-iteration traced smoke train -> a bench/v3 record with phases,
    # counters and the per-iteration ledger trajectory
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        JAX_PLATFORMS=cpu LGBM_TPU_TRACE="$tmp/trace.jsonl" \
        timeout -k 10 300 python bench.py --smoke --rows 4096 \
        --iters 2 --leaves 15 --json "$tmp/a.json" > /dev/null \
        || { echo "obs leg: traced bench capture failed"; return 1; }
    # gate 1: the record diffed against ITSELF must pass exactly
    # (counters exact-match, walls identical)
    python tools/perf_gate.py "$tmp/a.json" "$tmp/a.json" \
        || { echo "obs leg: self-diff failed"; return 1; }
    # gate 2: inject a 2x regression into the largest phase (summary
    # AND ledger trajectory) — the gate MUST flag it
    python - "$tmp/a.json" "$tmp/b.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
phases = rec.get("phases") or {}
if not phases:
    sys.exit("obs leg: traced record has no phases block")
name = max(phases, key=lambda n: phases[n].get("total_s", 0.0))
phases[name]["total_s"] *= 2.0
phases[name]["mean_s"] = phases[name]["mean_s"] * 2.0
for row in (rec.get("ledger") or {}).get("iterations", []):
    if name in row.get("phases", {}):
        row["phases"][name] *= 2.0
print(f"obs leg: injected 2x regression into phase {name!r}")
json.dump(rec, open(sys.argv[2], "w"))
PYEOF
    [ $? -eq 0 ] || { echo "obs leg: injection failed"; return 1; }
    if python tools/perf_gate.py "$tmp/a.json" "$tmp/b.json"; then
        echo "obs leg FAIL: injected 2x phase regression was NOT flagged"
        return 1
    fi
    echo "obs leg: self-diff clean, injected regression flagged"
    return 0
}

attr_leg() {
    echo "=== tier-1 leg 5: device-time kernel attribution (obs attr) ==="
    local tmp rc
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # gate 1: the checked-in synthetic fixture must render the EXACT
    # attribution table (decoder -> classifier -> cost-model join ->
    # phase overhead), with the pure-python decoder forced
    # stderr kept OUT of the byte-compared output: jax import-time
    # noise (absl/libtpu lines on chip hosts) must not fail the diff
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs attr \
        tests/data/synthetic.xplane.pb \
        --bench tests/data/synthetic_bench.json --roofline --no-tf \
        > "$tmp/attr.out" 2> "$tmp/attr.err"
    rc=$?
    if [ $rc -ne 0 ]; then
        echo "attr leg: obs attr exited $rc on the fixture"
        cat "$tmp/attr.out" "$tmp/attr.err"
        return 1
    fi
    if ! diff -u tests/data/synthetic_attr_expected.txt "$tmp/attr.out"
    then
        echo "attr leg: fixture table drifted from" \
             "tests/data/synthetic_attr_expected.txt (regenerate with" \
             "python -m lightgbm_tpu.obs.xattr + rerun attr if the" \
             "change is intended)"
        return 1
    fi
    # gate 2: defined failure modes, defined exit codes, no tracebacks
    mkdir -p "$tmp/empty"
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs attr "$tmp/empty" \
        > "$tmp/empty.out" 2>&1
    [ $? -eq 2 ] || { echo "attr leg: empty capture dir must exit 2"; \
                      cat "$tmp/empty.out"; return 1; }
    env JAX_PLATFORMS=cpu python - "$tmp/host.xplane.pb" <<'PYEOF'
import sys
from lightgbm_tpu.obs import xattr
space = xattr.synthetic_xspace(device_planes=0, with_host_plane=True)
open(sys.argv[1], "wb").write(xattr.encode_xspace(space))
PYEOF
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs attr \
        "$tmp/host.xplane.pb" > "$tmp/host.out" 2>&1
    [ $? -eq 1 ] || { echo "attr leg: no-TPU-plane capture must exit 1"; \
                      cat "$tmp/host.out"; return 1; }
    head -c 100 tests/data/synthetic.xplane.pb > "$tmp/trunc.xplane.pb"
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs attr \
        "$tmp/trunc.xplane.pb" > "$tmp/trunc.out" 2>&1
    [ $? -eq 2 ] || { echo "attr leg: truncated .pb must exit 2"; \
                      cat "$tmp/trunc.out"; return 1; }
    if grep -q "Traceback" "$tmp/empty.out" "$tmp/host.out" \
        "$tmp/trunc.out"; then
        echo "attr leg: a failure mode printed a traceback"
        return 1
    fi
    echo "attr leg: exact fixture table + 3 failure modes clean"
    return 0
}

lint_leg() {
    echo "=== tier-1 leg 6: static kernel-contract analyzer ==="
    # knobs unset: the analyzer registers the SHIPPING kernel builds
    # gate 1: the repo itself must be clean (post-fix / allowlisted),
    # warnings included (--strict)
    # -u the VMEM knobs too: a leftover LGBM_TPU_VMEM_LIMIT_MB sweep
    # export (PERF_NOTES round 10) would either fail every kernel or
    # silently raise the budget this gate exists to pin
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        -u LGBM_TPU_VMEM_GEN -u LGBM_TPU_VMEM_LIMIT_MB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --strict \
        || { echo "lint leg: clean --strict run failed"; return 1; }
    # gate 2: the red-team fixtures MUST be detected (an injected
    # lane-contract violation and an injected unpaired-DMA start each
    # exit nonzero) — otherwise the pass went blind
    if env -u LGBM_TPU_VMEM_GEN -u LGBM_TPU_VMEM_LIMIT_MB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --fixture bad_lane \
        > /dev/null 2>&1; then
        echo "lint leg FAIL: injected lane-contract violation" \
             "(bad_lane) was NOT flagged"
        return 1
    fi
    if env -u LGBM_TPU_VMEM_GEN -u LGBM_TPU_VMEM_LIMIT_MB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --fixture bad_dma \
        > /dev/null 2>&1; then
        echo "lint leg FAIL: injected unpaired-DMA fixture (bad_dma)" \
             "was NOT flagged"
        return 1
    fi
    echo "lint leg: clean strict run + both injected fixtures flagged"
    return 0
}

mesh_obs_leg() {
    echo "=== tier-1 leg 7: mesh flight recorder (multichip probe +" \
         "gate) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # traced 8-CPU mesh training -> a multichip bench/v3 record with
    # per-shard ledger rows, the skew series and the multichip block
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        JAX_PLATFORMS=cpu timeout -k 10 600 \
        python tools/multichip_probe.py --rows 6000 --iters 3 \
        --json "$tmp/mc.json" > /dev/null 2> "$tmp/probe.err" \
        || { echo "mesh-obs leg: multichip probe failed"; \
             cat "$tmp/probe.err"; return 1; }
    # the record must show the fast path: reduce-scatter engaged, no
    # psum-fallback event, per-shard rows keyed by all 8 shard ids
    python - "$tmp/mc.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
mc = rec.get("multichip") or {}
assert mc.get("schema") == "lightgbm_tpu/multichip/v1", mc.get("schema")
assert mc.get("n_shards") == 8, mc
assert mc.get("hist_scatter"), "reduce-scatter fast path did not engage"
ev = mc.get("events") or {}
assert "hist_scatter_psum_fallback" not in ev, ev
led = rec.get("ledger") or {}
colls = led.get("collectives") or []
assert colls, "no collective rows in the multichip ledger"
assert all(len(c.get("per_shard", {}).get("inbag_rows", [])) == 8
           for c in colls), "per-shard ledger rows missing"
mesh = led.get("mesh") or {}
assert len(mesh.get("skew_series", [])) == len(colls), mesh
print(f"mesh-obs leg: record ok ({len(colls)} collective rows, "
      f"skew series x{len(mesh['skew_series'])})")
PYEOF
    [ $? -eq 0 ] || { echo "mesh-obs leg: record shape check failed"; \
                      return 1; }
    # gate 1: the record diffed against ITSELF must pass
    python tools/perf_gate.py "$tmp/mc.json" "$tmp/mc.json" \
        || { echo "mesh-obs leg: self-diff failed"; return 1; }
    # gate 2: an injected 2x per-shard skew MUST be flagged
    python - "$tmp/mc.json" "$tmp/skew.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
for c in rec["ledger"]["collectives"]:
    rows = c["per_shard"]["inbag_rows"]
    rows[0] *= 2
    c["skew_max"] = max(rows)
mesh = rec["ledger"]["mesh"]
mesh["skew_series"] = [2.0] * len(mesh["skew_series"])
mesh["skew_max_ratio"] = mesh["skew_median_ratio"] = 2.0
json.dump(rec, open(sys.argv[2], "w"))
print("mesh-obs leg: injected 2x per-shard skew")
PYEOF
    if python tools/perf_gate.py "$tmp/mc.json" "$tmp/skew.json"; then
        echo "mesh-obs leg FAIL: injected 2x per-shard skew was NOT" \
             "flagged"
        return 1
    fi
    # gate 3: a mutated collective byte count MUST be flagged
    python - "$tmp/mc.json" "$tmp/bytes.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
rec["ledger"]["collectives"][0]["bytes_moved"] += 1
rec["ledger"]["mesh"]["bytes_moved_total"] += 1
json.dump(rec, open(sys.argv[2], "w"))
print("mesh-obs leg: mutated one collective byte count")
PYEOF
    if python tools/perf_gate.py "$tmp/mc.json" "$tmp/bytes.json"; then
        echo "mesh-obs leg FAIL: mutated collective bytes were NOT" \
             "flagged"
        return 1
    fi
    # gate 4: legacy MULTICHIP_r*.json artifacts are tolerated with a
    # clear fallback message (report) and refused cleanly (gate,
    # exit 2) — never a traceback
    echo '{"n_devices": 8, "rc": 0, "ok": true, "skipped": false, "tail": "dryrun ok"}' \
        > "$tmp/MULTICHIP_legacy.json"
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs report --bench \
        "$tmp/MULTICHIP_legacy.json" > "$tmp/legacy.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "legacy multichip dryrun" \
        "$tmp/legacy.out"; then
        echo "mesh-obs leg: legacy MULTICHIP reader fallback missing"
        cat "$tmp/legacy.out"
        return 1
    fi
    python tools/perf_gate.py "$tmp/MULTICHIP_legacy.json" "$tmp/mc.json" \
        > "$tmp/legacy_diff.out" 2>&1
    if [ $? -ne 2 ] || grep -q "Traceback" "$tmp/legacy_diff.out"; then
        echo "mesh-obs leg: legacy record diff must exit 2 cleanly"
        cat "$tmp/legacy_diff.out"
        return 1
    fi
    # gate 5: the pinned obs collectives fixture table (measured ICI
    # vs analytical contract, exact join)
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs collectives \
        tests/data/synthetic_mesh.xplane.pb \
        --bench tests/data/synthetic_mesh_bench.json --no-tf \
        > "$tmp/coll.out" 2> "$tmp/coll.err"
    if [ $? -ne 0 ]; then
        echo "mesh-obs leg: obs collectives exited nonzero on fixture"
        cat "$tmp/coll.out" "$tmp/coll.err"
        return 1
    fi
    if ! diff -u tests/data/synthetic_collectives_expected.txt \
        "$tmp/coll.out"; then
        echo "mesh-obs leg: collectives table drifted from" \
             "tests/data/synthetic_collectives_expected.txt" \
             "(regenerate via python -m lightgbm_tpu.obs.xattr)"
        return 1
    fi
    echo "mesh-obs leg: record + self-diff clean, skew and byte" \
         "mutations flagged, legacy readers tolerant, collectives" \
         "table exact"
    return 0
}

mem_leg() {
    echo "=== tier-1 leg 8: HBM flight recorder (obs mem + gates) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # gate 1: pinned `obs mem` table on the checked-in fixture record
    # (footprint model -> phase live-sets -> measured join, exact)
    env -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
        -u LGBM_TPU_STREAM \
        JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs mem \
        tests/data/synthetic_mem_record.json \
        > "$tmp/mem.out" 2> "$tmp/mem.err"
    if [ $? -ne 0 ]; then
        echo "mem leg: obs mem exited nonzero on the fixture record"
        cat "$tmp/mem.out" "$tmp/mem.err"
        return 1
    fi
    if ! diff -u tests/data/synthetic_mem_expected.txt "$tmp/mem.out"
    then
        echo "mem leg: fixture table drifted from" \
             "tests/data/synthetic_mem_expected.txt (regenerate with" \
             "python -m lightgbm_tpu.obs.mem if the change is intended)"
        return 1
    fi
    # gate 2: a freshly-captured traced record carries the memory
    # block, reports cleanly, and self-diffs green
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
        JAX_PLATFORMS=cpu LGBM_TPU_TRACE="$tmp/trace.jsonl" \
        timeout -k 10 300 python bench.py --smoke --rows 4096 \
        --iters 2 --leaves 15 --json "$tmp/a.json" > /dev/null \
        || { echo "mem leg: traced bench capture failed"; return 1; }
    python - "$tmp/a.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
mem = rec.get("memory") or {}
assert mem.get("schema") == "lightgbm_tpu/mem/v1", mem.get("schema")
assert "error" not in mem, mem.get("error")
assert mem.get("predicted", {}).get("peak_bytes", 0) > 0, mem
meas = mem.get("measured") or {}
assert meas.get("live_peak_bytes"), "no measured residency series"
rows = rec["ledger"]["iterations"]
assert any(r.get("hbm_phase_bytes") for r in rows), \
    "no per-phase residency timeline in the ledger"
print("mem leg: memory block ok (predicted "
      f"{mem['predicted']['peak_bytes']/1e6:.1f} MB peak, "
      f"{len(rows)} ledger rows)")
PYEOF
    [ $? -eq 0 ] || { echo "mem leg: memory block check failed"; \
                      return 1; }
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs mem "$tmp/a.json" \
        > /dev/null \
        || { echo "mem leg: obs mem failed on the fresh record"; \
             return 1; }
    python tools/perf_gate.py "$tmp/a.json" "$tmp/a.json" > /dev/null \
        || { echo "mem leg: self-diff failed"; return 1; }
    # gate 3: an injected 2x residency-peak regression MUST be flagged
    python - "$tmp/a.json" "$tmp/b.json" <<'PYEOF'
import json, sys
rec = json.load(open(sys.argv[1]))
for row in rec["ledger"]["iterations"]:
    for k in ("hbm_live_bytes", "hbm_peak_bytes"):
        if k in row:
            row[k] = int(row[k] * 2)
    if "hbm_phase_bytes" in row:
        row["hbm_phase_bytes"] = {p: v * 2 for p, v
                                  in row["hbm_phase_bytes"].items()}
meas = rec.get("memory", {}).get("measured", {})
for k in ("live_peak_bytes", "alloc_peak_bytes"):
    if k in meas:
        meas[k] = int(meas[k] * 2)
json.dump(rec, open(sys.argv[2], "w"))
print("mem leg: injected 2x residency-peak regression")
PYEOF
    [ $? -eq 0 ] || { echo "mem leg: injection failed"; return 1; }
    if python tools/perf_gate.py "$tmp/a.json" "$tmp/b.json" > /dev/null
    then
        echo "mem leg FAIL: injected 2x residency-peak regression was" \
             "NOT flagged"
        return 1
    fi
    # gate 4: the dropped-donation red-team fixture MUST fail the
    # hbm-budget pass (a donation audit that goes blind re-opens the
    # double-allocation class it exists to pin)
    if env -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes hbm-budget \
        --fixture bad_donation > /dev/null 2>&1; then
        echo "mem leg FAIL: dropped-donation fixture (bad_donation)" \
             "was NOT flagged"
        return 1
    fi
    # gate 5: the ROADMAP-5 acceptance pair — the unpaged 100M-row
    # geometry is over budget, the planner's schedule is accepted
    if env -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes hbm-budget \
        --hbm-geometry 100000000,28 > /dev/null 2>&1; then
        echo "mem leg FAIL: unpaged 100M-row geometry was NOT flagged"
        return 1
    fi
    local rpp
    rpp=$(env -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
          JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs mem --plan \
          --rows 100000000 --features 28 \
          | sed -n 's/^  rows\/page: \([0-9]*\) .*/\1/p')
    if [ -z "$rpp" ]; then
        echo "mem leg FAIL: obs mem --plan emitted no page schedule"
        return 1
    fi
    env -u LGBM_TPU_HBM_GEN -u LGBM_TPU_HBM_LIMIT_GB \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes hbm-budget \
        --hbm-geometry "100000000,28,256,$rpp" > /dev/null 2>&1 \
        || { echo "mem leg FAIL: planner page schedule (rows/page=" \
                  "$rpp) was NOT accepted by the hbm-budget pass"; \
             return 1; }
    # gate 6: legacy records degrade with a message, never a traceback
    echo '{"n_devices": 8, "rc": 0, "ok": true, "skipped": false, "tail": "dryrun ok"}' \
        > "$tmp/MULTICHIP_legacy.json"
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs mem \
        "$tmp/MULTICHIP_legacy.json" > "$tmp/legacy.out" 2>&1
    if [ $? -ne 2 ] || grep -q "Traceback" "$tmp/legacy.out"; then
        echo "mem leg: legacy record must exit 2 cleanly"
        cat "$tmp/legacy.out"
        return 1
    fi
    echo "mem leg: pinned table exact, memory block + self-diff clean," \
         "peak regression + dropped donation flagged, page schedule" \
         "accepted, legacy reader tolerant"
    return 0
}

routing_leg() {
    echo "=== tier-1 leg 9: routing + recompile auditor ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # gate 1: clean --strict routing pass (golden matrix current,
    # every row_order cell justified, recompile audit green).  -u the
    # path knobs: an exported sweep knob would re-route the audited
    # builds
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM -u LGBM_TPU_HIST_SCATTER \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing --strict \
        || { echo "routing leg: clean --strict run failed"; return 1; }
    # gate 2: both red-team fixtures MUST be detected
    if JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --fixture bad_route > /dev/null 2>&1; then
        echo "routing leg FAIL: unjustified-fallback fixture" \
             "(bad_route) was NOT flagged"
        return 1
    fi
    if JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --fixture bad_retrace > /dev/null 2>&1; then
        echo "routing leg FAIL: baked-constant retrace fixture" \
             "(bad_retrace) was NOT flagged"
        return 1
    fi
    # gate 3: a hand-mutated golden matrix cell MUST fail — written
    # back in CANONICAL form so only the cell (not formatting) is
    # wrong, and the CELL-level finding must fire specifically (a
    # formatting-induced STALE alone would let unjustified-fallback
    # detection rot behind a green gate)
    JAX_PLATFORMS=cpu python - "$tmp/mut.json" <<'PYEOF'
import json, sys
from lightgbm_tpu.ops import routing
doc = json.load(open("lightgbm_tpu/analysis/routing_matrix.json"))
key = next(k for k, v in doc["cells"].items() if "path=stream" in v)
doc["cells"][key] = doc["cells"][key].replace("path=stream",
                                              "path=row_order")
open(sys.argv[1], "wb").write(routing.canonical_bytes(doc))
print("routing leg: mutated one golden stream cell to row_order")
PYEOF
    [ $? -eq 0 ] || { echo "routing leg: mutation failed"; return 1; }
    JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --routing-matrix "$tmp/mut.json" > "$tmp/mut.out" 2>&1
    if [ $? -eq 0 ] || ! grep -q "ROUTING_UNJUSTIFIED_FALLBACK" \
        "$tmp/mut.out"; then
        echo "routing leg FAIL: mutated golden matrix cell was NOT" \
             "flagged at cell level"
        cat "$tmp/mut.out"
        return 1
    fi
    # gate 4: records with mismatched routing digests are
    # INCOMPARABLE (exit 2) in obs diff / perf_gate
    python - "$tmp/ra.json" "$tmp/rb.json" <<'PYEOF'
import json, sys
base = {"schema": "lightgbm_tpu/bench/v3", "metric": "m",
        "value": 1.0, "unit": "iters/sec"}
a = dict(base, routing={"digest": "aaaaaaaaaaaa", "path": "physical",
                        "pack": 1, "scheme": "permute",
                        "hist_merge": "none"})
b = dict(base, routing={"digest": "bbbbbbbbbbbb", "path": "row_order",
                        "pack": 1, "scheme": "none",
                        "hist_merge": "none"})
json.dump(a, open(sys.argv[1], "w"))
json.dump(b, open(sys.argv[2], "w"))
PYEOF
    JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs diff \
        "$tmp/ra.json" "$tmp/rb.json" > "$tmp/diff.out" 2>&1
    if [ $? -ne 2 ] || ! grep -q "routing-path mismatch" \
        "$tmp/diff.out"; then
        echo "routing leg FAIL: mismatched routing digests must exit" \
             "2 with a routing-path message"
        cat "$tmp/diff.out"
        return 1
    fi
    if python tools/perf_gate.py "$tmp/ra.json" "$tmp/rb.json" \
        > /dev/null 2>&1; then
        echo "routing leg FAIL: perf_gate passed mismatched routing" \
             "digests"
        return 1
    fi
    echo "routing leg: clean strict run, both fixtures + mutated" \
         "cell flagged, digest mismatch incomparable"
    return 0
}

chiprun_leg() {
    echo "=== tier-1 leg 10: chip-run autopilot (doctor + orchestrator" \
         "+ trend) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # gate 1: the doctor must be CLEAN on the CPU backend (exit 0) —
    # the same verdict a healthy chip host must produce.  -u the
    # budget knobs: a leftover sweep export would fail the memory
    # layer this gate pins
    env -u LGBM_TPU_VMEM_LIMIT_MB -u LGBM_TPU_HBM_LIMIT_GB \
        -u LGBM_TPU_DOCTOR_MIN_DISK_GB -u LGBM_TPU_CHIPRUN_DIR \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.obs doctor > "$tmp/doc.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "verdict CLEAN" "$tmp/doc.out"; then
        echo "chiprun leg: obs doctor must exit 0 CLEAN on cpu"
        cat "$tmp/doc.out"
        return 1
    fi
    # gate 2: the r03 bring-up log fixture must FAIL the doctor,
    # classified as the TPU-env-bringup class — the BENCH_r03
    # regression must be un-reintroducible
    env JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.obs doctor \
        --log tests/data/r03_env_failure.log --no-xplane-smoke \
        > "$tmp/r03.out" 2>&1
    if [ $? -ne 1 ] || ! grep -q "BRINGUP_TPU_ENV_BRINGUP" \
        "$tmp/r03.out"; then
        echo "chiprun leg FAIL: r03 fixture must exit 1 classified as" \
             "tpu_env_bringup"
        cat "$tmp/r03.out"
        return 1
    fi
    # gate 3: the full checked-in plan dry-runs end to end — every
    # step journaled executed-or-validated with a named reason,
    # consolidated report written
    env -u LGBM_TPU_CHIPRUN_DIR JAX_PLATFORMS=cpu timeout -k 10 600 \
        python tools/chip_run.py --dry-run --dir "$tmp/run" \
        > "$tmp/dry.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "chiprun leg: chip_run.py --dry-run failed"
        cat "$tmp/dry.out"
        return 1
    fi
    python - "$tmp/run" <<'PYEOF'
import json, sys
run_dir = sys.argv[1]
plan = json.load(open("tools/chip_plan.json"))
entries = [json.loads(l) for l in open(run_dir + "/journal.jsonl")]
by_step = {e["step"]: e for e in entries if "step" in e}
for step in plan["steps"]:
    ent = by_step.get(step["id"])
    assert ent, f"step {step['id']} missing from the journal"
    assert ent["status"] in ("ok", "validated"), ent
    assert ent["status"] == "ok" or ent.get("reason"), ent
rnd = plan["round"]
rep = json.load(open(run_dir + f"/CHIPRUN_r{rnd:02d}.json"))
assert rep["gate"]["verdict"] == "dry-validated", rep["gate"]
assert rep["doctor"]["verdict"] == "clean", rep["doctor"]
print(f"chiprun leg: dry journal complete ({len(by_step)} steps, "
      "doctor executed, rest validated)")
PYEOF
    [ $? -eq 0 ] || { echo "chiprun leg: dry journal check failed"; \
                      return 1; }
    # gate 4: killed-then-resumed dry run -> ONE merged journal, the
    # completed doctor step skipped by digest (exactly one executed
    # entry)
    env -u LGBM_TPU_CHIPRUN_DIR JAX_PLATFORMS=cpu timeout -k 10 600 \
        python tools/chip_run.py --dry-run --dir "$tmp/run2" \
        --halt-after doctor > /dev/null 2>&1 \
        || { echo "chiprun leg: halted dry run failed"; return 1; }
    env -u LGBM_TPU_CHIPRUN_DIR JAX_PLATFORMS=cpu timeout -k 10 600 \
        python tools/chip_run.py --dry-run --dir "$tmp/run2" \
        > "$tmp/resume.out" 2>&1 \
        || { echo "chiprun leg: resumed dry run failed"; \
             cat "$tmp/resume.out"; return 1; }
    python - "$tmp/run2" <<'PYEOF'
import json, sys
run_dir = sys.argv[1]
entries = [json.loads(l) for l in open(run_dir + "/journal.jsonl")]
doctor = [e for e in entries if e.get("step") == "doctor"]
assert len(doctor) == 1, \
    f"resume re-executed the doctor ({len(doctor)} journal entries)"
headers = [e for e in entries
           if e.get("schema") == "lightgbm_tpu/chiprun-journal/v1"]
assert len(headers) == 2 and headers[1]["resumed"], headers
plan = json.load(open("tools/chip_plan.json"))
rnd = plan["round"]
rep = json.load(open(run_dir + f"/CHIPRUN_r{rnd:02d}.json"))
assert rep["gate"]["verdict"] == "dry-validated", rep["gate"]
assert rep["gate"]["cached"] >= 1, rep["gate"]
print("chiprun leg: killed-then-resumed run merged into one journal "
      f"({rep['gate']['cached']} cached step(s))")
PYEOF
    [ $? -eq 0 ] || { echo "chiprun leg: resume journal check failed"; \
                      return 1; }
    # gate 5: the pinned trend table (exit 1: the synthetic fixture
    # trajectory carries an injected drift the view MUST flag)
    env JAX_PLATFORMS=cpu python -m lightgbm_tpu.obs trend \
        tests/data/trend_r01.json tests/data/trend_r02.json \
        tests/data/trend_r03.json > "$tmp/trend.out" 2> "$tmp/trend.err"
    if [ $? -ne 1 ]; then
        echo "chiprun leg: obs trend must exit 1 on the drift fixture"
        cat "$tmp/trend.out" "$tmp/trend.err"
        return 1
    fi
    if ! diff -u tests/data/trend_expected.txt "$tmp/trend.out"; then
        echo "chiprun leg: trend table drifted from" \
             "tests/data/trend_expected.txt (regenerate with" \
             "python -m lightgbm_tpu.obs.trend if intended)"
        return 1
    fi
    echo "chiprun leg: doctor clean + r03 classified, dry plan" \
         "complete, kill/resume merged, trend table exact"
    return 0
}

efb_leg() {
    echo "=== tier-1 leg 11: EFB graduation (ISSUE 12: bundled" \
         "columns on the physical fast path) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # gate 1: clean strict analyzer run with the REGENERATED matrix
    # (the efb_bundle rule is deleted; every formerly-row_order EFB
    # cell must now route physical/stream or carry efb_overwide)
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM -u LGBM_TPU_HIST_SCATTER \
        JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing --strict \
        || { echo "efb leg: clean strict routing run failed"; \
             return 1; }
    # no cell may still blame the deleted rule
    if grep -q "efb_bundle[^_]" lightgbm_tpu/analysis/routing_matrix.json
    then
        echo "efb leg FAIL: the regenerated matrix still references" \
             "the deleted efb_bundle rule"
        return 1
    fi
    # gate 2: the bit-parity matrix (bundled vs pre-unbundled trees
    # byte-identical across width x serial/mesh, real kernel bodies)
    # plus the original EFB invariants stay green
    env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
        -u LGBM_TPU_PART_INTERP \
        -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
        JAX_PLATFORMS=cpu timeout -k 10 600 python -m pytest \
        tests/test_efb_physical.py tests/test_efb.py \
        -q -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly \
        || { echo "efb leg: parity matrix failed"; return 1; }
    # gate 3: a hand-mutated EFB matrix cell (fast-path EFB cell
    # flipped back to row_order) MUST fail at cell level
    JAX_PLATFORMS=cpu python - "$tmp/mut.json" <<'PYEOF'
import json, sys
from lightgbm_tpu.ops import routing
doc = json.load(open("lightgbm_tpu/analysis/routing_matrix.json"))
key = next(k for k, v in doc["cells"].items()
           if "efb=1" in k and "ew=0" in k and "path=stream" in v)
doc["cells"][key] = doc["cells"][key].replace("path=stream",
                                              "path=row_order")
open(sys.argv[1], "wb").write(routing.canonical_bytes(doc))
print("efb leg: flipped one graduated EFB stream cell to row_order")
PYEOF
    [ $? -eq 0 ] || { echo "efb leg: mutation failed"; return 1; }
    JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --routing-matrix "$tmp/mut.json" > "$tmp/mut.out" 2>&1
    if [ $? -eq 0 ] || ! grep -q "ROUTING_UNJUSTIFIED_FALLBACK" \
        "$tmp/mut.out"; then
        echo "efb leg FAIL: mutated EFB matrix cell was NOT flagged"
        cat "$tmp/mut.out"
        return 1
    fi
    # gate 4: the efb_overwide red team — a cell claiming the over-wide
    # rule without the over-wide shape fact re-opens the graduated
    # fallback class and MUST fail
    if JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --fixture efb_overwide > /dev/null 2>&1; then
        echo "efb leg FAIL: unjustified efb_overwide fixture was NOT" \
             "flagged"
        return 1
    fi
    echo "efb leg: strict matrix clean (efb_bundle gone), parity" \
         "matrix green, mutated cell + overwide fixture flagged"
    return 0
}

faults_leg() {
    echo "=== tier-1 leg 12: fault tolerance (ISSUE 13: checkpoint/" \
         "resume + fault injection) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    # every invocation runs with the path knobs UNSET: an exported
    # sweep knob would change the engaged routing digest and make the
    # resume legs refuse for the wrong reason
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_HIST_SCATTER -u LGBM_TPU_NUMERICS \
            -u LGBM_TPU_FAULT -u LGBM_TPU_FAULT_RETRIES \
            -u LGBM_TPU_CKPT_DIR -u LGBM_TPU_CKPT_EVERY \
            -u LGBM_TPU_CKPT_KEEP \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: a clean run writes ckpt/v1 snapshots; a second
    # invocation RESUMES them instead of retraining tree 0
    demo env LGBM_TPU_CKPT_DIR="$tmp/ck" LGBM_TPU_CKPT_EVERY=2 \
        timeout -k 10 300 python -m lightgbm_tpu.resilience demo \
        --rounds 6 > "$tmp/clean.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "checkpoint written" "$tmp/clean.out"
    then
        echo "faults leg: clean checkpointed run failed"
        cat "$tmp/clean.out"
        return 1
    fi
    demo env LGBM_TPU_CKPT_DIR="$tmp/ck" LGBM_TPU_CKPT_EVERY=2 \
        timeout -k 10 300 python -m lightgbm_tpu.resilience demo \
        --rounds 8 > "$tmp/resume.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "resumed from iteration 6" \
        "$tmp/resume.out"; then
        echo "faults leg: second run did not resume the checkpoint"
        cat "$tmp/resume.out"
        return 1
    fi
    # gate 2: the death class is a REAL SIGKILL — the process dies
    # (rc 137), the snapshot survives, and the NEXT process recovers
    # by resuming it
    demo env LGBM_TPU_CKPT_DIR="$tmp/ck2" LGBM_TPU_CKPT_EVERY=2 \
        LGBM_TPU_FAULT=death@3 timeout -k 10 300 \
        python -m lightgbm_tpu.resilience demo --rounds 6 \
        > "$tmp/death.out" 2>&1
    if [ $? -ne 137 ]; then
        echo "faults leg: death@3 must SIGKILL the process (rc 137)"
        cat "$tmp/death.out"
        return 1
    fi
    demo env LGBM_TPU_CKPT_DIR="$tmp/ck2" LGBM_TPU_CKPT_EVERY=2 \
        timeout -k 10 300 python -m lightgbm_tpu.resilience demo \
        --rounds 6 > "$tmp/death_resume.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "resumed from iteration 2" \
        "$tmp/death_resume.out"; then
        echo "faults leg: post-death run did not resume from the" \
             "surviving checkpoint"
        cat "$tmp/death_resume.out"
        return 1
    fi
    # gate 3: each in-process fault class classifies into its
    # faultreport/v1 finding and RECOVERS from the last checkpoint
    # (exit 0 with a recovered WARNING finding)
    local spec cls n=2
    for spec in "oom@3:FAULT_RESOURCE_EXHAUSTED" \
                "hang@3:FAULT_COLLECTIVE_TIMEOUT"; do
        n=$((n + 1))
        cls="${spec#*:}"
        demo env LGBM_TPU_CKPT_DIR="$tmp/ck$n" LGBM_TPU_CKPT_EVERY=2 \
            LGBM_TPU_FAULT="${spec%%:*}" timeout -k 10 300 \
            python -m lightgbm_tpu.resilience demo --rounds 6 \
            > "$tmp/f$n.out" 2>&1
        if [ $? -ne 0 ] || ! grep -q "$cls" "$tmp/f$n.out" \
            || ! grep -q "recovered from checkpoint" "$tmp/f$n.out"
        then
            echo "faults leg: ${spec%%:*} must classify as $cls and" \
                 "recover"
            cat "$tmp/f$n.out"
            return 1
        fi
    done
    # gate 4: NaN-poisoned gradients under the raise guardrail —
    # classified nan_gradients, recovered from the checkpoint
    demo env LGBM_TPU_CKPT_DIR="$tmp/ck_nan" LGBM_TPU_CKPT_EVERY=2 \
        LGBM_TPU_FAULT=nan@3 LGBM_TPU_NUMERICS=raise \
        timeout -k 10 300 python -m lightgbm_tpu.resilience demo \
        --rounds 6 > "$tmp/nan.out" 2>&1
    if [ $? -ne 0 ] || ! grep -q "FAULT_NAN_GRADIENTS" "$tmp/nan.out" \
        || ! grep -q "recovered from checkpoint" "$tmp/nan.out"; then
        echo "faults leg: nan@3 + numerics=raise must recover as" \
             "FAULT_NAN_GRADIENTS"
        cat "$tmp/nan.out"
        return 1
    fi
    # gate 5: without a checkpoint dir the same fault degrades LOUDLY
    # — exit 1 with the classified finding, never a traceback
    demo env LGBM_TPU_FAULT=oom@3 timeout -k 10 300 \
        python -m lightgbm_tpu.resilience demo --rounds 6 \
        > "$tmp/nockpt.out" 2>&1
    if [ $? -ne 1 ] || ! grep -q "FAULT_RESOURCE_EXHAUSTED" \
        "$tmp/nockpt.out"; then
        echo "faults leg: unrecoverable fault must exit 1 classified"
        cat "$tmp/nockpt.out"
        return 1
    fi
    # gate 6: a corrupt/torn checkpoint refuses with exit 2
    mkdir -p "$tmp/bad"
    echo "ckpt_999999" > "$tmp/bad/LATEST"
    demo env LGBM_TPU_CKPT_DIR="$tmp/bad" timeout -k 10 300 \
        python -m lightgbm_tpu.resilience demo --rounds 2 \
        > "$tmp/bad.out" 2>&1
    if [ $? -ne 2 ] || ! grep -q "CKPT_CORRUPT" "$tmp/bad.out"; then
        echo "faults leg: corrupt checkpoint must exit 2 with a" \
             "CKPT_CORRUPT finding"
        cat "$tmp/bad.out"
        return 1
    fi
    # the whole leg: structured findings only, never a traceback
    if grep -l "Traceback (most recent call last)" "$tmp"/*.out; then
        echo "faults leg FAIL: a fault path printed a raw traceback"
        return 1
    fi
    echo "faults leg: clean ckpt write/resume, death survived +" \
         "resumed, oom/hang/nan recovered classified, no-ckpt exit 1," \
         "corrupt ckpt exit 2, zero tracebacks"
    return 0
}

serve_leg() {
    echo "=== tier-1 leg 13: serving engine (ISSUE 14: compiled" \
         "forest predict, bucketed dispatch, donated score buffers) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_SERVE -u LGBM_TPU_SERVE_BUCKETS \
            -u LGBM_TPU_SERVE_QUEUE \
            -u LGBM_TPU_HIST_SCATTER -u LGBM_TPU_NUMERICS \
            -u LGBM_TPU_FAULT -u LGBM_TPU_FAULT_RETRIES \
            -u LGBM_TPU_CKPT_DIR -u LGBM_TPU_CKPT_EVERY \
            -u LGBM_TPU_CKPT_KEEP \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the parity suite (leaf-index exact, ulp-bounded scores)
    # with the compiled path FORCED on this CPU backend
    demo env LGBM_TPU_SERVE=1 timeout -k 10 600 \
        python -m pytest tests/test_serve.py -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > "$tmp/parity.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve leg FAIL: parity suite"
        tail -30 "$tmp/parity.out"
        return 1
    fi
    # gate 2: the retrace pin at runtime — two same-bucket batch
    # sizes share ONE compiled program; a novel bucket compiles
    # EXACTLY one more
    demo timeout -k 10 300 python - > "$tmp/retrace.out" 2>&1 <<'PY'
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.serve import ServingEngine, ServingModel

rng = np.random.default_rng(0)
x = rng.normal(size=(1500, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                          "verbosity": -1},
                  train_set=lgb.Dataset(x, label=y))
for _ in range(3):
    bst.update()
eng = ServingEngine(ServingModel.from_booster(bst))
eng.predict(x[:400])                    # bucket 512
p1 = eng.stats()["programs"]
for n in (300, 257, 512):               # same bucket
    eng.predict(x[:n])
assert eng.stats()["programs"] == p1, \
    f"same-bucket retrace: {eng.stats()}"
eng.predict(x[:40])                     # novel bucket 64
assert eng.stats()["programs"] == p1 + 1, \
    f"novel bucket != one compile: {eng.stats()}"
print("RETRACE_PIN_OK", eng.stats()["buckets"])
PY
    if [ $? -ne 0 ] || ! grep -q "RETRACE_PIN_OK" "$tmp/retrace.out"
    then
        echo "serve leg FAIL: bucketed-dispatch retrace pin"
        cat "$tmp/retrace.out"
        return 1
    fi
    # gate 3: the analyzer stays clean over the registered serving
    # entrypoint (lane/vmem/hbm donation/host-sync + the
    # serving-forest-bucket retrace pin), strict
    demo timeout -k 10 600 python -m lightgbm_tpu.analysis --strict \
        --passes routing,hbm-budget,host-sync,lane-contract \
        > "$tmp/analysis.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve leg FAIL: analyzer not clean over the serving" \
             "entrypoints"
        tail -20 "$tmp/analysis.out"
        return 1
    fi
    # gate 4: bench --serve emits a serving block with zero retraces
    # after warmup, and obs trend reads the record without drift
    demo timeout -k 10 600 python bench.py --serve --smoke \
        --no-preflight --json "$tmp/serve_rec.json" \
        > "$tmp/bench.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve leg FAIL: bench.py --serve --smoke"
        tail -20 "$tmp/bench.out"
        return 1
    fi
    demo timeout -k 10 120 python - "$tmp/serve_rec.json" \
        > "$tmp/block.out" 2>&1 <<'PY'
import json
import sys

rec = json.load(open(sys.argv[1]))
sv = rec["serving"]
assert rec["unit"] == "rows/sec", rec["unit"]
assert sv["retraces_after_warmup"] == 0, sv
assert sv["bulk_rows_per_sec"] > 0 and sv["p99_ms"] > 0, sv
assert sv["digest"] == rec["routing"]["serving"]["digest"], sv
print("SERVING_BLOCK_OK")
PY
    if [ $? -ne 0 ] || ! grep -q "SERVING_BLOCK_OK" "$tmp/block.out"
    then
        echo "serve leg FAIL: serving block contract"
        cat "$tmp/block.out"
        return 1
    fi
    demo timeout -k 10 120 python -m lightgbm_tpu.obs trend \
        "$tmp/serve_rec.json" > "$tmp/trend.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve leg FAIL: obs trend rejected the serving record"
        cat "$tmp/trend.out"
        return 1
    fi
    echo "serve leg: parity suite green, same-bucket retrace pin" \
         "held, analyzer clean over serve entrypoints, serving block" \
         "gated (0 retraces)"
    return 0
}

paged_leg() {
    echo "=== tier-1 leg 14: paged comb (ISSUE 15: larger-than-HBM" \
         "training, double-buffered page DMA) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_PAGED -u LGBM_TPU_PAGE_ROWS \
            -u LGBM_TPU_HBM_LIMIT_GB \
            -u LGBM_TPU_HIST_SCATTER -u LGBM_TPU_NUMERICS \
            -u LGBM_TPU_FAULT -u LGBM_TPU_FAULT_RETRIES \
            -u LGBM_TPU_CKPT_DIR -u LGBM_TPU_CKPT_EVERY \
            -u LGBM_TPU_CKPT_KEEP -u LGBM_TPU_CKPT_AT_REFRESH \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the paged suite — schedule audit, byte-identical paged
    # vs unpaged matrix (width x scheme x fused x stream through the
    # real kernels), geometry == planner, AT_REFRESH cadence
    demo timeout -k 10 900 \
        python -m pytest tests/test_paged.py -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > "$tmp/paged.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "paged leg FAIL: paged suite"
        tail -30 "$tmp/paged.out"
        return 1
    fi
    # gate 2: the acceptance shape — a tiny HBM budget forces the
    # footprint over budget, training must END-TO-END page with trees
    # byte-identical to the budget-raised run, and the bench record
    # must carry the paged block
    demo env LGBM_TPU_PHYS=interpret LGBM_TPU_HBM_LIMIT_GB=0.012 \
        timeout -k 10 600 python bench.py --smoke --rows 32768 \
        --iters 2 --leaves 7 --json "$tmp/paged_bench.json" \
        > /dev/null 2>&1
    if [ $? -ne 0 ]; then
        echo "paged leg FAIL: forced-paged tiny-budget bench run"
        return 1
    fi
    demo timeout -k 10 120 python - "$tmp/paged_bench.json" <<'PY'
import json, sys
rec = json.load(open(sys.argv[1]))
paged = rec.get("paged")
assert paged and paged["n_pages"] >= 2, paged
assert rec["routing"]["paged"] is True, rec.get("routing")
m = paged.get("measured")
assert m and m["sweeps"] >= 1 and m["dma_bytes"] > 0, m
print("PAGED_BLOCK_OK", paged["n_pages"], "pages x",
      paged["rows_per_page"], "rows/page")
PY
    if [ $? -ne 0 ]; then
        echo "paged leg FAIL: bench record paged block"
        return 1
    fi
    # gate 3: analyzer strict stays clean over the paged entries
    # (window update/extract, grow-paged-off purity pin, the real
    # double-buffer schedules under the dma-race page audit)
    demo timeout -k 10 600 python -m lightgbm_tpu.analysis --strict \
        > "$tmp/lint.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "paged leg FAIL: analyzer strict over paged entries"
        tail -20 "$tmp/lint.out"
        return 1
    fi
    # gate 4: the red team — a schedule whose compute reads the
    # in-flight page MUST fail the dma-race pass
    demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes dma-race --fixture bad_page > "$tmp/badpage.out" 2>&1
    if [ $? -eq 0 ]; then
        echo "paged leg FAIL: bad_page fixture (compute reads the" \
             "in-flight page) was NOT flagged"
        return 1
    fi
    echo "paged leg: byte-identical paged matrix green, forced-paged" \
         "bench carries the paged block, analyzer strict clean," \
         "bad_page fixture flagged"
    return 0
}

cat_leg() {
    echo "=== tier-1 leg 15: cat-subset graduation (ISSUE 16: bitset" \
         "split kernels on the physical fast path) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_HIST_SCATTER \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: clean strict routing run with the REGENERATED matrix
    # (cat_subset and scatter_cat_subset are deleted; every formerly
    # row_order cat cell must now route physical/stream or carry the
    # narrow cat_overwide rule)
    demo timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing --strict \
        || { echo "cat leg: clean strict routing run failed"; \
             return 1; }
    # no cell may still blame the deleted rules (cat_subset also
    # catches scatter_cat_subset)
    if grep -q "cat_subset" lightgbm_tpu/analysis/routing_matrix.json
    then
        echo "cat leg FAIL: the regenerated matrix still references" \
             "the deleted cat_subset / scatter_cat_subset rules"
        return 1
    fi
    # gate 2: the bit-parity matrix (categorical trees byte-identical
    # across partition-scheme x fused x serial/mesh through the
    # REAL kernel bodies, edge predictions, serving round-trip, the
    # overwide build defense) plus the original host-side cat-subset
    # finder invariants stay green.  NO 'not slow' filter: tier-1
    # leg 1 runs a representative diagonal of the matrix; this leg
    # owns the slow-marked remainder
    demo timeout -k 10 900 python -m pytest \
        tests/test_cat_physical.py tests/test_cat_subset.py \
        -q -p no:cacheprovider -p no:xdist -p no:randomly \
        || { echo "cat leg: parity matrix failed"; return 1; }
    # gate 3: a hand-mutated cat matrix cell (graduated cat stream
    # cell flipped back to row_order) MUST fail at cell level
    JAX_PLATFORMS=cpu python - "$tmp/mut.json" <<'PYEOF'
import json, sys
from lightgbm_tpu.ops import routing
doc = json.load(open("lightgbm_tpu/analysis/routing_matrix.json"))
key = next(k for k, v in doc["cells"].items()
           if ";cat=1;" in k and ";u8=1;" in k and "path=stream" in v)
doc["cells"][key] = doc["cells"][key].replace("path=stream",
                                              "path=row_order")
open(sys.argv[1], "wb").write(routing.canonical_bytes(doc))
print("cat leg: flipped one graduated cat stream cell to row_order")
PYEOF
    [ $? -eq 0 ] || { echo "cat leg: mutation failed"; return 1; }
    JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes routing \
        --routing-matrix "$tmp/mut.json" > "$tmp/mut.out" 2>&1
    if [ $? -eq 0 ] || ! grep -q "ROUTING_UNJUSTIFIED_FALLBACK" \
        "$tmp/mut.out"; then
        echo "cat leg FAIL: mutated cat matrix cell was NOT flagged"
        cat "$tmp/mut.out"
        return 1
    fi
    # gate 4: the bad_cat red team — the per-node membership bitsets
    # parked in HBM as 16-lane i32 lines (instead of SMEM sel words)
    # is exactly the misaligned-DMA class the lane-contract pass
    # exists for; an analyzer blind to it would wave the "optimized"
    # bitset side table onto the chip
    if JAX_PLATFORMS=cpu timeout -k 10 300 \
        python -m lightgbm_tpu.analysis --passes lane-contract \
        --fixture bad_cat > /dev/null 2>&1; then
        echo "cat leg FAIL: bad_cat fixture (misaligned HBM bitset" \
             "memref) was NOT flagged"
        return 1
    fi
    echo "cat leg: strict matrix clean (cat_subset rules gone)," \
         "bitset parity matrix green, mutated cell + bad_cat fixture" \
         "flagged"
    return 0
}

serve_obs_leg() {
    echo "=== tier-1 leg 16: serving flight recorder (ISSUE 17:" \
         "digest-segmented servemetrics windows, obs serve, p999" \
         "gate) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_SERVE -u LGBM_TPU_SERVE_BUCKETS \
            -u LGBM_TPU_SERVE_QUEUE -u LGBM_TPU_SERVE_METRICS \
            -u LGBM_TPU_SERVE_METRICS_WINDOW_S \
            -u LGBM_TPU_HIST_SCATTER -u LGBM_TPU_NUMERICS \
            -u LGBM_TPU_FAULT -u LGBM_TPU_FAULT_RETRIES \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the pinned obs serve table over the checked-in synthetic
    # fixture (exit 1: the fixture's second segment carries an
    # injected retrace-after-warmup the view MUST flag)
    demo timeout -k 10 120 python -m lightgbm_tpu.obs serve \
        tests/data/servemetrics_r01.jsonl > "$tmp/serve.out" 2>&1
    if [ $? -ne 1 ]; then
        echo "serve-obs leg FAIL: obs serve must exit 1 on the" \
             "retrace fixture"
        cat "$tmp/serve.out"
        return 1
    fi
    if ! diff -u tests/data/servemetrics_expected.txt \
        "$tmp/serve.out"; then
        echo "serve-obs leg FAIL: obs serve table drifted from" \
             "tests/data/servemetrics_expected.txt (regenerate with" \
             "python -m lightgbm_tpu.obs.servemetrics if intended)"
        return 1
    fi
    # gate 2: a fresh recorder run — bench --serve with the knob live
    # emits servemetrics windows; the stream must be clean (0
    # retraces => obs serve exit 0) and the record must carry the
    # flight-recorder block
    demo env LGBM_TPU_SERVE_METRICS="$tmp/metrics" \
        LGBM_TPU_SERVE_METRICS_WINDOW_S=1 \
        timeout -k 10 600 python bench.py --serve --smoke \
        --no-preflight --json "$tmp/serve_rec.json" \
        > "$tmp/bench.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve-obs leg FAIL: bench.py --serve with" \
             "LGBM_TPU_SERVE_METRICS live"
        tail -20 "$tmp/bench.out"
        return 1
    fi
    demo timeout -k 10 120 python - "$tmp/serve_rec.json" \
        > "$tmp/block.out" 2>&1 <<'PY'
import json
import sys

rec = json.load(open(sys.argv[1]))
sv = rec["serving"]
assert sv["retraces_after_warmup"] == 0, sv
assert sv["p999_ms"] >= sv["p99_ms"] > 0, sv
assert 0.0 <= sv["padding_waste_ratio"] <= 1.0, sv
sm = sv["servemetrics"]
assert sm["schema"] == "lightgbm_tpu/servemetrics/v1", sm
assert sm["windows"] >= 1 and sm["emit_dir"], sm
print("SERVEMETRICS_BLOCK_OK")
PY
    if [ $? -ne 0 ] || ! grep -q "SERVEMETRICS_BLOCK_OK" \
        "$tmp/block.out"; then
        echo "serve-obs leg FAIL: flight-recorder bench block"
        cat "$tmp/block.out"
        return 1
    fi
    demo timeout -k 10 120 python -m lightgbm_tpu.obs serve \
        "$tmp/metrics" > "$tmp/fresh.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve-obs leg FAIL: fresh recorder stream must be" \
             "clean (0 retraces => exit 0)"
        cat "$tmp/fresh.out"
        return 1
    fi
    # gate 3: the perf gate — self-diff passes; an injected 2x p999
    # tail regression MUST fail
    demo timeout -k 10 120 python tools/perf_gate.py \
        "$tmp/serve_rec.json" "$tmp/serve_rec.json" \
        > "$tmp/self.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve-obs leg FAIL: serving record self-diff not clean"
        cat "$tmp/self.out"
        return 1
    fi
    demo timeout -k 10 120 python - "$tmp/serve_rec.json" \
        "$tmp/worse.json" <<'PY'
import json
import sys

rec = json.load(open(sys.argv[1]))
rec["serving"]["p999_ms"] = round(rec["serving"]["p999_ms"] * 2, 3)
json.dump(rec, open(sys.argv[2], "w"))
PY
    demo timeout -k 10 120 python tools/perf_gate.py \
        "$tmp/serve_rec.json" "$tmp/worse.json" \
        > "$tmp/gate.out" 2>&1
    if [ $? -ne 1 ] || ! grep -q "p999_latency" "$tmp/gate.out"; then
        echo "serve-obs leg FAIL: injected 2x p999 regression was" \
             "NOT flagged"
        cat "$tmp/gate.out"
        return 1
    fi
    # gate 4: the S3 CLI contract — truncated and legacy inputs exit
    # 2 with one clear line, never a traceback
    printf '{"schema": "lightgbm_tpu/servemet' > "$tmp/trunc.jsonl"
    demo timeout -k 10 120 python -m lightgbm_tpu.obs serve \
        "$tmp/trunc.jsonl" > "$tmp/trunc.out" 2>&1
    if [ $? -ne 2 ] || grep -q "Traceback" "$tmp/trunc.out"; then
        echo "serve-obs leg FAIL: truncated input must exit 2" \
             "without a traceback"
        cat "$tmp/trunc.out"
        return 1
    fi
    printf '{"schema": "lightgbm_tpu/serving/v1"}\n' \
        > "$tmp/legacy.jsonl"
    demo timeout -k 10 120 python -m lightgbm_tpu.obs serve \
        "$tmp/legacy.jsonl" > "$tmp/legacy.out" 2>&1
    if [ $? -ne 2 ] || grep -q "Traceback" "$tmp/legacy.out"; then
        echo "serve-obs leg FAIL: legacy-schema input must exit 2" \
             "without a traceback"
        cat "$tmp/legacy.out"
        return 1
    fi
    echo "serve-obs leg: pinned table exact, fresh recorder clean" \
         "(0 retraces), injected p999 regression flagged, truncated/" \
         "legacy inputs exit 2"
    return 0
}

serve_kernel_leg() {
    echo "=== tier-1 leg 17: VMEM-resident serving kernel (ISSUE 18:" \
         "Pallas traversal parity, engagement audit, bf16 leaves) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_SERVE -u LGBM_TPU_SERVE_BUCKETS \
            -u LGBM_TPU_SERVE_QUEUE -u LGBM_TPU_SERVE_KERNEL \
            -u LGBM_TPU_SERVE_INTERP -u LGBM_TPU_SERVE_LEAF_BF16 \
            -u LGBM_TPU_SERVE_METRICS \
            -u LGBM_TPU_HIST_SCATTER -u LGBM_TPU_NUMERICS \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the kernel parity suite with the interpret seam FORCED
    # (leaf-index-exact kernel==gather==host, VMEM-fit boundary,
    # donation aliasing, serving_kernel_bytes equality, bf16 leaves,
    # retrace pin) — the fixture inside the suite sets
    # LGBM_TPU_SERVE=1 + LGBM_TPU_SERVE_INTERP=kernel itself; forcing
    # them here too guards against a fixture regression silently
    # downgrading the whole leg to the gather walk
    demo env LGBM_TPU_SERVE=1 LGBM_TPU_SERVE_INTERP=kernel \
        timeout -k 10 600 \
        python -m pytest tests/test_serve_kernel.py -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > "$tmp/parity.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve-kernel leg FAIL: kernel parity suite"
        tail -30 "$tmp/parity.out"
        return 1
    fi
    # gate 2: the analyzer stays clean --strict over the registered
    # serve_traverse entry — lane contract on every forest operand,
    # the vmem pass pricing the resident-forest scratch against the
    # engagement cap, hbm donation on the score buffer, and the
    # predict-cell kernel audit over the golden matrix
    demo timeout -k 10 600 python -m lightgbm_tpu.analysis --strict \
        --passes routing,hbm-budget,vmem-budget,lane-contract \
        > "$tmp/analysis.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "serve-kernel leg FAIL: analyzer strict run"
        tail -20 "$tmp/analysis.out"
        return 1
    fi
    # gate 3: the red-team fixture — the serving forest staged as
    # 64-lane HBM node lines MUST trip the lane rule
    if demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes lane-contract --fixture bad_serve_kernel \
        > /dev/null 2>&1; then
        echo "serve-kernel leg FAIL: misaligned serve-forest fixture" \
             "(bad_serve_kernel) was NOT flagged"
        return 1
    fi
    # gate 4: a golden predict cell hand-mutated to kernel=0 with no
    # justifying kernel rule MUST fail at cell level (canonical
    # rewrite so only the cell, not formatting, is wrong) — this is
    # what keeps the engagement rule auditable: every disengagement
    # in the shipped matrix names its rule
    demo python - "$tmp/mut.json" <<'PYEOF'
import json, sys
from lightgbm_tpu.ops import routing
doc = json.load(open("lightgbm_tpu/analysis/routing_matrix.json"))
key = next(k for k, v in doc["predict_cells"].items()
           if "kernel=1" in v)
doc["predict_cells"][key] = \
    doc["predict_cells"][key].replace("kernel=1", "kernel=0")
open(sys.argv[1], "wb").write(routing.canonical_bytes(doc))
print("serve-kernel leg: mutated one golden predict cell to kernel=0")
PYEOF
    [ $? -eq 0 ] || { echo "serve-kernel leg: mutation failed"; \
        return 1; }
    demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes routing --routing-matrix "$tmp/mut.json" \
        > "$tmp/mut.out" 2>&1
    if [ $? -eq 0 ] || ! grep -q "ROUTING_UNJUSTIFIED_FALLBACK" \
        "$tmp/mut.out"; then
        echo "serve-kernel leg FAIL: mutated kernel=0 predict cell" \
             "was NOT flagged at cell level"
        cat "$tmp/mut.out"
        return 1
    fi
    # gate 5: the retrace pin through the kernel-interp engine — the
    # bucketed dispatch seam is shared with the gather walk, but the
    # kernel swaps in a different jitted entry; warm traffic across
    # one bucket must still compile exactly once
    demo env LGBM_TPU_SERVE=1 LGBM_TPU_SERVE_INTERP=kernel \
        timeout -k 10 300 python - > "$tmp/retrace.out" 2>&1 <<'PY'
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.serve import ServingEngine, ServingModel

rng = np.random.default_rng(0)
x = rng.normal(size=(1500, 8)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                          "verbosity": -1},
                  train_set=lgb.Dataset(x, label=y))
for _ in range(3):
    bst.update()
eng = ServingEngine(ServingModel.from_booster(bst))
assert eng.kernel_mode == "interpret", eng.stats()
eng.predict(x[:400])                    # bucket 512
eng.mark_warm()
for n in (300, 257, 512):               # same bucket, warm
    eng.predict(x[:n])
st = eng.stats()
assert st["retraces_after_warmup"] == 0, st
print("KERNEL_RETRACE_PIN_OK", st["buckets"], st["kernel"])
PY
    if [ $? -ne 0 ] || ! grep -q "KERNEL_RETRACE_PIN_OK" \
        "$tmp/retrace.out"
    then
        echo "serve-kernel leg FAIL: kernel retrace pin"
        cat "$tmp/retrace.out"
        return 1
    fi
    echo "serve-kernel leg: interp parity suite green, analyzer" \
         "strict clean, misaligned-forest fixture + mutated kernel" \
         "cell flagged, 0 retraces after warmup"
    return 0
}

multiclass_leg() {
    echo "=== tier-1 leg 18: batched multiclass grow (ISSUE 19:" \
         "ONE dispatch per iteration grows all K class trees) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
            -u LGBM_TPU_PART_INTERP \
            -u LGBM_TPU_PHYS -u LGBM_TPU_STREAM \
            -u LGBM_TPU_MC_BATCH -u LGBM_TPU_NUMERICS \
            -u LGBM_TPU_HIST_SCATTER \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the byte-identity parity suite with the slow cells
    # FORCED (no -m 'not slow') — batched-vs-serial tree equality is
    # the whole contract of the one-dispatch path, so every
    # partition/fused/learner cell runs here even though leg 1
    # skips the slow half
    demo timeout -k 10 900 \
        python -m pytest tests/test_multiclass_batched.py -q \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        > "$tmp/parity.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "multiclass leg FAIL: batched-vs-serial parity suite"
        tail -30 "$tmp/parity.out"
        return 1
    fi
    # gate 2: the analyzer stays clean --strict over the registered
    # grow_physical_mc entry — lane contract on the scan-carried
    # comb, donation on the threaded comb/scratch, and the
    # multiclass-cell audit over the golden matrix
    demo timeout -k 10 600 python -m lightgbm_tpu.analysis --strict \
        --passes routing,hbm-budget,vmem-budget,lane-contract \
        > "$tmp/analysis.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "multiclass leg FAIL: analyzer strict run"
        tail -20 "$tmp/analysis.out"
        return 1
    fi
    # gate 3a: the red-team fixture — per-class hist slices staged as
    # 64-lane HBM lines MUST trip the lane rule
    if demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes lane-contract --fixture bad_mc_batch \
        > /dev/null 2>&1; then
        echo "multiclass leg FAIL: 64-lane per-class hist fixture" \
             "(bad_mc_batch) was NOT flagged by lane-contract"
        return 1
    fi
    # gate 3b: the same fixture injects a physical multi cell that
    # trains serial-K with no named mc_batch rule — the routing audit
    # MUST refuse it
    if demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes routing --fixture bad_mc_batch \
        > /dev/null 2>&1; then
        echo "multiclass leg FAIL: serial-K multiclass cell fixture" \
             "(bad_mc_batch) was NOT flagged by the routing audit"
        return 1
    fi
    # gate 4: a golden multi cell hand-mutated to mcb=0 with no
    # justifying mc_batch rule MUST fail at cell level (canonical
    # rewrite so only the cell, not formatting, is wrong) — every
    # serial-K fallback in the shipped matrix names its rule
    demo python - "$tmp/mut.json" <<'PYEOF'
import json, sys
from lightgbm_tpu.ops import routing
doc = json.load(open("lightgbm_tpu/analysis/routing_matrix.json"))
key = next(k for k, v in doc["cells"].items()
           if ";k=multi;" in k and "path=physical" in v
           and "mcb=1" in v)
doc["cells"][key] = doc["cells"][key].replace("mcb=1", "mcb=0")
open(sys.argv[1], "wb").write(routing.canonical_bytes(doc))
print("multiclass leg: mutated one golden multi cell to mcb=0")
PYEOF
    [ $? -eq 0 ] || { echo "multiclass leg: mutation failed"; \
        return 1; }
    demo timeout -k 10 300 python -m lightgbm_tpu.analysis \
        --passes routing --routing-matrix "$tmp/mut.json" \
        > "$tmp/mut.out" 2>&1
    if [ $? -eq 0 ] || ! grep -q "ROUTING_UNJUSTIFIED_FALLBACK" \
        "$tmp/mut.out"; then
        echo "multiclass leg FAIL: mutated mcb=0 multi cell was NOT" \
             "flagged at cell level"
        cat "$tmp/mut.out"
        return 1
    fi
    # gate 5: the dispatch-count pin — the obs ledger's per-iteration
    # event deltas must show exactly ONE grow dispatch per boosting
    # iteration at K=4 on the batched path, and exactly K with the
    # knob forced off.  This is the perf contract the whole issue
    # exists for: if the scan-over-K silently decomposes back into K
    # python-loop dispatches, tree bytes stay identical and every
    # parity gate above still passes — only the dispatch ledger sees
    # it
    demo env LGBM_TPU_PHYS=interpret LGBM_TPU_PART_INTERP=kernel \
        timeout -k 10 600 python - > "$tmp/dispatch.out" 2>&1 <<'PY'
import numpy as np

K, N, ROUNDS = 4, 1200, 3
rng = np.random.default_rng(0)
x = rng.normal(size=(N, 10)).astype(np.float32)
sig = x[:, 0] + 0.5 * x[:, 1]
qs = np.quantile(sig, np.linspace(0, 1, K + 1)[1:-1])
y = np.searchsorted(qs, sig).astype(np.float32)
params = {"objective": "multiclass", "num_class": K,
          "num_leaves": 15, "verbosity": -1}


def run(mcb):
    import os
    import sys
    os.environ["LGBM_TPU_MC_BATCH"] = mcb
    for m in [k for k in list(sys.modules)
              if k.startswith("lightgbm_tpu")]:
        del sys.modules[m]
    import lightgbm_tpu as lgb2
    from lightgbm_tpu.obs.counters import reset_all
    from lightgbm_tpu.obs.metrics import ledger as led
    reset_all()
    bst = lgb2.Booster(params=params,
                       train_set=lgb2.Dataset(x, label=y))
    led.sample(-1, wall_s=0.0, hbm=False)   # flush warmup deltas
    for i in range(ROUNDS):
        bst.update()
        led.sample(i, wall_s=0.0, hbm=False)
    rows = [r for r in led.to_record()["iterations"]
            if r["iteration"] >= 0]
    eng = bool(getattr(bst._inner, "_mc_batched", False))
    return eng, [r.get("events", {}).get("grow_dispatch", 0)
                 for r in rows]


eng_b, disp_b = run("1")
assert eng_b is True, "batched path did not engage"
assert disp_b == [1] * ROUNDS, \
    f"batched K={K}: expected ONE grow dispatch/iter, got {disp_b}"
eng_s, disp_s = run("0")
assert eng_s is False, "serial run unexpectedly batched"
assert disp_s == [K] * ROUNDS, \
    f"serial K={K}: expected {K} grow dispatches/iter, got {disp_s}"
print("MC_DISPATCH_PIN_OK batched=", disp_b, " serial=", disp_s)
PY
    if [ $? -ne 0 ] || ! grep -q "MC_DISPATCH_PIN_OK" \
        "$tmp/dispatch.out"
    then
        echo "multiclass leg FAIL: grow-dispatch-count pin"
        cat "$tmp/dispatch.out"
        return 1
    fi
    echo "multiclass leg: byte-identity parity suite green (slow" \
         "cells forced), analyzer strict clean, bad_mc_batch fixture" \
         "failed lane-contract + routing, mutated mcb=0 cell flagged," \
         "ledger shows 1 grow dispatch/iter at K=4 (serial shows 4)"
    return 0
}

pulse_leg() {
    echo "=== tier-1 leg 19: live pulse telemetry (ISSUE 20:" \
         "heartbeat streams + stall watchdog + timeline) ==="
    local tmp
    tmp=$(mktemp -d) || return 1
    # shellcheck disable=SC2064 -- expand $tmp now, not at RETURN time
    trap "rm -rf '$tmp'" RETURN
    demo() {
        env -u LGBM_TPU_PULSE -u LGBM_TPU_PULSE_EVERY_S \
            -u LGBM_TPU_FAULT -u LGBM_TPU_CKPT_DIR \
            -u LGBM_TPU_CKPT_EVERY \
            JAX_PLATFORMS=cpu "$@"
    }
    # gate 1: the checked-in multi-role fixture renders byte-exactly.
    # watch at the pinned clock sees all four finding classes
    # (STALLED / RATE_COLLAPSE / CKPT_OVERDUE / SERVING_SLO, exit 1);
    # timeline merges its 7 sources into one monotonic view (exit 0)
    demo timeout -k 10 300 python -m lightgbm_tpu.obs watch \
        tests/data/pulse_r01 --once --now 1000070.0 --slo-p99-ms 5.0 \
        > "$tmp/watch.out" 2>&1
    if [ $? -ne 1 ]; then
        echo "pulse leg FAIL: fixture watch must exit 1 (findings)"
        cat "$tmp/watch.out"
        return 1
    fi
    if ! diff -u tests/data/pulse_watch_expected.txt \
        "$tmp/watch.out" > "$tmp/watch.diff" 2>&1; then
        echo "pulse leg FAIL: watch table drifted from" \
             "pulse_watch_expected.txt (regenerate with python -m" \
             "lightgbm_tpu.obs.pulse)"
        cat "$tmp/watch.diff"
        return 1
    fi
    demo timeout -k 10 300 python -m lightgbm_tpu.obs timeline \
        tests/data/pulse_r01 > "$tmp/timeline.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "pulse leg FAIL: fixture timeline must exit 0"
        cat "$tmp/timeline.out"
        return 1
    fi
    if ! diff -u tests/data/pulse_timeline_expected.txt \
        "$tmp/timeline.out" > "$tmp/timeline.diff" 2>&1; then
        echo "pulse leg FAIL: timeline drifted from" \
             "pulse_timeline_expected.txt (regenerate with python -m" \
             "lightgbm_tpu.obs.pulse)"
        cat "$tmp/timeline.diff"
        return 1
    fi
    # gate 2: a fresh pulse-on training run streams heartbeats plus a
    # terminal end event — watch over the live dir is CLEAN under the
    # default thresholds (exit 0, zero findings)
    demo env LGBM_TPU_PULSE="$tmp/live" LGBM_TPU_PULSE_EVERY_S=0.001 \
        timeout -k 10 600 python - > "$tmp/train.out" 2>&1 <<'PY'
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.default_rng(0)
x = rng.normal(size=(400, 5)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "verbosity": -1}
lgb.train(params, lgb.Dataset(x, label=y), num_boost_round=5)
print("PULSE_TRAIN_OK")
PY
    if [ $? -ne 0 ] || ! grep -q "PULSE_TRAIN_OK" "$tmp/train.out"
    then
        echo "pulse leg FAIL: pulse-on training run"
        cat "$tmp/train.out"
        return 1
    fi
    if ! ls "$tmp/live"/pulse-trainer-*.jsonl > /dev/null 2>&1; then
        echo "pulse leg FAIL: training emitted no trainer stream"
        ls -la "$tmp/live" 2>&1
        return 1
    fi
    demo timeout -k 10 300 python -m lightgbm_tpu.obs watch \
        "$tmp/live" --once > "$tmp/live_watch.out" 2>&1
    if [ $? -ne 0 ]; then
        echo "pulse leg FAIL: watch over a clean finished train must" \
             "exit 0 (zero findings)"
        cat "$tmp/live_watch.out"
        return 1
    fi
    # gate 3: an injected mid-training hang (LGBM_TPU_FAULT=hang@3
    # with no ckpt dir => unrecoverable FaultError, no end event)
    # leaves a silent tail — watch MUST flag it STALLED, naming the
    # trainer role and carrying the SAME collective_timeout class
    # faults.py assigned the hang
    demo env LGBM_TPU_PULSE="$tmp/stall" \
        LGBM_TPU_PULSE_EVERY_S=0.001 LGBM_TPU_FAULT=hang@3 \
        timeout -k 10 600 python - > "$tmp/hang.out" 2>&1 <<'PY'
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.resilience import faults

rng = np.random.default_rng(0)
x = rng.normal(size=(400, 5)).astype(np.float32)
y = (x[:, 0] > 0).astype(np.float32)
params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "verbosity": -1}
try:
    lgb.train(params, lgb.Dataset(x, label=y), num_boost_round=6)
except faults.FaultError:
    print("PULSE_HANG_RAISED")
else:
    raise SystemExit("injected hang did not fire")
PY
    if [ $? -ne 0 ] || ! grep -q "PULSE_HANG_RAISED" "$tmp/hang.out"
    then
        echo "pulse leg FAIL: hang@3 injection run"
        cat "$tmp/hang.out"
        return 1
    fi
    demo timeout -k 10 300 python -m lightgbm_tpu.obs watch \
        "$tmp/stall" --once > "$tmp/stall_watch.out" 2>&1
    if [ $? -ne 1 ] || ! grep -q "STALLED" "$tmp/stall_watch.out" \
        || ! grep -q "trainer" "$tmp/stall_watch.out" \
        || ! grep -q "collective_timeout" "$tmp/stall_watch.out"; then
        echo "pulse leg FAIL: injected hang was NOT flagged STALLED" \
             "with the collective_timeout class"
        cat "$tmp/stall_watch.out"
        return 1
    fi
    # gate 4: a stream truncated by a foreign writer is a named
    # exit-2 usage error, never a traceback
    mkdir -p "$tmp/trunc"
    head -c 37 tests/data/pulse_r01/pulse-trainer-4242.jsonl \
        > "$tmp/trunc/pulse-trainer-4242.jsonl"
    demo timeout -k 10 300 python -m lightgbm_tpu.obs watch \
        "$tmp/trunc" --once > "$tmp/trunc.out" 2>&1
    if [ $? -ne 2 ] || grep -q "Traceback" "$tmp/trunc.out"; then
        echo "pulse leg FAIL: truncated stream must exit 2 cleanly"
        cat "$tmp/trunc.out"
        return 1
    fi
    echo "pulse leg: fixture watch+timeline byte-exact, fresh" \
         "pulse-on train watches clean, injected hang flagged" \
         "STALLED (collective_timeout), truncated stream exits 2"
    return 0
}

if [ "$1" = "--fallback" ]; then
    fallback_leg
    exit $?
fi
if [ "$1" = "--obs" ]; then
    obs_leg
    exit $?
fi
if [ "$1" = "--attr" ]; then
    attr_leg
    exit $?
fi
if [ "$1" = "--lint" ]; then
    lint_leg
    exit $?
fi
if [ "$1" = "--mesh-obs" ]; then
    mesh_obs_leg
    exit $?
fi
if [ "$1" = "--mem" ]; then
    mem_leg
    exit $?
fi
if [ "$1" = "--routing" ]; then
    routing_leg
    exit $?
fi
if [ "$1" = "--chiprun" ]; then
    chiprun_leg
    exit $?
fi
if [ "$1" = "--efb" ]; then
    efb_leg
    exit $?
fi
if [ "$1" = "--faults" ]; then
    faults_leg
    exit $?
fi
if [ "$1" = "--serve" ]; then
    serve_leg
    exit $?
fi
if [ "$1" = "--paged" ]; then
    paged_leg
    exit $?
fi
if [ "$1" = "--cat" ]; then
    cat_leg
    exit $?
fi
if [ "$1" = "--serve-obs" ]; then
    serve_obs_leg
    exit $?
fi
if [ "$1" = "--serve-kernel" ]; then
    serve_kernel_leg
    exit $?
fi
if [ "$1" = "--multiclass" ]; then
    multiclass_leg
    exit $?
fi
if [ "$1" = "--pulse" ]; then
    pulse_leg
    exit $?
fi

echo "=== tier-1 leg 1: default knobs (ROADMAP command) ==="
rm -f /tmp/_t1.log
# -u: leg 1 must test the SHIPPING defaults even if the caller's shell
# exports fallback knobs (otherwise both legs silently run the same
# config and the default path goes untested)
timeout -k 10 870 env -u LGBM_TPU_FUSED -u LGBM_TPU_PARTITION \
    -u LGBM_TPU_PART_INTERP \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc1=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)"

fallback_leg
rc2=$?

obs_leg
rc4=$?

attr_leg
rc5=$?

lint_leg
rc6=$?

mesh_obs_leg
rc7=$?

mem_leg
rc8=$?

routing_leg
rc9=$?

chiprun_leg
rc10=$?

efb_leg
rc11=$?

faults_leg
rc12=$?

serve_leg
rc13=$?

paged_leg
rc14=$?

cat_leg
rc15=$?

serve_obs_leg
rc16=$?

serve_kernel_leg
rc17=$?

multiclass_leg
rc18=$?

pulse_leg
rc19=$?

echo "=== tier-1 summary: leg1 rc=$rc1 leg2 rc=$rc2" \
     "leg4 rc=$rc4 leg5 rc=$rc5 leg6 rc=$rc6 leg7 rc=$rc7" \
     "leg8 rc=$rc8 leg9 rc=$rc9 leg10 rc=$rc10 leg11 rc=$rc11" \
     "leg12 rc=$rc12 leg13 rc=$rc13 leg14 rc=$rc14 leg15 rc=$rc15" \
     "leg16 rc=$rc16 leg17 rc=$rc17 leg18 rc=$rc18" \
     "leg19 rc=$rc19 ==="
[ "$rc1" -eq 0 ] && [ "$rc2" -eq 0 ] \
    && [ "$rc4" -eq 0 ] && [ "$rc5" -eq 0 ] && [ "$rc6" -eq 0 ] \
    && [ "$rc7" -eq 0 ] && [ "$rc8" -eq 0 ] && [ "$rc9" -eq 0 ] \
    && [ "$rc10" -eq 0 ] && [ "$rc11" -eq 0 ] && [ "$rc12" -eq 0 ] \
    && [ "$rc13" -eq 0 ] && [ "$rc14" -eq 0 ] && [ "$rc15" -eq 0 ] \
    && [ "$rc16" -eq 0 ] && [ "$rc17" -eq 0 ] && [ "$rc18" -eq 0 ] \
    && [ "$rc19" -eq 0 ]
