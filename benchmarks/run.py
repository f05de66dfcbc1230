"""One cell of the benchmark, one run.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearse] [--out DIR]

One process, the program's normal entry points, data from ``--seed``.
No fallback: without a TPU, or with another device count than the cell's
``chips``, it says why and exits 1 before any data is made.  The last
line of a run's standard output is the result (``BENCHMARK.json``'s
contract); everything else a run has to say goes on earlier lines, one
JSON object each, and into ``--out`` (default
``.bench_out/<workload>-seed<seed>-trace<t>`` in the checkout).

``--rehearse`` walks the same control flow on the CPU at the cell's
``rehearse`` sizes with the kernels in interpret mode.  It prints no
result line and exits 3.

Cells, configurations, per-layer metrics, reducers and kinds are files,
found by name in the directories beside this one (README.md).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load(kind: str, name: str) -> dict:
    """``benchmarks/<kind>/<name>.json``; the name is the file's."""
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        have = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, kind))
                      if f.endswith(".json"))
        raise SystemExit(f"run.py: no {kind}/{name}.json (have: {have})")
    with open(path) as f:
        return json.load(f)


def load_all(kind: str) -> dict:
    return {f[:-5]: load(kind, f[:-5])
            for f in sorted(os.listdir(os.path.join(HERE, kind)))
            if f.endswith(".json")}


def module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"run.py: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unit_of(kind: str, name: str) -> str:
    """A metric's unit, from its own file."""
    return load(kind, name)["unit"]


class Context:
    """What a kind gets: the cell, its configuration, the arguments."""

    def __init__(self, args, cell, config, out_dir):
        self.cell, self.config = cell, config
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.out_dir = out_dir

    @staticmethod
    def note(topic: str, **fields) -> None:
        print(json.dumps({"note": topic, **fields}, default=str),
              flush=True)

    @staticmethod
    def memory_peak_bytes() -> int:
        """The peak on the fullest chip (0 where the backend keeps no
        count, which is the CPU of a rehearsal)."""
        import jax
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())


def layer_metrics(cell_name: str, cell: dict, result: dict):
    """(values by name, the traced slice): each per-layer metric of this
    cell through its reducer.  A reducer that finds nothing to read
    returns None and the metric is left out."""
    import trace as bench_trace

    sliced = None
    if result.get("trace_dir"):
        path = bench_trace.find_xplane(result["trace_dir"])
        sliced = bench_trace.read_slice(path) if path else None
    obs = {"spans": result.get("spans", []), "slice": sliced,
           "counters": result.get("counters", {})}
    values = {}
    for name, spec in load_all("layer_metrics").items():
        if name not in cell.get("per_layer", ()) \
                and cell_name not in spec.get("cells", ()):
            continue
        value = module("reducers", spec["reducer"]).reduce(
            obs, **spec.get("args", {}))
        if value is not None:
            values[name] = float(value)
    return values, sliced


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cell = load("workloads", args.workload)
    config = load("configs", cell["config"])
    if args.rehearse:
        # the chip's routes on the CPU: Pallas kernels in interpret mode,
        # compiled serving on a non-TPU backend, the cell's tiny sizes
        os.environ["LGBM_TPU_PHYS"] = "interpret"
        os.environ["LGBM_TPU_SERVE"] = "1"
        cell = {**cell, **cell.get("rehearse", {})}
    out_dir = os.path.abspath(args.out or os.path.join(
        REPO, ".bench_out",
        f"{args.workload}-seed{args.seed}-trace{args.trace}"))

    sys.path.insert(0, REPO)
    sys.path.insert(0, HERE)
    from lightgbm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"run.py: JAX found no TPU (devices: {device}); there is no "
              "CPU fallback - see --rehearse", file=sys.stderr)
        return 1
    if len(dev) != int(cell["chips"]):
        print(f"run.py: {args.workload} needs exactly {cell['chips']} "
              f"devices, JAX reports {len(dev)}", file=sys.stderr)
        return 1
    if not args.rehearse:
        import peaks
        peaks.of(device["kind"])        # an unknown chip is an error
    os.makedirs(out_dir, exist_ok=True)

    ctx = Context(args, cell, config, out_dir)
    result = module("kinds", cell["kind"]).run(ctx)

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        values, sliced = layer_metrics(args.workload, cell, result)
        if sliced is not None:
            device["busy_s"] = sliced.busy_s()
            device["window_s"] = sliced.window_s
            line["breakdown"] = sliced.breakdown()
    else:
        values = {k: float(v) for k, v in result["end_to_end"].items()
                  if k in cell["end_to_end"]}
        values["setup_s"] = result["setup_end"] - T_PROCESS
    kind = "layer_metrics" if args.trace else "end_to_end"
    line["metrics"] = {k: {"value": v, "unit": unit_of(kind, k)}
                       for k, v in values.items()}
    line["device"] = device
    if args.rehearse:
        print("run.py: rehearsal on " + device["platform"] + " finished; "
              "not a chip run, so no result line.  It would have read: "
              + json.dumps(line), file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
