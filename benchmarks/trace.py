"""From the profiler's xplane file to what the per-layer reducers read.

``jax.profiler.ProfileData.from_file`` gives planes > lines > events
(name, start_ns, duration_ns).  On a TPU every chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
HLO op, properly nested (a ``while`` contains its body's ops).  Host
threads are lines of ``/host:CPU``; ``jax.profiler.TraceAnnotation``
events land there on the same clock, which is how the traced slice
(``bench::slice``) and the program's spans (``obs::<name>``, mirrored by
``obs/tracer.py`` while ``tracer.annotate(True)``) are set beside the
device's idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SLICE_NAME = "bench::slice"
HOST_SPAN_PREFIXES = ("obs::", "bench::")

Interval = Tuple[int, int]          # [start_ns, end_ns)


@dataclass
class DeviceOps:
    """One chip's executed ops inside the slice: (name, start, end),
    sorted by start, outermost first."""
    ops: List[Tuple[str, int, int]]

    def busy_intervals(self) -> List[Interval]:
        out: List[Interval] = []
        for _, s, e in self.ops:
            if out and s <= out[-1][1]:
                if e > out[-1][1]:
                    out[-1] = (out[-1][0], e)
            else:
                out.append((s, e))
        return out

    def busy_ns(self) -> int:
        return sum(e - s for s, e in self.busy_intervals())

    def self_ns_by_name(self) -> Dict[str, int]:
        """Time in each op outside the ops nested in it, summed by name:
        a ``while`` keeps only its own overhead, its body's kernels keep
        theirs."""
        total: Dict[str, int] = {}
        stack: List[List] = []      # [name, end, self_ns]

        def close(upto: int) -> None:
            while stack and stack[-1][1] <= upto:
                name, _, self_ns = stack.pop()
                total[name] = total.get(name, 0) + self_ns

        for name, s, e in self.ops:
            close(s)
            if stack:
                stack[-1][2] -= min(e, stack[-1][1]) - s
            stack.append([name, e, e - s])
        close(1 << 62)
        return total


@dataclass
class SliceTrace:
    """The traced slice of one run."""
    start_ns: int
    end_ns: int
    devices: Dict[int, DeviceOps]
    host_spans: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns() for d in self.devices.values()) / 1e9 \
            / len(self.devices)

    def idle_gaps(self, device: int = 0) -> List[Interval]:
        gaps, at = [], self.start_ns
        for s, e in self.devices[device].busy_intervals():
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if self.end_ns > at:
            gaps.append((at, self.end_ns))
        return gaps

    def host_span_at(self, t_ns: int) -> str:
        """The innermost mirrored host span open at ``t_ns``."""
        best, best_len = "outside any host span", None
        for name, s, e in self.host_spans:
            if s <= t_ns < e and name != SLICE_NAME \
                    and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
        return best

    def breakdown(self, top: int = 10) -> dict:
        dev = min(self.devices)
        by_name = self.devices[dev].self_ns_by_name()
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(dev), key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[short_op_name(n), ns / 1e9] for n, ns in ops],
            "idle_gaps": [[self.host_span_at((s + e) // 2), (e - s) / 1e9]
                          for s, e in gaps],
        }


def short_op_name(name: str, limit: int = 160) -> str:
    """``%fusion.6 = f32[...] fusion(...)`` -> ``%fusion.6 fusion``, and a
    Pallas kernel keeps what tells it from the next one: the shapes of
    its result, cut to ``limit`` characters."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    m = re.search(r"\s([a-z][a-z0-9_-]*)\(", rest)
    kind = m.group(1) if m else ""
    if kind == "custom-call":
        return f"{head} custom-call {rest[:m.start()]}"[:limit]
    return f"{head} {kind}".strip()[:limit]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_slice(path: str) -> Optional[SliceTrace]:
    """None where the file holds no device ops (not a chip trace)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    raw: Dict[int, List[Tuple[str, int, int]]] = {}
    host_spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                raw[int(m.group(1))] = [
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns)) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIXES):
                        host_spans.append(
                            (e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    raw = {d: ops for d, ops in raw.items() if ops}
    if not raw:
        return None
    marks = [s for s in host_spans if s[0] == SLICE_NAME]
    if marks:
        start, end = marks[0][1], marks[0][2]
    else:
        start = min(o[1] for ops in raw.values() for o in ops)
        end = max(o[2] for ops in raw.values() for o in ops)
    devices = {}
    for d, ops in raw.items():
        clipped = [(n, max(s, start), min(e, end)) for n, s, e in ops
                   if e > start and s < end]
        clipped.sort(key=lambda o: (o[1], -o[2]))
        devices[d] = DeviceOps(clipped)
    return SliceTrace(start, end, devices, host_spans)
