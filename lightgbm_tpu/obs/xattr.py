"""Device-time kernel attribution from xplane captures (ISSUE 6
tentpole).

The PR-5 telemetry loop judges everything on HOST walls; this module
makes device time a first-class signal with three pieces:

* a **dependency-free xplane decoder** — a minimal varint /
  length-delimited protobuf reader for the ``tensorflow.tsl`` XSpace /
  XPlane / XLine / XEvent messages a ``jax.profiler`` capture writes
  (``plugins/profile/**/*.xplane.pb``).  Pure stdlib; when the real
  ``tensorflow.tsl`` proto IS installed it is used as an optional fast
  path (``load_xspace``), but nothing here imports TF, jax or numpy at
  module scope.  A tiny mirror **encoder** builds the synthetic
  fixtures the tests and the CI attr leg decode (round-tripped against
  the TF proto when that is installed).
* a **kernel classifier** (``classify_kernel``) mapping Mosaic/XLA op
  names onto the cost-model entries (partition scan, copyback, hist
  build, fused split, stream refresh, split finder, collectives) so
  measured device picoseconds can be joined with
  ``costmodel.kernel_model``'s predicted HBM bytes into achieved-GB/s
  per kernel.  Mosaic custom-calls keep their kernel function names
  (``_fused_scan_kernel`` …); anonymous XLA fusions land in ``other``.
* the **phase <-> kernel join** (``device_block``): per device plane
  (mesh runs get one plane per shard — measured straggler skew rides
  along), aggregate per-kernel device time, and per-phase
  host-wall-minus-device-time dispatch overhead against a traced
  bench/v3 record's phase walls.  The block embeds in bench records as
  ``rec["device"]`` (schema-additive, ``lightgbm_tpu/device/v1``);
  ``obs diff`` thresholds its per-kernel device times like walls.

CLI: ``python -m lightgbm_tpu.obs attr CAPTURE [--bench REC.json]
[--roofline]`` — see ``run_attr``.  Exit codes: 0 attributed, 1 decoded
but no TPU/GPU device plane, 2 unreadable input (missing path, empty
capture dir, truncated ``.pb``) — never a traceback.

The tracer side of the correlation lives in ``obs/tracer.py``: while an
xplane capture is active (``tools/profile_lib.xplane_capture`` /
``LGBM_TPU_XPLANE`` through ``bench.py``) every obs span also enters a
``jax.profiler.TraceAnnotation("obs::<name>")``, so host-plane TraceMe
events carry the obs phase names and xprof timelines line up with the
trace JSONL.  Off by default; on or off, the grow program is the same
(the ``grow-tracer-live`` purity pin).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_SCHEMA = "lightgbm_tpu/device/v1"


class XplaneParseError(ValueError):
    """Malformed / truncated xplane protobuf bytes."""


# ---------------------------------------------------------------------
# minimal protobuf wire reader (varint + length-delimited)
# ---------------------------------------------------------------------
_WIRE_VARINT, _WIRE_FIXED64, _WIRE_LEN, _WIRE_FIXED32 = 0, 1, 2, 5


def _read_varint(data: bytes, pos: int, end: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if pos >= end:
            raise XplaneParseError(
                f"truncated varint at byte {pos} (file cut mid-write?)")
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 63:
            raise XplaneParseError(f"varint longer than 10 bytes at "
                                   f"byte {pos}")


def _signed(v: int) -> int:
    """proto int64 rides the wire as two's-complement uint64."""
    return v - (1 << 64) if v >= (1 << 63) else v


def _iter_fields(data: bytes, start: int, end: int):
    """Yield ``(field_no, wire_type, value)`` over one message body.
    Length-delimited values are ``(start, end)`` offset pairs into
    ``data`` — no copies while descending the tree."""
    pos = start
    while pos < end:
        tag, pos = _read_varint(data, pos, end)
        field, wire = tag >> 3, tag & 7
        if field == 0:
            raise XplaneParseError(f"field number 0 at byte {pos} "
                                   "(not a protobuf?)")
        if wire == _WIRE_VARINT:
            v, pos = _read_varint(data, pos, end)
        elif wire == _WIRE_LEN:
            ln, pos = _read_varint(data, pos, end)
            if pos + ln > end:
                raise XplaneParseError(
                    f"length-delimited field {field} overruns the "
                    f"buffer at byte {pos} (truncated capture?)")
            v = (pos, pos + ln)
            pos += ln
        elif wire == _WIRE_FIXED64:
            if pos + 8 > end:
                raise XplaneParseError(f"truncated fixed64 at byte {pos}")
            v = int.from_bytes(data[pos:pos + 8], "little")
            pos += 8
        elif wire == _WIRE_FIXED32:
            if pos + 4 > end:
                raise XplaneParseError(f"truncated fixed32 at byte {pos}")
            v = int.from_bytes(data[pos:pos + 4], "little")
            pos += 4
        else:
            raise XplaneParseError(
                f"unsupported wire type {wire} for field {field} at "
                f"byte {pos}")
        yield field, wire, v


def _utf8(data: bytes, span: Tuple[int, int]) -> str:
    return data[span[0]:span[1]].decode("utf-8", errors="replace")


# ---------------------------------------------------------------------
# xplane object model (what the decoder fills and the encoder reads)
# ---------------------------------------------------------------------
class XEvent:
    __slots__ = ("metadata_id", "offset_ps", "duration_ps",
                 "num_occurrences", "stats")

    def __init__(self, metadata_id=0, offset_ps=0, duration_ps=0,
                 num_occurrences=0, stats=None):
        self.metadata_id = metadata_id
        self.offset_ps = offset_ps
        self.duration_ps = duration_ps
        self.num_occurrences = num_occurrences
        # {stat metadata_id: numeric value} — only the int64/uint64/
        # double stat kinds attribution consumes (ICI transfer sizes);
        # string/ref stats are skipped by the decoder
        self.stats = stats if stats is not None else {}


class XLine:
    __slots__ = ("id", "name", "timestamp_ns", "duration_ps", "events")

    def __init__(self, id=0, name="", timestamp_ns=0, duration_ps=0,
                 events=None):
        self.id = id
        self.name = name
        self.timestamp_ns = timestamp_ns
        self.duration_ps = duration_ps
        self.events = events if events is not None else []


class XPlane:
    __slots__ = ("id", "name", "lines", "event_metadata",
                 "stat_metadata")

    def __init__(self, id=0, name="", lines=None, event_metadata=None,
                 stat_metadata=None):
        self.id = id
        self.name = name
        self.lines = lines if lines is not None else []
        # {metadata_id: name} — the only payload attribution needs
        self.event_metadata = (event_metadata if event_metadata
                               is not None else {})
        self.stat_metadata = (stat_metadata if stat_metadata
                              is not None else {})

    def event_name(self, metadata_id: int) -> str:
        return self.event_metadata.get(metadata_id,
                                       f"<metadata {metadata_id}>")


class XSpace:
    __slots__ = ("planes", "hostnames")

    def __init__(self, planes=None, hostnames=None):
        self.planes = planes if planes is not None else []
        self.hostnames = hostnames if hostnames is not None else []


def _parse_stat(data: bytes, span) -> Tuple[int, Optional[float]]:
    """XStat {metadata_id: 1, double: 2, uint64: 3, int64: 4}: the
    numeric kinds only — collective transfer sizes ride uint64/int64
    stats; str/bytes/ref values are irrelevant to attribution."""
    import struct
    mid, val = 0, None
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            mid = _signed(v)
        elif field == 2 and wire == _WIRE_FIXED64:
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif field == 3 and wire == _WIRE_VARINT:
            val = float(v)
        elif field == 4 and wire == _WIRE_VARINT:
            val = float(_signed(v))
    return mid, val


def _parse_event(data: bytes, span) -> XEvent:
    ev = XEvent()
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            ev.metadata_id = v
        elif field == 2 and wire == _WIRE_VARINT:
            ev.offset_ps = _signed(v)
        elif field == 3 and wire == _WIRE_VARINT:
            ev.duration_ps = _signed(v)
        elif field == 4 and wire == _WIRE_LEN:
            mid, val = _parse_stat(data, v)
            if mid and val is not None:
                ev.stats[mid] = val
        elif field == 5 and wire == _WIRE_VARINT:
            ev.num_occurrences = _signed(v)
    return ev


def _parse_line(data: bytes, span) -> XLine:
    line = XLine()
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            line.id = _signed(v)
        elif field == 2 and wire == _WIRE_LEN:
            line.name = _utf8(data, v)
        elif field == 3 and wire == _WIRE_VARINT:
            line.timestamp_ns = _signed(v)
        elif field == 9 and wire == _WIRE_VARINT:
            line.duration_ps = _signed(v)
        elif field == 4 and wire == _WIRE_LEN:
            line.events.append(_parse_event(data, v))
    return line


def _parse_metadata_name(data: bytes, span) -> Tuple[int, str]:
    """XEventMetadata / XStatMetadata: {id: 1, name: 2}."""
    mid, name = 0, ""
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            mid = _signed(v)
        elif field == 2 and wire == _WIRE_LEN:
            name = _utf8(data, v)
    return mid, name


def _parse_map_entry(data: bytes, span) -> Tuple[int, Optional[tuple]]:
    """map<int64, X*Metadata> entry: {key: 1, value: 2}."""
    key, val_span = 0, None
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            key = _signed(v)
        elif field == 2 and wire == _WIRE_LEN:
            val_span = v
    return key, val_span


def _parse_plane(data: bytes, span) -> XPlane:
    plane = XPlane()
    for field, wire, v in _iter_fields(data, *span):
        if field == 1 and wire == _WIRE_VARINT:
            plane.id = _signed(v)
        elif field == 2 and wire == _WIRE_LEN:
            plane.name = _utf8(data, v)
        elif field == 3 and wire == _WIRE_LEN:
            plane.lines.append(_parse_line(data, v))
        elif field in (4, 5) and wire == _WIRE_LEN:
            key, val_span = _parse_map_entry(data, v)
            if val_span is not None:
                mid, name = _parse_metadata_name(data, val_span)
                target = (plane.event_metadata if field == 4
                          else plane.stat_metadata)
                # the map key and the message's own id field agree in
                # every real capture; prefer the embedded id when set
                target[mid or key] = name
    return plane


def parse_xspace(data: bytes) -> XSpace:
    """Decode serialized XSpace bytes.  Raises ``XplaneParseError`` on
    malformed/truncated input (never returns a half-parsed space)."""
    space = XSpace()
    for field, wire, v in _iter_fields(data, 0, len(data)):
        if field == 1 and wire == _WIRE_LEN:
            space.planes.append(_parse_plane(data, v))
        elif field == 4 and wire == _WIRE_LEN:
            space.hostnames.append(_utf8(data, v))
    return space


# ---------------------------------------------------------------------
# pprof heap-profile reader (jax.profiler.device_memory_profile):
# the same wire reader, pointed at perftools.profiles.Profile —
# counters.hbm_high_water_bytes' fallback census
# ---------------------------------------------------------------------
def parse_pprof_space_bytes(data: bytes) -> int:
    """Total live bytes in a (possibly gzipped) pprof Profile: the sum
    over samples of the value indexed by the ``space``/``bytes`` sample
    type (last value when the type table is absent)."""
    if data[:2] == b"\x1f\x8b":
        import gzip
        data = gzip.decompress(data)
    strings: List[str] = []
    sample_type_idx: List[int] = []     # string-table index per type
    sample_values: List[List[int]] = []
    for field, wire, v in _iter_fields(data, 0, len(data)):
        if field == 6 and wire == _WIRE_LEN:        # string_table
            strings.append(_utf8(data, v))
        elif field == 1 and wire == _WIRE_LEN:      # sample_type
            t = 0
            for f2, w2, v2 in _iter_fields(data, *v):
                if f2 == 1 and w2 == _WIRE_VARINT:  # ValueType.type
                    t = v2
            sample_type_idx.append(t)
        elif field == 2 and wire == _WIRE_LEN:      # sample
            vals: List[int] = []
            for f2, w2, v2 in _iter_fields(data, *v):
                if f2 == 2:                         # Sample.value
                    if w2 == _WIRE_LEN:             # packed int64s
                        pos, end = v2
                        while pos < end:
                            x, pos = _read_varint(data, pos, end)
                            vals.append(_signed(x))
                    elif w2 == _WIRE_VARINT:
                        vals.append(_signed(v2))
            sample_values.append(vals)
    col = -1
    for i, t in enumerate(sample_type_idx):
        if t < len(strings) and strings[t] in ("space", "bytes",
                                               "inuse_space"):
            col = i
            break
    total = 0
    for vals in sample_values:
        if not vals:
            continue
        total += vals[col] if -len(vals) <= col < len(vals) else vals[-1]
    return max(int(total), 0)


# ---------------------------------------------------------------------
# mirror encoder (synthetic fixtures; round-tripped vs TF when present)
# ---------------------------------------------------------------------
def _enc_varint(v: int) -> bytes:
    if v < 0:
        v += 1 << 64
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _enc_tag(field: int, wire: int) -> bytes:
    return _enc_varint(field << 3 | wire)


def _enc_int(field: int, v: int) -> bytes:
    if not v:
        return b""      # proto3 default elision (matches TF serialization)
    return _enc_tag(field, _WIRE_VARINT) + _enc_varint(v)


def _enc_bytes(field: int, payload: bytes) -> bytes:
    return (_enc_tag(field, _WIRE_LEN) + _enc_varint(len(payload))
            + payload)


def _enc_str(field: int, s: str) -> bytes:
    return _enc_bytes(field, s.encode("utf-8")) if s else b""


def _enc_double(field: int, v: float) -> bytes:
    import struct
    return _enc_tag(field, _WIRE_FIXED64) + struct.pack("<d", v)


def encode_stat(mid: int, val: float) -> bytes:
    body = _enc_int(1, mid)
    if float(val) == int(val):
        # int64_value: emitted EXPLICITLY even when zero — oneof
        # members serialize their value regardless of proto3 default
        # elision, and a measured bytes_accessed=0 must round-trip as
        # "measured zero", not vanish into "no bytes stat"
        body += _enc_tag(4, _WIRE_VARINT) + _enc_varint(int(val))
    else:
        body += _enc_double(2, float(val))   # double_value
    return body


def encode_event(ev: XEvent) -> bytes:
    out = (_enc_int(1, ev.metadata_id) + _enc_int(2, ev.offset_ps)
           + _enc_int(3, ev.duration_ps))
    for mid in sorted(ev.stats):
        out += _enc_bytes(4, encode_stat(mid, ev.stats[mid]))
    out += _enc_int(5, ev.num_occurrences)
    return out


def encode_line(line: XLine) -> bytes:
    out = (_enc_int(1, line.id) + _enc_str(2, line.name)
           + _enc_int(3, line.timestamp_ns))
    for ev in line.events:
        out += _enc_bytes(4, encode_event(ev))
    out += _enc_int(9, line.duration_ps)
    return out


def encode_plane(plane: XPlane) -> bytes:
    out = _enc_int(1, plane.id) + _enc_str(2, plane.name)
    for line in plane.lines:
        out += _enc_bytes(3, encode_line(line))
    for mid in sorted(plane.event_metadata):
        entry = _enc_int(1, mid) + _enc_bytes(
            2, _enc_int(1, mid) + _enc_str(2, plane.event_metadata[mid]))
        out += _enc_bytes(4, entry)
    for mid in sorted(plane.stat_metadata):
        entry = _enc_int(1, mid) + _enc_bytes(
            2, _enc_int(1, mid) + _enc_str(2, plane.stat_metadata[mid]))
        out += _enc_bytes(5, entry)
    return out


def encode_xspace(space: XSpace) -> bytes:
    out = b""
    for plane in space.planes:
        out += _enc_bytes(1, encode_plane(plane))
    for h in space.hostnames:
        out += _enc_str(4, h)
    return out


# ---------------------------------------------------------------------
# loading (optional tensorflow.tsl fast path, pure-python fallback)
# ---------------------------------------------------------------------
def _from_tf(xs_pb) -> XSpace:
    space = XSpace(hostnames=list(xs_pb.hostnames))
    for p in xs_pb.planes:
        plane = XPlane(id=p.id, name=p.name,
                       event_metadata={mid: m.name for mid, m
                                       in p.event_metadata.items()},
                       stat_metadata={mid: m.name for mid, m
                                      in p.stat_metadata.items()})
        for ln in p.lines:
            line = XLine(id=ln.id, name=ln.name,
                         timestamp_ns=ln.timestamp_ns,
                         duration_ps=ln.duration_ps)
            for ev in ln.events:
                stats = {}
                for st in ev.stats:
                    kind = st.WhichOneof("value")
                    if kind == "double_value":
                        stats[st.metadata_id] = float(st.double_value)
                    elif kind == "uint64_value":
                        stats[st.metadata_id] = float(st.uint64_value)
                    elif kind == "int64_value":
                        stats[st.metadata_id] = float(st.int64_value)
                line.events.append(XEvent(
                    metadata_id=ev.metadata_id, offset_ps=ev.offset_ps,
                    duration_ps=ev.duration_ps, stats=stats))
            plane.lines.append(line)
        space.planes.append(plane)
    return space


def load_xspace(path: str, prefer_tf: bool = True) -> XSpace:
    """Read one ``.xplane.pb``.  The ``tensorflow.tsl`` proto is used
    when importable (C++ decode of multi-GB chip captures); the
    pure-python reader is the contract and the fallback."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as e:
        raise XplaneParseError(f"cannot read {path}: {e}") from e
    if not data:
        raise XplaneParseError(f"{path}: empty xplane file")
    if prefer_tf:
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
            xs = xplane_pb2.XSpace()
            xs.ParseFromString(data)
            return _from_tf(xs)
        except Exception:   # absent TF / version drift: pure-python path
            pass
    try:
        return parse_xspace(data)
    except XplaneParseError as e:
        raise XplaneParseError(f"{path}: {e}") from e


# ---------------------------------------------------------------------
# kernel classifier: Mosaic/XLA op names -> cost-model entries
# ---------------------------------------------------------------------
# Ordered: first matching class wins.  fused_scan_kernel contains
# "scan_kernel" and the copyback name contains "kernel", so the fused /
# copyback rows must precede partition_scan.  Patterns are substring
# matches on the lowercased op name — Mosaic custom-calls carry the
# ``name=`` of their pallas_call (``lgbm_*`` since PR 27), older captures
# the kernel function names from ops/pallas/*.py.
KERNEL_CLASSES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    # serve_kernel contains "kernel" and the inference dispatch names
    # carry "serve", so the serving traversal row precedes every
    # training class (ISSUE 18)
    ("serve_traverse", ("serve_traverse", "serve_kernel")),
    ("fused_split", ("fused_scan_kernel", "fused_split",
                     "lgbm_split_scan")),
    ("partition_copyback", ("copyback",)),
    # the unfused scan's pallas_call name (the fused one's is
    # lgbm_split_scan): no fused pattern matches it
    ("partition_scan", ("lgbm_partition_scan", "scan_kernel",
                        "partition_kernel", "partition")),
    # refresh_hist_kernel contains "hist_kernel": stream_refresh
    # must be classified before hist_build
    ("stream_refresh", ("refresh_hist_kernel", "refresh_kernel",
                        "init_kernel", "stream_grad", "lgbm_refresh")),
    ("hist_build", ("hist2", "hist_kernel", "histogram", "lgbm_hist")),
    ("find_split", ("apply_find",)),
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective-permute",
                    "allreduce", "allgather", "reducescatter")),
    ("copy", ("copy", "dynamic-update-slice", "dynamic_update_slice",
              "memset")),
)

CLASS_ORDER: Tuple[str, ...] = tuple(c for c, _ in KERNEL_CLASSES) \
    + ("other",)

# which kernel classes execute under which traced obs phase — the
# phase <-> kernel join (host wall minus summed device time = dispatch
# overhead): the two phases whose walls cover WHOLE dispatch windows.
PHASE_KERNELS: Dict[str, Tuple[str, ...]] = {
    "Tree::grow": ("fused_split", "partition_scan",
                   "partition_copyback", "hist_build", "find_split",
                   "collective"),
    "Boosting": ("stream_refresh",),
}

ANNOTATION_PREFIX = "obs::"


def classify_kernel(name: str) -> str:
    low = name.lower()
    for cls, patterns in KERNEL_CLASSES:
        for pat in patterns:
            if pat in low:
                return cls
    return "other"


def _is_device_plane(name: str) -> bool:
    low = name.lower()
    return "/device:tpu" in low or "/device:gpu" in low


def _op_lines(plane: XPlane) -> List[XLine]:
    """The op-level line(s) of a device plane.  TPU planes carry
    several stacked lines (Steps / XLA Modules / XLA Ops / …); summing
    them all would double-count, so prefer lines whose name mentions
    ops and fall back to everything (planes from older jaxlibs name
    lines differently)."""
    ops = [ln for ln in plane.lines if "op" in ln.name.lower()]
    return ops or plane.lines


# stat names that carry an ICI/HBM transfer size on collective events
# (matched lowercased against the plane's stat metadata; jax/XLA
# captures spell it bytes_accessed, TPU collective traces
# transfer_size / bytes_transferred)
BYTES_STAT_NAMES = ("bytes_accessed", "bytes accessed",
                    "transfer_size", "bytes_transferred", "data_size",
                    "payload_size_bytes")


def event_bytes(plane: XPlane, ev: XEvent) -> Optional[int]:
    """The transfer size a device event's stats report, or ``None``
    when no bytes-like stat is attached (older captures)."""
    for mid, val in ev.stats.items():
        name = plane.stat_metadata.get(mid, "").lower()
        if name in BYTES_STAT_NAMES:
            return int(val)
    return None


def plane_collective_events(plane: XPlane) -> List[Dict[str, Any]]:
    """Measured collective traffic on one device plane: per op name,
    occurrence count, device ms and the summed transfer bytes its
    stats report (``bytes`` is ``None`` when the capture carries no
    size stat — measured-vs-predicted validation then has nothing to
    join and ``obs collectives`` says so instead of printing zeros)."""
    agg: Dict[str, Dict[str, Any]] = {}
    for line in _op_lines(plane):
        for ev in line.events:
            name = plane.event_name(ev.metadata_id)
            if classify_kernel(name) != "collective":
                continue
            a = agg.setdefault(name, {"name": name, "count": 0,
                                      "device_ms": 0.0, "bytes": None})
            a["count"] += 1
            a["device_ms"] = round(
                a["device_ms"] + max(int(ev.duration_ps), 0) / 1e9, 6)
            b = event_bytes(plane, ev)
            if b is not None:
                a["bytes"] = (a["bytes"] or 0) + b
    return [agg[k] for k in sorted(agg)]


# ---------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------
def attribute_plane(plane: XPlane) -> Dict[str, Any]:
    """Per-kernel-class device time for one device plane."""
    classes: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, int] = {}
    for line in _op_lines(plane):
        for ev in line.events:
            name = plane.event_name(ev.metadata_id)
            ps = max(int(ev.duration_ps), 0)
            ops[name] = ops.get(name, 0) + ps
            c = classes.setdefault(classify_kernel(name),
                                   {"device_ms": 0.0, "count": 0})
            c["device_ms"] += ps / 1e9
            c["count"] += 1
    for c in classes.values():
        c["device_ms"] = round(c["device_ms"], 6)
    return {
        "plane": plane.name,
        "total_device_ms": round(sum(c["device_ms"]
                                     for c in classes.values()), 6),
        "kernels": classes,
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1]),
    }


def host_annotations(space: XSpace) -> Dict[str, Dict[str, float]]:
    """obs:: TraceAnnotation events on host planes: {phase: {count,
    host_ms}} — proves the tracer<->xplane correlation is live."""
    out: Dict[str, Dict[str, float]] = {}
    for plane in space.planes:
        if _is_device_plane(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_name(ev.metadata_id)
                if not name.startswith(ANNOTATION_PREFIX):
                    continue
                a = out.setdefault(name[len(ANNOTATION_PREFIX):],
                                   {"count": 0, "host_ms": 0.0})
                a["count"] += 1
                a["host_ms"] = round(
                    a["host_ms"] + max(int(ev.duration_ps), 0) / 1e9, 6)
    return out


def device_block(source: str, spaces: Iterable[XSpace],
                 rec: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The ``rec["device"]`` block (schema ``lightgbm_tpu/device/v1``):
    per-plane and aggregate per-kernel device times, mesh straggler
    skew, host-side obs annotations, and — when a traced bench record
    is supplied — the per-phase host-wall-minus-device-time dispatch
    overhead join."""
    planes: List[Dict[str, Any]] = []
    annotations: Dict[str, Dict[str, float]] = {}
    for space in spaces:
        for plane in space.planes:
            if _is_device_plane(plane.name):
                planes.append(attribute_plane(plane))
        for name, a in host_annotations(space).items():
            agg = annotations.setdefault(name,
                                         {"count": 0, "host_ms": 0.0})
            agg["count"] += a["count"]
            agg["host_ms"] = round(agg["host_ms"] + a["host_ms"], 6)
    kernels: Dict[str, Dict[str, float]] = {}
    for p in planes:
        for cls, c in p["kernels"].items():
            agg = kernels.setdefault(cls, {"device_ms": 0.0, "count": 0})
            agg["device_ms"] = round(agg["device_ms"] + c["device_ms"],
                                     6)
            agg["count"] += c["count"]
    block: Dict[str, Any] = {
        "schema": DEVICE_SCHEMA,
        "source": source,
        "planes": [{"plane": p["plane"],
                    "total_device_ms": p["total_device_ms"],
                    "kernels": p["kernels"]} for p in planes],
        "kernels": kernels,
    }
    if len(planes) > 1:
        totals = [p["total_device_ms"] for p in planes]
        hi, lo = max(totals), min(totals)
        block["skew"] = {"max_ms": hi, "min_ms": lo,
                         "ratio": round(hi / lo, 4) if lo > 0 else None}
        # straggler ROOT CAUSE (ISSUE 8 tentpole 3): not just the skew
        # magnitude — name which shard plane is slow, and rank the
        # per-kernel-class device-time deltas vs the fastest plane so
        # the report says which kernel class (and therefore which
        # traced phase, via PHASE_KERNELS) the excess time sits in.
        # Suppressed below 1% skew: a balanced mesh must not render a
        # self-vs-self "straggler" out of tie/noise totals.
        if lo > 0 and hi / lo >= 1.01:
            slow = planes[totals.index(hi)]
            fast = planes[totals.index(lo)]
            kernel_phase = {cls: phase
                            for phase, classes in PHASE_KERNELS.items()
                            for cls in classes}
            causes: List[Dict[str, Any]] = []
            for cls in set(slow["kernels"]) | set(fast["kernels"]):
                d = (slow["kernels"].get(cls, {}).get("device_ms", 0.0)
                     - fast["kernels"].get(cls, {}).get("device_ms",
                                                        0.0))
                if d > 0:
                    causes.append({"kernel": cls,
                                   "delta_ms": round(d, 6),
                                   "phase": kernel_phase.get(cls,
                                                             "-")})
            causes.sort(key=lambda c: (-c["delta_ms"], c["kernel"]))
            block["straggler"] = {"plane": slow["plane"],
                                  "vs_plane": fast["plane"],
                                  "delta_ms": round(hi - lo, 6),
                                  "causes": causes}
    if annotations:
        block["annotations"] = annotations
    if rec:
        phases = rec.get("phases") or {}
        join: Dict[str, Dict[str, float]] = {}
        for phase, classes in PHASE_KERNELS.items():
            wall = phases.get(phase)
            if not isinstance(wall, dict):
                continue
            # shard planes run CONCURRENTLY: the host wall contains the
            # straggler plane's device time, not the cross-plane sum —
            # so the join takes the max per plane (single-plane runs
            # are unchanged)
            per_plane = [round(sum(p["kernels"].get(c, {})
                                   .get("device_ms", 0.0)
                                   for c in classes), 6)
                         for p in planes]
            dev_ms = max(per_plane) if per_plane else 0.0
            wall_ms = round(float(wall.get("total_s", 0.0)) * 1e3, 6)
            join[phase] = {
                "host_wall_ms": wall_ms,
                "device_ms": dev_ms,
                "dispatch_overhead_ms": round(wall_ms - dev_ms, 6),
            }
        if join:
            block["phases"] = join
    # keep the per-plane top-op lists out of the stored block (records
    # stay small); run_attr re-derives them for display
    return block


def resolve_capture(path: str) -> List[str]:
    """A capture dir (recursive ``*.xplane.pb`` glob) or one ``.pb``
    file -> ordered path list.  Raises ``XplaneParseError`` with an
    actionable message (the exit-2 contract) when there is nothing to
    decode."""
    if not os.path.exists(path):
        raise XplaneParseError(
            f"{path}: no such file or directory (expected an xplane "
            "capture dir or a .xplane.pb file)")
    if os.path.isdir(path):
        paths = sorted(glob.glob(os.path.join(path, "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise XplaneParseError(
                f"{path}: empty capture dir — no *.xplane.pb under it "
                "(did the profiler run? capture with LGBM_TPU_XPLANE="
                "dir or jax.profiler.trace)")
        return paths
    return [path]


def load_capture(path: str, prefer_tf: bool = True
                 ) -> List[Tuple[str, XSpace]]:
    return [(p, load_xspace(p, prefer_tf=prefer_tf))
            for p in resolve_capture(path)]


# ---------------------------------------------------------------------
# rendering (the `obs attr` table; exact output pinned by the CI leg)
# ---------------------------------------------------------------------
def _fmt_ms(ms: float) -> str:
    return f"{ms:10.3f}"


def render_attr(block: Dict[str, Any], *,
                planes_detail: Optional[List[Dict[str, Any]]] = None,
                model: Optional[Dict[str, Dict[str, float]]] = None,
                roofline: bool = False, peak_bw_gbps: float = 0.0,
                top: int = 0) -> List[str]:
    """Format a device block (+ optional cost-model join) as the attr
    table lines.  Deterministic: classes render in KERNEL_CLASSES
    order, raw ops by descending time then name."""
    lines: List[str] = []
    header = f"  {'kernel':<20} {'device ms':>10} {'count':>6}"
    if model is not None:
        header += f" {'pred GB':>9} {'GB/s':>8}"
        if roofline:
            header += f" {'%bw':>7}  bound"
    for p in (planes_detail or []):
        lines.append(f"plane {p['plane']}: "
                     f"{p['total_device_ms']:.3f} ms device time")
        for cls in CLASS_ORDER:
            c = p["kernels"].get(cls)
            if not c:
                continue
            lines.append(f"  {cls:<20} {_fmt_ms(c['device_ms'])} "
                         f"{c['count']:>6}")
        for name, ps in sorted(p.get("top_ops", []),
                               key=lambda kv: (-kv[1], kv[0]))[:top]:
            lines.append(f"    {ps / 1e9:10.3f} ms  {name[:90]}")
    kernels = block.get("kernels", {})
    total_ms = sum(c["device_ms"] for c in kernels.values())
    lines.append(f"kernel attribution ({len(block.get('planes', []))} "
                 f"device plane(s), {total_ms:.3f} ms device time):")
    lines.append(header)
    for cls in CLASS_ORDER:
        c = kernels.get(cls)
        if not c:
            continue
        row = f"  {cls:<20} {_fmt_ms(c['device_ms'])} {c['count']:>6}"
        pred = (model or {}).get(cls)
        if model is not None:
            if pred and pred.get("bytes") and c["device_ms"] > 0:
                gb = pred["bytes"] / 1e9
                gbps = pred["bytes"] / (c["device_ms"] / 1e3) / 1e9
                row += f" {gb:>9.3f} {gbps:>8.1f}"
                if roofline:
                    util = gbps / peak_bw_gbps
                    row += f" {util:>7.1%}  " + \
                        ("memory" if util >= 0.5 else "dispatch/compute")
            else:
                row += f" {'-':>9} {'-':>8}"
                if roofline:
                    row += f" {'-':>7}"
        lines.append(row)
    skew = block.get("skew")
    if skew:
        ratio = skew.get("ratio")
        lines.append(f"shard skew: slowest plane {skew['max_ms']:.3f} ms"
                     f" vs fastest {skew['min_ms']:.3f} ms"
                     + (f" (x{ratio:g})" if ratio else ""))
    straggler = block.get("straggler")
    if straggler:
        lines.append(f"straggler root-cause: {straggler['plane']} "
                     f"(+{straggler['delta_ms']:.3f} ms vs "
                     f"{straggler['vs_plane']}):")
        for c in straggler["causes"]:
            lines.append(f"  {'+' + format(c['delta_ms'], '.3f'):>9} "
                         f"ms  {c['kernel']:<20} phase {c['phase']}")
    for phase, j in (block.get("phases") or {}).items():
        lines.append(
            f"phase {phase}: host wall {j['host_wall_ms']:.3f} ms, "
            f"device {j['device_ms']:.3f} ms, dispatch overhead "
            f"{j['dispatch_overhead_ms']:.3f} ms")
    for name, a in sorted((block.get("annotations") or {}).items()):
        lines.append(f"annotation obs::{name}: x{a['count']}, "
                     f"{a['host_ms']:.3f} ms host")
    return lines


def run_attr(xplane: str, *, bench: str = "", roofline: bool = False,
             peak_bw: float = 0.0, top: int = 0, json_out: str = "",
             prefer_tf: bool = True) -> int:
    """``python -m lightgbm_tpu.obs attr`` body.  Exit codes: 0
    attributed; 1 capture decoded but holds no TPU/GPU device plane;
    2 unreadable input (missing path / empty dir / truncated pb /
    unreadable bench record)."""
    from .findings import cli_error
    try:
        loaded = load_capture(xplane, prefer_tf=prefer_tf)
    except XplaneParseError as e:
        return cli_error("obs attr", e)
    rec = None
    if bench:
        from .regress import load_record
        try:
            rec = load_record(bench)
        except ValueError as e:
            return cli_error("obs attr", e)
    print(f"obs attr: {xplane}: {len(loaded)} xplane file(s)")
    spaces = [s for _, s in loaded]
    block = device_block(xplane, spaces, rec=rec)
    if not block["planes"]:
        names = [p.name for s in spaces for p in s.planes]
        print("obs attr: no TPU/GPU device plane in the capture "
              f"(planes: {', '.join(names) or '(none)'}) — host-only "
              "trace? device attribution needs a chip run")
        for name, a in sorted((block.get("annotations") or {}).items()):
            print(f"  annotation obs::{name}: x{a['count']}, "
                  f"{a['host_ms']:.3f} ms host")
        return 1
    model = None
    peak = peak_bw
    if rec is not None:
        from .costmodel import (RecordModelError, kernel_model,
                                roofline_peak)
        try:
            model = kernel_model(rec)
            if roofline:
                peak = roofline_peak(rec, "bw_gbps", peak or None)
        except RecordModelError as e:
            print(f"obs attr: cost-model join skipped: {e}")
            model = None
    planes_detail = None
    if top:
        planes_detail = []
        for space in spaces:
            for plane in space.planes:
                if _is_device_plane(plane.name):
                    planes_detail.append(attribute_plane(plane))
    if roofline and model is not None:
        print(f"roofline peak {peak:g} GB/s")
    for line in render_attr(block, planes_detail=planes_detail,
                            model=model, roofline=roofline,
                            peak_bw_gbps=peak or 1.0, top=top):
        print(line)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(block, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"device block -> {json_out}")
    return 0


# ---------------------------------------------------------------------
# synthetic fixture (tests + the CI attr leg; checked in under
# tests/data/ — regenerate with `python -m lightgbm_tpu.obs.xattr`)
# ---------------------------------------------------------------------
def synthetic_xspace(device_planes: int = 2,
                     with_host_plane: bool = True) -> XSpace:
    """A deterministic XSpace shaped like a mesh chip capture: one "XLA
    Ops" line per device plane with one event per kernel class (shard 1
    runs 10% slower — measured straggler skew), plus a host plane
    carrying obs:: TraceAnnotation TraceMe events."""
    meta = {
        1: "_fused_scan_kernel",
        2: "_copyback_kernel",
        3: "_hist2_comb_kernel",
        4: "_refresh_hist_kernel",
        5: "_apply_find_kernel",
        6: "all-reduce.7",
        7: "fusion.42",
    }
    base_ps = {1: 6_000_000_000, 2: 1_500_000_000, 3: 2_000_000_000,
               4: 3_000_000_000, 5: 500_000_000, 6: 250_000_000,
               7: 750_000_000}
    space = XSpace(hostnames=["synthetic"])
    for d in range(device_planes):
        scale = 11 if d == 1 else 10    # shard 1 is the straggler
        events = []
        offset = 0
        for mid in sorted(base_ps):
            dur = base_ps[mid] * scale // 10
            events.append(XEvent(metadata_id=mid, offset_ps=offset,
                                 duration_ps=dur))
            offset += dur
        space.planes.append(XPlane(
            id=d + 1, name=f"/device:TPU:{d}",
            lines=[XLine(id=1, name="XLA Ops", timestamp_ns=1000,
                         events=events)],
            event_metadata=dict(meta)))
    if with_host_plane:
        hmeta = {1: "obs::Tree::grow", 2: "obs::Boosting",
                 3: "python_call"}
        hevents = [XEvent(metadata_id=1, offset_ps=0,
                          duration_ps=50_000_000_000),
                   XEvent(metadata_id=2, offset_ps=50_000_000_000,
                          duration_ps=10_000_000_000),
                   XEvent(metadata_id=3, offset_ps=0,
                          duration_ps=1_000_000)]
        space.planes.append(XPlane(
            id=99, name="/host:CPU",
            lines=[XLine(id=1, name="python", timestamp_ns=1000,
                         events=hevents)],
            event_metadata=hmeta))
    return space


def synthetic_bench_record() -> Dict[str, Any]:
    """The traced bench/v3 record the fixture's cost-model join uses:
    fused, streamed — so fused_split and stream_refresh carry
    the byte contracts and the table exercises the achieved-GB/s
    column."""
    return {
        "schema": "lightgbm_tpu/bench/v3",
        "metric": "synthetic_attr_fixture",
        "value": 1.0,
        "unit": "iters/sec",
        "backend": "tpu",
        "provenance": {"device_kind": "TPU v5 lite"},
        "counters": {"splits": 30.0, "rows_partitioned": 200000.0,
                     "rows_histogrammed": 150000.0, "fused_splits": 30.0},
        "shape": {"rows": 10000, "features": 28, "f_pad": 32,
                  "padded_bins": 256, "trees": 3, "stream": True},
        "knobs": {"partition": "permute", "fused": True},
        "phases": {"Tree::grow": {"total_s": 0.05, "count": 3,
                                  "mean_s": 0.05 / 3},
                   "Boosting": {"total_s": 0.012, "count": 3,
                                "mean_s": 0.004}},
    }


def write_synthetic_fixture(pb_path: str,
                            bench_path: str = "") -> None:
    with open(pb_path, "wb") as f:
        f.write(encode_xspace(synthetic_xspace()))
    if bench_path:
        with open(bench_path, "w") as f:
            json.dump(synthetic_bench_record(), f, indent=1,
                      sort_keys=True)
            f.write("\n")


# ---------------------------------------------------------------------
# mesh fixture (ISSUE 8): a multi-plane capture with COLLECTIVE events
# carrying transfer-size stats, plus the matching traced multichip
# bench record — what `obs collectives` joins.  Byte accounting is
# EXACT by construction: per shard plane, 2 reduce-scatter events of
# MESH_DISPATCH_BYTES each == the 2 ledger dispatch rows' bytes_moved.
# ---------------------------------------------------------------------
MESH_SHARDS = 8
MESH_DISPATCHES = 2
# hist payload [f_pad=32, padded_bins=64, 2ch] f32 = 16384 B;
# psum_scatter ring factor (8-1)/8 over 15 merges (num_leaves)
MESH_DISPATCH_BYTES = int(16384 * 7 / 8) * 15          # 215040


def synthetic_mesh_xspace() -> XSpace:
    """A deterministic mesh capture: one device plane per shard, each
    with 2 reduce-scatter events whose ``bytes_accessed`` stat carries
    the per-dispatch transfer size, one all-reduce WITHOUT a bytes
    stat (the no-stat rendering path), and one non-collective fusion.
    Shard 3 runs its collectives 30% slower — a measured straggler for
    the root-cause path."""
    space = XSpace(hostnames=["synthetic-mesh"])
    meta = {1: "reduce-scatter.11", 2: "all-reduce.3", 3: "fusion.1"}
    stat_meta = {1: "bytes_accessed"}
    for d in range(MESH_SHARDS):
        scale = 13 if d == 3 else 10     # shard 3 is the straggler
        events = []
        offset = 0
        for _ in range(MESH_DISPATCHES):
            dur = 400_000_000 * scale // 10
            events.append(XEvent(metadata_id=1, offset_ps=offset,
                                 duration_ps=dur,
                                 stats={1: MESH_DISPATCH_BYTES}))
            offset += dur
        events.append(XEvent(metadata_id=2, offset_ps=offset,
                             duration_ps=50_000_000))
        offset += 50_000_000
        events.append(XEvent(metadata_id=3, offset_ps=offset,
                             duration_ps=1_000_000_000 * scale // 10))
        space.planes.append(XPlane(
            id=d + 1, name=f"/device:TPU:{d}",
            lines=[XLine(id=1, name="XLA Ops", timestamp_ns=1000,
                         events=events)],
            event_metadata=dict(meta),
            stat_metadata=dict(stat_meta)))
    return space


def synthetic_multichip_record() -> Dict[str, Any]:
    """The traced multichip bench/v3 record the mesh fixture joins:
    per-dispatch ledger collective rows keyed by shard id, the ledger
    ``mesh`` skew-series summary, and the ``multichip`` block
    (tools/multichip_probe.py shape)."""
    shards = MESH_SHARDS
    per_dispatch = MESH_DISPATCH_BYTES
    rows_per_shard = 1024.0
    colls = []
    for _ in range(MESH_DISPATCHES):
        colls.append({
            "name": "DataParallelGrower::psum_scatter",
            "bytes_moved": per_dispatch,
            "shards": shards,
            "per_shard": {
                "inbag_rows": [rows_per_shard] * shards,
                "bytes": [per_dispatch] * shards,
            },
            "skew_max": rows_per_shard,
            "skew_min": rows_per_shard,
            "wall_s": 0.02,
            "merges_est": 15,
        })
    total = per_dispatch * MESH_DISPATCHES
    return {
        "schema": "lightgbm_tpu/bench/v3",
        "metric": f"multichip_iters_per_sec_data{shards}",
        "value": 2.0,
        "unit": "iters/sec",
        "backend": "tpu",
        "traced": True,
        "counters": {"splits": 28.0, "rows_partitioned": 160000.0,
                     "rows_histogrammed": 120000.0,
                     "fused_splits": 28.0},
        "shape": {"rows": 8192, "features": 20, "f_pad": 32,
                  "padded_bins": 64, "trees": MESH_DISPATCHES,
                  "stream": False},
        "knobs": {"partition": "permute",
                  "fused": True, "tree_learner": "data"},
        "phases": {"Tree::grow": {"total_s": 0.04,
                                  "count": MESH_DISPATCHES,
                                  "mean_s": 0.02}},
        "ledger": {
            "schema": "lightgbm_tpu/ledger/v1",
            "iterations": [
                {"iteration": i, "phases": {"Tree::grow": 0.02},
                 "counters": {"splits": 14.0}, "wall_s": 0.5}
                for i in range(MESH_DISPATCHES)],
            "collectives": colls,
            "mesh": {
                "dispatches": MESH_DISPATCHES,
                "shards": shards,
                "bytes_moved_total": total,
                "per_shard": {
                    "inbag_rows": [rows_per_shard * MESH_DISPATCHES]
                    * shards,
                    "bytes": [total] * shards,
                },
                "skew_series": [1.0] * MESH_DISPATCHES,
                "skew_max_ratio": 1.0,
                "skew_median_ratio": 1.0,
            },
        },
        "multichip": {
            "schema": "lightgbm_tpu/multichip/v1",
            "mesh": {"axes": {"data": shards}, "n_devices": shards,
                     "n_shards": shards, "device_kind": "synthetic"},
            "n_shards": shards,
            "learner": "data",
            "physical": True,
            "hist_scatter": True,
            "events": {},
        },
    }


def write_synthetic_mesh_fixture(pb_path: str,
                                 bench_path: str = "") -> None:
    with open(pb_path, "wb") as f:
        f.write(encode_xspace(synthetic_mesh_xspace()))
    if bench_path:
        with open(bench_path, "w") as f:
            json.dump(synthetic_multichip_record(), f, indent=1,
                      sort_keys=True)
            f.write("\n")


if __name__ == "__main__":   # fixture regeneration helper
    import sys
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tests", "data")
    os.makedirs(here, exist_ok=True)
    pb = os.path.join(here, "synthetic.xplane.pb")
    bench = os.path.join(here, "synthetic_bench.json")
    write_synthetic_fixture(pb, bench)
    print(f"wrote {pb} and {bench}", file=sys.stderr)
    mesh_pb = os.path.join(here, "synthetic_mesh.xplane.pb")
    mesh_bench = os.path.join(here, "synthetic_mesh_bench.json")
    write_synthetic_mesh_fixture(mesh_pb, mesh_bench)
    print(f"wrote {mesh_pb} and {mesh_bench}", file=sys.stderr)
    print("regenerate the pinned tables with:\n"
          "  python -m lightgbm_tpu.obs attr tests/data/synthetic"
          ".xplane.pb --bench tests/data/synthetic_bench.json "
          "--roofline --no-tf > tests/data/synthetic_attr_expected"
          ".txt\n"
          "  python -m lightgbm_tpu.obs collectives tests/data/"
          "synthetic_mesh.xplane.pb --bench tests/data/synthetic_"
          "mesh_bench.json --no-tf > tests/data/synthetic_"
          "collectives_expected.txt", file=sys.stderr)
