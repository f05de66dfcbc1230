"""Device-mesh construction and sharding helpers.

Reference analog: the Network layer's machine-list / rank wiring
(src/network/linkers_socket.cpp:24-67).  On TPU there is no transport to
build: a ``jax.sharding.Mesh`` over the local (or multi-host) device set IS
the network, and XLA inserts ICI/DCN collectives from sharding annotations.
``config.tpu_mesh_axes`` ("data:8" or "data:4,feature:2") pins a shape;
otherwise the full device count goes to the data axis.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..utils import log

DATA_AXIS = "data"
FEATURE_AXIS = "feature"


def parse_mesh_axes(spec: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition(":")
        out[name.strip()] = int(size)
    return out


def build_mesh(config: Optional[Config] = None,
               devices: Optional[List] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    axes = parse_mesh_axes(config.tpu_mesh_axes) if config else {}
    if not axes:
        axes = {DATA_AXIS: n}
    total = int(np.prod(list(axes.values())))
    if total != n:
        log.fatal("Mesh axes %s need %d devices but %d are available",
                  axes, total, n)
    arr = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def row_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    spec = [None] * ndim
    spec[0] = DATA_AXIS
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_rows_to_shards(n: int, num_shards: int, block: int = 1) -> int:
    """Rows must divide evenly across shards (and histogram row blocks)."""
    per = -(-n // num_shards)
    per = -(-per // block) * block
    return per * num_shards


def mesh_desc(mesh: Mesh) -> Dict[str, object]:
    """JSON-able mesh geometry for telemetry artifacts (the
    ``multichip`` block of bench/v3 records, ``tools/multichip_probe``):
    axis sizes, total device count and the device kind — everything a
    diff needs to judge two mesh records comparable (shard-count
    mismatch = incomparable) without identifying the machine."""
    axes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    devs = list(np.asarray(mesh.devices).reshape(-1))
    kinds = sorted({getattr(d, "device_kind", "unknown") for d in devs})
    return {
        "axes": axes,
        "n_devices": len(devs),
        "n_shards": axes.get(DATA_AXIS, len(devs)),
        "device_kind": kinds[0] if len(kinds) == 1 else kinds,
    }
