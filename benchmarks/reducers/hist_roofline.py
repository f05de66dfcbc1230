"""A kernel's share of its roofline, in percent: the least time the
chip could take for the work it did in the traced slice - the larger of
its MXU operations over the peak bf16 rate and its HBM bytes over the
peak bandwidth (``peaks.py`` by the device JAX reports, or
``device_kind``) - over the self
time of the ops named ``op`` on one chip.  The work is ``hist_work``'s,
from ``Tree::grow``'s args of the slice's iterations: ``rows_arg``
visits swept in ``hist_tiles`` tiles, at ``padded_bins`` bins a column.

None where no op of that name ran, or the spans do not carry the
counts: a program from before the tiled histogram has no
``hist_tiles``."""
import re

import hist_work
import peaks


def reduce(obs, op, padded_bins, rows_arg="rows_histogrammed",
           first="slice_iterations", device=0, device_kind=None):
    sliced = obs["slice"]
    if sliced is None or device not in sliced.devices:
        return None
    named = re.compile(r"%?" + re.escape(op) + r"(\.\d+)?(\s|$)")
    ns = [v for name, v in sliced.devices[device].self_ns_by_name().items()
          if named.match(name)]
    if not ns:
        return None
    n_first = int(obs["counters"].get(first, 0))
    spans = sorted((e for e in obs["spans"] if e["name"] == "Tree::grow"),
                   key=lambda e: e["ts"])[:n_first]
    if not n_first or len(spans) < n_first or any(
            rows_arg not in e["args"] or "hist_tiles" not in e["args"]
            for e in spans):
        return None
    flops = sum(hist_work.hist_flops(e["args"][rows_arg],
                                     int(e["args"]["hist_tiles"]),
                                     padded_bins) for e in spans)
    nbytes = sum(hist_work.hist_bytes(e["args"][rows_arg],
                                      int(e["args"]["hist_tiles"]))
                 for e in spans)
    if device_kind is None:
        import jax
        device_kind = jax.devices()[device].device_kind
    peak = peaks.of(device_kind)
    least_s = max(flops / peak["bf16_flops_per_s"],
                  nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(ns) / 1e9)
