"""Self time on one chip of the ops that ARE one of ``patterns``, in
the traced slice, over the sum of a span arg across the slice's spans
(``device_op_self_per``'s count): the collectives' time over
``Tree::grow.splits`` of the slice's trees is what one split pays for
its merges.  ``scale`` takes seconds to the metric's unit.  None where
the chip ran no such op (one chip has no collective) or the count is
missing.

An ``XLA Ops`` event is named by its whole HLO instruction, operands
included, so a pattern is looked for in the instruction's own name and
opcode only (``trace.short_op_name``: ``%pmin.21 all-reduce``): a
fusion that merely reads ``%collective-permute-done.2`` is not a
collective.  (``device_op_share`` looks in the whole text and counts
those fusions too; on the four-chip cell they are 3% of what it
reads, ``PERF.md`` section 5.)"""
from trace import short_op_name


def pattern_self_s(sliced, patterns, device=0):
    """Seconds, or None where no op of such a name ran."""
    if sliced is None or device not in sliced.devices:
        return None
    hits = [ns for name, ns in
            sliced.devices[device].self_ns_by_name().items()
            if any(p in short_op_name(name) for p in patterns)]
    return sum(hits) / 1e9 if hits else None


def reduce(obs, patterns, per_span_arg, scale=1.0, device=0):
    seconds = pattern_self_s(obs["slice"], patterns, device)
    if seconds is None:
        return None
    first = int(obs["counters"].get(per_span_arg["first"], 0))
    spans = sorted((e for e in obs["spans"]
                    if e["name"] == per_span_arg["span"]),
                   key=lambda e: e["ts"])[:first]
    if len(spans) < first or any(
            per_span_arg["arg"] not in e["args"] for e in spans):
        return None
    count = sum(e["args"][per_span_arg["arg"]] for e in spans)
    if not count:
        return None
    return seconds * scale / count
