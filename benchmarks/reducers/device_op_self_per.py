"""Self time on one chip of the ops the program named ``op`` (an ``XLA
Ops`` event is named by its HLO instruction, ``%<op>.<n> = ...``; the
program names its Pallas kernels with ``pallas_call(name=...)``), in
the traced slice, over a count of work done in the same slice:

* ``per_counter``: a counter of the run (``slice_iterations``), or
* ``per_span_arg``: ``{"span", "arg", "first"}`` - the sum of the span
  arg ``arg`` over the first ``counters[first]`` spans named ``span`` of
  the window, which are the slice's (``Tree::grow.rows_partitioned`` of
  the slice's iterations: the rows the scans visited).

``scale`` takes seconds to the metric's unit.  None where the slice
holds no such op or the count is missing: a program that does not name
its kernels, or does not put the counter on the span."""
import re


def op_self_s(sliced, op, device=0):
    """Seconds, or None where no op of that name ran."""
    if sliced is None or device not in sliced.devices:
        return None
    named = re.compile(r"%?" + re.escape(op) + r"(\.\d+)?(\s|$)")
    hits = [ns for name, ns in
            sliced.devices[device].self_ns_by_name().items()
            if named.match(name)]
    return sum(hits) / 1e9 if hits else None


def reduce(obs, op, per_counter=None, per_span_arg=None, scale=1.0,
           device=0):
    seconds = op_self_s(obs["slice"], op, device)
    if seconds is None:
        return None
    if per_counter is not None:
        count = obs["counters"].get(per_counter, 0)
    else:
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
