"""Idle time of one chip in the traced slice, booked to what the host
was doing beneath it, over a counter of the run; ``scale`` takes
seconds to the metric's unit.

While a capture is live the program's tracer mirrors every span as
``obs::<name>`` on the device's clock (``trace.SliceTrace.host_spans``).
``trace.breakdown`` names a gap by the one span open at its middle; a
10 ms gap from the end of one barrier to the start of the next program
lies under four spans.  Here every idle gap of the chip is cut at the
edges of the mirrored spans and each piece is booked to the innermost
span open over it (the shortest; the slice's own marker is no span),
or to ``outside any host span``.

* ``spans``: the names (without ``obs::``) whose pieces are summed;
* ``but: true``: all the pieces but theirs.

So two metrics with the same ``spans``, one of them ``but``, add up to
the chip's whole idle time.  None without a slice or the counter."""
import bisect

OUTSIDE = "outside any host span"


def idle_ns_by_span(sliced, device=0):
    """``{span name: idle ns}``; names as mirrored (``obs::<name>``)."""
    spans = [(s, e, name) for name, s, e in sliced.host_spans
             if name.startswith("obs::") and e > s]
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    # the innermost span over each stretch between two edges
    over = []
    for lo, hi in zip(edges, edges[1:]):
        open_ = [(e - s, name) for s, e, name in spans
                 if s <= lo and hi <= e]
        over.append(min(open_)[1] if open_ else OUTSIDE)
    total = {}

    def book(name, ns):
        if ns > 0:
            total[name] = total.get(name, 0) + ns

    for s, e in sliced.idle_gaps(device):
        if not edges or e <= edges[0] or s >= edges[-1]:
            book(OUTSIDE, e - s)
            continue
        book(OUTSIDE, edges[0] - s)
        book(OUTSIDE, e - edges[-1])
        i = max(bisect.bisect_right(edges, s) - 1, 0)
        while i + 1 < len(edges) and edges[i] < e:
            book(over[i], min(e, edges[i + 1]) - max(s, edges[i]))
            i += 1
    return total


def reduce(obs, spans, per_counter, but=False, scale=1.0, device=0):
    sliced = obs["slice"]
    count = obs["counters"].get(per_counter, 0)
    if sliced is None or device not in sliced.devices or not count:
        return None
    named = {"obs::" + n for n in spans}
    ns = sum(v for k, v in idle_ns_by_span(sliced, device).items()
             if (k in named) != bool(but))
    return ns / 1e9 * scale / count
