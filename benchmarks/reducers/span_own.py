"""A span's own time over the window: its duration minus the spans
inside it that are named in ``minus`` or end in ``minus_suffix``,
reduced by ``stat`` (``median`` or ``max``).  Milliseconds when
``scale`` is 1000.

* ``Train::iteration`` minus every ``*::wait`` inside it, median: what
  the host spent of its own per iteration, apart from waiting for the
  device (``obs/tracer.py`` records each barrier as a ``<name>::wait``
  child; they never nest in one another).  ``needs_minus``: None where
  the window holds no such span at all, a program whose barriers have
  no name.
* ``Train::iteration`` minus its ``Callbacks``, max: the program's
  longest iteration.  A median hides one stalled iteration, this is
  made of it; the benchmark's own callback stops the profiler inside
  the iteration that closes the slice, 1.2 s that are not the
  program's."""
import statistics

STATS = {"median": statistics.median, "max": max}


def reduce(obs, span, stat, minus=(), minus_suffix=None,
           needs_minus=False, scale=1000.0):
    spans = obs["spans"]
    outer = [e for e in spans if e["name"] == span]
    inner = [e for e in spans if e["name"] in minus or (
        minus_suffix is not None and e["name"].endswith(minus_suffix))]
    if not outer or (needs_minus and not inner):
        return None

    def inside(e, o):
        return (e["tid"] == o["tid"] and e["ts"] >= o["ts"]
                and e["ts"] + e["dur"] <= o["ts"] + o["dur"])

    own = [o["dur"] - sum(e["dur"] for e in inner if inside(e, o))
           for o in outer]
    return STATS[stat](own) / 1e6 * scale
