"""Median over the window of a span's duration minus the named spans
that lie inside it (``minus``): ``Train::iteration`` minus the
``Tree::grow`` inside it is the boosting loop's own time.  Milliseconds
when ``scale`` is 1000."""
import statistics


def reduce(obs, span, minus=(), scale=1000.0):
    spans = obs["spans"]
    outer = [e for e in spans if e["name"] == span]
    if not outer:
        return None
    inner = [e for e in spans if e["name"] in minus]

    def inside(e, o):
        return (e["tid"] == o["tid"] and e["ts"] >= o["ts"]
                and e["ts"] + e["dur"] <= o["ts"] + o["dur"])

    own = [o["dur"] - sum(e["dur"] for e in inner if inside(e, o))
           for o in outer]
    return statistics.median(own) / 1e6 * scale
