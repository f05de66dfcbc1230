"""Self time on one chip of the ops of one phase of the algorithm, in
the traced slice (``device_phase_self_per``'s reading, the phase tables
of ``Program::ops``), over the work a span's args count in the same
slice: the sum, over the first ``counters[first]`` spans named ``span``
of the window (the slice's), of the product of the ``args``, each
divided by ``per`` where given.  ``valid``'s time over
``UpdateScore::tail``'s ``valid_rows x replay_steps / valid_sets`` is
what one step of the valid walk costs a row.  ``scale`` takes seconds to
the metric's unit.  None where the phase did not run, the spans do not
carry the args, or the count is missing."""
import importlib.util
import math
import os

# the reducer beside this one, found by path as run.py finds reducers
_spec = importlib.util.spec_from_file_location(
    "bench_reducers_device_phase_self_per",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "device_phase_self_per.py"))
_phases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_phases)


def reduce(obs, phase, span, args, first="slice_iterations", per=None,
           scale=1.0, device=0):
    by_phase = _phases.phase_self_ns(obs, device)
    if by_phase is None or phase not in by_phase:
        return None
    n = int(obs["counters"].get(first, 0))
    spans = sorted((e for e in obs["spans"] if e["name"] == span),
                   key=lambda e: e["ts"])[:n]
    if not n or len(spans) < n or any(
            a not in e["args"] for e in spans for a in args + [per] if a):
        return None
    work = sum(math.prod(e["args"][a] for a in args)
               / (e["args"][per] if per else 1) for e in spans)
    if not work:
        return None
    return by_phase[phase] / 1e9 * scale / work
