"""Self time on one chip of the ops that serve one phase of the
algorithm, in the traced slice, over a counter of the run
(``device_op_self_per``'s ``per_counter``); ``scale`` takes seconds to
the metric's unit.

The program traces every op under one ``jax.named_scope("lgbm.<phase>")``
and the compiler keeps the scope in each instruction's ``metadata``,
which an ``XLA Ops`` event does not print: the event is named by the
instruction alone, ``%fusion.9 = f32[32,256]{1,0:T(8,128)} fusion(...)``,
and ``%fusion.9`` is another op after the next change.  So while a
capture is live the program's tracer writes, for each program an
iteration dispatches, one ``X`` event ``Program::ops`` (args
``program``, ``ops``: ``{phase: [key, ...]}``, ``""`` for the
instructions under no phase) into the spans.  A key is the
instruction's name and its result's shape without layouts
(``fusion.9 f32[32,256]``), which is how ``op_key`` here reads an event.

* ``phase``: the ``lgbm.<phase>`` to sum.
* ``phase: null``: the ops under no phase: in no table (the eager
  one-op programs of an iteration), under ``""`` in theirs (a nested
  library ``jit``), or ambiguous - a key that two programs of the
  slice put under different phases is never guessed.  With
  ``share: true`` as a percentage of the chip's busy time, which the
  self times of all its ops add up to.

None where the spans hold no ``Program::ops`` (a program without
phases: the parent of PR 38), where no op of the phase ran, or the
count is missing."""
import re

TABLE_EVENT = "Program::ops"
AMBIGUOUS = "ambiguous"
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
# a layout, or the ``/*index=5*/`` a long tuple is printed with
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")


def op_key(event_name):
    """``%fusion.9 = f32[32,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.9 f32[32,256]``; an event that is no instruction keeps its
    name."""
    head, sep, rest = event_name.partition(" = ")
    m = _OPCODE.search(rest) if sep else None
    if m is None:
        return event_name
    return head.lstrip("%") + " " + _LAYOUT.sub("", rest[:m.start()]).strip()


def phase_of_key(spans):
    """``{key: phase}`` over the ``Program::ops`` tables of the window
    (``""``: under no phase; ``AMBIGUOUS``: two programs disagree), or
    None where there is no table.  A program described twice (two
    captures) counts once."""
    tables = {}
    for e in spans:
        if e["name"] == TABLE_EVENT:
            tables[e["args"]["program"]] = e["args"]["ops"]
    if not tables:
        return None
    phase_of = {}
    for ops in tables.values():
        for phase, keys in ops.items():
            for key in keys:
                if phase_of.setdefault(key, phase) != phase:
                    phase_of[key] = AMBIGUOUS
    return phase_of


def phase_self_ns(obs, device=0):
    """``{phase: ns}`` of one chip's ops in the slice, ``None`` keyed
    for the ops under no phase; None where slice or tables are
    missing."""
    sliced = obs["slice"]
    if sliced is None or device not in sliced.devices:
        return None
    phase_of = phase_of_key(obs["spans"])
    if phase_of is None:
        return None
    total = {}
    for name, ns in sliced.devices[device].self_ns_by_name().items():
        phase = phase_of.get(op_key(name), "")
        if phase in ("", AMBIGUOUS):
            phase = None
        total[phase] = total.get(phase, 0) + ns
    return total


def reduce(obs, phase, per_counter=None, share=False, scale=1.0, device=0):
    by_phase = phase_self_ns(obs, device)
    if by_phase is None or (phase is not None and phase not in by_phase):
        return None
    ns = by_phase.get(phase, 0)
    if share:
        busy = sum(by_phase.values())
        return 100.0 * ns / busy if busy else None
    count = obs["counters"].get(per_counter, 0)
    if not count:
        return None
    return ns / 1e9 * scale / count
