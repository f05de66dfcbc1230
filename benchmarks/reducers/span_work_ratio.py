"""The ratio of two span args summed over the window, where numerator
and denominator come from spans of different names, times ``scale``:
``num`` and ``den`` are each ``{"span", "arg"}`` with an optional
``"per"``, an arg of the same span each value is divided by.  The sum
of ``Tree::grow.tree_depth`` over the sum of ``UpdateScore::tail``'s
``replay_steps / valid_sets`` is the share of the valid walk's steps
that a row of the deepest leaf needed.  None where no span carries the
args: a program that does not count them, or a job without valid
sets."""


def span_sum(spans, span, arg, per=None):
    """The sum of ``arg`` (over ``per``) over the spans named ``span``
    that carry it, or None where none does."""
    args = [e.get("args", {}) for e in spans if e["name"] == span]
    args = [a for a in args if arg in a and (per is None or a.get(per))]
    if not args:
        return None
    return sum(a[arg] / (a[per] if per else 1) for a in args)


def reduce(obs, num, den, scale=1.0):
    top = span_sum(obs["spans"], **num)
    bottom = span_sum(obs["spans"], **den)
    if top is None or not bottom:
        return None
    return scale * top / bottom
