"""The ratio of two args of a span, summed over the window's spans of
that name, times ``scale``: ``pairs_visited`` over ``pair_slots`` of the
``Boosting`` span is the share of the rank objective's pair work that is
not padding.  None where no such span carries both args: a program whose
objective counts nothing."""


def reduce(obs, span, num, den, scale=1.0):
    args = [e.get("args", {}) for e in obs["spans"] if e["name"] == span]
    args = [a for a in args if num in a and den in a]
    total = sum(a[den] for a in args)
    if not total:
        return None
    return scale * sum(a[num] for a in args) / total
