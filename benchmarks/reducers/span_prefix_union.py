"""Time spent under spans whose names begin with ``prefix`` inside the
spans named ``within``, as the union of their intervals
(``jax::backend_compile`` holds its ``jax::cache_load``, a
``jax::trace`` the traces it calls): what JAX built, loaded or retraced
inside the window's ``Train::iteration`` spans.  (A kind hands over
every span that began after the window opened, the checks that follow
the window too; those build what they need and lie in no iteration.)
0 is a reading, and the expected one.  None where there is no span
ending in ``witness_suffix``: only a tracer that names its barriers
also hears JAX's builds, so without them a 0 would say nothing.
Milliseconds when ``scale`` is 1000."""


def reduce(obs, prefix, within, witness_suffix, scale=1000.0):
    spans = obs["spans"]
    if not any(e["name"].endswith(witness_suffix) for e in spans):
        return None
    outer = [(o["tid"], o["ts"], o["ts"] + o["dur"]) for o in spans
             if o["name"] == within]
    total, end = 0.0, None
    for tid, s, e in sorted((e["tid"], e["ts"], e["ts"] + e["dur"])
                            for e in spans if e["name"].startswith(prefix)):
        if not any(t == tid and a <= s and e <= b for t, a, b in outer):
            continue
        if end is None or s > end:
            total, end = total + (e - s), e
        elif e > end:
            total, end = total + (e - end), e
    return total / 1e6 * scale
