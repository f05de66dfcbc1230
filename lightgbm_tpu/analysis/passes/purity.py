"""purity-pin pass: registered "knob off => identical program"
invariants.

The obs layer's contract since PR 2 is that telemetry is FREE when
off, and since ISSUE 27 when on too: a grow program built while the
tracer is live must be the bit-identical jaxpr of a build that never
saw it (``grow-tracer-live``), and exercising the
tracer / ledger / reset lifecycle must not leak into a later build.
Those pins used to live as ad-hoc ``jax.make_jaxpr`` string compares
inside individual tests; they are now REGISTERED invariants
(``registry.register_purity_pin``) with one checker, so every knob
that claims "off = identical" is enforced the same way and new knobs
add a registration instead of another test idiom.

A pin builder returns ``[(variant_name, fn, args), ...]``; the pass
traces every variant (abstract args — nothing executes) and requires
all jaxpr digests equal.
"""
from __future__ import annotations

import hashlib
from typing import List

from ..findings import Finding, SEV_ERROR
from .. import registry

PASS_NAME = "purity-pin"


def digest(fn, args) -> str:
    import jax
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()


def check_pin(name: str, builder) -> List[Finding]:
    variants = builder()
    digests = []
    for vname, fn, args in variants:
        digests.append((vname, digest(fn, args)))
    base_name, base = digests[0]
    out = []
    for vname, d in digests[1:]:
        if d != base:
            out.append(Finding(
                pass_name=PASS_NAME,
                code="PURITY_DIVERGES",
                severity=SEV_ERROR,
                where=f"pin:{name} variant:{vname}",
                message=(
                    f"variant {vname!r} compiles a DIFFERENT program "
                    f"than {base_name!r} (digest {d[:12]} != "
                    f"{base[:12]}): the knob leaks into the traced "
                    f"hot path when off"),
                entry=name))
    return out


def run(ctx) -> List[Finding]:
    out: List[Finding] = []
    pins = dict(registry.PURITY_PINS)
    pins.update(ctx.fixture_pins)   # injected seeded-violation pins
    for name, builder in sorted(pins.items()):
        if ctx.pin_filter and name not in ctx.pin_filter:
            continue
        try:
            findings = check_pin(name, builder)
        except Exception as e:   # pragma: no cover - build failures
            out.append(Finding(
                pass_name=PASS_NAME, code="PIN_BUILD_FAILED",
                severity=SEV_ERROR, where=f"pin:{name}",
                message=f"pin builder raised: {type(e).__name__}: {e}",
                entry=name, fixture=name in ctx.fixture_pins))
            continue
        for f in findings:
            f.fixture = name in ctx.fixture_pins
            out.append(f)
    return out
