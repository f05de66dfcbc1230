"""What the kinds share."""
from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCount:
    """Programs built (or fetched from the persistent cache) so far, by
    JAX's own monitoring events: the check that nothing compiles inside
    a window."""

    def __init__(self):
        from jax import monitoring
        self.built = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.built += 1

    def _on_event(self, name, **_kw):
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1


def fallback_events(obs_events, before: dict) -> list:
    """``routing_fallback_*`` events fired since ``before``
    (``chip_smoke.py``'s check)."""
    return sorted(k for k, v in obs_events.totals().items()
                  if k.startswith("routing_fallback_")
                  and v != before.get(k, 0))
