"""Count backend compiles and persistent-cache hits through JAX's
monitoring events (a copy of the counter in ``chip_smoke.py``)."""
from __future__ import annotations


class CompileCounter:
    """``n`` backend compiles, ``hits`` persistent-cache hits, and
    ``seconds`` spent compiling since construction."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple[int, int, float]:
        return self.n, self.hits, self.seconds
