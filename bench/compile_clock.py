"""Seconds JAX spends turning Python into device programs, read from
``jax.monitoring`` while a context is open."""

from __future__ import annotations

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Accumulates, while open: jaxpr tracing and MLIR lowering seconds,
    backend-compile seconds (persistent-cache reads included, as JAX
    times them), the number of backend compiles and of persistent-cache
    hits among them."""

    def __init__(self):
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.backend_s = 0.0
        self.traces = 0
        self.backend_compiles = 0
        self.cache_hits = 0

    def _duration(self, event, secs, **_):
        if event == TRACE:
            self.trace_s += secs
            self.traces += 1
        elif event == LOWER:
            self.lower_s += secs
        elif event == BACKEND:
            self.backend_s += secs
            self.backend_compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def as_dict(self) -> dict:
        return {"trace_s": self.trace_s, "lower_s": self.lower_s,
                "backend_s": self.backend_s, "traces": self.traces,
                "backend_compiles": self.backend_compiles,
                "cache_hits": self.cache_hits}
