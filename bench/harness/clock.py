"""Compile seconds and persistent-cache hits, from JAX's monitoring events."""

from __future__ import annotations


class CompileClock:
    """Counts what JAX traces, lowers and compiles in this process.

    ``compiles`` counts backend compilations; the event also fires for an
    executable loaded from the persistent cache, so a window that counts 0
    ran nothing it had not already loaded.
    """

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
