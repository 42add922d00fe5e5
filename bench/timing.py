"""Compile accounting on JAX's own events, shared by every cell.

`CompileCounter` adds up the seconds of JAX's compile events and counts
backend compiles, so set-up can report its compile time and a window
can show that nothing compiled inside it.
"""
from __future__ import annotations

import jax

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Compile seconds, backend compiles and persistent-cache hits."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration_secs
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1
