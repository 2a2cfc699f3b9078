"""Compile time and program count, from JAX's monitoring events (tracing,
lowering, and backend compiles or persistent-cache reads).  A copy of the
counter in ``chip_smoke.py``, kept here so the yardstick does not move
with the program."""
from __future__ import annotations


class Compiles:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.lowerings = 0          # new jit signatures, cached or not
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.programs += event.endswith("backend_compile_duration")
            self.lowerings += event.endswith("jaxpr_to_mlir_module_duration")

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self) -> tuple[float, int, int, int]:
        return self.seconds, self.programs, self.cache_hits, self.lowerings
