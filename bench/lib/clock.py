"""Compile accounting from JAX's own monitoring events (a copy of the
program's ``chip_smoke.CompileClock``, kept here so a change to the program
cannot move the yardstick)."""
from __future__ import annotations


class CompileClock:
    """Sums JAX's compile events: backend compiles (persistent-cache reads
    included) and jaxpr tracing + lowering, with persistent-cache hits."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.trace_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "trace_s": self.trace_s,
                "compiles": self.compiles, "cache_hits": self.cache_hits}
