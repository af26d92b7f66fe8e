"""What JAX spent obtaining executables, from JAX's own monitoring events
(copied from ``chip_smoke.CompileMeter``, PR 21, and split by event so the
parts of ``setup_s`` can be printed). ``programs`` counts executables
obtained by compiling or by reading the persistent cache; a window in
which it moves compiled something."""

from __future__ import annotations

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "to_mlir_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}


class CompileMeter:
    def __init__(self):
        import jax
        self.secs = {v: 0.0 for v in _DURATIONS.values()}
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        key = _DURATIONS.get(event)
        if key is not None:
            self.secs[key] += secs
            if key == "backend_compile_s":
                self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                **{k: round(v, 3) for k, v in self.secs.items()}}
