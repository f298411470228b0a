"""Compile accounting and device memory, read from JAX itself."""
from __future__ import annotations

from typing import Optional, Sequence


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling inside the block,
    how many backend compiles it ran, and how many programs it found in the
    persistent cache."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, secs, **_):
            if event in self.EVENTS:
                self.seconds += secs
                self.compiles += event == self.EVENTS[-1]

        def on_event(event, **_):
            self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listeners[0])
        jax.monitoring.unregister_event_listener(self._listeners[1])
        return False

    def __str__(self):
        return (f"compile_s={self.seconds:.2f} compiles={self.compiles} "
                f"cache_hits={self.cache_hits}")


def peak_bytes(devices: Sequence) -> Optional[int]:
    """The highest ``peak_bytes_in_use`` over ``devices`` (None where the
    backend keeps no such count)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
