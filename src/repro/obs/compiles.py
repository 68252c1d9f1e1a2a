"""JAX's own compile events, fed into a :class:`MetricsRegistry`.

JAX reports each compile's phases and each persistent-cache lookup through
``jax.monitoring``.  While :func:`watch_compiles` runs its block, the
durations of ``/jax/core/compile/{jaxpr_trace_duration,
jaxpr_to_mlir_module_duration, backend_compile_duration}`` land in the
histograms ``jax.compile.{trace_s, lower_s, backend_s}`` (count =
compiles, sum = seconds), and ``/jax/compilation_cache/{cache_hits,
cache_misses}`` in the counters ``jax.compile.{cache_hits, cache_misses}``.
A cache miss is counted when JAX writes the entry, so a compile too short
or too small for the cache counts as neither.
"""
from __future__ import annotations

import contextlib

from jax import monitoring

from repro.obs.metrics import MetricsRegistry

DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "jax.compile.backend_s",
}
EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax.compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "jax.compile.cache_misses",
}


@contextlib.contextmanager
def watch_compiles(registry: MetricsRegistry):
    """Record JAX's compile events into ``registry`` while the block runs
    (every thread's compiles, as JAX's listeners are process-wide)."""
    def on_duration(event: str, secs: float, **_) -> None:
        name = DURATIONS.get(event)
        if name is not None:
            registry.histogram(name).observe(secs)

    def on_event(event: str, **_) -> None:
        name = EVENTS.get(event)
        if name is not None:
            registry.counter(name).inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        yield registry
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
