"""repro.obs — low-overhead tracing + metrics for the MGG stack.

Leaf package: imports nothing from the rest of ``repro`` so core /
runtime / serve / store can all depend on it without cycles.

Two primitives:

- :class:`Tracer` (tracer.py): nestable wall-clock spans with an
  injectable monotonic clock, bounded ring buffer, thread safety, and a
  strict no-op fast path when disabled.  A live span also annotates the
  JAX profiler's trace.  Exports Chrome-trace JSON (opens directly in
  ui.perfetto.dev) and JSONL.
- :class:`MetricsRegistry` (metrics.py): labeled counters / gauges /
  histograms with percentile summaries and a JSON snapshot.

and two that name what happens inside JAX:

- scopes.py: the layer names the compiled GNN step carries
  (``jax.named_scope``), kept once.
- :func:`watch_compiles` (compiles.py): JAX's compile and compile-cache
  events, fed into a registry.

One exception to leaf-ness, deliberately quarantined: ``calibrate.py``
fits the §4 analytical latency model to measured latencies and so must
import ``repro.core.autotune``.  It is never imported here eagerly —
``import repro.obs.calibrate`` explicitly (or touch the lazy
``repro.obs.calibrate`` attribute) — so ``from repro.obs import Tracer``
stays dependency-free.

See docs/observability.md for the span taxonomy and metric names.
"""
from repro.obs.tracer import NULL_TRACER, Tracer, merge_traces
from repro.obs.metrics import MetricsRegistry
from repro.obs.compiles import watch_compiles

__all__ = ["Tracer", "NULL_TRACER", "MetricsRegistry", "merge_traces",
           "watch_compiles", "calibrate"]


def __getattr__(name):
    if name == "calibrate":  # lazy: pulls in repro.core.autotune
        import repro.obs.calibrate as _cal
        return _cal
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
