"""repro.obs — unified tracing + metrics for the memos pipeline.

The paper's memos is "powered by our newly designed kernel-level
monitoring module"; this package is that module's observability surface
for the repro, in two halves sharing one process-wide home:

  * **spans** (``obs/trace.py``) — monotonic-clock spans in a
    preallocated ring buffer, thread-aware, **disabled by default and a
    true no-op while disabled** (one branch, a shared null context
    manager, zero events, zero retained attributes).  Instrumentation
    covers the serving dispatch boundaries (admit / provision / dispatch
    / retire), the async memos snapshot -> plan -> commit phases (plan
    spans land on the worker thread), batched migration per (src, dst)
    tier group, and Start-Gap adoption.  While enabled, each span is also
    a ``jax.profiler.TraceAnnotation``, so a profiler trace shows the
    spans on the device ops' clock.
  * **metrics** (``obs/metrics.py``) — a registry of counters, gauges,
    and log-bucketed histograms that MemosManager, TierStore, and
    PagedServingEngine publish into at pass/dispatch boundaries:
    per-token and per-dispatch latency, plan latency vs. overlap window
    (the overlap-efficiency gauge), pages committed/degraded, per-tier
    occupancy, per-(src, dst) migration bytes, per-wear-tier energy and
    max wear.  Metric publication is boundary-granular and always on —
    its cost is a handful of dict/lock ops per dispatch, invisible next
    to a jitted K-token decode.

Exporters (``obs/export.py``): Chrome trace-event JSON (chrome://tracing
/ Perfetto) and Prometheus-style text.

Usage::

    from repro import obs

    obs.configure(trace=True)            # flip the span recorder on
    with obs.span("my.phase", k=16):     # timeline span
        ...
    obs.get_registry().histogram("my.latency_s").observe(dt)
    obs.export.write_chrome_trace("trace.json", obs.get_tracer())

The module-level singletons (`get_tracer()` / `get_registry()`) are the
process's default sinks; tests and sweeps isolate themselves with
``reset()`` (drops all events + metrics) rather than swapping instances,
because instrumented library code looks the singletons up at publish
time.
"""
from __future__ import annotations

from . import export  # noqa: F401  (re-export: obs.export.write_chrome_trace)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import NULL_SPAN, SpanEvent, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "SpanEvent",
    "Tracer", "NULL_SPAN", "configure", "get_registry", "get_tracer",
    "reset", "span", "tracing_enabled", "export",
]

_tracer = Tracer(enabled=False)
_registry = MetricsRegistry()


def get_tracer() -> Tracer:
    return _tracer


def get_registry() -> MetricsRegistry:
    return _registry


def configure(*, trace: bool | None = None,
              capacity: int | None = None) -> None:
    """Flip tracing on/off and/or resize the span ring.  Resizing drops
    recorded events (the ring is preallocated, never grown in place)."""
    global _tracer
    if capacity is not None and capacity != _tracer.capacity:
        _tracer = Tracer(capacity=capacity, enabled=_tracer.enabled)
    if trace is not None:
        _tracer.enabled = bool(trace)


def tracing_enabled() -> bool:
    return _tracer.enabled


def span(name: str, **attrs):
    """Time a span against the process tracer (no-op context manager
    while tracing is disabled)."""
    t = _tracer
    if not t.enabled:
        return NULL_SPAN
    return t.span(name, **attrs)


def reset() -> None:
    """Drop all recorded spans and all metrics (keeps the enabled flag
    and ring capacity) — sweep/test isolation."""
    _tracer.clear()
    _registry.reset()
