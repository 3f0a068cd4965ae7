"""Unified runtime observability (DESIGN.md §12): metrics registry,
tracing spans over a bounded ring + JSONL sink (each span also a
`jax.profiler` trace annotation once jax is imported), and the training
telemetry loop.

Dependency-free by design (stdlib only at import — no jax, no numpy): every
layer of the system imports this package without ordering hazards, and the
jaxpr auditor sees zero new primitives from instrumentation.
"""
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                Series)
from repro.obs.sites import SITE_PREFIXES, SITE_RE, check_site
from repro.obs.telemetry import SpikeDetector, TelemetryAlert, TelemetryLoop
from repro.obs.trace import (Obs, SpanEvent, TraceRing, configure, get_obs,
                             instant, profiler_trace, reset, span,
                             trace_event)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Series",
    "SITE_PREFIXES", "SITE_RE", "check_site",
    "SpikeDetector", "TelemetryAlert", "TelemetryLoop",
    "Obs", "SpanEvent", "TraceRing", "configure", "get_obs", "instant",
    "profiler_trace", "reset", "span", "trace_event",
]
