"""Observability: request-lifecycle tracing + metrics registry.

``repro.obs`` imports nothing from the rest of the package, so any layer
(serve, tune, benchmarks) can depend on it without cycles.
"""
from repro.obs.metrics import (QUANTA_BUCKETS, TIME_BUCKETS, Counter, Gauge,
                               Histogram, Registry)
from repro.obs.trace import (ANNOTATION_PREFIX, ENGINE_TRACK, LIFECYCLE,
                             NULL_TRACER, REQ_TRACK_BASE, SCHED_TRACK,
                             TERMINAL_STATES, TraceRecorder, validate_chrome)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "TIME_BUCKETS", "QUANTA_BUCKETS",
    "TraceRecorder", "NULL_TRACER", "validate_chrome", "ANNOTATION_PREFIX",
    "ENGINE_TRACK", "SCHED_TRACK", "REQ_TRACK_BASE",
    "LIFECYCLE", "TERMINAL_STATES",
]
