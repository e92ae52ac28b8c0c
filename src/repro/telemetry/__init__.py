# Unified telemetry (DESIGN.md §Telemetry): three zero-dependency pieces
# shared by every runtime layer —
#
#   tracing   span("engine.submit", ...) context managers -> an
#             in-process ring buffer -> JSONL / Chrome-trace exporters
#             (off by default; the disabled path is one attribute check)
#   metrics   counters/gauges/histograms with label sets, published by
#             the scheduler/executor/run_resumable; snapshot() dict,
#             periodic JSONL flush, one-shot Prometheus text export
#   health    threshold checks over the existing StreamingChainStats /
#             SwapStats / latency_summary accumulators -> structured
#             HealthAlert records + SamplerHealthWarning warnings
#
# Instrumentation sites are host-side and per-chunk/per-segment — never
# per chain step — and never touch the sampled stream (bit-parity with
# telemetry on vs off is asserted in tests/test_telemetry.py; the
# disabled-mode overhead is bench-gated in benchmarks/bench_telemetry.py).

from repro.telemetry.health import (
    HealthAlert,
    HealthMonitor,
    HealthThresholds,
    SamplerHealthWarning,
)
from repro.telemetry.metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    JsonlFlusher,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    snapshot,
)
from repro.telemetry.tracing import (
    SCHEMA_VERSION,
    TRACER,
    TraceEvent,
    Tracer,
    clock,
    disable,
    enable,
    enabled,
    instant,
    log,
    null_span,
    span,
    validate_event,
    validate_jsonl,
)

__all__ = [
    # tracing
    "Tracer",
    "TraceEvent",
    "TRACER",
    "SCHEMA_VERSION",
    "enable",
    "disable",
    "enabled",
    "span",
    "null_span",
    "instant",
    "log",
    "clock",
    "validate_event",
    "validate_jsonl",
    # metrics
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlFlusher",
    "REGISTRY",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    # health
    "HealthMonitor",
    "HealthThresholds",
    "HealthAlert",
    "SamplerHealthWarning",
]
