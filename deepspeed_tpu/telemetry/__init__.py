"""Telemetry: deterministic distributed tracing + always-on metrics.

The cross-cutting observability layer over the whole stack
(docs/OBSERVABILITY.md): a span :mod:`tracer <.trace>` whose timestamps
come from the pluggable serving clock (bit-reproducible traces under
``VirtualClock``), Chrome-trace/Perfetto + JSONL :mod:`exporters
<.export>` with atomic writes, and a :mod:`metrics <.metrics>` registry
(counters / gauges / fixed-log-bucket histograms) bridged into
``MonitorMaster`` as ``telemetry/*`` events.

Instrumented surfaces: engine step phases (fwd/bwd/optim and the
streamed-optimizer upload/compute/download pipeline), the serving
request lifecycle (one trace per request, preemptions as span events),
and fleet dispatch (the client trace_id survives replica failover).
"""

from .export import (load_chrome_trace, spans_to_jsonl, to_chrome_trace,
                     validate_chrome_trace, write_chrome_trace, write_jsonl)
from .flight_recorder import FlightRecorder
from .metrics import (Counter, Gauge, Histogram, HistogramWindow,
                      MetricsRegistry)
from .slo import BurnRateConfig, SLOBurnMonitor
from .spans import PHASE_OF_STATE, emit_attempt_spans, phase_intervals
from .step_anatomy import (HOST_SEGMENTS, NULL_ANATOMY, NullStepAnatomy,
                           StepAnatomy, recorders)
from .trace import (NULL_SPAN, NULL_TRACER, NullTracer, PerfClock, Span,
                    Tracer)

__all__ = [
    "load_chrome_trace", "spans_to_jsonl", "to_chrome_trace",
    "validate_chrome_trace", "write_chrome_trace", "write_jsonl",
    "FlightRecorder",
    "Counter", "Gauge", "Histogram", "HistogramWindow", "MetricsRegistry",
    "BurnRateConfig", "SLOBurnMonitor",
    "PHASE_OF_STATE", "emit_attempt_spans", "phase_intervals",
    "HOST_SEGMENTS", "NULL_ANATOMY", "NullStepAnatomy", "StepAnatomy",
    "recorders",
    "NULL_SPAN", "NULL_TRACER", "NullTracer", "PerfClock", "Span", "Tracer",
]
