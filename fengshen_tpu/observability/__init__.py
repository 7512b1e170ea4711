"""Unified observability: metrics registry, Prometheus exposition,
jsonl event sink, MFU/goodput step stats, and trace spans
(docs/observability.md).

Every subsystem plugs into this one core instead of inventing its own
telemetry dialect: the Trainer's step log, the serving engine's
`EngineMetrics` and the resilience events all write
through here; ``GET /metrics`` (api server routes + the standalone
exporter thread) and `/stats` read from it.
"""

from fengshen_tpu.observability.buildinfo import (BUILD_INFO_METRIC,
                                                  WARMUP_METRIC,
                                                  record_build_info,
                                                  record_warmup_seconds)
from fengshen_tpu.observability.exposition import (CONTENT_TYPE_LATEST,
                                                   MetricsServer,
                                                   render_prometheus,
                                                   start_metrics_server)
from fengshen_tpu.observability.flightrecorder import FlightRecorder
from fengshen_tpu.observability.flops import (CPU_NOMINAL_FLOPS,
                                              PEAK_FLOPS,
                                              estimate_flops_per_token,
                                              peak_flops_per_chip)
from fengshen_tpu.observability.registry import (Counter, Gauge, Histogram,
                                                 MetricsRegistry,
                                                 get_registry, percentile)
from fengshen_tpu.observability.sink import JsonlSink
from fengshen_tpu.observability.stepstats import StepStats
from fengshen_tpu.observability.timeline import (PHASE_NAMES,
                                                 RequestTimeline)
from fengshen_tpu.observability.tracectx import (SpanLedger,
                                                 TraceContext, TraceIds,
                                                 assemble_trace,
                                                 parse_traceparent)
from fengshen_tpu.observability.tracing import (current_span_stack, span,
                                                thread_times)

__all__ = [
    "BUILD_INFO_METRIC", "CONTENT_TYPE_LATEST", "Counter",
    "FlightRecorder", "Gauge", "Histogram", "JsonlSink",
    "MetricsRegistry", "MetricsServer", "CPU_NOMINAL_FLOPS",
    "PEAK_FLOPS", "PHASE_NAMES", "RequestTimeline", "SpanLedger",
    "StepStats", "TraceContext", "TraceIds", "WARMUP_METRIC",
    "assemble_trace", "current_span_stack", "estimate_flops_per_token",
    "get_registry", "parse_traceparent",
    "peak_flops_per_chip", "percentile", "record_build_info",
    "record_warmup_seconds", "render_prometheus", "span",
    "start_metrics_server", "thread_times",
]
