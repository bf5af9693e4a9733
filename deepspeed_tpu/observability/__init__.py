"""Serving & training observability: metrics core, request tracing,
lifecycle spans, flight recorder, Perfetto export, SLO/anomaly
detection, workload/capacity attribution (traffic analytics, HBM
ledger, per-program cost census, capacity advisor), machine-readable
sinks, XLA profiler integration, the communication observatory
(exposed-collective step anatomy, achieved bus-bandwidth ledger,
straggler detection — ``commscope.py``), and the live telemetry plane
(per-engine HTTP ops surface, goodput/badput wall-time ledger, fleet
scrape aggregator).

See ``docs/OBSERVABILITY.md`` for the metric namespace and runbook, and
``python -m deepspeed_tpu.observability.doctor`` for triage — file-based
(``--dir``) or against a live engine (``--url``).
"""

from .capacity import (ProgramCensus, capacity_report, hbm_ledger,
                       kv_cache_bytes, validate_capacity_report,
                       write_capacity_report)
from .commscope import (CommScope, CommScopeConfig, StragglerDetector,
                        bandwidth_ledger, classify_op, decompose,
                        step_anatomy)
from .expfmt import (exposition_from_events, labeled_name, parse_labels,
                     prometheus_series, render_exposition, split_series)
from .export import (HOP_NAMES, RequestLogSink, hop_trace,
                     merge_fleet_trace, request_record, to_chrome_trace,
                     validate_chrome_trace, write_chrome_trace)
from .fleet_scrape import FleetScraper
from .flight import (FlightRecorder, newest_flight_record,
                     read_flight_record)
from .goodput import BADPUT_BUCKETS, GoodputLedger
from .kvscope import KVScope, KVScopeConfig, measure_copy_bandwidth
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, Reservoir,
                      get_registry)
from .replay import (TRACE_SCHEMA, ReplayClock, ReplayDriver, ReplayReport,
                     TrafficCapture, TrafficTrace, advisor_backtest,
                     trace_from_request_log, write_backtest_report)
from .sinks import (JsonlSink, PrometheusTextfileSink,
                    format_prometheus_value, parse_prometheus_textfile,
                    prometheus_name)
from .server import (TelemetryConfig, TelemetryHooks, TelemetryServer,
                     flight_summary)
from .slo import (CompileStormDetector, MedianMADDetector, SLOConfig,
                  SLOScorer)
from .spans import SpanEvent, SpanRecorder
from .tenantscope import TenantScope, TenantScopeConfig
from .tracing import RequestRecord, RequestTracer, ServingStats
from .workload import WorkloadAnalyzer, WorkloadConfig
from .xla import TraceWindow, sample_memory

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Reservoir",
    "get_registry",
    "JsonlSink", "PrometheusTextfileSink", "parse_prometheus_textfile",
    "prometheus_name", "prometheus_series", "format_prometheus_value",
    "labeled_name", "split_series", "parse_labels",
    "render_exposition", "exposition_from_events",
    "GoodputLedger", "BADPUT_BUCKETS",
    "TelemetryConfig", "TelemetryHooks", "TelemetryServer",
    "flight_summary", "FleetScraper",
    "RequestRecord", "RequestTracer", "ServingStats",
    "SpanEvent", "SpanRecorder",
    "FlightRecorder", "newest_flight_record", "read_flight_record",
    "RequestLogSink", "request_record", "to_chrome_trace",
    "validate_chrome_trace", "write_chrome_trace",
    "merge_fleet_trace", "hop_trace", "HOP_NAMES",
    "SLOConfig", "SLOScorer", "MedianMADDetector", "CompileStormDetector",
    "WorkloadAnalyzer", "WorkloadConfig",
    "KVScope", "KVScopeConfig", "measure_copy_bandwidth",
    "ProgramCensus", "hbm_ledger", "kv_cache_bytes", "capacity_report",
    "validate_capacity_report", "write_capacity_report",
    "CommScope", "CommScopeConfig", "StragglerDetector",
    "bandwidth_ledger", "classify_op", "decompose", "step_anatomy",
    "TraceWindow", "sample_memory",
    "TrafficCapture", "TrafficTrace", "ReplayClock", "ReplayDriver",
    "ReplayReport", "advisor_backtest", "trace_from_request_log",
    "write_backtest_report", "TRACE_SCHEMA",
    "TenantScope", "TenantScopeConfig",
]
