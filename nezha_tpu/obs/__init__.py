"""Telemetry subsystem: process-wide registry + run-scoped sinks.

The stack self-reports its metrics of record (PAPER.md §0: steps/sec,
examples- and tokens-per-sec-per-chip, per-collective payload bytes and
bus bandwidth, compile-cache behavior) instead of leaving them to ad-hoc
computation in bench.py. Three pieces:

- ``registry``: counters / gauges / histograms / wall-clock spans with
  branch-only no-op fast paths while disabled (see registry.py docstring
  for the exact contract).
- ``sink``: ``start_run(run_dir)`` streams ``metrics.jsonl`` +
  ``spans.jsonl`` and writes a final ``summary.json`` —
  ``nezha-train --run-dir`` wires it up; ``nezha-telemetry <run-dir>``
  renders the report (obs/report.py).
- ``metrics`` / ``trace``: the JSONL logger, async-dispatch-aware
  StepTimer, and jax.profiler wrappers absorbed from ``utils/metrics.py``
  and ``utils/profiling.py`` (those modules remain as thin re-exports).
- ``timeseries`` / ``slo`` / ``watchdog``: the rolling-window layer —
  fixed-interval bucket rings with mergeable log-bucket sketches
  (``Registry.windows(duration)``, the Prometheus-style ``/metrics``
  exposition and its fleet merge), declarative SLOs with error-budget
  burn rate, and the anomaly watchdog streaming typed events to
  ``events.jsonl``.
"""

from nezha_tpu.obs.metrics import MetricsLogger, StepTimer, read_metrics
from nezha_tpu.obs.registry import (
    NULL_SPAN,
    Counter,
    Gauge,
    Histogram,
    REGISTRY,
    Registry,
    Span,
    TRACE_HEADER,
    adopt_trace_header,
    counter,
    current_trace,
    disable,
    emit_span,
    enable,
    enabled,
    gauge,
    histogram,
    mint_trace_id,
    new_span_id,
    record_collective,
    record_event,
    record_metrics,
    set_trace_sample,
    span,
    stats_snapshot,
    trace_context,
    trace_sample,
    traced_span,
    windows,
)
from nezha_tpu.obs.sink import (
    EVENTS_FILE,
    METRICS_FILE,
    SPANS_FILE,
    SUMMARY_FILE,
    RunSink,
    current_sink,
    end_run,
    start_run,
)
from nezha_tpu.obs.slo import (
    SLOConfig,
    SLOTracker,
    evaluate_slo,
    parse_slo,
    parse_slo_args,
    summarize_slo_events,
)
from nezha_tpu.obs.timeseries import (
    LogSketch,
    WINDOW_DURATIONS,
    WindowStore,
    current_windows,
    install_windows,
    merge_window_payloads,
    parse_prometheus,
    render_prometheus,
    uninstall_windows,
    windows_payload,
)
from nezha_tpu.obs.trace import (
    LAYER_SPANS,
    Tracer,
    annotate,
    annotate_step,
    profile_trace,
)
from nezha_tpu.obs.watchdog import Watchdog, WatchdogConfig, WatchdogThread

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Span", "REGISTRY",
    "NULL_SPAN", "counter", "gauge", "histogram", "span", "enabled",
    "enable", "disable", "record_metrics", "record_collective",
    "trace_context", "current_trace", "mint_trace_id", "new_span_id",
    "set_trace_sample", "trace_sample", "traced_span", "emit_span",
    "stats_snapshot", "TRACE_HEADER", "adopt_trace_header",
    "RunSink", "start_run", "end_run", "current_sink",
    "METRICS_FILE", "SPANS_FILE", "EVENTS_FILE", "SUMMARY_FILE",
    "MetricsLogger", "StepTimer", "read_metrics",
    "LAYER_SPANS", "Tracer", "annotate", "annotate_step", "profile_trace",
    "record_event", "windows",
    "LogSketch", "WindowStore", "WINDOW_DURATIONS",
    "install_windows", "uninstall_windows", "current_windows",
    "windows_payload", "merge_window_payloads",
    "render_prometheus", "parse_prometheus",
    "SLOConfig", "SLOTracker", "parse_slo", "parse_slo_args",
    "evaluate_slo", "summarize_slo_events",
    "Watchdog", "WatchdogConfig", "WatchdogThread",
]
