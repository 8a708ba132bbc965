"""Observability: tracing, metrics, time-series, SLOs, exposition.

Zero-dependency (numpy only) and off-hot-path by construction: every
instrument lives on the host side, never inside jitted code, and the
whole layer is a no-op until `enable()` attaches a recorder (span
tracing) or `enable_metrics()` flips the registry pushes on (the
lighter switch the SLO/telemetry path uses).

    rec = obs.enable()                # tracing on, events -> ring buffer
    ... serve traffic ...
    obs.disable()
    rec.export_jsonl("trace.jsonl")   # -> tools/trace_report.py

    obs.enable_metrics()              # registry pushes without tracing
    store = obs.TimeSeriesStore()     # windowed rates / percentiles
    mon = obs.SLOMonitor(store, obs.node_objectives(0, slo_s=1.5))
    srv = obs.TelemetryServer(metrics_fn=lambda: obs.to_prometheus(
        obs.registry().snapshot()), health_fn=mon.health).start()
"""
from repro.obs.export import (TelemetryServer, parse_key, parse_prometheus,
                              render_dashboard, to_prometheus)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               enable_metrics, escape_label, metric_key,
                               metrics_enabled, percentile, registry,
                               unescape_label)
from repro.obs.recorder import FlightRecorder
from repro.obs.slo import (DEFAULT_WINDOWS, FIRING, OK, Objective,
                           SLOMonitor, node_objectives)
from repro.obs.timeseries import TimeSeriesStore
from repro.obs.trace import (NULL_SPAN, Tracer, get_tracer, query_trace,
                             watch_compiles)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "percentile",
    "registry", "metric_key", "escape_label", "unescape_label",
    "enable_metrics", "metrics_enabled", "FlightRecorder", "NULL_SPAN",
    "Tracer", "get_tracer", "query_trace", "enable", "disable", "enabled",
    "TimeSeriesStore", "Objective", "SLOMonitor", "node_objectives",
    "DEFAULT_WINDOWS", "OK", "FIRING", "to_prometheus", "parse_prometheus",
    "parse_key", "TelemetryServer", "render_dashboard",
]


def enable(recorder=None, capacity=131072):
    """Turn tracing on (backend compiles become ``compile`` spans).
    Returns the recorder events will land in."""
    watch_compiles()
    rec = recorder if recorder is not None else FlightRecorder(capacity)
    tr = get_tracer()
    tr.recorder = rec
    tr.enabled = True
    return rec


def disable():
    """Turn tracing off (the fast path goes back to zero clock reads)."""
    tr = get_tracer()
    tr.enabled = False
    rec, tr.recorder = tr.recorder, None
    tr.reset()
    return rec


def enabled():
    return get_tracer().enabled
