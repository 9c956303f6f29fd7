"""Observability layer: telemetry recorder with wall-clock spans, metrics,
exporters, kernel launches on the span API, and the closed loop on top of
them — flight recorder, telemetry-fitted oracles, replay drift audits,
per-tenant SLO burn-rate monitors.

See DESIGN.md §2.9 and §2.12.  Import surface is dependency-free (stdlib
only) so the pure-numpy simulation path can enable telemetry without JAX
present; replay/fit lazy-import the simulator machinery on use.
"""

from .metrics import MetricsRegistry, NullMetrics, StreamingHistogram
from .telemetry import NULL, NullTelemetry, Telemetry
from .exporters import (chrome_trace, parse_prometheus, write_chrome_trace,
                        write_jsonl, write_metrics)
from .profiling import install, profiled
from .recorder import FlightRecorder, load_record
from .fit import FittedOracle, fit_oracle, fit_table
from .replay import drift_report, replay_record
from .slo import SLOConfig, SLOMonitor
from .schema import (SCHEMA_VERSION, validate_chrome_trace,
                     validate_drift_report, validate_flight_record,
                     validate_metrics_snapshot, validate_slo_alert,
                     validate_telemetry_summary)

__all__ = [
    "MetricsRegistry", "NullMetrics", "StreamingHistogram",
    "NULL", "NullTelemetry", "Telemetry",
    "chrome_trace", "parse_prometheus", "write_chrome_trace", "write_jsonl",
    "write_metrics",
    "install", "profiled",
    "FlightRecorder", "load_record",
    "FittedOracle", "fit_oracle", "fit_table",
    "drift_report", "replay_record",
    "SLOConfig", "SLOMonitor",
    "SCHEMA_VERSION", "validate_chrome_trace", "validate_drift_report",
    "validate_flight_record", "validate_metrics_snapshot",
    "validate_slo_alert", "validate_telemetry_summary",
]
