"""Unified observability plane: metrics, traces, exporters, health.

``obs`` is dependency-free (stdlib only) so every layer — engine, SAI,
WAL, block store, node runtime, gateway, transport — can import it
without cycles.  See docs/OBSERVABILITY.md for the metric-name table,
trace span hierarchy, and health verdict rules.
"""

from .metrics import Counter, CounterGroup, Gauge, Histogram, MetricsRegistry
from .trace import Span, Trace, Tracer, span
from .export import dump_slow_log, flatten, prometheus_text, truncate_tree
from .health import (
    Heartbeat,
    HeartbeatBoard,
    HealthConfig,
    HealthEngine,
    STATUS_CRITICAL,
    STATUS_OK,
    STATUS_WARN,
)
from .timeseries import MetricsSampler
from .httpexport import HealthHTTPServer

__all__ = [
    "Counter",
    "CounterGroup",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "Trace",
    "Tracer",
    "span",
    "dump_slow_log",
    "flatten",
    "prometheus_text",
    "truncate_tree",
    "Heartbeat",
    "HeartbeatBoard",
    "HealthConfig",
    "HealthEngine",
    "HealthHTTPServer",
    "MetricsSampler",
    "STATUS_CRITICAL",
    "STATUS_OK",
    "STATUS_WARN",
]
