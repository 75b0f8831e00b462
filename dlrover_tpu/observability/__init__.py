"""Distributed observability plane: trace context, unified metrics,
the fault-triggered flight recorder, and the hot paths' spans on the
profiler's clock (``spans.span``).

Layering note: :mod:`dlrover_tpu.common.events` imports this package on
every process start, so nothing here may import back into
``common.events`` (or anything that does). ``trace_merge`` (the
``tpurun-trace`` CLI) is deliberately NOT re-exported — it is an
offline tool and only loaded by its entry point."""

from . import flight_recorder, metrics, spans, trace
from .flight_recorder import FlightRecorder, get_recorder
from .metrics import (
    MetricsRegistry,
    MetricsServer,
    get_registry,
    maybe_start_metrics_server,
    reset_registry,
)
from .trace import SpanContext

__all__ = [
    "FlightRecorder",
    "MetricsRegistry",
    "MetricsServer",
    "SpanContext",
    "flight_recorder",
    "get_recorder",
    "get_registry",
    "maybe_start_metrics_server",
    "metrics",
    "reset_registry",
    "spans",
    "trace",
]
