"""Observability: per-operator stats, trace spans, events, metrics.

Reference parity: core/trino-main execution/QueryStats.java +
operator/OperatorStats.java (the per-operator rollup EXPLAIN ANALYZE and
the REST API render), core/trino-spi eventlistener/ (EventListener SPI:
QueryCreatedEvent / QueryCompletedEvent streamed to plugins), and the
JMX/OpenMetrics surface (io.airlift.stats counters exported per MBean)
collapsed to a process-wide registry served at GET /v1/metrics.

This package is the engine's measurement layer: the runner owns one
`QueryStatsCollector` per query, execution threads it through the local
planner, the distributed scheduler, and the jit cache, and everything
downstream — EXPLAIN ANALYZE, system.runtime.{queries,metrics}, event
listeners, Prometheus scrapes, benchmark/ — reads the same numbers.
"""

from trino_tpu.obs.listeners import (EventListener, LoggingEventListener,
                                     QueryEvent, register_listener,
                                     unregister_listener)
from trino_tpu.obs.metrics import REGISTRY, MetricsRegistry
from trino_tpu.obs.spans import Span
from trino_tpu.obs.stats import OperatorStats, QueryStatsCollector

__all__ = [
    "EventListener", "LoggingEventListener", "QueryEvent",
    "register_listener", "unregister_listener",
    "REGISTRY", "MetricsRegistry", "Span",
    "OperatorStats", "QueryStatsCollector",
]
