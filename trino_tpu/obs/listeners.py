"""EventListener registry: query lifecycle events with stats payloads.

Reference parity: core/trino-spi eventlistener/ — EventListener.java's
queryCreated/queryCompleted SPI, dispatched by QueryMonitor.java at
state-machine transitions, with the loaded listeners configured through
EventListenerManager. Here listeners register in-process; the query
tracker (exec/query_tracker.py) fires `query_created` when a query
registers, `query_completed` when it FINISHes, and `query_failed` when
it FAILs or is CANCELED, each carrying the query's final stats snapshot
and trace dump when the runner recorded them.

Metric side-effects are NOT a listener: the fire_* functions update the
process metrics registry unconditionally, so unregistering every
listener cannot silence /v1/metrics. Listener exceptions are swallowed —
a broken plugin must not fail queries (the reference wraps every
listener call the same way) — but never silently: each failure counts on
`trino_tpu_listener_errors_total{listener=...}` and the FIRST failure
per listener type logs the full traceback (one line of log noise per
broken plugin, not one per query).

The query-history ring (obs/history.py) is itself a listener on this
bus; the fire path imports it lazily so the ring is armed the moment any
query completes, without a module-level cycle.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Any, Dict, List, Optional

log = logging.getLogger("trino_tpu.obs")


@dataclasses.dataclass
class QueryEvent:
    """The payload all three events share (QueryCreatedEvent /
    QueryCompletedEvent collapse onto one shape: a created event simply
    has no terminal fields yet)."""

    query_id: str
    state: str
    user: str
    query: str
    wall_ms: Optional[int] = None
    cpu_time_ms: int = 0
    rows: int = 0
    output_bytes: int = 0
    retries: int = 0
    faults_injected: int = 0
    resource_group: Optional[str] = None
    peak_memory_bytes: int = 0
    error: Optional[str] = None
    error_name: Optional[str] = None
    stats: Optional[Dict[str, Any]] = None    # QueryStatsCollector.snapshot()
    trace: Optional[Dict[str, Any]] = None    # structured span dump
    trace_file: Optional[str] = None          # exported Chrome-trace path


class EventListener:
    """Base listener (EventListener.java): override any subset."""

    def query_created(self, event: QueryEvent) -> None:
        pass

    def query_completed(self, event: QueryEvent) -> None:
        pass

    def query_failed(self, event: QueryEvent) -> None:
        pass


class LoggingEventListener(EventListener):
    """The default implementation: lifecycle lines on the
    `trino_tpu.obs` logger (the reference ships an event logger the same
    way; operators replace it with their own sink)."""

    def query_created(self, event: QueryEvent) -> None:
        log.debug("query created %s user=%s", event.query_id, event.user)

    def query_completed(self, event: QueryEvent) -> None:
        log.info("query completed %s rows=%d wall_ms=%s cpu_ms=%d "
                 "bytes=%d", event.query_id, event.rows, event.wall_ms,
                 event.cpu_time_ms, event.output_bytes)

    def query_failed(self, event: QueryEvent) -> None:
        log.info("query failed %s state=%s error=%s: %s", event.query_id,
                 event.state, event.error_name, event.error)


_LOCK = threading.Lock()
_LISTENERS: List[EventListener] = [LoggingEventListener()]


def register_listener(listener: EventListener) -> EventListener:
    with _LOCK:
        if listener not in _LISTENERS:
            _LISTENERS.append(listener)
    return listener


def unregister_listener(listener: EventListener) -> None:
    with _LOCK:
        if listener in _LISTENERS:
            _LISTENERS.remove(listener)


def listeners() -> List[EventListener]:
    with _LOCK:
        return list(_LISTENERS)


def event_from_info(info) -> QueryEvent:
    """Build the payload from a QueryInfo (exec/query_tracker.py)."""
    return QueryEvent(
        query_id=info.query_id, state=info.state, user=info.user,
        query=info.query, wall_ms=info.wall_ms,
        cpu_time_ms=info.cpu_time_ms, rows=info.rows,
        output_bytes=info.output_bytes, retries=info.retries,
        faults_injected=info.faults_injected,
        resource_group=info.resource_group,
        peak_memory_bytes=info.pool_peak_bytes,
        error=info.error, error_name=info.error_name,
        stats=info.stats, trace=info.trace,
        trace_file=info.trace_file)


# listener types whose failure has already been logged (log ONCE per
# listener, count every failure — the counter is the ongoing signal)
_ERROR_LOGGED: set = set()


def _dispatch(method: str, event: QueryEvent) -> None:
    for listener in listeners():
        try:
            getattr(listener, method)(event)
        except Exception:   # noqa: BLE001 — a plugin must not fail queries
            name = type(listener).__name__
            from trino_tpu.obs import metrics as m
            m.LISTENER_ERRORS_TOTAL.inc(listener=name)
            if name not in _ERROR_LOGGED:
                _ERROR_LOGGED.add(name)
                log.exception(
                    "event listener %r failed on %s (logged once; "
                    "further failures count on "
                    "trino_tpu_listener_errors_total)", name, method)


def fire_query_created(info) -> None:
    _dispatch("query_created", event_from_info(info))


def _record_terminal_metrics(info) -> None:
    from trino_tpu.obs import metrics as m
    m.QUERIES_TOTAL.inc(state=info.state)
    m.QUERY_ROWS_TOTAL.inc(info.rows)
    m.QUERY_BYTES_TOTAL.inc(info.output_bytes)
    m.QUERY_RETRIES_TOTAL.inc(info.retries)
    m.FAULTS_INJECTED_TOTAL.inc(info.faults_injected)
    if info.stats:
        m.SPILLED_BYTES_TOTAL.inc(info.stats.get("spilled_bytes", 0))
        m.EXCHANGE_BYTES_TOTAL.inc(info.stats.get("exchange_bytes", 0))
        m.EXCHANGE_ROWS_TOTAL.inc(info.stats.get("exchange_rows", 0))
        m.EXCHANGES_TOTAL.inc(info.stats.get("exchanges_fused", 0),
                              mode="fused")
        m.EXCHANGES_TOTAL.inc(info.stats.get("exchanges_staged", 0),
                              mode="staged")
        m.SLICES_TOTAL.inc(info.stats.get("slices_executed", 0))
        m.CHECKPOINTS_TOTAL.inc(info.stats.get("checkpoints_saved", 0),
                                op="saved")
        m.CHECKPOINTS_TOTAL.inc(
            info.stats.get("checkpoints_restored", 0), op="restored")
        m.CHECKPOINT_BYTES_TOTAL.inc(
            info.stats.get("checkpoint_bytes", 0))
        preempt_ms = float(info.stats.get("preempt_latency_ms", 0) or 0)
        if preempt_ms > 0:
            m.PREEMPTIONS_TOTAL.inc()
            m.PREEMPT_LATENCY_SECONDS.observe(preempt_ms / 1000.0)
        for kind in ("agg_mode_downgrades", "agg_mode_upgrades",
                     "agg_recursions", "join_recursions",
                     "heavy_key_splits", "spill_fallbacks"):
            n = info.stats.get(kind, 0)
            if n:
                m.ADAPTIVE_EVENTS_TOTAL.inc(n, kind=kind)
    if info.stats:
        m.COMPILE_SECONDS_TOTAL.inc(
            float(info.stats.get("compile_time_ms", 0) or 0) / 1000.0)
        m.DEVICE_SECONDS_TOTAL.inc(
            float(info.stats.get("device_time_ms", 0) or 0) / 1000.0)
    if info.wall_ms is not None:
        m.QUERY_WALL_SECONDS.observe(info.wall_ms / 1000.0)
        # the serving tier's SLO surface: per-resource-group latency by
        # outcome — a group's p99 regression or failure-rate spike is one
        # PromQL query away (histogram_quantile over group series)
        m.GROUP_WALL_SECONDS.observe(
            info.wall_ms / 1000.0,
            group=info.resource_group or "global", outcome=info.state)


def _ensure_history() -> None:
    """Arm the query-history ring (its listener registers on import):
    lazy so listeners.py has no module-level dependency on history.py,
    unconditional so the ring records no matter who drove the query."""
    from trino_tpu.obs import history  # noqa: F401 — import side effect


def fire_query_completed(info) -> None:
    _ensure_history()
    _record_terminal_metrics(info)
    _dispatch("query_completed", event_from_info(info))


def fire_query_failed(info) -> None:
    _ensure_history()
    _record_terminal_metrics(info)
    _dispatch("query_failed", event_from_info(info))
