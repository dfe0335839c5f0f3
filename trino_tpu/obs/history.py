"""Persistent query-history tier: a bounded ring of completed queries.

Reference parity: the reference keeps completed QueryInfos in
QueryTracker past expiry ONLY briefly; production deployments rely on an
EventListener writing a query-history store (the completed-queries table
every Trino operator queries after an incident). Here the store is
in-process: `HISTORY` is a bounded ring of `CompletedQuery` records fed
from the EventListener bus (query_completed / query_failed — CANCELED
arrives through query_failed with state CANCELED), retaining the final
stats snapshot, the span dump, and the error taxonomy AFTER the live
tracker entry is pruned. Surfaced as `system.runtime.completed_queries`
(connector/system.py) and `GET /v1/query/{id}` (server/app.py), which
fall back here when the tracker no longer knows the id.

Feeding rides the listener bus on purpose — the history tier consumes
the exact payload any external listener plugin would, so it doubles as
the bus's own in-process reference consumer. The listener registers at
module import; the fire_* path imports this module lazily, so direct
runners and servers alike always have the ring armed.

The ring is bounded by `history_max_entries` (session property on the
owning runner; TrinoServer(history_max_entries=...) for deployments).
Eviction is strict FIFO by completion order — the retention contract the
tests pin down.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

from trino_tpu.obs.listeners import EventListener, register_listener

# a few minutes of a served dashboard: at 512 a server completing 20
# queries a second forgot a query's stats 25 s after it ended (PR 31)
DEFAULT_MAX_ENTRIES = 4096


@dataclasses.dataclass
class CompletedQuery:
    """One terminal query, frozen at completion: identity, outcome,
    the device/compile/host time split, and the error taxonomy
    (error_name/error_type/retryable from trino_tpu/errors.py) — the
    record an operator reads after the live tracker pruned the id."""

    query_id: str
    state: str
    user: str
    query: str
    ended_at: float                      # wall-clock epoch seconds
    wall_ms: int = 0
    cpu_time_ms: int = 0                 # host time (device/compile out)
    device_time_ms: float = 0.0
    compile_time_ms: float = 0.0
    rows: int = 0
    output_bytes: int = 0
    retries: int = 0
    faults_injected: int = 0
    resource_group: Optional[str] = None
    peak_memory_bytes: int = 0
    error: Optional[str] = None
    error_name: Optional[str] = None
    error_type: Optional[str] = None
    retryable: Optional[bool] = None
    stats: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, repr=False)
    trace: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, repr=False)
    trace_file: Optional[str] = None     # exported Chrome-trace path


def _taxonomy(error_name: Optional[str]):
    """(error_type, retryable) for a StandardErrorCode name — the code
    registry in trino_tpu/errors.py is the single source of truth."""
    if not error_name:
        return None, None
    from trino_tpu import errors
    for value in vars(errors).values():
        if isinstance(value, errors.ErrorCode) and value.name == error_name:
            return value.type, value.retryable
    return None, None


class QueryHistory:
    """Bounded FIFO ring of CompletedQuery records, lock-guarded (the
    listener bus fires from executor threads while HTTP threads and
    system-table scans read)."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self._lock = threading.Lock()
        self._ring: "collections.deque[CompletedQuery]" = \
            collections.deque(maxlen=max(1, int(max_entries)))
        self.recorded = 0            # lifetime, for the evicted gauge

    @property
    def max_entries(self) -> int:
        return self._ring.maxlen or 0

    def resize(self, max_entries: int) -> None:
        n = max(1, int(max_entries))
        with self._lock:
            if n == self._ring.maxlen:
                return
            # keep the NEWEST entries on a shrink (deque(maxlen) drops
            # from the left as the old ring replays in order)
            self._ring = collections.deque(self._ring, maxlen=n)

    def record(self, entry: CompletedQuery) -> None:
        with self._lock:
            self._ring.append(entry)
            self.recorded += 1

    def list(self) -> List[CompletedQuery]:
        """Oldest-first snapshot (completion order)."""
        with self._lock:
            return list(self._ring)

    def get(self, query_id: str) -> Optional[CompletedQuery]:
        with self._lock:
            for entry in reversed(self._ring):
                if entry.query_id == query_id:
                    return entry
        return None

    def stats(self) -> Dict[str, int]:
        with self._lock:
            n = len(self._ring)
            return {"entries": n, "max_entries": self._ring.maxlen or 0,
                    "recorded": self.recorded, "evicted": self.recorded - n}

    def clear(self) -> None:  # for tests
        with self._lock:
            self._ring.clear()


def record_from_event(event) -> CompletedQuery:
    """Freeze a terminal QueryEvent (obs/listeners.py) into the history
    record shape — THE single CompletedQuery builder (the listener and
    record_from_info both come through here, so a new field can never
    silently exist on one feed and not the other)."""
    stats = event.stats or {}
    error_type, retryable = _taxonomy(event.error_name)
    return CompletedQuery(
        query_id=event.query_id, state=event.state, user=event.user,
        query=event.query, ended_at=time.time(),
        wall_ms=event.wall_ms or 0, cpu_time_ms=event.cpu_time_ms,
        device_time_ms=float(stats.get("device_time_ms", 0.0) or 0.0),
        compile_time_ms=float(stats.get("compile_time_ms", 0.0) or 0.0),
        rows=event.rows, output_bytes=event.output_bytes,
        retries=event.retries, faults_injected=event.faults_injected,
        resource_group=event.resource_group,
        peak_memory_bytes=event.peak_memory_bytes,
        error=event.error, error_name=event.error_name,
        error_type=error_type, retryable=retryable,
        stats=dict(stats) if stats else None,
        trace=event.trace, trace_file=event.trace_file)


def record_from_info(info) -> CompletedQuery:
    """Freeze a terminal QueryInfo (exec/query_tracker.py) into the
    history record shape, through the same event mapping the listener
    bus uses. ended_at converts the tracker's MONOTONIC end stamp to
    the epoch clock (the ring stamps records at completion — a record
    built later from the live tracker must agree, not drift with
    request time)."""
    from trino_tpu.obs.listeners import event_from_info
    rec = record_from_event(event_from_info(info))
    if info.ended is not None:
        import time as _time
        rec.ended_at = _time.time() - (_time.monotonic() - info.ended)
    return rec


HISTORY = QueryHistory()


class _HistoryListener(EventListener):
    """The ring's feed: every terminal event appends one record. FAILED
    and CANCELED queries are retained exactly like FINISHED ones — the
    history tier exists for the post-incident question."""

    def query_completed(self, event) -> None:
        self._record(event)

    def query_failed(self, event) -> None:
        self._record(event)

    @staticmethod
    def _record(event) -> None:
        HISTORY.record(record_from_event(event))


_LISTENER = register_listener(_HistoryListener())
