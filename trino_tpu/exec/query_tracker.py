"""Process-wide query registry + lifecycle states.

Reference parity: execution/QueryTracker.java + QueryStateMachine.java —
every statement entering a runner is registered with a monotonically
assigned id and walks QUEUED -> RUNNING -> FINISHED | FAILED | CANCELED,
carrying the stats rollup (row count, wall time, error name, retry/fault
counters, resource group, memory-pool reservation/kill/leak counters)
that system.runtime.queries and the HTTP server surface.

Concurrency model (round 7): transitions arrive from MANY threads (the
server's executor pool runs queries concurrently while HTTP threads
cancel and page), so the registry lock guards membership and each
QueryInfo carries its own transition lock — the per-query CAS of the
reference's state machine. Illegal transitions (FINISHED -> RUNNING,
resurrecting a CANCELED query) raise instead of silently corrupting the
rollup; cancel keeps its race-tolerant first-writer-wins semantics.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import List, Optional

QUEUED = "QUEUED"
RUNNING = "RUNNING"
FINISHED = "FINISHED"
FAILED = "FAILED"
CANCELED = "CANCELED"

TERMINAL = (FINISHED, FAILED, CANCELED)

# QueryStateMachine's legal edges (terminal states have none)
_ALLOWED = {
    RUNNING: (QUEUED,),
    FINISHED: (RUNNING,),
    FAILED: (QUEUED, RUNNING),
    CANCELED: (QUEUED, RUNNING),
}


@dataclasses.dataclass
class QueryInfo:
    query_id: str
    state: str
    user: str
    query: str
    created: float
    started: Optional[float] = None
    ended: Optional[float] = None
    rows: int = 0
    error: Optional[str] = None
    error_name: Optional[str] = None
    retries: int = 0
    faults_injected: int = 0
    resource_group: Optional[str] = None
    # mesh shape the query executed over ("workers:8"); None for
    # single-device execution
    mesh: Optional[str] = None
    pool_peak_bytes: int = 0
    memory_kills: int = 0        # times the low-memory killer chose us
    leaked_bytes: int = 0        # nonzero ledger at successful end
    # observability rollup (obs/stats.py): HOST execution time (the
    # measured device and compile walls live in stats as
    # device_time_ms/compile_time_ms — cpu_time_ms stopped being
    # device-inclusive in round 13), output bytes, and the full
    # snapshot + span dump the runner stamps before the terminal
    # transition. trace_file is the exported Chrome-trace path when the
    # session ran with trace_export on.
    cpu_time_ms: int = 0
    output_bytes: int = 0
    stats: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)
    trace: Optional[dict] = dataclasses.field(
        default=None, repr=False, compare=False)
    trace_file: Optional[str] = None
    warnings: List[str] = dataclasses.field(default_factory=list)
    # the live memory context while executing (None before/after): lets
    # system.runtime.queries read the current pool reservation
    mem: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    @property
    def wall_ms(self) -> Optional[int]:
        """Elapsed since the query was created — for a served query the
        submit, so the executor queue is in it (stats.queued_ms says how
        much) — or None while it has not begun to run."""
        if self.started is None:
            return None
        end = self.ended if self.ended is not None else time.monotonic()
        return int((end - self.created) * 1000)

    @property
    def pool_reserved_bytes(self) -> int:
        ctx = self.mem
        return int(ctx.reserved) if ctx is not None else 0

    def _check_transition(self, to_state: str) -> None:
        """Validate an edge; the caller sets the stats fields and THEN
        publishes the state (readers don't take the per-info lock, so the
        terminal state must land last)."""
        if self.state not in _ALLOWED[to_state]:
            raise ValueError(
                f"illegal query state transition {self.state} -> "
                f"{to_state} for {self.query_id}")


class QueryTracker:
    def __init__(self, keep: int = 200):
        self._lock = threading.Lock()
        self._seq = itertools.count(1)
        self._queries: "dict[str, QueryInfo]" = {}
        self._keep = keep

    def begin(self, sql: str, user: str = "user",
              query_id: Optional[str] = None,
              resource_group: Optional[str] = None) -> QueryInfo:
        with self._lock:
            if query_id is not None and query_id in self._queries:
                # the HTTP server pre-registers at submit (QUEUED); the
                # runner's begin then adopts that entry instead of
                # double-counting the query
                return self._queries[query_id]
            qid = query_id or f"{time.strftime('%Y%m%d')}_{next(self._seq):06d}"
            info = QueryInfo(qid, QUEUED, user, sql, time.monotonic(),
                             resource_group=resource_group)
            self._queries[qid] = info
            # bound the registry (QueryTracker prunes expired queries)
            while len(self._queries) > self._keep:
                done = next((k for k, v in self._queries.items()
                             if v.state in TERMINAL), None)
                if done is None:
                    break
                del self._queries[done]
        # fire OUTSIDE the registry lock (QueryMonitor.queryCreatedEvent:
        # listeners may themselves consult the tracker)
        from trino_tpu.obs.listeners import fire_query_created
        fire_query_created(info)
        return info

    def running(self, info: QueryInfo) -> None:
        with info.lock:
            info._check_transition(RUNNING)
            info.started = time.monotonic()
            info.state = RUNNING

    def finish(self, info: QueryInfo, rows: int) -> None:
        with info.lock:
            info._check_transition(FINISHED)
            info.rows = rows
            info.ended = time.monotonic()
            info.state = FINISHED
        from trino_tpu.obs.listeners import fire_query_completed
        fire_query_completed(info)

    def fail(self, info: QueryInfo, error: str,
             error_name: Optional[str] = None) -> None:
        with info.lock:
            info._check_transition(FAILED)
            info.error = error
            info.error_name = error_name
            info.ended = time.monotonic()
            info.state = FAILED
        from trino_tpu.obs.listeners import fire_query_failed
        fire_query_failed(info)

    def cancel(self, info: QueryInfo,
               reason: str = "Query was canceled by user") -> None:
        with info.lock:
            if info.state in TERMINAL:
                return        # cancel raced a finish: first writer wins
            info._check_transition(CANCELED)
            info.error = reason
            info.error_name = "USER_CANCELED"
            info.ended = time.monotonic()
            info.state = CANCELED
        from trino_tpu.obs.listeners import fire_query_failed
        fire_query_failed(info)

    def list(self) -> List[QueryInfo]:
        with self._lock:
            return list(self._queries.values())


# the process-wide tracker (DiscoveryNodeManager-style singleton scope)
TRACKER = QueryTracker()
