"""HTTP /v1/statement server over a query runner.

Reference parity: server/protocol/ExecutingStatementResource.java +
dispatcher/QueuedStatementResource.java:95 + DispatchManager.java:140 —
POST /v1/statement submits SQL, the client then follows `nextUri` (GET)
until the response carries no `nextUri`; DELETE on the page URI cancels.
Session state travels in X-Trino-* headers both ways (Set-Session /
Clear-Session on SET/RESET), keeping the server stateless across requests
the way the reference's dispatcher is.

Dispatch model (round 7): queries submit into a RESOURCE-GROUP tree
(exec/resource_groups.py — the InternalResourceGroupManager analog) and a
pool of `max_running` executor threads drains it by weighted-fair
selection. Each query executes on a `runner.for_query()` clone (private
session + fault-tolerance state over shared catalogs), so independent
queries genuinely interleave: JAX dispatch is thread-safe and per-query
device programs queue on the device stream. Admission control: every
level of a query's group chain bounds its queue (`max_queued`) and an
over-limit submit fails with QUERY_QUEUE_FULL
(InternalResourceGroup.canQueueMore); `hard_concurrency` caps a group's
simultaneously running queries and `soft_memory_limit_bytes` stops
admitting queries from a group whose node-pool usage is over the line.
The query's group comes from the `resource_group` session property
(X-Trino-Session header).

Fault tolerance (round 6): the registry is lock-guarded (HTTP threads and
the executors mutate it concurrently) and pruned past `keep` terminal
queries (a pruned id answers 410 Gone, not 404). Every query registers in
the process-wide TRACKER under its server id, so system.runtime.queries
reflects server traffic. DELETE on a RUNNING query sets its cancel event;
the runner observes it at the next cooperative checkpoint
(exec/deadline.py), transitions the query to CANCELED, and frees the
executor for the next queued query. `query_timeout_s` is the per-query
wall-clock cap: one hung query fails with EXCEEDED_TIME_LIMIT instead of
wedging an executor forever.

Serving tier (trino_tpu/serve/): three layers above dispatch make the
repeated-prepared-statement hot path approximately one HTTP round trip:

- STREAMING statement lifecycle: each executing query writes result rows
  into a bounded ring buffer (serve/streaming.ResultStream) as operators
  produce them; `nextUri` paging serves chunk `token` straight off the
  ring, so the client sees its first page BEFORE the query completes and
  a slow client pauses the producer at a cooperative checkpoint instead
  of forcing the server to buffer the full result. Wire states:
  QUEUED -> RUNNING (producing) -> FINISHING (producer done, ring
  draining) -> FINISHED.
- RESULT-CACHE fast path: POST probes the runner's result-set cache
  (serve/caches.py) on the HTTP thread before touching the dispatch
  queue; a hit answers FINISHED — often with the data inline in the POST
  response — with zero planning, zero compiles, zero execution, and no
  executor handoff. INSERT/DDL evicts through the plan cache's
  invalidation hooks, so a stale cached answer is impossible.
- WEIGHTED CPU scheduling: each executor slice's wall charges to the
  query's resource group (ResourceGroupManager.charge), advancing the
  stride pass by seconds/weight — groups share executor time by weight,
  not just by admission counts.

A warmup manifest (`warmup_manifest=` / $TRINO_TPU_WARMUP_MANIFEST,
serve/warmup.py) PREPAREs and pre-executes representative statements at
startup so the first real request binds into a warm plan cache and warm
kernels. OTLP span export (obs/otlp.py) wires in when configured.
"""

from __future__ import annotations

import itertools
import json
import re
import socket
import threading
import time
import uuid
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Sequence

from trino_tpu.errors import QueryCanceledError
from trino_tpu.exec.resource_groups import ResourceGroupManager
from trino_tpu.exec.runner import MaterializedResult
from trino_tpu.serve.streaming import ResultStream
from trino_tpu.server import protocol

PAGE_ROWS = 1000

# What a deployment may depend on (`TrinoServer(requires=...)`): a
# deployment's file names what it needs of the engine, and a server that
# lacks a name does not start. A name is a fact of this engine that a test
# proves, never a switch: nothing reads the set but the constructor's check.
CAPABILITIES = frozenset({
    # proved by tests/test_plan_shapes.py::test_connected_joins_never_cross
    "joins_connected_never_cross",
    # proved by tests/test_q9.py::test_a_new_color_compiles_nothing
    "like_pattern_operand",
})

# live servers, for the /v1/metrics serving-tier gauges (weak: a stopped
# server's registry entry disappears with it)
_SERVERS: "weakref.WeakSet[TrinoServer]" = weakref.WeakSet()


def _server_gauges():
    """Scrape-time gauges over every live server: registry depth by
    state (the dispatch queue-depth signal alongside the per-group
    queued/running gauges)."""
    for srv in list(_SERVERS):
        with srv._lock:
            states: Dict[str, int] = {}
            for q in srv._queries.values():
                states[q.state] = states.get(q.state, 0) + 1
        for state, n in sorted(states.items()):
            yield ("trino_tpu_server_queries",
                   "Registered server queries by protocol state.",
                   n, {"state": state, "port": srv.port})

_SET_SESSION = re.compile(r"^\s*set\s+session\s+(\w+)\s*=\s*(.+?)\s*$",
                          re.IGNORECASE | re.DOTALL)
_RESET_SESSION = re.compile(r"^\s*reset\s+session\s+(\w+)\s*$",
                            re.IGNORECASE)
_PREPARE = re.compile(
    r'^\s*prepare\s+("(?:[^"]|"")*"|\w+)\s+from\s+(.+?)\s*$',
    re.IGNORECASE | re.DOTALL)
_DEALLOCATE = re.compile(
    r'^\s*deallocate\s+prepare\s+("(?:[^"]|"")*"|\w+)\s*$',
    re.IGNORECASE)


class _Query:
    def __init__(self, query_id: str, slug: str, sql: str, headers: dict):
        self.query_id = query_id
        self.slug = slug
        self.sql = sql
        self.headers = headers
        self.state = "QUEUED"
        self.result: Optional[MaterializedResult] = None
        self.error: Optional[dict] = None
        self.update_type: Optional[str] = None
        self.set_session: Optional[tuple] = None
        self.clear_session: Optional[str] = None
        # prepared-statement protocol state (StatementClientV1): a
        # PREPARE echoes (name, sql) back via X-Trino-Added-Prepare so
        # the stateless client re-sends it per request; DEALLOCATE
        # echoes the name via X-Trino-Deallocated-Prepare
        self.added_prepare: Optional[tuple] = None
        self.deallocated_prepare: Optional[str] = None
        # streaming result ring (serve/streaming.ResultStream): when the
        # runner opens it, paging serves chunks off the ring instead of
        # q.result; stays unopened for non-query statements, writers,
        # retry-capable sessions, and result-cache hits
        self.stream: Optional[ResultStream] = None
        self.cancelled = False
        # crossed by threads: DELETE (HTTP) cancels it, the runner's
        # cooperative checkpoints (executor thread) observe it; the
        # CancelEvent carries the request timestamp the runner turns
        # into preempt_latency_ms
        from trino_tpu.exec.deadline import CancelEvent
        self.cancel_event = CancelEvent()
        self.info = None               # QueryTracker entry
        self.started = time.monotonic()
        # when an executor thread took it off its resource group's
        # queue (same clock): started -> dequeued is the queue wait
        self.dequeued: Optional[float] = None

    @property
    def elapsed_ms(self) -> int:
        return int((time.monotonic() - self.started) * 1000)

    @property
    def queued_ms(self) -> int:
        """Submit -> an executor thread took it (so far, while queued;
        0 for a result-cache hit answered on the HTTP thread)."""
        if self.dequeued is None:
            return self.elapsed_ms if self.state == "QUEUED" else 0
        return int((self.dequeued - self.started) * 1000)

    @property
    def times(self) -> dict:
        """The statement response's clock fields (protocol.stats_json)."""
        return {"elapsed_ms": self.elapsed_ms, "queued_ms": self.queued_ms}

    @property
    def done(self) -> bool:
        # FINISHING counts: execution is over (cancel is a no-op, the
        # entry is prunable past `keep` — pruning an undrained stream
        # loses its chunks exactly like pruning buffered results)
        return self.state in ("FINISHED", "FINISHING", "FAILED",
                              "CANCELED")


class TrinoServer:
    """Wire-compatible statement server wrapping a query runner."""

    def __init__(self, runner, host: str = "127.0.0.1", port: int = 0,
                 max_queued: int = 200, keep: int = 200,
                 query_timeout_s: Optional[float] = None,
                 max_running: int = 4,
                 resource_groups: Optional[ResourceGroupManager] = None,
                 resource_groups_path: Optional[str] = None,
                 plan_cache_max_entries: Optional[int] = None,
                 streaming: bool = True,
                 result_cache: bool = True,
                 scan_cache: bool = True,
                 table_cache: bool = True,
                 stream_ring_chunks: int = 16,
                 stream_stall_timeout_s: float = 300.0,
                 warmup_manifest=None,
                 otlp_export: Optional[str] = None,
                 metrics_wall_buckets=None,
                 trace_dir: Optional[str] = None,
                 history_max_entries: Optional[int] = None,
                 drain_timeout_s: float = 10.0,
                 drain_idle_grace_s: float = 1.0,
                 listen_fd: Optional[int] = None,
                 requires: Sequence[str] = ()):
        # the handshake: a deployment that depends on what this engine
        # lacks is refused here, before a session property is set or a
        # table is warmed (CAPABILITIES, above)
        lacking = sorted(set(requires) - CAPABILITIES)
        if lacking:
            raise ValueError(f"this engine lacks: {', '.join(lacking)}")
        self.runner = runner
        # serving tier defaults: the server IS the production front door,
        # so result/scan caching default ON for server sessions (clones
        # inherit through the session property bag); direct runners keep
        # the metadata.py defaults (off)
        self.streaming_enabled = bool(streaming)
        self.stream_ring_chunks = int(stream_ring_chunks)
        self.stream_stall_timeout_s = float(stream_stall_timeout_s)
        self.result_cache_enabled = bool(result_cache)
        if result_cache:
            runner.session.set("result_cache_enabled", True)
        if scan_cache:
            runner.session.set("scan_cache_enabled", True)
        if table_cache:
            # the device-resident hot-table tier (exec/table_cache.py):
            # server sessions promote hot columns into HBM across
            # queries; warmup `tables:` entries preload them at start()
            runner.session.set("table_cache_enabled", True)
        # warmup manifest (serve/warmup.py): held here, applied in
        # start() BEFORE the executors spin up so the first real request
        # finds a warm plan cache and warm kernels
        import os as _os_env
        if warmup_manifest is None:
            warmup_manifest = _os_env.environ.get(
                "TRINO_TPU_WARMUP_MANIFEST") or None
        self._warmup_manifest = warmup_manifest
        self.warmup_report: List[dict] = []
        # OTLP span export (obs/otlp.py): off unless configured here or
        # via $TRINO_TPU_OTLP_ENDPOINT / $TRINO_TPU_OTLP_FILE
        from trino_tpu.obs.otlp import install_otlp_exporter
        self.otlp_exporter = install_otlp_exporter(otlp_export)
        # Chrome-trace export: a server constructed with trace_dir
        # exports EVERY query's span tree as Perfetto-loadable JSON into
        # that directory (QueryInfo.trace_file / GET
        # /v1/query/{id}/trace); the session property rides to
        # for_query() clones through the shared property bag
        if trace_dir is not None:
            runner._trace_dir = str(trace_dir)
            runner.session.set("trace_export", True)
        # query-history retention (obs/history.py): deployment-level
        # bound on the completed-queries ring, same owning-runner
        # discipline as plan_cache_max_entries
        if history_max_entries is not None:
            from trino_tpu.obs.history import HISTORY
            runner.session.set("history_max_entries",
                               int(history_max_entries))
            HISTORY.resize(int(history_max_entries))
        # deployment-tuned wall histogram buckets: the process default
        # is session-independent ($TRINO_TPU_METRICS_WALL_BUCKETS or the
        # static obs/metrics.DEFAULT_WALL_BUCKETS); a server that knows
        # its workload's latency envelope re-buckets here (the family
        # resets — restart semantics, see Histogram.set_buckets)
        if metrics_wall_buckets is not None:
            from trino_tpu.obs.metrics import set_wall_buckets
            set_wall_buckets(metrics_wall_buckets)
        # server-level plan-cache sizing: per-request X-Trino-Session
        # headers land on `for_query()` clones, which never resize the
        # SHARED cache (one client must not evict everyone's warm plans),
        # so the deployment bound is a constructor parameter on the
        # owning runner. The session property is set too: if the base
        # runner ever plans directly, its miss path re-reads the property
        # and must not snap the bound back to the default.
        if plan_cache_max_entries is not None:
            runner.session.set("plan_cache_max_entries",
                               int(plan_cache_max_entries))
            # resize (under the cache lock), not a bare attribute write:
            # a shrink over an already-warm runner must evict now
            runner._plan_cache.resize(int(plan_cache_max_entries))
        # cross-process compile reuse: XLA's on-disk cache, placed by the
        # one rule of trino_tpu.enable_persistent_cache(), so a cold
        # server start reloads compiled executables instead of recompiling
        # — with literal hoisting the cached programs are literal-free, so
        # the disk entries cover every parameter variant of a shape. The
        # in-process jit-cache LRU (exec/jit_cache.py) layers above this.
        import trino_tpu
        trino_tpu.enable_persistent_cache()
        # size the node pool from the backend's measured per-device
        # memory at server startup (HBM minus scan-cache budget); CPU
        # backends keep the static default (exec/memory.autosize_node_pool)
        from trino_tpu.exec.memory import autosize_node_pool
        autosize_node_pool()
        self.keep = keep
        self.query_timeout_s = query_timeout_s
        self.max_running = max(1, int(max_running))
        # the group tree this server dispatches through; callers may hand
        # in a preconfigured manager (group limits/weights) or a JSON
        # config file (`resource_groups.path` — the file-based
        # ResourceGroupConfigurationManager analog). max_queued stays the
        # SERVER-WIDE admission bound (round-5 contract) on top of
        # per-group budgets
        if resource_groups is None and resource_groups_path is not None:
            resource_groups = ResourceGroupManager.from_file(
                resource_groups_path, default_max_queued=max_queued,
                max_total_queued=max_queued)
        self.groups = resource_groups or ResourceGroupManager(
            default_max_queued=max_queued, max_total_queued=max_queued)
        # resource-group config hot-reload (round 14): an edited config
        # re-applies on mtime change WITHOUT a restart — fleet-wide
        # quota/limit changes don't need a rolling restart. Checked
        # (throttled) on the POST path, through the SAME FileWatch
        # primitive the fleet's quota maps use so engine and workers
        # cannot drift on when an edit takes effect.
        from trino_tpu.fleet.registry import FileWatch
        self._rg_path = resource_groups_path
        self._rg_watch = FileWatch(resource_groups_path)
        self._rg_reloads = 0
        # graceful drain (round 14): stop() stops accepting, then lets
        # RUNNING queries and actively-consumed result streams finish
        # before teardown. `drain_idle_grace_s` bounds how long an
        # ABANDONED stream (no page request) holds the drain.
        self.drain_timeout_s = float(drain_timeout_s)
        self.drain_idle_grace_s = float(drain_idle_grace_s)
        self.draining = threading.Event()
        # fleet integration seam: when set (fleet/server.py), the
        # result-cache fast path's per-group QPS quota check routes to
        # the FLEET-WIDE shared-memory buckets instead of the manager's
        # in-process ones, so engine-landed and worker-landed hits drain
        # one bucket per group
        self.fast_path_quota = None
        self._lock = threading.Lock()
        self._queries: Dict[str, _Query] = {}
        self._pruned: Dict[str, None] = {}   # ordered set of purged ids
        self._seq = itertools.count(1)
        self._stopping = threading.Event()
        handler = self._make_handler()
        # ThreadingHTTPServer's handler threads are daemonic, so
        # server_close() after the drain below never blocks on a parked
        # keep-alive connection
        if listen_fd is None:
            self._httpd = ThreadingHTTPServer((host, port), handler)
        else:
            # adopt an ALREADY-LISTENING socket received over SCM_RIGHTS
            # (fleet/handoff.py): the kernel accept queue — including
            # connections that arrived while no process was accepting —
            # transfers with the fd, which is what makes a planned
            # engine swap zero-drop. bind_and_activate=False skips
            # bind/listen; the placeholder socket is swapped for the fd.
            self._httpd = ThreadingHTTPServer((host, port), handler,
                                              bind_and_activate=False)
            placeholder = self._httpd.socket
            self._httpd.socket = socket.socket(fileno=listen_fd)
            placeholder.close()
            self._httpd.server_address = \
                self._httpd.socket.getsockname()[:2]
            self._httpd.server_name, self._httpd.server_port = \
                self._httpd.server_address
        self._thread: Optional[threading.Thread] = None
        self._executors: List[threading.Thread] = []
        _SERVERS.add(self)
        from trino_tpu.obs.metrics import REGISTRY
        REGISTRY.register_gauges(_server_gauges)   # idempotent

    # ---------------------------------------------------------- lifecycle

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def base_uri(self) -> str:
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def start(self) -> "TrinoServer":
        if self._warmup_manifest is not None:
            # synchronous, pre-executor: by the time start() returns, the
            # manifest's shapes are PREPAREd (shared map), planned (plan
            # cache), and compiled (jit cache, persistent-cache-backed)
            from trino_tpu.serve.warmup import apply_warmup
            self.warmup_report = apply_warmup(self.runner,
                                              self._warmup_manifest)
        for i in range(self.max_running):
            th = threading.Thread(target=self._drain, daemon=True,
                                  name=f"query-executor-{i}")
            th.start()
            self._executors.append(th)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self, drain_timeout_s: Optional[float] = None) -> None:
        """Graceful shutdown with drain (round 14): stop accepting new
        connections, reject new statements, then let in-flight work
        finish before teardown — RUNNING queries complete, and open
        `nextUri` result streams keep serving pages off still-open
        connections until drained (or abandoned past the idle grace).
        Queued-but-unstarted queries are canceled (they never produced
        anything a client could lose), and whatever is left at the
        drain deadline is canceled cooperatively. `drain_timeout_s=0`
        restores the old immediate-teardown behavior."""
        drain_s = self.drain_timeout_s if drain_timeout_s is None \
            else float(drain_timeout_s)
        # "stop accepting" means STATEMENTS, not connections: clients
        # without keep-alive open a fresh connection per nextUri page,
        # so the listener must keep serving GET/DELETE until the drain
        # completes — new POSTs answer SERVER_SHUTTING_DOWN immediately
        self.draining.set()
        deadline = time.monotonic() + max(drain_s, 0.0)
        with self._lock:
            queries = list(self._queries.values())
        for q in queries:            # never-started queries just cancel
            if q.state == "QUEUED":
                q.cancelled = True
                q.cancel_event.cancel()
        while time.monotonic() < deadline:
            if not self._drain_pending():
                break
            time.sleep(0.05)
        with self._lock:
            leftovers = [q for q in self._queries.values() if not q.done]
        for q in leftovers:          # past the deadline: cancel, don't hang
            q.cancelled = True
            q.cancel_event.cancel()
        self._httpd.shutdown()
        self._stopping.set()
        for th in self._executors:
            th.join(timeout=10)
        self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
        if self.otlp_exporter is not None:
            # the listener registry holds strong references: a stopped
            # server's exporter would keep exporting (and a restarted
            # one would double-export) every later query in the process
            from trino_tpu.obs.otlp import uninstall_otlp_exporter
            uninstall_otlp_exporter(self.otlp_exporter)
            self.otlp_exporter = None

    def _drain_pending(self) -> bool:
        """True while something a client could still lose is in flight:
        a RUNNING query, or an opened result stream that is not drained
        AND has seen consumer progress within the idle grace (an
        abandoned stream — client gone without DELETE — must not hold
        the drain for the full deadline; its query is canceled by the
        deadline sweep or the stall guard)."""
        now = time.monotonic()
        with self._lock:
            queries = list(self._queries.values())
        for q in queries:
            if q.state == "RUNNING":
                stream = q.stream
                if stream is not None and stream.opened and \
                        now - stream.last_consumer_contact > \
                        self.drain_idle_grace_s:
                    q.cancel_event.cancel()   # parked on a gone client
                    continue
                return True
            stream = q.stream
            if stream is not None and stream.opened \
                    and not stream.drained and q.error is None \
                    and not q.cancelled:
                if now - stream.last_consumer_contact <= \
                        self.drain_idle_grace_s:
                    return True    # actively consumed: let it finish
        return False

    def _maybe_reload_groups(self) -> None:
        """Resource-group config hot-reload: re-apply the JSON file when
        its mtime changes (throttled to one stat/s). A malformed or
        deleted file logs a warning and keeps the previous tree — an
        operator mishap must not strip a production server of its
        limits (quota MAPS are declarative and clear instead; see
        FileWatch's docstring for the split)."""
        if not self._rg_watch.changed():
            return
        import json as _json
        try:
            with open(self._rg_path) as fh:
                tree = _json.load(fh)
            # validate the WHOLE tree on a throwaway manager first: a
            # typo in group B must not leave group A half-reconfigured
            # (configure_from_dict applies specs sequentially)
            from trino_tpu.exec.resource_groups import _MANAGERS
            staged = ResourceGroupManager()
            _MANAGERS.discard(staged)   # not a live manager: keep it
            # out of system.runtime.resource_groups and the gauges
            staged.configure_from_dict(tree)
            self.groups.configure_from_dict(tree)
            self._rg_reloads += 1
        except Exception as e:   # noqa: BLE001 — keep the old config
            import logging
            logging.getLogger("trino_tpu.server").warning(
                "resource-group config reload failed for %s: %s",
                self._rg_path, e)

    # ---------------------------------------------------------- execution

    @staticmethod
    def _session_overrides(headers: dict) -> dict:
        """Parse the X-Trino-Session header once for everyone (reference
        wire format, ProtocolHeaders/StatementClientV1: comma-separated
        key=value pairs, values URL-encoded so raw commas never appear
        inside a value)."""
        from urllib.parse import unquote
        overrides = {}
        for part in headers.get("x-trino-session", "").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                overrides[k.strip()] = unquote(v.strip())
        return overrides

    def _group_for(self, headers: dict) -> str:
        """The query's resource group: the `resource_group` key of the
        client's X-Trino-Session header, else the base session default."""
        group = self._session_overrides(headers).get("resource_group")
        if group:
            return group
        try:
            return str(self.runner.session.get("resource_group"))
        except Exception:
            return "global"

    def _new_query_id(self) -> str:
        day = time.strftime("%Y%m%d")
        return f"{day}_{next(self._seq):06d}_{uuid.uuid4().hex[:5]}"

    def _submit(self, sql: str, headers) -> _Query:
        """Admit + enqueue (DispatchManager.createQuery analog): returns
        immediately with the QUEUED query; an executor-pool worker runs
        it after weighted-fair selection from its resource group."""
        from trino_tpu.exec.query_tracker import TRACKER
        qid = self._new_query_id()
        # lower-cased snapshot: header lookup must stay case-insensitive
        # after leaving the email.Message (HTTP header names are)
        q = _Query(qid, uuid.uuid4().hex[:12], sql,
                   {k.lower(): v for k, v in headers.items()})
        user = q.headers.get("x-trino-user", "user")
        # resolve the group BEFORE registering: the query_created event
        # fires from begin() and must carry the resource group
        group = self._group_for(q.headers)
        q.info = TRACKER.begin(sql, user=user, query_id=qid,
                               resource_group=group)
        q.started = q.info.created      # one submit stamp for both clocks
        with self._lock:
            self._queries[qid] = q
            self._prune_locked()
        if not self.groups.submit(group, q, qid):
            q.state = "FAILED"
            q.error = protocol.error_json(
                f"Too many queued queries for resource group {group!r}",
                error_name="QUERY_QUEUE_FULL",
                error_code=131074, error_type="INSUFFICIENT_RESOURCES")
            TRACKER.fail(q.info, "Too many queued queries",
                         error_name="QUERY_QUEUE_FULL")
        return q

    def _try_cached(self, sql: str, headers) -> Optional[_Query]:
        """POST-time result-cache probe — the serving tier's hot path.
        A hit is answered on the HTTP thread: no dispatch queue, no
        executor handoff, zero planning, zero compiles, zero execution
        (admission control is skipped too: a cache hit consumes no
        executor resources to admit). Any wrinkle — probe miss, parse
        error, header trouble — returns None and the normal dispatch
        path decides, so failures surface exactly as they always did."""
        from trino_tpu.exec.query_tracker import TRACKER
        if not self.result_cache_enabled:
            return None
        # cheap prefix gate: only statement kinds peek_cached_result can
        # resolve are worth a probe — DDL/INSERT/SET/PREPARE skip the
        # clone + parse entirely on the dispatch-bound path
        head = sql.lstrip()[:8].upper()
        if not head.startswith(("SELECT", "EXECUTE", "WITH", "VALUES",
                                "(", "TABLE")):
            return None
        hdrs = {k.lower(): v for k, v in headers.items()}
        try:
            runner = self.runner.for_query()
            session = runner.session
            catalog = hdrs.get("x-trino-catalog")
            schema = hdrs.get("x-trino-schema")
            if catalog:
                session.catalog = catalog
            if schema:
                session.schema = schema
            from trino_tpu.metadata import SESSION_PROPERTY_DEFAULTS
            for k, v in self._session_overrides(hdrs).items():
                if k in SESSION_PROPERTY_DEFAULTS:
                    session.set(k, v)
            self._apply_prepared_header(runner, hdrs)
            entry = runner.peek_cached_result(sql)
        except Exception:   # noqa: BLE001 — defer to the dispatch path
            return None
        if entry is None:
            return None
        qid = self._new_query_id()
        q = _Query(qid, uuid.uuid4().hex[:12], sql, hdrs)
        user = hdrs.get("x-trino-user", "user")
        group = self._group_for(hdrs)
        # per-group QPS quota on the fast path (round 14): every chain
        # level with a configured result_cache_qps must grant a token
        # BEFORE the hit is served; over quota answers QUERY_QUEUE_FULL
        # — the enforcement ROADMAP promised for the served_from_cache
        # accounting. Under a fleet, the check routes to the shared-
        # memory buckets (fast_path_quota) so the quota binds fleet-wide.
        if self.fast_path_quota is not None:
            allowed = self.fast_path_quota(group)
            if allowed:
                self.groups.record_cache_hit(group, enforce=False)
            else:
                # enforcement happened in the shared bucket; the group's
                # rejection counters must still move
                self.groups.record_cache_hit_rejection(group)
        else:
            allowed = self.groups.record_cache_hit(group) is not None
        if not allowed:
            q.state = "FAILED"
            q.error = protocol.error_json(
                f"Result-cache QPS quota exceeded for resource group "
                f"{group!r}", error_name="QUERY_QUEUE_FULL",
                error_code=131074, error_type="INSUFFICIENT_RESOURCES")
            q.info = TRACKER.begin(sql, user=user, query_id=qid,
                                   resource_group=group)
            TRACKER.fail(q.info, "Result-cache QPS quota exceeded",
                         error_name="QUERY_QUEUE_FULL")
            with self._lock:
                self._queries[qid] = q
                self._prune_locked()
            return q
        info = TRACKER.begin(sql, user=user, query_id=qid,
                             resource_group=group)
        q.info = info
        info.cpu_time_ms = 0
        info.output_bytes = entry.output_bytes
        # the delivery-mode-consistent stats contract: a hit reports the
        # SAME output rows/bytes a real run would with the zero-work
        # fields provably zero — built from a real collector snapshot so
        # the key set never drifts from obs/stats.py
        from trino_tpu.obs.stats import QueryStatsCollector
        col = QueryStatsCollector(qid)
        col.result_cache_hits = 1
        col.add_output(entry.row_count, entry.output_bytes)
        col.finish()
        stats = col.snapshot()
        stats["wall_s"] = 0.0
        info.stats = stats
        q.result = MaterializedResult(
            list(entry.column_names), list(entry.column_types),
            list(entry.rows), row_count=entry.row_count)
        # group accounting already happened at the quota gate above (the
        # fast path still skips submit/take/finish: a hit costs no
        # executor resources to admit)
        TRACKER.running(info)
        TRACKER.finish(info, entry.row_count)
        q.state = "FINISHED"
        with self._lock:
            self._queries[qid] = q
            self._prune_locked()
        return q

    def _prune_locked(self) -> None:
        """Bound the paging registry (QueryTracker expiry analog): drop
        the oldest terminal queries past `keep`, remembering their ids so
        a late GET answers 410 Gone instead of 404."""
        if len(self._queries) <= self.keep:
            return
        for qid in list(self._queries):
            if len(self._queries) <= self.keep:
                break
            if self._queries[qid].done:
                del self._queries[qid]
                self._pruned[qid] = None
        while len(self._pruned) > 5 * self.keep:
            self._pruned.pop(next(iter(self._pruned)))

    def _drain(self) -> None:
        """Executor-pool worker: block on the resource-group manager for
        the next weighted-fair pick, run it on a per-query runner clone;
        paging of finished queries proceeds on HTTP threads."""
        from trino_tpu.exec.query_tracker import TRACKER
        while not self._stopping.is_set():
            got = self.groups.take(timeout=0.2)
            if got is None:
                continue
            group, q = got
            slice_t0 = q.dequeued = time.monotonic()
            try:
                if q.cancelled:
                    q.state = "CANCELED"
                    TRACKER.cancel(q.info)
                    continue
                q.state = "RUNNING"
                try:
                    self._execute(q)
                    if q.cancelled and q.result is None:
                        q.state = "CANCELED"
                    elif q.error is not None:
                        q.state = "FAILED"
                    elif q.stream is not None and q.stream.opened \
                            and not q.stream.drained \
                            and (q.result is None
                                 or len(q.result.rows)
                                 != q.result.reported_rows):
                        # producer done, ring still draining AND the ring
                        # is the only copy: paging flips it to FINISHED
                        # on the final chunk (with a complete
                        # materialized copy the buffered path serves and
                        # the query is simply FINISHED)
                        q.state = "FINISHING"
                    else:
                        q.state = "FINISHED"
                except BaseException as e:  # noqa: BLE001 — keep draining
                    q.error = protocol.error_from_exception(e)
                    q.state = "FAILED"
                    self._fail_tracker(q, e)
            finally:
                # weighted CPU scheduling: this slice's wall charges to
                # the group chain (stride advances by seconds/weight),
                # so the next pick favors groups that consumed less
                # executor time per unit weight
                self.groups.charge(group, time.monotonic() - slice_t0,
                                   query_id=q.query_id)
                self.groups.finish(group, q.query_id)

    @staticmethod
    def _fail_tracker(q: _Query, exc: BaseException) -> None:
        """Transition the pre-registered tracker entry when a failure
        happens OUTSIDE runner.execute() (e.g. a malformed session
        property raising at set() time): without this the entry stays
        QUEUED forever — a phantom row in system.runtime.queries that
        pruning (terminal-only) never removes, and no query_failed
        event/metrics ever fire."""
        from trino_tpu.errors import classify
        from trino_tpu.exec.query_tracker import TERMINAL, TRACKER
        info = q.info
        if info is None or info.state in TERMINAL:
            return
        try:
            TRACKER.fail(info, f"{type(exc).__name__}: {exc}",
                         error_name=classify(exc).name)
        except ValueError:
            pass    # lost the race to a concurrent terminal transition

    @staticmethod
    def _apply_prepared_header(runner, headers: dict) -> None:
        """X-Trino-Prepared-Statement: comma-separated name=value pairs,
        both URL-encoded, each value a statement's SQL — the stateless
        client re-sends every prepared statement per request
        (ProtocolHeaders.requestPreparedStatement). Applied to a PRIVATE
        overlay of the runner's prepared map, so concurrent clients'
        names never collide server-side."""
        from urllib.parse import unquote
        from trino_tpu.sql import parse_statement
        # overlay even when the header is absent: a PREPARE executed by
        # this query must not leak into the shared base map (the client
        # gets it back via X-Trino-Added-Prepare instead)
        runner._prepared = dict(runner._prepared)
        header = headers.get("x-trino-prepared-statement", "")
        for part in header.split(","):
            if "=" not in part:
                continue
            name, _, enc = part.partition("=")
            runner._prepared[unquote(name.strip())] = \
                parse_statement(unquote(enc.strip()))

    def _execute(self, q: _Query) -> None:
        headers = q.headers
        # per-query runner clone: a PRIVATE session over the shared
        # catalogs, so concurrent executors never cross-contaminate
        # session state (the protocol is stateless — the
        # X-Trino-Set-Session response header hands SET SESSION state
        # back to THIS client, which re-sends it via X-Trino-Session)
        runner = self.runner.for_query()
        session = runner.session
        sink = None
        if self.streaming_enabled:
            # the runner opens it only for streaming-safe shapes (plain
            # reads under retry_policy=NONE without chaos); unopened, the
            # paging path falls back to the buffered result
            sink = ResultStream(
                max_chunks=self.stream_ring_chunks,
                chunk_rows=PAGE_ROWS,
                stall_timeout_s=self.stream_stall_timeout_s)
            q.stream = sink
        try:
            catalog = headers.get("x-trino-catalog")
            schema = headers.get("x-trino-schema")
            if catalog:
                session.catalog = catalog
            if schema:
                session.schema = schema
            from trino_tpu.metadata import SESSION_PROPERTY_DEFAULTS
            for k, v in self._session_overrides(headers).items():
                if k not in SESSION_PROPERTY_DEFAULTS:
                    continue    # tolerate properties this engine lacks
                # a KNOWN property with a malformed value fails the query
                # (set() coerces to the default's type at SET time) — the
                # pre-coercion contract, where the raw string failed at
                # execute(), kept the same visibility
                session.set(k, v)
            self._apply_prepared_header(runner, headers)
            # the runner builds the query's deadline AFTER the session
            # overrides apply (so header-sent limits bind), from the
            # submit time (query_max_run_time counts queueing) capped
            # by the server's per-query wall-clock limit, and adopts
            # q.cancel_event so DELETE cancels cooperatively
            result = runner.execute(
                q.sql, query_id=q.query_id, queued_at=q.started,
                dequeued_at=q.dequeued, wall_cap_s=self.query_timeout_s,
                cancel_event=q.cancel_event, result_sink=sink)
            m = _SET_SESSION.match(q.sql)
            if m:
                q.update_type = "SET SESSION"
                q.set_session = (m.group(1),
                                 m.group(2).strip().strip("'"))
            m = _RESET_SESSION.match(q.sql)
            if m:
                q.update_type = "RESET SESSION"
                q.clear_session = m.group(1)
            m = _PREPARE.match(q.sql)
            if m:
                q.update_type = "PREPARE"
                # echo the PARSER-normalized name (unquoted identifiers
                # lowercase, quoted verbatim): the stateless client
                # re-sends this key per request and EXECUTE resolves
                # names through the parser again, so echoing the raw
                # capture would install a key EXECUTE can never find.
                # The statement text rides from the regex (the AST can't
                # be un-parsed back to SQL).
                from trino_tpu.sql import parse_statement
                q.added_prepare = (parse_statement(q.sql).name.value,
                                   m.group(2).strip())
            m = _DEALLOCATE.match(q.sql)
            if m:
                q.update_type = "DEALLOCATE"
                from trino_tpu.sql import parse_statement
                q.deallocated_prepare = parse_statement(q.sql).name.value
            # publish LAST: a concurrently-polling client that sees
            # q.result must also see update_type/set_session (else the
            # X-Trino-Set-Session header is lost)
            q.result = result
            if sink is not None:
                sink.close()    # producer done; ring drains to the client
        except QueryCanceledError as e:
            q.cancelled = True         # surfaces as CANCELED, not FAILED
            if sink is not None:
                sink.fail(e)           # wake a blocked consumer
        except Exception as e:  # surface as QueryError, not HTTP 500
            q.error = protocol.error_from_exception(e)
            if sink is not None:
                sink.fail(e)
            # failures BEFORE runner.execute() (session-override coercion)
            # must still terminate the tracker entry; inside execute() the
            # runner already transitioned it (this is then a no-op)
            self._fail_tracker(q, e)

    # ----------------------------------------------------- query REST API

    @staticmethod
    def _query_info_payload(qid: str) -> Optional[dict]:
        """GET /v1/query/{id} (QueryResource.getQueryInfo analog): the
        live tracker entry while it exists, the history-ring record
        after pruning — a just-finished query's stats stay queryable
        past the tracker's retention bound."""
        from trino_tpu.exec.query_tracker import TRACKER
        from trino_tpu.obs.history import HISTORY, record_from_info
        for info in TRACKER.list():
            if info.query_id == qid:
                # the SAME record shape the history branch serves (one
                # builder — a consumer must never see fields flicker in
                # and out with prune timing), plus the live-only extras
                from trino_tpu.exec.query_tracker import TERMINAL
                rec = record_from_info(info)
                payload = TrinoServer._record_payload(rec, "tracker")
                if info.state not in TERMINAL:
                    payload["endedAt"] = None   # still executing
                return payload
        entry = HISTORY.get(qid)
        if entry is None:
            return None
        return TrinoServer._record_payload(entry, "history")

    @staticmethod
    def _record_payload(rec, source: str) -> dict:
        return {
            "queryId": rec.query_id, "state": rec.state,
            "user": rec.user, "query": rec.query,
            "rows": rec.rows, "outputBytes": rec.output_bytes,
            "wallMillis": rec.wall_ms,
            "cpuTimeMillis": rec.cpu_time_ms,
            "deviceTimeMillis": rec.device_time_ms,
            "compileTimeMillis": rec.compile_time_ms,
            "error": rec.error, "errorName": rec.error_name,
            "errorType": rec.error_type, "retryable": rec.retryable,
            "retries": rec.retries,
            "resourceGroup": rec.resource_group,
            "peakMemoryBytes": rec.peak_memory_bytes,
            "stats": rec.stats, "endedAt": rec.ended_at,
            "traceFile": rec.trace_file,
            "source": source,
        }

    @staticmethod
    def _query_trace_payload(qid: str) -> Optional[dict]:
        """GET /v1/query/{id}/trace: the query's span tree as
        Chrome-trace JSON (generated on demand — works whether or not
        the session exported a trace file), served from the live
        tracker or the history ring."""
        from trino_tpu.exec.query_tracker import TRACKER
        from trino_tpu.obs.spans import to_chrome_trace
        trace = None
        for info in TRACKER.list():
            if info.query_id == qid:
                trace = info.trace
                break
        if trace is None:
            from trino_tpu.obs.history import HISTORY
            entry = HISTORY.get(qid)
            if entry is not None:
                trace = entry.trace
        if trace is None:
            return None
        return to_chrome_trace(trace, qid)

    # ------------------------------------------------------------ paging

    def _page_uri(self, q: _Query, token: int) -> str:
        return (f"{self.base_uri}/v1/statement/executing/"
                f"{q.query_id}/{q.slug}/{token}")

    def _warnings_for(self, q: _Query) -> list:
        info = q.info
        if info is None or not info.warnings:
            return []
        return [protocol.warning_json(w) for w in info.warnings]

    def _response_for(self, q: _Query, token: int) -> dict:
        info = q.info
        # live while RUNNING (info.mem is the executing ledger), final
        # after close (info.pool_peak_bytes)
        peak = 0
        if info is not None:
            peak = max(info.pool_peak_bytes,
                       info.mem.peak if info.mem is not None else 0)
        if q.error is not None:
            return protocol.query_results(
                q.query_id, self.base_uri, state="FAILED", error=q.error,
                **q.times, peak_memory_bytes=peak,
                warnings=self._warnings_for(q))
        # a materialized result outranks a cancel flag: the query beat the
        # cancel to the finish line, so its buffered pages stay servable
        # (the reference treats cancel of a terminal query as a no-op)
        if q.cancelled and q.result is None:
            return protocol.query_results(
                q.query_id, self.base_uri, state="CANCELED",
                error=protocol.error_json(
                    "Query was canceled", error_name="USER_CANCELED",
                    error_code=3, error_type="USER_ERROR"),
                **q.times)
        stream = q.stream
        res = q.result
        if stream is not None and stream.opened and (
                res is None or len(res.rows) != res.reported_rows):
            # ring-only delivery: while executing (res is None) and for
            # results whose materialized copy was dropped past the cache
            # bound. Once a COMPLETE copy exists, the buffered path below
            # serves instead — its 1000-row pages are chunk-identical to
            # the ring's, and stay re-readable after the ring drains
            # (the pre-streaming paging contract)
            return self._stream_response(q, stream, token, info, peak)
        if q.result is None:
            # still queued/running: same token again (client poll loop)
            return protocol.query_results(
                q.query_id, self.base_uri,
                next_uri=self._page_uri(q, token), state=q.state,
                **q.times, peak_memory_bytes=peak)
        res = q.result
        cols = protocol.columns_json(res.column_names, res.column_types)
        lo, hi = token * PAGE_ROWS, (token + 1) * PAGE_ROWS
        chunk = res.rows[lo:hi]
        data = protocol.encode_rows(chunk, res.column_types)
        has_more = hi < len(res.rows)
        spilled = 0
        if info is not None and info.stats:
            spilled = int(info.stats.get("spilled_bytes", 0))
        return protocol.query_results(
            q.query_id, self.base_uri, columns=cols, data=data,
            next_uri=self._page_uri(q, token + 1) if has_more else None,
            state="RUNNING" if has_more else "FINISHED",
            update_type=q.update_type, rows=len(res.rows),
            **q.times, peak_memory_bytes=peak,
            cpu_time_ms=info.cpu_time_ms if info is not None else None,
            processed_bytes=info.output_bytes if info is not None else 0,
            spilled_bytes=spilled,
            warnings=self._warnings_for(q))

    def _stream_response(self, q: _Query, stream: ResultStream,
                         token: int, info, peak: int) -> dict:
        """Incremental paging off the result ring: chunk `token` is
        served the moment the producer writes it — the client's first
        page arrives while the query is still RUNNING. A 'pending' get
        (the producer hasn't reached this chunk yet) answers the SAME
        token so the client polls; 'end' closes the protocol
        (FINISHED, no nextUri, final stats)."""
        status, chunk = stream.get(token, timeout=0.2)
        cols = protocol.columns_json(stream.column_names,
                                     stream.column_types)
        state = q.state if q.state in ("RUNNING", "FINISHING") \
            else "RUNNING"
        if status == "error":
            exc = stream.error
            if isinstance(exc, QueryCanceledError) or q.cancelled:
                return protocol.query_results(
                    q.query_id, self.base_uri, state="CANCELED",
                    error=protocol.error_json(
                        "Query was canceled", error_name="USER_CANCELED",
                        error_code=3, error_type="USER_ERROR"),
                    **q.times)
            return protocol.query_results(
                q.query_id, self.base_uri, state="FAILED",
                error=q.error or protocol.error_from_exception(exc),
                **q.times, peak_memory_bytes=peak,
                warnings=self._warnings_for(q))
        if status == "gone":
            # behind the ack horizon: the client advanced past this
            # token, then came back — unservable, like a pruned query
            return protocol.query_results(
                q.query_id, self.base_uri, state="FAILED",
                error=protocol.error_json(
                    f"result page {token} was already consumed",
                    error_name="PAGE_TRANSPORT_ERROR", error_code=65545,
                    error_type="INTERNAL_ERROR"),
                **q.times)
        if status == "pending":
            return protocol.query_results(
                q.query_id, self.base_uri, columns=cols,
                next_uri=self._page_uri(q, token), state=state,
                **q.times, peak_memory_bytes=peak)
        spilled = 0
        cpu_ms = None
        nbytes = 0
        if info is not None and info.stats:
            spilled = int(info.stats.get("spilled_bytes", 0))
            cpu_ms = info.cpu_time_ms
            nbytes = info.output_bytes
        if status == "end":
            q.state = "FINISHED"
            return protocol.query_results(
                q.query_id, self.base_uri, columns=cols,
                state="FINISHED", update_type=q.update_type,
                rows=stream.total_rows, **q.times,
                peak_memory_bytes=peak, cpu_time_ms=cpu_ms,
                processed_bytes=nbytes, spilled_bytes=spilled,
                warnings=self._warnings_for(q))
        data = protocol.encode_rows(chunk, stream.column_types)
        return protocol.query_results(
            q.query_id, self.base_uri, columns=cols, data=data,
            next_uri=self._page_uri(q, token + 1), state=state,
            rows=stream.total_rows, **q.times,
            peak_memory_bytes=peak, cpu_time_ms=cpu_ms,
            processed_bytes=nbytes, spilled_bytes=spilled,
            warnings=self._warnings_for(q))

    # ----------------------------------------------------------- handler

    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # quiet
                pass

            def _send_json(self, payload: dict, q: Optional[_Query] = None,
                           status: int = 200):
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                if q is not None and q.set_session is not None:
                    from urllib.parse import quote
                    k, v = q.set_session
                    self.send_header("X-Trino-Set-Session",
                                     f"{k}={quote(str(v))}")
                if q is not None and q.clear_session is not None:
                    self.send_header("X-Trino-Clear-Session",
                                     q.clear_session)
                if q is not None and q.added_prepare is not None:
                    from urllib.parse import quote
                    name, stmt_sql = q.added_prepare
                    self.send_header(
                        "X-Trino-Added-Prepare",
                        f"{quote(name, safe='')}="
                        f"{quote(stmt_sql, safe='')}")
                if q is not None and q.deallocated_prepare is not None:
                    from urllib.parse import quote
                    self.send_header("X-Trino-Deallocated-Prepare",
                                     quote(q.deallocated_prepare,
                                           safe=""))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                if self.path.rstrip("/") != "/v1/statement":
                    self.send_error(404)
                    return
                length = int(self.headers.get("Content-Length", 0))
                sql = self.rfile.read(length).decode()
                if server.draining.is_set():
                    # drain protocol: no NEW statements; in-flight
                    # queries and open streams keep paging below
                    self._send_json(protocol.query_results(
                        "draining", server.base_uri, state="FAILED",
                        error=protocol.error_json(
                            "Server is shutting down",
                            error_name="SERVER_SHUTTING_DOWN",
                            error_code=131075,
                            error_type="INSUFFICIENT_RESOURCES")))
                    return
                # group-config hot-reload check rides the submit path
                # (throttled): an edited JSON file re-applies here
                server._maybe_reload_groups()
                # result-cache fast path: a hit answers FINISHED right
                # here — data inline when it fits the first page, else
                # paged off q.result — without touching the dispatcher
                q = server._try_cached(sql, self.headers)
                if q is not None:
                    self._send_json(server._response_for(q, 0), q)
                    return
                q = server._submit(sql, self.headers)
                # first response: QUEUED with a nextUri (the dispatcher
                # handshake the CLI expects), data starts at token 0
                if q.error is not None:
                    self._send_json(server._response_for(q, 0), q)
                    return
                self._send_json(protocol.query_results(
                    q.query_id, server.base_uri,
                    next_uri=server._page_uri(q, 0), state="QUEUED",
                    **q.times), q)

            def do_GET(self):
                parts = self.path.strip("/").split("/")
                if len(parts) >= 3 and parts[:2] == ["v1", "query"]:
                    # /v1/query/{id} + /v1/query/{id}/trace: query info
                    # and Chrome-trace export, live or from history
                    qid = parts[2]
                    if len(parts) == 4 and parts[3] == "trace":
                        payload = server._query_trace_payload(qid)
                    elif len(parts) == 3:
                        payload = server._query_info_payload(qid)
                    else:
                        payload = None
                    if payload is None:
                        self.send_error(404, "Query not found")
                        return
                    self._send_json(payload)
                    return
                if self.path.rstrip("/") == "/v1/metrics":
                    # Prometheus scrape endpoint (the jmx-prometheus
                    # agent surface of a reference deployment, native)
                    from trino_tpu.obs.metrics import REGISTRY
                    body = REGISTRY.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                q, token = self._resolve()
                if q is None:
                    return
                self._send_json(server._response_for(q, token), q)

            def do_DELETE(self):
                q, _ = self._resolve()
                if q is None:
                    return
                if not q.done:
                    # cancel of a terminal query is a no-op (reference
                    # semantics); otherwise the runner observes the
                    # event at its next cooperative checkpoint — no
                    # current-query bookkeeping race: if the executor
                    # picks this query up LATER, the already-set event
                    # cancels it at its first checkpoint
                    q.cancelled = True
                    # CancelEvent.cancel() stamps the DELETE time with
                    # the set: the runner's deadline reads it to report
                    # `preempt_latency_ms` (DELETE -> unwind, the
                    # slice-bounded cancellation wall)
                    q.cancel_event.cancel()
                self.send_response(204)
                self.send_header("Content-Length", "0")
                self.end_headers()

            def _resolve(self):
                parts = self.path.strip("/").split("/")
                # v1/statement/executing/{id}/{slug}/{token}
                if len(parts) != 6 or parts[:3] != ["v1", "statement",
                                                    "executing"]:
                    self.send_error(404)
                    return None, 0
                qid, slug, token_str = parts[3], parts[4], parts[5]
                with server._lock:
                    q = server._queries.get(qid)
                    purged = qid in server._pruned
                if q is None:
                    if purged:
                        # the query existed but its results were pruned:
                        # 410 tells the client retrying is pointless
                        self.send_error(410, "Query results purged")
                    else:
                        self.send_error(404, "Query not found")
                    return None, 0
                if q.slug != slug:
                    self.send_error(404, "Query not found")
                    return None, 0
                try:
                    token = int(token_str)
                except ValueError:
                    self.send_error(404, "Invalid page token")
                    return None, 0
                if token < 0:
                    self.send_error(404, "Invalid page token")
                    return None, 0
                return q, token

        return Handler
