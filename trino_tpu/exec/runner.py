"""LocalQueryRunner: SQL in, rows out, one process, one device.

Reference parity: core/trino-main testing/LocalQueryRunner.java:230 — full
parse/analyze/plan/optimize/execute without the HTTP scheduler, the workhorse
of engine tests and operator benchmarks. Also handles the session-level
statements (USE, SET SESSION, EXPLAIN, SHOW ...) the way the reference's
coordinator resources do.
"""

from __future__ import annotations

import contextlib
import dataclasses
import datetime
import decimal
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from trino_tpu import types as T
from trino_tpu.connector import blackhole, memory, tpch
from trino_tpu.connector.spi import (CatalogManager, ColumnMetadata,
                                     SchemaTableName, TableMetadata)
from trino_tpu.exec.local_planner import ExecutionError, LocalExecutionPlanner
from trino_tpu.metadata import Metadata, Session
from trino_tpu.planner import LogicalPlanner
from trino_tpu.planner.nodes import (OutputNode, TableWriterNode, Symbol,
                                     format_plan)
from trino_tpu.planner.optimizer import fragment_plan, optimize
from trino_tpu.sql import parse_statement
from trino_tpu.sql import tree as t
from trino_tpu.sql.analyzer import SemanticError


@dataclasses.dataclass
class MaterializedResult:
    """testing/MaterializedResult.java analog.

    `row_count` is the TRUE produced-row count when it differs from
    len(rows): a streamed query past the result-cache bound delivers its
    rows through the ring buffer only and drops the materialized copy —
    `rows` is then empty but the count (tracker, stats, the wire `rows`
    field) stays exact."""

    column_names: List[str]
    column_types: List[T.Type]
    rows: List[Tuple[Any, ...]]
    row_count: Optional[int] = None

    @property
    def reported_rows(self) -> int:
        return len(self.rows) if self.row_count is None else self.row_count

    def __len__(self):
        return len(self.rows)

    def only_value(self):
        assert len(self.rows) == 1 and len(self.rows[0]) == 1
        return self.rows[0][0]


def _to_python(value, typ: T.Type):
    if value is None:
        return None
    if isinstance(typ, T.ArrayType):
        return [_to_python(v, typ.element) for v in value]
    if isinstance(typ, T.MapType):
        return {_to_python(k, typ.key): _to_python(v, typ.value)
                for k, v in value.items()}
    if isinstance(typ, T.DecimalType):
        return decimal.Decimal(int(value)).scaleb(-typ.scale)
    if isinstance(typ, T.DateType):
        return datetime.date(1970, 1, 1) + datetime.timedelta(days=int(value))
    if isinstance(typ, T.TimestampType):
        return (datetime.datetime(1970, 1, 1)
                + datetime.timedelta(microseconds=int(value)))
    if isinstance(typ, T.BooleanType):
        return bool(value)
    if isinstance(typ, (T.DoubleType, T.RealType)):
        return float(value)
    if isinstance(typ, (T.VarcharType, T.CharType)):
        return str(value)
    if isinstance(typ, (T.IntervalDayTimeType, T.IntervalYearMonthType)):
        return int(value)
    return int(value)


class LocalQueryRunner:
    def __init__(self, session: Optional[Session] = None):
        from trino_tpu.exec.plan_cache import PlanCache
        self.catalogs = CatalogManager()
        self.metadata = Metadata(self.catalogs)
        self.session = session or Session()
        self._prepared = {}
        # optimized-plan reuse (exec/plan_cache.py): keyed on the
        # canonical statement fingerprint + context; per-runner (it holds
        # handles resolved against THIS runner's catalogs) and shared
        # with for_query() clones, so the server's executor pool warms
        # one cache. DDL/INSERT invalidate by referenced table.
        self._plan_cache = PlanCache()
        self._owns_plan_cache = True
        # serving-tier caches (trino_tpu/serve/caches.py): per-runner
        # like the plan cache, shared with for_query() clones, and
        # evicted by the SAME invalidation call DDL/INSERT drives into
        # the plan cache (hooks below) — a cached answer or staged scan
        # page can never outlive a table change
        from trino_tpu.serve.caches import ResultSetCache, ScanCache
        self._result_cache = ResultSetCache()
        self._scan_cache = ScanCache()
        self._plan_cache.add_invalidation_hook(self._result_cache.invalidate)
        self._plan_cache.add_invalidation_hook(self._scan_cache.invalidate)
        # device-resident hot-table cache (exec/table_cache.py): columns
        # promoted into HBM across queries, serving both the local
        # dispatch loop and mesh shard_map staging. Registered on the
        # SAME invalidation fan-out, so one DDL/INSERT call drops plans,
        # results, scan pages, AND resident device columns together.
        from trino_tpu.exec.table_cache import TableCache
        self._table_cache = TableCache()
        self._plan_cache.add_invalidation_hook(self._invalidate_table_cache)
        # materialized views (trino_tpu/mv/): lifecycle + rewrite +
        # update-on-write republish. Shared with for_query() clones like
        # the caches — its served-entry registry must see every clone's
        # rewrite publishes so a refresh can update them all
        from trino_tpu.mv.manager import MaterializedViewManager
        self._mv = MaterializedViewManager(self)
        # streaming result sink for the CURRENT query (serve/streaming
        # ResultStream, installed per execute() by the server): pages
        # leave through the ring as they are produced; None = buffered
        self._sink = None
        # result-cache collection bound for the CURRENT query (None =
        # unbounded materialization, the classic protocol)
        self._cache_collect: Optional[int] = None
        # tables the last executed plan referenced + its live output
        # bytes (result-cache bookkeeping, stamped by the attempt)
        self._last_plan_tables = frozenset()
        self._last_output_nbytes = 0
        # statement parameter values for the CURRENT execution
        # (EXECUTE ... USING): expr/hoist.py binds BoundParam plan
        # leaves from this tuple at lowering time
        self._exec_params: Tuple[Any, ...] = ()
        # per-query fault-tolerance state (set in execute, read by the
        # execution paths; one query at a time per runner — concurrent
        # queries each run on a for_query() clone)
        self._deadline = None
        self._faults = None
        self._memory = None
        self._retries = 0
        # preemptible sliced execution (exec/sliced/): the per-query
        # SliceScheduler (bounded-work slices + boundary protocol), the
        # per-query CheckpointStore fragment retries resume from, the
        # idempotent-write token (the query id — stable across attempts,
        # so a retried INSERT can never double-commit), and the tables
        # THIS query created (a QUERY-level CTAS retry re-creates its
        # own table without tripping "already exists")
        self._slices = None
        self._ckpts = None
        self._write_token = None
        self._created_tables = set()
        # per-query adaptive strategy state (exec/adaptive.py): shared
        # across retry ATTEMPTS so the once-per-query spill-forced
        # degrade re-run inherits the failed attempt's observed agg
        # modes and heavy join keys instead of restarting cold. Kept
        # until the next execute() so tests/diagnostics can inspect it.
        self._adaptive = None
        # the per-query QueryStatsCollector (obs/stats.py): phases,
        # output rows/bytes, jit hit/miss, spill bytes, operator stats
        self._collector = None
        # statement observer (fleet/supervisor.StatementStamper in the
        # fleet's engine child): begin(sql, query_id) before execution,
        # end(token) after — the crash-attribution stamp the poison
        # quarantine rides on. Intentionally SHARED with for_query()
        # clones (copy.copy keeps the reference): the server's per-query
        # clones must stamp through the engine-wide observer
        self._statement_observer = None
        # Chrome-trace export directory (TrinoServer(trace_dir=...) /
        # $TRINO_TPU_TRACE_DIR); None defers to the session's
        # trace_export property with a tempdir default
        self._trace_dir: Optional[str] = None
        # cumulative counters across the runner's lifetime + the last
        # query's snapshot
        # (the collector's full snapshot dict after each execute)
        self.stats = {"retries": 0, "faults_injected": 0}
        self.last_query_stats = {"retries": 0, "faults_injected": 0}
        # warm the query-history module at CONSTRUCTION: its listener
        # registers on first import, and paying that import inside the
        # first query's completion window would sit exactly in the
        # streaming protocol's producer-finish critical path
        from trino_tpu.obs import history as _history  # noqa: F401

    def for_query(self) -> "LocalQueryRunner":
        """Per-query view of this runner: shared catalogs/metadata/
        prepared statements, PRIVATE session and fault-tolerance state —
        the unit the server's executor pool runs, so concurrent queries
        never share a session property bag or a deadline
        (SqlQueryExecution-per-query vs the shared QueryRunner)."""
        import copy
        clone = copy.copy(self)
        clone.session = Session(
            catalog=self.session.catalog, schema=self.session.schema,
            user=self.session.user, start_date=self.session.start_date,
            properties=dict(self.session.properties))
        # _plan_cache and _prepared are intentionally SHARED (copy.copy
        # keeps the references): concurrent queries warm one plan cache,
        # and server-side prepared statements registered on the base
        # runner stay visible (the server gives each query a private
        # overlay for header-supplied statements). Clones do NOT own the
        # cache: their (header-overridable) plan_cache_max_entries must
        # not resize the shared LRU out from under other sessions.
        clone._owns_plan_cache = False
        clone._sink = None
        clone._cache_collect = None
        clone._exec_params = ()
        clone._deadline = None
        clone._faults = None
        clone._memory = None
        clone._retries = 0
        clone._collector = None
        clone._slices = None
        clone._ckpts = None
        clone._write_token = None
        clone._created_tables = set()
        clone._adaptive = None
        clone.stats = {"retries": 0, "faults_injected": 0}
        clone.last_query_stats = {"retries": 0, "faults_injected": 0}
        return clone

    @classmethod
    def tpch(cls, schema: str = "tiny") -> "LocalQueryRunner":
        """Runner with tpch/memory/blackhole catalogs (TpchQueryRunner)."""
        runner = cls(Session(catalog="tpch", schema=schema))
        runner.catalogs.register("tpch", tpch.create_connector())
        from trino_tpu.connector import tpcds
        runner.catalogs.register("tpcds", tpcds.create_connector())
        runner.catalogs.register("memory", memory.create_connector())
        runner.catalogs.register("blackhole", blackhole.create_connector())
        from trino_tpu.connector import lake
        runner.catalogs.register("lake", lake.create_connector())
        from trino_tpu.connector import system
        runner.catalogs.register("system", system.create_connector())
        return runner

    # ------------------------------------------------------------- execute

    def execute(self, sql: str, *, query_id: Optional[str] = None,
                queued_at: Optional[float] = None,
                dequeued_at: Optional[float] = None,
                wall_cap_s: Optional[float] = None,
                cancel_event=None, result_sink=None) -> MaterializedResult:
        """Run one statement through the query lifecycle registry
        (QueryStateMachine analog): QUEUED -> RUNNING ->
        FINISHED/FAILED/CANCELED, visible in system.runtime.queries while
        executing and after. Builds the query's fault-tolerance state: a
        QueryDeadline (query_max_run_time/query_max_execution_time +
        `wall_cap_s`, the server's per-query hard cap; `cancel_event`
        lets the HTTP DELETE handler cancel cooperatively), the seeded
        FaultInjector when chaos is on, and the retry loop for
        retry_policy=QUERY (fragment-level TASK retry lives in the
        execution paths). `queued_at`/`dequeued_at` are the server's
        `time.monotonic()` stamps of the submit and of the executor
        thread taking the query: the `queued` span of its stats."""
        from trino_tpu.errors import (EXCEEDED_DEVICE_MEMORY_LIMIT,
                                      QueryCanceledError, classify,
                                      is_retryable)
        from trino_tpu.exec.deadline import QueryDeadline
        from trino_tpu.exec.faults import FaultInjector
        from trino_tpu.exec.memory import (NODE_POOL, QueryMemoryContext,
                                           degrade_to_spill)
        from trino_tpu.exec import jit_cache
        from trino_tpu.exec.query_tracker import TRACKER
        from trino_tpu.obs.stats import QueryStatsCollector
        try:
            group = str(self.session.get("resource_group"))
        except Exception:
            group = None
        info = TRACKER.begin(sql, user=self.session.user,
                             query_id=query_id, resource_group=group)
        self._retries = 0
        # streaming sink (serve/streaming.ResultStream): the attempt
        # opens it only for shapes where streaming is safe (no writer,
        # no retries possible — see _run_plan_attempt); when it stays
        # unopened the caller falls back to buffered paging
        self._sink = result_sink
        # the query's stats pipeline: always-on query-level collection;
        # operator-level instrumentation is opt-in (session property) or
        # forced by EXPLAIN ANALYZE. The jit-cache observer is
        # thread-local, so concurrent queries attribute their own
        # hits/misses (each runs on its own executor thread)
        self._collector = QueryStatsCollector(
            info.query_id, queued_at=queued_at, dequeued_at=dequeued_at)
        jit_cache.set_observer(self._collector)
        # stamp the statement in flight BEFORE any work that could kill
        # the process; cleared in the finally. Observer failures must
        # never fail the query — the stamp is advisory telemetry
        obs = self._statement_observer
        obs_token = None
        if obs is not None:
            try:
                obs_token = obs.begin(sql, info.query_id)
            except Exception:   # noqa: BLE001
                obs_token = None
        TRACKER.running(info)
        try:
            # fault-tolerance setup INSIDE the try: a malformed session
            # property value must fail the tracker entry (terminal state,
            # prunable), not leave a phantom RUNNING row
            try:
                self._collector.operator_level = bool(
                    self.session.get("collect_operator_stats"))
                self._deadline = QueryDeadline.from_session(
                    self.session, queued_at=queued_at,
                    wall_cap_s=wall_cap_s, cancel_event=cancel_event)
                self._faults = FaultInjector.install(self.session,
                                                     self._faults)
                policy = str(self.session.get("retry_policy")).upper()
                attempts = max(1, int(self.session.get("retry_attempts"))) \
                    if policy == "QUERY" else 1
                # the query level of the query->operator->node accounting
                # hierarchy: the ledger reserves against the node pool,
                # making this query visible to the low-memory killer
                self._memory = QueryMemoryContext(
                    int(self.session.get("query_max_memory")),
                    query_id=info.query_id, pool=NODE_POOL,
                    wait_s=float(
                        self.session.get("cluster_memory_wait_ms")) / 1e3)
                info.mem = self._memory
                info.resource_group = str(
                    self.session.get("resource_group"))
                # preemptible sliced execution: one scheduler + one
                # checkpoint store per query, shared by every executor
                # (local pipeline, distributed shard tasks) it runs.
                # The store exists only under TASK retry — the ONLY
                # policy whose fragment re-runs can restore from it
                # (NONE never retries; QUERY re-plans, which clears) —
                # so the default path never pins shard outputs for a
                # resume that cannot happen
                from trino_tpu.exec.sliced import (CheckpointStore,
                                                   SliceScheduler)
                self._slices = SliceScheduler.from_session(self.session)
                self._ckpts = CheckpointStore(info.query_id) \
                    if policy == "TASK" else None
                # idempotent-write identity: defaults to the query id
                # (each execution is its own write), but a client that
                # RETRIES a failed INSERT/CTAS — e.g. after a fleet
                # ENGINE_UNAVAILABLE answer — sends the same
                # `write_token` on both attempts, and the sink's
                # committed-token ledger makes the replay exactly-once
                self._write_token = \
                    str(self.session.get("write_token") or "") \
                    or info.query_id
                self._created_tables = set()
                # fresh per query, shared across its retry attempts:
                # the degrade re-run must START where the failed
                # attempt's observations left off
                from trino_tpu.exec.adaptive import AdaptiveQueryState
                self._adaptive = AdaptiveQueryState()
                # query-history retention: the OWNING runner's session
                # sizes the process ring (same discipline as the plan
                # cache — per-request header overrides on pooled clones
                # must not shrink history out from under everyone)
                if self._owns_plan_cache:
                    from trino_tpu.obs.history import HISTORY
                    HISTORY.resize(
                        int(self.session.get("history_max_entries")))
            except (TypeError, ValueError) as e:
                from trino_tpu.errors import InvalidSessionPropertyError
                raise InvalidSessionPropertyError(
                    f"invalid session property value: {e}") from e
            stmt = parse_statement(sql)
            attempt = 0
            spill_forced = False
            while True:
                attempt += 1
                try:
                    if spill_forced:
                        with degrade_to_spill(self.session):
                            result = self._execute_statement(stmt)
                    else:
                        result = self._execute_statement(stmt)
                    break
                except Exception as e:
                    if classify(e) is EXCEEDED_DEVICE_MEMORY_LIMIT:
                        self._collector.device_oom_errors += 1
                    if self._sink is not None and self._sink.emitted:
                        # rows already left through the result stream: a
                        # re-run would duplicate them client-side (the
                        # attempt only opens the sink when no retry is
                        # possible, so this is a guard, not a path)
                        raise
                    if (attempts > 1 and not spill_forced
                            and _is_memory_pressure(e)):
                        # the killer's victim (or injected pressure):
                        # once per query, re-run with the spill path
                        # forced so the retry's footprint shrinks —
                        # this degrade re-run is free
                        spill_forced = True
                        attempt -= 1
                    elif attempt >= attempts or not is_retryable(e):
                        raise
                    self._retries += 1
                    self._memory.reset_attempt()
                    # a QUERY-level re-run RE-PLANS: the failed attempt's
                    # node objects die, so id()-keyed operator slots would
                    # duplicate (or, after id reuse, misattribute) — the
                    # rendered stats are the surviving attempt's
                    self._collector.operators.clear()
                    # checkpoints die with the plan too: a concurrent
                    # invalidation (or the degrade re-run's forced spill)
                    # can change the re-planned shape, and a colliding
                    # fragment id would silently restore the DEAD plan's
                    # pages as the new plan's output
                    if self._ckpts is not None:
                        self._ckpts.clear()
                    self._backoff(attempt)
        except BaseException as e:
            # BaseException too: a KeyboardInterrupt/SystemExit escaping
            # mid-query must not leave a forever-RUNNING phantom row in
            # system.runtime.queries
            if isinstance(e, QueryCanceledError) \
                    and self._deadline is not None \
                    and self._deadline.cancelled_at is not None \
                    and self._collector is not None:
                # preemption latency: cancel-request (DELETE / stall
                # guard / direct cancel) to unwind — the slice-bounded
                # wall the sliced executor promises
                import time as _time
                self._collector.preempt_latency_ms = round(
                    (_time.monotonic() - self._deadline.cancelled_at)
                    * 1000, 3)
            self._finish_query_stats(info)
            self._close_memory(info, failed=True)
            if isinstance(e, QueryCanceledError):
                TRACKER.cancel(info, str(e))
            else:
                TRACKER.fail(info, f"{type(e).__name__}: {e}",
                             error_name=classify(e).name)
            raise
        finally:
            self._deadline = None
            self._sink = None
            jit_cache.set_observer(None)
            if obs is not None:
                try:
                    obs.end(obs_token)
                except Exception:   # noqa: BLE001
                    pass
        self._finish_query_stats(info)
        self._close_memory(info, failed=False)
        TRACKER.finish(info, result.reported_rows)
        return result

    def _close_memory(self, info, failed: bool) -> None:
        """Close the query's ledger: record peak/kill counters and run
        the reservation LEAK DETECTOR — a successful query whose ledger
        is nonzero leaked an operator reservation (a missing free());
        surfaced as a query warning plus pool counters rather than an
        error, since the bytes ARE released here."""
        ctx = self._memory
        if ctx is None:
            return
        from trino_tpu.exec.memory import NODE_POOL, _fmt_bytes
        leaked = ctx.close()
        info.pool_peak_bytes = ctx.peak
        info.memory_kills = ctx.kills
        if leaked and not failed:
            info.leaked_bytes = leaked
            info.warnings.append(
                f"reservation leak: query {info.query_id} ended with "
                f"{_fmt_bytes(leaked)} still reserved (tags: "
                f"{ {k: v for k, v in ctx.by_tag.items() if v} })")
            NODE_POOL.record_leak(leaked)
        self._memory = None

    def lake_fsck(self, catalog: str = "lake", **kwargs) -> dict:
        """Run the lake integrity walk (connector/lake/integrity.py):
        verify pointer -> manifest -> files -> row groups, roll back a
        torn/corrupt pointer to the newest intact retained snapshot,
        GC orphan files past the grace age. Returns the report dict.
        kwargs: repair, deep, gc, gc_grace_s."""
        conn = self.metadata.connector(catalog)
        fsck = getattr(conn, "fsck", None)
        if fsck is None:
            raise ValueError(
                f"catalog {catalog!r} does not support fsck")
        report = fsck(**kwargs)
        # repaired tables may have rolled the manifest back: every cache
        # keyed on table state (plans, results, scan pages, device
        # columns) must drop through the standard invalidation fan-out
        for trep in report.get("tables", ()):
            if trep.get("rolled_back_to") is not None:
                schema, table = trep["table"].split(".", 1)
                self._plan_cache.invalidate((catalog, schema, table))
        return report

    def cancel_current(self) -> None:
        """Cancel the in-flight query (no-op when idle): sets the cancel
        flag; the executing thread raises QueryCanceledError at its next
        cooperative checkpoint."""
        deadline = self._deadline
        if deadline is not None:
            deadline.cancel()

    def _finish_query_stats(self, info) -> None:
        faults = self._faults.injected if self._faults else 0
        info.retries = self._retries
        info.faults_injected = faults
        col = self._collector
        if col is not None and self._memory is not None:
            col.memory_kills = self._memory.kills
        if col is not None and self._slices is not None:
            col.slices_executed = self._slices.slices_executed
        if col is not None and self._ckpts is not None:
            col.checkpoints_saved = self._ckpts.saved
            col.checkpoints_restored = self._ckpts.restored
            col.checkpoint_bytes = self._ckpts.bytes_saved
        if self._ckpts is not None:
            # release every checkpointed page with the query
            self._ckpts.clear()
        self._slices = None
        self._ckpts = None
        self._write_token = None
        if col is not None:
            # stamp the rollup BEFORE the terminal tracker transition:
            # event listeners receive info.stats/info.trace with the
            # completed/failed event (QueryMonitor orders the same way)
            col.retries = self._retries
            col.faults_injected = faults
            col.finish()
            # cpu_time_ms means HOST time: execution wall minus the
            # measured device walls (every dispatch of a fenced query)
            # minus the measured XLA compile walls — the device/compile
            # halves live in stats as device_time_ms/compile_time_ms.
            # Unfenced there is no device wall to take out: stats say
            # null, and this wire field keeps execution less compiles
            info.cpu_time_ms = int(col.host_time_s * 1000)
            info.output_bytes = col.output_bytes
            # mesh shape the query executed over (QueryMesh axis), for
            # system.runtime.queries consumers and event listeners
            info.mesh = (f"workers:{col.mesh_devices}"
                         if col.mesh_devices else None)
            info.stats = col.snapshot()
            info.trace = col.trace_json()
            self._export_trace(info)
            self.last_query_stats = info.stats
        else:
            self.last_query_stats = {"retries": self._retries,
                                     "faults_injected": faults}
        self.stats["retries"] += self._retries
        self.stats["faults_injected"] += faults
        if self._faults is not None:
            # reset at query END (not start): a next-query setup failure
            # then reads 0 instead of double-counting this query's faults
            self._faults.injected = 0
            self._faults.by_site.clear()

    def _export_trace(self, info) -> None:
        """Chrome-trace export (session `trace_export` / a server
        trace_dir): serialize the query's span dump as Perfetto-loadable
        JSON under the trace directory and stamp QueryInfo.trace_file.
        Export failure degrades to a warning — observability must not
        fail queries."""
        import os
        if info.trace is None:
            return
        try:
            if not bool(self.session.get("trace_export")):
                return
        except Exception:
            return
        try:
            import json
            import tempfile

            from trino_tpu.obs.spans import to_chrome_trace
            trace_dir = self._trace_dir \
                or os.environ.get("TRINO_TPU_TRACE_DIR") \
                or os.path.join(tempfile.gettempdir(), "trino_tpu_traces")
            os.makedirs(trace_dir, exist_ok=True)
            path = os.path.join(trace_dir,
                                f"{info.query_id}.trace.json")
            with open(path, "w") as fh:
                json.dump(to_chrome_trace(info.trace, info.query_id), fh)
            info.trace_file = path
        except Exception as e:   # noqa: BLE001
            info.warnings.append(f"trace export failed: {e}")

    def _backoff(self, attempt: int) -> None:
        """Exponential backoff + jitter between retry attempts
        (fault-tolerant execution's RetryPolicy backoff)."""
        import random
        import time as _time
        initial = float(self.session.get("retry_initial_delay_ms")) / 1e3
        cap = float(self.session.get("retry_max_delay_ms")) / 1e3
        delay = min(cap, initial * (2 ** (attempt - 1)))
        _time.sleep(delay * random.uniform(0.5, 1.0))

    def _check_deadline(self) -> None:
        if self._deadline is not None:
            self._deadline.check()
        if self._memory is not None:
            self._memory.poll()     # low-memory-killer checkpoint

    def _retry_task(self, label: str, fn):
        """Run one retry scope ('task': a fragment attempt, an exchange
        apply, the local plan run) under the session's retry policy.
        Retryable errors (errors.is_retryable: injected faults, exchange
        transport) re-run the task up to retry_attempts times with
        backoff under retry_policy=TASK; memory pressure — an
        ExceededMemoryLimitError or a low-memory-killer
        CLUSTER_OUT_OF_MEMORY — gets ONE re-run with the spill path
        forced on (graceful degradation) when any retry policy is
        active; everything else propagates. A failed attempt's unfreed
        reservations roll back so retries don't stack phantom bytes.
        Each attempt is also a fault-injection scope (faults.begin_task),
        so chaos arms at most one site per attempt."""
        from trino_tpu.errors import is_retryable
        from trino_tpu.exec.memory import (ExceededMemoryLimitError,
                                           degrade_to_spill)
        policy = str(self.session.get("retry_policy")).upper()
        attempts = max(1, int(self.session.get("retry_attempts"))) \
            if policy == "TASK" else 1
        mark = self._memory.reserved if self._memory is not None else 0
        spill_forced = False
        attempt = 0
        while True:
            attempt += 1
            if self._faults is not None:
                self._faults.begin_task((label, attempt))
            try:
                if self._faults is not None:
                    # the process-level site: inside a fleet engine
                    # child this kills the engine mid-dispatch
                    # (exec/faults.py), proving the supervisor + worker
                    # degraded-mode story; elsewhere it is an ordinary
                    # retryable InjectedFault
                    self._faults.site("engine", "dispatch")
                if spill_forced:
                    with degrade_to_spill(self.session):
                        return fn()
                return fn()
            except Exception as e:
                if self._sink is not None and self._sink.emitted:
                    raise   # streamed rows cannot be un-delivered
                memory_pressure = (isinstance(e, ExceededMemoryLimitError)
                                   or _is_memory_pressure(e))
                if memory_pressure and not spill_forced \
                        and policy != "NONE":
                    spill_forced = True
                    attempt -= 1      # the degrade re-run is free
                    self._retries += 1
                elif attempt >= attempts or not is_retryable(e):
                    raise
                else:
                    self._retries += 1
                    self._backoff(attempt)
                if self._memory is not None:
                    # roll back THIS attempt's delta only — bytes below
                    # `mark` belong to enclosing scopes (completed
                    # fragments' still-live state on the query-wide
                    # shared ledger) and must survive a task retry. In
                    # practice mark is ~0 at every scope entry, so a
                    # killed victim hands back everything the killer
                    # wanted; the kill mark clears under the pool lock.
                    self._memory.rollback_to(mark)
                    if memory_pressure:
                        self._memory.clear_kill()

    def _execute_statement(self, stmt: t.Statement) -> MaterializedResult:
        if isinstance(stmt, t.Query):
            return self._execute_query_cached(stmt)
        if isinstance(stmt, t.Explain):
            return self._explain(stmt)
        if isinstance(stmt, t.ShowTables):
            return self._show_tables(stmt)
        if isinstance(stmt, t.ShowSchemas):
            return self._show_schemas(stmt)
        if isinstance(stmt, t.ShowCatalogs):
            return MaterializedResult(
                ["Catalog"], [T.VARCHAR],
                [(c,) for c in self.catalogs.catalogs()])
        if isinstance(stmt, t.ShowColumns):
            return self._show_columns(stmt)
        if isinstance(stmt, t.ShowSession):
            from trino_tpu.metadata import SESSION_PROPERTY_DEFAULTS
            rows = [(k, str(self.session.get(k)), str(v))
                    for k, v in sorted(SESSION_PROPERTY_DEFAULTS.items())]
            return MaterializedResult(
                ["Name", "Value", "Default"], [T.VARCHAR] * 3, rows)
        if isinstance(stmt, t.SetSession):
            name = str(stmt.name)
            value = _literal_value(stmt.value)
            self.session.set(name, value)
            self._session_property_changed(name)
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.ResetSession):
            name = str(stmt.name)
            self.session.properties.pop(name, None)
            self._session_property_changed(name)
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.Use):
            if stmt.catalog is not None:
                self.session.catalog = stmt.catalog.value
            self.session.schema = stmt.schema.value
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, t.CreateTableAsSelect):
            return self._create_table_as(stmt)
        if isinstance(stmt, t.Insert):
            return self._insert(stmt)
        if isinstance(stmt, t.DropTable):
            return self._drop_table(stmt)
        if isinstance(stmt, t.CreateMaterializedView):
            return self._mv.create(self, stmt)
        if isinstance(stmt, t.RefreshMaterializedView):
            return self._mv.refresh(self, stmt)
        if isinstance(stmt, t.DropMaterializedView):
            return self._mv.drop(self, stmt)
        if isinstance(stmt, t.Prepare):
            self._prepared[stmt.name.value] = stmt.statement
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, t.ExecuteStatement):
            return self._execute_prepared(stmt)
        if isinstance(stmt, t.Deallocate):
            self._prepared.pop(stmt.name.value, None)
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        if isinstance(stmt, (t.Commit, t.Rollback, t.StartTransaction)):
            return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
        raise SemanticError(
            f"unsupported statement: {type(stmt).__name__}")

    # ------------------------------------------------ prepared statements

    def _execute_prepared(self, stmt: t.ExecuteStatement
                          ) -> MaterializedResult:
        """EXECUTE [... USING v1, .., vn]: bind values to the prepared
        statement's `?` markers and run it. Query statements take the
        FAST path — plan once with value-free BoundParam leaves, reuse
        the cached plan on every re-execution (any values, same types),
        and let literal hoisting bind the values into the same warm
        kernels — so a repeated EXECUTE costs parameter binding plus
        cached-executable dispatch (the PREPARE/EXECUTE ... USING
        protocol bound straight to ParameterRewriter slots). Non-query
        prepared statements (INSERT/CTAS/DDL) substitute the value
        expressions into the AST and run the normal path."""
        from trino_tpu.sql.analyzer import (check_execute_arity,
                                            count_parameters,
                                            substitute_parameters)
        prepared = self._prepared.get(stmt.name.value)
        if prepared is None:
            raise SemanticError(
                f"prepared statement not found: {stmt.name.value}")
        markers = count_parameters(prepared)
        check_execute_arity(stmt.name.value, markers, len(stmt.parameters))
        if markers == 0:
            return self._execute_statement(prepared)
        if not isinstance(prepared, t.Query):
            return self._execute_statement(
                substitute_parameters(prepared, stmt.parameters))
        types, values = self._bind_execute_parameters(stmt)
        if any(v is None for v in values):
            # NULL parameters: a NULL carries no type to key a value-free
            # plan on (and changes validity structure), so substitute the
            # AST and plan per execution — literal-NULL semantics, exactly
            # what the plain statement would do
            return self._execute_statement(
                substitute_parameters(prepared, stmt.parameters))
        self.session.param_types = types
        self._exec_params = values
        try:
            return self._execute_query_cached(prepared)
        finally:
            self.session.param_types = None
            self._exec_params = ()

    def _bind_execute_parameters(self, stmt: t.ExecuteStatement):
        """USING values -> (types, python values). Values must be
        constants; string parameters normalize to unbounded varchar so a
        different-length string binds the same cached plan."""
        from trino_tpu.expr.ir import Call as IRCall, Literal as IRLiteral
        from trino_tpu.planner.translate import ExpressionTranslator, Scope
        tr = ExpressionTranslator(Scope([]), session=self.session)
        types: List[T.Type] = []
        values: List[Any] = []
        for i, expr in enumerate(stmt.parameters):
            lit = tr.translate(expr)
            if isinstance(lit, IRCall) and lit.name == "negate" and \
                    isinstance(lit.args[0], IRLiteral):
                lit = IRLiteral(-lit.args[0].value, lit.type)
            if not isinstance(lit, IRLiteral):
                raise SemanticError(
                    f"EXECUTE parameter {i + 1} must be a constant "
                    f"literal: {expr}")
            typ = lit.type
            if T.is_string(typ):
                typ = T.VARCHAR
            types.append(typ)
            values.append(lit.value)
        return tuple(types), tuple(values)

    # --------------------------------------------------- result-set cache

    def _result_cache_eligible(self, query: t.Query) -> bool:
        from trino_tpu.serve.caches import statement_is_cacheable
        if not bool(self.session.get("result_cache_enabled")):
            return False
        if float(self.session.get("fault_injection_rate")) > 0:
            return False    # a cached answer would dodge the chaos
        col = self._collector
        if col is not None and col.operator_level:
            return False    # operator rows need a real execution
        if getattr(self.session, "_mv_scan_pins", None):
            return False    # version-pinned internal refresh scans must
                            # never publish as the unpinned statement
        return statement_is_cacheable(query)

    def _result_cache_key(self, query: t.Query):
        """The plan-cache key PLUS the bound parameter values: a
        prepared statement's plan is value-free, but its answer is
        not."""
        return (self._plan_cache_key(query), self._exec_params)

    def _execute_query_cached(self, query: t.Query) -> MaterializedResult:
        """SELECT through the serving tier's result-set cache: a hit
        returns the materialized answer with zero planning, zero
        compiles, zero operator execution; a miss executes normally and
        publishes the answer when it is cacheable (deterministic
        statement, non-system tables, within the row bound, and no
        concurrent invalidation raced the execution)."""
        from trino_tpu.serve.caches import CachedResult
        if not self._result_cache_eligible(query):
            return self._execute_query_rewritten(query)
        key = self._result_cache_key(query)
        entry = self._result_cache.get(key)
        col = self._collector
        if entry is not None and not self._mv.entry_fresh(
                self, key, entry):
            # update-on-write tier: an MV-backed answer past the
            # session's staleness budget re-executes instead of serving
            # (a refresh normally republishes it before it ever ages out)
            entry = None
        if entry is not None:
            if col is not None:
                col.result_cache_hit()
                # output accounting stays consistent with a real run:
                # rows/bytes count once whether executed, streamed, or
                # served from cache
                col.add_output(entry.row_count, entry.output_bytes)
            return MaterializedResult(
                list(entry.column_names), list(entry.column_types),
                list(entry.rows), row_count=entry.row_count)
        if col is not None:
            col.result_cache_miss()
        max_rows = int(self.session.get("result_cache_max_rows"))
        gen = self._result_cache.generation()
        self._cache_collect = max_rows
        try:
            result = self._execute_query_rewritten(query, cache_key=key)
        finally:
            self._cache_collect = None
        tables = self._last_plan_tables
        if (result.reported_rows <= max_rows
                and len(result.rows) == result.reported_rows
                and not any(tk[0] == "system" for tk in tables)):
            if self._owns_plan_cache:
                self._result_cache.resize(
                    int(self.session.get("result_cache_max_entries")))
            self._result_cache.put(
                key,
                CachedResult(tuple(result.column_names),
                             tuple(result.column_types),
                             tuple(result.rows), result.reported_rows,
                             self._last_output_nbytes, frozenset(tables)),
                gen=gen)
        return result

    def peek_cached_result(self, sql: str):
        """Parse-only result-cache probe for the server's POST-time fast
        path: resolves EXECUTE through the prepared map, binds parameter
        values, and looks the key up WITHOUT planning or executing.
        Returns the CachedResult or None (any wrinkle — unknown
        statement kind, NULL parameters, arity mismatch — defers to the
        normal dispatch path, which will surface the real error)."""
        from trino_tpu.sql.analyzer import count_parameters
        if not bool(self.session.get("result_cache_enabled")) or \
                float(self.session.get("fault_injection_rate")) > 0 or \
                bool(self.session.get("collect_operator_stats")):
            return None
        try:
            stmt = parse_statement(sql)
        except Exception:
            return None
        params: Tuple[Any, ...] = ()
        if isinstance(stmt, t.ExecuteStatement):
            prepared = self._prepared.get(stmt.name.value)
            if not isinstance(prepared, t.Query):
                return None
            if count_parameters(prepared) != len(stmt.parameters):
                return None
            if stmt.parameters:
                try:
                    types, values = self._bind_execute_parameters(stmt)
                except Exception:
                    return None
                if any(v is None for v in values):
                    return None
                self.session.param_types = types
                params = values
            stmt = prepared
        if not isinstance(stmt, t.Query):
            return None
        try:
            saved, self._exec_params = self._exec_params, params
            try:
                key = self._result_cache_key(stmt)
            finally:
                self._exec_params = saved
        finally:
            self.session.param_types = None
        return self._result_cache.get(key, count_miss=False)

    def _active_table_cache(self):
        """The shared device table cache when the session enables it and
        no chaos is armed (injected scan faults must fire, and a cached
        column must not dodge them). The OWNING runner applies its
        session's sizing; clones' header overrides never resize the
        shared tier."""
        if not bool(self.session.get("table_cache_enabled")) \
                or self._faults is not None:
            return None
        if self._owns_plan_cache:
            self._table_cache.configure(
                int(self.session.get("table_cache_max_bytes")),
                int(self.session.get("table_cache_min_scans")))
        return self._table_cache

    def _invalidate_table_cache(self, table) -> None:
        """PlanCache invalidation hook: drop resident device columns of
        the changed table (the fourth leg of the one-call fan-out:
        plans, results, scan pages, device columns)."""
        dropped = self._table_cache.invalidate(table)
        col = self._collector
        if dropped and col is not None:
            from trino_tpu.obs.stats import maybe_span
            with maybe_span(col, "table-cache-invalidate",
                            kind="table-cache", table=str(table),
                            entries=dropped):
                pass

    def _session_property_changed(self, name: str) -> None:
        """SET/RESET SESSION side effects: resizing the plan-cache LRU
        applies immediately on the OWNING runner (a hit-only steady-state
        workload never reaches the miss path's re-read, and a shrink must
        evict now, not on the next put). Clones never resize the shared
        cache — per-request header overrides must not evict other
        sessions' warm plans."""
        if name == "plan_cache_max_entries" and self._owns_plan_cache:
            self._plan_cache.resize(
                int(self.session.get("plan_cache_max_entries")))

    # ----------------------------------------------------------- planning

    def _phase(self, name: str):
        """The collector's phase scope, or a no-op outside execute()."""
        from trino_tpu.obs.stats import maybe_phase
        return maybe_phase(self._collector, name)

    def _plan(self, query: t.Statement) -> OutputNode:
        with self._phase("planning"):
            plan = LogicalPlanner(self.metadata, self.session).plan(query)
            return optimize(plan, self.metadata, self.session)

    def _plan_for_execution(self, query: t.Query) -> OutputNode:
        """The planning primitive `_plan_query` caches. Subclasses
        override (the distributed runner optimizes with distributed=True);
        each runner produces ONE plan kind here, so cached plans never
        cross execution modes."""
        return self._plan(query)

    def _plan_cache_key(self, query: t.Query):
        from trino_tpu.exec.plan_cache import (PLAN_PROPERTIES,
                                               statement_fingerprint)
        skeleton, values = statement_fingerprint(query)
        param_types = getattr(self.session, "param_types", None)
        return (skeleton, values,
                self.session.catalog, self.session.schema,
                self.session.start_date,
                None if param_types is None
                else tuple(t_.display() for t_ in param_types),
                tuple((p, self.session.get(p)) for p in PLAN_PROPERTIES))

    def _plan_query(self, query: t.Query) -> OutputNode:
        """Plan a SELECT through the plan cache: the key is the canonical
        literal-free statement fingerprint + masked literal values +
        catalog/schema/current_date + bound parameter types +
        plan-affecting session properties (exec/plan_cache.py). Lowering-
        time properties (hoist_literals, capacities, spill) re-apply per
        execution, so they never fragment the key."""
        from trino_tpu.exec.plan_cache import plan_tables
        if not bool(self.session.get("plan_cache_enabled")) \
                or getattr(self.session, "_mv_scan_pins", None):
            # pinned internal MV scans plan outside the cache: a
            # version-pinned plan under an unpinned statement's key
            # would serve stale snapshots to ordinary queries
            return self._plan_for_execution(query)
        key = self._plan_cache_key(query)
        plan = self._plan_cache.get(key)
        col = self._collector
        if plan is not None:
            if col is not None:
                col.plan_cache_hit()
            return plan
        if col is not None:
            col.plan_cache_miss()
        # generation BEFORE planning: if a concurrent clone's DDL/INSERT
        # invalidates a referenced table while this plan is being built,
        # put() rejects it — publishing it would let a pre-change plan
        # outlive the invalidation that should have dropped it
        gen = self._plan_cache.generation()
        plan = self._plan_for_execution(query)
        if self._owns_plan_cache:
            # the owning runner's plan_cache_max_entries binds (set via
            # SET SESSION or direct property writes); a clone's never does
            self._plan_cache.resize(
                int(self.session.get("plan_cache_max_entries")))
        self._plan_cache.put(key, plan, plan_tables(plan), gen=gen)
        return plan

    def _plan_query_for_analyze(self, query: t.Query) -> OutputNode:
        """EXPLAIN ANALYZE's planning path: the cache, here — its plans
        are the local kind `_explain_analyze` executes. The distributed
        runner overrides (its cached plans carry exchanges for its own
        executor and must not be mixed into the local analyze path)."""
        return self._plan_query(query)

    def _invalidate_plans(self, qname) -> None:
        """DDL/DML against a table: drop cached plans referencing it
        (stale handles and statistics must not outlive the change)."""
        self._plan_cache.invalidate(
            (qname.catalog, qname.schema, qname.table))

    def _execute_query_rewritten(self, query: t.Query,
                                 cache_key=None) -> MaterializedResult:
        """Execute through the MV rewrite hook: when the statement
        matches a registered fresh view, run the REWRITTEN query instead
        (it scans the view's storage table, so the published cache entry
        references storage — base inserts no longer invalidate it, the
        view's REFRESH updates it: the update-on-write flip)."""
        rw = self._mv.try_rewrite(self, query)
        if rw is None:
            return self._execute_query(query)
        view_key, rewritten = rw
        result = self._execute_query(rewritten)
        if cache_key is not None:
            self._mv.note_served(cache_key, view_key, rewritten)
        return result

    def _execute_query(self, query: t.Query) -> MaterializedResult:
        plan = self._plan_query(query)
        from trino_tpu.exec.plan_cache import plan_tables
        self._last_plan_tables = plan_tables(plan)
        return self._run_plan(plan)

    def _run_plan(self, plan: OutputNode) -> MaterializedResult:
        # the whole local plan is ONE retry scope (a single-fragment
        # "task"): retryable failures re-run it under retry_policy=TASK,
        # and an over-memory failure re-runs once with spill forced.
        # Write plans are exempt: re-running a TableWriterNode would
        # double-write (the reference's FTE requires connector support
        # for write retry — this engine's memory connector has none)
        with self._phase("execution"):
            if _contains_writer(plan):
                if self._writer_retry_safe(plan):
                    # idempotent sink (write token + commit-on-finish):
                    # a retried attempt stages fresh and a committed
                    # token never commits twice, so the write joins the
                    # normal retry scope — chaos included
                    return self._retry_task(
                        "local-plan",
                        lambda: self._run_plan_attempt(plan))
                self._check_deadline()
                return self._run_plan_attempt(plan, chaos=False)
            return self._retry_task("local-plan",
                                    lambda: self._run_plan_attempt(plan))

    def _writer_retry_safe(self, plan: OutputNode) -> bool:
        """True when every writer target's connector declares idempotent
        writes (staged tokens + commit-on-finish) — the condition under
        which re-running a TableWriterNode cannot double-write."""
        writers = _find_writers(plan)
        if not writers:
            return False
        for node in writers:
            try:
                conn = self.catalogs.get(node.catalog)
            except Exception:
                return False
            if not getattr(conn, "idempotent_writes", False):
                return False
        return True

    def _streaming_safe(self) -> bool:
        """Streaming is only safe when NO re-run is possible: a retry
        after rows left the ring would duplicate them client-side
        (retry_policy=NONE also rules out the memory-degrade re-run),
        and injected chaos exists to exercise retries."""
        return (str(self.session.get("retry_policy")).upper() == "NONE"
                and self._faults is None)

    def _run_plan_attempt(self, plan: OutputNode,
                          chaos: bool = True) -> MaterializedResult:
        self._check_deadline()
        executor = LocalExecutionPlanner(self.metadata, self.session)
        executor.faults = self._faults if chaos else None
        executor.deadline = self._deadline
        executor.collector = self._collector
        executor.exec_params = self._exec_params
        executor.slices = self._slices
        executor.write_token = self._write_token
        executor.adaptive = self._adaptive
        if bool(self.session.get("scan_cache_enabled")) \
                and self._faults is None:
            # chaos runs bypass the scan cache: the `scan` fault site
            # must fire, and injected scan failures must not poison it
            executor.scan_cache = self._scan_cache
        executor.table_cache = self._active_table_cache()
        executor.table_cache_min_scans = int(
            self.session.get("table_cache_min_scans"))
        if self._memory is not None:
            executor.memory = self._memory   # query-level shared ledger
        stream = executor.execute(plan)
        types = [s.type for s in plan.symbols]
        sink = self._sink
        if sink is not None and (_contains_writer(plan)
                                 or not self._streaming_safe()):
            sink = None     # unopened sink -> caller pages the buffered result
        if sink is not None:
            sink.open(list(plan.column_names), types)
        rows: List[Tuple[Any, ...]] = []
        # when streaming, the materialized copy exists only to feed the
        # result cache — past the collection bound it is dropped and the
        # rows live solely in the ring until the client drains them
        collect_cap = self._cache_collect if sink is not None else None
        collecting = sink is None or collect_cap is not None
        total = 0
        nbytes = 0
        from trino_tpu.exec.jit_cache import host_read, observed_activity
        from trino_tpu.exec.memory import live_page_bytes
        with contextlib.ExitStack() as fetching:
            # ONE `result_fetch` span per attempt, opened when the first
            # result page has landed (its row count is on the host) and
            # closed after the last: device -> host transfer and row
            # conversion. A one-page result (any blocking root) is just
            # that; a streamed many-page result also holds the pulls
            # that produce the later pages.
            for page in stream.iter_pages():
                self._check_deadline()      # page-batch cancellation point
                n = int(host_read(page.num_rows, "result_rows"))
                if n == 0:
                    continue
                if self._collector is not None and not total:
                    fetching.enter_context(self._collector.span(
                        "result_fetch", kind="phase"))
                nbytes += live_page_bytes(page, n)
                with observed_activity("to_host"):
                    cols = page.to_host(n)
                with observed_activity("rows_to_python"):
                    chunk = [tuple(_to_python(cols[j][i], types[j])
                                   for j in range(len(cols)))
                             for i in range(n)]
                total += n
                if sink is not None:
                    sink.put(chunk, checkpoint=self._check_deadline)
                    if collecting and (collect_cap is None
                                       or total <= collect_cap):
                        rows.extend(chunk)
                    else:
                        collecting = False
                        rows = []
                else:
                    rows.extend(chunk)
        if sink is not None:
            # publish the staged partial final chunk while still inside
            # execution (the FINISHING window opens only after the whole
            # result is ring-visible), then account delivery ONCE
            sink.flush(checkpoint=self._check_deadline)
            if self._collector is not None and total:
                self._collector.add_streamed(
                    -(-total // sink.chunk_rows), total)
        if chaos and self._faults is not None:
            self._faults.site("fragment", "local-plan")
        self._last_output_nbytes = nbytes
        if self._collector is not None:
            # rows/bytes count ONCE here, whether the result was
            # streamed through the ring or buffered (satellite contract:
            # QueryInfo.stats is delivery-mode independent)
            self._collector.add_output(total, nbytes)
        return MaterializedResult(list(plan.column_names), types, rows,
                                  row_count=total)

    # --------------------------------------------------------------- DDL

    def _resolve(self, name: t.QualifiedName):
        return self.metadata.resolve_table_name(name.parts, self.session)

    @staticmethod
    def _table_properties(stmt) -> Tuple[Tuple[str, Any], ...]:
        """CREATE TABLE ... WITH (key = literal) -> evaluated pairs the
        connector reads off TableMetadata.properties (the lake's
        partitioned_by/format channel; other connectors ignore them)."""
        return tuple((k, _literal_value(v))
                     for k, v in getattr(stmt, "properties", ()) or ())

    def _create_table(self, stmt: t.CreateTable) -> MaterializedResult:
        qname = self._resolve(stmt.name)
        conn = self.catalogs.get(qname.catalog)
        cols = tuple(ColumnMetadata(c.name.value, T.parse_type(c.type))
                     for c in stmt.elements)
        conn.metadata.create_table(
            TableMetadata(qname.schema_table, cols,
                          self._table_properties(stmt)), stmt.not_exists)
        self._invalidate_plans(qname)
        return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])

    def _create_table_as(self, stmt: t.CreateTableAsSelect
                         ) -> MaterializedResult:
        qname = self._resolve(stmt.name)
        conn = self.catalogs.get(qname.catalog)
        plan = self._plan(stmt.query)
        cols = tuple(
            ColumnMetadata(name, sym.type)
            for name, sym in zip(plan.column_names, plan.symbols))
        # a QUERY-level retry replays the whole statement: a table THIS
        # query already created must not trip "already exists" on the
        # re-run (the idempotent sink makes the data half exactly-once;
        # this makes the DDL half replayable)
        table_key = (qname.catalog, qname.schema, qname.table)
        replay = table_key in self._created_tables
        conn.metadata.create_table(
            TableMetadata(qname.schema_table, cols,
                          self._table_properties(stmt)),
            stmt.not_exists or replay)
        self._created_tables.add(table_key)
        self._invalidate_plans(qname)
        if not stmt.with_data:
            return MaterializedResult(["rows"], [T.BIGINT], [(0,)])
        handle = conn.metadata.get_table_handle(qname.schema_table)
        writer = TableWriterNode(
            plan.source, qname.catalog, handle, plan.symbols,
            Symbol("rows", T.BIGINT))
        out = OutputNode(writer, ("rows",), (Symbol("rows", T.BIGINT),))
        # invalidate again once the data lands: a concurrent clone may
        # have cached an empty-table plan between create and write
        try:
            return self._run_plan(out)
        finally:
            self._invalidate_plans(qname)

    def _insert(self, stmt: t.Insert) -> MaterializedResult:
        qname = self._resolve(stmt.target)
        conn = self.catalogs.get(qname.catalog)
        handle = conn.metadata.get_table_handle(qname.schema_table)
        if handle is None:
            raise SemanticError(f"table not found: {qname}")
        meta = conn.metadata.get_table_metadata(handle)
        if stmt.columns:
            raise SemanticError("INSERT with column list not supported yet")
        plan = self._plan(stmt.query)
        if len(plan.symbols) != len(meta.columns):
            raise SemanticError(
                f"INSERT has {len(plan.symbols)} columns but table has "
                f"{len(meta.columns)}")
        writer = TableWriterNode(
            plan.source, qname.catalog, handle, plan.symbols,
            Symbol("rows", T.BIGINT))
        out = OutputNode(writer, ("rows",), (Symbol("rows", T.BIGINT),))
        # INSERT changes data + statistics: cached plans over this table
        # (scan capacities, broadcast decisions) must re-plan. Invalidate
        # AFTER the write lands — invalidating first opens a window where
        # a concurrent clone re-caches a pre-insert plan that then
        # outlives the change. finally: a failed/partial write is still a
        # change (conservative).
        try:
            return self._run_plan(out)
        finally:
            self._invalidate_plans(qname)

    def _drop_table(self, stmt: t.DropTable) -> MaterializedResult:
        qname = self._resolve(stmt.name)
        conn = self.catalogs.get(qname.catalog)
        handle = conn.metadata.get_table_handle(qname.schema_table)
        if handle is None:
            if stmt.exists:
                return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])
            raise SemanticError(f"table not found: {qname}")
        conn.metadata.drop_table(handle)
        self._invalidate_plans(qname)
        return MaterializedResult(["result"], [T.BOOLEAN], [(True,)])

    # -------------------------------------------------------------- SHOW

    def _explain(self, stmt: t.Explain) -> MaterializedResult:
        if not isinstance(stmt.statement, t.Query):
            raise SemanticError("EXPLAIN requires a query")
        if stmt.analyze:
            # through the plan cache: the footer's plan-cache counters
            # are live, and EXPLAIN ANALYZE warms/reuses the same entry
            # the plain statement dispatches
            return self._explain_analyze(
                self._plan_query_for_analyze(stmt.statement))
        plan = self._plan(stmt.statement)
        if stmt.explain_type == "DISTRIBUTED":
            from trino_tpu.planner.optimizer import add_exchanges, \
                OptimizerContext, StatsEstimator
            ctx = OptimizerContext(self.metadata, self.session,
                                   StatsEstimator(self.metadata))
            plan = add_exchanges(plan, ctx)
            frag = fragment_plan(plan)
            text = _format_fragments(frag)
        else:
            text = format_plan(plan)
        return MaterializedResult(["Query Plan"], [T.VARCHAR], [(text,)])

    def _explain_analyze(self, plan: OutputNode) -> MaterializedResult:
        """EXPLAIN ANALYZE: run the query with per-node instrumentation
        (operator-level collection + device fencing forced on the query's
        collector) and render the plan annotated with each node's rows,
        bytes, and wall time (operator/ExplainAnalyzeOperator.java +
        OperatorStats.java via obs/stats.py)."""
        import time
        from trino_tpu.exec.jit_cache import host_read
        from trino_tpu.obs.stats import (QueryStatsCollector, maybe_phase,
                                         render_analyzed_plan)
        col = self._collector
        if col is None:
            # direct call outside execute(): a FRESH collector per call —
            # persisting it would let a second call's plan reuse the
            # first's id()-keyed operator slots after interpreter id reuse
            col = QueryStatsCollector("explain-analyze")
        col.operator_level = True
        col.fence = True
        executor = LocalExecutionPlanner(self.metadata, self.session)
        executor.collector = col
        executor.deadline = self._deadline
        executor.exec_params = self._exec_params
        executor.slices = self._slices
        executor.write_token = self._write_token
        executor.adaptive = self._adaptive
        executor.table_cache = self._active_table_cache()
        executor.table_cache_min_scans = int(
            self.session.get("table_cache_min_scans"))
        if self._memory is not None:
            executor.memory = self._memory
        t0 = time.perf_counter()
        n_out = 0
        with maybe_phase(col, "execution"):
            for page in executor.execute(plan).iter_pages():
                self._check_deadline()
                n_out += int(host_read(page.num_rows, "result_rows", col))
        total = time.perf_counter() - t0
        text = render_analyzed_plan(plan, col, n_out, total)
        return MaterializedResult(["Query Plan"], [T.VARCHAR], [(text,)])

    def _show_tables(self, stmt: t.ShowTables) -> MaterializedResult:
        catalog = self.session.catalog
        schema = self.session.schema
        if stmt.schema is not None:
            parts = stmt.schema.parts
            if len(parts) == 2:
                catalog, schema = parts
            else:
                schema = parts[0]
        conn = self.catalogs.get(catalog)
        tables = [n.table for n in conn.metadata.list_tables(schema)]
        if stmt.like:
            import re
            from trino_tpu.expr.functions import like_pattern_to_regex
            rx = re.compile(like_pattern_to_regex(stmt.like))
            tables = [x for x in tables if rx.match(x)]
        return MaterializedResult(["Table"], [T.VARCHAR],
                                  [(x,) for x in tables])

    def _show_schemas(self, stmt: t.ShowSchemas) -> MaterializedResult:
        catalog = stmt.catalog or self.session.catalog
        conn = self.catalogs.get(catalog)
        return MaterializedResult(
            ["Schema"], [T.VARCHAR],
            [(s,) for s in conn.metadata.list_schemas()])

    def _show_columns(self, stmt: t.ShowColumns) -> MaterializedResult:
        qname = self._resolve(stmt.table)
        conn = self.catalogs.get(qname.catalog)
        handle = conn.metadata.get_table_handle(qname.schema_table)
        if handle is None:
            raise SemanticError(f"table not found: {qname}")
        meta = conn.metadata.get_table_metadata(handle)
        return MaterializedResult(
            ["Column", "Type"], [T.VARCHAR, T.VARCHAR],
            [(c.name, c.type.display()) for c in meta.columns])


def _is_memory_pressure(exc: BaseException) -> bool:
    """A low-memory-killer verdict or injected node-pool pressure —
    retryable, and worth ONE spill-forced re-run."""
    from trino_tpu.errors import CLUSTER_OUT_OF_MEMORY, TrinoError
    return isinstance(exc, TrinoError) and exc.code is CLUSTER_OUT_OF_MEMORY


def _find_writers(node) -> List[TableWriterNode]:
    out = []
    if isinstance(node, TableWriterNode):
        out.append(node)
    for s in node.sources:
        out.extend(_find_writers(s))
    return out


def _contains_writer(node) -> bool:
    # derived from the single walker so the retry-exemption branch and
    # _writer_retry_safe can never disagree about what a plan writes
    return bool(_find_writers(node))


def _literal_value(e: t.Expression):
    if isinstance(e, t.StringLiteral):
        return e.value
    if isinstance(e, t.LongLiteral):
        return e.value
    if isinstance(e, t.BooleanLiteral):
        return e.value
    if isinstance(e, t.DoubleLiteral):
        return e.value
    raise SemanticError("SET SESSION value must be a literal")


def _format_fragments(frag, indent: int = 0) -> str:
    pad = " " * indent
    lines = [f"{pad}Fragment {frag.fragment_id} [{frag.partitioning}]"]
    for line in format_plan(frag.root).splitlines():
        lines.append(pad + "  " + line)
    for child in frag.children:
        lines.append(_format_fragments(child, indent + 2))
    return "\n".join(lines)
