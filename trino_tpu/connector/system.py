"""system catalog: runtime introspection tables.

Reference parity: core/trino-main connector/system/ —
system.runtime.{queries,tasks,nodes} backed by live engine state
(GlobalSystemConnector + QuerySystemTable/TaskSystemTable/NodeSystemTable).
Tables materialize a snapshot page at scan time from the process-wide
QueryTracker and the JAX device topology (the node inventory of a
single-controller TPU engine is its device list, not a discovery service).
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from trino_tpu import types as T
from trino_tpu.connector.spi import (
    ColumnHandle, ColumnMetadata, Connector, ConnectorMetadata,
    ConnectorPageSource, ConnectorSplitManager, ConnectorTableHandle,
    SchemaTableName, Split, TableMetadata, TableStatistics)
from trino_tpu.page import Column, Dictionary, Page

TABLES: Dict[str, tuple] = {
    "queries": (
        ("query_id", T.VarcharType()), ("state", T.VarcharType()),
        ("user", T.VarcharType()), ("query", T.VarcharType()),
        ("rows", T.BIGINT), ("bytes", T.BIGINT),
        ("wall_ms", T.BIGINT), ("cpu_time_ms", T.BIGINT),
        ("error", T.VarcharType()), ("error_name", T.VarcharType()),
        ("retries", T.BIGINT), ("faults_injected", T.BIGINT),
        ("resource_group", T.VarcharType()),
        ("pool_reserved_bytes", T.BIGINT), ("pool_peak_bytes", T.BIGINT),
        ("memory_kills", T.BIGINT), ("leaked_bytes", T.BIGINT),
        ("spilled_bytes", T.BIGINT),
        ("device_time_ms", T.DOUBLE), ("compile_time_ms", T.DOUBLE),
        # the executor's host timeline (obs/stats.activity): programs the
        # query enqueued, times its thread stopped to read a device
        # value, executables XLA compiled for it
        ("kernel_calls", T.BIGINT), ("host_reads", T.BIGINT),
        ("backend_compiles", T.BIGINT)),
    # the query-history ring (obs/history.py): terminal queries retained
    # past the live tracker's pruning bound, with the device/compile/host
    # time split and the full error taxonomy — the post-incident table
    "completed_queries": (
        ("query_id", T.VarcharType()), ("state", T.VarcharType()),
        ("user", T.VarcharType()), ("query", T.VarcharType()),
        ("rows", T.BIGINT), ("bytes", T.BIGINT),
        ("wall_ms", T.BIGINT), ("cpu_time_ms", T.BIGINT),
        ("device_time_ms", T.DOUBLE), ("compile_time_ms", T.DOUBLE),
        ("error", T.VarcharType()), ("error_name", T.VarcharType()),
        ("error_type", T.VarcharType()), ("retryable", T.BOOLEAN),
        ("retries", T.BIGINT), ("faults_injected", T.BIGINT),
        ("resource_group", T.VarcharType()),
        ("peak_memory_bytes", T.BIGINT), ("ended_at_ms", T.BIGINT)),
    "tasks": (
        ("query_id", T.VarcharType()), ("task_id", T.VarcharType()),
        ("state", T.VarcharType()), ("rows", T.BIGINT),
        ("wall_ms", T.BIGINT)),
    "nodes": (
        ("node_id", T.VarcharType()), ("node_version", T.VarcharType()),
        ("coordinator", T.BOOLEAN), ("state", T.VarcharType()),
        ("pool_limit_bytes", T.BIGINT), ("pool_reserved_bytes", T.BIGINT),
        ("pool_peak_bytes", T.BIGINT), ("pool_kills", T.BIGINT),
        ("pool_leaks", T.BIGINT), ("pool_leaked_bytes", T.BIGINT),
        ("pool_budget_source", T.VarcharType()),
        ("device_reserved_bytes", T.BIGINT),
        ("device_peak_bytes", T.BIGINT)),
    "resource_groups": (
        ("name", T.VarcharType()), ("parent", T.VarcharType()),
        ("queued", T.BIGINT), ("running", T.BIGINT),
        ("started", T.BIGINT), ("finished", T.BIGINT),
        ("served_from_cache", T.BIGINT),
        ("cache_hit_rejections", T.BIGINT),
        ("result_cache_qps", T.DOUBLE),
        ("hard_concurrency", T.BIGINT), ("max_queued", T.BIGINT),
        ("soft_memory_limit_bytes", T.BIGINT),
        ("scheduling_weight", T.BIGINT),
        ("memory_usage_bytes", T.BIGINT),
        ("scheduled_wall_ms", T.BIGINT)),
    # the serving tier's cache inventory (trino_tpu/serve/caches.py +
    # exec/plan_cache.py + exec/jit_cache.py): one row per cache layer,
    # the same counters /v1/metrics exports, SQL-queryable
    "caches": (
        ("cache", T.VarcharType()), ("entries", T.BIGINT),
        ("bytes", T.BIGINT), ("hits", T.BIGINT), ("misses", T.BIGINT),
        ("evictions", T.BIGINT), ("invalidations", T.BIGINT)),
    # the process metrics registry (obs/metrics.py) as a table: the same
    # samples GET /v1/metrics exposes, SQL-queryable
    "metrics": (
        ("name", T.VarcharType()), ("kind", T.VarcharType()),
        ("labels", T.VarcharType()), ("value", T.DOUBLE)),
    # deployment-level server/fleet knobs (metadata.SERVER_PROPERTY_DOCS):
    # constructor properties, not session properties — surfaced so
    # operators can discover them the same way they discover session
    # properties through SHOW SESSION
    "server_properties": (
        ("name", T.VarcharType()), ("description", T.VarcharType())),
    # the MV registry (trino_tpu/mv/): one row per materialized view
    # across live runners — definition freshness (seconds of unfolded
    # base history), the recorded base versions of the last refresh,
    # and the refresh/rewrite/republish counters behind trino_tpu_mv_*
    "materialized_views": (
        ("catalog", T.VarcharType()), ("schema", T.VarcharType()),
        ("name", T.VarcharType()), ("storage_table", T.VarcharType()),
        ("incremental", T.BOOLEAN), ("refreshed_at", T.DOUBLE),
        ("staleness_s", T.DOUBLE), ("base_versions", T.VarcharType()),
        ("refreshes_delta", T.BIGINT), ("refreshes_full", T.BIGINT),
        ("rewrite_hits", T.BIGINT), ("republished", T.BIGINT)),
}


def _rows_for(table: str) -> List[tuple]:
    from trino_tpu.exec.query_tracker import TRACKER
    if table == "queries":
        return [(q.query_id, q.state, q.user, q.query, q.rows,
                 q.output_bytes,
                 q.wall_ms if q.wall_ms is not None else 0,
                 q.cpu_time_ms, q.error,
                 q.error_name, q.retries, q.faults_injected,
                 q.resource_group, q.pool_reserved_bytes,
                 max(q.pool_peak_bytes,
                     q.mem.peak if q.mem is not None else 0),
                 max(q.memory_kills,
                     q.mem.kills if q.mem is not None else 0),
                 q.leaked_bytes,
                 (q.stats or {}).get("spilled_bytes", 0),
                 float((q.stats or {}).get("device_time_ms", 0) or 0),
                 float((q.stats or {}).get("compile_time_ms", 0) or 0),
                 (q.stats or {}).get("kernel_calls", 0),
                 (q.stats or {}).get("host_reads", 0),
                 (q.stats or {}).get("backend_compiles", 0))
                for q in TRACKER.list()]
    if table == "completed_queries":
        from trino_tpu.obs.history import HISTORY
        return [(c.query_id, c.state, c.user, c.query, c.rows,
                 c.output_bytes, c.wall_ms, c.cpu_time_ms,
                 c.device_time_ms, c.compile_time_ms, c.error,
                 c.error_name, c.error_type,
                 bool(c.retryable) if c.retryable is not None else None,
                 c.retries, c.faults_injected, c.resource_group,
                 c.peak_memory_bytes, int(c.ended_at * 1000))
                for c in HISTORY.list()]
    if table == "tasks":
        # single-controller engine: one task per query (the mesh's shards
        # are lanes inside one program, not separately tracked tasks)
        return [(q.query_id, f"{q.query_id}.0.0", q.state, q.rows,
                 q.wall_ms if q.wall_ms is not None else 0)
                for q in TRACKER.list()]
    if table == "nodes":
        import jax

        from trino_tpu.exec.memory import NODE_POOL
        devices = jax.devices()
        # the pool columns repeat per device row (the node pool is the
        # single-controller process's per-chip budget + source); the
        # device_* columns are THAT chip's attributed reservations, fed
        # by mesh shard executors and sharded staging
        pool = (NODE_POOL.limit or 0, NODE_POOL.reserved, NODE_POOL.peak,
                NODE_POOL.kills, NODE_POOL.leaks, NODE_POOL.leaked_bytes,
                NODE_POOL.budget_source)
        return [(f"{d.platform}-{d.id}", jax.__version__, d.id == 0,
                 "active") + pool
                + (NODE_POOL.device_reserved.get(i, 0),
                   NODE_POOL.device_peak.get(i, 0))
                for i, d in enumerate(devices)]
    if table == "resource_groups":
        from trino_tpu.exec.resource_groups import list_all_groups
        return [(g.name,
                 g.parent.name if g.parent is not None else None,
                 g.queued, len(g.running), g.started, g.finished,
                 g.served_from_cache,
                 g.cache_hit_rejections,
                 g.result_cache_qps if g.result_cache_qps is not None
                 else 0.0,
                 g.hard_concurrency, g.max_queued,
                 g.soft_memory_limit_bytes if
                 g.soft_memory_limit_bytes is not None else 0,
                 g.weight, g.memory_usage(),
                 int(g.scheduled_wall_s * 1000))
                for g in list_all_groups()]
    if table == "caches":
        from trino_tpu.exec import jit_cache, plan_cache
        from trino_tpu.exec.table_cache import table_cache_stats
        from trino_tpu.serve.caches import (result_cache_stats,
                                            scan_cache_stats)
        ps = plan_cache.stats()
        rs = result_cache_stats()
        ss = scan_cache_stats()
        ts = table_cache_stats()
        js = jit_cache.stats()
        return [
            ("plan", ps["entries"], 0, ps["hits"], ps["misses"],
             ps["evictions"], ps["invalidations"]),
            ("result", rs["entries"], 0, rs["hits"], rs["misses"],
             rs["evictions"], rs["invalidations"]),
            ("scan", ss["entries"], ss["bytes"], ss["hits"],
             ss["misses"], ss["evictions"], ss["invalidations"]),
            ("table", ts["entries"], ts["bytes"], ts["hits"],
             ts["misses"], ts["evictions"], ts["invalidations"]),
            ("jit", js["size"], 0, js["hits"], js["misses"],
             js["evictions"], 0),
        ]
    if table == "metrics":
        from trino_tpu.obs.metrics import REGISTRY
        return REGISTRY.samples()
    if table == "server_properties":
        from trino_tpu.metadata import SERVER_PROPERTY_DOCS
        return sorted(SERVER_PROPERTY_DOCS.items())
    if table == "materialized_views":
        from trino_tpu.mv.manager import all_materialized_view_rows
        return all_materialized_view_rows()
    raise KeyError(table)


class SystemMetadata(ConnectorMetadata):
    def list_schemas(self) -> List[str]:
        return ["runtime"]

    def list_tables(self, schema: Optional[str] = None
                    ) -> List[SchemaTableName]:
        return [SchemaTableName("runtime", t) for t in sorted(TABLES)]

    def get_table_handle(self, name: SchemaTableName
                         ) -> Optional[ConnectorTableHandle]:
        if name.schema == "runtime" and name.table in TABLES:
            return ConnectorTableHandle(name)
        return None

    def get_table_metadata(self, handle: ConnectorTableHandle
                           ) -> TableMetadata:
        cols = tuple(ColumnMetadata(n, ty)
                     for n, ty in TABLES[handle.name.table])
        return TableMetadata(handle.name, cols)

    def get_table_statistics(self, handle: ConnectorTableHandle
                             ) -> TableStatistics:
        return TableStatistics(float(len(_rows_for(handle.name.table))))


class SystemSplitManager(ConnectorSplitManager):
    def get_splits(self, handle: ConnectorTableHandle,
                   target_splits: int = 1) -> List[Split]:
        return [Split(handle, 0, 1, host=0)]


class SystemPageSource(ConnectorPageSource):
    def pages(self, split: Split, columns: Sequence[ColumnHandle],
              page_capacity: int) -> Iterator[Page]:
        table = split.table.name.table
        rows = _rows_for(table)
        n = len(rows)
        cap = max(8, 1 << max(3, (n - 1).bit_length()) if n else 8)
        cols = []
        spec = TABLES[table]
        for ch in columns:
            pos = next(i for i, (nm, _) in enumerate(spec) if nm == ch.name)
            vals = [r[pos] for r in rows]
            if T.is_string(ch.type):
                d, codes = Dictionary.build(np.asarray(
                    [v if v is not None else "" for v in vals] or [""],
                    dtype=object))
                arr = np.zeros(cap, dtype=np.int32)
                arr[:n] = codes[:n]
                valid = None
                if any(v is None for v in vals):
                    va = np.zeros(cap, dtype=bool)
                    va[:n] = [v is not None for v in vals]
                    valid = va
                cols.append(Column.from_numpy(arr, ch.type, valid=valid,
                                              dictionary=d))
            else:
                dt = T.to_numpy_dtype(ch.type)
                arr = np.zeros(cap, dtype=dt)
                arr[:n] = [0 if v is None else v for v in vals]
                cols.append(Column.from_numpy(arr, ch.type))
        yield Page(tuple(cols), n)


def create_connector() -> Connector:
    return Connector("system", SystemMetadata(), SystemSplitManager(),
                     SystemPageSource())
