"""Warmup/preload manifest: a cold server start that serves warm.

Reference parity: production deployments front the reference with
warm-up query storms (benchto's prewarm phase) because the first run of
every shape pays planning + codegen. On this engine the costs are plan
cache misses and XLA compiles — both cacheable — so the server takes a
MANIFEST of representative statements at startup
(`TrinoServer(warmup_manifest=...)` or $TRINO_TPU_WARMUP_MANIFEST),
PREPAREs the named ones into the shared prepared-statement map, and
executes each once: that populates the plan cache (value-free keys for
prepared statements — ANY later parameter values hit), traces every
kernel of the shape into the jit cache (loading compiled binaries from
the persistent compilation cache when one is configured, so even the
XLA compile is a disk read), and optionally seeds the result cache.
The first real user request then binds + dispatches: plan_cache_hits=1,
jit_misses=0.

Manifest format (JSON; a bare list of statement specs also loads):

    {"statements": [
      {"name": "dash_q6", "sql": "SELECT ... WHERE l_quantity < ?",
       "using": "24"},
      {"sql": "SELECT count(*) FROM nation"}
    ]}

`name` + `sql` with `?` markers -> PREPARE name FROM sql, then (when
`using` is present) EXECUTE name USING <using>. Plain `sql` executes
directly. A failing statement is recorded in the report and does NOT
abort the server start — a partially warm server beats no server.

The manifest also learns `tables:` entries — DATA warmup, not just
plans: each named table's columns are read through its connector ONCE
at start() and promoted straight into the device table cache
(exec/table_cache.py), so the FIRST real scan is an HBM hit with zero
host->device staging:

    {"tables": [
      {"table": "lake.default.orders_part"},
      {"table": "tpch.tiny.nation", "columns": ["n_nationkey",
                                                "n_name"]}
     ],
     "statements": [...]}

`table` is catalog.schema.table (or schema.table / table, resolved
against the runner's session); `columns` defaults to every column.
On a mesh runner (DistributedQueryRunner) the table becomes RESIDENT
SHARDS instead: chip i reads its own splits and keeps them, and every
mesh scan of those columns is handed the arrays
(exec/table_cache.ShardedTable).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Union


def load_manifest(source: Union[str, dict, list]) -> List[Dict[str, Any]]:
    """Path / parsed dict / bare list -> the statement-spec list."""
    if isinstance(source, str):
        with open(source) as f:
            source = json.load(f)
    if isinstance(source, list):
        statements = source
    elif isinstance(source, dict):
        statements = source.get("statements")
        if statements is None and "tables" in source:
            statements = []     # a data-only manifest is legitimate
        if statements is None:
            raise ValueError(
                "warmup manifest needs a top-level 'statements' list "
                f"(got keys: {sorted(source)})")
    else:
        raise ValueError(
            f"warmup manifest must be a path, dict, or list, "
            f"not {type(source).__name__}")
    out = []
    for i, spec in enumerate(statements):
        if not isinstance(spec, dict) or "sql" not in spec:
            raise ValueError(
                f"warmup statement #{i} needs an object with 'sql' "
                f"(got {spec!r})")
        unknown = sorted(set(spec) - {"name", "sql", "using"})
        if unknown:
            # same strictness as resource-group config: a typo'd key must
            # not silently skip the warmup the operator asked for
            raise ValueError(
                f"warmup statement #{i}: unknown keys {unknown}")
        out.append(spec)
    return out


def load_tables(source: Union[str, dict, list]) -> List[Dict[str, Any]]:
    """The manifest's `tables:` preload specs (empty for bare lists)."""
    if isinstance(source, str):
        with open(source) as f:
            source = json.load(f)
    if not isinstance(source, dict):
        return []
    tables = source.get("tables") or []
    out = []
    for i, spec in enumerate(tables):
        if not isinstance(spec, dict) or "table" not in spec:
            raise ValueError(
                f"warmup table #{i} needs an object with 'table' "
                f"(got {spec!r})")
        unknown = sorted(set(spec) - {"table", "columns"})
        if unknown:
            raise ValueError(f"warmup table #{i}: unknown keys {unknown}")
        out.append(spec)
    return out


def preload_table(runner, table: str,
                  columns: Optional[List[str]] = None) -> Dict[str, Any]:
    """Read one table through its connector and promote the columns
    into the runner's device table cache — the first real scan is then
    an HBM hit with zero host->device staging."""
    import jax

    if not bool(runner.session.get("table_cache_enabled")):
        # promoting into a tier no query will ever consult would pin
        # HBM (pool cache reservation) for nothing
        raise ValueError(
            "table_cache_enabled is false on this server — `tables:` "
            "warmup entries need the device table cache on")
    qname = runner.metadata.resolve_table_name(
        tuple(table.split(".")), runner.session)
    conn = runner.catalogs.get(qname.catalog)
    handle = conn.metadata.get_table_handle(qname.schema_table)
    if handle is None:
        raise ValueError(f"table not found: {table}")
    all_handles = conn.metadata.get_column_handles(handle)
    if columns:
        by_name = {c.name: c for c in all_handles}
        missing = [c for c in columns if c not in by_name]
        if missing:
            raise ValueError(f"{table}: unknown columns {missing}")
        handles = [by_name[c] for c in columns]
    else:
        handles = list(all_handles)
    cache = runner._table_cache
    gen = cache.generation()    # before reading: the promotion guard
    tkey = (qname.catalog, qname.schema, qname.table)
    names = [c.name for c in handles]
    mesh = getattr(runner, "mesh", None)
    if mesh is not None and mesh.n > 1 \
            and bool(runner.session.get("mesh_execution")):
        # a mesh runner keeps the table as resident shards: chip i reads
        # its own splits and holds them, nothing passes through chip 0
        from trino_tpu.exec.mesh_exec import admit_shards, stage_shards
        page, _ = stage_shards(runner, conn, handle, handles)
        _drop_scan_stats(conn)
        resident = admit_shards(cache, tkey, names, page, gen=gen)
        return {"table": str(qname), "columns": len(handles),
                "rows": int(sum(jax.device_get(page.num_rows))),
                "resident": bool(resident), "shards": mesh.n}
    stats = conn.metadata.get_table_statistics(handle)
    rows = int(stats.row_count or 0)
    cap = 1 << 16
    while cap < rows and cap < (1 << 22):
        cap *= 2
    cache.configure(int(runner.session.get("table_cache_max_bytes")),
                    int(runner.session.get("table_cache_min_scans")))
    pages = []
    for split in conn.split_manager.get_splits(handle, target_splits=1):
        for page in conn.page_source.pages(split, handles, cap):
            if not pages and rows and not cache.admits(page, rows):
                # by shapes alone the cache cannot take it (SF10's
                # lineitem against 1 GiB): the first page has warmed what
                # the connector keeps on the device (the tpch connector
                # builds its whole columns then); every further page
                # would be a copy held for a promotion that is refused
                _drop_scan_stats(conn)
                return {"table": str(qname), "columns": len(handles),
                        "rows": rows, "resident": False}
            pages.append(page)
    _drop_scan_stats(conn)
    counts = [int(c) for c in jax.device_get(
        [p.num_rows for p in pages])] if pages else []
    cache.note_scan(tkey, names)
    resident = cache.promote_from_pages(
        tkey, [(c.name, c) for c in handles], pages, counts, gen=gen)
    return {"table": str(qname), "columns": len(handles),
            "rows": int(sum(counts)), "resident": bool(resident)}


def _drop_scan_stats(conn) -> None:
    """The preload's thread-local scan counters are nobody's query's."""
    take = getattr(conn, "take_scan_stats", None)
    if take is not None:
        take()


def apply_warmup(runner, source: Union[str, dict, list]
                 ) -> List[Dict[str, Any]]:
    """Run the manifest against `runner` (the server's BASE runner, so
    PREPAREd names land in the shared map every request can EXECUTE).
    Preloads `tables:` into the device table cache first (data warmup),
    then PREPAREs/executes the statements (plan + kernel warmup).
    Returns the per-entry report: what warmed, what it cost, what the
    first real request will now skip."""
    report: List[Dict[str, Any]] = []
    for spec in load_tables(source):
        entry: Dict[str, Any] = {"table": spec["table"]}
        t0 = time.perf_counter()
        try:
            entry.update(preload_table(runner, spec["table"],
                                       spec.get("columns")))
            entry["wall_s"] = round(time.perf_counter() - t0, 4)
        except Exception as e:  # noqa: BLE001 — warm what we can
            entry["error"] = f"{type(e).__name__}: {str(e)[:160]}"
        report.append(entry)
    for spec in load_manifest(source):
        name = spec.get("name")
        label = name or spec["sql"][:60]
        entry: Dict[str, Any] = {"statement": label}
        t0 = time.perf_counter()
        try:
            if name:
                runner.execute(f"PREPARE {name} FROM {spec['sql']}")
                if spec.get("using"):
                    runner.execute(
                        f"EXECUTE {name} USING {spec['using']}")
            else:
                runner.execute(spec["sql"])
            stats = runner.last_query_stats
            entry.update({
                "wall_s": round(time.perf_counter() - t0, 4),
                "jit_misses": int(stats.get("jit_misses", 0)),
                "plan_cached": int(stats.get("plan_cache_misses", 0)) > 0
                or int(stats.get("plan_cache_hits", 0)) > 0,
            })
        except Exception as e:  # noqa: BLE001 — warm what we can
            entry["error"] = f"{type(e).__name__}: {str(e)[:160]}"
        report.append(entry)
    return report
