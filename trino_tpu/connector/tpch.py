"""TPC-H generator connector: deterministic in-memory data, no files.

Reference parity: plugin/trino-tpch (TpchMetadata.java, TpchRecordSetProvider
.java, TpchSplitManager.java) — schemas tiny/sf1/sf10/... expose the 8 TPC-H
tables, rows generated on demand. The reference delegates to io.airlift.tpch
(a dbgen port); data here comes from `tpch_gen` — stateless counter-hash
column streams reproducing dbgen's seekability (any column, any row range,
any process, identical bytes) so scans materialize only the columns and row
ranges they touch. That is what makes SF100 runnable on one host: a q9 scan
of 600M-row lineitem generates 7 of 16 columns, chunk by chunk, and pooled
varchar columns are emitted directly as dictionary codes (no Python string
objects on the scan path).

Correctness contract: engine and sqlite oracle read the SAME generated data
(the H2QueryRunner pattern); see tpch_gen's docstring for the documented
re-scope vs dbgen bit-identical rows.

All varchar columns come dictionary-encoded; dates are int32 days since epoch;
prices are short decimals (scaled int64).
"""

from __future__ import annotations

import math
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from trino_tpu import types as T
from trino_tpu.connector import tpch_dev, tpch_gen as G
from trino_tpu.connector.spi import (
    ColumnHandle, ColumnMetadata, Connector, ConnectorMetadata,
    ConnectorPageSource, ConnectorSplitManager, ConnectorTableHandle,
    ColumnStatistics, SchemaTableName, Split, TableMetadata, TableStatistics,
    pad_to_capacity, split_range)
from trino_tpu.page import Column, Dictionary, Page

_D12_2 = T.DecimalType(12, 2)

SCHEMAS = {
    "tiny": 0.01, "sf1": 1.0, "sf10": 10.0, "sf30": 30.0, "sf100": 100.0,
    "sf300": 300.0, "sf1000": 1000.0,
}

# table -> (columns, base row count at sf1); row counts per TPC-H spec 4.2.5
TABLES: Dict[str, tuple] = {
    "region": ((("r_regionkey", T.BIGINT), ("r_name", T.VarcharType(25)),
                ("r_comment", T.VarcharType(152))), None),
    "nation": ((("n_nationkey", T.BIGINT), ("n_name", T.VarcharType(25)),
                ("n_regionkey", T.BIGINT), ("n_comment", T.VarcharType(152))),
               None),
    "supplier": ((("s_suppkey", T.BIGINT), ("s_name", T.VarcharType(25)),
                  ("s_address", T.VarcharType(40)), ("s_nationkey", T.BIGINT),
                  ("s_phone", T.VarcharType(15)), ("s_acctbal", _D12_2),
                  ("s_comment", T.VarcharType(101))), 10_000),
    "customer": ((("c_custkey", T.BIGINT), ("c_name", T.VarcharType(25)),
                  ("c_address", T.VarcharType(40)), ("c_nationkey", T.BIGINT),
                  ("c_phone", T.VarcharType(15)), ("c_acctbal", _D12_2),
                  ("c_mktsegment", T.VarcharType(10)),
                  ("c_comment", T.VarcharType(117))), 150_000),
    "part": ((("p_partkey", T.BIGINT), ("p_name", T.VarcharType(55)),
              ("p_mfgr", T.VarcharType(25)), ("p_brand", T.VarcharType(10)),
              ("p_type", T.VarcharType(25)), ("p_size", T.INTEGER),
              ("p_container", T.VarcharType(10)), ("p_retailprice", _D12_2),
              ("p_comment", T.VarcharType(23))), 200_000),
    "partsupp": ((("ps_partkey", T.BIGINT), ("ps_suppkey", T.BIGINT),
                  ("ps_availqty", T.INTEGER), ("ps_supplycost", _D12_2),
                  ("ps_comment", T.VarcharType(199))), 800_000),
    "orders": ((("o_orderkey", T.BIGINT), ("o_custkey", T.BIGINT),
                ("o_orderstatus", T.VarcharType(1)), ("o_totalprice", _D12_2),
                ("o_orderdate", T.DATE),
                ("o_orderpriority", T.VarcharType(15)),
                ("o_clerk", T.VarcharType(15)), ("o_shippriority", T.INTEGER),
                ("o_comment", T.VarcharType(79))), 1_500_000),
    "lineitem": ((("l_orderkey", T.BIGINT), ("l_partkey", T.BIGINT),
                  ("l_suppkey", T.BIGINT), ("l_linenumber", T.INTEGER),
                  ("l_quantity", _D12_2), ("l_extendedprice", _D12_2),
                  ("l_discount", _D12_2), ("l_tax", _D12_2),
                  ("l_returnflag", T.VarcharType(1)),
                  ("l_linestatus", T.VarcharType(1)), ("l_shipdate", T.DATE),
                  ("l_commitdate", T.DATE), ("l_receiptdate", T.DATE),
                  ("l_shipinstruct", T.VarcharType(25)),
                  ("l_shipmode", T.VarcharType(10)),
                  ("l_comment", T.VarcharType(44))), None),  # ~4x orders
}


def table_row_count(table: str, sf: float) -> int:
    return G.row_count(table, sf)


def _column_ndv(table: str, name: str, sf: float, rows: float) -> float:
    """Real distinct counts (cost/StatsCalculator parity): FK columns get
    their DOMAIN size, not the table's row count — the round-4 q9
    join-order regression traced to l_partkey claiming 600M NDV."""
    fk_domain = {
        "l_partkey": "part", "ps_partkey": "part",
        "l_suppkey": "supplier", "ps_suppkey": "supplier",
        "l_orderkey": "orders",
    }
    if name in fk_domain:
        return float(G.row_count(fk_domain[name], sf))
    if name == "o_custkey":
        # spec: a third of customers place no orders
        return float(G.row_count("customer", sf)) * 2 / 3
    if name in ("c_nationkey", "s_nationkey", "n_nationkey"):
        return 25.0
    if name in ("n_regionkey", "r_regionkey"):
        return 5.0
    if G.string_kind(table, name) == "pooled":
        return float(min(rows, len(G.pool_values(table, name, sf))))
    if name.endswith("date"):
        return float(min(rows, 2500.0))   # ~7 years of days
    if name.endswith("key"):
        return rows                        # primary keys
    if name in ("l_quantity", "l_linenumber", "p_size", "l_discount",
                "l_tax", "o_shippriority"):
        return float(min(rows, 50.0))
    return float(min(rows, max(rows / 4, 1000.0)))


def _host_chunk(table: str, sf: float, column: str, start: int,
                end: int) -> np.ndarray:
    """Object strings or numerics for a row range (oracle / CTAS path)."""
    if G.string_kind(table, column) is not None:
        return G.object_chunk(table, sf, column, start, end)
    return G.numeric_chunk(table, sf, column, start, end)


def get_table(table: str, sf: float) -> Dict[str, np.ndarray]:
    """Full host arrays for one table (oracle loading; small sf only —
    large-sf scans go through the chunked code path instead)."""
    n = G.row_count(table, sf)
    return {name: _host_chunk(table, sf, name, 0, n)
            for name, _ in TABLES[table][0]}


_DICT_CACHE: Dict[tuple, Dictionary] = {}


def table_dictionary(table: str, sf: float, column: str) -> Dictionary:
    """Shared per-(table, sf, column) dictionary so every page of a scan uses
    one pool (stable codes across splits; one trace per table). Pooled
    columns build from their fixed pool without materializing the column;
    formatted (per-row unique) columns materialize once on first use."""
    key = (table, round(sf * 1000), column)
    if key not in _DICT_CACHE:
        if G.string_kind(table, column) == "pooled":
            _DICT_CACHE[key] = Dictionary(
                G.pool_values(table, column, sf))
        else:
            n = G.row_count(table, sf)
            data = G.object_chunk(table, sf, column, 0, n)
            _DICT_CACHE[key] = Dictionary.build(data)[0]
    return _DICT_CACHE[key]


class TpchMetadata(ConnectorMetadata):
    """plugin/trino-tpch TpchMetadata.java analog."""

    def list_schemas(self) -> List[str]:
        return sorted(SCHEMAS)

    def list_tables(self, schema: Optional[str] = None) -> List[SchemaTableName]:
        schemas = [schema] if schema else sorted(SCHEMAS)
        return [SchemaTableName(s, t) for s in schemas for t in sorted(TABLES)]

    def get_table_handle(self, name: SchemaTableName) -> Optional[ConnectorTableHandle]:
        if name.schema in SCHEMAS and name.table in TABLES:
            return ConnectorTableHandle(name)
        return None

    def get_table_metadata(self, handle: ConnectorTableHandle) -> TableMetadata:
        cols = tuple(ColumnMetadata(n, t)
                     for n, t in TABLES[handle.name.table][0])
        return TableMetadata(handle.name, cols)

    def get_table_statistics(self, handle: ConnectorTableHandle) -> TableStatistics:
        sf = SCHEMAS[handle.name.schema]
        rows = float(table_row_count(handle.name.table, sf))
        cols: Dict[str, ColumnStatistics] = {}
        for name, typ in TABLES[handle.name.table][0]:
            cols[name] = ColumnStatistics(
                null_fraction=0.0,
                distinct_count=_column_ndv(handle.name.table, name, sf,
                                           rows))
        return TableStatistics(rows, cols)

    # date-derived status columns are heavily skewed (e.g. ~2/3 of orders
    # are fulfilled 'F'), so pool-uniform estimation would mislead
    _SKEWED_POOLED = {"o_orderstatus", "l_returnflag", "l_linestatus"}

    def estimate_like_selectivity(self, handle, column, pattern,
                                  escape=None):
        """Exact match fraction over the column's dictionary pool — valid
        because every non-skewed pooled column draws codes UNIFORMLY from
        its pool (tpch_gen `_ui` streams)."""
        table = handle.name.table
        if G.string_kind(table, column) != "pooled" \
                or column in self._SKEWED_POOLED:
            return None
        import re as _re
        from trino_tpu.expr.functions import like_pattern_to_regex
        values = G.pool_values(table, column, SCHEMAS[handle.name.schema])
        if len(values) == 0:
            return None
        rx = _re.compile(like_pattern_to_regex(pattern, escape), _re.DOTALL)
        hits = sum(1 for v in values if rx.match(v))
        return hits / len(values)

    def apply_filter(self, handle, constraint):
        # accept the whole domain for split pruning; engine re-applies row-wise
        merged = handle.constraint.intersect(constraint)
        return (ConnectorTableHandle(handle.name, merged, handle.limit),
                constraint)

    def apply_limit(self, handle, limit):
        if handle.limit is not None and handle.limit <= limit:
            return None
        return ConnectorTableHandle(handle.name, handle.constraint, limit)


class TpchSplitManager(ConnectorSplitManager):
    def get_splits(self, handle: ConnectorTableHandle,
                   target_splits: int = 1) -> List[Split]:
        sf = SCHEMAS[handle.name.schema]
        rows = table_row_count(handle.name.table, sf)
        parts = max(1, min(target_splits, math.ceil(rows / 4096)))
        return [Split(handle, p, parts, host=p) for p in range(parts)]


import collections
import os
import threading

# device-side generation (tpch_dev): default ON; set =0 to force the host
# numpy path (debugging / byte-equivalence comparisons)
_DEVICE_GEN = os.environ.get("TRINO_TPU_DEVICE_GEN", "1") != "0"

# one lock for both LRU caches: the server's executor pool scans
# concurrently, and the byte-accounting (USED counters vs OrderedDict)
# must not interleave. Generation under the lock serializes a cold miss;
# warm hits are a dict probe.
_CACHE_LOCK = threading.RLock()

# host-side generated-chunk LRU: at SF100 the working set (~29GB for q9's
# seven lineitem/orders columns) exceeds the DEVICE cache budget, and
# regenerating hash streams for 600M rows costs minutes per run — the host
# has 125GB RAM, so warm benchmark runs keep the numpy chunks resident
_HOST_CHUNK_CACHE: "collections.OrderedDict[tuple, np.ndarray]" = \
    collections.OrderedDict()
_HOST_CHUNK_CACHE_BYTES = int(os.environ.get(
    "TRINO_TPU_HOST_CHUNK_CACHE_BYTES", 48 << 30))
_HOST_CHUNK_CACHE_USED = 0


def _host_cached(key: tuple, build) -> np.ndarray:
    global _HOST_CHUNK_CACHE_USED
    with _CACHE_LOCK:
        arr = _HOST_CHUNK_CACHE.get(key)
        if arr is not None:
            _HOST_CHUNK_CACHE.move_to_end(key)
            return arr
    # build OUTSIDE the lock: a cold SF100 chunk generation takes minutes
    # and must not stall concurrent queries' warm cache hits (two racers
    # may both build; check-then-insert keeps the accounting exact)
    arr = build()
    nbytes = arr.nbytes
    with _CACHE_LOCK:
        if nbytes <= _HOST_CHUNK_CACHE_BYTES \
                and key not in _HOST_CHUNK_CACHE:
            while (_HOST_CHUNK_CACHE_USED + nbytes > _HOST_CHUNK_CACHE_BYTES
                   and _HOST_CHUNK_CACHE):
                _, evicted = _HOST_CHUNK_CACHE.popitem(last=False)
                _HOST_CHUNK_CACHE_USED -= evicted.nbytes
            _HOST_CHUNK_CACHE[key] = arr
            _HOST_CHUNK_CACHE_USED += nbytes
    return arr


_DEVICE_COL_CACHE: "collections.OrderedDict[tuple, Column]" = \
    collections.OrderedDict()
# LRU byte budget for staged table columns (HBM residency is finite;
# unbounded growth was flagged in round 2). Override for small chips.
_DEVICE_COL_CACHE_BYTES = int(os.environ.get(
    "TRINO_TPU_SCAN_CACHE_BYTES", 4 << 30))
_DEVICE_COL_CACHE_USED = 0


def set_device_cache_budget(nbytes: int) -> None:
    """Adjust the staged-column LRU budget at runtime (bench shrinks it
    before SF100 rungs so join state owns the HBM, evicting as needed)."""
    global _DEVICE_COL_CACHE_BYTES, _DEVICE_COL_CACHE_USED
    with _CACHE_LOCK:
        _DEVICE_COL_CACHE_BYTES = int(nbytes)
        while _DEVICE_COL_CACHE_USED > _DEVICE_COL_CACHE_BYTES \
                and _DEVICE_COL_CACHE:
            _, evicted = _DEVICE_COL_CACHE.popitem(last=False)
            _DEVICE_COL_CACHE_USED -= evicted.nbytes


def _staged_column(table: str, sf: float, name: str, typ: T.Type,
                   off: int, hi: int, page_capacity: int) -> Column:
    """Generate + pad + stage one column slice to device, once per
    (table, sf, column, slice, capacity), LRU-evicted under a byte budget.

    The reference streams table data from storage per query; TPC-H data here
    is immutable generator output, so re-staging identical bytes to HBM on
    every execution would only re-measure PCIe. Real-table residency analog:
    Trino's memory connector / a warmed OS page cache."""
    global _DEVICE_COL_CACHE_USED
    import jax
    # a caller that pins its pages to a chip (`jax.default_device`: a mesh
    # scan makes shard i on chip i and keeps it there itself) gets fresh
    # arrays and leaves none here: this LRU is the default device's
    pinned = jax.config.jax_default_device is not None
    key = (table, round(sf * 1000), name, off, hi, page_capacity)
    with _CACHE_LOCK:
        col = None if pinned else _DEVICE_COL_CACHE.get(key)
        if col is not None:
            _DEVICE_COL_CACHE.move_to_end(key)
            return col
    hkey = (table, round(sf * 1000), name, off, hi)
    if _DEVICE_GEN and tpch_dev.supported(table, name):
        # generate ON the device: same hash-stream expressions jit'd via
        # jnp (tpch_dev docstring) — no host hashing, no column transfer
        import jax.numpy as jnp
        values = tpch_dev.generate(table, sf, name, off, hi, page_capacity)
        if T.is_string(typ):
            col = Column(values, None, typ,
                         table_dictionary(table, sf, name))
        else:
            col = Column(values.astype(T.to_numpy_dtype(typ)), None, typ)
    elif T.is_string(typ):
        d = table_dictionary(table, sf, name)
        if G.string_kind(table, name) == "pooled":
            codes = _host_cached(
                hkey, lambda: G.codes_chunk(table, sf, name, off, hi))
        else:
            codes = _host_cached(
                hkey, lambda: d.encode(
                    G.object_chunk(table, sf, name, off, hi)))
        col = Column.from_numpy(pad_to_capacity(codes, page_capacity, 0),
                                typ, dictionary=d)
    else:
        arr = pad_to_capacity(
            _host_cached(hkey, lambda: np.asarray(
                G.numeric_chunk(table, sf, name, off, hi),
                T.to_numpy_dtype(typ))), page_capacity, 0)
        col = Column.from_numpy(arr, typ)
    nbytes = col.nbytes
    with _CACHE_LOCK:
        if pinned or nbytes > _DEVICE_COL_CACHE_BYTES:
            return col   # the caller's, or larger than the whole budget
        if key not in _DEVICE_COL_CACHE:
            while (_DEVICE_COL_CACHE_USED + nbytes
                   > _DEVICE_COL_CACHE_BYTES and _DEVICE_COL_CACHE):
                _, evicted = _DEVICE_COL_CACHE.popitem(last=False)
                _DEVICE_COL_CACHE_USED -= evicted.nbytes
            _DEVICE_COL_CACHE[key] = col
            _DEVICE_COL_CACHE_USED += nbytes
    return col


class TpchPageSource(ConnectorPageSource):
    def pages(self, split: Split, columns: Sequence[ColumnHandle],
              page_capacity: int) -> Iterator[Page]:
        handle = split.table
        table = handle.name.table
        sf = SCHEMAS[handle.name.schema]
        total = table_row_count(table, sf)
        start, end = split_range(total, split.part, split.total_parts)
        if handle.limit is not None:
            end = min(end, start + handle.limit)
        for off in range(start, end, page_capacity):
            hi = min(off + page_capacity, end)
            n = hi - off
            cols = [_staged_column(table, sf, ch.name, ch.type, off, hi,
                                   page_capacity) for ch in columns]
            yield Page(tuple(cols), n)


def create_connector() -> Connector:
    return Connector("tpch", TpchMetadata(), TpchSplitManager(),
                     TpchPageSource())
