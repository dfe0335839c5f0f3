"""The benchmark's own host generator for the 14 TPC-H columns q6/q1/q3 read.

A standalone NumPy copy of the hash streams of
`trino_tpu/connector/tpch_gen.py` (original listed in PERF.md, Open
questions), so that the reference imports nothing of the program and a
later change to the program's data shows as `correct: false`. Every
column is a counter-based hash of the row index, so any order range can
be generated alone, in any process.

String columns are small integer codes into the sorted pools below.
Decimals are scaled integers (cents), dates are days since 1970-01-01.
"""

from __future__ import annotations

import zlib

import numpy as np

RETURNFLAGS = ("A", "N", "R")
LINESTATUSES = ("F", "O")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")

# every schema `connector/tpch.py` can be asked for, and sf30, which the
# four-chip deployment needs of it (PERF.md, Open questions)
SCALE_FACTORS = {"tiny": 0.01, "sf1": 1.0, "sf10": 10.0, "sf30": 30.0,
                 "sf100": 100.0, "sf300": 300.0, "sf1000": 1000.0}


def days(date: str) -> int:
    return int(np.datetime64(date, "D").astype(np.int64))


MIN_DATE = days("1992-01-01")
MAX_ORDER_DATE = days("1998-08-02")
CURRENT_DATE = days("1995-06-17")

_GOLD = np.uint64(0x9E3779B97F4A7C15)
_SM1 = np.uint64(0xBF58476D1CE4E5B9)
_SM2 = np.uint64(0x94D049BB133111EB)


def _u64(table: str, column: str, sf: float, idx: np.ndarray) -> np.ndarray:
    tag = f"{table}.{column}:{round(sf * 1000)}"
    seed = np.uint64(zlib.crc32(tag.encode()) + 0x1000) * _GOLD
    x = (idx.astype(np.uint64) + np.uint64(1)) * _GOLD + seed
    x = (x ^ (x >> np.uint64(30))) * _SM1
    x = (x ^ (x >> np.uint64(27))) * _SM2
    return x ^ (x >> np.uint64(31))


def _ui(table, column, sf, idx, lo: int, hi: int) -> np.ndarray:
    """Uniform integer in [lo, hi], int64."""
    return lo + (_u64(table, column, sf, idx)
                 % np.uint64(hi - lo + 1)).astype(np.int64)


def order_count(sf: float) -> int:
    return max(1, int(1_500_000 * sf))


def customer_count(sf: float) -> int:
    return max(1, int(150_000 * sf))


def lines_per_order(sf: float, o_first: int, o_last: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        return (1 + (_u64("lineitem", "l_count", sf,
                          np.arange(o_first, o_last, dtype=np.uint64))
                     % np.uint64(7))).astype(np.int64)


def lineitem_rows_before(sf: float, o_first: int) -> int:
    return int(lines_per_order(sf, 0, o_first).sum()) if o_first else 0


def row_counts(sf: float) -> dict:
    return {"lineitem": lineitem_rows_before(sf, order_count(sf)),
            "orders": order_count(sf), "customer": customer_count(sf)}


def customer(sf: float) -> dict:
    """The whole customer table (1.5 M rows at SF10)."""
    with np.errstate(over="ignore"):
        idx = np.arange(customer_count(sf), dtype=np.uint64)
        return {"c_custkey": idx.astype(np.int64) + 1,
                "c_mktsegment": _ui("customer", "c_mktsegment", sf, idx,
                                    0, 4).astype(np.int8)}


def orders_chunk(sf: float, o_first: int, o_last: int, row0=None) -> dict:
    """Orders [o_first, o_last) and every lineitem row of those orders;
    `row0` is `lineitem_rows_before(sf, o_first)` where the caller has it."""
    with np.errstate(over="ignore"):
        oidx = np.arange(o_first, o_last, dtype=np.uint64)
        ncust = customer_count(sf)
        odate = _ui("orders", "o_orderdate", sf, oidx, MIN_DATE,
                    MAX_ORDER_DATE - 152)
        ck = _ui("orders", "o_custkey", sf, oidx, 1, max(ncust, 2))
        # a third of the customers place no orders
        ck = np.where(ck % 3 == 0, np.maximum((ck + 1) % (ncust + 1), 1), ck)
        lines = lines_per_order(sf, o_first, o_last)
        if row0 is None:
            row0 = lineitem_rows_before(sf, o_first)
        local = np.repeat(np.arange(len(lines), dtype=np.int64), lines)
        idx = np.arange(row0, row0 + len(local), dtype=np.uint64)
        nparts = max(1, int(200_000 * sf))
        pk = _ui("lineitem", "l_partkey", sf, idx, 1, nparts)
        qty = _ui("lineitem", "l_quantity", sf, idx, 1, 50)
        retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        shipdate = odate[local] + _ui("lineitem", "l_sdays", sf, idx, 1, 121)
        receipt = shipdate + _ui("lineitem", "l_rdays", sf, idx, 1, 30)
        coin = (_u64("lineitem", "l_returnflag", sf, idx)
                & np.uint64(1)) == 0
        flag = np.where(receipt <= CURRENT_DATE, np.where(coin, 2, 0), 1)
        return {
            "o_orderkey": oidx.astype(np.int64) + 1,
            "o_custkey": ck,
            "o_orderdate": odate.astype(np.int32),
            "o_shippriority": np.zeros(len(oidx), dtype=np.int32),
            "l_orderkey": o_first + local + 1,
            "l_quantity": qty * 100,
            "l_extendedprice": qty * retail,
            "l_discount": _ui("lineitem", "l_discount", sf, idx, 0, 10),
            "l_tax": _ui("lineitem", "l_tax", sf, idx, 0, 8),
            "l_returnflag": flag.astype(np.int8),
            "l_linestatus": (shipdate > CURRENT_DATE).astype(np.int8),
            "l_shipdate": shipdate.astype(np.int32),
        }


FINGERPRINT_ORDERS = 4096


def fingerprint(sf: float) -> str:
    """crc32 over the first 4096 orders (and their lineitems, and the
    first 4096 customers) of each of the 14 columns, in name order."""
    cols = orders_chunk(sf, 0, min(FINGERPRINT_ORDERS, order_count(sf)))
    cols.update({k: v[:FINGERPRINT_ORDERS] for k, v in customer(sf).items()})
    crc = 0
    for name in sorted(cols):
        crc = zlib.crc32(np.ascontiguousarray(
            cols[name].astype(np.int64)).tobytes(), crc)
    return f"{crc:08x}"
