"""The five columns Q18 and Q4 read beyond `tpch_columns.py`'s fourteen.

Standalone NumPy copies of the streams in `trino_tpu/connector/tpch_gen.py`
(`o_totalprice`, `o_orderpriority`'s pool, `l_commitdate`, `l_receiptdate`,
and `c_name`'s format from the key), so that the reference still imports
nothing of the program. `reference._worker` hands a shape's `partial` the
14-column chunk and the customer table and nothing else — no scale factor,
no row index — so `of_chunk` takes the scale from the customer table's
length (150 000 x SF) and the chunk's first lineitem row from the count of
lines before its first order, and keeps what it made for the newest chunk:
a second query over the same chunk does not pay for it again.
"""

from __future__ import annotations

import zlib

import numpy as np

import tpch_columns as C

PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")

_LAST = {}      # the newest chunk: its key, its columns, where it ended


def scale_factor(customer: dict) -> float:
    """The scale factor of a whole customer table (150 000 x SF rows; at
    least one row at any scale)."""
    n = len(customer["c_custkey"])
    for sf in C.SCALE_FACTORS.values():
        if C.customer_count(sf) == n:
            return sf
    return n / 150_000


def c_name(custkey) -> list:
    """Customer#<key, nine digits>: the name is a format of the key."""
    return [f"Customer#{int(k):09d}" for k in np.asarray(custkey).ravel()]


def orders_extra(sf: float, o_first: int, o_last: int) -> dict:
    with np.errstate(over="ignore"):
        oidx = np.arange(o_first, o_last, dtype=np.uint64)
        return {
            "o_totalprice": C._ui("orders", "o_totalprice", sf, oidx,
                                  85000, 55558641),
            # the pool is sorted as written, so the raw index is the code
            "o_orderpriority": C._ui("orders", "o_orderpriority", sf, oidx,
                                     0, 4).astype(np.int8),
        }


def lineitem_extra(sf: float, chunk: dict, row0: int) -> dict:
    """`row0`: the table-wide index of the chunk's first lineitem row."""
    with np.errstate(over="ignore"):
        n = len(chunk["l_orderkey"])
        idx = np.arange(row0, row0 + n, dtype=np.uint64)
        local = chunk["l_orderkey"] - chunk["o_orderkey"][0]
        odate = chunk["o_orderdate"].astype(np.int64)[local]
        commit = odate + C._ui("lineitem", "l_cdays", sf, idx, 30, 90)
        receipt = chunk["l_shipdate"].astype(np.int64) \
            + C._ui("lineitem", "l_rdays", sf, idx, 1, 30)
        return {"l_commitdate": commit.astype(np.int32),
                "l_receiptdate": receipt.astype(np.int32)}


def of_chunk(chunk: dict, customer: dict) -> dict:
    """The five columns' four that lie in an order chunk (c_name is a
    function of the key), for the chunk `reference._worker` handed over."""
    sf = scale_factor(customer)
    o_first = int(chunk["o_orderkey"][0]) - 1
    o_last = o_first + len(chunk["o_orderkey"])
    key = (sf, o_first, o_last)
    if _LAST.get("key") != key:
        # a worker's chunks follow one another: the next one starts at
        # the row where the last one ended
        row0 = _LAST["row_end"] if _LAST.get("ends") == (sf, o_first) \
            else C.lineitem_rows_before(sf, o_first)
        _LAST.update(
            key=key, ends=(sf, o_last),
            row_end=row0 + len(chunk["l_orderkey"]),
            columns={**orders_extra(sf, o_first, o_last),
                     **lineitem_extra(sf, chunk, row0)})
    return _LAST["columns"]


def fingerprint(sf: float) -> str:
    """crc32 over the first 4096 orders' (and their lineitems', and the
    first 4096 customers' names) of the five columns, in name order."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    chunk = C.orders_chunk(sf, 0, n)
    cols = {**orders_extra(sf, 0, n), **lineitem_extra(sf, chunk, 0)}
    crc = 0
    for name in sorted(cols):
        crc = zlib.crc32(np.ascontiguousarray(
            cols[name].astype(np.int64)).tobytes(), crc)
    keys = C.customer(sf)["c_custkey"][:C.FINGERPRINT_ORDERS]
    crc = zlib.crc32("\n".join(c_name(keys)).encode(), crc)
    return f"{crc:08x}"
