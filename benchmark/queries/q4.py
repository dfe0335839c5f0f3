"""TPC-H Q4 (order priority checking): a quarter's orders that have a
line received after its commit date (EXISTS: a semi-join), counted by
priority."""

import numpy as np

from tpch_columns_q18_q4 import PRIORITIES, of_chunk
from wire import days

SQL = """
SELECT o_orderpriority, count(*) AS order_count
FROM orders
WHERE o_orderdate >= DATE '{date}'
  AND o_orderdate < DATE '{date}' + INTERVAL '3' MONTH
  AND EXISTS (SELECT * FROM lineitem
              WHERE l_orderkey = o_orderkey
                AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority ORDER BY o_orderpriority
"""

PREPARED = SQL.replace("DATE '{date}'", "?")

USING = "DATE '{date}', DATE '{date}', DATE '{date}'"

# TPC-H clause 2.4.4.3: DATE is the first day of a month between
# 1993-01 and 1997-10
DOMAIN = {"date": [f"{1993 + m // 12}-{m % 12 + 1:02d}-01"
                   for m in range(58)]}

COLUMNS = {
    "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"],
}


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    return sum(row_counts[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def quarter(date: str) -> tuple:
    """[first day, first day three months on) as days since 1970."""
    year, month = int(date[:4]), int(date[5:7]) + 3
    return days(date), days(f"{year + (month - 1) // 12}-"
                            f"{(month - 1) % 12 + 1:02d}{date[7:]}")


def partial(c: dict, p: dict, customer: dict) -> list:
    """Five counts: every lineitem of an order lies in the order's own
    chunk, so the EXISTS is whole here."""
    extra = of_chunk(c, customer)
    lo, hi = quarter(p["date"])
    late = extra["l_commitdate"] < extra["l_receiptdate"]
    has_late = np.zeros(len(c["o_orderkey"]), dtype=bool)
    has_late[(c["l_orderkey"] - c["o_orderkey"][0])[late]] = True
    keep = (c["o_orderdate"] >= lo) & (c["o_orderdate"] < hi) & has_late
    return np.bincount(extra["o_orderpriority"][keep],
                       minlength=len(PRIORITIES)).tolist()


def merge(partials: list, p: dict) -> list:
    counts = [sum(part[i] for part in partials)
              for i in range(len(PRIORITIES))]
    return [[name, n] for name, n in zip(PRIORITIES, counts) if n]
