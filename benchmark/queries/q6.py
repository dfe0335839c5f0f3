"""TPC-H Q6 (forecasting revenue change): scan, filter, one global sum."""

import numpy as np

from wire import days, dec

SQL = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= DATE '{date}'
  AND l_shipdate < DATE '{date}' + INTERVAL '1' YEAR
  AND l_discount BETWEEN {disc} - 0.01 AND {disc} + 0.01
  AND l_quantity < {qty}
"""

PREPARED = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= ?
  AND l_shipdate < ? + INTERVAL '1' YEAR
  AND l_discount BETWEEN ? - 0.01 AND ? + 0.01
  AND l_quantity < ?
"""

USING = "DATE '{date}', DATE '{date}', {disc}, {disc}, {qty}"

# TPC-H clause 2.4.6.3: DATE is Jan 1 of 1993..1997, DISCOUNT 0.02..0.09,
# QUANTITY 24..25
DOMAIN = {
    "date": [f"{year}-01-01" for year in range(1993, 1998)],
    "disc": [f"0.0{d}" for d in range(2, 10)],
    "qty": [24, 25],
}

COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]}


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    """Bytes a scan must read once: every row of every column named."""
    return sum(row_counts[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def partial(c: dict, p: dict, customer: dict) -> int:
    lo = days(p["date"])
    hi = days(f"{int(p['date'][:4]) + 1}{p['date'][4:]}")
    d = round(float(p["disc"]) * 100)
    keep = ((c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
            & (c["l_discount"] >= d - 1) & (c["l_discount"] <= d + 1)
            & (c["l_quantity"] < p["qty"] * 100))
    return int(np.sum(c["l_extendedprice"][keep] * c["l_discount"][keep]))


def merge(partials: list, p: dict) -> list:
    return [[dec(sum(partials), 4)]]
