"""TPC-H Q3 (shipping priority): customer x orders x lineitem, a
high-cardinality GROUP BY and a top ten."""

import numpy as np

from tpch_columns import SEGMENTS
from wire import date_text, days, dec

SQL = """
SELECT l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue,
       o_orderdate, o_shippriority
FROM customer, orders, lineitem
WHERE c_mktsegment = '{segment}' AND c_custkey = o_custkey
  AND l_orderkey = o_orderkey AND o_orderdate < DATE '{date}'
  AND l_shipdate > DATE '{date}'
GROUP BY l_orderkey, o_orderdate, o_shippriority
ORDER BY revenue DESC, o_orderdate LIMIT 10
"""

# the SEGMENT stays a literal of the prepared text: the engine keeps
# string literals static (expr/hoist.py), so each value keys its own
# kernels, and a mix draws one per run
PREPARED = SQL.replace("DATE '{date}'", "?")

USING = "DATE '{date}', DATE '{date}'"

# TPC-H clause 2.4.3.3: SEGMENT is one of five, DATE is 1995-03-01..31
DOMAIN = {
    "segment": list(SEGMENTS),
    "date": [f"1995-03-{day:02d}" for day in range(1, 32)],
}

COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"],
}


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    return sum(row_counts[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def partial(c: dict, p: dict, customer: dict) -> list:
    """The ten best groups of this range of orders: every lineitem of an
    order lies in the order's own chunk, so a group is whole here."""
    cut = days(p["date"])
    in_segment = customer["c_custkey"][
        customer["c_mktsegment"] == SEGMENTS.index(p["segment"])]
    omask = (c["o_orderdate"] < cut) & np.isin(c["o_custkey"], in_segment)
    lmask = (c["l_shipdate"] > cut) \
        & np.isin(c["l_orderkey"], c["o_orderkey"][omask])
    keys, group = np.unique(c["l_orderkey"][lmask], return_inverse=True)
    revenue = np.zeros(len(keys), dtype=np.int64)
    np.add.at(revenue, group, c["l_extendedprice"][lmask]
              * (100 - c["l_discount"][lmask]))
    at = np.searchsorted(c["o_orderkey"], keys)
    odate, prio = c["o_orderdate"][at], c["o_shippriority"][at]
    top = np.lexsort((keys, odate, -revenue))[:10]
    return [(int(revenue[i]), int(odate[i]), int(keys[i]), int(prio[i]))
            for i in top]


def merge(partials: list, p: dict) -> list:
    best = sorted((g for part in partials for g in part),
                  key=lambda g: (-g[0], g[1], g[2]))[:10]
    return [[key, dec(revenue, 4), date_text(odate), prio]
            for revenue, odate, key, prio in best]
