"""TPC-H Q1 (pricing summary report): scan, filter, low-cardinality
GROUP BY with eight aggregates."""

import numpy as np

from tpch_columns import LINESTATUSES, RETURNFLAGS
from wire import avg, days, dec

SQL = """
SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty, avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc, count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '{delta}' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

PREPARED = SQL.replace("INTERVAL '{delta}' DAY", "?")

USING = "INTERVAL '{delta}' DAY"

# TPC-H clause 2.4.1.3: DELTA is 60..120
DOMAIN = {"delta": list(range(60, 121))}

COLUMNS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                        "l_quantity", "l_extendedprice", "l_discount",
                        "l_tax"]}


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    return sum(row_counts[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def partial(c: dict, p: dict, customer: dict) -> list:
    """[[group, count, sum qty, price, disc_price, charge, disc], ...]
    with group = flag code * 2 + status code"""
    keep = c["l_shipdate"] <= days("1998-12-01") - p["delta"]
    group = (c["l_returnflag"][keep].astype(np.int64) * len(LINESTATUSES)
             + c["l_linestatus"][keep])
    qty, price = c["l_quantity"][keep], c["l_extendedprice"][keep]
    disc, tax = c["l_discount"][keep], c["l_tax"][keep]
    disc_price = price * (100 - disc)
    out = []
    for g in np.unique(group):
        m = group == g
        out.append([int(g), int(m.sum())] + [int(x[m].sum()) for x in (
            qty, price, disc_price, disc_price * (100 + tax), disc)])
    return out


def merge(partials: list, p: dict) -> list:
    total = {}
    for part in partials:
        for g, *sums in part:
            have = total.get(g, [0] * len(sums))
            total[g] = [a + b for a, b in zip(have, sums)]
    rows = []
    for g in sorted(total):
        n, qty, price, disc_price, charge, disc = total[g]
        flag, status = divmod(g, len(LINESTATUSES))
        rows.append([RETURNFLAGS[flag], LINESTATUSES[status],
                     dec(qty, 2), dec(price, 2), dec(disc_price, 4),
                     dec(charge, 6), dec(avg(qty, n), 2),
                     dec(avg(price, n), 2), dec(avg(disc, n), 2), n])
    return rows
