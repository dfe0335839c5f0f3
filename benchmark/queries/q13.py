"""TPC-H Q13 (customer distribution): customers LEFT OUTER JOIN their
orders, the orders whose comment is LIKE '%WORD1%WORD2%' left out by the
ON clause, counted by customer and then by that count — the customers
without a kept order are the `c_count = 0` row."""

import numpy as np

from tpch_columns_q13 import (WORD1, WORD2, excluded, o_comment_raw,
                              orders_per_customer, scale_factor)

SQL = """
SELECT c_count, count(*) AS custdist
FROM (SELECT c_custkey, count(o_orderkey) AS c_count
      FROM customer LEFT OUTER JOIN orders
        ON c_custkey = o_custkey
       AND o_comment NOT LIKE '%{word1}%{word2}%'
      GROUP BY c_custkey) AS c_orders
GROUP BY c_count ORDER BY custdist DESC, c_count DESC
"""

# the pattern stays a literal of the prepared text (a string parameter
# bakes in as a literal, expr/hoist.py): the LIKE table over o_comment's
# dictionary reaches the kernel as an operand either way
PREPARED = SQL

USING = ""

# TPC-H clause 2.4.13.3: WORD1 and WORD2, four words each
DOMAIN = {"word1": list(WORD1), "word2": list(WORD2)}

COLUMNS = {
    "customer": ["c_custkey"],
    "orders": ["o_orderkey", "o_custkey", "o_comment"],
}


def table_rows(row_counts: dict) -> dict:
    return dict(row_counts)


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    rows = table_rows(row_counts)
    return sum(rows[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def partial(c: dict, p: dict, customer: dict) -> list:
    """[first order, last order, customers, customer keys of the orders
    the pattern excludes]: `o_custkey` has no locality, so a histogram a
    chunk would be the whole customer table; the few excluded orders
    travel, `merge` counts the rest from the stream."""
    customers = len(customer["c_custkey"])
    o_first = int(c["o_orderkey"][0]) - 1
    o_last = o_first + len(c["o_orderkey"])
    raw = o_comment_raw(scale_factor(customers), o_first, o_last)
    out = excluded(p["word1"], p["word2"])[raw]
    return [o_first, o_last, customers, c["o_custkey"][out].tolist()]


def merge(partials: list, p: dict) -> list:
    customers = partials[0][2]
    sf = scale_factor(customers)
    ranges = sorted([lo, hi] for lo, hi, _, _ in partials)
    counts = orders_per_customer(sf, ranges).copy()
    left_out = np.fromiter((k for part in partials for k in part[3]),
                           dtype=np.int64)
    counts -= np.bincount(left_out, minlength=len(counts))
    dist = np.bincount(counts[1:])
    rows = sorted(((int(n), int(dist[n])) for n in np.flatnonzero(dist)),
                  key=lambda r: (-r[1], -r[0]))
    return [[n, custdist] for n, custdist in rows]


def kept_orders(orders: int, p: dict) -> float:
    """Orders the request's pair keeps, by the pool's share (every phrase
    is as likely as another)."""
    out = excluded(p["word1"], p["word2"])
    return orders * (1.0 - out.sum() / len(out))
