"""TPC-H Q9 (product type profit measure): six tables, a join key of two
columns, a LIKE over part names whose pattern changes per request, and the
profit by nation and year."""

import numpy as np

from tpch_columns_q9 import (COLORS, NATIONS, of_chunk, p_name_words,
                             ps_supplycost, ps_suppkey, s_nationkey)
from wire import dec

SQL = """
SELECT nation, o_year, sum(amount) AS sum_profit
FROM (SELECT n_name AS nation, extract(year FROM o_orderdate) AS o_year,
             l_extendedprice * (1 - l_discount)
             - ps_supplycost * l_quantity AS amount
      FROM part, supplier, lineitem, partsupp, orders, nation
      WHERE s_suppkey = l_suppkey AND ps_suppkey = l_suppkey
        AND ps_partkey = l_partkey AND p_partkey = l_partkey
        AND o_orderkey = l_orderkey AND s_nationkey = n_nationkey
        AND p_name LIKE '%{color}%') AS profit
GROUP BY nation, o_year ORDER BY nation, o_year DESC
"""

# the pattern stays a literal of the prepared text (a string parameter
# bakes in as a literal, expr/hoist.py): the LIKE table over p_name's
# dictionary reaches the kernel as an operand either way
PREPARED = SQL

USING = ""

# TPC-H clause 2.4.9.3: COLOR is one of the 92 words of P_NAME
DOMAIN = {"color": list(COLORS)}

COLUMNS = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_quantity",
                 "l_extendedprice", "l_discount"],
    "orders": ["o_orderkey", "o_orderdate"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name"],
}

YEAR0, YEARS = 1992, 7          # o_orderdate lies in 1992..1998


def table_rows(row_counts: dict) -> dict:
    """The six tables' rows: the configuration's `rows` where it states
    them, else by clause 4.2.5's ratios to orders (a copy of the
    configuration restated at another scale knows the 14-column
    reference's three tables alone)."""
    part = row_counts["orders"] * 2 // 15
    return {"part": part, "partsupp": 4 * part,
            "supplier": max(1, part // 20), "nation": len(NATIONS),
            **row_counts}


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    rows = table_rows(row_counts)
    return sum(rows[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def matches(color: str) -> np.ndarray:
    """[first word, second word] -> whether 'first second' LIKE
    '%color%'."""
    return np.array([[color in f"{a} {b}" for b in COLORS] for a in COLORS])


def partial(c: dict, p: dict, customer: dict) -> list:
    """Profit by (nation, year) over this range of orders: every lineitem
    of an order lies in the order's own chunk; part, partsupp, supplier and
    nation are functions of the line's keys. A line joins every partsupp
    row of its (part, supplier) pair — one, where the spread formula gives
    a part four distinct suppliers."""
    sf, keys = of_chunk(c, customer)
    pk, sk = keys["l_partkey"], keys["l_suppkey"]
    keep = np.flatnonzero(matches(p["color"])[p_name_words(pk, sf)])
    pk, sk = pk[keep], sk[keep]
    revenue = c["l_extendedprice"][keep] * (100 - c["l_discount"][keep])
    quantity = c["l_quantity"][keep]
    odate = c["o_orderdate"][(c["l_orderkey"] - c["o_orderkey"][0])[keep]]
    year = odate.astype("datetime64[D]").astype("datetime64[Y]") \
        .astype(np.int64) + 1970
    group = s_nationkey(sk, sf) * YEARS + (year - YEAR0)
    profit = np.zeros(len(NATIONS) * YEARS, dtype=np.int64)
    lines = np.zeros(len(NATIONS) * YEARS, dtype=np.int64)
    for i in range(4):
        hit = ps_suppkey(pk, i, sf) == sk
        amount = revenue[hit] - ps_supplycost(pk[hit], i, sf) * quantity[hit]
        np.add.at(profit, group[hit], amount)
        np.add.at(lines, group[hit], 1)
    return [[int(g), int(profit[g]), int(lines[g])]
            for g in np.flatnonzero(lines)]


def merge(partials: list, p: dict) -> list:
    profit = {}
    for part in partials:
        for g, amount, _ in part:
            profit[g] = profit.get(g, 0) + amount
    rows = sorted(((NATIONS[g // YEARS], YEAR0 + g % YEARS, amount)
                   for g, amount in profit.items()),
                  key=lambda r: (r[0], -r[1]))
    return [[nation, year, dec(amount, 4)] for nation, year, amount in rows]
