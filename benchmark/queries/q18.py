"""TPC-H Q18 (large volume customer): a GROUP BY of every order over all
of lineitem, a HAVING that keeps about a hundred of them, an IN over that,
two joins, and the best hundred by price."""

import numpy as np

from tpch_columns_q18_q4 import c_name, of_chunk
from wire import date_text, dec

SQL = """
SELECT c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
       sum(l_quantity)
FROM customer, orders, lineitem
WHERE o_orderkey IN (SELECT l_orderkey FROM lineitem
                     GROUP BY l_orderkey HAVING sum(l_quantity) > {quantity})
  AND c_custkey = o_custkey AND o_orderkey = l_orderkey
GROUP BY c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
ORDER BY o_totalprice DESC, o_orderdate LIMIT 100
"""

PREPARED = SQL.replace("{quantity}", "?")

USING = "{quantity}"

# TPC-H clause 2.4.18.3: QUANTITY is 312..315
DOMAIN = {"quantity": [312, 313, 314, 315]}

COLUMNS = {
    "customer": ["c_custkey", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"],
    "lineitem": ["l_orderkey", "l_quantity"],
}

LIMIT = 100


def needed_bytes(row_counts: dict, column_bytes: dict) -> int:
    return sum(row_counts[t] * column_bytes[c]
               for t, cols in COLUMNS.items() for c in cols)


def _order(g):
    """The specification's ORDER BY has no key after these two."""
    return (-g[0], g[1])


def partial(c: dict, p: dict, customer: dict) -> list:
    """This range's best hundred: every lineitem of an order lies in the
    order's own chunk, so the inner GROUP BY, the HAVING, the joins and
    the outer sum are whole here. One more than the LIMIT is kept, so that
    `merge` sees a tie at the cut."""
    price = of_chunk(c, customer)["o_totalprice"]
    local = c["l_orderkey"] - c["o_orderkey"][0]
    quantity = np.bincount(local, weights=c["l_quantity"],
                           minlength=len(c["o_orderkey"])).astype(np.int64)
    large = np.flatnonzero(quantity > p["quantity"] * 100)
    large = large[np.isin(c["o_custkey"][large], customer["c_custkey"])]
    top = large[np.lexsort((c["o_orderdate"][large], -price[large]))]
    return [(int(price[i]), int(c["o_orderdate"][i]),
             int(c["o_orderkey"][i]), int(c["o_custkey"][i]),
             int(quantity[i])) for i in top[:LIMIT + 1]]


def merge(partials: list, p: dict) -> list:
    best = sorted((g for part in partials for g in part),
                  key=_order)[:LIMIT + 1]
    keys = [_order(g) for g in best]
    assert len(set(keys)) == len(keys), \
        "q18: two of the best share (o_totalprice, o_orderdate): the " \
        "specification's ORDER BY leaves their order open"
    return [[c_name([custkey])[0], custkey, okey, date_text(odate),
             dec(price, 2), dec(quantity, 2)]
            for price, odate, okey, custkey, quantity in best[:LIMIT]]
