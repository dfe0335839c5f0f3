"""The columns Q9 reads beyond `tpch_columns.py`'s fourteen.

Standalone NumPy copies of the streams in `trino_tpu/connector/tpch_gen.py`
that Q9 needs — lineitem's `l_partkey` and `l_suppkey` (the choice `l_i4`
among the part's four suppliers, through the specification's spread
formula), partsupp's `ps_suppkey` and `ps_supplycost`, supplier's
`s_nationkey`, part's `p_name` (two of the 92 colour words, by their two
raw codes) and the 25 nation names — so that the reference still imports
nothing of the program. Part, partsupp, supplier and nation are functions
of their keys: Q9's reference evaluates them at a chunk's `l_partkey` and
`l_suppkey` and never holds the tables.

`reference._worker` hands a shape's `partial` the 14-column chunk and the
customer table and nothing else — no scale factor, no row index — so
`of_chunk` takes the scale from the customer table's length and the chunk's
first lineitem row from the count of lines before its first order, as
`tpch_columns_q18_q4.py` does, and keeps what it made for the newest chunk.
"""

from __future__ import annotations

import zlib

import numpy as np

import tpch_columns as C

COLORS = (
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished",
    "chartreuse", "chiffon", "chocolate", "coral", "cornflower", "cornsilk",
    "cream", "cyan", "dark", "deep", "dim", "dodger", "drab", "firebrick",
    "floral", "forest", "frosted", "gainsboro", "ghost", "goldenrod", "green",
    "grey", "honeydew", "hot", "indian", "ivory", "khaki", "lace", "lavender",
    "lawn", "lemon", "light", "lime", "linen", "magenta", "maroon", "medium",
    "metallic", "midnight", "mint", "misty", "moccasin", "navajo", "navy",
    "olive", "orange", "orchid", "pale", "papaya", "peach", "peru", "pink",
    "plum", "powder", "puff", "purple", "red", "rose", "rosy", "royal",
    "saddle", "salmon", "sandy", "seashell", "sienna", "sky", "slate", "smoke",
    "snow", "spring", "steel", "tan", "thistle", "tomato", "turquoise",
    "violet", "wheat", "white", "yellow")
# n_name by n_nationkey (clause 4.2.3)
NATIONS = (
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
    "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
    "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES")

_LAST = {}      # the newest chunk: its key, its columns, where it ended


def part_count(sf: float) -> int:
    return max(1, int(200_000 * sf))


def supplier_count(sf: float) -> int:
    return max(1, int(10_000 * sf))


def row_counts(sf: float) -> dict:
    return {**C.row_counts(sf), "partsupp": 4 * part_count(sf),
            "part": part_count(sf), "supplier": supplier_count(sf),
            "nation": len(NATIONS)}


def scale_factor(customer: dict) -> float:
    """The scale factor of a whole customer table (150 000 x SF rows; at
    least one row at any scale)."""
    n = len(customer["c_custkey"])
    for sf in C.SCALE_FACTORS.values():
        if C.customer_count(sf) == n:
            return sf
    return n / 150_000


def ps_suppkey(partkey, i, sf: float):
    """The i-th (0..3) supplier of a part: the specification's spread."""
    s = supplier_count(sf)
    return (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1


def ps_supplycost(partkey, i, sf: float):
    """Cents, of partsupp's row 4 (partkey - 1) + i."""
    with np.errstate(over="ignore"):
        row = (4 * (np.asarray(partkey, dtype=np.int64) - 1)
               + i).astype(np.uint64)
        return C._ui("partsupp", "ps_supplycost", sf, row, 100, 100000)


def s_nationkey(suppkey, sf: float):
    with np.errstate(over="ignore"):
        row = (np.asarray(suppkey, dtype=np.int64) - 1).astype(np.uint64)
        return C._ui("supplier", "s_nationkey", sf, row, 0, 24)


def p_name_words(partkey, sf: float):
    """(first word, second word) of `p_name`, as indexes into COLORS."""
    with np.errstate(over="ignore"):
        row = (np.asarray(partkey, dtype=np.int64) - 1).astype(np.uint64)
        last = len(COLORS) - 1
        return (C._ui("part", "p_name1", sf, row, 0, last),
                C._ui("part", "p_name2", sf, row, 0, last))


def p_name(partkey, sf: float) -> list:
    first, second = p_name_words(partkey, sf)
    return [f"{COLORS[a]} {COLORS[b]}" for a, b in zip(first, second)]


def lineitem_keys(sf: float, row0: int, n: int) -> dict:
    """`l_partkey` and `l_suppkey` of lineitem rows [row0, row0 + n)."""
    with np.errstate(over="ignore"):
        idx = np.arange(row0, row0 + n, dtype=np.uint64)
        pk = C._ui("lineitem", "l_partkey", sf, idx, 1, part_count(sf))
        i4 = C._ui("lineitem", "l_i4", sf, idx, 0, 3)
        return {"l_partkey": pk, "l_suppkey": ps_suppkey(pk, i4, sf)}


def of_chunk(chunk: dict, customer: dict) -> tuple:
    """(scale factor, {l_partkey, l_suppkey}) for the chunk
    `reference._worker` handed over."""
    sf = scale_factor(customer)
    o_first = int(chunk["o_orderkey"][0]) - 1
    o_last = o_first + len(chunk["o_orderkey"])
    key = (sf, o_first, o_last)
    if _LAST.get("key") != key:
        # a worker's chunks follow one another: the next one starts at
        # the row where the last one ended
        row0 = _LAST["row_end"] if _LAST.get("ends") == (sf, o_first) \
            else C.lineitem_rows_before(sf, o_first)
        n = len(chunk["l_orderkey"])
        _LAST.update(key=key, ends=(sf, o_last), row_end=row0 + n,
                     columns=lineitem_keys(sf, row0, n))
    return sf, _LAST["columns"]


def fingerprint(sf: float) -> str:
    """crc32 over the first 4096 orders' lineitems' two keys, the first
    4096 parts' two words and four supply costs and suppliers, the first
    4096 suppliers' nations, and the nation names."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    rows = C.lineitem_rows_before(sf, n)
    cols = dict(lineitem_keys(sf, 0, rows))
    pk = np.arange(1, min(C.FINGERPRINT_ORDERS, part_count(sf)) + 1)
    cols["p_name1"], cols["p_name2"] = p_name_words(pk, sf)
    for i in range(4):
        cols[f"ps_suppkey{i}"] = ps_suppkey(pk, i, sf)
        cols[f"ps_supplycost{i}"] = ps_supplycost(pk, i, sf)
    cols["s_nationkey"] = s_nationkey(np.arange(
        1, min(C.FINGERPRINT_ORDERS, supplier_count(sf)) + 1), sf)
    crc = 0
    for name in sorted(cols):
        crc = zlib.crc32(np.ascontiguousarray(
            cols[name].astype(np.int64)).tobytes(), crc)
    crc = zlib.crc32("\n".join(COLORS + NATIONS).encode(), crc)
    return f"{crc:08x}"
