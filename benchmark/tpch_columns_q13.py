"""The column Q13 reads beyond `tpch_columns.py`'s fourteen: `o_comment`.

A standalone NumPy copy of the stream in
`trino_tpu/connector/tpch_gen.py` — the 52 words, the fixed pool of 2 048
five-word phrases from its own seeded generator (cut at the column's 79
characters), and the raw pool index of each order row — so that the
reference still imports nothing of the program. It is not dbgen's grammar
text (the configuration's `assumed`): `'%WORD1%WORD2%'` excludes a few of
the 2 048 phrases, and with them 0.05-0.54 % of the orders.

Q13's reference also needs every customer's number of orders whatever the
words are; `orders_per_customer` makes that histogram once a set of order
ranges from the `o_custkey` stream alone (15 M hashes at SF10) and keeps
the newest.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

import tpch_columns as C

WORDS = (
    "about", "above", "according", "accounts", "after", "against", "along",
    "among", "around", "asymptotes", "attainments", "bold", "braids",
    "carefully", "courts", "deposits", "dependencies", "depths", "dolphins",
    "dugouts", "engage", "escapades", "even", "excuses", "express", "final",
    "fluffily", "foxes", "furiously", "gifts", "grouches", "ideas",
    "instructions", "ironic", "packages", "pending", "pinto", "platelets",
    "quickly", "quietly", "regular", "requests", "sauternes", "sentiments",
    "silent", "sleepy", "slyly", "special", "theodolites", "unusual",
    "waters", "wishes")
# TPC-H clause 2.4.13.3
WORD1 = ("special", "pending", "unusual", "express")
WORD2 = ("packages", "requests", "accounts", "deposits")
POOL_SIZE = 2048
POOL_SEED = 12345
O_COMMENT_LEN = 79

_POOL = []      # the phrases, by raw index
_LAST = {}      # the newest histogram: its ranges, its counts


def pool() -> list:
    """The 2 048 phrases by RAW index (the engine's dictionary is these,
    sorted and distinct)."""
    if not _POOL:
        picks = np.random.default_rng(POOL_SEED).integers(
            0, len(WORDS), size=(POOL_SIZE, 5))
        _POOL.extend(" ".join(WORDS[i] for i in row)[:O_COMMENT_LEN]
                     for row in picks)
    return _POOL


@functools.lru_cache(maxsize=None)
def excluded(word1: str, word2: str) -> np.ndarray:
    """raw index -> whether the phrase is LIKE '%word1%word2%' (kept a
    pair: every chunk's partial asks again; read, never written)."""
    def like(s: str) -> bool:
        at = s.find(word1)
        return at >= 0 and s.find(word2, at + len(word1)) >= 0
    return np.array([like(s) for s in pool()])


def o_comment_raw(sf: float, o_first: int, o_last: int) -> np.ndarray:
    """The raw pool index of orders [o_first, o_last)'s `o_comment`."""
    with np.errstate(over="ignore"):
        idx = np.arange(o_first, o_last, dtype=np.uint64)
        return (C._u64("orders", "o_comment", sf, idx)
                % np.uint64(POOL_SIZE)).astype(np.int64)


def o_comment(sf: float, o_first: int, o_last: int) -> list:
    phrases = pool()
    return [phrases[i] for i in o_comment_raw(sf, o_first, o_last)]


def o_custkey(sf: float, o_first: int, o_last: int) -> np.ndarray:
    """`tpch_columns.orders_chunk`'s `o_custkey`, alone."""
    with np.errstate(over="ignore"):
        oidx = np.arange(o_first, o_last, dtype=np.uint64)
        ncust = C.customer_count(sf)
        ck = C._ui("orders", "o_custkey", sf, oidx, 1, max(ncust, 2))
        return np.where(ck % 3 == 0,
                        np.maximum((ck + 1) % (ncust + 1), 1), ck)


def scale_factor(customers: int) -> float:
    """The scale factor of a customer table of that many rows."""
    for sf in C.SCALE_FACTORS.values():
        if C.customer_count(sf) == customers:
            return sf
    return customers / 150_000


def orders_per_customer(sf: float, ranges: list) -> np.ndarray:
    """customer key -> its orders within the order ranges
    [[o_first, o_last), ...], no order left out. Slot 0 is unused."""
    key = (sf, tuple(map(tuple, ranges)))
    if _LAST.get("key") != key:
        counts = np.zeros(C.customer_count(sf) + 1, dtype=np.int64)
        step = 2_000_000
        for lo, hi in ranges:
            for first in range(lo, hi, step):
                counts += np.bincount(
                    o_custkey(sf, first, min(first + step, hi)),
                    minlength=len(counts))
        _LAST.update(key=key, counts=counts)
    return _LAST["counts"]


def fingerprint(sf: float) -> str:
    """crc32 over the first 4096 orders' raw comment indexes and the
    pool's phrases."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    crc = zlib.crc32(np.ascontiguousarray(
        o_comment_raw(sf, 0, n)).tobytes())
    return f"{zlib.crc32(chr(10).join(pool()).encode(), crc):08x}"
