"""Device-side TPC-H column generation.

Reference parity: plugin/trino-tpch streams rows from io.airlift.tpch on
worker CPUs. Here the chip's host has few cores and every column would
cross PCIe, so host hashing + column transfer dominated SF100 scans
(round-4 measurement: q9 SF100 wall was mostly datagen). The fix is
TPU-first:
`tpch_gen.column_stream` / `code_stream` are array-module agnostic, so the
SAME hash-stream expressions jit onto the device — generation becomes a
few fused elementwise kernels per chunk, bit-identical to the host path
by construction (one shared code body), verified by
tests/test_connector.py::test_device_gen_matches_host.

Only lineitem's order-index map (8B/row) is uploaded per chunk — the
seekable line-count index stays host-side — cutting host->device traffic
~7x for a q9-style scan and eliminating host hashing entirely.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.connector import tpch_gen as G
from trino_tpu.exec.jit_cache import named

_DEV_TABLES = {"supplier", "customer", "part", "partsupp", "orders",
               "lineitem"}
# rowmap-derived: generated host-side (cheap repeat, no hashing)
_HOST_ONLY = {("lineitem", "l_linenumber")}
_NEEDS_OIDX = {("lineitem", c) for c in
               ("l_orderkey", "l_shipdate", "l_commitdate",
                "l_receiptdate", "l_returnflag", "l_linestatus")}


def supported(table: str, column: str) -> bool:
    """Device generation covers every numeric + pooled column of the big
    tables; formatted (per-row unique) strings and the tiny fixed tables
    stay on the host path."""
    if table not in _DEV_TABLES:
        return False
    if (table, column) in _HOST_ONLY:
        return False
    kind = G.string_kind(table, column)
    if kind == "formatted":
        return False
    return True


_JIT_CACHE: Dict[tuple, object] = {}


def _chunk_fn(table: str, column: str, sf: float, cap: int,
              needs_oidx: bool):
    key = (table, column, round(sf * 1000), cap, needs_oidx)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    pooled = G.string_kind(table, column) == "pooled"
    lut = None
    if pooled:
        lut = jnp.asarray(G._pool_for(table, column, sf).lut)

    def body(start, oidx):
        idx = start + jax.lax.iota(jnp.uint64, cap)
        if pooled:
            raw = G.code_stream(table, sf, column, idx, oidx)
            return jnp.take(lut, raw, mode="clip").astype(jnp.int32)
        return G.column_stream(table, sf, column, idx, oidx)

    tag = ("tpch-generate-pooled" if pooled else "tpch-generate",)
    if needs_oidx:
        fn = jax.jit(named(body, tag))
    else:
        f0 = jax.jit(named(lambda start: body(start, None), tag))
        fn = lambda start, oidx: f0(start)   # noqa: E731
    _JIT_CACHE[key] = fn
    return fn


# small LRU of per-chunk device order-index arrays: the columns of one
# scan chunk are staged consecutively, so a handful of entries gives full
# reuse of one reconstruction
_OIDX_CACHE: "collections.OrderedDict[tuple, jnp.ndarray]" = \
    collections.OrderedDict()
_OIDX_CACHE_MAX = 4


def _oidx_fn(sf: float, cap: int):
    """Jitted on-device order-index reconstruction for lineitem chunks.

    dbgen's defining seekability trick re-thought for the chip: the
    per-order line count is ITSELF a hash stream (1 + mix64(o) % 7), so a
    chunk's order map needs no host data at all beyond two scalars — the
    first covering order and its absolute start row. The device generates
    the local line counts, cumsums them into order-start positions, and
    scatter-marks each start; an inclusive cumsum of the marks is then
    exactly `oidx - o_first` per row. ~45MB/chunk of host upload gone."""
    key = ("oidx", round(sf * 1000), cap)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn

    def f(o_first, s0, start):
        # at most `cap` orders cover `cap` rows (every order has >=1 line)
        o_ids = (o_first + jax.lax.iota(jnp.int64, cap)).astype(jnp.uint64)
        lines = (1 + (G._u64("lineitem", "l_count", sf, o_ids)
                      % np.uint64(7))).astype(jnp.int64)
        # absolute start row of order o_first+j+1, relative to the chunk
        rel = (s0 + jnp.cumsum(lines)) - start
        ind = jnp.zeros(cap, jnp.int32).at[rel].add(1, mode="drop")
        return o_first + jnp.cumsum(ind).astype(jnp.int64)

    fn = jax.jit(named(f, ("tpch-generate-oidx",)))
    _JIT_CACHE[key] = fn
    return fn


def _device_oidx(sf: float, start: int, end: int, cap: int) -> jnp.ndarray:
    # the chip the caller generates on is part of the key: a mesh scan
    # makes shard i on chip i, and chip 0's array is not chip 2's
    key = (round(sf * 1000), start, end, cap, jax.config.jax_default_device)
    got = _OIDX_CACHE.get(key)
    if got is not None:
        _OIDX_CACHE.move_to_end(key)
        return got
    # host side: two scalars from the cached line index (bisect, O(log n))
    _, starts = G._line_index(sf)
    o_first = int(np.searchsorted(starts, start, side="right")) - 1
    s0 = int(starts[o_first])
    dev = _oidx_fn(sf, cap)(jnp.int64(o_first), jnp.int64(s0),
                            jnp.int64(start))
    while len(_OIDX_CACHE) >= _OIDX_CACHE_MAX:
        _OIDX_CACHE.popitem(last=False)
    _OIDX_CACHE[key] = dev
    return dev


def generate(table: str, sf: float, column: str, start: int, end: int,
             cap: int) -> jnp.ndarray:
    """Device array [cap] for rows [start, end); tail rows are garbage
    padding (a Page's num_rows delimits live rows)."""
    needs_oidx = (table, column) in _NEEDS_OIDX
    fn = _chunk_fn(table, column, sf, cap, needs_oidx)
    oidx = _device_oidx(sf, start, end, cap) if needs_oidx else None
    return fn(jnp.uint64(start), oidx)
