"""Multi-key sorting as passes of ONE small sort.

`lax.sort` with several operands or 64-bit keys is cheap to run on the TPU
and ruinous to compile: the compiler builds a sort network per operand and
emulates every 64-bit compare, and the cost is paid again in every program
that holds such a sort (PR 23, v5e compiler, 32 768 rows: `(u64, bool,
i32)` with two keys 87 s; `(u32, u32, i32)` with two keys 62 s; one
`(u32, i32)` pair 29 s — and five passes of that same pair 32 s, because
identical sorts in one program are built once).

So the sort-based kernels order rows the way a radix sort does: every key
is cut into 32-bit digits whose unsigned order is the key's order, and the
rows are ordered by stable passes of `sort((u32 digit, i32 row))`, least
significant digit first. The result is the stable lexicographic order of
the keys — what `lax.sort(keys + [iota], num_keys=len(keys) + 1)` gives —
and the payload is gathered through the permutation instead of riding
through the sort.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from trino_tpu.page import shared_scope

_U32 = jnp.uint32
_U64 = jnp.uint64


def _split64(bits: jnp.ndarray) -> List[jnp.ndarray]:
    return [(bits >> _U64(32)).astype(_U32),
            (bits & _U64(0xFFFFFFFF)).astype(_U32)]


def order_digits(x: jnp.ndarray) -> List[jnp.ndarray]:
    """uint32 digits of `x`, most significant first, whose lexicographic
    unsigned order is `lax.sort`'s order on `x` (floats: -0 == +0, NaN
    last)."""
    dt = x.dtype
    if dt == jnp.bool_ or jnp.issubdtype(dt, jnp.unsignedinteger):
        if dt.itemsize == 8:
            return _split64(x)
        return [x.astype(_U32)]
    if jnp.issubdtype(dt, jnp.signedinteger):
        if dt.itemsize == 8:
            return _split64(
                jax.lax.bitcast_convert_type(x, _U64) ^ _U64(1 << 63))
        return [jax.lax.bitcast_convert_type(x.astype(jnp.int32), _U32)
                ^ _U32(1 << 31)]
    if jnp.issubdtype(dt, jnp.floating):
        wide = dt.itemsize == 8
        x = x if wide else x.astype(jnp.float32)
        x = jnp.where(x == 0, jnp.zeros((), x.dtype), x)
        x = jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)
        ubits, sign = (_U64, _U64(1 << 63)) if wide else (_U32, _U32(1 << 31))
        bits = jax.lax.bitcast_convert_type(x, ubits)
        key = jnp.where((bits & sign) != 0, ~bits, bits | sign)
        return _split64(key) if wide else [key]
    raise TypeError(f"no sort digits for dtype {dt}")


def _all_digits(keys: Sequence[jnp.ndarray]) -> List[jnp.ndarray]:
    """Digits of every key in significance order; a run of boolean keys
    (liveness and null flags) packs into one digit, one pass for all."""
    out: List[jnp.ndarray] = []
    flags: List[jnp.ndarray] = []

    def flush():
        for at in range(0, len(flags), 32):
            word = jnp.zeros(flags[0].shape, dtype=_U32)
            for f in flags[at:at + 32]:
                word = (word << 1) | f.astype(_U32)
            out.append(word)
        flags.clear()

    for k in keys:
        if k.dtype == jnp.bool_:
            flags.append(k)
        else:
            flush()
            out.extend(order_digits(k))
    flush()
    return out


def stable_argsort(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """int32 permutation putting rows in ascending lexicographic order of
    `keys` (first key most significant), equal rows in input order."""
    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    digits = _all_digits(keys)
    # every pass under one name; the family is the calling operator's
    with shared_scope("radix_pass", "sort"):
        for i, digit in enumerate(reversed(digits)):
            if i:
                digit = jnp.take(digit, perm, mode="clip")
            perm = jax.lax.sort([digit, perm], num_keys=1,
                                is_stable=True)[1]
    return perm


def in_order(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Scalar: no row sorts before the row ahead of it, so
    `stable_argsort(keys)` is the identity. Read off the same digits the
    passes sort by — the sort's order, NaN and -0 included — in one
    elementwise pass and a reduce."""
    out_of_order = tied = None
    for digit in _all_digits(keys):
        ahead = jnp.concatenate([digit[:1], digit[:-1]])
        gt, eq = ahead > digit, ahead == digit
        out_of_order = gt if tied is None else out_of_order | (tied & gt)
        tied = eq if tied is None else tied & eq
    return ~jnp.any(out_of_order)


def sorted_by(keys: Sequence[jnp.ndarray], lanes):
    """-> (`lanes` — any tree of arrays a lane a row — gathered through
    the permutation that sorts `keys`, that permutation)."""
    perm = stable_argsort(keys)
    with shared_scope("radix_gather", "sort"):
        return jax.tree_util.tree_map(
            lambda a: jnp.take(a, perm, axis=0, mode="clip"), lanes), perm


def sort_by_keys(keys: Sequence[jnp.ndarray]):
    """-> (keys in sorted order, the permutation that sorted them)."""
    return sorted_by(keys, list(keys))
