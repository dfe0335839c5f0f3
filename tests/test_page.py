"""Columnar core tests (types, Column/Page, dictionary encoding).

Mirrors the reference's spi-level unit tier (core/trino-spi tests, SURVEY §4):
drive the data model directly with numpy rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.page import Column, Dictionary, Page, concat_pages


def test_type_registry_roundtrip():
    for text, typ in [
        ("bigint", T.BIGINT), ("integer", T.INTEGER), ("double", T.DOUBLE),
        ("boolean", T.BOOLEAN), ("varchar", T.VARCHAR), ("date", T.DATE),
        ("decimal(12,2)", T.DecimalType(12, 2)),
        ("varchar(25)", T.VarcharType(25)),
    ]:
        assert T.parse_type(text) == typ


def test_coercion_lattice():
    assert T.common_super_type(T.INTEGER, T.BIGINT) == T.BIGINT
    assert T.common_super_type(T.BIGINT, T.DOUBLE) == T.DOUBLE
    assert T.common_super_type(T.UNKNOWN, T.DATE) == T.DATE
    assert T.common_super_type(
        T.DecimalType(12, 2), T.DecimalType(10, 4)) == T.DecimalType(14, 4)
    # bigint forces 19 integer digits -> would exceed short-decimal precision;
    # round 1 falls back to double rather than long decimals
    assert T.common_super_type(T.DecimalType(10, 2), T.BIGINT) == T.DOUBLE
    assert T.common_super_type(T.DecimalType(10, 2), T.INTEGER) == T.DecimalType(12, 2)
    assert T.common_super_type(
        T.TimestampType(3), T.TimestampType(6)) == T.TimestampType(6)
    assert T.common_super_type(T.BOOLEAN, T.BIGINT) is None


def test_dictionary_sorted_codes_preserve_order():
    d, codes = Dictionary.build(["cherry", "apple", "banana", "apple"])
    assert list(d.values) == ["apple", "banana", "cherry"]
    assert codes.tolist() == [2, 0, 1, 0]
    assert d.code_of("banana") == 1
    assert d.code_of("zzz") == -1
    # code order == string order
    assert (codes[1] < codes[2]) == ("apple" < "banana")


def test_page_from_numpy_and_back():
    page = Page.from_numpy(
        [np.array([1, 2, 3]), np.array([1.5, 2.5, 3.5]),
         np.array(["b", "a", "b"], dtype=object)],
        [T.BIGINT, T.DOUBLE, T.VARCHAR])
    assert page.capacity == 3 and int(page.num_rows) == 3
    rows = page.to_pylist()
    assert rows == [(1, 1.5, "b"), (2, 2.5, "a"), (3, 3.5, "b")]


def test_page_filter_compacts():
    page = Page.from_numpy([np.arange(8), np.arange(8) * 10.0],
                           [T.BIGINT, T.DOUBLE])
    mask = jnp.asarray([True, False, True, False, True, False, False, True])
    out = page.filter(mask)
    assert out.capacity == 8
    assert int(out.num_rows) == 4
    assert out.to_pylist() == [(0, 0.0), (2, 20.0), (4, 40.0), (7, 70.0)]


def test_page_filter_respects_num_rows():
    # rows beyond num_rows are padding and must not pass the filter
    page = Page.from_numpy([np.arange(8)], [T.BIGINT])
    page = Page(page.columns, jnp.asarray(5, dtype=jnp.int32))
    out = page.filter(jnp.ones(8, dtype=jnp.bool_))
    assert int(out.num_rows) == 5


def test_page_filter_under_jit():
    page = Page.from_numpy([np.arange(16), np.arange(16) * 2.0],
                           [T.BIGINT, T.DOUBLE])

    @jax.jit
    def go(p):
        return p.filter(p.column(0).values % 3 == 0)

    out = go(page)
    assert int(out.num_rows) == 6
    assert [r[0] for r in out.to_pylist()] == [0, 3, 6, 9, 12, 15]


FILTER_CAPACITIES = (1, 8, 1024, 3 << 12, 1 << 16)
FILTER_SHARES = (0.0, 0.03, 0.5, 1.0)
_POOL = Dictionary(np.array(["a", "b", "c", "d"], dtype=object))
_MAP = T.MapType(key=T.INTEGER, value=T.BIGINT)


def _filter_case(capacity, nullable, seed):
    """A page of every layout `filter` moves — BIGINT, INTEGER, DOUBLE,
    BOOLEAN, a dictionary string, a MAP (`lengths` + 2-D `values`/`aux`) —
    as NumPy arrays (the oracle's side) and as a Page."""
    rng = np.random.default_rng(seed)
    arrays = [
        rng.integers(-2**62, 2**62, capacity, dtype=np.int64),
        rng.integers(-2**31, 2**31 - 1, capacity).astype(np.int32),
        rng.standard_normal(capacity),
        rng.random(capacity) < 0.5,
        rng.integers(0, 4, capacity).astype(np.int32),
        rng.integers(0, 99, (capacity, 3)).astype(np.int32),
    ]
    types = (T.BIGINT, T.INTEGER, T.DOUBLE, T.BOOLEAN, T.VARCHAR, _MAP)
    lengths = rng.integers(0, 4, capacity).astype(np.int32)
    aux = rng.integers(-2**40, 2**40, (capacity, 3), dtype=np.int64)
    cols, host = [], []
    for i, (values, typ) in enumerate(zip(arrays, types)):
        valid = rng.random(capacity) < 0.8 if nullable and i % 2 == 0 \
            else None
        col = Column(jnp.asarray(values),
                     None if valid is None else jnp.asarray(valid), typ,
                     _POOL if typ == T.VARCHAR else None)
        parts = {"values": values, "valid": valid}
        if typ == _MAP:
            col = Column(col.values, col.valid, typ, None,
                         jnp.asarray(lengths), jnp.asarray(aux))
            parts.update(lengths=lengths, aux=aux)
        cols.append(col)
        host.append(parts)
    return cols, host


@pytest.mark.parametrize("nullable", [False, True], ids=["dense", "nullable"])
@pytest.mark.parametrize("share", FILTER_SHARES)
@pytest.mark.parametrize("capacity", FILTER_CAPACITIES)
def test_page_filter_is_numpy_boolean_indexing_on_the_kept_prefix(
        capacity, share, nullable):
    """The contract of `Page.filter`, whatever moves the rows: num_rows is
    the kept count and the prefix [0, num_rows) of every array of every
    column is NumPy's `a[mask]` — the kept rows, in input order, dtypes
    and dictionaries untouched. Under jit with a traced mask and a traced
    num_rows below the capacity; nothing is said of the lanes behind the
    prefix."""
    cols, host = _filter_case(capacity, nullable, seed=capacity + nullable)
    rows = capacity - capacity // 5          # the tail is padding
    rng = np.random.default_rng(int(share * 100) + capacity)
    mask = rng.random(capacity) < share
    live = mask & (np.arange(capacity) < rows)
    out = jax.jit(lambda p, m: p.filter(m))(
        Page(tuple(cols), jnp.asarray(rows, dtype=jnp.int32)),
        jnp.asarray(mask))
    count = int(live.sum())
    assert int(out.num_rows) == count
    assert out.capacity == capacity and out.selection is None
    assert len(out.columns) == len(cols)
    for got, src, parts in zip(out.columns, cols, host):
        assert got.type == src.type
        assert got.dictionary is src.dictionary
        for name, want in parts.items():
            have = getattr(got, name)
            if want is None:
                assert have is None, name
                continue
            assert have.shape == want.shape and have.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(have)[:count],
                                          want[live], err_msg=name)


@pytest.mark.parametrize("share", FILTER_SHARES)
def test_page_filter_eager_and_twice(share):
    """Outside jit, and a filter of a filtered page (its num_rows is then
    a device scalar under its capacity): the survivors of both masks."""
    capacity = 1000                          # not a power of two
    cols, host = _filter_case(capacity, True, seed=7)
    rng = np.random.default_rng(11)
    first, second = rng.random(capacity) < 0.7, rng.random(capacity) < share
    once = Page(tuple(cols), jnp.asarray(capacity, dtype=jnp.int32)).filter(
        jnp.asarray(first))
    # the second mask is over the once-filtered page's lanes
    twice = once.filter(jnp.asarray(second))
    kept_once = np.flatnonzero(first)
    kept = kept_once[second[:len(kept_once)]]
    assert int(twice.num_rows) == len(kept)
    for got, parts in zip(twice.columns, host):
        np.testing.assert_array_equal(
            np.asarray(got.values)[:len(kept)], parts["values"][kept])
        if parts["valid"] is not None:
            np.testing.assert_array_equal(
                np.asarray(got.valid)[:len(kept)], parts["valid"][kept])


def test_nulls_roundtrip():
    page = Page.from_numpy([np.array([1, 2, 3])], [T.BIGINT],
                           valids=[np.array([True, False, True])])
    assert page.to_pylist() == [(1,), (None,), (3,)]


def test_concat_pages():
    p1 = Page.from_numpy([np.array([1, 2])], [T.BIGINT])
    p2 = Page.from_numpy([np.array([3])], [T.BIGINT])
    out = concat_pages([p1, p2])
    assert out.to_pylist() == [(1,), (2,), (3,)]


def test_page_is_pytree():
    page = Page.from_numpy([np.arange(4)], [T.BIGINT])
    leaves = jax.tree_util.tree_leaves(page)
    assert len(leaves) == 2  # values + num_rows
    rebuilt = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(page), leaves)
    assert rebuilt.to_pylist() == page.to_pylist()
