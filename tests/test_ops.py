"""Operator tests — driven RowPagesBuilder-style (SURVEY §4 unit tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.expr import Call, InputRef, Literal
from trino_tpu.ops import (
    AggSpec, JoinType, SortKey, Step, filter_project, hash_aggregate,
    hash_join, limit, order_by, top_n)
from trino_tpu.page import Page


def page_of(*cols):
    arrays, typs, valids = [], [], []
    for c in cols:
        if len(c) == 3:
            a, t, v = c
        else:
            (a, t), v = c, None
        arrays.append(np.asarray(a) if not isinstance(a, np.ndarray) else a)
        typs.append(t)
        valids.append(None if v is None else np.asarray(v, dtype=bool))
    return Page.from_numpy(arrays, typs, valids=valids)


# ---------------------------------------------------------------------------
# aggregation

def test_global_aggregation():
    page = page_of(([1, 2, 3, 4], T.BIGINT), ([1.0, 2.0, 3.0, 4.0], T.DOUBLE))
    op = hash_aggregate([], [
        AggSpec("sum", 0, T.BIGINT), AggSpec("count", None, None),
        AggSpec("avg", 1, T.DOUBLE), AggSpec("min", 0, T.BIGINT),
        AggSpec("max", 1, T.DOUBLE)])
    out = jax.jit(op)(page)
    assert out.to_pylist() == [(10, 4, 2.5, 1, 4.0)]


def test_group_by_aggregation():
    page = page_of(([2, 1, 2, 1, 3], T.BIGINT), ([10.0, 20.0, 30.0, 40.0, 50.0], T.DOUBLE))
    op = hash_aggregate([0], [AggSpec("sum", 1, T.DOUBLE),
                              AggSpec("count", None, None)])
    out = jax.jit(op)(page)
    rows = sorted(out.to_pylist())
    assert rows == [(1, 60.0, 2), (2, 40.0, 2), (3, 50.0, 1)]


def test_group_by_null_key_and_null_inputs():
    page = page_of(([1, 1, 2, 2], T.BIGINT, [1, 0, 1, 0]),
                   ([5.0, 6.0, 7.0, 8.0], T.DOUBLE, [1, 1, 0, 1]))
    op = hash_aggregate([0], [AggSpec("sum", 1, T.DOUBLE),
                              AggSpec("count", 1, T.DOUBLE)])
    out = jax.jit(op)(page)
    rows = out.to_pylist()
    # nulls group together (one NULL group from rows 1 & 3)
    by_key = {r[0]: r[1:] for r in rows}
    assert by_key[1] == (5.0, 1)
    assert by_key[2] == (None, 0)  # sum of all-null group is NULL, count 0
    assert by_key[None] == (14.0, 2)
    assert len(rows) == 3


def test_group_by_respects_num_rows():
    page = page_of(([1, 2, 1, 2, 9, 9], T.BIGINT), ([1, 1, 1, 1, 1, 1], T.BIGINT))
    page = Page(page.columns, jnp.asarray(4, jnp.int32))  # last two rows dead
    op = hash_aggregate([0], [AggSpec("sum", 1, T.BIGINT)])
    out = jax.jit(op)(page)
    assert sorted(out.to_pylist()) == [(1, 2), (2, 2)]


def test_partial_then_final_aggregation():
    page = page_of(([1, 2, 1, 2], T.BIGINT), ([1.0, 2.0, 3.0, 4.0], T.DOUBLE))
    partial = hash_aggregate([0], [AggSpec("avg", 1, T.DOUBLE)],
                             step=Step.PARTIAL)
    p_out = jax.jit(partial)(page)
    # partial layout: key, avg_sum, avg_count
    assert p_out.num_columns == 3
    final = hash_aggregate([0], [AggSpec("avg", 1, T.DOUBLE)], step=Step.FINAL,
                           partial_state_channels=[[1, 2]])
    f_out = jax.jit(final)(p_out)
    assert sorted(f_out.to_pylist()) == [(1, 2.0), (2, 3.0)]


def test_aggregation_filter_mask_channel():
    # count(x) FILTER (WHERE flag)
    page = page_of(([1, 1, 1, 1], T.BIGINT), ([10, 20, 30, 40], T.BIGINT),
                   ([True, False, True, False], T.BOOLEAN))
    op = hash_aggregate([0], [AggSpec("sum", 1, T.BIGINT, mask_channel=2)])
    out = jax.jit(op)(page)
    assert out.to_pylist() == [(1, 40)]


# ---------------------------------------------------------------------------
# join

def test_inner_join_duplicate_keys():
    probe = page_of(([1, 2, 3, 2], T.BIGINT), ([10.0, 20.0, 30.0, 40.0], T.DOUBLE))
    build = page_of(([2, 2, 1], T.BIGINT), ([100, 200, 300], T.BIGINT))
    op = hash_join([0], [0], JoinType.INNER, output_capacity=8)
    out, total = jax.jit(op)(probe, build)
    assert int(total) == 5  # 1x1 + 2x2 + 0 + 2x2... probe row 2 & 4 each match 2
    rows = sorted(out.to_pylist())
    assert rows == [(1, 10.0, 1, 300), (2, 20.0, 2, 100), (2, 20.0, 2, 200),
                    (2, 40.0, 2, 100), (2, 40.0, 2, 200)]


def test_join_overflow_detection():
    probe = page_of(([1, 1], T.BIGINT))
    build = page_of(([1, 1, 1], T.BIGINT))
    op = hash_join([0], [0], JoinType.INNER, output_capacity=4)
    out, total = jax.jit(op)(probe, build)
    assert int(total) == 6 and int(out.num_rows) == 4  # truncated, flagged


def test_left_join_null_extension():
    probe = page_of(([1, 5], T.BIGINT))
    build = page_of(([1], T.BIGINT), ([99], T.BIGINT))
    op = hash_join([0], [0], JoinType.LEFT, output_capacity=4)
    out, _ = jax.jit(op)(probe, build)
    assert sorted(out.to_pylist(), key=str) == [(1, 1, 99), (5, None, None)]


def test_null_keys_never_match():
    probe = page_of(([1, 2], T.BIGINT, [0, 1]))
    build = page_of(([1, 2], T.BIGINT, [0, 1]), ([7, 8], T.BIGINT))
    op = hash_join([0], [0], JoinType.INNER, output_capacity=4)
    out, total = jax.jit(op)(probe, build)
    assert out.to_pylist() == [(2, 2, 8)]


def test_semi_and_anti_join():
    probe = page_of(([1, 2, 3, 4], T.BIGINT))
    build = page_of(([2, 4, 4], T.BIGINT))
    semi = hash_join([0], [0], JoinType.SEMI)
    out, _ = jax.jit(semi)(probe, build)
    assert [r[0] for r in out.to_pylist()] == [2, 4]
    anti = hash_join([0], [0], JoinType.ANTI)
    out, _ = jax.jit(anti)(probe, build)
    assert [r[0] for r in out.to_pylist()] == [1, 3]


def test_composite_semi_anti_join():
    # exercises the verified expansion path (scatter-back per probe row)
    probe = page_of(([1, 1, 2, 3], T.BIGINT), ([10, 20, 10, 30], T.BIGINT))
    build = page_of(([1, 2, 2], T.BIGINT), ([10, 10, 10], T.BIGINT))
    semi = hash_join([0, 1], [0, 1], JoinType.SEMI)
    out, total = jax.jit(semi)(probe, build)
    assert sorted(r[:2] for r in out.to_pylist()) == [(1, 10), (2, 10)]
    assert int(total) == 2
    anti = hash_join([0, 1], [0, 1], JoinType.ANTI)
    out, total = jax.jit(anti)(probe, build)
    assert sorted(r[:2] for r in out.to_pylist()) == [(1, 20), (3, 30)]
    assert int(total) == 2


def test_composite_semi_overflow_contract():
    # cap too small for the hash expansion -> total > cap signals re-run
    probe = page_of(([1, 1, 1], T.BIGINT), ([5, 5, 5], T.BIGINT))
    build = page_of(([1] * 8, T.BIGINT), ([5] * 8, T.BIGINT))
    semi = hash_join([0, 1], [0, 1], JoinType.SEMI, output_capacity=4)
    out, total = jax.jit(semi)(probe, build)
    assert int(total) > 4  # 24 hash matches exceed cap; executor must re-run
    big = hash_join([0, 1], [0, 1], JoinType.SEMI, output_capacity=32)
    out, total = jax.jit(big)(probe, build)
    assert int(total) == 3 and int(out.num_rows) == 3


def test_composite_key_join():
    probe = page_of(([1, 1, 2], T.BIGINT), ([10, 20, 10], T.BIGINT))
    build = page_of(([1, 2], T.BIGINT), ([10, 10], T.BIGINT), ([111, 222], T.BIGINT))
    op = hash_join([0, 1], [0, 1], JoinType.INNER, output_capacity=6)
    out, _ = jax.jit(op)(probe, build)
    assert sorted(out.to_pylist()) == [(1, 10, 1, 10, 111), (2, 10, 2, 10, 222)]


def test_join_under_single_jit_with_filter():
    probe = page_of((np.arange(100) % 10, T.BIGINT), (np.arange(100, dtype=float), T.DOUBLE))
    build = page_of(([3, 7], T.BIGINT), ([333, 777], T.BIGINT))
    join_op = hash_join([0], [0], JoinType.INNER, output_capacity=128)

    @jax.jit
    def frag(p, b):
        out, total = join_op(p, b)
        agg = hash_aggregate([0], [AggSpec("count", None, None)])(out)
        return agg, total

    agg, total = frag(probe, build)
    assert int(total) == 20
    assert sorted(agg.to_pylist()) == [(3, 10), (7, 10)]


# ---------------------------------------------------------------------------
# sort / topn / limit

def test_order_by_asc_desc_nulls():
    page = page_of(([3, 1, 2, 1], T.BIGINT, [1, 1, 0, 1]),
                   ([1.0, 2.0, 3.0, 4.0], T.DOUBLE))
    # ASC: nulls last (Trino default)
    out = jax.jit(order_by([SortKey(0, ascending=True)]))(page)
    assert [r[0] for r in out.to_pylist()] == [1, 1, 3, None]
    # DESC: nulls first
    out = jax.jit(order_by([SortKey(0, ascending=False)]))(page)
    assert [r[0] for r in out.to_pylist()] == [None, 3, 1, 1]
    # stability: equal keys keep input order
    out = jax.jit(order_by([SortKey(0)]))(page)
    assert out.to_pylist()[0] == (1, 2.0) and out.to_pylist()[1] == (1, 4.0)


def test_order_by_multi_key_and_float_desc():
    page = page_of(([1, 1, 2], T.BIGINT), ([5.0, 9.0, 1.0], T.DOUBLE))
    out = jax.jit(order_by([SortKey(0, True), SortKey(1, False)]))(page)
    assert out.to_pylist() == [(1, 9.0), (1, 5.0), (2, 1.0)]


def test_nan_sorts_largest():
    page = page_of(([1.0, float("nan"), 0.5], T.DOUBLE))
    out = jax.jit(order_by([SortKey(0, True)]))(page)
    vals = [r[0] for r in out.to_pylist()]
    assert vals[0] == 0.5 and vals[1] == 1.0 and np.isnan(vals[2])
    out = jax.jit(order_by([SortKey(0, False)]))(page)
    vals = [r[0] for r in out.to_pylist()]
    assert np.isnan(vals[0]) and vals[1] == 1.0


def test_top_n_and_limit():
    page = page_of((np.arange(10)[::-1].copy(), T.BIGINT))
    out = jax.jit(top_n(3, [SortKey(0, True)]))(page)
    assert [r[0] for r in out.to_pylist()] == [0, 1, 2]
    out = jax.jit(limit(4))(page)
    assert int(out.num_rows) == 4


def test_filter_project_operator():
    page = page_of(([1, 2, 3, 4], T.BIGINT), ([2.0, 4.0, 6.0, 8.0], T.DOUBLE))
    op = filter_project(
        Call("gt", (InputRef(0, T.BIGINT), Literal(1, T.BIGINT)), T.BOOLEAN),
        [Call("multiply", (InputRef(1, T.DOUBLE), Literal(10.0, T.DOUBLE)), T.DOUBLE)])
    out = jax.jit(op)(page)
    assert out.to_pylist() == [(40.0,), (60.0,), (80.0,)]


def test_min_max_varchar_keeps_dictionary():
    page = page_of(([1, 1, 2], T.BIGINT),
                   (np.array(["bb", "aa", "cc"], dtype=object), T.VARCHAR))
    op = hash_aggregate([0], [AggSpec("min", 1, T.VARCHAR),
                              AggSpec("max", 1, T.VARCHAR)])
    out = jax.jit(op)(page)
    assert sorted(out.to_pylist()) == [(1, "aa", "bb"), (2, "cc", "cc")]


def test_composite_join_total_after_collision_filter():
    probe = page_of(([1, 2], T.BIGINT), ([10, 20], T.BIGINT))
    build = page_of(([1, 2], T.BIGINT), ([10, 99], T.BIGINT))
    op = hash_join([0, 1], [0, 1], JoinType.INNER, output_capacity=4)
    out, total = jax.jit(op)(probe, build)
    # only (1,10) truly matches; total must reflect the post-verify count
    assert int(out.num_rows) == 1 and int(total) == 1


# ---------------------------------------------------------------------------
# outer joins (FULL/RIGHT) + composite-key verification

def test_full_join_kernel_and_finisher():
    from trino_tpu.ops.join import unmatched_build_page
    probe = page_of(([1, 5], T.BIGINT))
    build = page_of(([1, 7], T.BIGINT), ([11, 77], T.BIGINT))
    op = hash_join([0], [0], JoinType.FULL, output_capacity=4)
    out, total, bm = jax.jit(op)(probe, build)
    assert sorted(out.to_pylist(), key=str) == [(1, 1, 11), (5, None, None)]
    assert list(np.asarray(bm)) == [True, False]
    fin = unmatched_build_page(((T.BIGINT, None),))
    tail = jax.jit(fin)(build, bm)
    assert tail.to_pylist() == [(None, 7, 77)]


def test_full_join_null_keys_both_sides():
    probe = page_of(([1, 2], T.BIGINT, [1, 0]))
    build = page_of(([1, 3], T.BIGINT, [0, 1]), ([10, 30], T.BIGINT))
    op = hash_join([0], [0], JoinType.FULL, output_capacity=8)
    out, total, bm = jax.jit(op)(probe, build)
    # null probe key never matches -> both probe rows null-extended
    assert sorted(out.to_pylist(), key=str) == [
        (1, None, None), (None, None, None)]
    assert list(np.asarray(bm)) == [False, False]


def test_left_composite_collision_rescue(monkeypatch):
    # force total hash collision: every composite key hashes identically, so
    # verification must both drop fabricated matches AND rescue probe rows
    # whose every candidate was a collision (ADVICE r1/r2 carryover)
    import trino_tpu.ops.join as J
    monkeypatch.setattr(J, "_mix64", lambda x: jnp.zeros_like(
        x.astype(jnp.uint64)))
    probe = page_of(([1, 2], T.BIGINT), ([10, 20], T.BIGINT))
    build = page_of(([1, 9], T.BIGINT), ([10, 99], T.BIGINT),
                    ([111, 999], T.BIGINT))
    op = hash_join([0, 1], [0, 1], JoinType.LEFT, output_capacity=8)
    out, total = op(probe, build)  # not jit: monkeypatch must stay visible
    assert sorted(out.to_pylist(), key=str) == [
        (1, 10, 1, 10, 111), (2, 20, None, None, None)]
    assert int(total) == 2


def test_mark_join_build_null_3vl():
    # IN-subquery 3VL: no match + NULL on build side => NULL, not FALSE
    probe = page_of(([1, 4, 7], T.BIGINT, [1, 1, 0]))
    build = page_of(([1, 2], T.BIGINT, [1, 0]))
    op = hash_join([0], [0], JoinType.MARK)
    out, _ = jax.jit(op)(probe, build)
    marks = [r[-1] for r in out.to_pylist()]
    # 1 matches -> TRUE; 4 has no match but build has NULL -> NULL;
    # NULL probe vs non-empty build -> NULL
    assert marks == [True, None, None]


def test_mark_join_no_build_nulls_definite_false():
    probe = page_of(([1, 4], T.BIGINT))
    build = page_of(([1, 2], T.BIGINT))
    op = hash_join([0], [0], JoinType.MARK)
    out, _ = jax.jit(op)(probe, build)
    assert [r[-1] for r in out.to_pylist()] == [True, False]


# ---------------------------------------------------------------------------
# the set table of a single-key SEMI, ANTI or MARK join (PR 46)

def _with_rows(page, n):
    """The same lanes, the first `n` of them live."""
    return Page(page.columns, jnp.asarray(n, dtype=jnp.int32))


def _varchar_pages(probe_words, build_words, build_valid=None):
    """Two one-column VARCHAR pages over ONE dictionary."""
    from trino_tpu.page import Column, Dictionary
    d, codes = Dictionary.build(np.asarray(probe_words + build_words,
                                           dtype=object))
    n = len(probe_words)
    probe = Page((Column.from_numpy(codes[:n], T.VARCHAR, None, d),),
                 jnp.asarray(n, dtype=jnp.int32))
    valid = None if build_valid is None else np.asarray(build_valid, bool)
    build = Page((Column.from_numpy(codes[n:], T.VARCHAR, valid, d),),
                 jnp.asarray(len(build_words), dtype=jnp.int32))
    return probe, build


def _set_table_case(name):
    """(probe_page, build_page) of one case; the key is channel 0."""
    rng = np.random.default_rng(46)
    if name == "duplicates":
        return (page_of(([1, 2, 3, 4, 4], T.BIGINT), ([10, 20, 30, 40, 50],
                                                      T.BIGINT)),
                page_of(([2, 4, 4, 4, 2], T.BIGINT)))
    if name == "dead_build_lanes":
        # lanes past num_rows hold keys the probe asks for: never there
        return (page_of(([2, 4, 9, 7], T.BIGINT)),
                _with_rows(page_of(([2, 4, 9, 9, 7, 0], T.BIGINT)), 2))
    if name == "null_build_keys":
        return (page_of(([1, 2, 3, 4], T.BIGINT)),
                page_of(([2, 3, 4, 1], T.BIGINT, [1, 0, 1, 0])))
    if name == "null_probe_keys":
        return (page_of(([1, 2, 3, 4], T.BIGINT, [1, 0, 0, 1])),
                page_of(([2, 4, 1], T.BIGINT)))
    if name == "null_keys_on_both_sides":
        return (page_of(([1, 2, 3, 4], T.BIGINT, [1, 0, 1, 0])),
                page_of(([2, 4, 1, 5], T.BIGINT, [1, 1, 0, 1])))
    if name == "empty_build":
        return (page_of(([1, 2, 3], T.BIGINT, [1, 0, 1])),
                _with_rows(page_of(([1, 2, 3, 3], T.BIGINT)), 0))
    if name == "probe_outside_the_span":
        return (page_of(([-7, 0, 99, 100, 105, 106, 2 ** 40], T.BIGINT)),
                page_of(([100, 105, 103], T.BIGINT)))
    if name == "negative_keys":
        return (page_of(([-6, -5, -4, -3, -2, 1], T.BIGINT)),
                page_of(([-3, -5, -3], T.BIGINT)))
    if name == "shuffled_build":
        keys = rng.permutation(np.repeat(np.arange(1000, 1400, 3), 2))
        probe = rng.integers(990, 1410, 512)
        return (page_of((probe, T.BIGINT), (np.arange(512), T.BIGINT)),
                _with_rows(page_of((keys, T.BIGINT),
                                   (np.arange(len(keys)), T.BIGINT)), 200))
    if name == "dictionary_key":
        return _varchar_pages(["b", "zz", "a", "q", "b"],
                              ["q", "b", "b", "m"], [1, 1, 1, 0])
    if name == "integer_key":
        return (page_of((np.asarray([5, -1, 7, 9], np.int32), T.INTEGER)),
                page_of((np.asarray([9, 5, 5], np.int32), T.INTEGER)))
    raise AssertionError(name)


def _set_join(probe, build, join_type, null_aware):
    """The join the way `_prepare_probe` runs it for lookup 'set': a pass
    of reductions, one scatter from the unsorted lanes, one gather."""
    from trino_tpu.ops.join import (build_set_table, semi_build_stats,
                                    set_semi_join)
    kmin, kmax, n_rows, has_null = jax.jit(semi_build_stats([0]))(build)
    span = max(int(kmax) - int(kmin) + 1, 1)     # an empty build: 1 slot
    size = 1 << (span - 1).bit_length()
    table, key_cols = jax.jit(build_set_table([0], size))(build, kmin)
    assert table.shape == (size,) and table.dtype == jnp.int32
    assert all(c.values.shape == (0,) for c in key_cols)
    prepared = (table, kmin, n_rows, has_null, key_cols)
    return jax.jit(set_semi_join([0], join_type, null_aware))(
        probe, prepared), (kmin, kmax, n_rows, has_null), size


@pytest.mark.parametrize("case", [
    "duplicates", "dead_build_lanes", "null_build_keys", "null_probe_keys",
    "null_keys_on_both_sides", "empty_build", "probe_outside_the_span",
    "negative_keys", "shuffled_build", "dictionary_key", "integer_key"])
@pytest.mark.parametrize("null_aware", [True, False],
                         ids=["in", "exists"])
@pytest.mark.parametrize("join_type", [JoinType.SEMI, JoinType.ANTI,
                                       JoinType.MARK])
def test_the_set_table_answers_as_the_sorted_build_does(join_type,
                                                        null_aware, case):
    """The set build and set probe (no sort) against hash_join over a
    sorted build, by search and through the position table: the same
    page, row for row, NULL for NULL, and the same total — and the stats
    pass reads what prepare_build read."""
    from trino_tpu.ops.join import build_dense_table, prepare_build
    probe, build = _set_table_case(case)
    (got, got_total), stats, size = _set_join(probe, build, join_type,
                                              null_aware)
    prepared = jax.jit(prepare_build([0], semi=True))(build)
    assert [int(x) for x in stats] == [
        int(prepared[i]) for i in (8, 9, 4, 5)]
    table = jax.jit(build_dense_table(size, semi=True))(
        prepared[1], prepared[3], prepared[8])
    for lookup, prep in (("search", prepared),
                         ("dense", prepared + (table,))):
        want, want_total = jax.jit(hash_join(
            [0], [0], join_type, prepared=True, lookup=lookup,
            null_aware=null_aware))(probe, prep)
        assert got.to_pylist() == want.to_pylist(), lookup
        assert int(got_total) == int(want_total) == int(got.num_rows)
    if case == "empty_build":
        assert int(stats[0]) > int(stats[1]) and int(stats[2]) == 0


def test_the_set_table_refuses_keys_of_two_dictionaries():
    """Codes of different pools are not comparable: the set probe fails
    as loudly as hash_join does, from the build's key column cut to no
    lane."""
    probe, _ = _varchar_pages(["a", "b"], ["c"])
    _, build = _varchar_pages(["x"], ["a", "b"])
    with pytest.raises(NotImplementedError, match="distinct dictionaries"):
        _set_join(probe, build, JoinType.SEMI, True)
    with pytest.raises(NotImplementedError, match="distinct dictionaries"):
        hash_join([0], [0], JoinType.SEMI)(probe, build)


@pytest.mark.parametrize("join_type", [JoinType.INNER, JoinType.LEFT,
                                       JoinType.FULL])
def test_the_set_table_serves_no_join_that_emits_build_rows(join_type):
    from trino_tpu.ops.join import set_semi_join
    with pytest.raises(ValueError, match="SEMI, ANTI and MARK"):
        set_semi_join([0], join_type)
    with pytest.raises(ValueError, match="one key column"):
        set_semi_join([0, 1], JoinType.SEMI)
