"""Aggregate function library vs python/sqlite oracles.

Reference parity: testing/trino-testing AbstractTestAggregations — breadth
coverage of the aggregate registry (operator/aggregation/: variance/
covariance state in CovarianceState.java, min_by/max_by, bool_and/or,
count_if, approx_distinct) over the tpch tiny schema.
"""

import math
import statistics

import pytest

from trino_tpu.exec import LocalQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch("tiny")


@pytest.fixture(scope="module")
def cust(runner):
    return runner.execute(
        "SELECT c_nationkey, c_custkey, c_acctbal, c_name FROM customer").rows


def by_nation(cust):
    out = {}
    for nk, ck, bal, name in cust:
        out.setdefault(nk, []).append((ck, float(bal), name))
    return out


def test_stddev_variance_global(runner, cust):
    vals = [float(r[2]) for r in cust]
    got = runner.execute(
        "SELECT stddev(c_acctbal), stddev_pop(c_acctbal), "
        "variance(c_acctbal), var_pop(c_acctbal), var_samp(c_acctbal) "
        "FROM customer").rows[0]
    assert got[0] == pytest.approx(statistics.stdev(vals), rel=1e-9)
    assert got[1] == pytest.approx(statistics.pstdev(vals), rel=1e-9)
    assert got[2] == pytest.approx(statistics.variance(vals), rel=1e-9)
    assert got[3] == pytest.approx(statistics.pvariance(vals), rel=1e-9)
    assert got[4] == got[2]


def test_stddev_grouped(runner, cust):
    groups = by_nation(cust)
    rows = runner.execute(
        "SELECT c_nationkey, stddev(c_acctbal) FROM customer "
        "GROUP BY c_nationkey").rows
    for nk, sd in rows:
        vals = [v for _, v, _ in groups[nk]]
        assert sd == pytest.approx(statistics.stdev(vals), rel=1e-9)


def test_var_samp_single_row_null(runner):
    rows = runner.execute(
        "SELECT var_samp(n_nationkey), var_pop(n_nationkey) "
        "FROM nation WHERE n_nationkey = 7").rows
    assert rows == [(None, 0.0)]


def test_corr_covar(runner, cust):
    xs = [float(r[2]) for r in cust]
    ys = [float(r[1]) for r in cust]
    got = runner.execute(
        "SELECT corr(c_acctbal, c_custkey), covar_samp(c_acctbal, c_custkey),"
        " covar_pop(c_acctbal, c_custkey) FROM customer").rows[0]
    assert got[0] == pytest.approx(statistics.correlation(xs, ys), rel=1e-6)
    assert got[1] == pytest.approx(statistics.covariance(xs, ys), rel=1e-6)
    n = len(xs)
    assert got[2] == pytest.approx(
        statistics.covariance(xs, ys) * (n - 1) / n, rel=1e-6)


def test_regr_slope_intercept(runner, cust):
    xs = [float(r[1]) for r in cust]   # x = custkey
    ys = [float(r[2]) for r in cust]   # y = acctbal
    slope, intercept = statistics.linear_regression(xs, ys)
    got = runner.execute(
        "SELECT regr_slope(c_acctbal, c_custkey), "
        "regr_intercept(c_acctbal, c_custkey) FROM customer").rows[0]
    assert got[0] == pytest.approx(slope, rel=1e-6)
    assert got[1] == pytest.approx(intercept, rel=1e-6)


def test_min_by_max_by(runner, cust):
    groups = by_nation(cust)
    rows = runner.execute(
        "SELECT c_nationkey, min_by(c_name, c_acctbal), "
        "max_by(c_name, c_acctbal) FROM customer GROUP BY c_nationkey").rows
    for nk, lo, hi in rows:
        g = groups[nk]
        assert lo == min(g, key=lambda t: t[1])[2]
        assert hi == max(g, key=lambda t: t[1])[2]


def test_bool_and_or_count_if(runner, cust):
    groups = by_nation(cust)
    rows = runner.execute(
        "SELECT c_nationkey, bool_and(c_acctbal > 0), "
        "bool_or(c_acctbal > 9000), count_if(c_acctbal > 0), "
        "every(c_acctbal > -1000) FROM customer GROUP BY c_nationkey").rows
    for nk, ba, bo, ci, ev in rows:
        vals = [v for _, v, _ in groups[nk]]
        assert ba == all(v > 0 for v in vals)
        assert bo == any(v > 9000 for v in vals)
        assert ci == sum(1 for v in vals if v > 0)
        assert ev is True


def test_approx_distinct_exact(runner):
    rows = runner.execute(
        "SELECT approx_distinct(o_orderstatus), "
        "count(DISTINCT o_orderstatus) FROM orders").rows
    assert rows[0][0] == rows[0][1]


def test_arbitrary_any_value(runner):
    rows = runner.execute(
        "SELECT arbitrary(n_name), any_value(n_name) "
        "FROM nation WHERE n_nationkey = 3").rows
    assert rows == [("CANADA", "CANADA")]


def test_geometric_mean(runner):
    vals = [r[0] for r in runner.execute(
        "SELECT c_custkey FROM customer").rows]
    got = runner.execute(
        "SELECT geometric_mean(c_custkey) FROM customer").rows[0][0]
    expected = math.exp(sum(math.log(v) for v in vals) / len(vals))
    assert got == pytest.approx(expected, rel=1e-9)


def test_min_by_null_y_skipped(runner):
    r = LocalQueryRunner.tpch("tiny")
    r.execute("CREATE TABLE memory.default.mb (x varchar, y bigint)")
    r.execute("INSERT INTO memory.default.mb VALUES "
              "('a', NULL), ('b', 5), ('c', 2), (NULL, 1)")
    rows = r.execute(
        "SELECT min_by(x, y), max_by(x, y) FROM memory.default.mb").rows
    assert rows == [(None, "b")]   # min y=1 has NULL x; y NULL row skipped


def test_distinct_agg_with_filter(runner):
    rows = runner.execute(
        "SELECT count(DISTINCT o_orderstatus) "
        "FILTER (WHERE o_totalprice > 100000), count(DISTINCT o_orderstatus)"
        " FROM orders").rows
    assert rows[0][0] <= rows[0][1]


def test_variance_large_mean_stable(runner):
    # naive E[x^2]-E[x]^2 catastrophically cancels with a 1e9 offset;
    # centered two-pass must agree with the unshifted variance
    a = runner.execute(
        "SELECT stddev(c_custkey + 1000000000), stddev(c_custkey) "
        "FROM customer WHERE c_custkey <= 100").rows[0]
    assert a[0] == pytest.approx(a[1], rel=1e-6)
    assert a[0] > 0


def test_covar_corr_large_mean_stable(runner):
    a = runner.execute(
        "SELECT covar_samp(c_acctbal + 1000000000, c_custkey + 1000000000), "
        "covar_samp(c_acctbal, c_custkey), "
        "corr(c_acctbal + 1000000000, c_custkey + 1000000000), "
        "corr(c_acctbal, c_custkey) "
        "FROM customer WHERE c_custkey <= 100").rows[0]
    assert a[0] == pytest.approx(a[1], rel=1e-6)
    assert a[2] is not None
    assert a[2] == pytest.approx(a[3], rel=1e-6)


@pytest.fixture(scope="module")
def nan_runner():
    r = LocalQueryRunner.tpch("tiny")
    r.execute("CREATE TABLE memory.default.nantab AS "
              "SELECT 1 AS g, sqrt(-1e0) AS x "
              "UNION ALL SELECT 1, sqrt(-1e0) "
              "UNION ALL SELECT 1, sqrt(-1e0) "
              "UNION ALL SELECT 1, 1.0e0 "
              "UNION ALL SELECT 1, 1.0e0 "
              "UNION ALL SELECT 2, 2.0e0")
    return r


def test_count_distinct_nan_single_value(nan_runner):
    rows = nan_runner.execute(
        "SELECT count(DISTINCT x) FROM memory.default.nantab").rows
    assert rows == [(3,)]  # {NaN, 1.0, 2.0}


def test_group_by_nan_single_group(nan_runner):
    rows = nan_runner.execute(
        "SELECT count(*) FROM (SELECT x, count(*) AS c "
        "FROM memory.default.nantab GROUP BY x) t").rows
    assert rows == [(3,)]


def test_min_max_by_nan_largest(nan_runner):
    rows = nan_runner.execute(
        "SELECT min_by(g, x), max_by(g, x) FROM memory.default.nantab "
        "WHERE g = 1").rows
    # min ignores NaN (treated as largest); max picks a NaN row
    assert rows == [(1, 1)]
    rows = nan_runner.execute(
        "SELECT min_by(g, x) FROM memory.default.nantab").rows
    assert rows == [(1,)]


def test_variance_distinct(runner):
    # var over DISTINCT values must differ from var over all rows
    r = LocalQueryRunner.tpch("tiny")
    r.execute("CREATE TABLE memory.default.vd AS "
              "SELECT 1 AS x UNION ALL SELECT 1 "
              "UNION ALL SELECT 1 UNION ALL SELECT 2")
    got = r.execute("SELECT var_pop(DISTINCT x), var_pop(x) "
                    "FROM memory.default.vd").rows[0]
    assert got[0] == pytest.approx(0.25)
    assert got[1] == pytest.approx(0.1875)


def test_approx_distinct_in_correlated_subquery(runner):
    rows = runner.execute(
        "SELECT r_name, (SELECT approx_distinct(n_name) FROM nation "
        "WHERE n_regionkey = r_regionkey) FROM region").rows
    assert sorted(v for _, v in rows) == [5, 5, 5, 5, 5]


def test_min_by_distinct_rejected(runner):
    with pytest.raises(Exception):
        runner.execute("SELECT min_by(DISTINCT n_name, n_nationkey) "
                       "FROM nation")


# ------------------------------------------------- sketch aggregates (r4)

def test_approx_distinct_accuracy(runner):
    # HLL m=2048 -> 2.30% standard error; orders.o_custkey at tiny has
    # ~1000 distinct customers with orders
    exact = runner.execute(
        "SELECT count(DISTINCT o_custkey) FROM orders").only_value()
    approx = runner.execute(
        "SELECT approx_distinct(o_custkey) FROM orders").only_value()
    assert abs(approx - exact) <= max(3 * 0.023 * exact, 2), (approx, exact)


def test_approx_distinct_grouped(runner):
    rows = runner.execute(
        "SELECT o_orderpriority, approx_distinct(o_custkey), "
        "count(DISTINCT o_custkey) FROM orders "
        "GROUP BY o_orderpriority").rows
    assert len(rows) == 5
    for _, approx, exact in rows:
        assert abs(approx - exact) <= max(3 * 0.023 * exact, 2)


def test_approx_distinct_small_exact(runner):
    # linear-counting range: tiny cardinalities must be near-exact
    v = runner.execute(
        "SELECT approx_distinct(n_regionkey) FROM nation").only_value()
    assert v == 5
    v = runner.execute(
        "SELECT approx_distinct(n_nationkey) FROM nation").only_value()
    assert v == 25


def test_approx_distinct_empty_and_null(runner):
    v = runner.execute("SELECT approx_distinct(n_nationkey) FROM nation "
                       "WHERE n_nationkey < 0").only_value()
    assert v == 0


def test_approx_percentile(runner):
    # exact nearest-rank at single step
    rows = runner.execute(
        "SELECT approx_percentile(o_totalprice, 0.5e0), "
        "approx_percentile(o_totalprice, 0.9e0) FROM orders").rows
    med, p90 = rows[0]
    exact = runner.execute(
        "SELECT o_totalprice FROM orders ORDER BY o_totalprice").rows
    vals = [r[0] for r in exact]
    n = len(vals)
    import math
    assert med == vals[max(1, math.ceil(0.5 * n)) - 1]
    assert p90 == vals[max(1, math.ceil(0.9 * n)) - 1]


def test_approx_percentile_grouped(runner):
    rows = runner.execute(
        "SELECT o_orderpriority, approx_percentile(o_totalprice, 0.5e0) "
        "FROM orders GROUP BY o_orderpriority ORDER BY 1").rows
    assert len(rows) == 5 and all(r[1] is not None for r in rows)


def test_checksum(runner):
    a = runner.execute("SELECT checksum(n_nationkey) FROM nation").only_value()
    # order-independent: same value regardless of scan order
    b = runner.execute("SELECT checksum(k) FROM (SELECT n_nationkey AS k "
                       "FROM nation ORDER BY n_name)").only_value()
    assert a == b and a != 0
    c = runner.execute("SELECT checksum(n_nationkey) FROM nation "
                       "WHERE n_nationkey < 0").only_value()
    assert c is None        # ChecksumAggregationFunction: NULL on empty


# ------------------------------------------------------------------
# The direct GROUP BY's two reduce forms (PR 29): a tiny static slot table
# reduces lane-wise under slot masks, a larger one scatters, and both must
# give the rows the sort-based path gives — on every step.

def _direct_page(selection):
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.page import Column, Dictionary, Page
    cap, rows = 256, 200
    rng = np.random.default_rng(29)
    big = rng.integers(2**62, 2**63 - 1, cap)      # three of them wrap
    page = Page((
        Column(jnp.asarray(rng.integers(0, 3, cap).astype(np.int32)),
               jnp.asarray(rng.random(cap) > 0.15), T.VARCHAR,
               Dictionary(np.array(["A", "N", "R"], dtype=object))),
        Column(jnp.asarray(rng.integers(0, 2, cap).astype(np.int32)), None,
               T.VARCHAR, Dictionary(np.array(["F", "O"], dtype=object))),
        Column(jnp.asarray(big), jnp.asarray(rng.random(cap) > 0.2),
               T.BIGINT, None),
        Column(jnp.asarray(rng.integers(-10**7, 10**7, cap)), None,
               T.DecimalType(12, 2), None),
        Column(jnp.asarray(rng.normal(0.0, 1e6, cap)),
               jnp.asarray(rng.random(cap) > 0.1), T.DOUBLE, None),
        Column(jnp.asarray(rng.random(cap) > 0.4),
               jnp.asarray(rng.random(cap) > 0.1), T.BOOLEAN, None),
    ), jnp.asarray(rows, dtype=jnp.int32))
    if selection == "selection":
        page = page.with_selection(jnp.asarray(rng.random(cap) > 0.3))
    elif selection == "empty":
        page = Page(page.columns, jnp.asarray(0, dtype=jnp.int32))
    return page


def _direct_specs():
    from trino_tpu import types as T
    from trino_tpu.ops import AggSpec
    dec = T.DecimalType(12, 2)
    return {
        "int64_sum_wraps": [AggSpec("sum", 2, T.BIGINT),
                            AggSpec("count", 2, T.BIGINT)],
        "decimal_sum_avg_count": [AggSpec("sum", 3, dec),
                                  AggSpec("avg", 3, dec),
                                  AggSpec("count", None, None)],
        "min_max": [AggSpec("min", 2, T.BIGINT), AggSpec("max", 3, dec),
                    AggSpec("min", 4, T.DOUBLE), AggSpec("max", 4, T.DOUBLE)],
        "bool_and_or_count_if": [AggSpec("bool_and", 5, T.BOOLEAN),
                                 AggSpec("bool_or", 5, T.BOOLEAN),
                                 AggSpec("count_if", 5, T.BOOLEAN)],
        "checksum": [AggSpec("checksum", 2, T.BIGINT),
                     AggSpec("checksum", 4, T.DOUBLE)],
        "float64_sum_avg": [AggSpec("sum", 4, T.DOUBLE),
                            AggSpec("avg", 4, T.DOUBLE),
                            AggSpec("geometric_mean", 3, dec)],
        "filter_where": [AggSpec("sum", 3, dec, 5),
                         AggSpec("count", None, None, 5),
                         AggSpec("min", 3, dec, 5)],
    }


def _agg_rows(page, nkeys):
    """{key tuple: [values]} of a result page; NULL by the valid mask (the
    direct path leaves the NULL slot's code in the value lane)."""
    import numpy as np
    assert page.selection is None
    n = int(page.num_rows)
    cols = []
    for c in page.columns:
        vals = np.asarray(c.values)[:n]
        valid = np.ones(n, dtype=bool) if c.valid is None \
            else np.asarray(c.valid)[:n]
        cols.append([None if not ok else
                     str(c.dictionary.values[v]) if c.dictionary is not None
                     else v.item() for v, ok in zip(vals, valid)])
    out = {}
    for i in range(n):
        key = tuple(c[i] for c in cols[:nkeys])
        assert key not in out, f"group {key} twice"
        out[key] = [c[i] for c in cols[nkeys:]]
    return out


def _state_channels(nkeys, specs):
    from trino_tpu.ops.aggregate import get_aggregate
    out, ch = [], nkeys
    for spec in specs:
        k = len(get_aggregate(spec.name, spec.input_type)
                .state(spec.input_type))
        out.append(list(range(ch, ch + k)))
        ch += k
    return out


def _run_steps(page, keys, specs, steps):
    """The aggregate as a plan runs it: SINGLE; PARTIAL then FINAL over
    the partial page; or PARTIAL, INTERMEDIATE, FINAL."""
    import jax

    from trino_tpu.ops import Step, hash_aggregate
    nkeys = len(keys)
    if steps == "single":
        return jax.jit(hash_aggregate(keys, specs, Step.SINGLE))(page)
    chans = _state_channels(nkeys, specs)
    merged = list(range(nkeys))
    out = jax.jit(hash_aggregate(keys, specs, Step.PARTIAL))(page)
    if steps == "partial_intermediate_final":
        out = jax.jit(hash_aggregate(merged, specs, Step.INTERMEDIATE,
                                     chans))(out)
    return jax.jit(hash_aggregate(merged, specs, Step.FINAL, chans))(out)


def _assert_same_rows(got, want, what):
    assert set(got) == set(want), what
    for key, vals in want.items():
        for g, w in zip(got[key], vals):
            if isinstance(w, float) and w == w:
                assert g == pytest.approx(w, rel=1e-9), (what, key)
            else:
                assert g == w or (g != g and w != w), (what, key, g, w)


_FORMS = {"masked": {"_MASKED_MAX_SLOT_STATES": 1 << 30},
          "scatter": {"_MASKED_MAX_SLOT_STATES": 0},
          "sorted": {"_DIRECT_MAX_GROUPS": 0}}


# the merge steps never see the input page: a selection or an empty page
# goes through SINGLE and PARTIAL -> FINAL
@pytest.mark.parametrize("selection, steps", [
    ("rows", "single"), ("rows", "partial_final"),
    ("rows", "partial_intermediate_final"),
    ("selection", "single"), ("selection", "partial_final"),
    ("empty", "single"), ("empty", "partial_final")])
@pytest.mark.parametrize("case", list(_direct_specs()))
def test_direct_reduce_forms_and_the_sorted_path_give_the_same_rows(
        monkeypatch, case, selection, steps):
    from trino_tpu.ops import aggregate
    from trino_tpu.page import trace_notes
    specs = _direct_specs()[case]
    page = _direct_page(selection)
    keys = [0, 1]           # NULL flags take the last slot of key 0's space
    rows, notes = {}, {}
    for form, consts in _FORMS.items():
        with monkeypatch.context() as m:
            for name, value in consts.items():
                m.setattr(aggregate, name, value)
            with trace_notes() as said:
                rows[form] = _agg_rows(_run_steps(page, keys, specs, steps),
                                       len(keys))
            notes[form] = set(said)
    said_sorted = notes.pop("sorted")
    assert notes == {"masked": {"direct_reduce_masked"},
                     "scatter": {"direct_reduce_scattered"}}
    assert said_sorted and all(
        fact.startswith("sorted_reduce_scan:") for fact in said_sorted)
    if selection == "empty":
        assert rows["masked"] == {}
    elif selection == "rows":
        assert (None, "F") in rows["masked"]        # the NULL-key group
    ints_exact = case not in ("float64_sum_avg", "min_max")
    if ints_exact:
        # int64, decimal, count, checksum: bit for bit, wrapped or not
        assert rows["masked"] == rows["scatter"] == rows["sorted"]
    _assert_same_rows(rows["masked"], rows["scatter"], "masked vs scatter")
    _assert_same_rows(rows["masked"], rows["sorted"], "masked vs sorted")


def test_int64_sums_wrap_the_same_in_every_form():
    """The wrap is real: the page's bigints sum past 2**63."""
    import numpy as np
    page = _direct_page("rows")
    col = page.column(2)
    live = np.arange(256) < 200
    total = sum(int(v) for v, ok, keep in zip(
        np.asarray(col.values), np.asarray(col.valid), live) if ok and keep)
    assert total >= 2**63


@pytest.mark.parametrize("past, masked", [(0, True), (1, False)])
def test_slot_count_at_the_crossover_and_one_past_it(monkeypatch, past,
                                                     masked):
    """Four sums are eight state columns: with the crossover's slots the
    masked form runs, with one slot more the scatter, as the module's own
    constant says, and both give the sorted path's rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.ops import AggSpec, Step, aggregate, hash_aggregate
    from trino_tpu.page import Column, Dictionary, Page, trace_notes
    slots = aggregate._MASKED_MAX_SLOT_STATES // 8 + past
    assert slots <= aggregate._DIRECT_MAX_GROUPS
    nvalues = slots - 1             # the NULL slot is the last
    rng = np.random.default_rng(slots)
    cap = 4096
    pool = Dictionary(np.array([f"v{i:04d}" for i in range(nvalues)],
                               dtype=object))
    page = Page((
        Column(jnp.asarray(rng.integers(0, nvalues, cap).astype(np.int32)),
               None, T.VARCHAR, pool),) + tuple(
        Column(jnp.asarray(rng.integers(-99, 99, cap)), None, T.BIGINT,
               None) for _ in range(4)), jnp.asarray(4000, dtype=jnp.int32))
    specs = [AggSpec("sum", ch, T.BIGINT) for ch in (1, 2, 3, 4)]
    with trace_notes() as said:
        got = _agg_rows(jax.jit(hash_aggregate([0], specs, Step.SINGLE))(
            page), 1)
    assert said == {"direct_reduce_masked" if masked
                    else "direct_reduce_scattered"}
    monkeypatch.setattr(aggregate, "_DIRECT_MAX_GROUPS", 0)
    want = _agg_rows(jax.jit(hash_aggregate([0], specs, Step.SINGLE))(
        page), 1)
    assert got == want and len(got) > 1000


# ------------------------------------------------------------------
# The sorted GROUP BY's reduce (PR 40): a segmented scan over the sorted
# lanes and one compaction, against NumPy's reduceat over the same rows.

_SORTED_LAYOUTS = ("mixed", "selection", "one_group", "own_groups",
                   "long_groups", "ends_on_last_lane", "empty")
_SORTED_POOL = ["AIR", "FOB", "MAIL", "RAIL", "SHIP", "TRUCK"]   # sorted


def _sorted_columns(layout, cap):
    """NumPy columns of one input page: (key, key_valid, live, inputs)
    with inputs = [(values, valid)] for BIGINT, DECIMAL(12,2), DOUBLE, a
    dictionary string's codes and the BOOLEAN of a FILTER."""
    import numpy as np
    rng = np.random.default_rng(40 + cap + _SORTED_LAYOUTS.index(layout))
    rows = {"mixed": cap * 4 // 5, "selection": cap * 9 // 10,
            "one_group": cap - 3, "own_groups": cap, "long_groups": cap - 1,
            "ends_on_last_lane": cap, "empty": 0}[layout]
    key = rng.integers(0, max(cap // 8, 2), cap)
    key_valid = np.ones(cap, dtype=bool)
    if layout in ("mixed", "selection"):
        key_valid = rng.random(cap) > 0.05          # NULL is one more group
    elif layout == "one_group":
        key[:] = 7                 # longer than 2^k + 1 lanes for every k
    elif layout == "own_groups":
        key = rng.permutation(cap)
    elif layout == "long_groups":
        # groups of 2^k + 2 rows, k = 0, 1, 2, ..., in shuffled row order
        lengths, k = [], 0
        while sum(lengths) + (1 << k) + 2 <= rows:
            lengths.append((1 << k) + 2)
            k += 1
        key[:] = len(lengths)      # what is left: one more group
        key[:sum(lengths)] = np.repeat(np.arange(len(lengths)), lengths)
        key[:rows] = rng.permutation(key[:rows])
    elif layout == "ends_on_last_lane":
        key[rng.choice(cap, 5, replace=False)] = cap   # sorts last: 5 rows
    live = np.arange(cap) < rows
    if layout == "selection":
        live &= rng.random(cap) > 0.3
    inputs = [
        (rng.integers(-2**40, 2**40, cap), rng.random(cap) > 0.2),
        (rng.integers(-10**7, 10**7, cap), rng.random(cap) > 0.2),
        (rng.uniform(1.0, 1e6, cap), rng.random(cap) > 0.2),
        (rng.integers(0, len(_SORTED_POOL), cap).astype(np.int32),
         rng.random(cap) > 0.2),
        (rng.random(cap) > 0.4, rng.random(cap) > 0.1)]
    return key, key_valid, live, inputs


def _sorted_specs(cap=1024):
    """(spec, input column of `inputs` or None, FILTER). Every kind of
    state (dtype x reducer) is one more pair of round sets to compile, so
    the large page carries Q18's kinds and one of each other type, the
    small one all of them."""
    from trino_tpu import types as T
    from trino_tpu.ops import AggSpec
    dec = T.DecimalType(12, 2)
    specs = [(AggSpec("sum", 2, dec), 1, False),
             (AggSpec("count", None, None), None, False),
             (AggSpec("min", 4, T.VARCHAR), 3, False),
             (AggSpec("sum", 2, dec, 5), 1, True)]
    if cap > 1024:
        return specs
    return specs + [(AggSpec("avg", 3, T.DOUBLE), 2, False),
                    (AggSpec("sum", 1, T.BIGINT), 0, False),
                    (AggSpec("min", 1, T.BIGINT), 0, False),
                    (AggSpec("max", 1, T.BIGINT), 0, False),
                    (AggSpec("count", 1, T.BIGINT), 0, False),
                    (AggSpec("avg", 1, T.BIGINT), 0, False),
                    (AggSpec("avg", 2, dec), 1, False),
                    (AggSpec("min", 2, dec), 1, False),
                    (AggSpec("sum", 3, T.DOUBLE), 2, False),
                    (AggSpec("max", 3, T.DOUBLE), 2, False),
                    (AggSpec("max", 4, T.VARCHAR), 3, False),
                    (AggSpec("count", None, None, 5), None, True)]


def _sorted_reference(specs, key, key_valid, live, inputs):
    """-> (group keys with NULL as None's stand-in -1 last, [(values,
    valid)] an aggregate), by NumPy's reduceat over the stably sorted live
    rows."""
    import numpy as np

    from trino_tpu import types as T
    idx = np.flatnonzero(live)
    k = np.where(key_valid, key, 0)[idx]
    order = idx[np.lexsort((k, ~key_valid[idx]))]   # NULL keys last
    ks, kv = np.where(key_valid, key, 0)[order], key_valid[order]
    if len(order) == 0:
        return ks, kv, None
    first = np.ones(len(order), dtype=bool)
    first[1:] = (ks[1:] != ks[:-1]) | (kv[1:] != kv[:-1])
    starts = np.flatnonzero(first)
    out = []
    filt = inputs[4][0] & inputs[4][1]
    for spec, src, filtered in specs:
        mask = np.ones(len(order), dtype=bool)
        vals = np.zeros(len(order), dtype=np.int64)
        if src is not None:
            vals, mask = inputs[src][0][order], inputs[src][1][order]
        if filtered:
            mask = mask & filt[order]
        cnt = np.add.reduceat(mask.astype(np.int64), starts)
        if spec.name == "count":
            out.append((cnt, np.ones(len(starts), dtype=bool)))
            continue
        if spec.name in ("sum", "avg"):
            if spec.name == "avg" \
                    and not isinstance(spec.input_type, T.DecimalType):
                vals = vals.astype(np.float64)      # avg(BIGINT) is DOUBLE
            tot = np.add.reduceat(np.where(mask, vals, 0), starts)
            if spec.name == "avg":
                den = np.maximum(cnt, 1)
                if tot.dtype == np.float64:
                    tot = tot / den
                else:       # decimal: HALF_UP at the column's scale
                    half = den // 2
                    adj = np.where(tot >= 0, tot + half, tot - half)
                    tot = np.sign(adj) * (np.abs(adj) // den)
            out.append((tot, cnt > 0))
            continue
        red = np.minimum if spec.name == "min" else np.maximum
        if vals.dtype == np.float64:
            ident = np.inf if spec.name == "min" else -np.inf
        else:
            info = np.iinfo(vals.dtype)
            ident = info.max if spec.name == "min" else info.min
        out.append((red.reduceat(np.where(mask, vals, ident), starts),
                    cnt > 0))
    return ks[starts], kv[starts], out


def _sorted_page(layout, cap, extra_selection=None):
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.page import Column, Dictionary, Page
    key, key_valid, live, inputs = _sorted_columns(layout, cap)
    types = (T.BIGINT, T.DecimalType(12, 2), T.DOUBLE, T.VARCHAR, T.BOOLEAN)
    pool = Dictionary(np.array(_SORTED_POOL, dtype=object))
    cols = [Column(jnp.asarray(key), jnp.asarray(key_valid), T.BIGINT, None)]
    for (vals, valid), t in zip(inputs, types):
        cols.append(Column(jnp.asarray(vals), jnp.asarray(valid), t,
                           pool if t is T.VARCHAR else None))
    rows = int(live.sum()) if layout != "selection" \
        else int(np.flatnonzero(live)[-1]) + 1
    page = Page(tuple(cols), jnp.asarray(rows, dtype=jnp.int32))
    selection = None
    if layout == "selection":
        selection = live
    if extra_selection is not None:
        selection = extra_selection if selection is None \
            else selection & extra_selection
    return page if selection is None else \
        page.with_selection(jnp.asarray(selection))


_SORTED_OPS: dict = {}


def _sorted_op(step, cap):
    """One jitted operator a (step, capacity), for every layout."""
    import jax

    from trino_tpu.ops import Step, hash_aggregate
    if (step, cap) not in _SORTED_OPS:
        specs = [s for s, _, _ in _sorted_specs(cap)]
        chans = None if step in (Step.SINGLE, Step.PARTIAL) \
            else _state_channels(1, specs)
        _SORTED_OPS[step, cap] = jax.jit(
            hash_aggregate([0], specs, step, chans))
    return _SORTED_OPS[step, cap]


@pytest.mark.parametrize("cap", [1024, 131072])
@pytest.mark.parametrize("steps", ["single", "partial_final",
                                   "partial_intermediate_final"])
@pytest.mark.parametrize("layout", _SORTED_LAYOUTS)
def test_sorted_path_reduces_like_numpy(layout, steps, cap):
    """sum / min / max / count / avg over BIGINT, DECIMAL(12,2), DOUBLE and
    a dictionary string, with NULL inputs, a FILTER mask, dead rows and a
    deferred selection, through the sorted path's scan (a BIGINT key has
    no slot table), on every step. The merge steps see each group's state
    twice: the page's even and odd rows go through PARTIAL apart."""
    import numpy as np

    from trino_tpu.ops import Step
    from trino_tpu.page import concat_pages, trace_notes
    with trace_notes():         # the ops are traced once a module: keep
        if steps == "single":   # the notes out of whoever listens
            # (every page carries a selection, so one program serves all)
            out = _sorted_op(Step.SINGLE, cap)(
                _sorted_page(layout, cap, np.ones(cap, dtype=bool)))
        else:
            even = np.arange(cap) % 2 == 0
            halves = [_sorted_op(Step.PARTIAL, cap)(
                _sorted_page(layout, cap, m)) for m in (even, ~even)]
            for half in halves:
                assert half.selection is None
            # each half holds at most cap / 2 groups
            out = concat_pages(halves).pad_to(cap)
            if steps == "partial_intermediate_final":
                out = _sorted_op(Step.INTERMEDIATE, cap)(out)
            out = _sorted_op(Step.FINAL, cap)(out)
    assert out.selection is None
    ks, kv, want = _sorted_reference(_sorted_specs(cap),
                                     *_sorted_columns(layout, cap))
    n = int(out.num_rows)
    assert n == len(ks)
    if n == 0:
        return
    kcol = out.column(0)
    got_kv = np.asarray(kcol.valid_mask())[:n]
    got_k = np.where(got_kv, np.asarray(kcol.values)[:n], 0)
    order = np.lexsort((got_k, ~got_kv))
    assert np.array_equal(got_k[order], ks)
    assert np.array_equal(got_kv[order], kv)
    for ci, ((spec, _, _), (vals, valid)) in enumerate(
            zip(_sorted_specs(cap), want), start=1):
        col = out.column(ci)
        got_valid = np.asarray(col.valid_mask())[:n][order]
        got = np.asarray(col.values)[:n][order]
        assert np.array_equal(got_valid, valid), (spec, "NULLs")
        if got.dtype == np.float64:
            assert np.allclose(got[valid], vals[valid], rtol=1e-9,
                               atol=0.0), spec
        else:       # integers, decimals and dictionary codes: bit for bit
            assert np.array_equal(got[valid], vals[valid]), spec


# ------------------------------------------------------------------
# The sorted GROUP BY over lanes that arrive in key order (PR 45): the
# device tests the order and, in order, neither sorts nor gathers. Against
# the form it replaced — sort always, every input and the keys gathered
# through the permutation — kept here as the oracle, array for array.

def _permuted_group_by(page, key_channels, specs, step, state_channels):
    """The sorted path of `hash_aggregate` as PR 44 left it."""
    import jax.numpy as jnp

    from trino_tpu.ops import Step, aggregate as A
    from trino_tpu.ops.radix import sort_by_keys
    from trino_tpu.page import Column, Page, shift_takes
    n = page.capacity
    sorted_keys, perm = sort_by_keys(A._sort_key_arrays(page, key_channels))
    live = ~sorted_keys[0]
    boundary = A._boundary_scan(sorted_keys[1:], n) & live
    group_of_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.sum(boundary).astype(jnp.int32)
    first_idx = jnp.zeros(n, dtype=jnp.int32).at[
        jnp.where(boundary, group_of_sorted, n)].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    key_row = jnp.take(perm, first_idx, mode="clip")
    out = [page.column(ch).gather(key_row) for ch in key_channels]
    entries = []
    for ai, spec in enumerate(specs):
        fn = A.get_aggregate(spec.name, spec.input_type)
        states = fn.state(spec.input_type)
        if step in (Step.FINAL, Step.INTERMEDIATE):
            contribs = []
            for sc, ch in zip(states, state_channels[ai]):
                vals = jnp.take(page.column(ch).values, perm, mode="clip")
                ident = jnp.zeros((), vals.dtype) if sc.reducer == "sum" \
                    else A._ident_for(vals.dtype, sc.reducer == "min")
                contribs.append((jnp.where(live, vals, ident), sc.reducer))
            dictionary = page.column(state_channels[ai][0]).dictionary
        else:
            vals, mask, dictionary = A._agg_inputs(page, spec, fn, live,
                                                   gather=perm)
            contribs = [(sc.contrib(vals, mask), sc.reducer)
                        for sc in states]
        entries.append((spec, fn, states, dictionary, contribs))
    takes, count = shift_takes(boundary)
    reduced = iter(A._scan_reduce(
        [c for e in entries for c in e[4]], boundary, live, takes,
        jnp.arange(n, dtype=jnp.int32) < count))
    for spec, fn, states, dictionary, _ in entries:
        arrays = [next(reduced) for _ in states]
        if step in (Step.PARTIAL, Step.INTERMEDIATE):
            out.extend(Column(a.astype(sc.type.dtype), None, sc.type, None)
                       for sc, a in zip(states, arrays))
        else:
            values, valid = fn.final(arrays, None)
            out.append(A._agg_out_column(fn, spec, values, valid,
                                         dictionary))
    return Page(tuple(out), num_groups)


_ORDER_KEYS = ("bigint", "nullable", "two_keys", "double", "string")
_ORDER_CASES = ("in_order", "dead_lane_inside", "inversion_at_the_last_lane",
                "reversed", "all_dead", "one_group")
_ORDER_CAP, _ORDER_ROWS = 32, 24
_ORDER_POOL = ["AIR", "FOB", "MAIL", "RAIL", "SHIP", "TRUCK"]   # sorted


def _order_key_rows(kind):
    """`_ORDER_ROWS` key rows in the sort's ascending order, with ties:
    a row is one (value, is NULL) a key column."""
    import numpy as np
    if kind == "bigint":
        vals = [-(2**62), -5, -5, 0, 0, 0, 1, 2**33, 2**33, 2**62]
        return [((v, False),) for v in np.repeat(vals, 3)[:_ORDER_ROWS]]
    if kind == "nullable":      # NULLs group after the values, as one key
        vals = [(-9, False), (3, False), (3, False), (2**40, False),
                (77, True), (-1, True), (5, True)]
        return [(v,) for v in vals for _ in range(4)][:_ORDER_ROWS]
    if kind == "two_keys":
        return [((a, False), (b, False)) for a in (-3, 4, 2**35)
                for b in (-7, -7, 0, 0, 1, 9, 9, 2**20)]
    if kind == "double":        # -0 is +0, NaN is one value and the last
        vals = [-np.inf, -1.5, -1.5, -0.0, 0.0, -0.0, 2.5, 2.5, np.inf,
                np.nan, np.nan, np.nan]
        return [((v, False),) for v in np.repeat(vals, 2)]
    codes = np.repeat(np.arange(len(_ORDER_POOL)), 4)
    return [((int(c), False),) for c in codes]


def _order_page(kind, case, step):
    """-> (page, key channels, specs, state channels, lanes in order)."""
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.ops import AggSpec, Step
    from trino_tpu.ops.aggregate import get_aggregate
    from trino_tpu.page import Column, Dictionary, Page
    cap = _ORDER_CAP
    rng = np.random.default_rng(_ORDER_KEYS.index(kind) * 16
                                + _ORDER_CASES.index(case))
    rows = _order_key_rows(kind)
    assert len(rows) == _ORDER_ROWS
    live = _ORDER_ROWS
    selection = None
    if case == "dead_lane_inside":
        selection = np.ones(cap, dtype=bool)
        selection[5] = False
    elif case == "inversion_at_the_last_lane":
        rows = rows[:-1] + [rows[0]]
    elif case == "reversed":
        rows = rows[::-1]
    elif case == "all_dead":
        live = 0
    elif case == "one_group":
        rows = [rows[7]] * len(rows)
    # what lies behind the live rows is whatever was there: not in order
    rows = rows + [rows[i] for i in rng.integers(0, 8, cap - len(rows))]
    key_types = {"bigint": (T.BIGINT,), "nullable": (T.BIGINT,),
                 "two_keys": (T.BIGINT, T.INTEGER), "double": (T.DOUBLE,),
                 "string": (T.VARCHAR,)}[kind]
    cols = []
    for k, typ in enumerate(key_types):
        values = np.array([r[k][0] for r in rows],
                          dtype=T.to_numpy_dtype(typ))
        null = np.array([r[k][1] for r in rows])
        cols.append(Column(
            jnp.asarray(values), jnp.asarray(~null) if kind == "nullable"
            else None, typ, Dictionary(np.array(_ORDER_POOL, dtype=object))
            if kind == "string" else None))
    nkeys = len(cols)
    specs = [AggSpec("sum", nkeys, T.BIGINT),
             AggSpec("count", None, None),
             AggSpec("max", nkeys + 1, T.DOUBLE),
             AggSpec("avg", nkeys + 1, T.DOUBLE, mask_channel=nkeys + 2)]
    state_channels = None
    if step in (Step.PARTIAL, Step.SINGLE):
        cols += [
            Column(jnp.asarray(rng.integers(-2**40, 2**40, cap)),
                   jnp.asarray(rng.random(cap) > 0.2), T.BIGINT, None),
            Column(jnp.asarray(rng.normal(size=cap) * 1e3), None, T.DOUBLE,
                   None),
            Column(jnp.asarray(rng.random(cap) > 0.3),
                   jnp.asarray(rng.random(cap) > 0.1), T.BOOLEAN, None)]
    else:       # the merge steps read state columns: keys first, then them
        state_channels, ch = [], nkeys
        for spec in specs:
            states = get_aggregate(spec.name, spec.input_type).state(
                spec.input_type)
            state_channels.append(list(range(ch, ch + len(states))))
            ch += len(states)
            for sc in states:
                dtype = T.to_numpy_dtype(sc.type)
                values = rng.normal(size=cap) * 1e3 \
                    if np.issubdtype(dtype, np.floating) \
                    else rng.integers(0, 2**40, cap)
                cols.append(Column(jnp.asarray(values.astype(dtype)), None,
                                   sc.type, None))
    page = Page(tuple(cols), jnp.asarray(live, jnp.int32),
                None if selection is None else jnp.asarray(selection))
    in_order = case in ("in_order", "all_dead", "one_group")
    return (page, list(range(nkeys)), specs, state_channels,
            cap if in_order else 0)


@pytest.mark.parametrize("case", _ORDER_CASES)
@pytest.mark.parametrize("kind", _ORDER_KEYS)
@pytest.mark.parametrize("step", ["PARTIAL", "FINAL", "INTERMEDIATE",
                                  "SINGLE"])
def test_lanes_in_key_order_are_neither_sorted_nor_gathered(
        monkeypatch, step, kind, case):
    """Whatever order the lanes arrive in, the operator's page is the
    sort-always form's, array for array — keys, states, NULL masks, the
    lanes past the groups — and what it says of the lanes' order is what
    the case is."""
    import jax
    import numpy as np

    from trino_tpu.ops import aggregate, hash_aggregate
    from trino_tpu.page import device_notes
    # a dictionary key's few values would take the direct path
    monkeypatch.setattr(aggregate, "_DIRECT_MAX_GROUPS", 0)
    page, keys, specs, state_channels, lanes_in_order = _order_page(
        kind, case, step)
    with device_notes() as said:
        got = hash_aggregate(keys, specs, step, state_channels)(page)
    said = {name: int(value) for name, value in said}
    assert said == {"group_by_lanes_in_order": lanes_in_order,
                    "group_by_lanes_sorted": page.capacity - lanes_in_order}
    want = _permuted_group_by(page, keys, specs, step, state_channels)
    assert int(got.num_rows) == int(want.num_rows)
    if case == "all_dead":
        assert int(got.num_rows) == 0
    elif case == "one_group":
        assert int(got.num_rows) == 1
    else:
        assert int(got.num_rows) > 3
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for ci, (g, w) in enumerate(zip(got.columns, want.columns)):
        assert g.type == w.type and g.dictionary == w.dictionary, ci
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(w)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), \
                (ci, a, b)
