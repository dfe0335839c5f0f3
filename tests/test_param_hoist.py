"""Parameterized kernel compilation: literal hoisting (expr/hoist.py).

The contract under test (PageFunctionCompiler parity, TPU edition): the
jit-cache key is the literal-free canonical expression tree, so executing
a TPC-H query and then the SAME shape with perturbed numeric/date
constants must (a) produce rows identical to the unhoisted
(hoist_literals=false) execution of the same SQL — the oracle-verified
pre-hoisting code path — and (b) report jit_misses == 0 on the second
run via QueryStatsCollector: zero XLA compiles for a new literal set.

The 22-query sweep doubles as a trace-count regression guard: any change
that sneaks a literal value back into a kernel cache key shows up here as
a nonzero miss count on the variant run.
"""

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.exec import LocalQueryRunner, jit_cache
from trino_tpu.expr.hoist import hoist_literal_seq, hoist_literals
from trino_tpu.expr.ir import Call, InputRef, Literal, Param, SpecialForm, \
    SpecialKind
from trino_tpu.expr.functions import days_from_civil

from oracle import assert_same, load_tpch_sqlite
from tpch_sql import QUERIES

SF = 0.01


def d(text: str) -> int:
    y, m, dd = text.split("-")
    return days_from_civil(int(y), int(m), int(dd))


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch("tiny")


@pytest.fixture(scope="module")
def oracle():
    conn = load_tpch_sqlite(SF)
    yield conn
    conn.close()


# ---------------------------------------------------------------- hoist pass


def test_hoist_numeric_comparison():
    e = Call("lt", (InputRef(0, T.BIGINT), Literal(24, T.BIGINT)),
             T.BOOLEAN)
    canon, values = hoist_literals(e)
    assert canon == Call("lt", (InputRef(0, T.BIGINT),
                                Param(0, T.BIGINT)), T.BOOLEAN)
    assert len(values) == 1
    assert values[0].dtype == np.dtype(np.int64)
    assert values[0].item() == 24
    # different literal, same canonical tree — the whole point
    canon2, values2 = hoist_literals(
        Call("lt", (InputRef(0, T.BIGINT), Literal(25, T.BIGINT)),
             T.BOOLEAN))
    assert canon2 == canon
    assert values2[0].item() == 25


def test_hoist_keeps_strings_nulls_booleans_static():
    vt = T.VARCHAR
    e = SpecialForm(SpecialKind.AND, (
        Call("eq", (InputRef(0, vt), Literal("FOO", vt)), T.BOOLEAN),
        Call("eq", (InputRef(1, T.BIGINT), Literal(None, T.BIGINT)),
             T.BOOLEAN),
        Literal(True, T.BOOLEAN)), T.BOOLEAN)
    canon, values = hoist_literals(e)
    assert canon == e           # nothing hoistable
    assert values == ()


def test_hoist_respects_static_call_annotations():
    vt = T.VARCHAR
    # LIKE pattern + escape stay literal (host like-table)
    like = Call("like", (InputRef(0, vt), Literal("F%", vt)), T.BOOLEAN)
    assert hoist_literals(like)[0] == like
    # substr is fully static, numeric args included (host dict transform)
    sub = Call("substr", (InputRef(0, vt), Literal(1, T.BIGINT),
                          Literal(2, T.BIGINT)), vt)
    assert hoist_literals(sub)[0] == sub
    # date_add: the unit string is static, the count hoists
    da = Call("date_add", (Literal("day", vt), Literal(3, T.BIGINT),
                           InputRef(0, T.DATE)), T.DATE)
    canon, values = hoist_literals(da)
    assert canon.args[0] == Literal("day", vt)
    assert canon.args[1] == Param(0, T.BIGINT)
    assert values[0].item() == 3


def test_hoist_seq_shares_one_numbering():
    es = (Call("add", (InputRef(0, T.BIGINT), Literal(1, T.BIGINT)),
               T.BIGINT),
          Call("multiply", (InputRef(0, T.BIGINT), Literal(2, T.BIGINT)),
               T.BIGINT))
    canon, values = hoist_literal_seq(es)
    assert canon[0].args[1] == Param(0, T.BIGINT)
    assert canon[1].args[1] == Param(1, T.BIGINT)
    assert [v.item() for v in values] == [1, 2]


def test_hoist_decimal_scaled_int_value():
    dt = T.DecimalType(12, 2)
    canon, values = hoist_literals(Literal(605, dt))   # 6.05 scaled
    assert canon == Param(0, dt)
    assert values[0].dtype == np.dtype(dt.dtype)
    assert values[0].item() == 605


# --------------------------------------------------------------- jit cache


def test_param_hit_and_eviction_counters():
    """cached_kernel attribution: same canonical key + new values = a
    param hit; LRU overflow counts evictions. Runs against a scratch
    cache snapshot so the suite's warm kernels survive."""
    with jit_cache._LOCK:
        saved = list(jit_cache._CACHE.items())
        saved_max = jit_cache._MAX_KERNELS
        jit_cache._CACHE.clear()
        jit_cache._MAX_KERNELS = 2
    base = jit_cache.stats()
    try:
        def build():
            return lambda x, p: x
        jit_cache.cached_kernel(("ph-k1",), build, params=(np.int64(1),))
        jit_cache.cached_kernel(("ph-k1",), build, params=(np.int64(1),))
        s = jit_cache.stats()
        assert s["param_hits"] == base["param_hits"]      # same values
        jit_cache.cached_kernel(("ph-k1",), build, params=(np.int64(2),))
        s = jit_cache.stats()
        assert s["param_hits"] == base["param_hits"] + 1  # new values
        # overflow the shrunken LRU: 3rd distinct key evicts the oldest
        jit_cache.cached_kernel(("ph-k2",), build)
        jit_cache.cached_kernel(("ph-k3",), build)
        s = jit_cache.stats()
        assert s["evictions"] >= base["evictions"] + 1
    finally:
        with jit_cache._LOCK:
            jit_cache._MAX_KERNELS = saved_max
            jit_cache._CACHE.clear()
            jit_cache._CACHE.update(saved)


def test_jit_cache_metrics_exported(runner):
    from trino_tpu.obs.metrics import REGISTRY
    runner.execute("SELECT count(*) FROM region")
    text = REGISTRY.render()
    assert "trino_tpu_jit_cache_param_hits" in text
    assert "trino_tpu_jit_cache_evictions_total" in text


def test_compilation_cache_env_var(monkeypatch, tmp_path):
    """One rule, no knob: with $JAX_COMPILATION_CACHE_DIR set the engine
    leaves the directory to JAX (no config.update of it in code); unset,
    the cache goes to the fixed <checkout>/.jax_cache."""
    import os
    import jax
    import trino_tpu
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val)))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        trino_tpu.enable_persistent_cache()
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        trino_tpu.enable_persistent_cache()
        assert "jax_compilation_cache_dir" in updates
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(trino_tpu.__file__)))
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            checkout, ".jax_cache")
    finally:
        real_update("jax_compilation_cache_dir", before)
        real_update("jax_persistent_cache_min_compile_time_secs", min_secs)


# ------------------------------------------------- TPC-H literal variants
#
# Engine-SQL rewrites perturbing every hoistable numeric/date constant.
# Static-by-design constants are deliberately NOT touched: LIKE patterns,
# string literals, substring positions, LIMIT/TopN counts, interval UNIT
# strings (the counts inside INTERVAL '<n>' do hoist). Queries absent
# here have no hoistable constants (q9/q13/q21: strings + LIKE only) —
# their "variant" is the identical statement, which must hit outright.

PERTURB = {
    "q1": [("INTERVAL '90' DAY", "INTERVAL '60' DAY")],
    "q2": [("p_size = 15", "p_size = 14")],
    "q3": [("DATE '1995-03-15'", "DATE '1995-03-08'")],
    "q4": [("DATE '1993-07-01'", "DATE '1993-08-01'")],
    "q5": [("DATE '1994-01-01'", "DATE '1995-01-01'")],
    "q6": [("DATE '1994-01-01'", "DATE '1995-01-01'"),
           ("0.06", "0.07"),
           ("l_quantity < 24", "l_quantity < 25")],
    "q7": [("DATE '1995-01-01'", "DATE '1995-02-01'"),
           ("DATE '1996-12-31'", "DATE '1996-11-30'")],
    "q8": [("DATE '1995-01-01'", "DATE '1995-02-01'"),
           ("DATE '1996-12-31'", "DATE '1996-11-30'")],
    "q10": [("DATE '1993-10-01'", "DATE '1993-11-01'")],
    "q11": [("0.0001", "0.0002")],
    "q12": [("DATE '1994-01-01'", "DATE '1995-01-01'")],
    "q14": [("DATE '1995-09-01'", "DATE '1995-04-01'"),
            ("DATE '1995-10-01'", "DATE '1995-05-01'")],
    "q15": [("DATE '1996-01-01'", "DATE '1996-04-01'")],
    "q16": [("(49, 14, 23, 45, 19, 3, 36, 9)",
             "(48, 15, 22, 44, 18, 4, 35, 8)")],
    "q17": [("0.2 * avg", "0.3 * avg")],
    "q18": [("sum(l_quantity) > 200", "sum(l_quantity) > 250")],
    "q19": [("l_quantity >= 1 AND l_quantity <= 11",
             "l_quantity >= 2 AND l_quantity <= 12"),
            ("l_quantity >= 10 AND l_quantity <= 20",
             "l_quantity >= 11 AND l_quantity <= 21"),
            ("l_quantity >= 20 AND l_quantity <= 30",
             "l_quantity >= 21 AND l_quantity <= 31"),
            # upper bound only: `p_size >= 1` is a conjunct COMMON to all
            # three OR branches, which the optimizer extracts into a
            # pushed-down scan filter — perturbing one branch's lower
            # bound breaks the extraction and legitimately changes plan
            # structure (a different shape, not a hoisting gap)
            ("p_size BETWEEN 1 AND 5", "p_size BETWEEN 1 AND 6")],
    "q20": [("0.5 * sum", "0.6 * sum"),
            ("DATE '1994-01-01'", "DATE '1995-01-01'")],
    "q22": [("c_acctbal > 0.00", "c_acctbal > 1.00")],
}


def variant_sql(name: str) -> str:
    sql = QUERIES[name][0]
    for old, new in PERTURB.get(name, []):
        assert old in sql, f"{name}: perturbation target {old!r} not found"
        sql = sql.replace(old, new)
    return sql


@pytest.mark.parametrize("name", list(QUERIES))
def test_literal_variant_zero_jit_misses(runner, name):
    """Acceptance: base literals warm the canonical kernels; the
    perturbed-literal re-run must dispatch ONLY warm executables."""
    engine_sql = QUERIES[name][0]
    runner.execute(engine_sql)
    runner.execute(variant_sql(name))
    stats = runner.last_query_stats
    assert stats["jit_misses"] == 0, (
        f"{name}: literal variant recompiled {stats['jit_misses']} "
        f"kernels (hoisting gap)")
    if PERTURB.get(name):
        assert stats["jit_param_hits"] > 0, (
            f"{name}: perturbed constants never reached a kernel as "
            f"parameters — are they being hoisted at all?")


# parity subset: shapes covering fused filter/project chains, residual
# join filters (q19), HAVING over aggregation (q18/q11), correlated
# scalar subqueries (q17/q20), semi/anti joins (q22)
PARITY = ["q1", "q3", "q6", "q7", "q11", "q12", "q14", "q17", "q18",
          "q19", "q20", "q22"]


@pytest.mark.parametrize("name", PARITY)
def test_hoisted_rows_match_unhoisted(runner, name):
    """The hoisted execution of a perturbed-literal query must be
    row-identical to hoist_literals=false — the literal-embedding
    pre-hoisting code path that test_queries.py oracle-verifies."""
    sql = variant_sql(name)
    ordered = QUERIES[name][2]
    hoisted = runner.execute(sql)
    runner.session.set("hoist_literals", False)
    try:
        unhoisted = runner.execute(sql)
    finally:
        runner.session.properties.pop("hoist_literals", None)
    assert_same(hoisted.rows, unhoisted.rows, ordered)


def test_variant_oracle_parity_q6(runner, oracle):
    got = runner.execute(variant_sql("q6"))
    expected = oracle.execute(f"""
        SELECT sum(l_extendedprice * l_discount) FROM lineitem
        WHERE l_shipdate >= {d('1995-01-01')}
          AND l_shipdate < {d('1996-01-01')}
          AND l_discount BETWEEN 6 AND 8 AND l_quantity < 2500
        """).fetchall()
    assert_same(got.rows, expected, ordered=False)


def test_variant_oracle_parity_q18(runner, oracle):
    got = runner.execute(variant_sql("q18"))
    oracle_sql = QUERIES["q18"][1].replace(
        "sum(l_quantity) > 20000", "sum(l_quantity) > 25000")
    expected = oracle.execute(oracle_sql).fetchall()
    assert_same(got.rows, expected, ordered=True)


def test_round_digits_hoists_trace_safe(runner):
    """round(int_col, d) used Python `if d >= 0` control flow on the
    digits argument, which fails at trace time now that d arrives as a
    traced scalar (pre-existing break the hoisting whitelist audit
    surfaced — it failed under the chain kernel's trace even with the
    constant embedded). The jnp rewrite must round correctly for both
    signs of d and share one kernel across digit values."""
    got = runner.execute(
        "SELECT o_orderkey, round(o_orderkey, -2), round(o_orderkey, 1) "
        "FROM orders ORDER BY o_orderkey LIMIT 50").rows
    for k, rm2, rp1 in got:
        scaled = (abs(k) + 50) // 100 * 100
        assert rm2 == (scaled if k >= 0 else -scaled)
        assert rp1 == k                       # d >= 0: identity on ints
    # same shape, different digits: one kernel (digits are hoisted)
    runner.execute(
        "SELECT round(o_orderkey, -2) FROM orders ORDER BY o_orderkey "
        "LIMIT 50")
    runner.execute(
        "SELECT round(o_orderkey, -3) FROM orders ORDER BY o_orderkey "
        "LIMIT 50")
    assert runner.last_query_stats["jit_misses"] == 0


def test_hoist_literals_off_compiles_per_literal(runner):
    """The debugging pin: with hoisting off, a fresh literal value is a
    fresh cache key — the query pays compiles again."""
    runner.session.set("hoist_literals", False)
    try:
        runner.execute(
            "SELECT count(*) FROM lineitem WHERE l_quantity < 17")
        first = runner.last_query_stats["jit_misses"]
        assert first > 0
        runner.execute(
            "SELECT count(*) FROM lineitem WHERE l_quantity < 18")
        assert runner.last_query_stats["jit_misses"] > 0
    finally:
        runner.session.properties.pop("hoist_literals", None)
    # back on: yet another literal reuses the canonical kernel
    runner.execute("SELECT count(*) FROM lineitem WHERE l_quantity < 16")
    runner.execute("SELECT count(*) FROM lineitem WHERE l_quantity < 19")
    assert runner.last_query_stats["jit_misses"] == 0
