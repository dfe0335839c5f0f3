"""TPC-H Q9 through the served path at `tiny`: the CPU twin of the benchmark
cell `sf10-power-q9` (PR 42).

The requests are the cell's own (`benchmark/traffic/power-q9.json` through
the one traffic generator: a COLOR per request), every answer is compared
with the benchmark's NumPy reference (`benchmark/queries/q9.py` over
`tpch_columns_q9.py`, independent of the engine), and the second and later
COLORs must find every kernel compiled: the LIKE table over `p_name`'s
dictionary reaches the part filter as an operand (expr/hoist.py). Beside
it: the operand path against the static one on an escape, a NULL and an
empty dictionary; the two-column join with every hash made to collide;
the three new counters.
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.connector import tpch_gen as G
from trino_tpu.exec import LocalQueryRunner, jit_cache
from trino_tpu.server import TrinoServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import loadgen                      # noqa: E402
import reference                    # noqa: E402
import tpch_columns as C            # noqa: E402
import tpch_columns_q9 as X         # noqa: E402
import traffic_gen                  # noqa: E402

SEED, REQUESTS, TINY = 2147483941, 6, 0.01


# ------------------------------------------------------------ the columns

@pytest.mark.parametrize("sf", [0.01, 10.0], ids=["tiny", "sf10"])
def test_the_new_columns_equal_the_engines_generator(sf):
    """The reference's streams are the engine's, value for value, on the
    first 4 096 orders' lines, parts (with their four partsupp rows) and
    suppliers."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    rows = C.lineitem_rows_before(sf, n)
    got = X.lineitem_keys(sf, 0, rows)
    for column in ("l_partkey", "l_suppkey"):
        want = G.numeric_chunk("lineitem", sf, column, 0, rows)
        assert np.array_equal(got[column], want), column
    parts = min(C.FINGERPRINT_ORDERS, X.part_count(sf))
    pk = np.arange(1, parts + 1)
    assert X.part_count(sf) == G.row_count("part", sf)
    assert X.supplier_count(sf) == G.row_count("supplier", sf)
    assert 4 * X.part_count(sf) == G.row_count("partsupp", sf)
    for column, mine in (("ps_suppkey", X.ps_suppkey),
                         ("ps_supplycost", X.ps_supplycost)):
        want = G.numeric_chunk("partsupp", sf, column, 0, 4 * parts)
        for i in range(4):
            assert np.array_equal(mine(pk, i, sf), want[i::4]), (column, i)
    assert np.array_equal(G.numeric_chunk("partsupp", sf, "ps_partkey", 0,
                                          4 * parts), np.repeat(pk, 4))
    supps = min(C.FINGERPRINT_ORDERS, X.supplier_count(sf))
    assert np.array_equal(
        X.s_nationkey(np.arange(1, supps + 1), sf),
        G.numeric_chunk("supplier", sf, "s_nationkey", 0, supps))
    names = G.pool_values("part", "p_name", sf)
    codes = G.codes_chunk("part", sf, "p_name", 0, parts)
    assert X.p_name(pk, sf) == [names[c] for c in codes]
    assert len(names) == len(X.COLORS) ** 2 == 8464
    assert list(G.pool_values("nation", "n_name", sf)) == sorted(X.NATIONS)
    nations = G.pool_values("nation", "n_name", sf)[
        G.codes_chunk("nation", sf, "n_name", 0, 25)]
    assert list(nations) == list(X.NATIONS)


def test_a_later_chunk_takes_its_rows_from_where_the_last_one_ended():
    customer = C.customer(TINY)
    whole = X.lineitem_keys(TINY, 0, C.lineitem_rows_before(TINY, 15000))
    for first in (0, 4000, 8000, 12000):
        chunk = C.orders_chunk(TINY, first, first + 3000)
        sf, part = X.of_chunk(chunk, customer)
        row = C.lineitem_rows_before(TINY, first)
        n = len(chunk["l_orderkey"])
        assert sf == TINY
        for column in ("l_partkey", "l_suppkey"):
            assert np.array_equal(part[column],
                                  whole[column][row:row + n]), first
        assert X.of_chunk(chunk, customer)[1] is part   # kept, not redone


def test_the_configuration_states_the_new_columns_fingerprint():
    with open(os.path.join(BENCH, "configs",
                           "tpch-sf10-1chip-q9.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint"] == C.fingerprint(10.0)
    assert config["data_fingerprint_q9"] == X.fingerprint(10.0)
    assert X.fingerprint(TINY) != X.fingerprint(10.0)
    assert config["rows"] == {
        k: v for k, v in X.row_counts(10.0).items() if k != "customer"}


# ---------------------------------------------------------- the reference

def test_color_matches_183_of_the_8464_names():
    q9 = reference.load_by_path("queries", "q9")
    assert q9.DOMAIN["color"] == list(X.COLORS) and len(X.COLORS) == 92
    for color in ("green", "almond", "yellow"):
        assert int(q9.matches(color).sum()) == 183
    # no word lies inside another, so every COLOR keeps 183 names
    for color in X.COLORS:
        assert sum(color in w for w in X.COLORS) == 1, color
        assert int(q9.matches(color).sum()) == 183, color


def test_merge_orders_by_nation_and_year_descending():
    q9 = reference.load_by_path("queries", "q9")
    g = lambda nation, year: X.NATIONS.index(nation) * 7 + year - 1992
    rows = q9.merge([[[g("PERU", 1995), 10500, 2], [g("CHINA", 1992), 7, 1]],
                     [[g("PERU", 1995), -20000, 1],
                      [g("PERU", 1998), 123456789, 4]]], {"color": "green"})
    assert rows == [["CHINA", 1992, "0.0007"], ["PERU", 1998, "12345.6789"],
                    ["PERU", 1995, "-0.9500"]]


# ------------------------------------------------------- the served path

@pytest.fixture(scope="module")
def served():
    """The first six requests of the cell's own stream at `tiny`, each
    with its rows, its query info and the reference's rows."""
    plan = traffic_gen.make_plan(
        traffic_gen.load_traffic("power-q9"), SEED, 60)
    assert plan["cycle"] == 1 and plan["n_clients"] == 1
    requests = [{"shape": shape, "params": params}
                for shape, params in plan["clients"][0][:REQUESTS]]
    assert {r["shape"] for r in requests} == {"q9"}
    assert len({r["params"]["color"] for r in requests}) == REQUESTS
    stmts = loadgen.Statements(plan)
    server = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    conn = loadgen.Conn("127.0.0.1", server.port, "q9")
    try:
        for r in requests:
            sql, headers = stmts.build(r["shape"], r["params"])
            assert f"'%{r['params']['color']}%'" in sql
            assert "result_cache_enabled=false" in headers["X-Trino-Session"]
            r.update(conn.statement(sql, headers))
            r["stats"] = conn.get(f"/v1/query/{r['qid']}")["stats"]
    finally:
        conn.close()
        server.stop()
    keys = [(r["shape"], r["params"]) for r in requests]
    for r, want in zip(requests, reference.compute(TINY, keys, 2)):
        r["want"] = want
    return requests


@pytest.mark.parametrize("i", range(REQUESTS))
def test_every_answer_equals_the_reference(served, i):
    r = served[i]
    assert r["error"] is None, r["error"]
    assert reference.compare(r["rows"], r["want"]) == "", r["params"]
    assert 100 < len(r["rows"]) <= 175
    assert r["stats"]["result_cache_hits"] == 0
    keys = [(row[0], -row[1]) for row in r["rows"]]
    assert keys == sorted(keys)


@pytest.mark.parametrize("i", range(1, REQUESTS))
def test_a_new_color_compiles_nothing(served, i):
    """`like_pattern_operand` (server/app.CAPABILITIES): COLOR differs
    from every earlier request's and reaches the part filter as the operand of its LIKE table: the same executables, the
    change counted as a parameter hit."""
    r = served[i]
    assert r["stats"]["jit_misses"] == 0
    assert r["stats"]["jit_param_hits"] >= 1
    # at `tiny` a COLOR's 30 to 60 parts and 900 to 1 800 lines cross a
    # power of two from one request to the next, so a join downstream may
    # meet a page shape it has not seen; the filter over part never does
    # (at SF10 every COLOR keeps 183 of 8 464 names: 43 K parts, 1.3 M
    # lines, the same rungs)
    assert not any("chain_filter" in name
                   for name in r["stats"]["backend_compiled"])
    assert r["stats"]["spilled_bytes"] == 0
    assert r["stats"]["spill_fallbacks"] == 0


@pytest.mark.parametrize("i", range(REQUESTS))
def test_the_new_counters_read(served, i):
    """No cross join; one LIKE table a query (one dictionary, one
    pattern), built under the activity `like_table`; the join on
    (partkey, suppkey) alone goes through `search`, over partsupp's
    lanes."""
    stats = served[i]["stats"]
    assert stats["cross_joins"] == 0
    assert stats["like_tables_built"] == 1
    assert stats["host_calls"]["like_table"] == 1
    assert stats["host_ms"]["like_table"] > 0
    assert stats["probe_lookups_search"] == 1
    assert stats["probe_lookup_lanes_search"] == 8192     # 8 000 partsupp
    assert stats["probe_lookup_lanes"] > stats["probe_lookup_lanes_search"]


def test_q9_starts_from_the_filtered_part_and_holds_no_cross_join():
    q9 = reference.load_by_path("queries", "q9")
    runner = LocalQueryRunner.tpch("tiny")
    plan = "\n".join(row[0] for row in runner.execute(
        "EXPLAIN " + q9.SQL.format(color="green")).rows)
    assert "Join[cross" not in plan and plan.count("Join[inner") == 5


# ------------------------------------------ the LIKE table as an operand

@pytest.fixture(scope="module")
def strings():
    runner = LocalQueryRunner.tpch("tiny")
    runner.execute("CREATE TABLE memory.default.q9_like (k bigint, s varchar)")
    runner.execute(
        "INSERT INTO memory.default.q9_like VALUES (1, '50%'), (2, '50x'), "
        "(3, NULL), (4, 'a_b'), (5, 'axb'), (6, '100% sure')")
    runner.execute("CREATE TABLE memory.default.q9_empty (k bigint, s varchar)")
    yield runner
    runner.execute("DROP TABLE memory.default.q9_like")
    runner.execute("DROP TABLE memory.default.q9_empty")


LIKES = [
    ("q9_like", "s LIKE '50!%' ESCAPE '!'", [1]),
    ("q9_like", "s LIKE '%!%%' ESCAPE '!'", [1, 6]),
    ("q9_like", "s LIKE 'a!_b' ESCAPE '!'", [4]),
    ("q9_like", "s LIKE 'a_b'", [4, 5]),
    ("q9_like", "s NOT LIKE '50%'", [4, 5, 6]),        # the NULL drops
    ("q9_like", "s LIKE '%'", [1, 2, 4, 5, 6]),
    ("q9_like", "lower(s) LIKE '%x%'", [2, 5]),         # a derived dictionary
    ("q9_empty", "s LIKE '%green%'", []),
    ("q9_empty", "s NOT LIKE '%green%'", []),
]


@pytest.mark.parametrize("table, predicate, want", LIKES)
def test_like_as_an_operand_answers_as_the_static_table_did(
        strings, table, predicate, want):
    sql = f"SELECT k FROM memory.default.{table} WHERE {predicate} ORDER BY k"
    got = [row[0] for row in strings.execute(sql).rows]
    assert got == want
    strings.execute("SET SESSION hoist_literals = false")
    try:
        old = [row[0] for row in strings.execute(sql).rows]
    finally:
        strings.execute("SET SESSION hoist_literals = true")
    assert old == got


def test_two_patterns_share_one_chain_program(strings):
    sql = "SELECT k FROM memory.default.q9_like WHERE s LIKE '{}' ORDER BY k"
    strings.execute(sql.format("5%"))
    second = strings.execute(sql.format("%b"))
    assert [row[0] for row in second.rows] == [4, 5]
    stats = strings.last_query_stats
    assert stats["jit_misses"] == 0
    assert stats["jit_param_hits"] >= 1
    assert stats["like_tables_built"] == 1


def test_the_hoister_takes_like_on_the_chain_path_alone():
    from trino_tpu import types as T
    from trino_tpu.expr.hoist import LikeOperand, hoist_literals
    from trino_tpu.expr.ir import Call, InputRef, Literal, Param
    like = Call("like", (InputRef(0, T.VARCHAR), Literal("%green%",
                                                         T.VARCHAR)),
                T.BOOLEAN)
    canon, values = hoist_literals(like, like_operands=True)
    assert canon == Call("$like_table", (InputRef(0, T.VARCHAR),
                                         Param(0, T.BOOLEAN)), T.BOOLEAN)
    assert values == (LikeOperand("%green%", None),)
    other, _ = hoist_literals(Call("like", like.args[:1] + (
        Literal("%almond%", T.VARCHAR),), T.BOOLEAN), like_operands=True)
    assert other == canon                   # the key holds no pattern
    assert hoist_literals(like) == (like, ())       # elsewhere: static
    escaped = Call("like", like.args + (Literal("!", T.VARCHAR),), T.BOOLEAN)
    assert hoist_literals(escaped, like_operands=True)[1] \
        == (LikeOperand("%green%", "!"),)
    null = Call("like", (like.args[0], Literal(None, T.VARCHAR)), T.BOOLEAN)
    assert hoist_literals(null, like_operands=True) == (null, ())
    # the operand: one boolean a code, and a row to clip to where the
    # dictionary holds nothing
    from trino_tpu.page import Dictionary
    d = Dictionary(np.asarray(["dark green", "green", "grey"], dtype=object))
    assert LikeOperand("%green%").table(d).tolist() == [True, True, False]
    empty = Dictionary(np.asarray([], dtype=object))
    assert LikeOperand("%green%").table(empty).tolist() == [False]
    assert jit_cache._param_signature(((LikeOperand("a%", None),),)) \
        != jit_cache._param_signature(((LikeOperand("b%", None),),))


# ------------------------------------------------ the two-column join key

def test_the_two_column_join_survives_every_hash_colliding(monkeypatch):
    """A key of two columns is mix-hashed to 64 bits, so equal hashes do
    not make equal keys: with `_mix64` made to return 0 for everything,
    every probe row meets every build row as a candidate, and
    `join__composite_verify` leaves what the reference has."""
    import trino_tpu.ops.join as J
    sql = ("SELECT count(*), sum(ps_supplycost), sum(l_quantity) "
           "FROM partsupp, lineitem WHERE ps_partkey = l_partkey "
           "AND ps_suppkey = l_suppkey AND l_orderkey <= 64 "
           "AND ps_partkey <= 400")
    runner = LocalQueryRunner.tpch("tiny")
    honest = runner.execute(sql)
    assert runner.last_query_stats["probe_lookups_search"] == 1
    jit_cache.clear()
    monkeypatch.setattr(J, "_mix64", lambda x: jnp.zeros_like(
        x.astype(jnp.uint64)))
    try:
        collided = runner.execute(sql)
    finally:
        monkeypatch.undo()
        jit_cache.clear()
    assert collided.rows == honest.rows
    # the reference: lines of the first 64 orders whose part is under 401
    rows = C.lineitem_rows_before(TINY, 64)
    keys = X.lineitem_keys(TINY, 0, rows)
    keep = keys["l_partkey"] <= 400
    pk, sk = keys["l_partkey"][keep], keys["l_suppkey"][keep]
    quantity = C.orders_chunk(TINY, 0, 64)["l_quantity"][keep]
    count = cost = 0
    for i in range(4):
        hit = X.ps_suppkey(pk, i, TINY) == sk
        count += int(hit.sum())
        cost += int(X.ps_supplycost(pk[hit], i, TINY).sum())
    assert count == int(keep.sum()) > 0
    from wire import dec
    assert [list(map(str, honest.rows[0]))] \
        == [[str(count), dec(cost, 2), dec(int(quantity.sum()), 2)]]
