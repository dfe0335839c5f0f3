"""TPC-H Q18 and Q4 through the served path at `tiny`: the CPU twin of the
benchmark cell `sf10-power-q18-q4` (PR 37).

The requests are the cell's own (`benchmark/traffic/power-q18-q4.json`
through the one traffic generator), every answer is compared with the
benchmark's NumPy reference (`benchmark/queries/q18.py`, `q4.py` over
`tpch_columns_q18_q4.py`, independent of the engine), and the second cycle
must find every kernel compiled: QUANTITY and DATE reach them as operands.
At `tiny` no order reaches the specification's QUANTITY of 312..315, so
the test sends 250..253, which keeps a few dozen groups.
"""

import json
import os
import sys

import numpy as np
import pytest

from trino_tpu.connector import tpch_gen as G
from trino_tpu.exec import LocalQueryRunner
from trino_tpu.server import TrinoServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import loadgen                      # noqa: E402
import reference                    # noqa: E402
import tpch_columns as C            # noqa: E402
import tpch_columns_q18_q4 as X     # noqa: E402
import traffic_gen                  # noqa: E402

SEED, CYCLES, TINY = 2147483929, 2, 0.01
QUANTITY_AT_TINY = -62              # 312..315 -> 250..253


# ------------------------------------------------------------ the columns

@pytest.mark.parametrize("sf", [0.01, 10.0], ids=["tiny", "sf10"])
def test_the_new_columns_equal_the_engines_generator(sf):
    """The reference's five columns are the engine's, value for value, on
    the first 4 096 orders (and their lineitems, and customers)."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    chunk = C.orders_chunk(sf, 0, n)
    customer = C.customer(sf)
    got = X.of_chunk(chunk, customer)
    rows = len(chunk["l_orderkey"])
    for column, table, count in (("o_totalprice", "orders", n),
                                 ("l_commitdate", "lineitem", rows),
                                 ("l_receiptdate", "lineitem", rows)):
        want = G.numeric_chunk(table, sf, column, 0, count)
        assert np.array_equal(got[column], want), column
    codes = G.codes_chunk("orders", sf, "o_orderpriority", 0, n)
    assert np.array_equal(got["o_orderpriority"], codes)
    assert list(G.pool_values("orders", "o_orderpriority", sf)) \
        == list(X.PRIORITIES)
    keys = customer["c_custkey"][:n]
    assert X.c_name(keys) == list(
        G.object_chunk("customer", sf, "c_name", 0, len(keys)))


def test_a_later_chunk_takes_its_rows_from_where_the_last_one_ended():
    """`partial` is handed no row index: the module counts the lines
    before a chunk once, and not again for the chunk that follows it."""
    customer = C.customer(TINY)
    whole = X.of_chunk(C.orders_chunk(TINY, 0, 15000), customer)
    for first in (0, 4000, 8000, 12000):
        chunk = C.orders_chunk(TINY, first, first + 3000)
        part = X.of_chunk(chunk, customer)
        row = C.lineitem_rows_before(TINY, first)
        n = len(chunk["l_orderkey"])
        for column in ("l_commitdate", "l_receiptdate"):
            assert np.array_equal(part[column],
                                  whole[column][row:row + n]), first
        assert np.array_equal(part["o_totalprice"],
                              whole["o_totalprice"][first:first + 3000])
        assert X.of_chunk(chunk, customer) is part      # kept, not redone


def test_the_configuration_states_the_new_columns_fingerprint():
    with open(os.path.join(BENCH, "configs",
                           "tpch-sf10-1chip-q18-q4.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint"] == C.fingerprint(10.0)
    assert config["data_fingerprint_q18_q4"] == X.fingerprint(10.0)
    assert X.fingerprint(TINY) != X.fingerprint(10.0)


# -------------------------------------------------------- the references

def test_q18_refuses_a_tie_at_its_sort_key():
    """The specification's ORDER BY ends at o_orderdate: two of the best
    that share (o_totalprice, o_orderdate) would make a row-for-row
    comparison a coin, so the reference refuses to answer."""
    q18 = reference.load_by_path("queries", "q18")
    a = (50000, 9000, 7, 70, 32000)
    b = (50000, 9001, 8, 80, 33000)
    rows = q18.merge([[a], [b]], {"quantity": 313})
    assert [r[2] for r in rows] == [7, 8]
    assert rows[0] == ["Customer#000000070", 70, 7, "1994-08-23",
                       "500.00", "320.00"]
    with pytest.raises(AssertionError, match="o_totalprice, o_orderdate"):
        q18.merge([[a], [(50000, 9000, 9, 90, 34000)]], {"quantity": 313})


@pytest.mark.parametrize("date, lo, hi", [
    ("1993-01-01", "1993-01-01", "1993-04-01"),
    ("1995-10-01", "1995-10-01", "1996-01-01"),
    ("1997-11-01", "1997-11-01", "1998-02-01")])
def test_q4_quarter_is_three_calendar_months(date, lo, hi):
    q4 = reference.load_by_path("queries", "q4")
    assert q4.quarter(date) == (C.days(lo), C.days(hi))
    assert len(q4.DOMAIN["date"]) == 58
    assert q4.DOMAIN["date"][0] == "1993-01-01"
    assert q4.DOMAIN["date"][-1] == "1997-10-01"


def test_q4_merge_leaves_out_a_priority_nobody_has():
    q4 = reference.load_by_path("queries", "q4")
    assert q4.merge([[1, 0, 2, 0, 0], [3, 0, 0, 0, 1]], {}) \
        == [["1-URGENT", 4], ["3-MEDIUM", 2], ["5-LOW", 1]]


# ------------------------------------------------------- the served path

@pytest.fixture(scope="module")
def served():
    """Two cycles of the cell's own stream (q18, q4, q18, q4) at `tiny`,
    each request with its rows, its query info and the reference's rows."""
    plan = traffic_gen.make_plan(
        traffic_gen.load_traffic("power-q18-q4"), SEED, 60)
    assert plan["cycle"] == 2 and plan["n_clients"] == 1
    requests = []
    for shape, params in plan["clients"][0][:2 * CYCLES]:
        if shape == "q18":
            params = {"quantity": params["quantity"] + QUANTITY_AT_TINY}
        requests.append({"shape": shape, "params": params})
    assert [r["shape"] for r in requests] == ["q18", "q4"] * CYCLES
    stmts = loadgen.Statements(plan)
    server = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    conn = loadgen.Conn("127.0.0.1", server.port, "q18-q4")
    try:
        for r in requests:
            sql, headers = stmts.build(r["shape"], r["params"])
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


@pytest.mark.parametrize("i", range(2 * CYCLES))
def test_every_answer_equals_the_reference(served, i):
    r = served[i]
    assert r["error"] is None, r["error"]
    assert reference.compare(r["rows"], r["want"]) == "", \
        (r["shape"], r["params"])
    assert r["stats"]["result_cache_hits"] == 0


@pytest.mark.parametrize("i", range(0, 2 * CYCLES, 2))
def test_q18s_having_keeps_some_groups_and_not_all(served, i):
    r = served[i]
    assert 1 <= len(r["rows"]) <= 100
    # the IN's build is what the HAVING kept, its probe every order
    assert 1 <= r["stats"]["semi_join_build_rows"] <= 200
    assert len(r["rows"]) == r["stats"]["semi_join_build_rows"]
    assert r["stats"]["semi_join_probe_rows"] == C.order_count(TINY)
    prices = [float(row[4]) for row in r["rows"]]
    assert prices == sorted(prices, reverse=True)
    for row in r["rows"]:
        assert row[0] == f"Customer#{row[1]:09d}"
        assert float(row[5]) > r["params"]["quantity"]


@pytest.mark.parametrize("i", range(2, 2 * CYCLES))
def test_the_second_cycle_compiles_nothing(served, i):
    """QUANTITY and DATE differ from the first cycle's and reach the
    kernels as operands (expr/hoist.py): no kernel is built again."""
    first, r = served[i - 2], served[i]
    assert first["params"] != r["params"]
    assert r["stats"]["jit_misses"] == 0, r["shape"]
    assert r["stats"]["jit_compiles"] == 0
    assert r["stats"]["spilled_bytes"] == 0
    assert r["stats"]["spill_fallbacks"] == 0


@pytest.mark.parametrize("i", range(2 * CYCLES))
def test_the_sorted_reduce_runs_in_q18_and_not_in_q4(served, i):
    """Q18's GROUP BYs (15 000 groups, then the few dozen the HAVING
    kept) take the sorted path, whose reduce is the segmented scan
    (PR 40): a PARTIAL chain a page and a FINAL kernel each. Q4 groups by
    five pooled values: the direct path, no sorted reduce."""
    stats = served[i]["stats"]
    if served[i]["shape"] == "q18":
        assert stats["sorted_reduces_scanned"] >= 2
        assert stats["sorted_reduce_lanes"] > 0
    else:
        assert stats["sorted_reduces_scanned"] == 0
        assert stats["sorted_reduce_lanes"] == 0


@pytest.mark.parametrize("i", range(2 * CYCLES))
def test_q18s_lanes_arrive_in_key_order_and_are_not_sorted(served, i):
    """lineitem is stored in `l_orderkey` order, and so are the partial
    pages' states that FINAL merges: the inner GROUP BY's pages are found
    in order on the device and run with no sort and no gather (PR 45);
    the outer one's few dozen rows, in the join's order, sort. Every lane
    a sorted reduce ran over is booked to the one or the other. Q4 has
    no sorted GROUP BY."""
    stats = served[i]["stats"]
    assert stats["group_by_lanes_in_order"] \
        + stats["group_by_lanes_sorted"] == stats["sorted_reduce_lanes"]
    if served[i]["shape"] == "q18":
        assert stats["group_by_lanes_in_order"] \
            > 9 * stats["group_by_lanes_sorted"] > 0
    else:
        assert stats["group_by_lanes_in_order"] == 0
        assert stats["group_by_lanes_sorted"] == 0


@pytest.mark.parametrize("i", range(2 * CYCLES))
def test_the_semi_joins_build_set_tables_and_sort_no_lane(served, i):
    """Q4's EXISTS asks whether an order has a late line, not where it
    sorts: its build — lineitem's lanes as the scan left them — goes
    into a set table by one scatter (PR 46), and nothing of it is
    sorted. At `tiny` Q18's IN (the few dozen orders its HAVING kept,
    spanning under 2^20 keys) gets one too, as it got a position table
    before; at SF10 those keys span 15 M and the router reads `search`
    (tests/test_join_shapes.py: `semi-past-the-fill-rule`)."""
    stats = served[i]["stats"]
    assert stats["probe_lookups_set_table"] == 1
    assert stats["probe_lookups_position_table"] == 0
    assert stats["probe_lookups_search"] == 0
    assert stats["semi_build_lanes_sorted"] == 0
    lanes = stats["semi_build_lanes_set"]
    assert lanes > 0 and lanes & (lanes - 1) == 0
    if served[i]["shape"] == "q4":
        # the late lines' page keeps the scan page's lanes
        assert lanes >= stats["semi_join_build_rows"] > 30000
    else:
        assert lanes >= stats["semi_join_build_rows"]


def test_q18_over_a_shuffled_lineitem_answers_the_same_and_sorts(served):
    """The same rows in another order (a copy of lineitem in the memory
    connector, ordered by price): the order test fails on the scan's
    page, the sorting branch runs, and the answer is the one in key
    order. The table is ONE page at `tiny`, and a sorted GROUP BY's
    output is in key order: FINAL, over that one partial page's states,
    still finds its lanes in order — nothing else does."""
    q18 = reference.load_by_path("queries", "q18")
    runner = LocalQueryRunner.tpch("tiny")
    runner.execute("DROP TABLE IF EXISTS memory.default.l18")
    runner.execute(
        "CREATE TABLE memory.default.l18 AS SELECT l_orderkey, l_quantity "
        "FROM lineitem ORDER BY l_extendedprice, l_orderkey")
    keys = [r[0] for r in runner.execute(
        "SELECT l_orderkey FROM memory.default.l18").rows]
    assert keys != sorted(keys) and len(keys) == C.lineitem_rows_before(
        TINY, C.order_count(TINY))
    first = served[0]
    sql = q18.SQL.format(**first["params"])
    assert sql.count(" lineitem") == 2
    got = runner.execute(sql.replace(" lineitem", " memory.default.l18"))
    stats = runner.last_query_stats
    assert stats["group_by_lanes_sorted"] >= len(keys)      # the scan's page
    assert stats["group_by_lanes_in_order"] <= 15000 * 2    # FINAL's alone
    assert stats["group_by_lanes_in_order"] \
        + stats["group_by_lanes_sorted"] == stats["sorted_reduce_lanes"]
    # the same query over the table in key order, on this runner: the
    # served answer, which the reference has checked
    want = runner.execute(sql)
    assert got.rows == want.rows and len(want.rows) == len(first["rows"])
    assert [int(r[2]) for r in want.rows] == [r[2] for r in first["rows"]]
    assert runner.last_query_stats["group_by_lanes_in_order"] \
        == first["stats"]["group_by_lanes_in_order"] >= len(keys)


def test_q18s_in_is_planned_under_the_join_on_orders():
    """The IN's semi join runs on orders, below both joins (optimizer
    rule PushSemiJoinThroughJoin): the joins see the orders the HAVING
    left, not lineitem x orders x customer."""
    q18 = reference.load_by_path("queries", "q18")
    runner = LocalQueryRunner.tpch("tiny")
    plan = "\n".join(row[0] for row in runner.execute(
        "EXPLAIN " + q18.SQL.format(quantity=250)).rows).splitlines()
    semi = next(i for i, line in enumerate(plan) if "SemiJoin[" in line)
    assert "TableScan[tpch.tiny.orders]" in plan[semi + 1]
    assert "Filter[match" in plan[semi - 1]
    assert sum("Join[inner" in line for line in plan[:semi]) == 2
    assert not any("Join[inner" in line for line in plan[semi:])


# ------------------------------------------------------ the table cache

def test_a_newcomer_pushes_out_colder_entries_and_never_hotter_ones():
    """At SF10 two lineitem columns at their pow2 envelope are the table
    cache's whole budget (2^26 lanes x 16 B = 1 GiB): admitted on their
    second scan they wiped the resident orders and customer, Q4's set
    wiped them in turn, and every round was a full-length copy and a new
    page shape to compile for (step 0 of PR 37: a second Q18 took 74.7 s
    against 26.4 s). An entry now pushes out what scores below it and
    nothing else, and is refused from shapes alone before any copy."""
    from trino_tpu.exec import table_cache
    from trino_tpu.exec.table_cache import ResidentTable, TableCache

    def entry(name, nbytes, freq, last_used):
        return ResidentTable(("tpch", "sf10", name), {}, 1, nbytes, None,
                             freq, last_used)
    cache = TableCache(max_bytes=100, min_scans=2)
    try:
        assert cache._admit(entry("orders", 45, 3, 5.0), frozenset("o"))
        assert cache._admit(entry("customer", 5, 1, 6.0), frozenset("c"))
        denied = table_cache.table_cache_stats()["admission_denied"]
        # the whole budget, used once: orders (three scans) stays
        assert not cache._admit(entry("lineitem", 100, 1, 7.0),
                                frozenset("l"))
        assert table_cache.table_cache_stats()["admission_denied"] \
            == denied + 1
        assert {k[0][2] for k in cache._entries} == {"orders", "customer"}
        # what fits beside orders once the colder customer is gone
        assert cache._admit(entry("part", 55, 1, 8.0), frozenset("p"))
        assert {k[0][2] for k in cache._entries} == {"orders", "part"}
        assert cache.resident_bytes == 100
        # shapes alone: no column is built for what cannot be admitted
        with cache._lock:
            assert not cache._room_locked(1, (1, 0.0))
            assert cache._room_locked(55, (1, 9.0))
    finally:
        cache.clear()


def test_a_collected_scan_page_counts_its_rows_as_a_concatenation_does():
    """One scan page or two, a blocking operator's input has one
    signature: the count is an int32 either way, so the program it feeds
    is traced once (step 0 of PR 37: Q18's customer build came as two
    pages of 2^20 lanes cold and as one of 2^21 warm, and compiled
    `join__join_prep` again, 37 s inside the window)."""
    import jax
    import jax.numpy as jnp
    from trino_tpu import types as T
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    from trino_tpu.page import Page
    runner = LocalQueryRunner.tpch("tiny")
    planner = LocalExecutionPlanner.__new__(LocalExecutionPlanner)
    planner.session = runner.session
    whole = Page.from_numpy([np.arange(2048)], [T.BIGINT])
    whole = Page(whole.columns, 1500)                   # as a scan makes it
    halves = [Page((whole.columns[0].gather(jnp.arange(1024) + off),), n)
              for off, n in ((0, 1024), (1024, 476))]
    one = planner._merge_buf([whole], 1500)
    two = planner._merge_buf(halves, 1500)
    traces = []

    @jax.jit
    def program(page):
        traces.append(1)
        return page.row_mask().sum()
    assert int(program(one)) == int(program(two)) == 1500
    assert len(traces) == 1
    assert one.capacity == two.capacity == 2048
