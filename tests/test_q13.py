"""TPC-H Q13 through the served path at `tiny`: the CPU twin of the
benchmark cell `sf10-power-q13` (PR 44).

The requests are the cell's own (`benchmark/traffic/power-q13.json` through
the one traffic generator: WORD1 and WORD2 per request), every answer is
compared with the benchmark's NumPy reference (`benchmark/queries/q13.py`
over `tpch_columns_q13.py`, independent of the engine), and the second and
later word pairs must find every kernel compiled: the `NOT LIKE` of the
join's ON clause is pushed onto the orders scan, where its table over
`o_comment`'s dictionary is an operand. Beside it: the LEFT join's edge
cases against a plain reference on seeded random tables, and the counter
of probe pages that ran twice.
"""

import collections
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
import tpch_columns_q13 as X        # noqa: E402
import traffic_gen                  # noqa: E402

SEED, REQUESTS, TINY = 2147484413, 6, 0.01
PAIRS = [(a, b) for a in X.WORD1 for b in X.WORD2]


# ------------------------------------------------------------- the column

@pytest.mark.parametrize("sf", [0.01, 10.0], ids=["tiny", "sf10"])
def test_the_comment_stream_equals_the_engines_generator(sf):
    """The reference's `o_comment` is the engine's, value for value, on
    the first 4 096 orders, and its pool is the engine's dictionary."""
    n = min(C.FINGERPRINT_ORDERS, C.order_count(sf))
    values = G.pool_values("orders", "o_comment", sf)
    codes = G.codes_chunk("orders", sf, "o_comment", 0, n)
    assert X.o_comment(sf, 0, n) == [values[c] for c in codes]
    assert sorted(set(X.pool())) == list(values)
    assert len(X.pool()) == X.POOL_SIZE == 2048
    assert max(map(len, X.pool())) <= X.O_COMMENT_LEN
    assert np.array_equal(
        X.o_custkey(sf, 0, n), C.orders_chunk(sf, 0, n)["o_custkey"])
    assert np.array_equal(
        X.o_custkey(sf, 0, n),
        G.numeric_chunk("orders", sf, "o_custkey", 0, n))


def test_the_configuration_states_the_new_columns_fingerprint():
    with open(os.path.join(BENCH, "configs",
                           "tpch-sf10-1chip-q13.json")) as f:
        config = json.load(f)
    assert config["data_fingerprint"] == C.fingerprint(10.0)
    assert config["data_fingerprint_q13"] == X.fingerprint(10.0)
    assert X.fingerprint(TINY) != X.fingerprint(10.0)
    rows = C.row_counts(10.0)
    assert config["rows"] == {"customer": rows["customer"],
                              "orders": rows["orders"]}
    assert config["reduced"] == ["queries"]
    assert config["server"]["requires"] == ["like_pattern_operand"]


# ---------------------------------------------------------- the reference

# phrases of the 2 048 that are LIKE '%WORD1%WORD2%': the configuration's
# `assumed` states them
EXCLUDED = {"special": (8, 7, 6, 7), "pending": (1, 8, 10, 6),
            "unusual": (3, 5, 7, 11), "express": (4, 6, 5, 3)}


@pytest.mark.parametrize("word1, word2", PAIRS)
def test_a_word_pair_excludes_a_few_phrases(word1, word2):
    """1 to 11 of the 2 048 phrases, by a matcher written apart from the
    reference's (a regular expression), and by the engine's own LIKE."""
    import re
    from trino_tpu.expr.hoist import LikeOperand
    from trino_tpu.page import Dictionary
    out = X.excluded(word1, word2)
    pattern = re.compile(f".*{word1}.*{word2}.*", re.S)
    assert out.tolist() == [bool(pattern.fullmatch(s)) for s in X.pool()]
    assert int(out.sum()) == EXCLUDED[word1][X.WORD2.index(word2)]
    values = G.pool_values("orders", "o_comment", TINY)
    table = LikeOperand(f"%{word1}%{word2}%").table(Dictionary(values))
    assert sorted(values[np.asarray(table)]) \
        == sorted({s for s, o in zip(X.pool(), out) if o})
    q13 = reference.load_by_path("queries", "q13")
    kept = q13.kept_orders(15_000_000, {"word1": word1, "word2": word2})
    assert 14_919_000 < kept < 14_993_000       # 0.05-0.54 % left out


def test_merge_counts_customers_by_their_kept_orders():
    """Three ranges' partials at `tiny`: the histogram of the `o_custkey`
    stream less the excluded orders, counted by count, ordered by
    `custdist` and then `c_count`, both descending."""
    q13 = reference.load_by_path("queries", "q13")
    p = {"word1": "unusual", "word2": "deposits"}
    customer = C.customer(TINY)
    partials = [q13.partial(C.orders_chunk(TINY, lo, hi), p, customer)
                for lo, hi in ((0, 5000), (5000, 10000), (10000, 15000))]
    assert json.loads(json.dumps(partials)) == partials
    assert sum(len(part[3]) for part in partials) < 150     # 11 of 2 048
    rows = q13.merge(partials, p)
    # the plain way: every order's comment as text, one customer at a time
    orders = C.orders_chunk(TINY, 0, 15000)
    per_customer = collections.Counter()
    for key, text in zip(orders["o_custkey"].tolist(),
                         X.o_comment(TINY, 0, 15000)):
        at = text.find("unusual")
        if not (at >= 0 and text.find("deposits", at + 7) >= 0):
            per_customer[key] += 1
    dist = collections.Counter(per_customer.get(k, 0)
                               for k in customer["c_custkey"].tolist())
    assert rows == [[n, d] for n, d in sorted(
        dist.items(), key=lambda r: (-r[1], -r[0]))]
    assert rows[0] == [0, 500]
    assert sum(d for _, d in rows) == 1500
    assert sum(n * d for n, d in rows) \
        == 15000 - sum(len(part[3]) for part in partials)


def test_the_histogram_is_kept_for_the_next_word_pair():
    a = X.orders_per_customer(TINY, [[0, 7000], [7000, 15000]])
    assert X.orders_per_customer(TINY, [[0, 7000], [7000, 15000]]) is a
    assert int(a.sum()) == 15000 and a[0] == 0
    assert X.orders_per_customer(TINY, [[0, 7000]]) is not a


# ------------------------------------------------------- the served path

@pytest.fixture(scope="module")
def served():
    """The first six requests of the cell's own stream at `tiny`, each
    with its rows, its query info and the reference's rows."""
    traffic = traffic_gen.load_traffic("power-q13")
    plan = traffic_gen.make_plan(traffic, SEED, 60)
    assert plan["cycle"] == 1 and plan["n_clients"] == 1
    assert traffic["verify_max_distinct"] == 16
    assert "query_max_execution_time" not in traffic["session"]
    # the sixteen pairs of clause 2.4.13.3, each as likely as another
    q13 = reference.load_by_path("queries", "q13")
    assert len(traffic_gen._combinations(q13, [])) == 16
    first16 = {(p["word1"], p["word2"])
               for _, p in plan["clients"][0][:16]}
    assert first16 == set(PAIRS)
    requests = [{"shape": shape, "params": params}
                for shape, params in plan["clients"][0][:REQUESTS]]
    assert {r["shape"] for r in requests} == {"q13"}
    stmts = loadgen.Statements(plan)
    # as run.py starts it: the configuration's columns warmed on the
    # device (resident in the table cache before the first request)
    manifest = {"tables": [{"table": f"tpch.tiny.{t}", "columns": names}
                           for t, names in q13.COLUMNS.items()]}
    server = TrinoServer(LocalQueryRunner.tpch("tiny"),
                         warmup_manifest=manifest,
                         requires=("like_pattern_operand",)).start()
    assert not [e for e in server.warmup_report if "error" in e]
    conn = loadgen.Conn("127.0.0.1", server.port, "q13")
    try:
        for r in requests:
            sql, headers = stmts.build(r["shape"], r["params"])
            assert "NOT LIKE '%{word1}%{word2}%'".format(**r["params"]) \
                in sql
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
    assert 25 < len(r["rows"]) < 45
    assert r["stats"]["result_cache_hits"] == 0
    keys = [(-row[1], -row[0]) for row in r["rows"]]
    assert keys == sorted(keys)


@pytest.mark.parametrize("i", range(REQUESTS))
def test_the_zero_row_counts_the_null_extended_customers(served, i):
    """`c_count = 0` is the customers without a kept order — the rows the
    LEFT join null-extends, `count(o_orderkey)` skipping their NULL —,
    counted here from the streams: the third the generator gives no order
    and whoever's orders are all excluded."""
    r = served[i]
    out = X.excluded(r["params"]["word1"], r["params"]["word2"])
    kept = ~out[X.o_comment_raw(TINY, 0, 15000)]
    have = np.unique(X.o_custkey(TINY, 0, 15000)[kept])
    zero = [row for row in r["rows"] if row[0] == 0]
    assert zero == [[0, 1500 - len(have)]]
    assert zero[0][1] >= 500
    assert sum(row[1] for row in r["rows"]) == 1500
    assert sum(row[0] * row[1] for row in r["rows"]) == int(kept.sum())


@pytest.mark.parametrize("i", range(1, REQUESTS))
def test_a_new_word_pair_compiles_nothing(served, i):
    """The pair differs from every earlier request's and reaches the
    orders filter as the operand of its LIKE table: the same executables,
    the change counted as a parameter hit — and XLA compiled nothing at
    all: a pair's answer has 33 to 35 rows at `tiny` (45 or 46 at SF10),
    and the result page is cut on the device at the power of two above
    its length (`Page.to_host`), not at the length itself."""
    r = served[i]
    assert r["stats"]["jit_misses"] == 0
    assert r["stats"]["jit_param_hits"] >= 1
    assert r["stats"]["backend_compiled"] == []
    assert r["stats"]["backend_compiles"] == 0
    assert r["stats"]["spilled_bytes"] == 0
    assert r["stats"]["spill_fallbacks"] == 0


@pytest.mark.parametrize("i", range(REQUESTS))
def test_the_counters_read(served, i):
    """One LIKE table a query; the build's keys are dense, so the lookup
    is the position table over the customer page's lanes; 1 500 customers
    and their 15 500 joined rows fit the first capacity at `tiny`, so no
    page runs twice (at SF10 the one customer page does: PERF.md)."""
    stats = served[i]["stats"]
    assert stats["like_tables_built"] == 1
    assert stats["host_calls"]["like_table"] == 1
    assert stats["cross_joins"] == 0
    assert stats["probe_lookups_position_table"] == 1
    assert stats["probe_lookups_search"] == 0
    assert stats["probe_lookup_lanes_search"] == 0
    assert stats["probe_lookup_lanes"] == 2048
    assert stats["probe_overflow_reruns"] == 0
    assert stats["sorted_reduces_scanned"] >= 2    # both GROUP BYs sorted


@pytest.mark.parametrize("i", range(REQUESTS))
def test_the_joined_lanes_arrive_in_customer_order_and_are_not_sorted(
        served, i):
    """The LEFT join emits its output in the probe's row order, customers
    by `c_custkey`: the GROUP BY on it finds PARTIAL's and FINAL's lanes in
    order on the device and neither sorts nor gathers them (PR 45). The
    GROUP BY on `c_count` — 1 500 counts in customer order — sorts."""
    stats = served[i]["stats"]
    assert stats["group_by_lanes_in_order"] \
        + stats["group_by_lanes_sorted"] == stats["sorted_reduce_lanes"]
    assert stats["group_by_lanes_in_order"] >= 16384   # the joined rows
    assert 0 < stats["group_by_lanes_sorted"] \
        < stats["group_by_lanes_in_order"] / 5


def test_q13_plans_the_not_like_under_the_join():
    q13 = reference.load_by_path("queries", "q13")
    runner = LocalQueryRunner.tpch("tiny")
    plan = [line.strip() for row in runner.execute(
        "EXPLAIN " + q13.SQL.format(word1="express", word2="requests")).rows
        for line in row[0].splitlines()]
    joins = [line for line in plan if line.startswith("- Join[")]
    assert joins == ["- Join[left; c_custkey_0 = o_custkey_9; replicated]"]
    assert "- Filter[not(like(o_comment_16, '%express%requests%'))]" in plan


# ---------------------------------------------------- LEFT-join edge cases

N_CUST, N_ORD = 240, 900


@pytest.fixture(scope="module")
def tables():
    """Seeded random customers and orders in the memory catalog: customer
    keys 1..240 and one NULL key; orders for two thirds of them, a few with
    a NULL customer; comments of three kinds, so that a pattern can drop
    one customer's every order, or every order there is."""
    rng = np.random.default_rng(4413)
    runner = LocalQueryRunner.tpch("tiny")
    runner.execute("CREATE TABLE memory.default.q13_c (ck bigint)")
    runner.execute("CREATE TABLE memory.default.q13_o "
                   "(ok bigint, ck bigint, note varchar)")
    runner.execute("CREATE TABLE memory.default.q13_none "
                   "(ok bigint, ck bigint, note varchar)")
    cust = [None] + list(range(1, N_CUST + 1))
    runner.execute("INSERT INTO memory.default.q13_c VALUES " + ", ".join(
        f"({'NULL' if k is None else k})" for k in cust))
    orders = []
    for ok in range(1, N_ORD + 1):
        ck = int(rng.integers(1, N_CUST + 1))
        ck = ck if ck % 3 else ck - 1       # a third place no order
        note = ("special packages", "pending deposits", "plain")[
            int(rng.integers(0, 3))]
        if ck == 7:
            note = "special packages"       # customer 7: every order goes
        orders.append((ok, None if ok % 97 == 0 else ck, note))
    assert any(ck == 7 for _, ck, _ in orders)
    runner.execute("INSERT INTO memory.default.q13_o VALUES " + ", ".join(
        f"({ok}, {'NULL' if ck is None else ck}, '{note}')"
        for ok, ck, note in orders))
    yield runner, cust, orders
    for t in ("q13_c", "q13_o", "q13_none"):
        runner.execute(f"DROP TABLE memory.default.{t}")


def _plain(cust, orders, dropped):
    """Q13 the plain way: customers by the number of their orders whose
    note is not in `dropped`; a NULL key matches nothing."""
    per = collections.Counter(ck for _, ck, note in orders
                              if ck is not None and note not in dropped)
    dist = collections.Counter(per.get(k, 0) if k is not None else 0
                               for k in cust)
    return [[n, d] for n, d in sorted(dist.items(),
                                      key=lambda r: (-r[1], -r[0]))]


Q13_OVER = """
SELECT c_count, count(*) AS custdist
FROM (SELECT c.ck, count(o.ok) AS c_count
      FROM memory.default.q13_c c LEFT OUTER JOIN memory.default.{orders} o
        ON c.ck = o.ck AND o.note NOT LIKE '{pattern}'
      GROUP BY c.ck) AS c_orders
GROUP BY c_count ORDER BY custdist DESC, c_count DESC
"""

EDGES = [
    # (orders table, pattern, notes the pattern drops)
    ("q13_o", "%special%packages%", {"special packages"}),
    ("q13_o", "%pending%deposits%", {"pending deposits"}),
    ("q13_o", "%nothing%", set()),
    # a build whose every row the filter drops
    ("q13_o", "%", {"special packages", "pending deposits", "plain"}),
    # an empty build
    ("q13_none", "%special%packages%", set()),
]


@pytest.mark.parametrize("orders_table, pattern, dropped", EDGES)
def test_left_join_edges_answer_as_the_plain_reference(
        tables, orders_table, pattern, dropped):
    runner, cust, orders = tables
    if orders_table == "q13_none":
        orders = []
    got = [list(row) for row in runner.execute(Q13_OVER.format(
        orders=orders_table, pattern=pattern)).rows]
    assert got == _plain(cust, orders, dropped)
    assert sum(d for _, d in got) == len(cust)      # none is dropped
    if not orders or len(dropped) == 3:
        assert got == [[0, len(cust)]]


def test_a_customer_whose_orders_are_all_excluded_counts_zero(tables):
    """Customer 7 has orders, every one `special packages`: it stays in
    the answer with `c_count` 0; so does the customer whose key is NULL."""
    runner, cust, orders = tables
    sql = ("SELECT c.ck, count(o.ok) FROM memory.default.q13_c c "
           "LEFT JOIN memory.default.q13_o o ON c.ck = o.ck "
           "AND o.note NOT LIKE '%special%packages%' "
           "WHERE c.ck = 7 OR c.ck IS NULL GROUP BY c.ck ORDER BY c.ck")
    assert [list(r) for r in runner.execute(sql).rows] \
        == [[7, 0], [None, 0]]
    kept = "SELECT count(*) FROM memory.default.q13_o WHERE ck = 7"
    assert runner.execute(kept).only_value() >= 1


def test_a_page_that_overflows_its_first_capacity_runs_twice(tables):
    """241 customers in one probe page join 891 orders: at
    `page_capacity` 256 the join's first capacity is 256, the true total
    is read, and the page runs again at 1 024 — counted once, the answer
    the same."""
    runner, cust, orders = tables
    sql = Q13_OVER.format(orders="q13_o", pattern="%special%packages%")
    want = _plain(cust, orders, {"special packages"})
    runner.execute("SET SESSION page_capacity = 256")
    try:
        got = [list(row) for row in runner.execute(sql).rows]
        stats = runner.last_query_stats
    finally:
        runner.execute("RESET SESSION page_capacity")
    assert got == want
    assert stats["probe_overflow_reruns"] == 1
    runner.execute(sql)
    assert runner.last_query_stats["probe_overflow_reruns"] == 0


def test_a_result_is_cut_at_a_power_of_two_on_the_device():
    """`Page.to_host(n)`: the eager slice is `x[:k]` for the rung k above
    n, so answers of 45 and 46 rows share one executable; the host trims
    to n, validity and all."""
    import jax
    import jax.numpy as jnp
    from trino_tpu import types as T
    from trino_tpu.page import Column, Page
    values = jnp.arange(128, dtype=jnp.int64)
    valid = values % 5 != 0
    page = Page((Column(values, valid, T.BIGINT, None),), 46)
    shapes = []
    real = jax.device_get

    def spy(tree):
        shapes.extend(x.shape for x in jax.tree_util.tree_leaves(tree))
        return real(tree)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "device_get", spy)
        got46 = page.to_host(46)
        got45 = page.to_host(45)
        got64 = page.to_host(64)
        got65 = page.to_host(65)
        got1 = page.to_host(1)
    assert shapes == [(64,), (64,)] * 3 + [(128,), (128,)] + [(1,), (1,)]
    want = [None if i % 5 == 0 else i for i in range(128)]
    assert got46[0].tolist() == want[:46]
    assert got45[0].tolist() == want[:45]
    assert got64[0].tolist() == want[:64]
    assert got65[0].tolist() == want[:65]
    assert got1[0].tolist() == [None]
