"""The chain that walks (PR 43): a scan of resident columns whose chain
runs deferred into a direct partial aggregate is ONE launch — the program
takes each column as one buffer and walks it a page at a time inside
(`local_planner.compose_walk`, `page.in_chunks`) — where it was a launch a
page. Same steps, same per-page arithmetic, same answers; every other
chain keeps its launch a page. And the store under it: the tpch connector
keeps a column once, whole, and cuts pages from it for whoever asks for
pages.
"""

import math

import numpy as np
import pytest

from trino_tpu.connector import tpch
from trino_tpu.exec import LocalQueryRunner, local_planner
from trino_tpu.exec.deadline import CancelEvent, QueryCanceledError
from trino_tpu.page import SplitColumn

from tpch_sql import QUERIES

CAP = 8192                      # lineitem at `tiny`: 60 050 rows, 8 pages
LINEITEM_ROWS = 60050
COUNTERS = ("chain_walks", "chain_walk_pages", "compactions_deferred",
            "compactions_run", "kernel_calls")


def _runner(cap=CAP):
    r = LocalQueryRunner.tpch("tiny")
    r.session.set("page_capacity", cap)
    r.session.set("scan_page_capacity", cap)
    return r


def _stats(runner):
    return {k: runner.last_query_stats[k] for k in COUNTERS}


@pytest.fixture
def per_page(monkeypatch):
    """Enter to make every chain take a launch a page, as before PR 43."""
    def enter():
        monkeypatch.setattr(local_planner, "compose_walk",
                            lambda *a, **k: None)
    return enter


@pytest.fixture
def empty_store():
    with tpch._CACHE_LOCK:
        tpch._DEVICE_COL_CACHE.clear()
        tpch._DEVICE_COL_CACHE_USED = 0
    yield
    tpch.set_device_cache_budget(4 << 30)


WALKING = {
    "q6": (QUERIES["q6"][0], "lineitem"),
    "q1": (QUERIES["q1"][0], "lineitem"),
    "count_like": ("SELECT count(*) FROM part WHERE p_name LIKE '%green%'",
                   "part"),
    "ragged": ("SELECT count(*), sum(l_quantity), min(l_shipdate) "
               "FROM lineitem", "lineitem"),
    "no_filter_direct": ("SELECT l_linestatus, count(*), max(l_tax) "
                         "FROM lineitem GROUP BY l_linestatus "
                         "ORDER BY l_linestatus", "lineitem"),
}
TABLE_ROWS = {"lineitem": LINEITEM_ROWS, "part": 2000}


@pytest.mark.parametrize("case", sorted(WALKING))
def test_walked_and_per_page_answers_agree(case, per_page):
    sql, table = WALKING[case]
    cap = CAP if table == "lineitem" else 1024
    pages = math.ceil(TABLE_ROWS[table] / cap)
    assert pages > 1 and TABLE_ROWS[table] % cap
    walker = _runner(cap)
    walked = walker.execute(sql).rows
    st = _stats(walker)
    assert (st["chain_walks"], st["chain_walk_pages"]) == (1, pages), st
    one_page = LocalQueryRunner.tpch("tiny").execute(sql).rows
    assert walked == one_page
    per_page()
    paged = walker.execute(sql).rows
    assert walker.last_query_stats["chain_walks"] == 0
    assert walked == paged
    if case == "ragged":
        assert walked[0][0] == LINEITEM_ROWS


@pytest.mark.parametrize("name", ["q6", "q1"])
def test_a_walk_counts_its_launch_its_pages_and_their_filters(name):
    r = _runner()
    r.execute(QUERIES[name][0])
    st = _stats(r)
    assert st["chain_walks"] == 1 and st["chain_walk_pages"] == 8, st
    # pages, not launches: what it counted when each was a launch
    assert st["compactions_deferred"] == 8 and st["compactions_run"] == 0


@pytest.mark.parametrize("name", ["q18", "q3", "q4"])
def test_chains_that_cannot_walk_keep_a_launch_a_page(name, per_page):
    """Q18's inner GROUP BY sorts and holds a state a lane, q3's chains
    compact and their pages leave the program, Q4's aggregate reads a
    join: none walks, and each launches what it launched."""
    r = _runner()
    rows = r.execute(QUERIES[name][0]).rows
    st = _stats(r)
    assert st["chain_walks"] == 0 and st["chain_walk_pages"] == 0, st
    per_page()
    assert r.execute(QUERIES[name][0]).rows == rows
    assert _stats(r) == st


@pytest.mark.parametrize("name", ["q18", "q3", "q4"])
def test_one_page_tables_launch_what_they_launched(name, empty_store):
    """At `tiny`'s own page every table is one page, served as the
    store's buffers themselves: no cut, no walk, no launch more."""
    r = LocalQueryRunner.tpch("tiny")
    r.execute(QUERIES[name][0])
    r.execute(QUERIES[name][0])
    st = _stats(r)
    assert st["chain_walks"] == 0
    assert st["kernel_calls"] == {"q18": 24, "q3": 22, "q4": 8}[name], st


def test_kernel_calls_do_not_grow_with_the_page_count():
    calls = {}
    for cap in (8192, 2048):
        r = _runner(cap)
        r.execute(QUERIES["q6"][0])
        st = _stats(r)
        assert st["chain_walk_pages"] == math.ceil(LINEITEM_ROWS / cap)
        calls[cap] = st["kernel_calls"]
    assert calls[8192] == calls[2048] <= 3, calls


def test_a_table_over_the_lane_bound_takes_several_launches(monkeypatch):
    want = _runner().execute(QUERIES["q1"][0]).rows
    monkeypatch.setattr(local_planner, "_WALK_LANES", 3 * CAP)
    r = _runner()
    assert r.execute(QUERIES["q1"][0]).rows == want
    st = _stats(r)
    # 8 pages, 3 a launch: the last launch walks two and a dead one
    assert (st["chain_walks"], st["chain_walk_pages"]) == (3, 8), st
    assert st["compactions_deferred"] == 8


def test_a_cancel_between_launches_ends_the_query(monkeypatch):
    monkeypatch.setattr(local_planner, "_WALK_LANES", 2 * CAP)
    cancel = CancelEvent()
    launched = []
    real = local_planner.compose_walk

    def cancelling(*args):
        op = real(*args)

        def call(span):
            launched.append(span.first)
            out = op(span)
            cancel.cancel()
            return out
        return call
    monkeypatch.setattr(local_planner, "compose_walk", cancelling)
    with pytest.raises(QueryCanceledError):
        _runner().execute(QUERIES["q6"][0], cancel_event=cancel)
    assert launched == [0]


def test_column_spans_are_equal_and_cover_the_rows(monkeypatch):
    monkeypatch.setattr(local_planner, "_WALK_LANES", 4 * 1024)
    spans = list(local_planner.column_spans((), 9 * 1024 + 5, 1024))
    assert [(s.first, s.pages, s.live_pages, s.num_rows) for s in spans] \
        == [(0, 4, 4, 4096), (4, 4, 4, 4096), (8, 4, 2, 1029)]
    (one,) = local_planner.column_spans((), 2000, 1024)
    assert (one.first, one.pages, one.num_rows) == (0, 2, 2000)


# ----------------------------------------------------------- table cache

def test_a_resident_table_is_walked_whole_and_an_insert_is_seen():
    """A `TableCache` entry is whole columns already: a walking chain is
    handed them as they are (NULLs and all), `build_pages` cuts nothing,
    and an INSERT's invalidation reaches the next walk."""
    r = _runner(1024)
    r.session.set("table_cache_enabled", True)
    r.execute(
        "CREATE TABLE memory.default.walk_t AS SELECT o_orderkey AS k, "
        "CASE WHEN o_orderkey % 7 = 0 THEN NULL ELSE o_totalprice END AS p, "
        "o_orderstatus AS s FROM orders WHERE o_orderkey < 12001")
    sql = ("SELECT s, count(*), count(p), sum(p), min(k) "
           "FROM memory.default.walk_t WHERE k > 10 GROUP BY s ORDER BY s")
    paged = r.execute(sql).rows         # the memory connector: pages
    assert r.last_query_stats["chain_walks"] == 0
    r.execute(sql)                      # the second scan promotes
    assert r.execute(sql).rows == paged
    st = r.last_query_stats
    assert st["table_cache_hits"] == 1 and st["chain_walks"] == 1, st
    assert st["chain_walk_pages"] == 12
    assert sum(row[1] for row in paged) > sum(row[2] for row in paged)
    r.execute("INSERT INTO memory.default.walk_t VALUES (99999999, 1.00, 'F')")
    fresh = r.execute(sql).rows
    assert r.last_query_stats["chain_walks"] == 0       # dropped: pages
    assert fresh[0][1] == paged[0][1] + 1
    r.execute(sql)
    assert r.execute(sql).rows == fresh                 # promoted again
    assert r.last_query_stats["chain_walks"] == 1


# ------------------------------------------------------- the column store

def _store(table):
    with tpch._CACHE_LOCK:
        return {k[2]: c for k, c in tpch._DEVICE_COL_CACHE.items()
                if k[0] == table}


def test_a_column_is_resident_once_whatever_reads_it(empty_store):
    """q6 and q1 walk lineitem's columns, q3 takes pages of them: one
    buffer a column, as long as its pages were when a page was an entry."""
    r = _runner()
    for name in ("q6", "q1", "q3", "q6"):
        r.execute(QUERIES[name][0])
    cols = _store("lineitem")
    assert sorted(cols) == sorted([
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_tax", "l_returnflag", "l_linestatus", "l_shipdate"])
    with tpch._CACHE_LOCK:
        keys = [k for k in tpch._DEVICE_COL_CACHE if k[0] == "lineitem"]
    assert len(keys) == len(cols)
    widths = dict(l_returnflag=4, l_linestatus=4, l_shipdate=4)
    for name, col in cols.items():
        assert col.capacity == 8 * CAP, name
        assert col.nbytes == 8 * CAP * widths.get(name, 8), name
        # a 64-bit column of several pages is kept as its two words
        assert isinstance(col, SplitColumn) == (name not in widths), name
    with tpch._CACHE_LOCK:
        assert tpch._DEVICE_COL_CACHE_USED == sum(
            c.nbytes for c in tpch._DEVICE_COL_CACHE.values())


@pytest.mark.parametrize("cap", [CAP, 4096, 16384, 1 << 16])
def test_pages_cut_from_the_store_are_the_pages_generated(empty_store, cap):
    """Every consumer of pages gets the capacity, rows and values a page
    generated on its own has — at the capacity the buffers were built
    for (8 192), at one that divides it, at one that reaches past their
    end, and at one that holds the table in a page."""
    conn = LocalQueryRunner.tpch("tiny").catalogs.get("tpch")
    handle = conn.metadata.get_table_handle(
        tpch.SchemaTableName("tiny", "lineitem"))
    by_name = {c.name: c for c in conn.metadata.get_column_handles(handle)}
    columns = [by_name[n] for n in ("l_orderkey", "l_shipdate",
                                    "l_returnflag", "l_extendedprice")]
    (split,) = conn.split_manager.get_splits(handle, target_splits=1)
    list(conn.page_source.pages(split, columns, CAP))    # built at 8 192
    pages = list(conn.page_source.pages(split, columns, cap))
    assert len(pages) == math.ceil(LINEITEM_ROWS / cap)
    for i, page in enumerate(pages):
        off = i * cap
        n = min(cap, LINEITEM_ROWS - off)
        assert page.num_rows == n and page.capacity == cap
        for ch, col in zip(columns, page.columns):
            want = tpch._staged_column("lineitem", 0.01, ch.name, ch.type,
                                       off, off + n, cap)
            assert col.values.shape == want.values.shape
            assert col.values.dtype == want.values.dtype
            assert col.dictionary is want.dictionary
            assert np.array_equal(np.asarray(col.values)[:n],
                                  np.asarray(want.values)[:n]), (i, ch.name)
    assert len(_store("lineitem")) == len(columns)


def test_the_budget_still_evicts(empty_store):
    r = _runner()
    r.execute(QUERIES["q1"][0])
    used = tpch._DEVICE_COL_CACHE_USED
    assert used > 0 and len(_store("lineitem")) == 7
    tpch.set_device_cache_budget(used // 2)
    assert 0 < tpch._DEVICE_COL_CACHE_USED <= used // 2
    assert len(_store("lineitem")) < 7
    # a column larger than the whole budget is streamed, not kept
    tpch.set_device_cache_budget(1024)
    want = LocalQueryRunner.tpch("tiny").execute(QUERIES["q6"][0]).rows
    assert r.execute(QUERIES["q6"][0]).rows == want
    assert r.last_query_stats["chain_walks"] == 0
    assert tpch._DEVICE_COL_CACHE_USED == 0


def test_a_refused_promotion_gathers_no_pages(empty_store, monkeypatch):
    """A scan whose columns the table cache cannot admit keeps none of
    its pages for the promotion: cut from the store, they are copies."""
    from trino_tpu.exec.table_cache import TableCache
    gathered = []
    real = TableCache.promote_from_pages

    def spy(self, table, symbols_cols, pages, counts, **kw):
        gathered.append(len(pages))
        return real(self, table, symbols_cols, pages, counts, **kw)
    monkeypatch.setattr(TableCache, "promote_from_pages", spy)
    r = _runner()
    r.session.set("table_cache_enabled", True)
    r.session.set("table_cache_max_bytes", 1 << 16)     # lineitem: no
    sql = "SELECT l_orderkey FROM lineitem WHERE l_quantity < 2"
    rows = r.execute(sql).rows
    for _ in range(2):
        assert r.execute(sql).rows == rows
    assert gathered == []
    r.session.set("table_cache_max_bytes", 1 << 30)
    r.execute(sql)
    assert gathered == [8]


def test_a_column_first_kept_as_one_page_is_split_when_pages_are_asked(
        empty_store):
    """The form a 64-bit column is kept in follows what reads it: one
    page is the buffer itself (no cut, no copy), several pages are a
    walk's or a cut's, and those read its two words."""
    one_page = LocalQueryRunner.tpch("tiny")
    want = one_page.execute(QUERIES["q6"][0]).rows
    kept = _store("lineitem")
    assert kept and not any(isinstance(c, SplitColumn) for c in kept.values())
    used = tpch._DEVICE_COL_CACHE_USED
    r = _runner()
    assert r.execute(QUERIES["q6"][0]).rows == want
    assert r.last_query_stats["chain_walks"] == 1
    kept = _store("lineitem")
    assert sum(isinstance(c, SplitColumn) for c in kept.values()) == 3
    assert tpch._DEVICE_COL_CACHE_USED == used
    assert one_page.execute(QUERIES["q6"][0]).rows == want
