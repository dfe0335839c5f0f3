"""Lake connector + device table cache: the real data plane.

The acceptance shape of the lake round: a TPC-H query CTAS'd into a
partitioned lake table re-reads oracle-correct with files_pruned > 0
under a selective predicate; INSERT replay is exactly-once under QUERY
retry (atomic manifest-swap commit); a repeated scan serves from the
HBM table cache with ZERO host->device staging bytes (local path here;
the 8-device mesh proof lives in test_mesh_queries.py); and one INSERT
invalidates plans, results, scan pages, and device columns through a
single PlanCache hook fan-out.
"""

import os

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.connector.lake import lake_stats
from trino_tpu.errors import InjectedFault
from trino_tpu.exec import LocalQueryRunner


@pytest.fixture()
def runner(tmp_path, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_LAKE_DIR", str(tmp_path / "lake"))
    return LocalQueryRunner.tpch("tiny")


def _enable_table_cache(r, min_scans=1):
    r.session.set("table_cache_enabled", True)
    r.session.set("table_cache_min_scans", min_scans)


# ------------------------------------------------------------ round trips


def test_ctas_roundtrip_oracle_correct(runner):
    runner.execute("CREATE TABLE lake.default.orders_l AS "
                   "SELECT * FROM orders")
    got = runner.execute(
        "SELECT o_orderstatus, count(*), sum(o_totalprice) "
        "FROM lake.default.orders_l GROUP BY o_orderstatus "
        "ORDER BY o_orderstatus").rows
    exp = runner.execute(
        "SELECT o_orderstatus, count(*), sum(o_totalprice) "
        "FROM orders GROUP BY o_orderstatus ORDER BY o_orderstatus").rows
    assert got == exp


def test_partitioned_ctas_prunes_files(runner):
    runner.execute(
        "CREATE TABLE lake.default.orders_p "
        "WITH (partitioned_by = 'o_orderstatus') AS "
        "SELECT * FROM orders")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.orders_p "
        "WHERE o_orderstatus = 'F'")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM orders WHERE o_orderstatus = 'F'"
    ).only_value()
    assert got.only_value() == exp
    # 3 partitions (F/O/P): the selective predicate reads exactly one
    assert st["files_pruned"] == 2, st


def test_zone_map_row_group_pruning(runner):
    runner.execute(
        "CREATE TABLE lake.default.li_g WITH (row_group_rows = 4096) AS "
        "SELECT l_orderkey, l_partkey, l_extendedprice FROM lineitem")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.li_g WHERE l_orderkey < 100")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM lineitem WHERE l_orderkey < 100"
    ).only_value()
    assert got.only_value() == exp
    # lineitem is orderkey-ordered: a low-key predicate keeps the first
    # group and prunes the rest
    assert st["row_groups_pruned"] > 0, st


def test_zone_map_or_predicate_pruning(runner):
    """OR of single-column ranges extracts a multi-range TupleDomain:
    a low-key OR high-key predicate prunes every middle row group."""
    runner.execute(
        "CREATE TABLE lake.default.li_or WITH (row_group_rows = 4096) AS "
        "SELECT l_orderkey, l_extendedprice FROM lineitem")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.li_or "
        "WHERE l_orderkey < 100 OR l_orderkey > 59000")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM lineitem "
        "WHERE l_orderkey < 100 OR l_orderkey > 59000").only_value()
    assert got.only_value() == exp
    assert st["row_groups_pruned"] > 0, st


def test_zone_map_in_list_pruning(runner):
    """IN-list predicates extract a discrete-value TupleDomain and
    prune row groups whose [min, max] misses every listed value."""
    runner.execute(
        "CREATE TABLE lake.default.li_in WITH (row_group_rows = 4096) AS "
        "SELECT l_orderkey, l_extendedprice FROM lineitem")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.li_in "
        "WHERE l_orderkey IN (1, 2, 3)")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM lineitem WHERE l_orderkey IN (1, 2, 3)"
    ).only_value()
    assert got.only_value() == exp
    assert st["row_groups_pruned"] > 0, st


def test_zone_map_or_equalities_prune_files(runner):
    """OR of partition-key equalities prunes whole files: reading two
    of three o_orderstatus partitions skips the third."""
    runner.execute(
        "CREATE TABLE lake.default.orders_or "
        "WITH (partitioned_by = 'o_orderstatus') AS "
        "SELECT * FROM orders")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.orders_or "
        "WHERE o_orderstatus = 'F' OR o_orderstatus = 'O'")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM orders "
        "WHERE o_orderstatus = 'F' OR o_orderstatus = 'O'").only_value()
    assert got.only_value() == exp
    assert st["files_pruned"] == 1, st


def test_zone_maps_disabled_session_prop(runner):
    runner.execute(
        "CREATE TABLE lake.default.li_off WITH (row_group_rows = 4096) "
        "AS SELECT l_orderkey FROM lineitem")
    runner.execute("SET SESSION lake_zone_maps_enabled = false")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.li_off WHERE l_orderkey < 100")
    st = dict(runner.last_query_stats)
    assert got.only_value() == 392
    assert st["row_groups_pruned"] == 0 and st["files_pruned"] == 0, st


def test_dynamic_filter_prunes_row_groups(runner):
    """Join dynamic filter -> connector pruning: the build side's key
    range lands in the lake scan's TupleDomain before splits are
    chosen, so non-overlapping row groups never stage."""
    runner.execute(
        "CREATE TABLE lake.default.li_dyn WITH (row_group_rows = 4096) "
        "AS SELECT l_orderkey, l_extendedprice FROM lineitem")
    got = runner.execute(
        "SELECT count(*) FROM lake.default.li_dyn l "
        "JOIN orders o ON l.l_orderkey = o.o_orderkey "
        "WHERE o.o_orderkey < 100")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT count(*) FROM lineitem l "
        "JOIN orders o ON l.l_orderkey = o.o_orderkey "
        "WHERE o.o_orderkey < 100").only_value()
    assert got.only_value() == exp
    assert st["row_groups_pruned"] > 0, st


def test_npz_native_format_roundtrip(runner):
    """The pyarrow-free fallback format end to end: partitioned CTAS,
    pruning, strings, and nulls all work on .npz files."""
    runner.execute(
        "CREATE TABLE lake.default.nation_nz "
        "WITH (format = 'npz', partitioned_by = 'n_regionkey') AS "
        "SELECT * FROM nation")
    conn = runner.catalogs.get("lake")
    m = conn._metadata.load_manifest(
        __import__("trino_tpu.connector.spi",
                   fromlist=["SchemaTableName"]).SchemaTableName(
                       "default", "nation_nz"))
    assert m["format"] == "npz"
    assert all(e["path"].endswith(".npz") for e in m["files"])
    got = runner.execute(
        "SELECT n_name FROM lake.default.nation_nz "
        "WHERE n_regionkey = 2 ORDER BY n_name")
    st = dict(runner.last_query_stats)
    exp = runner.execute(
        "SELECT n_name FROM nation WHERE n_regionkey = 2 "
        "ORDER BY n_name").rows
    assert got.rows == exp
    assert st["files_pruned"] == 4, st   # 5 region partitions, 1 read


def test_nulls_roundtrip(runner):
    runner.execute(
        "CREATE TABLE lake.default.withnull (a bigint, s varchar)")
    runner.execute("INSERT INTO lake.default.withnull VALUES "
                   "(1, 'x'), (NULL, NULL), (3, 'y')")
    rows = runner.execute(
        "SELECT a, s FROM lake.default.withnull ORDER BY a").rows
    assert rows == [(1, "x"), (3, "y"), (None, None)]
    assert runner.execute("SELECT count(*) FROM lake.default.withnull "
                          "WHERE a IS NULL").only_value() == 1


def test_all_null_varchar_column(runner):
    """Empty string pool: codes emit the reserved -1 null code."""
    runner.execute("CREATE TABLE lake.default.an (a bigint, s varchar)")
    runner.execute("INSERT INTO lake.default.an VALUES (1, NULL), "
                   "(2, NULL)")
    assert runner.execute("SELECT a, s FROM lake.default.an ORDER BY a"
                          ).rows == [(1, None), (2, None)]


def test_drop_table_removes_directory(runner):
    runner.execute("CREATE TABLE lake.default.gone (x bigint)")
    conn = runner.catalogs.get("lake")
    tdir = os.path.join(conn._metadata.base_dir, "default", "gone")
    assert os.path.exists(tdir)
    runner.execute("DROP TABLE lake.default.gone")
    assert not os.path.exists(tdir)
    assert runner.execute("SHOW TABLES FROM lake.default").rows == []


# -------------------------------------------------- exactly-once writes


def test_insert_exactly_once_under_query_retry(runner):
    """INSERT replay under retry_policy=QUERY with chaos that fires
    AFTER the commit (site `fragment` fires post-sink-finish): the
    replayed attempt detects its committed token in the manifest,
    deletes its orphan files, and no-ops — the table lands EXACTLY the
    source rows, manifest-swap-atomically."""
    runner.execute("CREATE TABLE lake.default.li_once AS "
                   "SELECT l_orderkey FROM lineitem WHERE false")
    before = lake_stats()["replayed_commits"]
    runner.session.set("fault_injection_rate", 0.5)
    runner.session.set("fault_injection_seed", 1)
    runner.session.set("fault_injection_sites", "fragment")
    runner.session.set("retry_policy", "QUERY")
    runner.session.set("retry_attempts", 5)
    runner.execute("INSERT INTO lake.default.li_once "
                   "SELECT l_orderkey FROM lineitem WHERE l_orderkey < 50")
    assert runner.last_query_stats["retries"] > 0
    runner.session.set("fault_injection_rate", 0.0)
    count = runner.execute(
        "SELECT count(*) FROM lake.default.li_once").only_value()
    exp = runner.execute("SELECT count(*) FROM lineitem "
                         "WHERE l_orderkey < 50").only_value()
    assert count == exp, "retried INSERT must not duplicate"
    assert lake_stats()["replayed_commits"] > before, \
        "the retry must have replayed a committed token as a no-op"


def test_insert_none_policy_aborts_cleanly(runner):
    """A failed un-retried INSERT commits NOTHING: abort deletes the
    attempt's staged files and the manifest never swaps."""
    runner.execute("CREATE TABLE lake.default.li_abort AS "
                   "SELECT l_orderkey FROM lineitem WHERE false")
    runner.session.set("fault_injection_rate", 1.0)
    runner.session.set("fault_injection_seed", 1)
    runner.session.set("fault_injection_sites", "scan")
    runner.session.set("retry_policy", "NONE")
    with pytest.raises(InjectedFault):
        runner.execute("INSERT INTO lake.default.li_abort "
                       "SELECT l_orderkey FROM lineitem "
                       "WHERE l_orderkey < 50")
    runner.session.set("fault_injection_rate", 0.0)
    assert runner.execute("SELECT count(*) FROM lake.default.li_abort"
                          ).only_value() == 0
    conn = runner.catalogs.get("lake")
    ddir = os.path.join(conn._metadata.base_dir, "default", "li_abort",
                        "data")
    assert os.listdir(ddir) == [], "aborted attempt left orphan files"


def test_sink_token_idempotent_direct(runner):
    """SPI-level: two sinks with ONE token commit once."""
    from trino_tpu.connector.spi import SchemaTableName
    from trino_tpu.page import Column, Page
    runner.execute("CREATE TABLE lake.default.tok (x bigint)")
    conn = runner.catalogs.get("lake")
    h = conn.metadata.get_table_handle(SchemaTableName("default", "tok"))
    page = Page((Column.from_numpy(
        np.arange(5, dtype=np.int64), T.BIGINT),), 5)
    for _ in range(2):
        sink = conn.page_sink(h, write_token="tok-1")
        sink.append_page(page)
        sink.finish()
    assert runner.execute("SELECT count(*) FROM lake.default.tok"
                          ).only_value() == 5


# ------------------------------------------------------ device table cache


def test_repeated_scan_serves_from_hbm_zero_staging(runner):
    """The tentpole counter proof: scan 1 stages from the connector
    (scan_staging_bytes > 0) and promotes; scan 2 is a table-cache hit
    with ZERO host->device staging bytes."""
    runner.execute("CREATE TABLE lake.default.hot AS SELECT * FROM orders")
    _enable_table_cache(runner, min_scans=1)
    q = ("SELECT count(*), sum(o_totalprice), min(o_orderdate) "
         "FROM lake.default.hot")
    first = runner.execute(q).rows
    st1 = dict(runner.last_query_stats)
    assert st1["table_cache_hits"] == 0 and st1["scan_staging_bytes"] > 0
    second = runner.execute(q).rows
    st2 = dict(runner.last_query_stats)
    assert second == first
    assert st2["table_cache_hits"] == 1, st2
    assert st2["scan_staging_bytes"] == 0, st2
    assert len(runner._table_cache) == 1
    assert runner._table_cache.resident_bytes > 0


def test_table_cache_serves_column_subsets(runner):
    """A promoted working set serves any SUBSET of its columns."""
    runner.execute("CREATE TABLE lake.default.sub AS SELECT * FROM nation")
    _enable_table_cache(runner, min_scans=1)
    runner.execute("SELECT * FROM lake.default.sub")         # promote all
    got = runner.execute("SELECT n_name FROM lake.default.sub "
                         "WHERE n_regionkey = 0 ORDER BY n_name")
    st = dict(runner.last_query_stats)
    exp = runner.execute("SELECT n_name FROM nation WHERE n_regionkey = 0 "
                         "ORDER BY n_name").rows
    assert got.rows == exp
    assert st["table_cache_hits"] == 1 and st["scan_staging_bytes"] == 0


def test_min_scans_admission(runner):
    """min_scans=2: the first scan is not promoted, the second promotes,
    the third hits."""
    runner.execute("CREATE TABLE lake.default.adm AS SELECT * FROM region")
    _enable_table_cache(runner, min_scans=2)
    q = "SELECT count(*) FROM lake.default.adm"
    runner.execute(q)
    assert len(runner._table_cache) == 0
    runner.execute(q)
    assert len(runner._table_cache) == 1
    runner.execute(q)
    assert runner.last_query_stats["table_cache_hits"] == 1


def test_insert_invalidates_whole_fanout(runner):
    """ONE INSERT drops plans, cached results, staged scan pages, AND
    resident device columns through the single PlanCache hook fan-out —
    and the re-read sees the new row."""
    runner.execute("CREATE TABLE lake.default.fan AS SELECT * FROM nation")
    _enable_table_cache(runner, min_scans=1)
    runner.session.set("result_cache_enabled", True)
    runner.session.set("scan_cache_enabled", True)
    q = "SELECT count(*) FROM lake.default.fan"
    assert runner.execute(q).only_value() == 25
    runner.execute(q)   # result-cache + table-cache warm
    assert len(runner._table_cache) == 1
    assert len(runner._result_cache) >= 1
    assert len(runner._plan_cache) >= 1
    runner.execute("INSERT INTO lake.default.fan "
                   "SELECT * FROM nation WHERE n_nationkey = 0")
    tkey = ("lake", "default", "fan")
    assert all(tkey not in e.tables
               for e in runner._result_cache._entries.values())
    assert all(k[0] != tkey for k in runner._scan_cache._entries)
    # the INSERT's own source scan (tpch nation) may have promoted — the
    # assertion is that NO resident columns of the CHANGED table survive
    assert all(k[0] != tkey for k in runner._table_cache._entries), \
        "device columns must die with the table change"
    assert runner.execute(q).only_value() == 26
    st = dict(runner.last_query_stats)
    assert st["scan_staging_bytes"] > 0, \
        "post-invalidation scan must re-stage fresh data"


def test_table_cache_budget_eviction(runner):
    """Admission under a tiny budget evicts the lowest-frequency entry
    first; an over-budget candidate is refused outright."""
    from trino_tpu.exec.table_cache import TableCache
    runner.execute("CREATE TABLE lake.default.ev1 AS SELECT * FROM region")
    runner.execute("CREATE TABLE lake.default.ev2 AS SELECT * FROM nation")
    _enable_table_cache(runner, min_scans=1)
    runner.execute("SELECT count(*) FROM lake.default.ev1")
    runner.execute("SELECT count(*) FROM lake.default.ev1")  # freq 2
    runner.execute("SELECT count(*) FROM lake.default.ev2")
    cache = runner._table_cache
    assert len(cache) == 2
    # shrink the budget to one entry's worth: lowest-frequency evicts
    sizes = sorted(e.nbytes for e in cache._entries.values())
    cache.configure(max_bytes=sizes[-1], min_scans=1)
    assert len(cache) == 1
    left = next(iter(cache._entries.values()))
    assert left.table == ("lake", "default", "ev1")
    assert isinstance(cache, TableCache)


def test_over_budget_promotion_is_refused_before_any_copy(monkeypatch):
    """What an entry would hold is known from shapes: a candidate one
    byte over the budget is refused without building a single column
    (at SF10 every scan of lineitem paid a full-length copy of each
    column, then was refused), one that just fits is admitted at
    exactly that size."""
    import jax.numpy as jnp

    from trino_tpu.exec import table_cache
    from trino_tpu.exec.table_cache import TableCache
    from trino_tpu.page import Column, Page
    pages = [Page((Column.from_numpy(np.arange(600, dtype=np.int64),
                                     T.BIGINT),
                   Column.from_numpy(np.arange(600, dtype=np.int32),
                                     T.INTEGER, np.arange(600) % 3 > 0)),
                  600) for _ in range(2)]
    cols = [("a", Column(None, None, T.BIGINT, None)),
            ("b", Column(None, None, T.INTEGER, None))]
    need = 2048 * (8 + 4 + 1)          # 1 200 rows -> capacity 2 048
    key = ("lake", "default", "budget_probe_pr26")

    tight = TableCache(max_bytes=need - 1, min_scans=1)
    denied = table_cache.table_cache_stats()["admission_denied"]
    with monkeypatch.context() as m:
        m.setattr(jnp, "concatenate", lambda *a, **k: pytest.fail(
            "built a column for an entry that cannot be admitted"))
        assert not tight.promote_from_pages(key, cols, pages, [600, 600])
    assert table_cache.table_cache_stats()["admission_denied"] == denied + 1
    assert len(tight) == 0

    fits = TableCache(max_bytes=need, min_scans=1)
    assert fits.promote_from_pages(key, cols, pages, [600, 600])
    assert fits.resident_bytes == need
    fits.clear()


def test_node_pool_accounts_cache_residency(runner):
    from trino_tpu.exec.memory import NODE_POOL
    runner.execute("CREATE TABLE lake.default.acct AS SELECT * FROM region")
    _enable_table_cache(runner, min_scans=1)
    base = NODE_POOL.cache_reserved
    runner.execute("SELECT count(*) FROM lake.default.acct")
    held = runner._table_cache.resident_bytes
    assert held > 0
    assert NODE_POOL.cache_reserved >= base + held
    runner._table_cache.clear()
    assert NODE_POOL.cache_reserved <= base


# ------------------------------------------------------- chaos interplay


def test_chaos_bypasses_table_cache(runner):
    """Armed fault injection must not serve scans from the cache (the
    `scan` site has to fire) nor poison it."""
    runner.execute("CREATE TABLE lake.default.chaos AS "
                   "SELECT * FROM region")
    _enable_table_cache(runner, min_scans=1)
    runner.execute("SELECT count(*) FROM lake.default.chaos")  # promote
    runner.session.set("fault_injection_rate", 1.0)
    runner.session.set("fault_injection_sites", "scan")
    runner.session.set("retry_policy", "NONE")
    with pytest.raises(InjectedFault):
        runner.execute("SELECT count(*) FROM lake.default.chaos")
    runner.session.set("fault_injection_rate", 0.0)
    st = runner.execute("SELECT count(*) FROM lake.default.chaos")
    assert st.only_value() == 5


# ----------------------------------------------------- warmup + surfaces


def test_warmup_manifest_tables_preload(runner):
    """`tables:` entries preload device columns at warmup: the FIRST
    real scan is an HBM hit with zero staging."""
    from trino_tpu.serve.warmup import apply_warmup
    runner.execute("CREATE TABLE lake.default.warm AS SELECT * FROM nation")
    _enable_table_cache(runner, min_scans=2)
    report = apply_warmup(runner, {
        "tables": [{"table": "lake.default.warm"}],
        "statements": []})
    assert report and report[0].get("resident") is True, report
    got = runner.execute("SELECT count(*) FROM lake.default.warm")
    st = dict(runner.last_query_stats)
    assert got.only_value() == 25
    assert st["table_cache_hits"] == 1 and st["scan_staging_bytes"] == 0

    with pytest.raises(ValueError):
        apply_warmup(runner, {"tables": [{"tabel": "oops"}]})


def test_metrics_and_caches_surfaces(runner):
    runner.execute("CREATE TABLE lake.default.met AS SELECT * FROM region")
    _enable_table_cache(runner, min_scans=1)
    runner.execute("SELECT count(*) FROM lake.default.met")
    runner.execute("SELECT count(*) FROM lake.default.met")
    from trino_tpu.obs.metrics import REGISTRY
    text = REGISTRY.render()
    for name in ("trino_tpu_table_cache_hits",
                 "trino_tpu_table_cache_bytes",
                 "trino_tpu_table_cache_device_bytes",
                 "trino_tpu_lake_files_written",
                 "trino_tpu_lake_files_pruned"):
        assert name in text, name
    rows = runner.execute(
        "SELECT cache, entries, bytes FROM system.runtime.caches "
        "WHERE cache = 'table'").rows
    assert len(rows) == 1 and rows[0][2] > 0, rows


def test_explain_analyze_through_lake(runner):
    runner.execute("CREATE TABLE lake.default.ea AS SELECT * FROM region")
    text = runner.execute(
        "EXPLAIN ANALYZE SELECT count(*) FROM lake.default.ea"
    ).only_value()
    assert "TableScan" in text
