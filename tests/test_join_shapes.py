"""Every SQL join shape on the one probe path, against the sqlite oracle:
join-project over unique and duplicate builds, semi/anti joins,
distinct-project, aggregating joins (one-to-many and many-to-many, the
TPC-DS q64/q72 shapes), the spilled build — and the one lookup router
(exec/local_planner._prepare_probe): `dense` up to the span limit,
`search` past it, the same answer for every caller, three scalars
fetched and two kernels dispatched, nothing else to set.
"""

import re

import jax
import pytest

from trino_tpu.exec import LocalQueryRunner

from oracle import assert_same, load_tpch_sqlite

SF = 0.01
# the deleted matmul path's name, as its lookup value and its knobs'
# prefix; in two parts so that a grep for it over tests/ stays empty
GONE = "mx" + "u"


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch("tiny")


@pytest.fixture(scope="module")
def oracle():
    conn = load_tpch_sqlite(SF)
    yield conn
    conn.close()


def _ctas(r, conn, name, select):
    """The same table in the engine's memory catalog and in the oracle."""
    r.execute(f"CREATE TABLE memory.default.{name} AS {select}")
    conn.execute(f"CREATE TABLE {name} AS {select}")


# ------------------------------------------------------------- shapes


def test_join_project_unique_build(runner, oracle):
    sql = ("SELECT count(*), sum(l_extendedprice) FROM lineitem, part "
           "WHERE l_partkey = p_partkey AND p_size > 25")
    assert_same(runner.execute(sql).rows, oracle.execute(sql).fetchall(),
                False)


def test_join_project_duplicate_build(runner, oracle):
    # orders is NOT unique per custkey: the cumsum-expansion kernel
    sql = ("SELECT count(*) FROM customer, orders "
           "WHERE c_custkey = o_custkey AND o_orderstatus = 'F'")
    assert_same(runner.execute(sql).rows, oracle.execute(sql).fetchall(),
                False)


def test_semijoin_and_anti(runner, oracle):
    for sql in [
        "SELECT count(*) FROM orders WHERE o_custkey IN "
        "(SELECT c_custkey FROM customer WHERE c_acctbal > 0)",
        "SELECT count(*) FROM orders WHERE o_custkey NOT IN "
        "(SELECT c_custkey FROM customer WHERE c_acctbal > 0)",
        "SELECT count(*) FROM customer c WHERE EXISTS "
        "(SELECT 1 FROM orders o WHERE o.o_custkey = c.c_custkey)",
    ]:
        assert_same(runner.execute(sql).rows,
                    oracle.execute(sql).fetchall(), False)


def test_distinct_project(runner, oracle):
    sql = ("SELECT DISTINCT s_nationkey FROM supplier, nation "
           "WHERE s_nationkey = n_nationkey")
    assert_same(runner.execute(sql).rows, oracle.execute(sql).fetchall(),
                False)


def test_aggregating_join(runner, oracle):
    # probe-side group keys + probe/build-side COUNT/SUM
    sql = ("SELECT s_nationkey, count(*), sum(s_acctbal), "
           "sum(n_regionkey), count(n_comment) "
           "FROM supplier, nation WHERE s_nationkey = n_nationkey "
           "GROUP BY s_nationkey ORDER BY s_nationkey")
    assert_same(runner.execute(sql).rows, oracle.execute(sql).fetchall(),
                ordered=True)


def test_aggregating_join_many_to_many(oracle):
    # both sides duplicate keys: the join materializes the cross product
    # per key and the aggregation runs over it
    r = LocalQueryRunner.tpch("tiny")
    _ctas(r, oracle, "mm_probe",
          "SELECT l_orderkey % 256 AS k, l_suppkey % 16 AS g, "
          "l_quantity AS v FROM lineitem")
    _ctas(r, oracle, "mm_build",
          "SELECT o_orderkey % 256 AS k, o_totalprice AS w FROM orders")
    sql = ("SELECT g, count(*), sum(v), sum(w) FROM {0}mm_probe p, "
           "{0}mm_build b WHERE p.k = b.k GROUP BY g ORDER BY g")
    got = r.execute(sql.format("memory.default."))
    assert_same(got.rows, oracle.execute(sql.format("")).fetchall(),
                ordered=True)


def test_aggregating_join_build_sum_null_groups(oracle):
    # a key whose EVERY build value is NULL: SUM(w) must be NULL for
    # groups that only joined such keys, while COUNT(w) reads 0 there
    r = LocalQueryRunner.tpch("tiny")
    _ctas(r, oracle, "nb",
          "SELECT o_orderkey % 8 AS k, CASE WHEN o_orderkey % 8 = 3 "
          "THEN NULL ELSE o_custkey END AS w FROM orders")
    _ctas(r, oracle, "np",
          "SELECT s_suppkey % 8 AS k, s_suppkey % 4 AS g FROM supplier")
    sql = ("SELECT g, count(*), sum(w), count(w) FROM {0}np p, {0}nb b "
           "WHERE p.k = b.k GROUP BY g ORDER BY g")
    got = r.execute(sql.format("memory.default."))
    # nulls excluded from count(w): the k=3 build rows are all NULL
    assert any(row[3] < row[1] for row in got.rows)
    assert_same(got.rows, oracle.execute(sql.format("")).fetchall(),
                ordered=True)


# -------------------------------------------- spilled-build staging


def test_spilled_build_chunked_staging(oracle, monkeypatch):
    """PR 10 leftover fix: the keys-on-device spill path stages build
    payload columns chunk-wise (many small transfers, one bounded
    device transient) instead of materializing the whole build again."""
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    monkeypatch.setattr(LocalExecutionPlanner,
                        "_SPILL_STAGE_CHUNK_BYTES", 1 << 12)
    r = LocalQueryRunner.tpch("tiny")
    r.execute("SET SESSION join_spill_threshold_bytes = 4096")
    sql = ("SELECT count(*), sum(o_totalprice) FROM lineitem, orders "
           "WHERE l_orderkey = o_orderkey")
    got = r.execute(sql)
    assert r.last_query_stats.get("spilled_bytes", 0) > 0
    assert_same(got.rows, oracle.execute(sql).fetchall(), False)


# ------------------------- the probe side's two-step compaction (PR 31)

_PROBE_COUNTERS = ("probe_compactions_tight", "probe_compactions_full",
                   "probe_compactions_skipped")


def _spy_compactions(monkeypatch):
    """[(tag, kept, live, capacity in, capacity out)] of every page the
    probe path hands `_compact_counted`."""
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    seen = []
    real = LocalExecutionPlanner._compact_counted

    def spy(self, page, mask, kept, live, tag="probe-compact"):
        out = real(self, page, mask, kept, live, tag)
        seen.append((tag, kept, live, page.capacity, out.capacity))
        return out
    monkeypatch.setattr(LocalExecutionPlanner, "_compact_counted", spy)
    return seen


@pytest.mark.parametrize("caller", ["memory", "spill"])
@pytest.mark.parametrize("build_filter, form", [
    ("o_custkey % 100 = 0", "tight"),       # ~1 % of lineitem matches
    ("o_custkey % 5 < 3", "full"),          # ~60 %
    ("o_orderkey > 0", "skipped")])         # every row
def test_probe_compaction_takes_the_form_the_counts_show(
        oracle, monkeypatch, caller, build_filter, form):
    """A unique-build join whose build keys span the probe's range (so the
    prefilter measures itself useless and moves nothing): the probe's
    matched rows are compacted at their own pow2 rung when the buffer is
    more than twice it, by a full filter when not, and not at all when
    every row matched — in memory and against a spilled build alike, the
    counters saying which, the oracle saying the rows are the same."""
    seen = _spy_compactions(monkeypatch)
    r = LocalQueryRunner.tpch("tiny")
    if caller == "spill":
        r.execute("SET SESSION join_spill_threshold_bytes = 1")
    sql = ("SELECT count(*), sum(l_extendedprice), sum(o_totalprice) "
           "FROM lineitem, orders WHERE l_orderkey = o_orderkey "
           f"AND {build_filter}")
    got = r.execute(sql)
    stats = r.last_query_stats
    assert_same(got.rows, oracle.execute(sql).fetchall(), False)
    assert (stats.get("spilled_bytes", 0) > 0) == (caller == "spill")
    for name in _PROBE_COUNTERS:
        if name.endswith(form):
            assert stats[name] >= 1, (name, stats[name])
        else:
            assert stats[name] == 0, (name, stats[name])
    lanes_in = stats["probe_compaction_lanes_in"]
    gathered = stats["probe_compaction_lanes_gathered"]
    if form == "tight":
        assert 0 < gathered < lanes_in
    else:
        assert gathered == lanes_in == (0 if form == "skipped"
                                        else seen[0][3])
    assert {tag for tag, *_ in seen} == {"probe-compact"}
    for _tag, kept, live, cap_in, cap_out in seen:
        # the page that goes on to the attach has the capacity `_tight`
        # gave it before there was a tight form
        rung = 1 << max(kept - 1, 0).bit_length()
        assert cap_out == (rung if cap_in > 2 * rung else cap_in)
        assert (kept == live) == (form == "skipped")


@pytest.mark.parametrize("build_filter, selective", [
    ("o_orderkey < 2000", True),     # a narrow key range: prunes ~87 %
    ("o_custkey % 3 = 1", False)])   # half of the keys, all over
def test_prefilter_measures_before_it_moves_anything(
        oracle, monkeypatch, build_filter, selective):
    """The dynamic-filter prefilter is a mask and a count per page. A
    build in a narrow key range keeps it, and its pages are compacted by
    their fetched counts (here at a tight rung; every row that is left
    then matches, so the probe's own compaction is skipped). A build
    that spans the probe's range drops it after the first window, and
    not one page was gathered to find that out. NULL probe keys match
    nothing either way."""
    from trino_tpu.exec import jit_cache
    seen = _spy_compactions(monkeypatch)
    r = LocalQueryRunner.tpch("tiny")
    _ctas(r, oracle, f"pf_probe_{int(selective)}",
          "SELECT CASE WHEN l_linenumber = 7 THEN NULL ELSE l_orderkey "
          "END AS k, l_quantity AS v FROM lineitem")
    sql = ("SELECT count(*), sum(v), sum(o_totalprice), count(k) "
           f"FROM {{0}}pf_probe_{int(selective)}, orders "
           f"WHERE k = o_orderkey AND {build_filter}")
    got = r.execute(sql.format("memory.default."))
    stats = r.last_query_stats
    want = oracle.execute(sql.format("")).fetchall()
    assert_same(got.rows, want, False)
    assert want[0][0] == want[0][3] > 0      # no NULL key joined
    with jit_cache._LOCK:
        tags = {jit_cache.key_tag(k) for k in jit_cache._CACHE}
    assert "dfrange-mask" in tags
    by_tag = {}
    for tag, kept, live, cap_in, cap_out in seen:
        by_tag.setdefault(tag, []).append((kept, live, cap_in, cap_out))
    if selective:
        assert by_tag["dfrange"], seen
        for kept, live, cap_in, cap_out in by_tag["dfrange"]:
            assert kept < live and cap_out < cap_in
        # what the range let through all matches
        assert all(kept == live for kept, live, *_ in
                   by_tag["probe-compact"])
        assert stats["probe_compactions_tight"] == len(by_tag["dfrange"])
        assert stats["probe_compactions_skipped"] >= 1
        assert stats["probe_compactions_full"] == 0
    else:
        assert "dfrange" not in by_tag, seen
        # the NULL keys reached the probe and fell to its liveness test
        live_in = sum(live for _kept, live, *_ in by_tag["probe-compact"])
        assert live_in == oracle.execute(
            "SELECT count(*) FROM pf_probe_0").fetchall()[0][0]
        assert stats["probe_compactions_skipped"] == 0


# -------------------------------- dispatch-loop cache promotion


def test_dispatch_loop_table_cache_promotes():
    """PR 11 leftover fix: the per-shard dispatch loop now records scan
    frequency and promotes into the device table cache — the second
    dispatch-loop scan serves from HBM with zero host->device bytes."""
    from trino_tpu.exec.distributed import DistributedQueryRunner
    r = DistributedQueryRunner.tpch("tiny")
    r.execute("SET SESSION mesh_execution = false")
    r.execute("SET SESSION table_cache_enabled = true")
    r.execute("SET SESSION table_cache_min_scans = 1")
    sql = "SELECT count(*), sum(s_acctbal) FROM supplier"
    first = r.execute(sql)
    assert r.last_query_stats.get("scan_staging_bytes", 0) > 0
    second = r.execute(sql)
    st = r.last_query_stats
    assert st.get("table_cache_hits", 0) > 0
    assert st.get("scan_staging_bytes") == 0
    assert first.rows == second.rows


# ------------------------------------------------- q64/q72 shapes


@pytest.fixture(scope="module")
def tpcds_oracle():
    from oracle import load_tpcds_sqlite
    conn = load_tpcds_sqlite(SF)
    yield conn
    conn.close()


def test_q72_shape(tpcds_oracle):
    r = LocalQueryRunner.tpch("tiny")
    r.execute("USE tpcds.tiny")
    engine = """
SELECT i_item_desc, w_warehouse_name, d1.d_week_seq, count(*) total_cnt
FROM catalog_sales
JOIN inventory ON (cs_item_sk = inv_item_sk)
JOIN warehouse ON (w_warehouse_sk = inv_warehouse_sk)
JOIN item ON (i_item_sk = cs_item_sk)
JOIN date_dim d1 ON (cs_sold_date_sk = d1.d_date_sk)
JOIN date_dim d2 ON (inv_date_sk = d2.d_date_sk)
WHERE d1.d_week_seq = d2.d_week_seq
  AND inv_quantity_on_hand < cs_quantity AND d1.d_year = 1999
GROUP BY i_item_desc, w_warehouse_name, d1.d_week_seq
ORDER BY total_cnt DESC, i_item_desc, w_warehouse_name, d1.d_week_seq
LIMIT 100"""
    got = r.execute(engine)
    assert_same(got.rows, tpcds_oracle.execute(engine).fetchall(),
                ordered=True)


def test_q64_core_shape(tpcds_oracle):
    r = LocalQueryRunner.tpch("tiny")
    r.execute("USE tpcds.tiny")
    engine = """
SELECT i_product_name, d1.d_year, count(*) AS cnt,
       sum(ss_wholesale_cost) AS s1
FROM store_sales, store_returns, date_dim d1, item
WHERE ss_sold_date_sk = d1.d_date_sk
  AND ss_item_sk = i_item_sk
  AND ss_item_sk = sr_item_sk
  AND ss_ticket_number = sr_ticket_number
  AND i_current_price BETWEEN 35 AND 45
GROUP BY i_product_name, d1.d_year
ORDER BY i_product_name, d1.d_year, cnt LIMIT 100"""
    oracle_sql = engine.replace("BETWEEN 35 AND 45",
                                "BETWEEN 3500 AND 4500")
    got = r.execute(engine)
    assert_same(got.rows, tpcds_oracle.execute(oracle_sql).fetchall(),
                ordered=True)


# ------------- the unique INNER lookup: one gather, a table of build rows

def _unique_case(name):
    """(probe Page, build Page, probe key values or None where NULL/dead,
    build key -> row) of one shape the row table has to get right."""
    import jax.numpy as jnp
    import numpy as np
    from trino_tpu import types as T
    from trino_tpu.page import Dictionary, Page
    rng = np.random.default_rng(38)
    base = {"kmin-far-from-0": 9_000_000_000}.get(name, 5)
    # 200 distinct keys in a span of 1 000: most slots hold the sentinel
    bkeys = base + np.sort(rng.choice(1000, size=200, replace=False))
    pkeys = base + rng.integers(0, 1000, size=500)
    if name == "below-kmin-and-above-kmax":
        pkeys[::3] = bkeys[0] - 1 - rng.integers(0, 50, size=len(pkeys[::3]))
        pkeys[1::3] = bkeys[-1] + 1 + rng.integers(0, 50,
                                                   size=len(pkeys[1::3]))
    pvalid = None
    if name == "null-probe-keys":
        pvalid = rng.random(500) > 0.3
    typ, dictionary = T.BIGINT, None
    if name == "string-key-on-a-shared-dictionary":
        dictionary, _ = Dictionary.build(
            [f"k{i:04d}" for i in range(1005)])
        typ = T.VARCHAR
        bkeys, pkeys = bkeys.astype(np.int32), pkeys.astype(np.int32)
    order = rng.permutation(len(bkeys))          # the build is not sorted
    bvals = np.zeros(256, bkeys.dtype)
    bvals[:200] = bkeys[order]
    payload = np.arange(256, dtype=np.int64) * 7
    build = Page.from_numpy([bvals, payload], [typ, T.BIGINT],
                            dictionaries=[dictionary, None])
    n_build = 150 if name == "dead-lanes-on-both-sides" else 200
    build = Page(build.columns, jnp.asarray(n_build, jnp.int32))
    blive = np.arange(256) < n_build
    if name == "live-build-rows-not-a-prefix":
        keep = rng.random(256) > 0.4
        build = build.with_selection(jnp.asarray(keep))
        blive &= keep
    pvals = np.zeros(512, pkeys.dtype)
    pvals[:500] = pkeys
    probe = Page.from_numpy(
        [pvals, np.arange(512, dtype=np.int64)], [typ, T.BIGINT],
        valids=[None if pvalid is None else np.append(pvalid, [True] * 12),
                None], dictionaries=[dictionary, None])
    n_probe = 400 if name == "dead-lanes-on-both-sides" else 500
    probe = Page(probe.columns, jnp.asarray(n_probe, jnp.int32))
    plive = np.arange(512) < n_probe
    if pvalid is not None:
        plive[:500] &= pvalid
    rows = {int(k): i for i, k in enumerate(bvals) if blive[i]}
    return probe, build, [int(k) if ok else None
                          for k, ok in zip(pvals, plive)], rows


@pytest.mark.parametrize("case", [
    "keys-missing-inside-the-span", "below-kmin-and-above-kmax",
    "kmin-far-from-0", "null-probe-keys", "dead-lanes-on-both-sides",
    "live-build-rows-not-a-prefix", "string-key-on-a-shared-dictionary"])
def test_unique_dense_lookup_is_the_search_lookup_and_a_numpy_join(case):
    """`unique_inner_probe(lookup="dense")` — one gather a lane against
    the table of build rows — finds, for every probe lane, the build row
    the `search` lookup finds and a dictionary of the build's keys gives,
    and nothing where the key is absent, NULL or the lane dead."""
    import jax.numpy as jnp
    import numpy as np
    from trino_tpu.ops.join import (_DENSE_SENTINEL, attach_build,
                                    build_dense_table, prepare_build,
                                    unique_inner_probe)
    probe, build, pkeys, rows = _unique_case(case)
    prepared = prepare_build([0])(build)
    assert int(prepared[7]) == 1                 # max_run: a unique build
    kmin, kmax = int(prepared[8]), int(prepared[9])
    assert (kmin, kmax) == (min(rows), max(rows))
    table = build_dense_table(1024)(prepared[1], prepared[3], prepared[8],
                                    prepared[2])
    held = np.asarray(table)
    assert sorted(held[held != _DENSE_SENTINEL]) == sorted(rows.values())
    want = np.array([rows.get(k, -1) if k is not None else -1
                     for k in pkeys])
    assert 0 < (want >= 0).sum() < len(want)
    for lookup, prep in (("dense", prepared + (table,)),
                         ("search", prepared)):
        pre, found, count = unique_inner_probe([0], [0], lookup=lookup)(
            probe, prep)
        assert np.array_equal(np.asarray(found), want >= 0), lookup
        assert int(count) == (want >= 0).sum()
        brow = np.asarray(pre.columns[-1].values)
        assert np.array_equal(brow, np.where(want >= 0, want, 0)), lookup
        if lookup == "dense":
            assert brow.dtype == np.int32 and pre.capacity == probe.capacity
            # the build's payload column arrives at the matched lanes
            out = attach_build(2)(pre, prep)
            got = np.asarray(out.columns[3].values)
            assert np.array_equal(got[want >= 0], want[want >= 0] * 7)


@pytest.mark.parametrize("sql, counted, rows", [
    ("SELECT count(*), sum(v) FROM memory.default.lp p, "
     "memory.default.lu b WHERE p.k = b.k", "row_table", (2, 30)),
    ("SELECT count(*), sum(v) FROM memory.default.lp p, "
     "memory.default.ld b WHERE p.k = b.k", "position_table", (3, 51)),
    ("SELECT count(*), sum(v) FROM memory.default.lp p LEFT JOIN "
     "memory.default.lu b ON p.k = b.k", "position_table", (5, 30)),
    ("SELECT count(*) FROM memory.default.lp p WHERE EXISTS (SELECT 1 "
     "FROM memory.default.lu b WHERE b.k = p.k)", "set_table", (2,)),
    ("SELECT count(*), sum(v) FROM memory.default.lp p, "
     "memory.default.ls b WHERE p.k = b.k", "row_table", (1, 10)),
    ("SELECT count(*), sum(v) FROM memory.default.lp p, "
     "memory.default.lx b WHERE p.k = b.k", "search", (1, 10)),
    ("SELECT count(*) FROM memory.default.lp p WHERE p.k NOT IN (SELECT k "
     "FROM memory.default.ld)", "set_table", (2,)),
    ("SELECT count(*) FROM memory.default.lp p WHERE NOT EXISTS (SELECT 1 "
     "FROM memory.default.ld b WHERE b.k = p.k)", "set_table", (3,)),
    ("SELECT count(*) FROM (SELECT p.k IN (SELECT k FROM "
     "memory.default.ld) AS f FROM memory.default.lp p) WHERE f IS NULL",
     "set_table", (1,)),
    ("SELECT count(*) FROM memory.default.lp p WHERE EXISTS (SELECT 1 "
     "FROM memory.default.ls b WHERE b.k = p.k)", "search", (1,)),
    ("SELECT count(*) FROM memory.default.lp p WHERE EXISTS (SELECT 1 "
     "FROM memory.default.ld b WHERE b.k = p.k AND b.v = p.u * 10)",
     "search", (1,))],
    ids=["unique-inner", "max-run-2", "left", "semi",
         "unique-inner-past-the-fill-rule", "past-the-slot-cap",
         "not-in", "not-exists", "mark", "semi-past-the-fill-rule",
         "semi-on-two-columns"])
def test_the_router_picks_the_tables_payload(monkeypatch, sql, counted,
                                             rows):
    """`_prepare_probe` gives the table of build rows to the unique INNER
    probe alone: a build with a duplicate key and a LEFT join read
    run_len at the key's position and keep the position table. The row
    table is bounded by the slot cap alone (2^26), not by the position
    table's fill rule (4 slots a build lane, at least 2^20): a gather
    costs the same whatever the table's fill (PERF.md, PR 38); past the
    cap the build is searched. A SEMI, ANTI or MARK join on one column
    asks whether a key is there, not where: under the fill rule it gets
    the set table and its build is never sorted (PR 46) — `_prepare_build`
    is not called, the build page's lanes are booked to
    `semi_build_lanes_set`; past the rule, or on two columns (a hashed
    key, verified through the permutation), it sorts and searches as it
    did. One decision a join, counted; the probe's lanes counted from
    shapes."""
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    sorted_builds = []
    prepare = LocalExecutionPlanner._prepare_build

    def spy(self, build_keys, build_page, semi=False, outer=False):
        sorted_builds.append((semi, build_page.capacity))
        return prepare(self, build_keys, build_page, semi, outer)
    monkeypatch.setattr(LocalExecutionPlanner, "_prepare_build", spy)
    r = LocalQueryRunner.tpch("tiny")
    r.execute("CREATE TABLE memory.default.lp (k BIGINT, u BIGINT)")
    r.execute("INSERT INTO memory.default.lp VALUES (1, 1), (2, 1), "
              "(3, 1), (9, 1), (NULL, 1)")
    for name, values in (("lu", "(1, 10), (2, 20), (4, 40)"),
                         ("ld", "(1, 10), (2, 20), (2, 21)"),
                         ("ls", f"(1, 10), ({_LIMIT + 1}, 20)"),
                         ("lx", f"(1, 10), ({(1 << 26) + 1}, 20)")):
        r.execute(f"CREATE TABLE memory.default.{name} (k BIGINT, v BIGINT)")
        r.execute(f"INSERT INTO memory.default.{name} VALUES {values}")
    got = r.execute(sql)
    stats = r.last_query_stats
    lookups = {k: stats["probe_lookups_" + k]
               for k in ("row_table", "position_table", "set_table",
                         "search")}
    assert lookups == {k: int(k == counted) for k in lookups}
    semi_lanes = sum(cap for semi, cap in sorted_builds if semi)
    if counted == "set_table":
        assert sorted_builds == []
        assert stats["semi_build_lanes_set"] >= 8
        assert stats["semi_build_lanes_sorted"] == 0
    else:
        assert len(sorted_builds) == 1
        assert stats["semi_build_lanes_set"] == 0
        assert stats["semi_build_lanes_sorted"] == semi_lanes
        assert (semi_lanes > 0) == (" EXISTS " in sql)
    lanes = stats["probe_lookup_lanes"]
    assert lanes >= 8 and lanes & (lanes - 1) == 0   # one buffer's capacity
    assert got.rows == [rows]


# ------------------------------------------------- the one router

# the span limit of a small build: min(max(4 * capacity, 2^20), 2^26)
_LIMIT = 1 << 20


@pytest.mark.parametrize("caller", ["memory", "spill"])
@pytest.mark.parametrize("span,lookup", [(_LIMIT, "dense"),
                                         (_LIMIT + 1, "search")])
def test_router_at_the_span_limit(monkeypatch, caller, span, lookup):
    """A build whose live keys span exactly the limit gets the
    direct-address table, one slot more gets the searchsorted probe —
    whether the in-memory join asks or the spill path (a duplicate-key
    build over the spill threshold with partitioning off), and the join
    answers the same either way."""
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    seen = []
    route = LocalExecutionPlanner._prepare_probe

    def spy(self, build_keys, build_page, **kind):
        prepared, max_run, mode = route(self, build_keys, build_page,
                                        **kind)
        assert 4 * build_page.capacity <= _LIMIT
        kmin, kmax = (int(x) for x in jax.device_get(
            [prepared[8], prepared[9]]))
        seen.append((kmax - kmin + 1, mode, len(prepared),
                     prepared[10].shape[0] if len(prepared) > 10 else 0))
        return prepared, max_run, mode
    monkeypatch.setattr(LocalExecutionPlanner, "_prepare_probe", spy)
    spilled = []
    run_spilled = LocalExecutionPlanner._run_spilled_inner
    monkeypatch.setattr(
        LocalExecutionPlanner, "_run_spilled_inner",
        lambda self, *a, **kw: spilled.append(1) or run_spilled(
            self, *a, **kw))

    r = LocalQueryRunner.tpch("tiny")
    r.execute("CREATE TABLE memory.default.rb (k BIGINT, v BIGINT)")
    r.execute(f"INSERT INTO memory.default.rb VALUES (7, 1), (7, 10), "
              f"({7 + span - 1}, 100)")
    r.execute("CREATE TABLE memory.default.rp (k BIGINT, u BIGINT)")
    r.execute(f"INSERT INTO memory.default.rp VALUES (7, 1), (8, 2), "
              f"({7 + span - 1}, 3), ({7 + span}, 4), (7, 5), (NULL, 6)")
    if caller == "spill":
        r.execute("SET SESSION join_spill_threshold_bytes = 1")
        r.execute("SET SESSION spill_partition_count = 1")
    got = r.execute("SELECT count(*), sum(u), sum(v) FROM "
                    "memory.default.rp p, memory.default.rb b "
                    "WHERE p.k = b.k")
    assert len(spilled) == (caller == "spill")
    assert seen == [(span, lookup,
                     11 if lookup == "dense" else 10,
                     _LIMIT if lookup == "dense" else 0)]
    # probe rows k=7 (twice) meet two build rows each, k=7+span-1 one
    assert got.rows == [(5, 1 + 1 + 5 + 5 + 3, 11 + 11 + 100)]


@pytest.mark.parametrize("probe", ["hash_join", "unique_inner_probe"])
def test_a_lookup_is_search_or_dense(probe):
    from trino_tpu.ops import join
    for ok in ("search", "dense"):
        getattr(join, probe)([0], [0], lookup=ok)
    with pytest.raises(ValueError, match="'search' or 'dense'"):
        getattr(join, probe)([0], [0], lookup=GONE)


def test_prepare_fetches_three_scalars_and_two_kernels(monkeypatch):
    """What a join pays before its first probe page: the build's sort
    (`join-prep`), ONE fetch of (max_run, kmin, kmax), and the
    direct-address table — of build rows (`dense-table-rows`), both of
    q3's builds being unique and INNER — every join of q3 at `tiny`."""
    from trino_tpu.exec import local_planner
    from trino_tpu.exec.local_planner import LocalExecutionPlanner
    import chip_smoke
    kernels, fetches = [], []
    route = LocalExecutionPlanner._prepare_probe
    lookup_kernel = local_planner.cached_kernel
    device_get = jax.device_get

    def spy(self, build_keys, build_page, **kind):
        def kernel(key, *a, **kw):
            kernels[-1].append(key[0])
            return lookup_kernel(key, *a, **kw)

        def get(tree):
            fetches[-1].append(len(jax.tree_util.tree_leaves(tree)))
            return device_get(tree)
        kernels.append([])
        fetches.append([])
        with monkeypatch.context() as m:
            m.setattr(local_planner, "cached_kernel", kernel)
            m.setattr(local_planner.jax, "device_get", get)
            return route(self, build_keys, build_page, **kind)
    monkeypatch.setattr(LocalExecutionPlanner, "_prepare_probe", spy)
    r = LocalQueryRunner.tpch("tiny")
    assert len(r.execute(chip_smoke.Q3).rows) == 10
    assert kernels == [["join-prep", "dense-table-rows"]] * 2
    assert fetches == [[3]] * 2


@pytest.mark.parametrize("knob", ["join_enabled",
                                  "join_density_threshold",
                                  "join_max_slots"])
def test_the_matmul_knobs_are_unknown_properties(runner, knob):
    from trino_tpu.errors import InvalidSessionPropertyError
    from trino_tpu.metadata import SESSION_PROPERTY_DEFAULTS
    assert len(SESSION_PROPERTY_DEFAULTS) == 56
    with pytest.raises(InvalidSessionPropertyError,
                       match="unknown session property"):
        runner.execute(f"SET SESSION {GONE}_{knob} = 1")


# ------------------------------------------------------------- mesh


@pytest.mark.mesh
def test_q3_mesh_program_has_one_lookup_per_join(monkeypatch):
    """q3 under PARTITIONED on four devices at `tiny`, as `MeshLowerer`
    builds it: the join-bearing program holds no `dot_general`, and each
    of its two joins looks its probe keys up once, by `searchsorted`
    (the StableHLO of the program `_run_program` dispatches)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    import chip_smoke
    from trino_tpu.exec import mesh_exec
    from trino_tpu.exec.distributed import DistributedQueryRunner
    texts = []
    run_program = mesh_exec._run_program

    def spy(runner, top_fn, staged, struct_key, ladder, params):
        snapshot = dict(ladder)

        def per_shard(params, *pages):
            env = mesh_exec._Env(pages, snapshot, params)
            return top_fn(env), env.aux
        program = runner.mesh.shard_map(per_shard, replicated=1)
        texts.append(jax.jit(program).lower(params, *staged).as_text(
            debug_info=True))
        return run_program(runner, top_fn, staged, struct_key, ladder,
                           params)
    monkeypatch.setattr(mesh_exec, "_run_program", spy)
    r = DistributedQueryRunner.tpch("tiny", devices=jax.devices()[:4])
    r.session.set("join_distribution_type", "PARTITIONED")
    assert len(r.execute(chip_smoke.Q3).rows) == 10
    assert r.last_query_stats.get("exchanges_staged") == 0
    joins = [t for t in texts if "join__probe_lookup" in t]
    assert len(joins) >= 1
    for text in joins:
        assert "dot_general" not in text
        scoped = re.findall(r'loc\("([^"]*join__probe_lookup[^"]*)"', text)
        assert sum(s.endswith("/jit(searchsorted)") for s in scoped) == 2
        assert not [s for s in scoped
                    if re.search(r"scatter|while|dot_general", s)]
