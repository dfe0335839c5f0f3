"""Preemptible sliced execution: the ISSUE-9 acceptance suite.

- bounded-work slices: scheduler units (budget, wall-EWMA retune,
  boundary protocol) and sliced-scan row parity with slice counters;
- mid-slice failure: chaos site `slice` kills queries between slices —
  TASK/QUERY retries absorb it oracle-green, NONE provably fails;
- cancellation latency: DELETE (the shared cancel event) on a RUNNING
  long scan unwinds within ~one slice, far below the query's remaining
  wall, reports `preempt_latency_ms`, and the HBM ledger reads zero
  (the conftest leak gate enforces the pool globally; asserted here
  explicitly too);
- checkpoint resume: a fragment retry restores per-shard checkpoints
  instead of re-running completed shards (checkpoints_restored > 0
  while the query stays oracle-correct);
- idempotent writes: INSERT/CTAS under retry_policy=QUERY retries
  through the staged write-token sink and lands EXACTLY the source
  rows — no duplicates, and a NONE-policy failure leaves zero rows.
"""

import threading
import time
import types

import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.errors import InjectedFault, QueryCanceledError
from trino_tpu.exec import LocalQueryRunner
from trino_tpu.exec.sliced import (CheckpointStore, OperatorCheckpoint,
                                   SliceScheduler)

from oracle import assert_same, load_tpch_sqlite
from tpch_sql import QUERIES

LINEITEM_ROWS = 60050   # tpch tiny (generated hash-stream shape)


def _sliced_runner(schema="tiny", *, slice_rows=4096, page_rows=4096):
    """Runner whose tiny-table scans actually produce many slices (the
    production defaults are sized for million-row scans)."""
    r = LocalQueryRunner.tpch(schema)
    r.session.set("page_capacity", page_rows)
    r.session.set("slice_target_rows", slice_rows)
    r.session.set("slice_target_ms", 0)     # static budget: deterministic
    return r


def _chaos(r, *, sites, rate, seed=11, policy="TASK", attempts=10):
    r.session.set("fault_injection_rate", rate)
    r.session.set("fault_injection_seed", seed)
    r.session.set("fault_injection_sites", sites)
    r.session.set("retry_policy", policy)
    r.session.set("retry_attempts", attempts)


# ------------------------------------------------------------ scheduler


class _FakePage:
    def __init__(self, n, cap=None):
        self.num_rows = n
        self.capacity = cap if cap is not None else n


def test_scheduler_slices_and_boundaries():
    s = SliceScheduler(target_rows=100, target_ms=0)
    pages = [_FakePage(40) for _ in range(10)]      # 400 rows
    boundaries = []
    sites = []
    out = list(s.run(iter(pages),
                     checkpoint=lambda: boundaries.append(1),
                     fault_site=lambda site, d="": sites.append(site)))
    assert out == pages
    # 3 full slices (120 rows each) + the final partial (40)
    assert s.slices_executed == 4
    assert s.slice_rows == 400
    assert len(boundaries) == 3
    assert sites == ["slice"] * 3


def test_scheduler_wall_ewma_retune():
    s = SliceScheduler(target_rows=1000, target_ms=100)
    s.observe(100_000, 1.0)     # measured 1e5 rows/s -> 100ms = 10k rows
    assert s.target_rows == 10_000
    # EWMA damps: a second, slower measurement moves the budget DOWN
    # but not all the way to the instantaneous rate
    s.observe(10_000, 1.0)
    assert s.min_rows <= s.target_rows < 10_000


def test_scheduler_capacity_cap():
    s = SliceScheduler(target_rows=5000, target_ms=0)
    assert s.capacity_cap(floor=1024) == 8192       # pow2 envelope
    # the session page capacity floors the cap: slicing never shrinks
    # pages below the engine's normal streaming grain
    assert s.capacity_cap(floor=1 << 16) == 1 << 16


def test_the_clock_retunes_the_budget_and_never_a_scan_pages_capacity():
    """A page's capacity is a compiled program's shape: it follows the
    session's static `slice_target_rows`, whatever the wall-clock EWMA
    makes of the row budget between checkpoints (ROADMAP D14; TPC-H Q9's
    six scans took two sets of shapes in turn until PR 42)."""
    s = SliceScheduler(target_rows=1 << 20, target_ms=250)
    before = s.capacity_cap(floor=1 << 16)
    assert before == 1 << 20
    s.observe(1 << 20, 0.001)           # a cached scan: the budget grows
    assert s.target_rows == s.max_rows
    assert s.capacity_cap(floor=1 << 16) == before
    for _ in range(60):
        s.observe(1 << 12, 5.0)         # a cold one: it shrinks
    assert s.target_rows == s.min_rows
    assert s.capacity_cap(floor=1 << 16) == before


def test_scheduler_session_pin():
    from trino_tpu.metadata import Session
    sess = Session()
    assert SliceScheduler.from_session(sess) is not None
    sess.set("sliced_execution", False)
    assert SliceScheduler.from_session(sess) is None


def test_checkpoint_store_counters():
    store = CheckpointStore("q1")
    page = types.SimpleNamespace(
        columns=[types.SimpleNamespace(nbytes=64)])
    store.save("frag-1/shard-0",
               OperatorCheckpoint(scope="frag-1/shard-0", cursor=3,
                                  pages=[page]))
    assert store.saved == 1 and store.bytes_saved == 64
    assert store.peek("frag-1/shard-0") is not None
    assert store.restored == 0      # peek never counts a restore
    ck = store.load("frag-1/shard-0")
    assert ck.cursor == 3 and store.restored == 1
    assert store.load("missing") is None
    assert store.restored == 1      # a miss is not a restore
    assert store.resident_bytes() == 64
    store.clear()
    assert len(store) == 0 and store.resident_bytes() == 0


# ------------------------------------------------------ sliced execution


def _walks_a_slice_a_launch(monkeypatch, lanes=4096):
    """A scan into a direct aggregate walks its pages inside one launch
    of up to `local_planner._WALK_LANES` lanes (PR 43), the slice boundary
    between launches: bound a launch to a slice, as a table of more than
    2^26 lanes is bound, so that tiny's lineitem takes many."""
    from trino_tpu.exec import local_planner
    monkeypatch.setattr(local_planner, "_WALK_LANES", lanes)


def test_sliced_scan_parity_and_counters(monkeypatch):
    _walks_a_slice_a_launch(monkeypatch)
    r = _sliced_runner()
    got = r.execute(
        "SELECT count(*), sum(l_quantity) FROM lineitem")
    stats = r.last_query_stats
    assert stats["slices_executed"] >= LINEITEM_ROWS // 4096
    base = LocalQueryRunner.tpch("tiny")
    base.session.set("sliced_execution", False)
    expect = base.execute(
        "SELECT count(*), sum(l_quantity) FROM lineitem")
    assert got.rows == expect.rows
    assert base.last_query_stats["slices_executed"] == 0
    assert stats["chain_walks"] == stats["slices_executed"] == 15


def test_sliced_tpch_parity_q1(monkeypatch):
    """A full aggregation query through many small slices matches the
    sqlite oracle (slice boundaries are invisible to semantics)."""
    _walks_a_slice_a_launch(monkeypatch)
    oracle = load_tpch_sqlite(0.01)
    try:
        r = _sliced_runner()
        sql, oracle_sql, ordered = QUERIES["q1"]
        got = r.execute(sql)
        assert r.last_query_stats["slices_executed"] > 1
        assert_same(got.rows, oracle.execute(oracle_sql).fetchall(),
                    ordered)
    finally:
        oracle.close()


# ------------------------------------------------------ mid-slice chaos


def test_slice_site_chaos_task_retry_green():
    """Chaos kills the query BETWEEN slices; TASK retry re-runs the
    plan task and the answer stays exact."""
    r = _sliced_runner()
    _chaos(r, sites="slice", rate=0.5)
    got = r.execute("SELECT sum(l_extendedprice * l_discount) "
                    "FROM lineitem WHERE l_quantity < 24")
    clean = LocalQueryRunner.tpch("tiny")
    expect = clean.execute("SELECT sum(l_extendedprice * l_discount) "
                           "FROM lineitem WHERE l_quantity < 24")
    assert got.rows == expect.rows
    assert r.stats["faults_injected"] > 0
    assert r.stats["retries"] >= r.stats["faults_injected"]


def test_slice_site_chaos_none_fails():
    """Same chaos, retry_policy=NONE: the mid-slice kill is fatal and
    retryable-classified — proof the green run above was retries."""
    r = _sliced_runner()
    _chaos(r, sites="slice", rate=1.0, policy="NONE")
    with pytest.raises(InjectedFault) as exc:
        r.execute("SELECT sum(l_extendedprice) FROM lineitem")
    from trino_tpu.errors import is_retryable
    assert is_retryable(exc.value)
    assert "slice" in str(exc.value)


# --------------------------------------------------- cancellation latency


class _SlowTableMeta:
    """Minimal connector trio serving one BIGINT column over many
    deliberately slow pages — a long-running scan whose remaining wall
    dwarfs one slice, so cancellation latency is measurable."""

    def __init__(self, npages, rows_per_page):
        from trino_tpu.connector.spi import (ColumnMetadata,
                                             SchemaTableName,
                                             TableMetadata)
        self.npages = npages
        self.rows_per_page = rows_per_page
        self.name = SchemaTableName("default", "stream")
        self.table_meta = TableMetadata(
            self.name, (ColumnMetadata("x", T.BIGINT),))


def _slow_connector(npages=200, rows_per_page=1024, delay_s=0.01):
    from trino_tpu.connector.spi import (
        Connector, ConnectorMetadata, ConnectorPageSource,
        ConnectorSplitManager, ConnectorTableHandle, Split,
        TableStatistics)
    from trino_tpu.page import Column, Page

    spec = _SlowTableMeta(npages, rows_per_page)

    class Meta(ConnectorMetadata):
        def list_schemas(self):
            return ["default"]

        def list_tables(self, schema=None):
            return [spec.name]

        def get_table_handle(self, name):
            return ConnectorTableHandle(name) if name == spec.name \
                else None

        def get_table_metadata(self, handle):
            return spec.table_meta

        def get_table_statistics(self, handle):
            return TableStatistics(float(npages * rows_per_page))

    class Splits(ConnectorSplitManager):
        def get_splits(self, handle, target_splits=1):
            return [Split(handle, 0, 1)]

    class Source(ConnectorPageSource):
        def pages(self, split, columns, page_capacity):
            n = min(rows_per_page, page_capacity)
            arr = np.arange(n, dtype=np.int64)
            for _ in range(npages):
                time.sleep(delay_s)
                yield Page((Column.from_numpy(arr, T.BIGINT),), n)

    return Connector("slow", Meta(), Splits(), Source())


def test_cancel_latency_slice_bounded():
    """The acceptance bar: DELETE (the server's shared cancel event) on
    a RUNNING long scan frees the executor within ~one slice — far
    below the scan's remaining wall — reports preempt_latency_ms, and
    every HBM reservation unwinds."""
    from trino_tpu.exec.deadline import CancelEvent
    npages, delay = 200, 0.01           # ~2s of scan if never canceled
    r = _sliced_runner(slice_rows=1024, page_rows=1024)
    r.catalogs.register("slow", _slow_connector(npages, 1024, delay))
    outcome = {}
    cancel_event = CancelEvent()

    def worker():
        try:
            r.execute("SELECT sum(x) FROM slow.default.stream",
                      query_id="preempt_me", cancel_event=cancel_event)
            outcome["state"] = "finished"
        except QueryCanceledError:
            outcome["state"] = "canceled"
        except BaseException as e:      # noqa: BLE001
            outcome["state"] = f"error: {e!r}"
        outcome["done_at"] = time.monotonic()

    th = threading.Thread(target=worker)
    th.start()
    time.sleep(10 * delay)              # let a few slices complete
    cancel_event.cancel()               # the server's DELETE path
    th.join(timeout=30)
    assert not th.is_alive()
    assert outcome["state"] == "canceled", outcome
    freed_s = outcome["done_at"] - cancel_event.cancelled_at
    # one slice is one 1024-row page (~delay seconds of producer wall);
    # the bound is generous vs the ~1.9s the scan had left
    assert freed_s < 1.0, freed_s
    stats = r.last_query_stats
    assert 0 < stats["preempt_latency_ms"] < 1000
    assert stats["slices_executed"] >= 1
    from trino_tpu.exec.memory import NODE_POOL
    assert NODE_POOL.reserved == 0


# ------------------------------------------------- checkpointed resume


def test_fragment_retry_resumes_from_shard_checkpoints():
    """Distributed chaos at site `fragment`: every armed attempt dies
    AFTER at least one shard's checkpoint landed, so the retry restores
    completed shards instead of re-running them — checkpoints_restored
    counts the work NOT re-executed, and the answer stays exact."""
    from trino_tpu.exec.distributed import DistributedQueryRunner
    dist = DistributedQueryRunner.tpch("tiny")
    # seed 3 @ rate 0.45 injects >= 2 non-root fragment faults on q3
    # (seeds whose only hit is the checkpoint-less root fragment would
    # retry without restoring)
    _chaos(dist, sites="fragment", rate=0.45, seed=3, attempts=12)
    sql, oracle_sql, ordered = QUERIES["q3"]
    got = dist.execute(sql)
    stats = dist.last_query_stats
    assert stats["retries"] > 0, "seed injected nothing; pick another"
    assert stats["checkpoints_restored"] > 0
    assert stats["checkpoints_saved"] > 0
    assert stats["checkpoint_bytes"] > 0
    oracle = load_tpch_sqlite(0.01)
    try:
        assert_same(got.rows, oracle.execute(oracle_sql).fetchall(),
                    ordered)
    finally:
        oracle.close()


# --------------------------------------------------- idempotent writes


def test_insert_query_retry_writes_no_duplicates():
    """INSERT under retry_policy=QUERY with mid-slice chaos: the staged
    write-token sink makes the retries duplicate-free — the table lands
    EXACTLY the source rows."""
    r = _sliced_runner()
    r.execute("CREATE TABLE memory.default.li_copy AS "
              "SELECT l_orderkey FROM lineitem WHERE false")
    _chaos(r, sites="slice", rate=0.5, seed=3, policy="QUERY")
    r.execute("INSERT INTO memory.default.li_copy "
              "SELECT l_orderkey FROM lineitem")
    insert_stats = dict(r.last_query_stats)
    assert insert_stats["retries"] > 0, \
        "seed injected nothing; pick another"
    r.session.set("fault_injection_rate", 0.0)
    count = r.execute(
        "SELECT count(*) FROM memory.default.li_copy").only_value()
    assert count == LINEITEM_ROWS


def test_insert_none_policy_aborts_cleanly():
    """The other half of exactly-once: a failed un-retried INSERT
    commits NOTHING (abort drops the staging)."""
    r = _sliced_runner()
    r.execute("CREATE TABLE memory.default.li_none AS "
              "SELECT l_orderkey FROM lineitem WHERE false")
    _chaos(r, sites="slice", rate=1.0, policy="NONE")
    with pytest.raises(InjectedFault):
        r.execute("INSERT INTO memory.default.li_none "
                  "SELECT l_orderkey FROM lineitem")
    r.session.set("fault_injection_rate", 0.0)
    count = r.execute(
        "SELECT count(*) FROM memory.default.li_none").only_value()
    assert count == 0


def test_ctas_query_retry_exactly_once():
    """CTAS under QUERY retry: the DDL half replays (the query's own
    table re-creates without 'already exists') and the data half
    commits exactly once."""
    r = _sliced_runner()
    _chaos(r, sites="slice", rate=0.5, seed=9, policy="QUERY")
    r.execute("CREATE TABLE memory.default.li_ctas AS "
              "SELECT l_orderkey, l_quantity FROM lineitem")
    assert r.last_query_stats["retries"] > 0, \
        "seed injected nothing; pick another"
    r.session.set("fault_injection_rate", 0.0)
    count = r.execute(
        "SELECT count(*) FROM memory.default.li_ctas").only_value()
    assert count == LINEITEM_ROWS


def test_write_token_sink_idempotent_commit():
    """SPI-level contract: the same write token commits once; a fresh
    token commits again; abort drops staging."""
    from trino_tpu.connector import memory as mem
    from trino_tpu.connector.spi import (ColumnMetadata, SchemaTableName,
                                         TableMetadata)
    from trino_tpu.page import Column, Page
    conn = mem.create_connector()
    name = SchemaTableName("default", "tok")
    conn.metadata.create_table(TableMetadata(
        name, (ColumnMetadata("a", T.BIGINT),)))
    h = conn.metadata.get_table_handle(name)
    page = Page((Column.from_numpy(
        np.arange(4, dtype=np.int64), T.BIGINT),), 4)

    sink = conn.page_sink(h, write_token="q1")
    sink.append_page(page)
    sink.finish()
    retry = conn.page_sink(h, write_token="q1")     # the retried attempt
    retry.append_page(page)
    retry.finish()                                  # no-op: q1 committed
    aborted = conn.page_sink(h, write_token="q2")
    aborted.append_page(page)
    aborted.abort()
    aborted.finish()        # staging was dropped; q2 commits zero rows
    fresh = conn.page_sink(h, write_token="q3")
    fresh.append_page(page)
    fresh.finish()
    assert conn._metadata.stored(name).row_count == 8   # q1 + q3 only


# ------------------------------------------------------------ satellites


def test_plan_cache_generation_guard_unified():
    """PR 7 follow-up: all three table-keyed caches share ONE
    put-generation race guard (the _GenerationGuard mixin)."""
    from trino_tpu.exec.plan_cache import PlanCache, _GenerationGuard
    from trino_tpu.serve.caches import ResultSetCache, ScanCache
    assert issubclass(PlanCache, _GenerationGuard)
    assert issubclass(ResultSetCache, _GenerationGuard)
    assert issubclass(ScanCache, _GenerationGuard)
    pc = PlanCache()
    gen = pc.generation()
    pc.invalidate(("m", "d", "t"))
    pc.put("k", object(), frozenset({("m", "d", "t")}), gen=gen)
    assert pc.get("k") is None      # pre-invalidation plan rejected


def test_group_cache_hit_accounting():
    """A result-cache fast-path completion charges the whole group
    chain's completed/served-from-cache counters (group QPS quotas see
    cached traffic) without touching the stride pass."""
    from trino_tpu.exec.resource_groups import ResourceGroupManager
    mgr = ResourceGroupManager()
    g = mgr.get_or_create("adhoc.alice")
    pass_before = g._pass
    mgr.record_cache_hit("adhoc.alice")
    assert g.served_from_cache == 1
    assert g.started == 1 and g.finished == 1
    assert g._pass == pass_before       # zero executor cost, zero stride
    parent = mgr.get_or_create("adhoc")
    assert parent.served_from_cache == 1 and parent.finished == 1


def test_resource_groups_table_served_from_cache_column():
    r = LocalQueryRunner.tpch("tiny")
    got = r.execute("SELECT name, served_from_cache "
                    "FROM system.runtime.resource_groups")
    assert got.column_names == ["name", "served_from_cache"]


def test_server_cache_hit_charges_group():
    """Over the wire: the second identical POST answers from the result
    cache AND lands on the group's served_from_cache counter."""
    import json
    from urllib import request as urlreq
    from trino_tpu.server import TrinoServer
    srv = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    try:
        headers = {"X-Trino-User": "t",
                   "X-Trino-Session": "resource_group=cached.bi"}
        sql = "SELECT count(*) FROM nation"

        def post():
            req = urlreq.Request(f"{srv.base_uri}/v1/statement",
                                 data=sql.encode(), headers=headers)
            out = json.loads(urlreq.urlopen(req).read())
            while out.get("nextUri"):
                out = json.loads(urlreq.urlopen(out["nextUri"]).read())
            return out

        post()                          # miss: executes + caches
        post()                          # hit: the POST-time fast path
        group = srv.groups.get_or_create("cached.bi")
        assert group.served_from_cache >= 1
        assert group.finished >= group.served_from_cache
    finally:
        srv.stop()


def test_wall_buckets_configurable():
    from trino_tpu.obs.metrics import (QUERY_WALL_SECONDS, REGISTRY,
                                       set_wall_buckets)
    saved = QUERY_WALL_SECONDS.buckets
    try:
        set_wall_buckets((0.25, 2.5, 25.0))
        assert QUERY_WALL_SECONDS.buckets == (0.25, 2.5, 25.0)
        QUERY_WALL_SECONDS.observe(1.0)
        text = REGISTRY.render()
        assert 'trino_tpu_query_wall_seconds_bucket{le="2.5"}' in text
        assert 'le="0.005"' not in text.split(
            "trino_tpu_query_wall_seconds")[1]
    finally:
        QUERY_WALL_SECONDS.set_buckets(saved)


def test_wall_buckets_env_default(monkeypatch):
    from trino_tpu.obs import metrics as m
    monkeypatch.setenv("TRINO_TPU_METRICS_WALL_BUCKETS", "0.5, 5, 50")
    assert m._env_wall_buckets() == (0.5, 5.0, 50.0)
    monkeypatch.setenv("TRINO_TPU_METRICS_WALL_BUCKETS", "bogus")
    assert m._env_wall_buckets() == m.DEFAULT_WALL_BUCKETS
    monkeypatch.delenv("TRINO_TPU_METRICS_WALL_BUCKETS")
    assert m._env_wall_buckets() == m.DEFAULT_WALL_BUCKETS


def test_slice_metrics_exported():
    """The new counter families reach the Prometheus rendering after a
    sliced query completes."""
    r = _sliced_runner()
    r.execute("SELECT count(*) FROM lineitem")
    assert r.last_query_stats["slices_executed"] >= 1
    from trino_tpu.obs.metrics import REGISTRY
    text = REGISTRY.render()
    assert "trino_tpu_slices_total" in text
    assert "trino_tpu_checkpoint_bytes_total" in text
    assert "trino_tpu_preempt_latency_seconds_bucket" in text
    assert "trino_tpu_checkpoints_saved" in text
