"""Memory accounting: query ledger, node pool, low-memory killer, leaks.

Reference parity: memory/MemoryPool.java:44 reservations +
ExceededMemoryLimitException ("Query exceeded per-node memory limit"),
checked at blocking-operator materialization; memory/ClusterMemoryManager
+ TotalReservationLowMemoryKiller for the node-pool overflow path; tpch
device-column cache honors an LRU byte budget (round-2 finding).
"""

import threading

import pytest

from trino_tpu.exec import LocalQueryRunner
from trino_tpu.exec.memory import (NODE_POOL, ClusterOutOfMemoryError,
                                   ExceededMemoryLimitError,
                                   NodeMemoryPool, QueryMemoryContext,
                                   page_bytes)


def test_context_reserve_and_limit():
    ctx = QueryMemoryContext(1000)
    ctx.reserve(600, "join-build")
    ctx.reserve(300, "collect")
    assert ctx.reserved == 900 and ctx.peak == 900
    with pytest.raises(ExceededMemoryLimitError) as e:
        ctx.reserve(200, "sort")
    assert "Query exceeded per-node memory limit" in str(e.value)
    assert "sort" in str(e.value)
    ctx.free(600, "join-build")
    ctx.reserve(200, "sort")        # fits after free
    assert ctx.peak == 900


def test_query_over_limit_fails_cleanly():
    r = LocalQueryRunner.tpch("tiny")
    r.execute("SET SESSION query_max_memory = 1000")
    try:
        with pytest.raises(ExceededMemoryLimitError):
            # order-by collects the whole customer table: >> 1kB
            r.execute("SELECT c_custkey FROM customer ORDER BY c_acctbal")
    finally:
        r.execute("RESET SESSION query_max_memory")
    # and runs fine once the limit is back to default
    out = r.execute("SELECT count(*) FROM customer")
    assert out.rows == [(1500,)]


def test_page_bytes_counts_values_and_nulls():
    r = LocalQueryRunner.tpch("tiny")
    res = r.execute("SELECT 1")
    assert res.rows == [(1,)]


def test_device_cache_bounded():
    from trino_tpu.connector import tpch as m
    assert m._DEVICE_COL_CACHE_USED <= m._DEVICE_COL_CACHE_BYTES
    assert m._DEVICE_COL_CACHE_USED == sum(
        c.nbytes for c in m._DEVICE_COL_CACHE.values())


def test_query_max_memory_zero_is_zero():
    r = LocalQueryRunner.tpch("tiny")
    r.execute("SET SESSION query_max_memory = 0")
    with pytest.raises(ExceededMemoryLimitError):
        r.execute("SELECT c_custkey FROM customer ORDER BY c_acctbal")


# ----------------------------------------------------------- node pool


def test_node_pool_accounting_and_release():
    pool = NodeMemoryPool(limit_bytes=1000)
    a = QueryMemoryContext(None, query_id="qa", pool=pool)
    b = QueryMemoryContext(None, query_id="qb", pool=pool)
    a.reserve(400, "collect")
    b.reserve(500, "collect")
    assert pool.reserved == 900 and pool.peak == 900
    a.free(400, "collect")
    assert pool.reserved == 500
    assert a.close() == 0
    assert b.close() == 500          # b leaked; close releases anyway
    assert pool.reserved == 0


def test_killer_selects_largest_reservation():
    """total-reservation policy: the victim is the query with the
    biggest ledger, NOT the requester (TotalReservationLowMemoryKiller),
    and the victim dies at its next reservation/checkpoint."""
    pool = NodeMemoryPool(limit_bytes=1000)
    big = QueryMemoryContext(None, query_id="big", pool=pool)
    small = QueryMemoryContext(None, query_id="small", pool=pool,
                               wait_s=0.05)
    big.reserve(700, "join-build")
    small.reserve(200, "collect")
    # small's next reservation would overflow -> killer marks `big`;
    # big never frees (no thread runs it), so small times out retryable
    with pytest.raises(ClusterOutOfMemoryError):
        small.reserve(300, "collect")
    assert big.kill_reason is not None and "big" in big.kill_reason
    assert big.kills == 1 and pool.kills == 1
    with pytest.raises(ClusterOutOfMemoryError):
        big.poll()                   # victim dies at its checkpoint
    with pytest.raises(ClusterOutOfMemoryError):
        big.reserve(1, "collect")    # ... or at its next reservation
    big.close()
    small.close()
    assert pool.reserved == 0


def test_killer_self_inflicted_fails_requester():
    """When the requester IS the largest reservation, it self-kills
    immediately (no pointless wait) with the retryable error."""
    pool = NodeMemoryPool(limit_bytes=1000)
    only = QueryMemoryContext(None, query_id="only", pool=pool)
    only.reserve(900, "collect")
    with pytest.raises(ClusterOutOfMemoryError) as e:
        only.reserve(200, "collect")
    assert e.value.retryable
    assert e.value.error_name == "CLUSTER_OUT_OF_MEMORY"
    only.reset_attempt()             # retry path clears the mark
    assert only.kill_reason is None and pool.reserved == 0
    only.reserve(500, "collect")     # fits after the rollback
    only.free(500, "collect")
    only.close()


def test_killer_waits_for_victim_release():
    """The requester blocks while the marked victim unwinds on its own
    thread, then proceeds — no error on either side's SECOND attempt."""
    pool = NodeMemoryPool(limit_bytes=1000)
    victim = QueryMemoryContext(None, query_id="victim", pool=pool)
    victim.reserve(800, "collect")
    requester = QueryMemoryContext(None, query_id="req", pool=pool,
                                   wait_s=5.0)

    def victim_thread():
        # poll until killed, then unwind (release everything)
        for _ in range(500):
            try:
                victim.poll()
            except ClusterOutOfMemoryError:
                break
            threading.Event().wait(0.01)
        victim.rollback_to(0)
    th = threading.Thread(target=victim_thread)
    th.start()
    requester.reserve(600, "collect")   # blocks, then granted
    th.join(timeout=10)
    assert pool.reserved == 600
    assert victim.kill_reason is not None
    requester.free(600, "collect")
    victim.close()
    requester.close()
    assert pool.reserved == 0


def test_killer_policy_none_fails_requester():
    pool = NodeMemoryPool(limit_bytes=100, killer_policy="none")
    a = QueryMemoryContext(None, query_id="a", pool=pool)
    b = QueryMemoryContext(None, query_id="b", pool=pool, wait_s=0.05)
    a.reserve(90, "collect")
    with pytest.raises(ClusterOutOfMemoryError):
        b.reserve(50, "collect")
    # NOBODY killed and NO kill recorded: pool_kills must read zero on a
    # node whose killer is disabled
    assert a.kill_reason is None and b.kill_reason is None
    assert pool.kills == 0 and a.kills == 0 and b.kills == 0
    a.close()
    b.close()


def test_cluster_oom_retry_query_reruns_and_succeeds():
    """End-to-end: a query whose collect overflows the shared NODE pool
    is killed retryable; retry_policy=QUERY re-runs it (spill-forced)
    and it completes once the competing reservation is gone."""
    r = LocalQueryRunner.tpch("tiny")
    hog = QueryMemoryContext(None, query_id="hog", pool=NODE_POOL)
    sql = "SELECT c_custkey FROM customer ORDER BY c_acctbal"
    with NODE_POOL.limited(64 << 10):
        hog.reserve(60 << 10, "join-build")
        r.execute("SET SESSION retry_policy = 'NONE'")
        with pytest.raises(ClusterOutOfMemoryError):
            r.execute(sql)
        # the hog (largest reservation) was marked victim
        assert hog.kill_reason is not None
        hog.rollback_to(0)           # "the victim unwinds"
        hog.close()
        r.execute("SET SESSION retry_policy = 'QUERY'")
        out = r.execute(sql)
        assert len(out.rows) == 1500
    r.execute("RESET SESSION retry_policy")
    assert NODE_POOL.reserved == 0


def test_leak_detector_warns_and_counts():
    """A successful query whose ledger ends nonzero surfaces a warning +
    counters; the bytes still release (the leak gate stays green)."""
    from trino_tpu.exec.query_tracker import TRACKER
    r = LocalQueryRunner.tpch("tiny")
    leaks_before = NODE_POOL.leaks
    # sabotage: make free() a no-op for this one query's executor
    import trino_tpu.exec.local_planner as lp
    orig = lp.LocalExecutionPlanner._free_collected
    lp.LocalExecutionPlanner._free_collected = lambda self, page: None
    try:
        out = r.execute("SELECT c_custkey FROM customer ORDER BY c_acctbal")
        assert len(out.rows) == 1500
    finally:
        lp.LocalExecutionPlanner._free_collected = orig
    assert NODE_POOL.leaks == leaks_before + 1
    assert NODE_POOL.reserved == 0           # close() released the leak
    info = next(q for q in TRACKER.list()
                if q.query_id == r.session.query_id or
                q.query and "c_acctbal" in q.query and q.leaked_bytes)
    assert info.leaked_bytes > 0
    assert any("reservation leak" in w for w in info.warnings)
    rows = r.execute(
        "SELECT leaked_bytes FROM system.runtime.queries "
        "WHERE leaked_bytes > 0").rows
    assert rows and rows[0][0] > 0


def test_per_device_enforcement_for_measured_budgets():
    """A MEASURED pool limit is one chip's HBM: device-hinted
    reservations enforce against that chip's running total, so a mesh
    query staging n shards of size ~limit/n each fits even though the
    cross-chip SUM exceeds the single-chip limit. Hand-set limits keep
    the historical global-sum enforcement (the chaos-test contract)."""
    from trino_tpu.exec.memory import (ClusterOutOfMemoryError,
                                       NodeMemoryPool, QueryMemoryContext)
    pool = NodeMemoryPool(limit_bytes=1000, killer_policy="none")
    pool.enforce_per_device = True
    ctx = QueryMemoryContext(None, pool=pool, wait_s=0.0)
    try:
        for shard in range(8):
            ctx.reserve(800, "mesh-stage", device=shard)   # sum = 6400
        assert pool.reserved == 6400
        assert all(pool.device_reserved[d] == 800 for d in range(8))
        # the same chip overflowing ITS budget still fails
        with pytest.raises(ClusterOutOfMemoryError):
            ctx.reserve(300, "mesh-stage", device=0)
        # global-sum enforcement for un-hinted reservations is unchanged
        with pytest.raises(ClusterOutOfMemoryError):
            ctx.reserve(10, "collect")
        for shard in range(8):
            ctx.free(800, "mesh-stage", device=shard)
        assert pool.reserved == 0
        assert all(v == 0 for v in pool.device_reserved.values())
    finally:
        assert ctx.close() == 0


def _device_refusal():
    import jax
    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 1.50G. That was not possible. There are 1.2G free.")


@pytest.mark.parametrize("make, name, retryable", [
    (_device_refusal, "EXCEEDED_DEVICE_MEMORY_LIMIT", True),
    (lambda: type(_device_refusal())("INVALID_ARGUMENT: bad shape"),
     "GENERIC_INTERNAL_ERROR", False),
    (lambda: RuntimeError("RESOURCE_EXHAUSTED: not XLA's"),
     "GENERIC_INTERNAL_ERROR", False),
], ids=["resource_exhausted", "another_status", "another_class"])
def test_device_refusal_classifies_as_the_memory_error(make, name,
                                                       retryable):
    """An XLA runtime error whose status is RESOURCE_EXHAUSTED is the
    engine's retryable memory error; other statuses and other classes
    stay internal errors."""
    from trino_tpu.errors import classify, is_retryable
    code = classify(make())
    assert code.name == name and code.retryable is retryable
    assert is_retryable(make()) is retryable
    if retryable:
        assert code.type == "INSUFFICIENT_RESOURCES"


@pytest.mark.parametrize("policy", ["NONE", "QUERY"])
def test_device_refusal_is_counted_and_releases_the_ledger(policy,
                                                           monkeypatch):
    """A dispatch the device refuses fails the query under its own name
    with `device_oom_errors` 1 and nothing left reserved; under
    retry_policy=QUERY the query is run again and answers."""
    from trino_tpu.exec.query_tracker import TRACKER
    r = LocalQueryRunner.tpch("tiny")
    r.execute(f"SET SESSION retry_policy = '{policy}'")
    run, refused = LocalQueryRunner._execute_statement, []

    def refuse_once(self, stmt):
        if not refused:
            refused.append(stmt)
            self._memory.reserve(4096, "collect")
            raise _device_refusal()
        return run(self, stmt)
    monkeypatch.setattr(LocalQueryRunner, "_execute_statement", refuse_once)
    sql = "SELECT count(*) FROM nation"
    if policy == "NONE":
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            r.execute(sql, query_id="refused_none")
    else:
        assert r.execute(sql, query_id="refused_query").rows == [(25,)]
    info = next(q for q in TRACKER.list()
                if q.query_id == f"refused_{policy.lower()}")
    assert info.stats["device_oom_errors"] == 1
    assert info.stats["memory_kills"] == 0
    assert info.stats["retries"] == (policy == "QUERY")
    if policy == "NONE":
        assert info.state == "FAILED"
        assert info.error_name == "EXCEEDED_DEVICE_MEMORY_LIMIT"
    assert NODE_POOL.reserved == 0


def test_the_killers_victim_says_so_in_its_stats():
    """A query alone in a pool too small for it is its own victim:
    `memory_kills` 1 in the stats `GET /v1/query/<id>` serves."""
    from trino_tpu.exec.query_tracker import TRACKER
    r = LocalQueryRunner.tpch("tiny")
    kills = NODE_POOL.kills
    with NODE_POOL.limited(8 << 10):
        with pytest.raises(ClusterOutOfMemoryError):
            r.execute("SELECT c_custkey FROM customer ORDER BY c_acctbal",
                      query_id="own_victim")
    info = next(q for q in TRACKER.list() if q.query_id == "own_victim")
    assert info.stats["memory_kills"] == 1 == NODE_POOL.kills - kills
    assert info.stats["device_oom_errors"] == 0
    assert NODE_POOL.reserved == 0
