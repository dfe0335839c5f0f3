"""Chaos runs: TPC-H under fault injection, oracle-verified.

Reference parity: testing/trino-faulttolerant-tests
(TestFaultTolerantExecution* — TPC queries stay correct under injected
task failure with RetryPolicy.TASK).

With a FIXED seed the injector's decisions replay exactly, so the green
runs under retry_policy=TASK and the red run under retry_policy=NONE
prove retries (not luck) produced the green results.

Named test_zz_* so these sweeps collect LAST: the tier-1 wall budget
spends on the seed suites first and on chaos afterwards. The full
distributed sweep (all 22 queries, ~12 min) is marked slow; tier-1 keeps
one seed over all 22 queries on the local engine plus a cheap
distributed subset.
"""

import pytest

from trino_tpu.errors import InjectedFault, is_retryable
from trino_tpu.exec import LocalQueryRunner
from trino_tpu.exec.distributed import DistributedQueryRunner

from oracle import assert_same, load_tpch_sqlite
from tpch_sql import PASSING, QUERIES

CHAOS_SEED = 42
CHAOS_RATE = 0.2

# tier-1 distributed chaos subset (cheap fragments); the rest of the
# distributed sweep runs under `slow`
CHEAP_DIST = ["q1", "q6", "q12", "q14"]


def set_chaos(runner, *, seed=CHAOS_SEED, rate=CHAOS_RATE, policy="TASK"):
    runner.session.set("fault_injection_seed", seed)
    runner.session.set("fault_injection_rate", rate)
    runner.session.set("retry_policy", policy)


@pytest.fixture(scope="module")
def oracle():
    conn = load_tpch_sqlite(0.01)
    yield conn
    conn.close()


@pytest.fixture(scope="module")
def chaos_dist():
    runner = DistributedQueryRunner.tpch("tiny")
    set_chaos(runner, policy="TASK")
    return runner


@pytest.fixture(scope="module")
def chaos_local():
    runner = LocalQueryRunner.tpch("tiny")
    set_chaos(runner, policy="TASK")
    return runner


@pytest.mark.parametrize("name", PASSING)
def test_tpch_chaos_local(chaos_local, oracle, name):
    """One seed over ALL 22 queries in tier-1 (local engine: same retry
    scopes — plan task, scan and spill sites — at a fraction of the
    distributed sweep's wall cost)."""
    sql, oracle_sql, ordered = QUERIES[name]
    got = chaos_local.execute(sql)
    expected = oracle.execute(oracle_sql).fetchall()
    assert_same(got.rows, expected, ordered)


@pytest.mark.parametrize("name", CHEAP_DIST)
def test_tpch_chaos_distributed(chaos_dist, oracle, name):
    """Seed 42 / rate 0.2 / retry_policy=TASK — fragment-retry chaos on
    the distributed engine, oracle-verified."""
    sql, oracle_sql, ordered = QUERIES[name]
    got = chaos_dist.execute(sql)
    expected = oracle.execute(oracle_sql).fetchall()
    assert_same(got.rows, expected, ordered)


@pytest.mark.slow
@pytest.mark.parametrize("name", [q for q in PASSING
                                  if q not in CHEAP_DIST])
def test_tpch_chaos_distributed_full(chaos_dist, oracle, name):
    """Acceptance sweep: seed 42 / rate 0.2 / retry_policy=TASK — EVERY
    TPC-H query oracle-verifies despite injected fragment/exchange/scan
    faults (verified green in full before being marked slow for the
    tier-1 wall budget)."""
    sql, oracle_sql, ordered = QUERIES[name]
    got = chaos_dist.execute(sql)
    expected = oracle.execute(oracle_sql).fetchall()
    assert_same(got.rows, expected, ordered)


def test_tpch_chaos_injected_something(chaos_dist, chaos_local):
    """The green sweeps above must actually have seen faults — otherwise
    they prove nothing. Cumulative counters live on the runners; the
    cheap subset runs here once more so the counters are not empty on an
    xdist worker that was handed this test without the sweeps."""
    for name in CHEAP_DIST:
        chaos_local.execute(QUERIES[name][0])
        chaos_dist.execute(QUERIES[name][0])
    injected = (chaos_local.stats["faults_injected"]
                + chaos_dist.stats["faults_injected"])
    retries = chaos_local.stats["retries"] + chaos_dist.stats["retries"]
    assert injected > 0
    assert retries >= injected


def test_tpch_chaos_retry_none_fails():
    """Same seed, retry_policy=NONE: the sweep fails with a
    retryable-classified error — proof the TASK runs' green came from
    retries, not luck. (Site `memory` raises CLUSTER_OUT_OF_MEMORY-
    classified pressure; every other site is REMOTE_TASK_ERROR.)"""
    runner = DistributedQueryRunner.tpch("tiny")
    set_chaos(runner, policy="NONE")
    saw_fault = None
    for name in PASSING:
        sql, _, _ = QUERIES[name]
        try:
            runner.execute(sql)
        except InjectedFault as e:
            saw_fault = e
            break
    assert saw_fault is not None
    assert is_retryable(saw_fault)
    assert saw_fault.error_name in ("REMOTE_TASK_ERROR",
                                    "CLUSTER_OUT_OF_MEMORY")


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_tpch_chaos_seed_sweep(oracle, seed):
    """High-iteration chaos: several seeds at a higher rate, local engine
    (cheaper per query, same retry scopes)."""
    runner = LocalQueryRunner.tpch("tiny")
    set_chaos(runner, seed=seed, rate=0.3, policy="TASK")
    for name in PASSING:
        sql, oracle_sql, ordered = QUERIES[name]
        got = runner.execute(sql)
        expected = oracle.execute(oracle_sql).fetchall()
        assert_same(got.rows, expected, ordered)


# ------------------------------------------------- concurrency + node OOM
#
# The round-7 resource-governance acceptance bar: concurrent TPC-H
# queries over a NODE pool sized to fit only ~2 of them, fault site
# `memory` active — the low-memory killer selects victims, victims fail
# with retryable CLUSTER_OUT_OF_MEMORY, retry_policy=QUERY re-runs them,
# and everything finishes oracle-correct; under NONE the same pressure
# provably loses queries.

CONCURRENT_QS = ["q1", "q3", "q10", "q18"]


def _solo_peak(name) -> int:
    """Peak node-pool bytes of one query run alone (sizes the pool)."""
    from trino_tpu.exec.query_tracker import TRACKER
    r = LocalQueryRunner.tpch("tiny")
    qid = f"solo_peak_{name}_{id(r)}"
    r.execute(QUERIES[name][0], query_id=qid)
    info = next(q for q in TRACKER.list() if q.query_id == qid)
    return info.pool_peak_bytes


def _tight_pool(queries=None) -> int:
    """A pool that fits ~2 of the concurrent set: each query runs fine
    alone (>= 1.2x the largest solo peak) but the set's combined peaks
    overflow (~55% of their sum)."""
    queries = queries or CONCURRENT_QS
    peaks = [_solo_peak(n) for n in queries]
    return max(int(1.2 * max(peaks)), int(0.55 * sum(peaks)), 1 << 20)


def _run_concurrent(policy, pool_limit, *, rate=0.0, rounds=1,
                    attempts=10, queries=None):
    """Run each query on its own thread (per-query runner clones over
    shared catalogs — the server's executor-pool shape), all released by
    a barrier, over a bounded NODE pool. Returns (results, errors)."""
    import threading

    from trino_tpu.exec.memory import NODE_POOL
    queries = queries or CONCURRENT_QS
    base = LocalQueryRunner.tpch("tiny")
    results, errors = {}, {}
    barrier = threading.Barrier(len(queries))

    def worker(name):
        try:
            r = base.for_query()
            r.session.set("retry_policy", policy)
            r.session.set("retry_attempts", attempts)
            r.session.set("cluster_memory_wait_ms", 500)
            if rate > 0:
                r.session.set("fault_injection_rate", rate)
                r.session.set("fault_injection_seed", CHAOS_SEED)
                r.session.set("fault_injection_sites", "memory")
            barrier.wait(timeout=60)
            for _ in range(rounds):
                results[name] = r.execute(QUERIES[name][0])
        except Exception as e:  # noqa: BLE001 — the assertions decide
            errors[name] = e
            results.pop(name, None)

    with NODE_POOL.limited(pool_limit):
        threads = [threading.Thread(target=worker, args=(n,))
                   for n in queries]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        assert not any(th.is_alive() for th in threads)
    return results, errors


def test_zz_concurrent_pair_smoke(oracle):
    """Tier-1 smoke: two concurrent queries over a bounded pool with
    QUERY retry — both oracle-correct, pool drains to zero (the full
    4-query OOM sweeps run under `slow`)."""
    from trino_tpu.exec.memory import NODE_POOL
    pair = ["q1", "q3"]
    results, errors = _run_concurrent("QUERY", _tight_pool(pair),
                                      queries=pair)
    assert not errors, {k: repr(v) for k, v in errors.items()}
    for name in pair:
        _, oracle_sql, ordered = QUERIES[name]
        expected = oracle.execute(oracle_sql).fetchall()
        assert_same(results[name].rows, expected, ordered)
    assert NODE_POOL.reserved == 0


@pytest.mark.slow
def test_zz_concurrent_oom_query_retry_all_correct(oracle):
    """4 concurrent TPC-H queries, pool sized for ~2, chaos site
    `memory` armed: kills/pressure happen, QUERY retry absorbs them, and
    EVERY query finishes oracle-correct."""
    from trino_tpu.exec.memory import NODE_POOL
    pool_limit = _tight_pool()
    kills_before = NODE_POOL.kills
    results, errors = _run_concurrent("QUERY", pool_limit, rate=0.25,
                                      rounds=2)
    assert not errors, {k: repr(v) for k, v in errors.items()}
    for name in CONCURRENT_QS:
        _, oracle_sql, ordered = QUERIES[name]
        expected = oracle.execute(oracle_sql).fetchall()
        assert_same(results[name].rows, expected, ordered)
    # the run must have actually seen pressure (killer or injected)
    from trino_tpu.exec.query_tracker import TRACKER
    pressure = (NODE_POOL.kills - kills_before) + sum(
        q.faults_injected for q in TRACKER.list())
    assert pressure > 0
    assert NODE_POOL.reserved == 0


@pytest.mark.slow
def test_zz_concurrent_oom_retry_none_loses_victims():
    """Same pressure, retry_policy=NONE: the victims are LOST, and they
    die with the retryable CLUSTER_OUT_OF_MEMORY verdict (proof the
    QUERY-policy green above came from retries, not luck)."""
    results, errors = _run_concurrent("NONE", _tight_pool(), rate=0.25,
                                      rounds=3)
    assert errors, "expected at least one lost victim under NONE"
    from trino_tpu.errors import TrinoError, is_retryable
    for name, e in errors.items():
        assert isinstance(e, TrinoError), (name, repr(e))
        assert e.error_name == "CLUSTER_OUT_OF_MEMORY", (name, repr(e))
        assert is_retryable(e)


@pytest.mark.slow
def test_zz_concurrent_oom_sustained_rounds(oracle):
    """Sustained load: every query runs multiple rounds under the tight
    pool + chaos; all rounds stay oracle-correct."""
    results, errors = _run_concurrent("QUERY", _tight_pool(), rate=0.25,
                                      rounds=3)
    assert not errors, {k: repr(v) for k, v in errors.items()}
    for name in CONCURRENT_QS:
        _, oracle_sql, ordered = QUERIES[name]
        expected = oracle.execute(oracle_sql).fetchall()
        assert_same(results[name].rows, expected, ordered)


@pytest.mark.slow
def test_zz_concurrent_all22_two_lanes(oracle):
    """Two lanes race through ALL 22 TPC-H queries concurrently over an
    UNBOUNDED pool (pure concurrency shake-out of the shared caches /
    tracker / ledger); verification runs on the main thread afterwards
    (the sqlite oracle connection is thread-bound)."""
    import threading
    base = LocalQueryRunner.tpch("tiny")
    lanes = {0: list(PASSING), 1: list(reversed(PASSING))}
    got_rows = {0: {}, 1: {}}
    failures = []

    def worker(lane):
        r = base.for_query()
        name = None
        try:
            for name in lanes[lane]:
                got_rows[lane][name] = r.execute(QUERIES[name][0]).rows
        except BaseException as e:  # noqa: BLE001
            failures.append((lane, name, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=1200)
    assert not failures, failures[:2]
    for lane in (0, 1):
        for name in PASSING:
            _, oracle_sql, ordered = QUERIES[name]
            expected = oracle.execute(oracle_sql).fetchall()
            assert_same(got_rows[lane][name], expected, ordered)


# -------------------------------------- adaptive spill paths under chaos
#
# PR-10 acceptance: fault site `spill` must provably fire INSIDE the
# recursive-repartition / heavy-key / chunked-fallback paths — not just
# at the first streaming flush. The injector's site entries accept a
# pass-skip suffix ("spill@K" fires on the (K+1)-th pass), and site
# passes are deterministic per config, so the proof protocol is:
# count the passes with an unreachable skip, target the LAST pass (the
# deepest recursion-side event), show it is FATAL under NONE with the
# path name in the error, and oracle-GREEN under TASK retry.

ADAPTIVE_AGG_SQL = (
    "SELECT l_orderkey, l_linenumber, sum(l_extendedprice) AS s "
    "FROM lineitem GROUP BY l_orderkey, l_linenumber")
ADAPTIVE_AGG_ORACLE = (
    "SELECT l_orderkey, l_linenumber, sum(l_extendedprice) "
    "FROM lineitem GROUP BY l_orderkey, l_linenumber")
ADAPTIVE_JOIN_SQL = (
    "SELECT count(*), sum(l2.l_extendedprice) FROM lineitem l1 "
    "JOIN lineitem l2 ON l1.l_orderkey = l2.l_orderkey")
ADAPTIVE_JOIN_ORACLE = ADAPTIVE_JOIN_SQL


def _adaptive_chaos_runner(policy, sites, seed=11, rate=1.0, attempts=8):
    runner = LocalQueryRunner.tpch("tiny")
    for k, v in {"page_capacity": 2048, "scan_page_capacity": 2048,
                 "spill_partition_count": 4,
                 "agg_spill_threshold_bytes": 1 << 15,
                 "join_spill_threshold_bytes": 1 << 14,
                 "spill_max_recursion": 2,
                 "retry_policy": policy,
                 "retry_attempts": attempts,
                 "fault_injection_seed": seed,
                 "fault_injection_rate": rate,
                 "fault_injection_sites": sites}.items():
        runner.session.set(k, v)
    return runner


def _count_spill_passes(sql):
    """Deterministic spill-site pass count for one query under the
    adaptive-chaos config: arm `spill` with an unreachable skip and read
    how far the skip counter ran down."""
    runner = _adaptive_chaos_runner("NONE", "spill@1000000")
    runner.execute(sql)
    return 1000000 - runner._faults._skip


def _spill_chaos_proof(oracle, sql, oracle_sql, inside_tags):
    passes = _count_spill_passes(sql)
    assert passes > 0
    target = f"spill@{passes - 1}"
    # fatal under NONE, with the recursion-side path named in the error
    runner = _adaptive_chaos_runner("NONE", target, rate=1.0)
    with pytest.raises(InjectedFault) as ei:
        runner.execute(sql)
    msg = str(ei.value)
    assert any(tag in msg for tag in inside_tags), \
        f"fault fired outside the adaptive paths: {msg}"
    assert is_retryable(ei.value)
    # oracle-green under TASK with the SAME deep targeting; at least one
    # seed must actually inject (and then retry through) the deep fault
    injected_inside = False
    for seed in range(6):
        green = _adaptive_chaos_runner("TASK", target, seed=seed,
                                       rate=0.45, attempts=8)
        got = green.execute(sql)
        expected = oracle.execute(oracle_sql).fetchall()
        assert_same(got.rows, expected, False)
        if green.stats["faults_injected"] > 0:
            details = green._faults.by_detail
            assert any(k[0] == "spill" and
                       any(t in k[1] for t in inside_tags)
                       for k in details), details
            injected_inside = True
            break
    assert injected_inside, "no TASK seed injected the deep spill fault"


def test_chaos_spill_fires_inside_agg_recursion(oracle):
    _spill_chaos_proof(oracle, ADAPTIVE_AGG_SQL, ADAPTIVE_AGG_ORACLE,
                       ("agg-recurse", "agg-heavy", "agg-fallback"))


def test_chaos_spill_fires_inside_join_recursion(oracle):
    _spill_chaos_proof(oracle, ADAPTIVE_JOIN_SQL, ADAPTIVE_JOIN_ORACLE,
                       ("join-recurse", "join-heavy", "join-fallback"))


# --------------------- data-plane corruption chaos (checksummed lake)

LAKE_CHAOS_QS = ["q1", "q6"]    # lineitem-only: one CTAS seeds the lake


@pytest.fixture(scope="module")
def lake_chaos(tmp_path_factory):
    """TPC-H lineitem CTAS'd into a checksummed lake table; the session
    then points at the lake catalog so the stock query texts scan it."""
    import os
    d = tmp_path_factory.mktemp("lakechaos")
    old = os.environ.get("TRINO_TPU_LAKE_DIR")
    os.environ["TRINO_TPU_LAKE_DIR"] = str(d / "lake")
    try:
        runner = LocalQueryRunner.tpch("tiny")
        runner.execute("CREATE TABLE lake.tiny.lineitem AS "
                       "SELECT * FROM lineitem")
        runner.session.catalog = "lake"
        yield runner
    finally:
        if old is None:
            os.environ.pop("TRINO_TPU_LAKE_DIR", None)
        else:
            os.environ["TRINO_TPU_LAKE_DIR"] = old


def test_zz_corruption_chaos_sweep(lake_chaos, oracle):
    """The data-integrity acceptance sweep: `corrupt`-site chaos (a
    deterministic bit flip in a decoded column, between decode and
    verification) at rate 0.3 over lake-backed TPC-H. Under BOTH retry
    policies every query either returns oracle-correct rows or fails
    with the classified LAKE_DATA_CORRUPTION error — zero silent wrong
    answers. The error is NON-retryable by design (re-reading the same
    flipped page cannot succeed), so TASK retry must not mask it; at
    least one seed must actually inject and at least one query must
    fail classified, or the sweep proved nothing."""
    from trino_tpu.errors import LakeDataCorruptionError
    runner = lake_chaos
    injected = classified = 0
    for policy in ("TASK", "NONE"):
        for seed in (1, 2, 3):
            runner.session.set("retry_policy", policy)
            runner.session.set("fault_injection_rate", 0.3)
            runner.session.set("fault_injection_seed", seed)
            runner.session.set("fault_injection_sites", "corrupt")
            for name in LAKE_CHAOS_QS:
                sql, oracle_sql, ordered = QUERIES[name]
                try:
                    got = runner.execute(sql)
                except LakeDataCorruptionError as e:
                    assert "row group" in str(e)     # classified, named
                    classified += 1
                    continue
                expected = oracle.execute(oracle_sql).fetchall()
                assert_same(got.rows, expected, ordered)
            if runner._faults is not None:
                injected += sum(
                    n for (site, _), n in runner._faults.by_detail.items()
                    if site == "corrupt")
    assert injected > 0, "no seed armed the corrupt site"
    assert classified > 0, "no injected flip was caught classified"
    # the detectors leave no residue: with chaos off the table is clean
    runner.session.set("fault_injection_rate", 0.0)
    sql, oracle_sql, ordered = QUERIES["q6"]
    assert_same(runner.execute(sql).rows,
                oracle.execute(oracle_sql).fetchall(), ordered)
