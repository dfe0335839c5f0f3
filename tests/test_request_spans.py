"""The request's life on one clock (PR 25): `queued`, `planning`,
`execution`, `compile` and `result_fetch` spans stamped with
`time.monotonic()`, a true `queuedTimeMillis`, and the two counters that
say what they count (`scan_host_staging_bytes`, fenced `host_time_ms`)."""

import json
import threading
import time
import urllib.request

import pytest

from trino_tpu.exec import LocalQueryRunner
from trino_tpu.obs.stats import REQUEST_SPANS, QueryStatsCollector
from trino_tpu.server import TrinoServer

SLOW = ("SELECT count(*) FROM lineitem l1, lineitem l2 "
        "WHERE l1.l_orderkey = l2.l_orderkey "
        "AND l1.l_partkey = l2.l_partkey")
JOIN = ("SELECT count(*), sum(o_totalprice) FROM customer, orders "
        "WHERE c_custkey = o_custkey")


def _post(server, sql):
    req = urllib.request.Request(
        f"{server.base_uri}/v1/statement", data=sql.encode(), method="POST")
    req.add_header("X-Trino-User", "test")
    req.add_header("X-Trino-Session", "result_cache_enabled=false")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def _get(uri):
    with urllib.request.urlopen(uri) as resp:
        return json.loads(resp.read())


def _drain(payload):
    while "nextUri" in payload:
        payload = _get(payload["nextUri"])
    assert payload["stats"]["state"] == "FINISHED", payload
    return payload


def _info(server, qid):
    return _get(f"{server.base_uri}/v1/query/{qid}")


def _spans(stats, name):
    return [(s, e) for n, s, e in stats["spans"] if n == name]


@pytest.fixture(scope="module")
def one_at_a_time():
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"), max_running=1,
                      result_cache=False).start()
    _drain(_post(srv, SLOW))             # compile outside the tests
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def queued_pair(one_at_a_time):
    """Two statements on a one-thread executor: the second waits for the
    first. -> (final response, query info) of each."""
    srv = one_at_a_time
    first = _post(srv, SLOW + " AND 1 = 1")
    second = _post(srv, "SELECT count(*) FROM orders")
    out = []
    for posted in (first, second):
        final = _drain(posted)
        out.append((final, _info(srv, posted["id"])))
    return out


def test_the_second_statement_reports_its_queue_wait(queued_pair):
    (_, first), (final, info) = queued_pair
    assert final["stats"]["queuedTimeMillis"] > 0
    assert info["stats"]["queued_ms"] > 0
    # it waited for about as long as the first one ran
    assert info["stats"]["queued_ms"] \
        >= 0.5 * first["stats"]["execution_s"] * 1e3
    assert final["stats"]["elapsedTimeMillis"] \
        >= final["stats"]["queuedTimeMillis"]
    assert final["stats"]["wallTimeMillis"] \
        == final["stats"]["elapsedTimeMillis"] \
        - final["stats"]["queuedTimeMillis"]


@pytest.mark.parametrize("which", [0, 1])
def test_queued_planning_execution_make_up_the_wall(queued_pair, which):
    _, info = queued_pair[which]
    stats = info["stats"]
    parts = stats["queued_ms"] + 1e3 * (stats["planning_s"]
                                        + stats["execution_s"])
    assert parts >= 0.95 * info["wallMillis"], (parts, info["wallMillis"])
    assert parts <= info["wallMillis"] + 1.0, (parts, info["wallMillis"])


def test_the_five_spans_nest_as_the_request_lived(queued_pair):
    _, info = queued_pair[1]
    stats = info["stats"]
    assert {n for n, _, _ in stats["spans"]} <= set(REQUEST_SPANS)
    (q0, q1), = _spans(stats, "queued")
    (p0, p1), = _spans(stats, "planning")
    (x0, x1), = _spans(stats, "execution")
    (f0, f1), = _spans(stats, "result_fetch")
    assert q0 <= q1 <= p0 <= p1 <= x0 <= f0 <= f1 <= x1
    for c0, c1 in _spans(stats, "compile"):
        assert x0 <= c0 <= c1 <= x1
    assert stats["queued_ms"] == pytest.approx((q1 - q0) * 1e3, abs=0.01)
    # the relative dump for /v1/query/<id>/trace still starts at 0
    srv_trace = info.get("traceFile")
    assert srv_trace is None or isinstance(srv_trace, str)


def test_the_trace_dump_stays_relative(one_at_a_time):
    posted = _post(one_at_a_time, "SELECT count(*) FROM nation")
    _drain(posted)
    trace = _get(f"{one_at_a_time.base_uri}/v1/query/{posted['id']}/trace")
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert min(e["ts"] for e in events) == 0.0
    assert {"queued", "planning", "execution"} <= {e["name"]
                                                   for e in events}


def test_concurrent_queries_interleave_on_one_clock():
    """Spans of two queries running at once are stamped on the clock the
    caller reads (`time.monotonic()`), so they overlap as they ran."""
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"), max_running=2,
                      result_cache=False).start()
    try:
        _drain(_post(srv, SLOW))
        t0 = time.monotonic()
        posted = [_post(srv, SLOW + f" AND {i} = {i}") for i in (2, 3)]
        finals = []
        threads = [threading.Thread(
            target=lambda p=p: finals.append(_drain(p))) for p in posted]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t1 = time.monotonic()
        (a0, a1), (b0, b1) = (
            _spans(_info(srv, p["id"])["stats"], "execution")[0]
            for p in posted)
        assert t0 <= min(a0, b0) and max(a1, b1) <= t1
        assert a0 < b1 and b0 < a1, ((a0, a1), (b0, b1))
    finally:
        srv.stop()


def test_a_hit_on_the_http_thread_has_no_spans_and_no_queue():
    srv = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    try:
        sql = "SELECT count(*) AS span_hit_probe FROM region"
        _drain(_post_cached(srv, sql))
        final = _post_cached(srv, sql)
        assert final["stats"]["state"] == "FINISHED"
        assert final["stats"]["queuedTimeMillis"] == 0
        stats = _info(srv, final["id"])["stats"]
        assert stats["result_cache_hits"] == 1
        assert stats["queued_ms"] == 0 and stats["spans"] == []
    finally:
        srv.stop()


def _post_cached(server, sql):
    req = urllib.request.Request(
        f"{server.base_uri}/v1/statement", data=sql.encode(), method="POST")
    req.add_header("X-Trino-User", "test")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def test_a_collector_without_a_submit_stamp_has_no_queued_span():
    col = QueryStatsCollector("q")
    with col.phase("planning"):
        pass
    col.finish()
    snap = col.snapshot()
    assert snap["queued_ms"] == 0
    assert [n for n, _, _ in snap["spans"]] == ["planning"]
    now = time.monotonic()
    queued = QueryStatsCollector("q", queued_at=now - 2.0,
                                 dequeued_at=now - 0.5)
    queued.finish()
    snap = queued.snapshot()
    assert snap["queued_ms"] == pytest.approx(1500.0, abs=0.01)
    assert snap["wall_s"] >= 2.0        # the tree starts at the submit


# ------------------------------------------------------------ the counters


def test_host_staging_tells_device_generation_from_host_staging():
    runner = LocalQueryRunner.tpch("tiny")
    runner.session.set("scan_cache_enabled", False)
    runner.session.set("table_cache_enabled", False)
    # generated on the device by the connector: nothing crosses
    runner.execute("SELECT sum(l_extendedprice), max(l_orderkey) "
                   "FROM lineitem")
    on_device = dict(runner.last_query_stats)
    assert on_device["scan_staging_bytes"] > 0
    assert on_device["scan_host_staging_bytes"] == 0
    # a table of the memory connector lives on the host: every scan
    # stages it (this worker's other tests may have left any tpch column
    # in the connector's device cache, so none is asked for here)
    runner.execute("CREATE TABLE memory.default.staged_probe_pr25 AS "
                   "SELECT n_nationkey, n_regionkey FROM nation")
    for _ in range(2):
        runner.execute("SELECT sum(n_regionkey) "
                       "FROM memory.default.staged_probe_pr25")
        staged = dict(runner.last_query_stats)
        assert staged["scan_host_staging_bytes"] > 0
        assert staged["scan_host_staging_bytes"] \
            <= staged["scan_staging_bytes"]


def test_host_time_is_null_unfenced_and_excludes_the_join_fenced():
    runner = LocalQueryRunner.tpch("tiny")
    runner.execute(JOIN)                     # warm: compiles are out
    runner.execute(JOIN)
    plain = dict(runner.last_query_stats)
    assert plain["host_time_ms"] is None and plain["device_time_ms"] is None
    runner.session.set("collect_operator_stats", True)
    runner.execute(JOIN)
    fenced = dict(runner.last_query_stats)
    exec_ms = fenced["execution_s"] * 1e3
    chains_ms = sum(o["device_ms"] for o in fenced["operators"])
    # device time is every dispatch's, not only the fused chains':
    # the join's build, probe and attach kernels are in it
    assert fenced["device_time_ms"] > chains_ms + 0.05, fenced
    assert fenced["host_time_ms"] == pytest.approx(
        exec_ms - fenced["device_time_ms"] - fenced["compile_time_ms"],
        abs=0.01)
    assert fenced["host_time_ms"] < exec_ms - chains_ms
