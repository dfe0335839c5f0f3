"""/v1/statement wire protocol vs a stdlib HTTP client.

Reference parity: the documented Trino client protocol
(client/trino-client StatementClientV1.java:61 — POST, follow nextUri,
typed columns, data rows, Set-Session headers, DELETE cancel) exercised
exactly the way the stock CLI drives it.
"""

import json
import urllib.request

import pytest

from trino_tpu.exec import LocalQueryRunner
from trino_tpu.server import TrinoServer


@pytest.fixture(scope="module")
def server():
    srv = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    yield srv
    srv.stop()


def _post(server, sql, headers=None):
    req = urllib.request.Request(
        f"{server.base_uri}/v1/statement", data=sql.encode(), method="POST")
    req.add_header("X-Trino-User", "test")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def _get(uri):
    with urllib.request.urlopen(uri) as resp:
        return json.loads(resp.read()), dict(resp.headers)


def run_query(server, sql, headers=None):
    """Client loop: POST, then follow nextUri until absent. Data may
    appear in ANY response including the first (StatementClientV1 reads
    it wherever it shows up — the result-cache fast path answers
    FINISHED with the rows inline in the POST response)."""
    payload, hdrs = _post(server, sql, headers)
    columns = payload.get("columns")
    rows = list(payload.get("data", []))
    states = [payload["stats"]["state"]]
    while "nextUri" in payload:
        payload, h = _get(payload["nextUri"])
        hdrs.update(h)
        states.append(payload["stats"]["state"])
        if "columns" in payload:
            columns = payload["columns"]
        rows.extend(payload.get("data", []))
    return payload, columns, rows, states, hdrs


def test_simple_query(server):
    payload, columns, rows, states, _ = run_query(
        server, "SELECT n_nationkey, n_name FROM nation "
                "WHERE n_regionkey = 1 ORDER BY n_nationkey")
    assert states[0] == "QUEUED" and states[-1] == "FINISHED"
    assert [c["name"] for c in columns] == ["n_nationkey", "n_name"]
    assert columns[0]["type"] == "bigint"
    assert columns[1]["type"].startswith("varchar")
    assert columns[0]["typeSignature"]["rawType"] == "bigint"
    assert rows == [[1, "ARGENTINA"], [2, "BRAZIL"], [3, "CANADA"],
                    [17, "PERU"], [24, "UNITED STATES"]]
    assert "error" not in payload


def test_typed_values(server):
    _, columns, rows, _, _ = run_query(
        server, "SELECT o_orderdate, o_totalprice, o_orderkey = 1 "
                "FROM orders WHERE o_orderkey = 1")
    assert columns[0]["type"] == "date"
    assert columns[1]["type"].startswith("decimal")
    (date_s, price_s, flag), = rows
    assert len(date_s.split("-")) == 3       # ISO date string
    assert "." in price_s                     # decimal as string
    assert flag is True


def test_paging(server):
    payload, _, rows, states, _ = run_query(
        server, "SELECT c_custkey FROM customer")
    assert len(rows) == 1500
    # at least one intermediate page: RUNNING while producing, or
    # FINISHING while the result ring drains (the streaming lifecycle)
    assert states.count("RUNNING") + states.count("FINISHING") >= 1
    assert "nextUri" not in payload


def test_error_surfaced_as_query_error(server):
    payload, _, _, states, _ = run_query(server, "SELECT bogus_fn(1)")
    assert states[-1] == "FAILED"
    assert "bogus_fn" in payload["error"]["message"]
    assert payload["error"]["errorType"] == "USER_ERROR"


def test_set_session_header_roundtrip(server):
    payload, _, _, _, hdrs = run_query(
        server, "SET SESSION join_distribution_type = 'PARTITIONED'")
    assert payload.get("updateType") == "SET SESSION"
    assert hdrs.get("X-Trino-Set-Session") == \
        "join_distribution_type=PARTITIONED"
    _, _, _, _, hdrs = run_query(
        server, "RESET SESSION join_distribution_type")
    assert hdrs.get("X-Trino-Clear-Session") == "join_distribution_type"


def test_catalog_schema_headers(server):
    _, _, rows, _, _ = run_query(
        server, "SELECT count(*) FROM nation",
        headers={"X-Trino-Catalog": "tpch", "X-Trino-Schema": "tiny"})
    assert rows == [[25]]


def test_cancel():
    # a dedicated max_running=1 server: occupy the single executor so the
    # victim stays deterministically QUEUED when the DELETE lands (cancel
    # of a TERMINAL query is a no-op, reference semantics — racing a bare
    # SELECT 1 against the default executor POOL would flake)
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"), max_running=1).start()
    try:
        blocker, _ = _post(srv, "SELECT count(*) FROM lineitem l1, "
                                "lineitem l2 WHERE l1.l_orderkey = "
                                "l2.l_orderkey AND l1.l_partkey = "
                                "l2.l_partkey")
        payload, _ = _post(srv, "SELECT 1")
        uri = payload["nextUri"]
        req = urllib.request.Request(uri, method="DELETE")
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 204
        payload, _ = _get(uri)
        assert payload["stats"]["state"] == "CANCELED"
        assert payload["error"]["errorCode"] == 3      # USER_CANCELED
        while "nextUri" in blocker:                    # drain the blocker
            blocker, _ = _get(blocker["nextUri"])
        assert blocker["stats"]["state"] == "FINISHED"
    finally:
        srv.stop()


def test_cancel_finished_query_is_noop(server):
    """DELETE on a FINISHED query must not destroy access to its
    buffered results (code-review finding)."""
    import time
    payload, _ = _post(server, "SELECT n_nationkey FROM nation")
    uri = payload["nextUri"]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        p, _ = _get(uri)
        if p["stats"]["state"] not in ("QUEUED", "RUNNING"):
            break
        time.sleep(0.05)
    req = urllib.request.Request(uri, method="DELETE")
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 204
    rows = []
    p, _ = _get(uri)
    rows.extend(p.get("data", []))
    while "nextUri" in p:
        p, _ = _get(p["nextUri"])
        rows.extend(p.get("data", []))
    assert p["stats"]["state"] == "FINISHED"
    assert len(rows) == 25


def test_unknown_query_404(server):
    try:
        _get(f"{server.base_uri}/v1/statement/executing/nope/slug/0")
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_concurrent_paging_during_long_query(server):
    """Dispatch queue (round 5): a running query must not block another
    client paging an already-finished query's results
    (DispatchManager.java:140 / resource-group max_running=1 shape)."""
    import threading
    import time

    # finish a short query first; keep its page-0 URI (a statement no
    # earlier test cached — a result-cache hit answers the POST inline
    # with no nextUri to page)
    payload, _ = _post(server, "SELECT n_nationkey, n_regionkey "
                               "FROM nation")
    first_uri = payload["nextUri"]
    while "nextUri" in payload:
        payload, _ = _get(payload["nextUri"])
    # launch a LONG query in a side thread (self-join at tiny ~seconds)
    long_sql = ("SELECT count(*) FROM lineitem l1, lineitem l2 "
                "WHERE l1.l_orderkey = l2.l_orderkey "
                "AND l1.l_partkey = l2.l_partkey")
    done = {}

    def run_long():
        done["result"] = run_query(server, long_sql)
    th = threading.Thread(target=run_long)
    th.start()
    # while it runs, page the finished query's buffered results: must be
    # immediate (no engine lock on the paging path)
    t0 = time.perf_counter()
    page, _ = _get(first_uri)
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, f"paging blocked for {elapsed:.1f}s"
    assert page.get("data") or "nextUri" in page
    th.join(timeout=120)
    assert done["result"][2][0][0] > 0       # long query completed too


def test_invalid_token_is_404_not_500(server):
    """A malformed or negative page token must answer 404, not crash the
    handler into an HTTP 500 (the _resolve int() fix)."""
    payload, _ = _post(server, "SELECT 1")
    base = payload["nextUri"].rsplit("/", 1)[0]
    for bad in ("abc", "-1", "1x", ""):
        try:
            _get(f"{base}/{bad}")
            assert False, f"expected 404 for token {bad!r}"
        except urllib.error.HTTPError as e:
            assert e.code == 404, f"token {bad!r} -> {e.code}"
    # drain the good query so the module fixture stays clean
    while "nextUri" in payload:
        payload, _ = _get(payload["nextUri"])


def test_pruned_query_answers_410_gone():
    """Past the keep bound, a finished query's results are pruned and a
    late GET answers 410 Gone (retrying is pointless), not a bare 404."""
    from trino_tpu.exec import LocalQueryRunner
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"), keep=2).start()
    try:
        # finish one query and hold its page-0 URI, then submit enough
        # queries to push it past the keep bound
        first, _ = _post(srv, "SELECT 100")
        first_uri = first["nextUri"]
        p = first
        while "nextUri" in p:
            p, _ = _get(p["nextUri"])
        for i in range(8):       # push the first query past keep=2
            run_query(srv, f"SELECT {200 + i}")
        try:
            _get(first_uri)
            assert False, "expected 410"
        except urllib.error.HTTPError as e:
            assert e.code == 410
        # a never-existed id still answers 404
        try:
            _get(f"{srv.base_uri}/v1/statement/executing/nope/slug/0")
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()


def test_cancel_running_query_frees_executor(server):
    """DELETE on a RUNNING query transitions it to CANCELED at the next
    cooperative checkpoint and the executor picks up the next queued
    query (the ISSUE acceptance bar for cancellation)."""
    import time
    long_sql = ("SELECT count(*) FROM lineitem l1, lineitem l2, "
                "lineitem l3 WHERE l1.l_orderkey = l2.l_orderkey "
                "AND l2.l_orderkey = l3.l_orderkey "
                "AND l1.l_partkey = l2.l_partkey AND l1.l_tax = l2.l_tax")
    # small scan pages => MANY page-batch checkpoints, so the cooperative
    # cancel lands in seconds even when the fused join kernels are warm
    # (one giant fused program can otherwise run minutes checkpoint-free)
    hdrs = {"X-Trino-Session": "scan_page_capacity=4096,page_capacity=4096"}
    payload, _ = _post(server, long_sql, headers=hdrs)
    uri = payload["nextUri"]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        p, _ = _get(uri)
        if p["stats"]["state"] == "RUNNING":
            break
        time.sleep(0.05)
    req = urllib.request.Request(uri, method="DELETE")
    with urllib.request.urlopen(req) as resp:
        assert resp.status == 204
    p, _ = _get(uri)
    assert p["stats"]["state"] == "CANCELED"
    assert p["error"]["errorName"] == "USER_CANCELED"
    # the executor pool must serve the next client promptly even though
    # the canceled query would have run for much longer
    _, _, rows, _, _ = run_query(server, "SELECT 41 + 1")
    assert rows == [[42]]
    # the RUNNER observes the cancel at its next cooperative checkpoint
    # and the tracker records CANCELED under the server's query id (the
    # server answers CANCELED immediately; the tracker flips when the
    # executing thread actually unwinds — poll for it)
    from trino_tpu.exec.query_tracker import TRACKER
    deadline = time.monotonic() + 120
    state = None
    while time.monotonic() < deadline:
        state = next((q.state for q in TRACKER.list()
                      if q.query_id == p["id"]), None)
        if state == "CANCELED":
            break
        time.sleep(0.1)
    assert state == "CANCELED", state


def test_concurrent_submit_poll_cancel_race(server):
    """N client threads submit/poll/cancel concurrently: no HTTP 500s,
    every query reaches a terminal state, and the registry (now
    lock-guarded) never corrupts."""
    import threading

    N = 8
    results = [None] * N
    failures = []

    def client(i):
        try:
            sql = f"SELECT n_nationkey + {i} FROM nation"
            payload, _ = _post(server, sql)
            if i % 3 == 0:
                # cancel mid-flight (QUEUED or RUNNING — both legal)
                req = urllib.request.Request(payload["nextUri"],
                                             method="DELETE")
                with urllib.request.urlopen(req) as resp:
                    assert resp.status == 204
            while "nextUri" in payload:
                payload, _ = _get(payload["nextUri"])
            results[i] = payload["stats"]["state"]
        except BaseException as e:  # noqa: BLE001
            failures.append((i, e))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(N)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not failures, failures
    assert all(r in ("FINISHED", "CANCELED") for r in results), results
    # cancels observed as CANCELED or raced to FINISHED; non-cancelled
    # clients must all have finished
    assert all(results[i] == "FINISHED" for i in range(N) if i % 3)


def test_concurrent_queries_interleave(server):
    """max_running > 1 (round 7): independent queries genuinely run
    concurrently — the tracker observes >= 2 simultaneously RUNNING
    server queries while the pool drains a batch."""
    import threading
    import time

    from trino_tpu.exec.query_tracker import TRACKER

    sql = ("SELECT count(*) FROM lineitem l1, lineitem l2 "
           "WHERE l1.l_orderkey = l2.l_orderkey "
           "AND l1.l_partkey = l2.l_partkey")
    ids = []
    for i in range(3):
        payload, _ = _post(server, sql + f" AND {i} = {i}")
        ids.append(payload["id"])
    # the pool should mark several RUNNING almost immediately
    seen_concurrent = 0
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        states = {q.query_id: q.state for q in TRACKER.list()}
        running = sum(1 for qid in ids if states.get(qid) == "RUNNING")
        seen_concurrent = max(seen_concurrent, running)
        if seen_concurrent >= 2:
            break
        time.sleep(0.01)
    # drain them all (also proves none was lost to the pool rework)
    for qid in ids:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            states = {q.query_id: q.state for q in TRACKER.list()}
            if states.get(qid) == "FINISHED":
                break
            time.sleep(0.05)
        assert states.get(qid) == "FINISHED", states.get(qid)
    assert seen_concurrent >= 2, seen_concurrent


def test_resource_group_routing(server):
    """The resource_group session property routes a query through the
    named group and lands in system.runtime.queries +
    system.runtime.resource_groups."""
    _, _, rows, _, _ = run_query(
        server, "SELECT 5",
        headers={"X-Trino-Session": "resource_group=etl.nightly"})
    assert rows == [[5]]
    _, _, rows, _, _ = run_query(
        server,
        "SELECT resource_group FROM system.runtime.queries "
        "WHERE query = 'SELECT 5'")
    assert ["etl.nightly"] in rows
    _, _, rows, _, _ = run_query(
        server,
        "SELECT name, parent, finished FROM "
        "system.runtime.resource_groups ORDER BY name")
    by_name = {r[0]: r for r in rows}
    assert "etl" in by_name and "etl.nightly" in by_name
    assert by_name["etl.nightly"][1] == "etl"
    assert by_name["etl.nightly"][2] >= 1


def test_queue_full_admission(server):
    """Admission control: an over-limit submit fails as
    QUERY_QUEUE_FULL, not an HTTP error (InternalResourceGroup
    canQueueMore analog) — driven through a zero-capacity group so no
    timing games are needed. The statement must be one the result cache
    has never seen: a cache hit consumes no executor resources and is
    legitimately answered without admission."""
    server.groups.configure("zeroq", max_queued=0)
    payload, _, _, _, _ = run_query(
        server, "SELECT 1 + 0 * 9",
        headers={"X-Trino-Session": "resource_group=zeroq"})
    assert payload["stats"]["state"] == "FAILED"
    assert payload["error"]["errorName"] == "QUERY_QUEUE_FULL"
    # the default group still admits
    _, _, rows, _, _ = run_query(server, "SELECT 7")
    assert rows == [[7]]


def test_bad_session_value_fails_unknown_name_tolerated(server):
    # unknown property names from newer clients are ignored
    payload, _, rows, _, _ = run_query(
        server, "SELECT 1", {"X-Trino-Session": "not_a_real_prop=1"})
    assert rows == [[1]] and "error" not in payload
    # a KNOWN property with a malformed value fails the query visibly
    payload, _, _, _, _ = run_query(
        server, "SELECT 1", {"X-Trino-Session": "retry_attempts=abc"})
    assert payload["error"]["errorName"] == "INVALID_SESSION_PROPERTY"
    # ... and terminates its tracker entry (no phantom QUEUED row)
    from trino_tpu.exec.query_tracker import TRACKER
    info = next(q for q in TRACKER.list() if q.query_id == payload["id"])
    assert info.state == "FAILED"
    assert info.error_name == "INVALID_SESSION_PROPERTY"


# ------------------------------------------------- requires (the handshake)

def test_a_server_starts_with_what_it_has():
    from trino_tpu.server.app import CAPABILITIES
    assert {"joins_connected_never_cross", "like_pattern_operand"} \
        <= CAPABILITIES
    srv = TrinoServer(LocalQueryRunner.tpch("tiny"),
                      requires=["joins_connected_never_cross",
                                "like_pattern_operand"]).start()
    try:
        assert run_query(srv, "SELECT 7")[2] == [[7]]
    finally:
        srv.stop()


def test_a_server_that_lacks_a_requirement_does_not_start():
    """Refused in the constructor: before a session property is set, the
    warm-up manifest is touched or a port is bound."""
    class Untouched:
        def __getattr__(self, name):
            raise AssertionError(f"the runner was touched: {name}")

    class Manifest:
        def __getattr__(self, name):
            raise AssertionError(f"the manifest was touched: {name}")

    with pytest.raises(ValueError, match=r"this engine lacks: "
                       r"composite_key_table, zz_unknown$"):
        TrinoServer(Untouched(), warmup_manifest=Manifest(),
                    requires=["zz_unknown", "like_pattern_operand",
                              "composite_key_table"])


def test_every_capability_is_cited_by_a_test():
    """A name in CAPABILITIES is a fact a test proves: some test file
    other than this one names it, and the comment beside the name in
    server/app.py names a test that exists."""
    import os
    import re
    import trino_tpu.server.app as app
    here = os.path.dirname(__file__)
    sources = {f: open(os.path.join(here, f)).read()
               for f in os.listdir(here)
               if f.startswith("test_") and f.endswith(".py")
               and f != os.path.basename(__file__)}
    with open(app.__file__) as f:
        block = re.search(r"CAPABILITIES = frozenset\(\{(.*?)\}\)",
                          f.read(), re.DOTALL).group(1)
    cited = re.findall(
        r'# proved by tests/(test_\w+\.py)::(test_\w+)\n\s*"(\w+)"', block)
    assert {name for _, _, name in cited} == set(app.CAPABILITIES)
    for file, test, name in cited:
        assert f"def {test}(" in sources[file], (file, test)
        assert name in sources[file], (file, name)
