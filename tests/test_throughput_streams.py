"""Three query streams against one server at its defaults: TPC-H's
throughput test (clause 5.3.4) at `tiny`, the CPU twin of the benchmark
cell `sf10-throughput-s3`.

Every answer is compared with the benchmark's own NumPy reference
(`benchmark/reference.py`, independent of the engine). Nothing may fail,
be killed for memory or be answered from the result cache the traffic
turned off; the streams' joins overlap on the executor pool.
"""

import os
import sys
import threading

import pytest

from trino_tpu.exec import LocalQueryRunner
from trino_tpu.exec.memory import NODE_POOL
from trino_tpu.server import TrinoServer

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import loadgen          # noqa: E402
import reference        # noqa: E402
import traffic_gen      # noqa: E402

STREAMS, CYCLES, SEED = 3, 2, 2147483659


def _plan():
    """The cell's own traffic: three streams of q6, q1, q3."""
    return traffic_gen.make_plan(traffic_gen.load_traffic("throughput-s3"),
                                 SEED, 60)


def _info(conn, qid):
    return conn.get(f"/v1/query/{qid}")


def _warm_up(server, plan) -> dict:
    """One statement per shape, alone, as the harness's warm-up sends
    them. -> each shape's ledger peak (`peakMemoryBytes`)."""
    stmts = loadgen.Statements(plan)
    conn = loadgen.Conn("127.0.0.1", server.port, "streams-warmup")
    peaks = {}
    try:
        for step in plan["setup"]:
            sql, headers = stmts.build(step["shape"], step["params"],
                                       step["session"])
            got = conn.statement(sql, headers)
            assert got["error"] is None, got
            peaks[step["shape"]] = _info(conn, got["qid"])["peakMemoryBytes"]
    finally:
        conn.close()
    return peaks


def _run_streams(server, plan) -> list:
    """-> the requests of three clients cycling q6 -> q1 -> q3 twice, each
    with its rows and its `GET /v1/query/<id>`. A barrier starts the
    streams' requests of one shape together, as the window's start does."""
    stmts = loadgen.Statements(plan)
    out, barrier = [], threading.Barrier(STREAMS)

    def client(idx):
        conn = loadgen.Conn("127.0.0.1", server.port, f"stream-{idx}")
        try:
            for shape, params in plan["clients"][idx][:3 * CYCLES]:
                sql, headers = stmts.build(shape, params)
                barrier.wait(timeout=120)
                got = conn.statement(sql, headers)
                out.append({"shape": shape, "params": params, **got,
                            "info": got["qid"] and _info(conn, got["qid"])})
        finally:
            conn.close()
    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(STREAMS)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    assert not any(th.is_alive() for th in threads)
    return out


def _assert_all_answered_exactly(requests):
    assert len(requests) == STREAMS * 3 * CYCLES
    failed = [(r["shape"], r["error"]) for r in requests if r["error"]]
    assert not failed, failed
    keys = [(r["shape"], r["params"]) for r in requests]
    for r, want in zip(requests, reference.compute(0.01, keys, 2)):
        assert reference.compare(r["rows"], want) == "", (r["shape"],
                                                          r["params"])
    for r in requests:
        stats = r["info"]["stats"]
        assert r["info"]["state"] == "FINISHED"
        assert stats["memory_kills"] == 0
        assert stats["device_oom_errors"] == 0
        assert stats["retries"] == 0
        assert stats["result_cache_hits"] == 0     # the traffic turned it off


@pytest.fixture()
def server():
    srv = TrinoServer(LocalQueryRunner.tpch("tiny")).start()
    try:
        yield srv
    finally:
        srv.stop()


@pytest.mark.parametrize("warmed", [True, False],
                         ids=["after_warmup", "first_sight"])
def test_three_streams_are_all_answered_exactly(server, warmed):
    """`warmed`: one statement per shape ran alone first, as the harness's
    set-up sends them; else the three streams meet the server cold."""
    plan = _plan()
    kills = NODE_POOL.kills
    assert NODE_POOL.limit is None
    if warmed:
        assert _warm_up(server, plan)["q3"] > 0
    requests = _run_streams(server, plan)
    _assert_all_answered_exactly(requests)
    assert NODE_POOL.kills == kills and NODE_POOL.reserved == 0


def test_the_streams_joins_overlap_on_the_executor_pool(server):
    """The server's default executor pool takes the three streams at
    once: some instant is covered by more than one `execution` span (what
    `queries_in_flight_mean` averages)."""
    plan = _plan()
    _warm_up(server, plan)
    requests = _run_streams(server, plan)
    _assert_all_answered_exactly(requests)
    spans = sorted((start, end) for r in requests
                   for name, start, end in r["info"]["stats"]["spans"]
                   if name == "execution")
    assert len(spans) == len(requests)
    assert any(b_start < a_end for (_, a_end), (b_start, _)
               in zip(spans, spans[1:]))
