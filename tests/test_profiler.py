"""Device-time truth (round 13): cost-model operator attribution,
compile-vs-execute accounting, and Chrome-trace export.

The acceptance contract: `collect_operator_stats` observes the SAME
executables the plain query runs (no chain splitting — a warm
instrumented run dispatches zero new kernels), per-operator device
attribution sums to the measured chain walls, compile walls are measured
events rather than cold-vs-warm deltas, and the span tree exports as
valid Chrome-trace JSON.
"""

import json
import os
import re

import pytest

from trino_tpu.exec import LocalQueryRunner

from oracle import assert_same, load_tpch_sqlite
from tpch_sql import QUERIES

SF = 0.01


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner.tpch("tiny")


@pytest.fixture(scope="module")
def oracle():
    conn = load_tpch_sqlite(SF)
    yield conn
    conn.close()


def _with_operator_stats(runner, sql):
    runner.session.set("collect_operator_stats", True)
    try:
        out = runner.execute(sql)
    finally:
        runner.session.properties.pop("collect_operator_stats", None)
    return out, dict(runner.last_query_stats)


# ------------------------------------------------- no-splitting contract


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_operator_stats_dispatch_same_kernels(runner, name):
    """THE regression this round exists for: after a plain warm run,
    turning operator-level collection on must dispatch ZERO new kernels
    — the old node-boundary instrumentation split fused chains into
    per-operator programs (jit misses on every instrumented run), which
    meant profiling changed what was measured."""
    engine_sql, _, _ = QUERIES[name]
    runner.execute(engine_sql)              # warm the fused chain shapes
    runner.execute(engine_sql)
    assert runner.last_query_stats["jit_misses"] == 0   # warm baseline
    _, snap = _with_operator_stats(runner, engine_sql)
    assert snap["jit_misses"] == 0, snap    # same executables, stats on
    assert snap["operators"], snap          # and rows were collected


@pytest.mark.parametrize("name", ["q1", "q5"])
def test_device_attribution_sums_to_chain_walls(runner, oracle, name):
    """Per-operator device shares (XLA cost-model apportionment of each
    fused chain's fenced wall) redistribute the chains' walls and never
    invent: they sum to no more than the collector's device total, which
    since PR 25 also holds the blocking operators' own kernels (final
    aggregates, join builds and probes, sorts), fenced at the jit cache."""
    engine_sql, oracle_sql, ordered = QUERIES[name]
    got, snap = _with_operator_stats(runner, engine_sql)
    expected = oracle.execute(oracle_sql or engine_sql).fetchall()
    assert_same(got.rows, expected, ordered)    # instrumented == correct
    ops = snap["operators"]
    assert ops and snap["device_time_ms"] > 0, snap
    dev_sum = sum(o["device_ms"] for o in ops)
    assert 0 < dev_sum <= snap["device_time_ms"] + 0.5, \
        (dev_sum, snap["device_time_ms"])
    # and the rest is device time too, no longer called host time
    assert snap["host_time_ms"] <= snap["execution_s"] * 1000 \
        - snap["device_time_ms"] + 0.5, snap
    # streaming chain operators carry nonzero device shares
    assert any(o["device_ms"] > 0 for o in ops
               if o["name"] in ("FilterNode", "ProjectNode")), ops


def test_plain_queries_skip_the_fence(runner):
    """Without operator-level collection nothing is fenced: device time
    stays folded into execution wall, so device and host time read null
    (not 0 and the execution wall), and no operator rows exist — the
    default path pays nothing for attribution."""
    runner.execute("SELECT count(*) FROM orders")
    snap = runner.last_query_stats
    assert snap["device_time_ms"] is None
    assert snap["host_time_ms"] is None
    assert "operators" not in snap


# --------------------------------------------- compile-vs-execute split


def test_compile_wall_is_a_measured_event(runner):
    """A never-seen chain shape pays a measured XLA compile (wall +
    HLO op count + cost-model flops/bytes); the warm re-run pays none.
    The structure below is unique to this test so the shared process
    jit cache cannot have warmed it."""
    sql = ("SELECT sum(l_quantity * 7 - l_tax * 3 + l_discount * 11) "
           "FROM lineitem WHERE l_partkey * 13 > l_suppkey * 17")
    runner.execute(sql)
    cold = dict(runner.last_query_stats)
    assert cold["compile_time_ms"] > 0, cold
    assert cold["jit_compiles"] >= 1, cold
    assert cold["compiled_hlo_ops"] > 0, cold
    assert cold["estimated_bytes"] > 0, cold
    runner.execute(sql)
    warm = dict(runner.last_query_stats)
    assert warm["compile_time_ms"] == 0.0, warm
    assert warm["jit_compiles"] == 0, warm


def test_cpu_time_means_host_time(runner):
    """host_time_ms (and QueryInfo.cpu_time_ms) = execution - device -
    compile, clamped at zero: the three walls partition execution."""
    from trino_tpu.exec.query_tracker import TRACKER
    sql = "SELECT max(o_totalprice) AS host_time_probe FROM orders"
    _, snap = _with_operator_stats(runner, sql)
    exec_ms = snap["execution_s"] * 1000
    assert snap["host_time_ms"] <= exec_ms + 1e-6, snap
    assert abs((snap["host_time_ms"] + snap["device_time_ms"]
                + snap["compile_time_ms"]) - exec_ms) < 1.0 \
        or snap["host_time_ms"] == 0.0, snap
    info = next(q for q in TRACKER.list() if q.query == sql)
    assert info.cpu_time_ms == int(snap["host_time_ms"]), \
        (info.cpu_time_ms, snap["host_time_ms"])


def test_explain_analyze_reports_the_split(runner):
    """EXPLAIN ANALYZE q1 (acceptance): device_time_ms and
    compile_time_ms render separately from host time in the footer, and
    fused-chain node annotations carry their device share."""
    engine_sql, _, _ = QUERIES["q1"]
    text = runner.execute("EXPLAIN ANALYZE " + engine_sql).only_value()
    m = re.search(r"device ([\d.]+)ms / compile ([\d.]+)ms / "
                  r"host ([\d.]+)ms", text)
    assert m, text
    assert float(m.group(1)) > 0, text          # chains were fenced
    assert "compiles" in text
    assert re.search(r"device: [\d.]+ms", text), text   # per-node share


# ------------------------------------------------- jit cache accounting


def test_jit_cache_exports_compile_ledger(runner):
    from trino_tpu.exec import jit_cache
    s = jit_cache.stats()
    for key in ("compiles", "compile_s", "hlo_ops", "aot_fallbacks"):
        assert key in s, s
    assert s["compiles"] >= 1 and s["compile_s"] > 0
    # the profiled AOT dispatch path must not be misfiring: fallbacks
    # mean signature drift between lower() and call time
    assert s["aot_fallbacks"] == 0, s
    runner.execute("SELECT name, value FROM system.runtime.metrics "
                   "WHERE name = 'trino_tpu_jit_compile_seconds_total'")


# ------------------------------------------------------- trace export


def _check_chrome_trace(payload):
    """The fast schema check (satellite): Chrome-trace JSON with
    well-typed ph/ts/dur on every complete event."""
    assert isinstance(payload, dict) and "traceEvents" in payload
    complete = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    assert complete, payload
    for e in payload["traceEvents"]:
        assert isinstance(e.get("ph"), str) and e["ph"] in ("X", "M"), e
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)), e
            assert isinstance(e["dur"], (int, float)), e
            assert isinstance(e.get("name"), str), e
    return complete


def test_chrome_trace_from_span_dump(runner):
    from trino_tpu.exec.query_tracker import TRACKER
    from trino_tpu.obs.spans import to_chrome_trace
    sql = "SELECT count(*) AS chrome_probe FROM customer"
    runner.execute(sql)
    info = next(q for q in TRACKER.list() if q.query == sql)
    payload = json.loads(json.dumps(to_chrome_trace(info.trace,
                                                    info.query_id)))
    complete = _check_chrome_trace(payload)
    cats = {e["cat"] for e in complete}
    assert "query" in cats and "phase" in cats, cats


def test_trace_export_distributed_q5(tmp_path):
    """Acceptance: an exported trace for a distributed q5 run opens as
    valid Chrome-trace JSON containing query, fragment, and operator
    spans; QueryInfo.trace_file points at the file."""
    from trino_tpu.exec.distributed import DistributedQueryRunner
    from trino_tpu.exec.query_tracker import TRACKER
    r = DistributedQueryRunner.tpch("tiny")
    r._trace_dir = str(tmp_path)
    r.session.set("trace_export", True)
    r.session.set("collect_operator_stats", True)
    engine_sql, _, _ = QUERIES["q5"]
    out = r.execute(engine_sql)
    assert out.rows
    info = next(q for q in TRACKER.list()
                if q.query == engine_sql and q.trace_file)
    assert os.path.exists(info.trace_file), info.trace_file
    with open(info.trace_file) as fh:
        payload = json.load(fh)
    complete = _check_chrome_trace(payload)
    cats = {e["cat"] for e in complete}
    assert {"query", "fragment", "operator"} <= cats, cats


def test_trace_export_off_by_default(runner):
    from trino_tpu.exec.query_tracker import TRACKER
    sql = "SELECT count(*) AS no_trace_probe FROM region"
    runner.execute(sql)
    info = next(q for q in TRACKER.list() if q.query == sql)
    assert info.trace_file is None
