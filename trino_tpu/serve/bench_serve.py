"""Closed-loop QPS benchmark: N clients hammer prepared EXECUTEs over
HTTP.

The serving tier's acceptance instrument (`run_qps_bench`): start a
TrinoServer over the tiny TPC-H catalog, warm it through the warmup
manifest (PREPARE + one priming EXECUTE per parameter value), then run
`clients` closed-loop threads — each POSTs `EXECUTE qps_probe USING k`
on a persistent HTTP connection, follows `nextUri` when present, and
immediately issues the next request. Reported: sustained completed
executions/second over the measurement window, latency percentiles,
cache hit rates, and the zero-work proof for cache hits (a sampled hit's
stats read planning_s == 0, jit_misses == 0, execution_s == 0).

Closed-loop means throughput is the system's, not the generator's: every
client always has exactly one request in flight, so sustained QPS =
completed / window with per-request latency the full POST->FINISHED
round trip.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Any, Dict, List, Optional

PROBE_NAME = "qps_probe"
PROBE_SQL = ("SELECT n_name, n_regionkey FROM nation "
             "WHERE n_nationkey = ?")
PROBE_VALUES = 25     # nation keys 0..24


def _client_loop(host: str, port: int, idx: int, stop_at: List[float],
                 measure_from: List[float], latencies: List[float],
                 counters: Dict[str, int], lock: threading.Lock) -> None:
    conn = http.client.HTTPConnection(host, port)
    n = 0
    try:
        while time.monotonic() < stop_at[0]:
            value = (idx * 7 + n) % PROBE_VALUES
            n += 1
            t0 = time.monotonic()
            try:
                conn.request(
                    "POST", "/v1/statement",
                    body=f"EXECUTE {PROBE_NAME} USING {value}",
                    headers={"X-Trino-User": f"qps-{idx}"})
                resp = conn.getresponse()
                payload = json.loads(resp.read())
                while "nextUri" in payload:
                    path = payload["nextUri"].split(f":{port}", 1)[1]
                    conn.request("GET", path)
                    resp = conn.getresponse()
                    payload = json.loads(resp.read())
                ok = payload["stats"]["state"] == "FINISHED" \
                    and "error" not in payload
            except Exception:   # noqa: BLE001 — count, reconnect, go on
                ok = False
                conn.close()
                conn = http.client.HTTPConnection(host, port)
            dt = time.monotonic() - t0
            with lock:
                if t0 >= measure_from[0]:
                    if ok:
                        latencies.append(dt)
                        counters["completed"] += 1
                    else:
                        counters["errors"] += 1
    finally:
        conn.close()


def _percentile(sorted_vals: List[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def run_qps_bench(duration_s: float = 8.0, clients: int = 8,
                  warmup_s: float = 1.0, max_running: int = 4,
                  server=None) -> Dict[str, Any]:
    """Run the closed loop and return the report dict. A caller-provided
    `server` (tests) is used as-is and NOT stopped; otherwise a fresh
    tiny-TPC-H server starts, warms via the manifest, and stops after."""
    from trino_tpu.exec.plan_cache import stats as plan_stats
    from trino_tpu.serve.caches import result_cache_stats

    own_server = server is None
    if own_server:
        from trino_tpu.exec import LocalQueryRunner
        from trino_tpu.server import TrinoServer
        manifest = {"statements": [
            # PREPARE + one priming EXECUTE: plan cache + kernels warm
            {"name": PROBE_NAME, "sql": PROBE_SQL, "using": "0"},
        ]}
        server = TrinoServer(
            LocalQueryRunner.tpch("tiny"), max_running=max_running,
            query_timeout_s=60, warmup_manifest=manifest).start()
    try:
        host, port = "127.0.0.1", server.port
        # prime every parameter value once so the measurement window is
        # the steady state (result-cache hits), not first-touch misses
        conn = http.client.HTTPConnection(host, port)
        for value in range(PROBE_VALUES):
            conn.request("POST", "/v1/statement",
                         body=f"EXECUTE {PROBE_NAME} USING {value}",
                         headers={"X-Trino-User": "qps-prime"})
            payload = json.loads(conn.getresponse().read())
            while "nextUri" in payload:
                conn.request("GET",
                             payload["nextUri"].split(f":{port}", 1)[1])
                payload = json.loads(conn.getresponse().read())
        conn.close()

        plan_before = plan_stats()
        result_before = result_cache_stats()
        now = time.monotonic()
        measure_from = [now + warmup_s]
        stop_at = [now + warmup_s + duration_s]
        latencies: List[float] = []
        counters = {"completed": 0, "errors": 0}
        lock = threading.Lock()
        threads = [threading.Thread(
            target=_client_loop,
            args=(host, port, i, stop_at, measure_from, latencies,
                  counters, lock), daemon=True)
            for i in range(clients)]
        t_start = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=warmup_s + duration_s + 60)
        window = max(time.monotonic() - t_start - warmup_s, 1e-6)
        window = min(window, duration_s + 5.0)

        result_after = result_cache_stats()
        plan_after = plan_stats()
        hits = result_after["hits"] - result_before["hits"]
        misses = result_after["misses"] - result_before["misses"]
        lat = sorted(latencies)
        report: Dict[str, Any] = {
            "clients": clients,
            "duration_s": round(window, 2),
            "completed": counters["completed"],
            "errors": counters["errors"],
            "qps": round(counters["completed"] / window, 1),
            "p50_ms": round(_percentile(lat, 0.50) * 1000, 2),
            "p95_ms": round(_percentile(lat, 0.95) * 1000, 2),
            "p99_ms": round(_percentile(lat, 0.99) * 1000, 2),
            "result_cache_hit_rate": round(
                hits / max(hits + misses, 1), 4),
            "plan_cache_hits_delta":
                plan_after["hits"] - plan_before["hits"],
        }
        # the zero-work proof: sample a measurement-window cache hit's
        # stats from the tracker — planning, jit, and operator execution
        # must all read zero for a result served from cache
        from trino_tpu.exec.query_tracker import TRACKER
        sample = next(
            (q.stats for q in reversed(TRACKER.list())
             if q.stats and q.stats.get("result_cache_hits")), None)
        if sample is not None:
            report["cache_hit_zero_planning"] = \
                sample.get("planning_s", 1) == 0
            report["cache_hit_zero_jit"] = \
                sample.get("jit_misses", 1) == 0
            report["cache_hit_zero_execution"] = \
                sample.get("execution_s", 1) == 0
        if own_server:
            report["warmup_report"] = server.warmup_report
        return report
    finally:
        if own_server:
            server.stop()
