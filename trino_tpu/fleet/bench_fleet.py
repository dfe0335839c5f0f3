"""Fleet QPS scaling bench: the per-worker-count curve + rolling restart.

`run_fleet_qps` runs the curve: for each worker count N it starts a
fleet over the tiny TPC-H catalog (N=0 is the PR-7 single-process
TrinoServer baseline), primes the probe's parameter space so the
measurement window is the steady state, and hammers it with SUBPROCESS
load generators (fleet/bench_client.py — one process per client, so the
generator scales past the GIL exactly like the serving side does).
Reported per rung: sustained executions/s over the window, latency
percentiles, and error counts.

Two acceptance passes ride along at the top rung:

- MISSES: the same closed loop with `result_cache_enabled=false`, so
  every statement dispatches through a worker to the engine and
  executes — the fleet's proxy hop must not regress the miss path
  (ratio vs. the single-process miss rung).
- ROLLING RESTART: a mid-bench `FleetServer.rolling_restart()` replaces
  every worker while the closed loop runs; the drain protocol
  (`Connection: close` grace, listener close, straggler wait) plus the
  clients' reconnect-retry must land `errors == 0` — the zero-drop
  upgrade proof.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

PROBE_NAME = "qps_probe"
PROBE_SQL = ("SELECT n_name, n_regionkey FROM nation "
             "WHERE n_nationkey = ?")
PROBE_VALUES = 25

WARMUP_MANIFEST = {"statements": [
    {"name": PROBE_NAME, "sql": PROBE_SQL, "using": "0"},
]}


def _percentile(sorted_vals: List[float], p: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(p * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _prime(host: str, port: int) -> None:
    """One pass over every probe value so the window measures steady-
    state hits, not first-touch misses (and, through a fleet, so every
    value is published to the shared tier)."""
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=30)
    try:
        for value in range(PROBE_VALUES):
            conn.request("POST", "/v1/statement",
                         body=f"EXECUTE {PROBE_NAME} USING {value}",
                         headers={"X-Trino-User": "prime"})
            payload = json.loads(conn.getresponse().read())
            while "nextUri" in payload:
                conn.request("GET",
                             payload["nextUri"].split(f":{port}", 1)[1])
                payload = json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _run_clients(host: str, port: int, duration_s: float,
                 warmup_s: float, procs: int, threads: int,
                 mode: str = "hit") -> Dict[str, Any]:
    """Spawn the subprocess load generators, gather their JSON lines."""
    # run the client FILE directly, not `-m trino_tpu.fleet.bench_client`
    # — the -m form imports the trino_tpu package (and jax) into every
    # generator process, which costs seconds per client and contends
    # with the very fleet being measured; the script is stdlib-only
    client_py = os.path.join(os.path.dirname(__file__),
                             "bench_client.py")
    cmd = [sys.executable, client_py,
           host, str(port), str(duration_s), str(warmup_s),
           str(threads), mode, PROBE_NAME, str(PROBE_VALUES)]
    children = [subprocess.Popen(cmd, stdout=subprocess.PIPE)
                for _ in range(procs)]
    completed = errors = 0
    lat: List[float] = []
    deadline = duration_s + warmup_s + 120
    for child in children:
        try:
            out, _ = child.communicate(timeout=deadline)
        except subprocess.TimeoutExpired:
            child.kill()
            out, _ = child.communicate()
        try:
            rec = json.loads(out.splitlines()[-1])
        except (ValueError, IndexError):
            errors += 1
            continue
        completed += rec["completed"]
        errors += rec["errors"]
        lat.extend(rec["lat"])
    lat.sort()
    return {
        "clients": procs * threads,
        "completed": completed, "errors": errors,
        "qps": round(completed / max(duration_s, 1e-6), 1),
        "p50_ms": round(_percentile(lat, 0.50) * 1000, 2),
        "p95_ms": round(_percentile(lat, 0.95) * 1000, 2),
        "p99_ms": round(_percentile(lat, 0.99) * 1000, 2),
    }


def _single_process_server():
    from trino_tpu.exec import LocalQueryRunner
    from trino_tpu.server import TrinoServer
    return TrinoServer(LocalQueryRunner.tpch("tiny"), max_running=4,
                       query_timeout_s=60,
                       warmup_manifest=WARMUP_MANIFEST).start()


def run_fleet_qps(worker_counts: Optional[List[int]] = None,
                  duration_s: float = 6.0, client_procs: int = 8,
                  client_threads: int = 2, warmup_s: float = 1.0,
                  miss_duration_s: float = 4.0,
                  with_rolling_restart: bool = True) -> Dict[str, Any]:
    from trino_tpu.fleet.server import FleetServer
    worker_counts = worker_counts or [0, 1, 2, 4, 8]
    host = "127.0.0.1"
    report: Dict[str, Any] = {"worker_counts": worker_counts,
                              "duration_s": duration_s,
                              "client_procs": client_procs,
                              "client_threads": client_threads,
                              "rungs": []}
    miss_single = miss_fleet = None
    for n in worker_counts:
        if n <= 0:
            server = _single_process_server()
            port = server.port
            fleet = None
        else:
            fleet = FleetServer(workers=n, host=host,
                                warmup_manifest=WARMUP_MANIFEST).start()
            server = None
            port = fleet.port
        try:
            _prime(host, port)
            rung = _run_clients(host, port, duration_s, warmup_s,
                                client_procs, client_threads)
            rung["workers"] = n
            report["rungs"].append(rung)
            is_last = n == max(worker_counts)
            if n <= 0 and 0 in worker_counts:
                miss_single = _run_clients(
                    host, port, miss_duration_s, 0.5,
                    max(2, client_procs // 2), client_threads,
                    mode="miss")
            elif is_last and fleet is not None:
                miss_fleet = _run_clients(
                    host, port, miss_duration_s, 0.5,
                    max(2, client_procs // 2), client_threads,
                    mode="miss")
                if with_rolling_restart:
                    report["rolling_restart"] = _restart_pass(
                        fleet, host, port, duration_s, warmup_s,
                        client_procs, client_threads)
        finally:
            if fleet is not None:
                fleet.stop()
            if server is not None:
                server.stop()
    by_workers = {r["workers"]: r for r in report["rungs"]}
    top = max(worker_counts)
    if 0 in by_workers and top in by_workers:
        base = max(by_workers[0]["qps"], 1e-6)
        report["scaling_vs_single_process"] = round(
            by_workers[top]["qps"] / base, 2)
    if miss_single and miss_fleet:
        ratio = miss_fleet["qps"] / max(miss_single["qps"], 1e-6)
        report["miss"] = {"single_qps": miss_single["qps"],
                          "fleet_qps": miss_fleet["qps"],
                          "single_p99_ms": miss_single["p99_ms"],
                          "fleet_p99_ms": miss_fleet["p99_ms"],
                          "ratio": round(ratio, 3),
                          "no_regression": ratio >= 0.85}
    return report


def _restart_pass(fleet, host: str, port: int, duration_s: float,
                  warmup_s: float, procs: int, threads: int
                  ) -> Dict[str, Any]:
    """The zero-drop proof: rolling-restart every worker while the
    closed loop runs; errors must be 0 and every worker pid must
    change."""
    before = sorted(r["pid"] for r in fleet.workers())
    result: Dict[str, Any] = {}

    def _restart():
        time.sleep(warmup_s + 0.5)   # restart INSIDE the window
        t0 = time.monotonic()
        fleet.rolling_restart()
        result["restart_wall_s"] = round(time.monotonic() - t0, 2)

    th = threading.Thread(target=_restart, daemon=True)
    th.start()
    rung = _run_clients(host, port, duration_s, warmup_s, procs, threads)
    th.join(timeout=120)
    after = sorted(r["pid"] for r in fleet.workers())
    result.update(rung)
    result["workers_before"] = before
    result["workers_after"] = after
    result["all_workers_replaced"] = not set(before) & set(after)
    result["zero_dropped"] = rung["errors"] == 0
    return result


# ---------------------------------------------------------------- chaos


def _chaos_query(host: str, port: int, sql: str,
                 headers: Optional[Dict[str, str]] = None
                 ) -> Dict[str, Any]:
    """One statement through the fleet port on a FRESH connection,
    nextUri followed to the terminal payload. Returns
    {ok, error_name, worker_served, wall_s}; never raises — transport
    failures are what the chaos phases are here to count."""
    import http.client
    hdrs = {"X-Trino-User": "chaos"}
    hdrs.update(headers or {})
    t0 = time.monotonic()
    conn = http.client.HTTPConnection(host, port, timeout=20)
    try:
        conn.request("POST", "/v1/statement", body=sql, headers=hdrs)
        payload = json.loads(conn.getresponse().read())
        while "nextUri" in payload:
            conn.request("GET",
                         payload["nextUri"].split(f":{port}", 1)[1])
            payload = json.loads(conn.getresponse().read())
    except (OSError, ValueError):
        return {"ok": False, "error_name": "TRANSPORT",
                "worker_served": False,
                "wall_s": time.monotonic() - t0}
    finally:
        conn.close()
    err = payload.get("error") or {}
    return {"ok": payload.get("stats", {}).get("state") == "FINISHED"
            and not err,
            "error_name": err.get("errorName"),
            "worker_served": "_fleet_" in str(payload.get("id", "")),
            "wall_s": time.monotonic() - t0}


def run_chaos_fleet(workers: int = 2,
                    planned_duration_s: float = 14.0,
                    outage_budget_s: float = 90.0) -> Dict[str, Any]:
    """The process-level fault matrix against a LIVE fleet, one phase
    per process class.

    - ENGINE CRASH: kill -9 the engine generation mid-serving; a
      closed loop of shared-tier HITS must stay fully available
      (`hit_availability_during_outage`), misses must surface only the
      classified retryable ENGINE_UNAVAILABLE taxonomy (never a raw
      transport error), and the supervisor must restore an active
      rehydrated generation within `recovery_s`.
    - WORKER CRASH: kill -9 a worker; siblings keep the shared port
      serving (SO_REUSEPORT) and the supervisor respawns the headcount.
    - PLANNED RESTART: `engine_restart()` under a subprocess closed
      loop of cache MISSES — the SCM_RIGHTS listener handoff plus the
      workers' drain-retry must land `errors == 0` (zero-drop proof).
    """
    import signal as _signal
    from trino_tpu.fleet.registry import read_engine_record
    from trino_tpu.fleet.server import FleetServer
    from trino_tpu.fleet.supervisor import read_supervisor_record
    host = "127.0.0.1"
    fleet = FleetServer(workers=workers, host=host,
                        warmup_manifest=WARMUP_MANIFEST,
                        probe_interval_s=0.2, probe_timeout_s=1.0,
                        breaker_reset_s=0.5,
                        forward_backoff_s=0.02).start()
    report: Dict[str, Any] = {"workers": workers,
                              "probe": PROBE_NAME}
    try:
        port = fleet.port
        _prime(host, port)
        hit_sql = f"EXECUTE {PROBE_NAME} USING 7"
        miss_hdr = {"X-Trino-Session": "result_cache_enabled=false"}

        # ---- phase 1: engine crash under load -----------------------
        epoch_before = fleet.engine_epoch
        os.kill(fleet.engine_proc.pid, _signal.SIGKILL)
        t_kill = time.monotonic()
        hit_ok = hit_fail = hit_from_worker = 0
        miss_classified = miss_raw = miss_ok = 0
        recovery_s = None
        while time.monotonic() - t_kill < outage_budget_s:
            res = _chaos_query(host, port, hit_sql)
            if res["ok"]:
                hit_ok += 1
                hit_from_worker += res["worker_served"]
            else:
                hit_fail += 1
            mres = _chaos_query(host, port, hit_sql, headers=miss_hdr)
            if mres["ok"]:
                miss_ok += 1
            elif mres["error_name"] == "ENGINE_UNAVAILABLE":
                miss_classified += 1
            else:
                miss_raw += 1
            rec = read_engine_record(fleet.fleet_dir) or {}
            if (rec.get("epoch", 0) >= epoch_before + 1
                    and rec.get("state") == "active"):
                recovery_s = round(time.monotonic() - t_kill, 2)
                break
            time.sleep(0.05)
        report["engine_crash"] = {
            "hit_ok": hit_ok, "hit_fail": hit_fail,
            "hit_served_by_worker_shm": hit_from_worker,
            "hit_availability_during_outage": round(
                hit_ok / max(hit_ok + hit_fail, 1), 4),
            "miss_classified_unavailable": miss_classified,
            "miss_raw_errors": miss_raw,
            "miss_served_by_supervisor_race": miss_ok,
            "recovery_s": recovery_s,
            "recovered": recovery_s is not None,
        }
        # post-recovery: a miss resolves again (breaker reset by the
        # engine_epoch bus notice); bounded retry while it propagates
        deadline = time.monotonic() + 30
        post = {"ok": False}
        while time.monotonic() < deadline and not post["ok"]:
            post = _chaos_query(host, port, hit_sql, headers=miss_hdr)
            if not post["ok"]:
                time.sleep(0.2)
        report["engine_crash"]["miss_resolves_after_recovery"] = \
            post["ok"]

        # ---- phase 2: worker crash under load -----------------------
        victims = sorted(r["pid"] for r in fleet.workers())
        os.kill(victims[0], _signal.SIGKILL)
        t_kill = time.monotonic()
        w_ok = w_fail = 0
        w_recovery = None
        while time.monotonic() - t_kill < outage_budget_s:
            res = _chaos_query(host, port, hit_sql)
            if res["ok"]:
                w_ok += 1
            else:
                w_fail += 1
            pids = sorted(r["pid"] for r in fleet.workers())
            if len(pids) >= workers and victims[0] not in pids:
                w_recovery = round(time.monotonic() - t_kill, 2)
                break
            time.sleep(0.05)
        report["worker_crash"] = {
            "hit_ok": w_ok, "hit_fail": w_fail,
            "recovery_s": w_recovery,
            "recovered": w_recovery is not None,
        }

        # ---- phase 3: planned engine restart, zero-drop -------------
        swap: Dict[str, Any] = {}

        def _swap():
            time.sleep(1.0)     # restart INSIDE the miss window
            t0 = time.monotonic()
            swap["epoch"] = fleet.engine_restart()
            swap["wall_s"] = round(time.monotonic() - t0, 2)

        epoch_before = fleet.engine_epoch
        th = threading.Thread(target=_swap, daemon=True)
        th.start()
        rung = _run_clients(host, port, planned_duration_s, 0.0,
                            procs=2, threads=2, mode="miss")
        th.join(timeout=120)
        report["planned_restart"] = {
            "completed": rung["completed"], "errors": rung["errors"],
            "p99_ms": rung["p99_ms"],
            "swap_wall_s": swap.get("wall_s"),
            "epoch_advanced":
                swap.get("epoch", 0) == epoch_before + 1,
            "zero_dropped": rung["errors"] == 0
            and rung["completed"] > 0,
        }

        sup = read_supervisor_record(fleet.fleet_dir) or {}
        report["supervisor"] = {
            "engine_restarts": sup.get("engine_restarts"),
            "worker_restarts": sup.get("worker_restarts"),
            "outage_seconds": sup.get("outage_seconds"),
        }
        report["chaos_clean"] = bool(
            report["engine_crash"]["hit_availability_during_outage"]
            == 1.0
            and report["engine_crash"]["miss_raw_errors"] == 0
            and report["engine_crash"]["recovered"]
            and report["engine_crash"]["miss_resolves_after_recovery"]
            and report["worker_crash"]["recovered"]
            and report["planned_restart"]["zero_dropped"])
    finally:
        fleet.stop()
    return report
