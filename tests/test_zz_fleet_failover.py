"""Engine failover (ISSUE 16): supervised crash recovery, worker
degraded-mode serving, zero-drop planned engine restart over SCM_RIGHTS.

The acceptance suite for the supervised-engine topology: the engine is
a SUBPROCESS the FleetSupervisor monitors; kill -9 mid-stream must leave
shared-tier HITS serving uninterrupted, classify misses as the
retryable ENGINE_UNAVAILABLE taxonomy (never a raw connection reset),
and restore a rehydrated engine generation (prepared statements, warm
caches) without a single stale shm read. The planned path proves the
stronger claim: `engine_restart()` swaps generations by passing the
live dispatch listener over SCM_RIGHTS, so a closed loop of cache
MISSES sees zero errors across the swap.

Named test_zz_* so these process-chaos sweeps collect LAST (the tier-1
wall budget spends on the seed suites first)."""

import json
import os
import signal
import socket
import threading
import time
import urllib.request

import pytest

pytestmark = pytest.mark.skipif(
    not hasattr(socket, "SO_REUSEPORT"),
    reason="fleet serving needs SO_REUSEPORT")


# ------------------------------------------------------------ unit layer


def test_circuit_breaker_state_machine():
    from trino_tpu.fleet.worker import CircuitBreaker
    br = CircuitBreaker(failure_threshold=3, reset_s=0.2)
    assert br.state == 0 and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == 0 and br.allow()      # under threshold: CLOSED
    br.record_failure()
    assert br.state == 2 and not br.allow()  # threshold consecutive: OPEN
    time.sleep(0.25)
    assert br.allow()                        # one HALF_OPEN trial
    assert br.state == 1
    assert not br.allow()                    # others fast-fail mid-trial
    br.record_failure()                      # trial failed: straight back
    assert br.state == 2 and not br.allow()
    time.sleep(0.25)
    assert br.allow()
    br.record_success()                      # trial succeeded: CLOSED
    assert br.state == 0 and br.allow()
    # a success resets the consecutive-failure count entirely
    br.record_failure()
    br.record_failure()
    br.record_success()
    br.record_failure()
    br.record_failure()
    assert br.state == 0
    # reset() is the engine_epoch bus notice's hammer
    br.record_failure()
    assert br.state == 2
    br.reset()
    assert br.state == 0 and br.allow()


def test_scm_rights_handoff_roundtrip(tmp_path):
    """A LISTENING socket fd crosses a unix socket via SCM_RIGHTS and
    keeps accepting on the other side — the mechanism under
    engine_restart()'s zero-drop swap."""
    from trino_tpu.fleet.handoff import HandoffListener, offer_fds
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(8)
    port = lsock.getsockname()[1]
    path = str(tmp_path / "handoff.sock")
    listener = HandoffListener(path)
    meta_sent = {"port": port, "epoch": 7}

    def _offer():
        offer_fds(path, [lsock.fileno()], meta_sent, timeout_s=10)

    th = threading.Thread(target=_offer, daemon=True)
    th.start()
    fds, meta = listener.accept_fds(timeout_s=10)
    th.join(timeout=10)
    listener.close()
    assert meta == meta_sent and len(fds) == 1
    # a connection initiated BEFORE the original fd closes is accepted
    # through the passed fd (the kernel backlog carries the gap)
    client = socket.create_connection(("127.0.0.1", port), timeout=5)
    lsock.close()       # old generation exits
    adopted = socket.socket(fileno=fds[0])
    adopted.settimeout(5)
    conn, _ = adopted.accept()
    client.sendall(b"ping")
    assert conn.recv(4) == b"ping"
    conn.close()
    client.close()
    adopted.close()


def test_bus_drops_counted_and_logged_once(tmp_path, capfd):
    from trino_tpu.fleet.bus import FleetBus
    bus = FleetBus(str(tmp_path), "solo")
    try:
        # a member that vanished without unbinding: every send drops
        dead = os.path.join(str(tmp_path), "bus", "ghost.sock")
        with open(dead, "w"):
            pass
        assert not bus.send_to("ghost", {"kind": "hits", "n": 1})
        assert not bus.send_to("ghost", {"kind": "hits", "n": 2})
        assert not bus.send_to("ghost", {"kind": "prepare", "name": "x"})
        # oversize datagrams drop under their own kind
        bus.publish({"kind": "hits", "pad": "x" * 70000})
        drops = bus.drops_snapshot()
        assert drops["hits"] == 3
        assert drops["prepare"] == 1
        err = capfd.readouterr().err
        assert err.count("dropped 'hits' datagram") == 1     # once per kind
        assert err.count("dropped 'prepare' datagram") == 1
    finally:
        bus.close()


# ------------------------------------------------- the fleet, end to end


FAILOVER_RG = {"groups": [{"name": "global"}]}


def _http(base, sql, headers=None, timeout=30):
    req = urllib.request.Request(f"{base}/v1/statement",
                                 data=sql.encode(), method="POST")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    resp = urllib.request.urlopen(req, timeout=timeout)
    payload = json.loads(resp.read())
    rows = list(payload.get("data", []))
    while "nextUri" in payload:
        r2 = urllib.request.urlopen(payload["nextUri"], timeout=timeout)
        payload = json.loads(r2.read())
        rows.extend(payload.get("data", []))
    return payload, rows


@pytest.fixture(scope="module")
def fo(tmp_path_factory):
    from trino_tpu.fleet import FleetServer
    d = tmp_path_factory.mktemp("failover")
    rg_path = str(d / "rg.json")
    with open(rg_path, "w") as fh:
        json.dump(FAILOVER_RG, fh)
    server = FleetServer(
        workers=2, resource_groups_path=rg_path,
        engine_env={"TRINO_TPU_LAKE_DIR": str(d / "lake")},
        probe_interval_s=0.2, probe_timeout_s=1.0,
        breaker_reset_s=0.5, forward_backoff_s=0.02,
        drain_timeout_s=6.0,
        warmup_manifest={"statements": [
            {"name": "fo_probe",
             "sql": "SELECT n_name, n_regionkey FROM nation "
                    "WHERE n_nationkey = ?",
             "using": "0"}]}).start()
    yield server
    server.stop()


def _wait_engine_state(fo, epoch, state="active", timeout_s=90.0):
    from trino_tpu.fleet.registry import read_engine_record
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rec = read_engine_record(fo.fleet_dir)
        if rec and int(rec.get("epoch", -1)) >= epoch \
                and rec.get("state") == state:
            return rec
        time.sleep(0.1)
    raise TimeoutError(f"engine epoch {epoch} not {state}")


def _prime_hit(fo, sql):
    """Run `sql` until a WORKER answers it from the shared tier."""
    payload, rows = _http(fo.base_uri, sql)
    assert payload["stats"]["state"] == "FINISHED"
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        payload, got = _http(fo.base_uri, sql)
        if "_fleet_" in payload.get("id", ""):     # worker-served hit
            return got
        time.sleep(0.1)
    # fall back on result equality: the hit path is asserted below by
    # serving through a DEAD engine, which only the tier can do
    return rows


def test_engine_crash_failover(fo):
    """kill -9 the engine mid-fleet: hits keep serving from shm with
    zero errors, a miss answers the classified retryable
    ENGINE_UNAVAILABLE (not a connection reset), the supervisor
    respawns a rehydrated generation, and headerless EXECUTE resolves
    against it (prepared registry rehydration)."""
    from trino_tpu.fleet.supervisor import read_supervisor_record
    hit_sql = "EXECUTE fo_probe USING 5"
    before_rows = _prime_hit(fo, hit_sql)
    assert before_rows == [["ETHIOPIA", 0]]
    old_pid = fo.engine_proc.pid
    epoch_before = fo.engine_epoch
    os.kill(old_pid, signal.SIGKILL)

    # degraded mode: shared-tier hits never notice the dead engine
    outage_hits = 0
    saw_unavailable = False
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not saw_unavailable:
        payload, rows = _http(fo.base_uri, hit_sql)
        assert payload["stats"]["state"] == "FINISHED", payload
        assert rows == before_rows        # zero stale reads, ever
        outage_hits += 1
        # a MISS during the outage: classified, retryable, named
        p2, _ = _http(fo.base_uri, "SELECT count(*) + 17 FROM nation",
                      timeout=60)
        err = p2.get("error")
        if err is None:
            # the supervisor already won the race; that's the next
            # assertion's job
            break
        assert err["errorName"] == "ENGINE_UNAVAILABLE", err
        assert err["errorType"] == "INTERNAL_ERROR"
        saw_unavailable = True
    assert outage_hits >= 1
    # the taxonomy the client replays on: classified AND retryable
    from trino_tpu.errors import ENGINE_UNAVAILABLE
    assert ENGINE_UNAVAILABLE.retryable
    assert ENGINE_UNAVAILABLE.code == 65544

    # supervised recovery: a NEW pid, epoch bumped, crash counted
    rec = _wait_engine_state(fo, epoch=epoch_before + 1)
    assert int(rec["pid"]) != old_pid
    # crash is counted at restart START, outage accumulated at the END
    # of the respawn — wait for both writes, not just the first
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        sup = read_supervisor_record(fo.fleet_dir) or {}
        if ((sup.get("engine_restarts") or {}).get("crash", 0) >= 1
                and sup.get("outage_seconds", 0) > 0):
            break
        time.sleep(0.2)
    sup = read_supervisor_record(fo.fleet_dir)
    assert sup["engine_restarts"]["crash"] >= 1
    assert sup["outage_seconds"] > 0

    # misses resolve again (breaker reset via the engine_epoch notice)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        p3, rows3 = _http(fo.base_uri,
                          "SELECT count(*) + 17 FROM nation", timeout=60)
        if p3["stats"]["state"] == "FINISHED":
            assert rows3 == [[42]]
            break
        time.sleep(0.3)
    else:
        raise AssertionError("miss never recovered after engine respawn")

    # prepared rehydration: a HEADERLESS EXECUTE of the warmed name,
    # with a parameter value nobody cached, must execute on the NEW
    # generation (the registry snapshot rehydrated its prepared map)
    p4, rows4 = _http(fo.base_uri, "EXECUTE fo_probe USING 11",
                      timeout=60)
    assert p4["stats"]["state"] == "FINISHED", p4
    assert rows4 == [["IRAQ", 4]]
    # and the pre-crash hit still serves, still correct
    _, rows5 = _http(fo.base_uri, hit_sql)
    assert rows5 == before_rows


def test_insert_replay_exactly_once_across_crash(fo):
    """The idempotent-write token makes a client replay of an INSERT
    exactly-once even when the engine DIED after committing: the lake
    manifest's committed-token ledger survives the process."""
    _http(fo.base_uri,
          "CREATE TABLE lake.default.fo_once (a BIGINT)", timeout=60)
    tok_hdr = {"X-Trino-Session": "write_token=fo-tok-1"}
    p, _ = _http(fo.base_uri,
                 "INSERT INTO lake.default.fo_once VALUES (1)",
                 headers=tok_hdr, timeout=60)
    assert p["stats"]["state"] == "FINISHED", p
    old_pid = fo.engine_proc.pid
    epoch_before = fo.engine_epoch
    os.kill(old_pid, signal.SIGKILL)
    _wait_engine_state(fo, epoch=epoch_before + 1)
    # the replay: same statement, same token, NEW engine generation
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        p2, _ = _http(fo.base_uri,
                      "INSERT INTO lake.default.fo_once VALUES (1)",
                      headers=tok_hdr, timeout=60)
        if p2["stats"]["state"] == "FINISHED":
            break
        time.sleep(0.3)
    else:
        raise AssertionError("replay INSERT never succeeded")
    _, rows = _http(fo.base_uri,
                    "SELECT count(*) FROM lake.default.fo_once",
                    headers={"X-Trino-Session":
                             "result_cache_enabled=false"}, timeout=60)
    assert rows == [[1]]       # the replay deduped: exactly once
    # a DIFFERENT token appends normally
    p3, _ = _http(fo.base_uri,
                  "INSERT INTO lake.default.fo_once VALUES (2)",
                  headers={"X-Trino-Session": "write_token=fo-tok-2"},
                  timeout=60)
    assert p3["stats"]["state"] == "FINISHED"
    _, rows = _http(fo.base_uri,
                    "SELECT count(*) FROM lake.default.fo_once",
                    headers={"X-Trino-Session":
                             "result_cache_enabled=false"}, timeout=60)
    assert rows == [[2]]


def test_worker_respawn_after_kill(fo):
    """Satellite: a worker dying mid-flight is respawned by the
    supervisor; the fleet returns to full strength with a new pid."""
    before = {r["pid"] for r in fo.workers()}
    assert len(before) == 2
    victim_pid = sorted(before)[0]
    os.kill(victim_pid, signal.SIGKILL)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        recs = fo.workers()
        pids = {r["pid"] for r in recs}
        if len(recs) == 2 and victim_pid not in pids:
            break
        time.sleep(0.2)
    else:
        raise AssertionError(f"worker fleet never recovered: "
                             f"{fo.workers()}")
    # the replacement serves: a hit through the shared port still lands
    payload, _ = _http(fo.base_uri, "EXECUTE fo_probe USING 5")
    assert payload["stats"]["state"] == "FINISHED"


def test_planned_engine_restart_zero_drop_misses(fo):
    """THE acceptance bar: engine_restart() under a closed loop of
    cache MISSES completes with zero errors — the replacement warms up
    first, the old generation drains, and the listener crosses over
    SCM_RIGHTS so no connection ever lands on a dead port."""
    from trino_tpu.fleet.bench_client import run as client_run
    _http(fo.base_uri, "EXECUTE fo_probe USING 3")
    epoch_before = fo.engine_epoch
    result = {}

    def _swap():
        time.sleep(1.0)
        result["epoch"] = fo.engine_restart()

    th = threading.Thread(target=_swap, daemon=True)
    th.start()
    rec = client_run("127.0.0.1", fo.port, duration_s=25.0,
                     warmup_s=0.0, threads=3, mode="miss",
                     probe="fo_probe", values=25)
    th.join(timeout=120)
    assert result.get("epoch") == epoch_before + 1
    assert rec["errors"] == 0, rec
    assert rec["completed"] > 50, rec
    # post-swap sanity: the new generation executes and serves hits
    payload, rows = _http(fo.base_uri, "EXECUTE fo_probe USING 21",
                          timeout=60)
    assert payload["stats"]["state"] == "FINISHED"
    assert rows == [["VIETNAM", 2]]


def test_failover_metrics_surface(fo):
    """The observability satellite wiring: supervisor counters, breaker
    state, deferred-miss counters, and bus drop counts all land in ONE
    shared-port scrape. Causes what it asserts — one crash restart (whose
    outage drops the workers' hit batches on the dead engine socket) and
    one planned restart — so it holds on whichever xdist worker runs it,
    with or without the crash tests above in the same process."""
    from trino_tpu.fleet.supervisor import read_supervisor_record

    def restarts():
        sup = read_supervisor_record(fo.fleet_dir) or {}
        counts = sup.get("engine_restarts") or {}
        return counts.get("crash", 0), counts.get("planned", 0)

    def scrape():
        return urllib.request.urlopen(f"{fo.base_uri}/v1/metrics",
                                      timeout=30).read().decode()

    crash_before, planned_before = restarts()
    hit_sql = "EXECUTE fo_probe USING 5"
    _prime_hit(fo, hit_sql)
    epoch_before = fo.engine_epoch
    os.kill(fo.engine_proc.pid, signal.SIGKILL)
    # hits keep serving through the outage; their batches to the dead
    # engine socket are the drops the scrape must show
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        _http(fo.base_uri, hit_sql)
        if "trino_tpu_fleet_bus_drops_total" in scrape():
            break
        time.sleep(0.2)
    _wait_engine_state(fo, epoch=epoch_before + 1)
    # the supervisor adopts the respawned generation (and bumps its own
    # epoch) a moment after the engine's record says active
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and (
            restarts()[0] <= crash_before
            or fo.engine_epoch <= epoch_before):
        time.sleep(0.2)
    assert fo.engine_restart() == epoch_before + 2

    text = scrape()
    assert 'trino_tpu_engine_restarts_total{kind="crash"}' in text
    assert "trino_tpu_engine_outage_seconds" in text
    assert "trino_tpu_fleet_breaker_state" in text
    assert "trino_tpu_fleet_worker_deferred_misses" in text
    assert "trino_tpu_engine_epoch" in text
    assert "trino_tpu_fleet_bus_drops_total" in text
    # counts match the supervisor's own record
    crash, planned = restarts()
    assert crash >= crash_before + 1
    assert planned >= planned_before + 1


def test_zz_poison_statement_stops_crash_loop(fo):
    """Poison-statement quarantine end to end: a digest stamped in
    flight across two crash-correlated engine restarts is published to
    poison.json, the supervisor record tells the story, and the workers
    then fast-fail the statement with the non-retryable
    STATEMENT_QUARANTINED taxonomy instead of crash-looping the
    replacement engine. Innocent statements keep executing."""
    from trino_tpu.fleet import supervisor as sup
    sql = "SELECT 41999 + 1"
    digest = sup.statement_digest(sql)
    for qid in ("q-poison-1", "q-poison-2"):
        # the record going active races the supervisor swapping in the
        # new Popen handle — wait for a LIVE engine process to murder
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline \
                and fo.engine_proc.poll() is not None:
            time.sleep(0.05)
        assert fo.engine_proc.poll() is None
        epoch = fo.engine_epoch
        # stamp the statement in flight exactly as the engine-side
        # observer does, then die before clearing it
        sup.StatementStamper(fo.fleet_dir, epoch=epoch).begin(sql, qid)
        os.kill(fo.engine_proc.pid, signal.SIGKILL)
        _wait_engine_state(fo, epoch=epoch + 1)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline \
            and digest not in sup.read_poison(fo.fleet_dir):
        time.sleep(0.1)
    rec = sup.read_poison(fo.fleet_dir)[digest]
    assert rec["crashes"] >= 2 and rec["sql"] == sql
    assert rec["query_id"] == "q-poison-2"
    sup_rec = sup.read_supervisor_record(fo.fleet_dir)
    assert digest in sup_rec["poisoned"]
    # every worker fast-fails it now — the engine never sees it
    for _ in range(3):
        payload, _rows = _http(fo.base_uri, sql)
        assert payload["stats"]["state"] == "FAILED"
        assert payload["error"]["errorName"] == "STATEMENT_QUARANTINED"
        assert payload["error"]["errorType"] == "INTERNAL_ERROR"
    # an innocent statement still executes through the same fleet
    payload2, rows2 = _http(fo.base_uri, "SELECT 2 + 2")
    assert payload2["stats"]["state"] == "FINISHED"
    assert rows2 == [[4]]
    # the gauge surfaces on the fleet scrape
    text = urllib.request.urlopen(
        f"{fo.base_uri}/v1/metrics", timeout=10).read().decode()
    assert "trino_tpu_fleet_poisoned_statements" in text
