"""FleetServer: N SO_REUSEPORT worker processes over one device runner.

Reference parity: Trino's production story is a dispatcher fronting many
coordinators; this engine's analog keeps the DEVICE single-owner — one
process holds the runner (jit cache, plan cache, node pool, table
cache) and executes every cache miss — while N worker processes share
the accept load on ONE port and answer result-cache hits from the
cross-process shared tier (fleet/shm.py) without ever touching the
engine. The parent process:

- SUPERVISES the engine: by default the engine is its own subprocess
  (`python -m trino_tpu.fleet.engine`, fleet/engine.py) so a device
  wedge or OOM kills a REPLACEABLE process, not the fleet. The
  supervisor thread (fleet/supervisor.py) detects the death, respawns a
  generation that rehydrates its warm state from the fleet directory,
  and the workers keep serving shared-tier hits the whole time
  (fleet/worker.py degraded mode). `engine_in_process=True` (implied by
  passing a `runner`) keeps the PR-13 topology: the engine runs inside
  this process and crash recovery is out of scope.
- spawns/monitors the worker subprocesses, writes the fleet.json
  rendezvous config (ports, shm path, the engine session's keying
  context), and — in-process mode — ingests the workers' cache-hit
  accounting batches into the engine's resource-group counters and
  query tracker (the subprocess engine ingests its own).
- performs the zero-drop restarts: worker-by-worker rolling restart
  (spawn replacement, drain, wait), and `engine_restart()` — a PLANNED
  engine swap that passes the live dispatch listener to the replacement
  over SCM_RIGHTS (fleet/handoff.py), so even cache MISSES in flight
  during the swap complete with zero errors.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from trino_tpu.fleet.bus import FleetBus
from trino_tpu.fleet.registry import (PreparedRegistry, ReloadableQuotaMap,
                                      list_worker_records, quota_allows,
                                      read_engine_record, read_fleet_config,
                                      write_fleet_config)
from trino_tpu.fleet.shm import (DEFAULT_DATA_BYTES, SharedCacheTier,
                                 key_fingerprint)
from trino_tpu.fleet.supervisor import FleetSupervisor
from trino_tpu.serve.caches import (DEFAULT_RESULT_MAX_ENTRIES,
                                    ResultSetCache)

WORKER_READY_TIMEOUT_S = 90.0
ENGINE_READY_TIMEOUT_S = 240.0


class MirroredResultSetCache(ResultSetCache):
    """The engine's result cache with the shared tier as a write-through
    mirror. `generation()` snapshots BOTH counters (tier first — the
    wider scope must not be newer than the narrower one), `put` publishes
    to the tier only when the local put survived its own generation
    guard AND the tier's guard accepts the tier-side snapshot, and
    `get` falls back to the tier on a local miss (a restarted engine
    re-adopts the fleet's warm results). Stale publishes stay
    structurally impossible in either direction."""

    def __init__(self, tier: SharedCacheTier,
                 max_entries: int = DEFAULT_RESULT_MAX_ENTRIES):
        super().__init__(max_entries)
        self.tier = tier

    def generation(self):
        tier_gen = self.tier.generation()
        return (tier_gen, super().generation())

    @staticmethod
    def _split(gen):
        return gen if isinstance(gen, tuple) else (None, gen)

    def put(self, key, entry, gen=None) -> bool:
        tier_gen, local_gen = self._split(gen)
        ok = super().put(key, entry, gen=local_gen)
        if ok:
            self.tier.put(key_fingerprint(key), entry, entry.tables,
                          gen=tier_gen)
        return ok

    def get(self, key, count_miss: bool = True):
        entry = super().get(key, count_miss=count_miss)
        if entry is not None:
            return entry
        local_gen = super().generation()    # BEFORE the tier read: an
        # invalidation racing the adoption below must reject it
        found = self.tier.get(key_fingerprint(key))
        if found is None:
            return None
        entry = found[0]
        super().put(key, entry, gen=local_gen)
        return entry

    def invalidate(self, table) -> int:
        n = super().invalidate(table)
        self.tier.invalidate(table)
        return n


class _QuotaGate:
    """The engine's fast-path quota check, rebased onto the fleet-wide
    shared-memory buckets so engine-landed and worker-landed hits drain
    ONE bucket per group. Hot-reloads the quota map on file mtime
    through the same ReloadableQuotaMap the workers use."""

    def __init__(self, shared: SharedCacheTier, rg_path: Optional[str]):
        self.shared = shared
        self.quotas = ReloadableQuotaMap(rg_path)

    def __call__(self, group: str) -> bool:
        return quota_allows(self.shared, self.quotas.current(), group)


class FleetServer:
    def __init__(self, runner=None, workers: int = 2,
                 host: str = "127.0.0.1", port: int = 0,
                 fleet_dir: Optional[str] = None,
                 schema: str = "tiny",
                 resource_groups_path: Optional[str] = None,
                 warmup_manifest=None,
                 in_process: bool = False,
                 engine_in_process: Optional[bool] = None,
                 drain_grace_s: float = 0.5,
                 drain_timeout_s: float = 10.0,
                 shm_data_bytes: int = DEFAULT_DATA_BYTES,
                 worker_env: Optional[Dict[str, str]] = None,
                 engine_env: Optional[Dict[str, str]] = None,
                 probe_interval_s: float = 0.5,
                 probe_timeout_s: float = 2.0,
                 engine_stall_probes: int = 6,
                 worker_respawn_max: int = 3,
                 respawn_backoff_s: float = 0.25,
                 breaker_failure_threshold: int = 3,
                 breaker_reset_s: float = 1.0,
                 forward_retries: int = 3,
                 forward_backoff_s: float = 0.05,
                 handoff_enabled: bool = True,
                 poison_crash_threshold: int = 2,
                 poison_ttl_s: float = 300.0,
                 **engine_kwargs):
        # a caller-supplied runner can only live in THIS process, so it
        # implies the in-process engine; otherwise the engine defaults
        # to a supervised subprocess (in which case engine_kwargs must
        # be JSON-serializable — they ride fleet.json to the child)
        if engine_in_process is None:
            engine_in_process = runner is not None or bool(in_process)
        self.engine_in_process = bool(engine_in_process)
        self.host = host
        self.schema = schema
        self.n_workers = int(workers)
        self.in_process = bool(in_process)
        self.drain_grace_s = float(drain_grace_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.worker_env = dict(worker_env or {})
        self.engine_env = dict(engine_env or {})
        self.probe_interval_s = float(probe_interval_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.engine_stall_probes = int(engine_stall_probes)
        self.worker_respawn_max = int(worker_respawn_max)
        self.respawn_backoff_s = float(respawn_backoff_s)
        self.breaker_failure_threshold = int(breaker_failure_threshold)
        self.breaker_reset_s = float(breaker_reset_s)
        self.forward_retries = int(forward_retries)
        self.forward_backoff_s = float(forward_backoff_s)
        self.handoff_enabled = bool(handoff_enabled)
        self.poison_crash_threshold = int(poison_crash_threshold)
        self.poison_ttl_s = float(poison_ttl_s)
        self.warmup_manifest = warmup_manifest
        self.engine_kwargs = engine_kwargs
        self._owns_dir = fleet_dir is None
        self.fleet_dir = fleet_dir or tempfile.mkdtemp(prefix="tpu_fleet_")
        os.makedirs(self.fleet_dir, exist_ok=True)
        self.shm_path = os.path.join(self.fleet_dir, "cache.shm")
        self.shared = SharedCacheTier(self.shm_path, create=True,
                                      data_bytes=int(shm_data_bytes))
        self.resource_groups_path = resource_groups_path
        self.engine = None
        self.runner = None
        self.engine_proc: Optional[subprocess.Popen] = None
        self.engine_epoch = 0
        self.engine_port = 0
        self._engine_expected_down = False
        self._draining: set = set()
        self.supervisor: Optional[FleetSupervisor] = None
        if self.engine_in_process:
            if runner is None:
                from trino_tpu.exec import LocalQueryRunner
                runner = LocalQueryRunner.tpch(schema)
            self.runner = runner
            # the engine: a full single-process TrinoServer on a private
            # loopback port, the sole owner of the device runner
            from trino_tpu.server import TrinoServer
            self.engine = TrinoServer(
                runner, host="127.0.0.1", port=0,
                resource_groups_path=resource_groups_path,
                warmup_manifest=warmup_manifest, **engine_kwargs)
            # swap the engine's result cache for the mirrored one and
            # hang it on the SAME plan-cache invalidation fan-out
            # DDL/INSERT drives — one INSERT drops plans, local caches,
            # the shared tier, and (via the bus notice below) every
            # worker's hot copies
            self._mirrored = MirroredResultSetCache(self.shared)
            runner._result_cache = self._mirrored
            runner._plan_cache.add_invalidation_hook(
                self._mirrored.invalidate)
            runner._plan_cache.add_invalidation_hook(
                self._publish_invalidate)
            self.engine.fast_path_quota = _QuotaGate(self.shared,
                                                     resource_groups_path)
            self.engine_port = self.engine.port
        # in subprocess mode "engine" names the engine CHILD on the bus;
        # the parent is just another member
        bus_name = "engine" if self.engine_in_process else "fleet"
        self.bus = FleetBus(self.fleet_dir, bus_name,
                            on_message=self._on_bus)
        self._procs: Dict[str, subprocess.Popen] = {}
        self._inproc: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self.port = self._pick_port(host, port)
        self.base_uri = f"http://{host}:{self.port}"
        self.fleet_hits_ingested = 0
        if self.engine_in_process:
            self._register_gauges()

    # ----------------------------------------------------------- lifecycle

    @staticmethod
    def _pick_port(host: str, port: int) -> int:
        """Reserve the fleet's shared port: bind with SO_REUSEPORT (so
        the workers' later binds of the same port succeed), read the
        assignment, release. The parent must NOT keep a bound socket —
        a listener that never accepts would eat its share of the
        kernel's SO_REUSEPORT distribution."""
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            if hasattr(socket, "SO_REUSEPORT"):
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            s.bind((host, port))
            return s.getsockname()[1]
        finally:
            s.close()

    @property
    def worker_procs(self) -> Dict[str, subprocess.Popen]:
        return self._procs

    def start(self) -> "FleetServer":
        # sticky prepared statements, leg 0: the warmup manifest's named
        # statements seed the FLEET registry too, so workers can key
        # EXECUTEs of warmed shapes before any client ever PREPAREd one
        # through the fleet — and a respawned engine rehydrates them
        self.prepared = PreparedRegistry(self.fleet_dir)
        if self.warmup_manifest is not None:
            from trino_tpu.serve.warmup import load_manifest
            try:
                for spec in load_manifest(self.warmup_manifest):
                    if spec.get("name") and spec.get("sql"):
                        self.prepared.register(str(spec["name"]).lower(),
                                               spec["sql"])
            except Exception:   # noqa: BLE001 — warmup stays best-effort
                pass
        if self.engine_in_process:
            self.engine.start()
            self._write_config(self._keying_context_local())
        else:
            # the engine port is FIXED for the fleet's lifetime: every
            # respawned generation rebinds (or SCM_RIGHTS-inherits) the
            # same port, so workers never re-resolve their upstream
            self.engine_port = self._pick_port("127.0.0.1", 0)
            self._write_config({})
            self.engine_proc = self._spawn_engine(epoch=1)
            self.engine_epoch = 1
            rec = self._wait_engine(self.engine_proc, "active", 1,
                                    ENGINE_READY_TIMEOUT_S)
            # the engine session's keying context (current_date pin,
            # plan-affecting base properties) is only known once the
            # child built its runner: merge it into fleet.json before
            # any worker reads it
            self._write_config({
                "start_date": rec.get("start_date"),
                "base_properties": rec.get("base_properties") or {},
                "default_group": rec.get("default_group", "global"),
                "catalog": rec.get("catalog", "tpch"),
                "schema": rec.get("schema", self.schema),
            })
        ids = [self.spawn_worker(wait=False)
               for _ in range(self.n_workers)]
        self._wait_ready(ids)
        self.supervisor = FleetSupervisor(
            self, probe_interval_s=self.probe_interval_s,
            probe_timeout_s=self.probe_timeout_s,
            stall_probes=self.engine_stall_probes,
            worker_respawn_max=self.worker_respawn_max,
            respawn_backoff_s=self.respawn_backoff_s,
            poison_crash_threshold=self.poison_crash_threshold,
            poison_ttl_s=self.poison_ttl_s).start()
        return self

    def _keying_context_local(self) -> Dict:
        from trino_tpu.exec.plan_cache import PLAN_PROPERTIES
        session = self.runner.session
        return {
            # the keying context workers must replicate EXACTLY:
            # current_date is pinned at engine-session construction, and
            # any plan-affecting property set on the base session is
            # part of every key
            "start_date": session.start_date,
            "base_properties": {
                p: session.properties[p] for p in PLAN_PROPERTIES
                if p in session.properties},
            "default_group": str(session.get("resource_group")),
            "catalog": session.catalog, "schema": session.schema,
        }

    def _write_config(self, keying_context: Dict) -> None:
        config = {
            "host": self.host, "port": self.port,
            "engine_host": "127.0.0.1", "engine_port": self.engine_port,
            "engine_base": f"http://127.0.0.1:{self.engine_port}",
            "fleet_dir": self.fleet_dir, "shm_path": self.shm_path,
            "schema": self.schema,
            "resource_groups_path": self.resource_groups_path,
            "drain_grace_s": self.drain_grace_s,
            "drain_timeout_s": self.drain_timeout_s,
            "breaker_failure_threshold": self.breaker_failure_threshold,
            "breaker_reset_s": self.breaker_reset_s,
            "forward_retries": self.forward_retries,
            "forward_backoff_s": self.forward_backoff_s,
            "handoff_enabled": self.handoff_enabled,
            "engine_mode": "in-process" if self.engine_in_process
            else "subprocess",
        }
        if not self.engine_in_process:
            config["warmup_manifest"] = self.warmup_manifest
            config["engine_kwargs"] = self.engine_kwargs
        config.update(keying_context)
        write_fleet_config(self.fleet_dir, config)

    # ------------------------------------------------------------ engine

    def _spawn_engine(self, epoch: int,
                      handoff_path: Optional[str] = None
                      ) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "trino_tpu.fleet.engine",
               self.fleet_dir, "--epoch", str(epoch)]
        if handoff_path is not None:
            cmd += ["--handoff", handoff_path]
        else:
            cmd += ["--port", str(self.engine_port)]
        env = dict(os.environ)
        # the engine child owns the device — it inherits the parent's
        # backend selection unmodified; the marker lets the chaos
        # harness's `engine` fault site know a SIGKILL here is fair game
        env["TRINO_TPU_ENGINE_CHILD"] = "1"
        env.update(self.engine_env)
        log_path = os.path.join(self.fleet_dir, "engine.log")
        log = open(log_path, "a")
        proc = subprocess.Popen(cmd, stdout=log,
                                stderr=subprocess.STDOUT, env=env,
                                start_new_session=True)
        log.close()
        return proc

    def _wait_engine(self, proc: subprocess.Popen, state: str,
                     epoch: int, timeout_s: float) -> Dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            rec = read_engine_record(self.fleet_dir)
            if rec and int(rec.get("epoch", -1)) == epoch:
                if rec.get("state") == state:
                    return rec
                if rec.get("state") == "failed":
                    raise RuntimeError(
                        f"fleet engine (epoch {epoch}) failed at "
                        f"startup: {rec.get('error')}; see "
                        f"{self.fleet_dir}/engine.log")
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fleet engine (epoch {epoch}) died at startup "
                    f"(rc={proc.returncode}): "
                    f"{self._log_tail('engine.log')}")
            time.sleep(0.05)
        raise TimeoutError(
            f"fleet engine (epoch {epoch}) not {state} within "
            f"{timeout_s}s")

    def _log_tail(self, rel_path: str, nbytes: int = 2000) -> str:
        try:
            with open(os.path.join(self.fleet_dir, rel_path), "rb") as fh:
                fh.seek(0, os.SEEK_END)
                fh.seek(max(0, fh.tell() - nbytes))
                return fh.read().decode("utf-8", "replace").strip()
        except OSError:
            return "<no log>"

    def _respawn_engine(self) -> None:
        """CRASH recovery (called by the supervisor): spawn the next
        generation in bind mode on the SAME engine port. The replacement
        rehydrates prepared statements, warmup priming, and the shared
        tier's warm results before going active (fleet/engine.py), so
        recovery restores the dead generation's steady state."""
        new_epoch = self.engine_epoch + 1
        proc = self._spawn_engine(new_epoch)
        try:
            self._wait_engine(proc, "active", new_epoch,
                              ENGINE_READY_TIMEOUT_S)
        except BaseException:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10.0)
            raise
        self.engine_proc = proc
        self.engine_epoch = new_epoch
        # tell the workers: breakers reset, stale upstream connections
        # drop, the deferred misses' clients can retry NOW
        self.bus.publish({"kind": "engine_epoch", "epoch": new_epoch},
                         exclude_self=True)

    def engine_restart(self, timeout_s: Optional[float] = None) -> int:
        """PLANNED zero-drop engine swap. The replacement generation
        builds its runner and warms up first; the old engine then
        drains fully and passes the live dispatch listener over
        SCM_RIGHTS (fleet/handoff.py) — connections arriving in the
        no-accept gap wait in the kernel backlog, so a closed loop of
        cache MISSES sees zero errors across the swap. With
        `handoff_enabled=False` the swap is stop-then-bind: a brief
        miss outage (covered by the workers' SERVER_SHUTTING_DOWN /
        retry discipline) instead of fd passing. Returns the new
        epoch."""
        if self.engine_in_process:
            raise RuntimeError(
                "engine_restart() needs the subprocess engine "
                "(engine_in_process=False)")
        drain_budget = self.drain_timeout_s + self.drain_grace_s
        timeout_s = timeout_s if timeout_s is not None else \
            ENGINE_READY_TIMEOUT_S + drain_budget
        new_epoch = self.engine_epoch + 1
        old = self.engine_proc
        self._engine_expected_down = True
        try:
            if self.handoff_enabled:
                path = os.path.join(self.fleet_dir,
                                    f"handoff-{new_epoch}.sock")
                proc = self._spawn_engine(new_epoch, handoff_path=path)
                try:
                    self._wait_engine(proc, "ready-for-handoff",
                                      new_epoch, timeout_s)
                    if not self.bus.send_to(
                            "engine", {"kind": "handoff", "path": path}):
                        raise RuntimeError(
                            "old engine unreachable for handoff")
                    old.wait(timeout=drain_budget + 30.0)
                    self._wait_engine(proc, "active", new_epoch,
                                      timeout_s)
                except BaseException:
                    if proc.poll() is None:
                        proc.kill()
                        proc.wait(timeout=10.0)
                    raise
            else:
                self.bus.send_to("engine", {"kind": "stop"})
                try:
                    old.wait(timeout=drain_budget + 30.0)
                except subprocess.TimeoutExpired:
                    old.kill()
                    old.wait(timeout=10.0)
                proc = self._spawn_engine(new_epoch)
                self._wait_engine(proc, "active", new_epoch, timeout_s)
            self.engine_proc = proc
            self.engine_epoch = new_epoch
        finally:
            self._engine_expected_down = False
        if self.supervisor is not None:
            self.supervisor.count_planned_restart()
        self.bus.publish({"kind": "engine_epoch", "epoch": new_epoch},
                         exclude_self=True)
        return new_epoch

    # ----------------------------------------------------------- workers

    def spawn_worker(self, wait: bool = True,
                     timeout_s: float = WORKER_READY_TIMEOUT_S) -> str:
        worker_id = f"w-{uuid.uuid4().hex[:8]}"
        if self.in_process:
            from trino_tpu.fleet.worker import WorkerServer
            server = WorkerServer(read_fleet_config(self.fleet_dir),
                                  worker_id=worker_id).start()
            with self._lock:
                self._inproc[worker_id] = server
        else:
            env = dict(os.environ)
            # workers never execute queries: pin them to the CPU backend
            # whatever the fleet was started with — a chip belongs to one
            # process, the engine's
            env["JAX_PLATFORMS"] = "cpu"
            env.update(self.worker_env)
            log_path = os.path.join(self.fleet_dir, "workers",
                                    f"{worker_id}.log")
            os.makedirs(os.path.dirname(log_path), exist_ok=True)
            log = open(log_path, "w")
            proc = subprocess.Popen(
                [sys.executable, "-m", "trino_tpu.fleet.worker",
                 self.fleet_dir, worker_id],
                stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
            log.close()
            with self._lock:
                self._procs[worker_id] = proc
        if wait:
            self._wait_ready([worker_id], timeout_s)
        return worker_id

    def _wait_ready(self, worker_ids: List[str],
                    timeout_s: float = WORKER_READY_TIMEOUT_S) -> None:
        """Wait for workers to report active — and RESPAWN, bounded, the
        ones that die on the way up (a lost SO_REUSEPORT bind race, an
        import-time wobble): each logical worker gets
        `worker_respawn_max` extra attempts with exponential backoff
        before startup fails naming the worker, its exit code, and the
        tail of its log."""
        deadline = time.monotonic() + timeout_s
        pending = {wid: wid for wid in worker_ids}   # current -> original
        attempts = {wid: 0 for wid in worker_ids}    # respawns used
        while pending and time.monotonic() < deadline:
            active = {rec.get("worker_id")
                      for rec in list_worker_records(self.fleet_dir)
                      if rec.get("state") == "active"}
            for wid in [w for w in pending if w in active]:
                pending.pop(wid)
            for wid in list(pending):
                with self._lock:
                    proc = self._procs.get(wid)
                if proc is None or proc.poll() is None:
                    continue
                original = pending.pop(wid)
                with self._lock:
                    self._procs.pop(wid, None)
                n = attempts[original] = attempts[original] + 1
                if n > self.worker_respawn_max:
                    raise RuntimeError(
                        f"fleet worker {original} died at startup "
                        f"{n} times (last rc={proc.returncode}); log "
                        f"tail:\n{self._log_tail(f'workers/{wid}.log')}")
                time.sleep(self.respawn_backoff_s * (2 ** (n - 1)))
                replacement = self.spawn_worker(wait=False)
                pending[replacement] = original
            if pending:
                time.sleep(0.05)
        if pending:
            raise TimeoutError(
                f"fleet workers not ready within {timeout_s}s: "
                f"{sorted(pending)}")

    def workers(self) -> List[Dict]:
        return list_worker_records(self.fleet_dir)

    # ------------------------------------------------------ drain/restart

    def drain_worker(self, worker_id: str,
                     timeout_s: Optional[float] = None) -> None:
        # mark BEFORE the drain request: the supervisor must not
        # mistake this planned exit for a crash and respawn it
        self._draining.add(worker_id)
        rec = next((r for r in self.workers()
                    if r.get("worker_id") == worker_id), None)
        if rec is not None:
            import http.client
            try:
                body = json.dumps({"timeout_s": timeout_s}).encode() \
                    if timeout_s is not None else None
                conn = http.client.HTTPConnection(
                    self.host, rec["admin_port"], timeout=5)
                conn.request("POST", "/v1/fleet/drain", body=body)
                conn.getresponse().read()
                conn.close()
                return
            except OSError:
                pass
        self.bus.send_to(worker_id, {"kind": "drain",
                                     "timeout_s": timeout_s})

    def _wait_exit(self, worker_id: str, timeout_s: float) -> bool:
        with self._lock:
            proc = self._procs.pop(worker_id, None)
            inproc = self._inproc.pop(worker_id, None)
        try:
            if inproc is not None:
                return inproc.join(timeout_s)
            if proc is None:
                return True
            try:
                proc.wait(timeout=timeout_s)
                return True
            except subprocess.TimeoutExpired:
                proc.terminate()
                try:
                    proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=5)
                return False
        finally:
            self._draining.discard(worker_id)

    def rolling_restart(self,
                        timeout_s: Optional[float] = None) -> List[str]:
        """Upgrade the fleet worker-by-worker without dropping a query:
        spawn the replacement FIRST (the port briefly has N+1
        listeners), then drain the old worker and wait for its exit.
        Returns the new worker ids."""
        timeout_s = timeout_s if timeout_s is not None else \
            self.drain_timeout_s + self.drain_grace_s + 20.0
        with self._lock:
            old = list(self._procs) + list(self._inproc)
        fresh = []
        for worker_id in old:
            fresh.append(self.spawn_worker(wait=True))
            self.drain_worker(worker_id)
            self._wait_exit(worker_id, timeout_s)
        return fresh

    def stop(self, cleanup: bool = True) -> None:
        # supervision ends FIRST: a shutdown must not look like a crash
        if self.supervisor is not None:
            self.supervisor.stop()
        with self._lock:
            alive = list(self._procs) + list(self._inproc)
        for worker_id in alive:
            self.drain_worker(worker_id, timeout_s=2.0)
        for worker_id in alive:
            self._wait_exit(
                worker_id, self.drain_grace_s + 5.0)
        if self.engine is not None:
            self.engine.stop()
        if self.engine_proc is not None:
            self.bus.send_to("engine", {"kind": "stop"})
            try:
                self.engine_proc.wait(
                    timeout=self.drain_timeout_s + self.drain_grace_s
                    + 15.0)
            except subprocess.TimeoutExpired:
                self.engine_proc.terminate()
                try:
                    self.engine_proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    self.engine_proc.kill()
                    self.engine_proc.wait(timeout=5)
        self.bus.close()
        self.shared.close()
        if cleanup and self._owns_dir:
            shutil.rmtree(self.fleet_dir, ignore_errors=True)

    # ------------------------------------------------------------- the bus

    def _publish_invalidate(self, table) -> None:
        """Plan-cache invalidation hook leg 5 (in-process engine): tell
        every worker to drop its hot local copies NOW. Advisory — the
        shm generation bump the mirrored cache already performed is what
        makes staleness impossible; this just evicts dead weight
        promptly."""
        self.bus.publish({"kind": "invalidate", "table": list(table)},
                         exclude_self=True)

    def _on_bus(self, message: Dict) -> None:
        if self.engine is None:
            return     # subprocess mode: the engine child ingests
        kind = message.get("kind")
        if kind == "hits":
            from trino_tpu.fleet.engine import ingest_hits
            self.fleet_hits_ingested += ingest_hits(self.engine, message)
        elif kind == "prepare":
            # sticky routing leg 2: statements PREPAREd through any
            # worker land in the engine's base prepared map too, so an
            # EXECUTE that reaches the engine without headers resolves
            from trino_tpu.fleet.engine import register_prepared
            register_prepared(self.runner, message["name"],
                              message["sql"])
        elif kind == "deallocate":
            self.runner._prepared.pop(message.get("name"), None)

    # ------------------------------------------------------------- gauges

    def _register_gauges(self) -> None:
        from trino_tpu.obs.metrics import REGISTRY
        fleet = self

        def _fleet_gauges():
            yield ("trino_tpu_fleet_workers",
                   "Live fleet worker processes.",
                   len(fleet.workers()), {})
            yield ("trino_tpu_fleet_shared_cache_entries",
                   "Live entries in the cross-process result cache.",
                   fleet.shared.entry_count(), {})
            yield ("trino_tpu_fleet_hits_ingested",
                   "Worker cache hits ingested into fleet accounting.",
                   fleet.fleet_hits_ingested, {})

        REGISTRY.register_gauges(_fleet_gauges)
